"""The program's own host spans, read from the same trace file as the
device events and split against the device's idle time.

``LLMEngine`` and the ``LLMServer`` pump mark their host phases with
``jax.profiler.TraceAnnotation`` (``engine:step`` and its children,
``engine:add_request``, ``engine:abort_request``, ``pump:deliver``).
With a profiler session open they land in the ``/host:CPU`` plane of the
``.xplane.pb`` that also holds the device's lines, one line per thread,
so they share the device events' clock by construction.

1. ``read_spans(file)``: the file becomes a plain list of
   ``Span(thread, name, start, dur, attrs)``, seconds on the trace's
   clock, attributes from the annotation's keyword arguments.
2. Everything below it works on such lists and on ``traceread.Event``
   lists only, so it runs, and is tested, on made-up spans
   (``benchmarks/tests/test_hostspans.py``).

Nesting is by time on one thread: the innermost span that covers an
instant names it. What lies between two ``engine:step`` spans is the
executor hop, ``pump:deliver`` and the event loop's other work:
``between``.

``python3 -m benchmarks.hostspans <trace dir>`` prints a trace's idle
time by span, to read by hand.
"""

from __future__ import annotations

import bisect
import functools
import glob
import os
import statistics
from typing import NamedTuple

from benchmarks import traceread
from benchmarks.traceread import length, subtract, union

PREFIXES = ("engine:", "pump:")
STEP = "engine:step"
BETWEEN = "between"


class Span(NamedTuple):
    thread: str  # the host plane's line; one per thread
    name: str
    start: float  # seconds, on the trace's own clock
    dur: float  # seconds
    attrs: dict

    @property
    def end(self) -> float:
        return self.start + self.dur


# ------------------------------------------------------------ file -> list
def read_spans(path: str) -> list[Span]:
    from jax.profiler import ProfileData

    out: list[Span] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for i, line in enumerate(plane.lines):
            thread = f"{line.name}/{i}"
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    out.append(Span(thread, e.name, e.start_ns * 1e-9,
                                    e.duration_ns * 1e-9, dict(e.stats)))
    return out


@functools.lru_cache(maxsize=1)
def _read_once(path: str, mtime_ns: int) -> tuple[Span, ...]:
    return tuple(read_spans(path))


def newest_trace(root: str) -> str | None:
    """The run's trace file. ``ctx`` carries no path: the runner clears
    the cell's trace directory before each run, so the newest file under
    the benchmark's output directory is this run's."""
    found = glob.glob(os.path.join(root, "**", "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def spans_of(ctx: dict) -> list[Span]:
    """The host spans that belong to ``ctx["events"]``: ``ctx["spans"]``
    where a caller made them up, else those of the newest trace file,
    and nothing unless their time range overlaps the device events'
    window (another run's file, or a program without spans)."""
    if "spans" in ctx:
        return list(ctx["spans"])
    window = _window(ctx["events"])
    if window is None:
        return []
    from benchmarks.runners import common

    path = newest_trace(common.OUT)
    if path is None:
        return []
    spans = _read_once(path, os.stat(path).st_mtime_ns)
    if not spans:
        return []
    lo, hi = min(s.start for s in spans), max(s.end for s in spans)
    if hi <= window[0] or lo >= window[1]:
        return []
    return list(spans)


# ---------------------------------------------------------- list -> numbers
def _window(events) -> tuple[float, float] | None:
    devs = traceread.devices(events)
    return traceread.window_of(events, devs[0]) if devs else None


def _listed(names) -> list[str]:
    return [names] if isinstance(names, str) else list(names)


def named(spans, names) -> list[Span]:
    wanted = _listed(names)
    return [s for s in spans if s.name in wanted]


def nest(spans) -> list[tuple[Span, int | None]]:
    """The spans in order of start within each thread, each with the
    place in this list of the span that holds it on its thread (None at
    the top)."""
    out: list[tuple[Span, int | None]] = []
    by_thread: dict[str, list[Span]] = {}
    for s in spans:
        by_thread.setdefault(s.thread, []).append(s)
    for mine in by_thread.values():
        stack: list[int] = []  # places in out, innermost last
        for s in sorted(mine, key=lambda s: (s.start, -s.dur)):
            while stack and out[stack[-1]][0].end <= s.start:
                stack.pop()
            out.append((s, stack[-1] if stack else None))
            stack.append(len(out) - 1)
    return out


def own_intervals(spans) -> list[tuple[Span, bool, list[tuple[float, float]]]]:
    """Each span with whether it is, or lies inside, an ``engine:step``,
    and with the instants that are its own: those no span nested in it
    covers."""
    nested = nest(spans)
    children: list[list[tuple[float, float]]] = [[] for _ in nested]
    in_step: list[bool] = []
    for s, parent in nested:
        if parent is not None:
            children[parent].append((s.start, s.end))
        in_step.append(s.name == STEP or (parent is not None and in_step[parent]))
    return [
        (s, in_step[i], subtract([(s.start, s.end)], union(children[i])))
        for i, (s, _) in enumerate(nested)
    ]


def with_child(spans, name: str) -> list[Span]:
    """The spans that hold a span called ``name`` directly."""
    nested = nest(spans)
    holders = {parent for s, parent in nested
               if parent is not None and s.name == name}
    return [nested[i][0] for i in sorted(holders)]


def overlap(a, b) -> list[tuple[float, float]]:
    """The instants of disjoint sorted ``a`` that ``b`` covers too."""
    return subtract(a, subtract(a, b))


class Measure:
    """Disjoint sorted intervals, asked many times how much of them lies
    within a stretch: a bisection each, not a pass."""

    def __init__(self, intervals):
        self.starts = [lo for lo, _ in intervals]
        self.ends = [hi for _, hi in intervals]
        self.before = [0.0]  # length of the intervals before each
        for lo, hi in intervals:
            self.before.append(self.before[-1] + hi - lo)

    def upto(self, t: float) -> float:
        i = bisect.bisect_right(self.starts, t) - 1
        if i < 0:
            return 0.0
        return self.before[i] + min(t, self.ends[i]) - self.starts[i]

    def within(self, stretches) -> float:
        return sum(self.upto(hi) - self.upto(lo) for lo, hi in stretches)


class IdleSplit(NamedTuple):
    in_steps: dict  # span name -> idle seconds in its own instants
    between_by: dict  # the same for spans outside every step
    between: float  # idle seconds between two steps of a running pump
    idle: float  # all idle seconds of the device's window
    steps: int  # engine:step spans that began inside the window

    def seconds(self, names) -> float:
        """Idle seconds under these names: a span inside a step, a span
        outside one, ``between`` for all that lies between steps, or
        ``*`` for everything that has a name."""
        if names == "*":
            return sum(self.in_steps.values()) + self.between
        return sum(
            self.between if n == BETWEEN
            else self.in_steps.get(n, 0.0) + self.between_by.get(n, 0.0)
            for n in _listed(names)
        )


def split_idle(idle, window, spans):
    """Device-idle intervals (disjoint, sorted) against the host spans.
    Inside an ``engine:step`` an instant belongs to the innermost span
    of the step's thread. Outside every step, between two steps of a
    running pump, it is ``between``, and within that it also belongs to
    the innermost span of any other thread (``pump:deliver``,
    ``engine:add_request``). The pump ran through a gap if the step
    after it found requests in hand (``active`` or ``prefilling``): with
    none the engine had drained, and the chip's idle time until the next
    arrival is nobody's host work, like that before the first step and
    after the last."""
    steps = sorted(named(spans, STEP), key=lambda s: s.start)
    covered = union([(s.start, s.end) for s in steps])
    gaps = [(a.end, b.start) for a, b in zip(steps, steps[1:])
            if b.attrs.get("active") or b.attrs.get("prefilling")]
    idle_between = overlap(idle, subtract(union(gaps), covered))
    in_steps_of, between_of = Measure(overlap(idle, covered)), Measure(idle_between)
    in_steps: dict[str, float] = {}
    between_by: dict[str, float] = {}
    for s, in_step, own in own_intervals(spans):
        into, of = ((in_steps, in_steps_of) if in_step
                    else (between_by, between_of))
        into[s.name] = into.get(s.name, 0.0) + of.within(own)
    return IdleSplit(
        in_steps=in_steps,
        between_by=between_by,
        between=length(idle_between),
        idle=length(idle),
        steps=sum(1 for s in steps if window[0] <= s.start < window[1]),
    )


def idle_split(ctx: dict) -> IdleSplit | None:
    """``split_idle`` of the first chip's idle time in ``ctx``; None
    where there are no device events or no steps. Kept on ``ctx`` for
    the next metric that asks."""
    key = "idle_split"
    if key not in ctx:
        ctx[key] = None
        spans = spans_of(ctx)
        window = _window(ctx["events"])
        if spans and window is not None:
            dev = traceread.devices(ctx["events"])[0]
            idle = subtract([window],
                            traceread.busy_intervals(ctx["events"], dev))
            split = split_idle(idle, window, spans)
            ctx[key] = split if split.steps else None
    return ctx[key]


def statistic(values: list[float], stat: str) -> float | None:
    if not values:
        return None
    if stat == "median":
        return statistics.median(values)
    if stat == "mean":
        return statistics.fmean(values)
    if stat == "p90":
        from benchmarks.loadgen import percentile

        return percentile(values, 90)
    raise ValueError(f"unknown stat {stat!r}")


def describe(path: str) -> list[str]:
    """A trace's idle time by host span, and each span's count and
    durations: what to read by hand."""
    events = traceread.read_events(path)
    spans = read_spans(path)
    rows = [f"{len(spans)} host spans, {len(events)} device events"]
    by_name: dict[str, list[float]] = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s.dur * 1e3)
    rows.append("SPANS count, median ms, p90 ms, sum s")
    for name, ms in sorted(by_name.items()):
        rows.append(f"  {name:24s} {len(ms):6d} {statistic(ms, 'median'):9.3f} "
                    f"{statistic(ms, 'p90'):9.3f} {sum(ms) / 1e3:9.4f}")
    split = idle_split({"events": events, "spans": spans})
    if split is None:
        return rows + ["no device window or no engine:step: nothing to split"]
    window = _window(events)
    rows.append(f"IDLE {split.idle:.4f} s of a window of "
                f"{window[1] - window[0]:.4f} s, {split.steps} steps")
    named_idle = dict(split.in_steps)
    for name, secs in split.between_by.items():
        named_idle[f"{BETWEEN}: {name}"] = secs
    named_idle[f"{BETWEEN}: no span"] = (
        split.between - sum(split.between_by.values()))
    named_idle["nobody"] = split.idle - split.seconds("*")
    for name, secs in sorted(named_idle.items(), key=lambda kv: -kv[1]):
        if secs <= 0 and name != "nobody":
            continue
        rows.append(f"  {name:24s} {secs:9.4f} s {1e3 * secs / split.steps:8.3f} "
                    f"ms/step {100 * secs / max(split.idle, 1e-12):6.2f}%")
    return rows


if __name__ == "__main__":
    # python3 -m benchmarks.hostspans .bench_out/<cell>/trace
    import sys

    print("\n".join(describe(traceread.find_trace_file(sys.argv[1]))))
