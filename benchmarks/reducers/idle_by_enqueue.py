"""Device-idle time by who the chip waited for: the host, which had not
launched the next program yet, or the launch, which had been made and
whose program had not begun.

``LLMEngine`` puts every call that places a program on the device in a
span of its own, ``launch:*``, around the call and nothing else, and the
device runs programs in the order they were launched. So the k-th launch
span of a trace is its k-th program, once the programs that were
launched before the trace began are set aside (``pair``).

**The two clocks.** The host's spans and the device's events lie in one
file but are stamped by two clocks, which the profiler sets against each
other once a session, to within a millisecond or two: a trace of
``qwen3next-longdoc-16`` had programs BEGIN up to 0.7 ms before their
launch did (my chip runs, PR 57). What this reducer splits is of that
size, so it sets the device's clock itself, from the two things that
cannot be: a program beginning before its launch began, and the read-back
of a decode step (``engine:decode_sync``) returning before that step's
program ended. The device's clock is put in the middle of what the two
leave (``clock_offset``: the two shortest latencies, host to device and
back, taken as equal), and the device's events are moved by that much
before anything is split. Every idle instant of the first chip then has
one of three names:

``launch``  a paired launch span had ended and its program had not begun;
``host``    no launched program was outstanding and the pump was running:
            inside an ``engine:step``, or between two steps of which the
            later found work in hand (``hostspans.split_idle``'s rule,
            asked of it and not written again);
``empty``   the rest: a drained engine, before the first step, after the
            last.

``kind``: which of the three, or ``call`` for the part of ``host`` that
lies inside a launch span (the host was in the call, which had not put
its program on the device yet). ``per``: ``step`` gives milliseconds per
``engine:step`` of the traced window, ``idle`` a percentage of all the
window's idle time. Nothing where the trace holds no ``launch:*`` span
(a program that has none).

``hostspans.read_spans`` keeps ``engine:`` and ``pump:`` names only, so
the launch spans are read here. ``python3 -m
benchmarks.reducers.idle_by_enqueue <trace dir>`` prints a trace's
split, its launches and programs counted and how far apart they lie.
"""

from __future__ import annotations

import functools
import os
import statistics
from typing import NamedTuple

from benchmarks import hostspans, traceread
from benchmarks.hostspans import Span
from benchmarks.traceread import length, subtract, union

LAUNCH = "launch:"
# Read with the launches, for `describe` alone: the engine's read-backs
# that are no step's usual wait (`readback:moe_counts`).
READBACK = "readback:"
KINDS = ("launch", "host", "empty")
CALL = "call"  # the part of `host` inside a launch span


# ------------------------------------------------------------ file -> list
def read_launches(path: str, prefixes=(LAUNCH,)) -> list[Span]:
    from jax.profiler import ProfileData

    out: list[Span] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(prefixes):
                    out.append(Span(f"{line.name}/{i}", e.name,
                                    e.start_ns * 1e-9, e.duration_ns * 1e-9,
                                    dict(e.stats)))
    return out


@functools.lru_cache(maxsize=1)
def _read_once(path: str, mtime_ns: int) -> tuple[Span, ...]:
    return tuple(read_launches(path))


def launches_of(ctx: dict) -> list[Span]:
    """The launch spans of the trace that ``hostspans.spans_of`` reads
    for this ``ctx``; ``ctx["launches"]`` where a caller made them up."""
    if "launches" in ctx:
        return list(ctx["launches"])
    from benchmarks.runners import common

    path = hostspans.newest_trace(common.OUT)
    if path is None:
        return []
    return list(_read_once(path, os.stat(path).st_mtime_ns))


# ---------------------------------------------------------- list -> numbers
# How many pairs in a hundred may have the program beginning before its
# launch did, on the file's clocks as they are: with a program too few
# set aside it is 43-100 of them, with the clocks 1.8 ms apart 7 (the
# four cells' traces, my chip runs, PR 57).
EARLY_PCT = 20
# The read-backs whose shortest sets the device's clock: the 5th of a
# hundred, since the first of a trace may have read a step that was
# launched before the trace began.
READ_BACK_QUANTILE = 20
SYNC = "engine:decode_sync"


def pair(launches, programs) -> tuple[list[tuple[Span, traceread.Event]], int]:
    """Launch spans and one chip's programs, each sorted by start, paired
    in order, and how many of the first programs were set aside as
    launched before the trace began: the fewest that leave at most
    ``EARLY_PCT`` pairs in a hundred with the program beginning before
    its launch did (none could, were the clocks one). Launches past the
    last program, and programs past the last launch, stay unpaired."""
    for aside in range(len(programs) + 1):
        pairs = list(zip(launches, programs[aside:]))
        early = sum(1 for l, p in pairs if p.start < l.start)
        if 100 * early <= EARLY_PCT * len(pairs):
            return pairs, aside
    return [], len(programs)


def clock_offset(pairs, spans) -> float:
    """Seconds to add to the device's times to set them on the host's
    clock: the middle of what causality allows. At least so much that no
    program begins before its launch span did; at most so much that no
    read-back returns before its program ended, a decode step being read
    by the oldest ``engine:decode_sync`` after its launch that has read
    none. Without read-backs, the least that lets no program begin before
    its launch."""
    if not pairs:
        return 0.0
    least = max(l.start - p.start for l, p in pairs)
    happenings = [(l.start, 0, p) for l, p in pairs
                  if l.name == "launch:decode"]
    happenings += [(s.start, 1, s) for s in hostspans.named(spans, SYNC)]
    unread, returned_after = [], []
    for _, is_sync, what in sorted(happenings, key=lambda h: h[:2]):
        if not is_sync:
            unread.append(what)
        elif unread:
            program = unread.pop(0)
            returned_after.append(what.end - (program.start + program.dur))
    if len(returned_after) < READ_BACK_QUANTILE:
        return max(least, 0.0)
    most = statistics.quantiles(returned_after, n=READ_BACK_QUANTILE)[0]
    return (least + most) / 2 if most > least else max(least, 0.0)


class Split(NamedTuple):
    launch: float  # idle seconds between a launch's end and its program
    host: float  # idle seconds of a running pump with nothing launched
    empty: float  # the other idle seconds
    call: float  # of `host`, the seconds inside a launch span
    idle: float  # all idle seconds of the device's window
    steps: int  # engine:step spans that began inside the window
    launches: int  # launch spans that began inside the window
    programs: int  # programs that began inside the window
    aside: int  # first programs taken as launched before the trace
    offset: float  # seconds the device's clock was moved by


def split_idle(idle, window, programs, launches, spans) -> Split:
    """Device-idle intervals (disjoint, sorted) of a chip whose programs
    are ``programs``, against the ``launch:*`` spans and the
    ``engine:step`` spans of ``spans``."""
    programs = sorted(programs, key=lambda p: p.start)
    launches = sorted(launches, key=lambda s: s.start)
    pairs, aside = pair(launches, programs)
    offset = clock_offset(pairs, spans)
    # The device's events on the host's clock.
    idle = [(lo + offset, hi + offset) for lo, hi in idle]
    window = (window[0] + offset, window[1] + offset)
    programs = [p._replace(start=p.start + offset) for p in programs]
    pairs = list(zip(launches, programs[aside:]))
    launched = union([(l.end, p.start) for l, p in pairs])
    rest = subtract(idle, launched)
    steps = hostspans.named(spans, hostspans.STEP)
    running = hostspans.split_idle(rest, window, steps)
    host = running.seconds("*")
    calls = union([(l.start, l.end) for l in launches])
    in_call = hostspans.split_idle(
        hostspans.overlap(rest, calls), window, steps
    ).seconds("*")

    def inside(starts):
        return sum(1 for t in starts if window[0] <= t < window[1])

    return Split(
        launch=length(idle) - length(rest),
        host=host,
        empty=length(rest) - host,
        call=in_call,
        idle=length(idle),
        steps=running.steps,
        launches=inside(s.start for s in launches),
        programs=inside(p.start for p in programs),
        aside=aside,
        offset=offset,
    )


def idle_split(ctx: dict) -> Split | None:
    """``split_idle`` of the first chip's idle time in ``ctx``; None
    where there are no device events, no steps or no launch spans. Kept
    on ``ctx`` for the next metric that asks."""
    key = "idle_by_enqueue"
    if key not in ctx:
        ctx[key] = None
        spans = hostspans.spans_of(ctx)
        launches = launches_of(ctx) if spans else []
        devs = traceread.devices(ctx["events"])
        window = traceread.window_of(ctx["events"], devs[0]) if devs else None
        if launches and window is not None:
            idle = subtract(
                [window], traceread.busy_intervals(ctx["events"], devs[0])
            )
            programs = traceread.select(ctx["events"], devs[0],
                                        traceread.PROGRAMS)
            split = split_idle(idle, window, programs, launches, spans)
            ctx[key] = split if split.steps else None
    return ctx[key]


def reduce(ctx, kind: str, per: str = "step"):
    if kind not in (*KINDS, CALL):
        raise ValueError(f"unknown kind {kind!r}")
    split = idle_split(ctx)
    if split is None:
        return None
    seconds = getattr(split, kind)
    if per == "step":
        return 1e3 * seconds / split.steps
    if per == "idle":
        return 100.0 * seconds / split.idle if split.idle > 0 else None
    raise ValueError(f"unknown per {per!r}")


def describe(path: str) -> list[str]:
    """A trace's idle time by the three names, its launches and programs
    counted, and how long after its launch a program began: what to read
    by hand before trusting the pairing."""
    events = traceread.read_events(path)
    own = read_launches(path, (LAUNCH, READBACK))
    ctx = {"events": events, "spans": hostspans.read_spans(path),
           "launches": [s for s in own if s.name.startswith(LAUNCH)]}
    split = idle_split(ctx)
    if split is None:
        return ["no device window, no engine:step or no launch:* span"]
    rows = [
        f"IDLE {split.idle:.4f} s over {split.steps} steps; "
        f"{split.launches} launches and {split.programs} programs began "
        f"inside the window, {split.aside} programs set aside; the "
        f"device's clock moved by {1e3 * split.offset:+.3f} ms"
    ]
    for kind in (*KINDS, CALL):
        secs = getattr(split, kind)
        rows.append(f"  {kind:8s} {secs:9.4f} s {1e3 * secs / split.steps:8.3f} "
                    f"ms/step {100 * secs / max(split.idle, 1e-12):6.2f}%")
    dev = traceread.devices(events)[0]
    programs = traceread.select(events, dev, traceread.PROGRAMS)
    pairs, _ = pair(sorted(ctx["launches"], key=lambda s: s.start), programs)
    by_name: dict[str, list] = {}
    for l, p in pairs:
        began = p.start + split.offset
        by_name.setdefault(f"{l.name} = {p.name}", []).append(
            (1e3 * (began - l.start), 1e3 * (began - l.end), 1e3 * l.dur)
        )
    rows.append("PAIRS count; program's start (moved) after its launch's "
                "start (min, median ms) and end (median ms); launch span "
                "median ms")
    for name, rows_of in sorted(by_name.items()):
        after_start, after_end, dur = zip(*rows_of)
        rows.append(
            f"  {name:60s} {len(rows_of):6d} {min(after_start):9.3f} "
            f"{statistics.median(after_start):9.3f} "
            f"{statistics.median(after_end):9.3f} "
            f"{statistics.median(dur):9.3f}"
        )
    readbacks = [s for s in own if s.name.startswith(READBACK)]
    rows.append(f"READ-BACKS {len(readbacks)}: name, ms, attributes")
    rows += [f"  {s.name} {1e3 * s.dur:9.3f} {s.attrs}" for s in readbacks]
    return rows


if __name__ == "__main__":
    # python3 -m benchmarks.reducers.idle_by_enqueue .bench_out/<cell>/trace
    import sys

    print("\n".join(describe(traceread.find_trace_file(sys.argv[1]))))
