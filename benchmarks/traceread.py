"""From a profiler trace to numbers, in two steps.

1. ``read_events(file)``: the ``.xplane.pb`` that ``jax.profiler`` wrote
   becomes a plain list of ``Event(device, line, name, start, dur)``,
   times in seconds. Read with ``jax.profiler.ProfileData`` and nothing
   of the program's.
2. Everything below it works on such lists only, so it runs, and is
   tested, without a chip (``benchmarks/tests/test_traceread.py``).

On a TPU each chip is a plane ``/device:TPU:<n>`` whose line
``XLA Modules`` holds one event per program execution and whose line
``XLA Ops`` holds one per HLO operation, nested where an operation (a
``while`` of a scan) contains others. On the CPU (the harness rehearsal)
there is no device plane: operations are host-thread events that carry an
``hlo_op`` stat, and programs are put together from their ``run_id``.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import NamedTuple

OPS = "ops"
PROGRAMS = "programs"


class Event(NamedTuple):
    device: str  # plane name; one per chip
    line: str  # OPS or PROGRAMS
    name: str  # short: "convert_element_type convert bf16[32768,4096]"
    start: float  # seconds, on the trace's own clock
    dur: float  # seconds
    text: str = ""  # an operation's HLO instruction as the trace has it


# ------------------------------------------------------------ file -> list
def find_trace_file(trace_dir: str) -> str | None:
    found = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    )
    return found[-1] if found else None


def _program_name(name: str) -> str:
    """``jit_paged_verify(1234)`` -> ``jit_paged_verify``."""
    return re.sub(r"\(\d+\)$", "", name)


def short_op_name(text: str) -> str:
    """A TPU trace names an operation by its whole HLO instruction,
    ``%fusion.4 = bf16[2,64]{1,0:T(8,128)} fusion(bf16[...] %p), kind=...``.
    Kept: the name without its number, the opcode and the result's
    shape without layouts, so that the same operation of every layer and
    step sums under one name."""
    head, eq, rest = text.partition(" = ")
    if not eq:
        return re.sub(r"\.\d+$", "", text.lstrip("%"))
    name = re.sub(r"\.\d+$", "", head.lstrip("%"))
    call = re.search(r"\s([a-z][\w\-]*)\(", rest)
    if call is None:
        return name
    shape = re.sub(r"\{[^}]*\}|/\*.*?\*/", "", rest[: call.start()]).strip()
    return f"{name} {call.group(1)} {shape[:80]}"


def read_events(path: str) -> list[Event]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: list[Event] = []
    device_planes = [p for p in data.planes if p.name.startswith("/device:")]
    for plane in device_planes:
        for line in plane.lines:
            kind = {"XLA Ops": OPS, "XLA Modules": PROGRAMS}.get(line.name)
            if kind is None:
                continue
            short: dict[str, str] = {}
            for e in line.events:
                text = e.name
                if text not in short:
                    short[text] = (short_op_name(text) if kind == OPS
                                   else _program_name(text))
                out.append(
                    Event(plane.name, kind, short[text], e.start_ns * 1e-9,
                          e.duration_ns * 1e-9, text)
                )
    if device_planes:
        return out
    # No device plane: the CPU rehearsal.
    runs: dict[tuple, list[float]] = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats)
                if "hlo_op" not in stats:
                    continue
                start, dur = e.start_ns * 1e-9, e.duration_ns * 1e-9
                out.append(Event("/host:CPU", OPS, e.name, start, dur, e.name))
                key = (stats.get("hlo_module", "?"), stats.get("run_id", 0))
                span = runs.setdefault(key, [start, start + dur])
                span[0] = min(span[0], start)
                span[1] = max(span[1], start + dur)
    for (module, _), (lo, hi) in runs.items():
        out.append(Event("/host:CPU", PROGRAMS, module, lo, hi - lo))
    return out


def describe(path: str, per_line: int = 6) -> list[str]:
    """Planes, lines and a few events of a trace file: what to read by
    hand before trusting a reduction on a new runtime."""
    from jax.profiler import ProfileData

    rows = []
    events = read_events(path)
    devs = devices(events)
    if devs:
        by_text: dict[str, float] = {}
        for e, own in self_times(select(events, devs[0], OPS)):
            by_text[e.text] = by_text.get(e.text, 0.0) + own
        rows.append(f"TOP OPERATIONS of {devs[0]} by own time")
        for text, secs in sorted(by_text.items(), key=lambda kv: -kv[1])[:40]:
            rows.append(f"  {secs:.6f} {text[:700]}")
        rows.append("CUSTOM CALLS")
        for text in sorted({t for t in by_text if "custom-call" in t}):
            rows.append(f"  {by_text[text]:.6f} {text[:1500]}")
    for plane in ProfileData.from_file(path).planes:
        rows.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            rows.append(f"  LINE {line.name} ({len(events)} events)")
            for e in events[:per_line]:
                rows.append(
                    f"    {e.name[:100]} start={e.start_ns} "
                    f"dur={e.duration_ns} stats={list(e.stats)[:6]}"
                )
    return rows


# ---------------------------------------------------------- list -> numbers
def devices(events: list[Event]) -> list[str]:
    return sorted({e.device for e in events})


def select(events, device: str, line: str) -> list[Event]:
    return sorted(
        (e for e in events if e.device == device and e.line == line),
        key=lambda e: (e.start, -e.dur),
    )


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted, disjoint intervals covering the same instants."""
    merged: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return [(lo, hi) for lo, hi in merged]


def length(intervals) -> float:
    return sum(hi - lo for lo, hi in intervals)


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(a, b) -> list[tuple[float, float]]:
    """The instants of disjoint sorted ``a`` that no interval of disjoint
    sorted ``b`` covers."""
    out = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def window_of(events, device: str) -> tuple[float, float] | None:
    """The steady window of one chip: from the start of its first
    program to the end of its last, so that the profiler's own start
    and stop are not counted as idle."""
    programs = select(events, device, PROGRAMS)
    if not programs:
        ops = select(events, device, OPS)
        if not ops:
            return None
        return ops[0].start, max(e.start + e.dur for e in ops)
    return programs[0].start, max(e.start + e.dur for e in programs)


def busy_intervals(events, device: str):
    window = window_of(events, device)
    if window is None:
        return []
    ops = [(e.start, e.start + e.dur) for e in leaf_ops(events, device)]
    return clip(union(ops), *window)


def busy_and_window(events) -> tuple[float, float]:
    """Seconds in which an operation ran, and the window's length, each
    averaged over the chips that appear in the trace."""
    busy, window = [], []
    for dev in devices(events):
        w = window_of(events, dev)
        if w is None:
            continue
        busy.append(length(busy_intervals(events, dev)))
        window.append(w[1] - w[0])
    if not window:
        return 0.0, 0.0
    return sum(busy) / len(busy), sum(window) / len(window)


def self_times(ops: list[Event]) -> list[tuple[Event, float]]:
    """Each operation with the time that is its own: its duration less
    that of the operations nested in it (a scan's ``while`` contains its
    body's operations). ``ops`` is one chip's, sorted by start."""
    out: list[list] = []
    stack: list[int] = []  # indices into out, innermost last
    for e in ops:
        end = e.start + e.dur
        while stack and out[stack[-1]][0].start + out[stack[-1]][0].dur <= e.start:
            stack.pop()
        if stack and end <= out[stack[-1]][0].start + out[stack[-1]][0].dur + 1e-12:
            out[stack[-1]][1] -= e.dur
        out.append([e, e.dur])
        stack.append(len(out) - 1)
    return [(e, max(t, 0.0)) for e, t in out]


def leaf_ops(events, device: str) -> list[Event]:
    """One chip's operations that nest no other: a ``while`` holds the
    chip only through the operations of its body."""
    timed = self_times(select(events, device, OPS))
    return [e for e, own in timed if own >= e.dur - 1e-12]


def op_seconds(events, match: str | list[str] | None = None) -> float:
    """Own time of the operations whose HLO text contains ``match``
    (every string of it, if a list; all operations, if None), averaged
    over chips."""
    wanted = [match] if isinstance(match, str) else list(match or [])
    per_device = []
    for dev in devices(events):
        total = 0.0
        for e, own in self_times(select(events, dev, OPS)):
            if all(w in e.text for w in wanted):
                total += own
        per_device.append(total)
    return sum(per_device) / len(per_device) if per_device else 0.0


def top_ops(events, n: int = 10) -> list[list]:
    """The operations that took most own time, summed by name over the
    first chip's window."""
    devs = devices(events)
    if not devs:
        return []
    by_name: dict[str, float] = {}
    for e, own in self_times(select(events, devs[0], OPS)):
        by_name[e.name] = by_name.get(e.name, 0.0) + own
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[name, secs] for name, secs in ranked]


def idle_gaps(events, n: int = 10) -> list[list]:
    """The first chip's idle time, summed by the programs on either side
    of each gap: ``a -> b`` between programs, ``inside a`` within one."""
    devs = devices(events)
    if not devs:
        return []
    dev = devs[0]
    window = window_of(events, dev)
    if window is None:
        return []
    programs = select(events, dev, PROGRAMS)
    starts = [p.start for p in programs]

    def name_gap(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1  # last program begun by t
        if i >= 0 and t <= programs[i].start + programs[i].dur:
            return f"inside {programs[i].name}"
        before = programs[i].name if i >= 0 else "start"
        after = programs[i + 1].name if i + 1 < len(programs) else "end"
        return f"{before} -> {after}"

    by_name: dict[str, float] = {}
    gaps = subtract([window], busy_intervals(events, dev))
    for lo, hi in gaps:
        name = name_gap((lo + hi) / 2)
        by_name[name] = by_name.get(name, 0.0) + (hi - lo)
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[name, secs] for name, secs in ranked]


def program_runs(events, match: str) -> list[list[Event]]:
    """Per chip, the executions of the programs whose name contains
    ``match``, in order of start."""
    return [
        [p for p in select(events, dev, PROGRAMS) if match in p.name]
        for dev in devices(events)
    ]


COLLECTIVES = ("all-gather", "reduce-scatter", "all-reduce", "all-to-all",
               "collective-permute", "async-collective")


def collective_kind(text: str) -> str | None:
    """Which collective an operation is, from its HLO text; None for
    any other operation. On a TPU a collective shows as its own opcode
    (``all-gather(...)``), as ``async-collective-start`` / ``-done``, or
    as a fusion that ``calls=%all-reduce-scatter...``. An operation that
    only *takes* a collective's result (``fusion(... %all-gather.3)``)
    is compute."""
    head, eq, rest = text.partition(" = ")
    name = head.lstrip("%")
    opcode, called = "", ""
    if eq:
        call = re.search(r"\s([a-z][\w\-]*)\(", rest)
        opcode = call.group(1) if call else ""
        calls = re.search(r"calls=%([\w\-.]+)", rest)
        called = calls.group(1) if calls else ""
    for kind in COLLECTIVES:
        if name.startswith(kind) or opcode.startswith(kind) or kind in called:
            return kind
    return None


def is_collective(text: str) -> bool:
    return collective_kind(text) is not None


def _in_flight(leaves: list[Event]) -> list[tuple[float, float]]:
    """Intervals in which a collective was under way. A synchronous
    collective is its own operation. An asynchronous one is a
    ``<kind>-start`` and a later ``<kind>-done``: the transfer runs from
    the start of the first to the end of the second, while other
    operations may run between them. A done is paired with the oldest
    open start of its kind."""
    spans = []
    open_starts: dict[str, list[float]] = {}
    for e in leaves:
        kind = collective_kind(e.text)
        head = e.text.partition(" = ")[0]
        if f"{kind}-start" in head:
            open_starts.setdefault(kind, []).append(e.start)
        elif f"{kind}-done" in head:
            began = open_starts.get(kind)
            lo = began.pop(0) if began else e.start
            spans.append((lo, e.start + e.dur))
        else:
            spans.append((e.start, e.start + e.dur))
    return spans


def collective_seconds(events, exposed: bool) -> float:
    """Seconds a collective was under way, averaged over chips; with
    ``exposed``, only the seconds in which a collective operation held
    the chip and no other operation ran (the wait in a ``-done``, or a
    synchronous collective). Containers (``while``) are not compute:
    only operations that nest nothing count as running."""
    per_device = []
    for dev in devices(events):
        window = window_of(events, dev)
        if window is None:
            continue
        leaves = leaf_ops(events, dev)
        coll = [e for e in leaves if is_collective(e.text)]
        if exposed:
            held = union([(e.start, e.start + e.dur) for e in coll])
            other = union([(e.start, e.start + e.dur) for e in leaves
                           if not is_collective(e.text)])
            spans = subtract(held, other)
        else:
            spans = union(_in_flight(coll))
        per_device.append(length(clip(spans, *window)))
    return sum(per_device) / len(per_device) if per_device else 0.0


if __name__ == "__main__":
    # python3 -m benchmarks.traceread .bench_out/<cell>/trace
    import sys

    print("\n".join(describe(find_trace_file(sys.argv[1]))))
