"""``scope_time_share`` for a process that runs several programs (a
serving replica: one decode program, one prefill program a bucket):
own device time of the operations that a ``jax.named_scope`` of the
program covers, as a share of the time the chip was busy (``over:
busy``) or of the traced window (``over: window``).

A TPU trace names an operation by its HLO instruction, and two programs
both have a ``fusion.12``. So each traced operation is looked up in the
text of the program *it ran in*: the execution on the device's ``XLA
Modules`` line that covers it names the program, and
``counters["program_texts"]`` maps that name to the text the server
wrote beside the trace. ``instructions`` lists substrings of instruction
names counted as well (the compiler's own kernels, ``ragged-dot``, carry
no scope); ``program`` keeps only the programs whose name contains it.
None where the run wrote no program text or no instruction carries any
of ``scopes``: a program without those scopes has nothing to read."""

import bisect
import os

from benchmarks import traceread
from benchmarks.reducers.scope_time_share import (
    instruction_name,
    instruction_scopes,
)


def selected(ctx, scopes, instructions=(), program=None):
    """(own seconds of the selected operations, executions of the kept
    programs that lie whole inside the trace and hold at least one), each
    averaged over chips; None where there is nothing to read."""
    texts = ctx["counters"].get("program_texts") or {}
    names = {}
    for prog, path in texts.items():
        if os.path.exists(path):
            with open(path) as f:
                names[prog] = instruction_scopes(f.read(), scopes)
    if not any(names.values()):
        return None
    seconds, executions = [], []
    for dev in traceread.devices(ctx["events"]):
        runs = sorted(traceread.select(ctx["events"], dev, traceread.PROGRAMS),
                      key=lambda p: p.start)
        starts = [p.start for p in runs]
        total, held = 0.0, set()
        ops = traceread.select(ctx["events"], dev, traceread.OPS)
        for e, own in traceread.self_times(ops):
            i = bisect.bisect_right(starts, e.start) - 1
            if i < 0 or e.start + e.dur > runs[i].start + runs[i].dur + 1e-9:
                continue  # in no execution the trace holds whole
            prog = runs[i].name
            if program is not None and program not in prog:
                continue
            name = instruction_name(e.text)
            if name in names.get(prog, ()) or any(s in name for s in instructions):
                total += own
                held.add(i)
        seconds.append(total)
        executions.append(len(held))
    if not seconds:
        return None
    return sum(seconds) / len(seconds), sum(executions) / len(executions)


def reduce(ctx, scopes: list[str], over: str = "busy",
           instructions: tuple[str, ...] = (), program: str | None = None):
    found = selected(ctx, scopes, instructions, program)
    busy, window = traceread.busy_and_window(ctx["events"])
    base = {"busy": busy, "window": window}[over]
    if found is None or base <= 0:
        return None
    return 100.0 * found[0] / base
