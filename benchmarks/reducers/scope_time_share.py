"""Own device time of the operations that a ``jax.named_scope`` of the
program covers, as a share of the time the chip was busy (``over:
busy``) or of the traced window (``over: window``).

A TPU trace names an operation by its HLO instruction without the
instruction's metadata, so the scope is not in the trace. It is in the
compiled program's text (``metadata={op_name=".../moe:experts/..."}``),
under the same instruction name: the train loop writes that text beside
the trace (``counters["step_program_text"]``) and this reads the scope
of each traced instruction from it. A fusion carries the scope of the
operation it was built around; backward and recomputed operations carry
their forward operation's scope inside ``transpose(jvp(...))`` and
``rematted_computation``. A kernel the compiler itself puts in place of
an operation (``ragged-dot-none`` for ``jax.lax.ragged_dot``) carries
the kernel's name and no scope: ``instructions`` lists the substrings of
instruction names that are counted as well. None where the run wrote no
program text or no instruction carries any of ``scopes``: a program
without those scopes has nothing to read."""

import os
import re

from benchmarks import traceread

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*\bop_name="([^"]*)"'
)


def instruction_scopes(program_text: str, scopes: list[str]) -> set[str]:
    """Names of the instructions whose ``op_name`` contains any of
    ``scopes``."""
    names = set()
    for line in program_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and any(s in m.group(2) for s in scopes):
            names.add(m.group(1))
    return names


def instruction_name(event_text: str) -> str:
    return event_text.partition(" = ")[0].strip().lstrip("%")


def reduce(ctx, scopes: list[str], over: str = "busy",
           instructions: tuple[str, ...] = ()):
    path = ctx["counters"].get("step_program_text")
    if not path or not os.path.exists(path):
        return None
    with open(path) as f:
        names = instruction_scopes(f.read(), scopes)
    busy, window = traceread.busy_and_window(ctx["events"])
    base = {"busy": busy, "window": window}[over]
    per_device = []
    for dev in traceread.devices(ctx["events"]):
        ops = traceread.select(ctx["events"], dev, traceread.OPS)
        per_device.append(sum(
            own for e, own in traceread.self_times(ops)
            if (name := instruction_name(e.text)) in names
            or any(i in name for i in instructions)
        ))
    if base <= 0 or not names or not per_device:
        return None
    return 100.0 * sum(per_device) / len(per_device) / base
