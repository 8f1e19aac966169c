"""A group of operations' share of the chip's HBM bandwidth: the bytes
they have to move in the traced executions of one program, over their
own device time, over the peak (``benchmarks/peaks.json``).

The operations are those under ``scopes`` (and the compiler's kernels in
``instructions``) inside the programs whose name contains ``program``,
found as ``program_scope_share`` finds them. The bytes of ONE execution
come from the model's module (``benchmarks/models/<model>.py``,
``bytes_fn(config, engine stats)``: what the arithmetic has to read and
write, not what the program happens to), times the executions the
trace holds whole. Memory bounds these operations (a decode step
multiplies a row or two by each expert's 20 MB), so the share of the
bandwidth is their roofline share."""

import importlib

from benchmarks import peaks
from benchmarks.reducers import program_scope_share


def reduce(ctx, scopes: list[str], program: str, bytes_fn: str,
           instructions: tuple[str, ...] = ()):
    name = ctx["config"].get("model")
    if name is None or ctx["device"]["platform"] != "tpu":
        return None
    found = program_scope_share.selected(ctx, scopes, instructions, program)
    if found is None or found[0] <= 0:
        return None
    seconds, executions = found
    model = importlib.import_module(f"benchmarks.models.{name}")
    per_execution = getattr(model, bytes_fn)(
        ctx["config"], ctx["counters"].get("engine", {})
    )
    peak = peaks.load(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * per_execution * executions / seconds / peak
