"""A kernel's share of its roofline where neither bound is known to be
the one: the LARGER of the bytes it has to move over the HBM peak and
the operations it has to do over the bf16 peak, over its own device
time (``hbm_share`` takes the first alone, ``matmul_roofline`` the
second alone and per trained token).

The operations are those under ``scopes`` (and ``instructions``) inside
the programs whose name contains ``program``, found as
``program_scope_share`` finds them; the bytes and operations of ONE
execution come from the model's module (``benchmarks/models/<model>.py``,
``bytes_fn`` and ``flops_fn`` of (config, engine stats): what the
arithmetic needs, not what the program happens to do), times the
executions the trace holds whole. The latent decode kernel does 242
operations a byte at a v5e's ridge of 240: which of the two is larger
turns on the device's table entry, so both are computed."""

import importlib

from benchmarks import peaks
from benchmarks.reducers import program_scope_share


def reduce(ctx, scopes: list[str], program: str, bytes_fn: str,
           flops_fn: str, instructions: tuple[str, ...] = ()):
    name = ctx["config"].get("model")
    if name is None or ctx["device"]["platform"] != "tpu":
        return None
    found = program_scope_share.selected(ctx, scopes, instructions, program)
    if found is None or found[0] <= 0:
        return None
    seconds, executions = found
    model = importlib.import_module(f"benchmarks.models.{name}")
    engine = ctx["counters"].get("engine", {})
    peak = peaks.load(ctx["device"]["kind"])
    least = max(
        getattr(model, bytes_fn)(ctx["config"], engine) / peak["hbm_bytes_per_s"],
        getattr(model, flops_fn)(ctx["config"], engine) / peak["bf16_flops"],
    )
    return 100.0 * least * executions / seconds
