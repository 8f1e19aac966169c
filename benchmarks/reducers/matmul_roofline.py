"""A family of matmul kernels' share of the chip's bf16 peak: the
operations they executed in the traced steps over their own device
time, over the peak (``benchmarks/peaks.json``). Compute bounds these
kernels (at OLMoE's shapes an expert's three matmuls do 2 x 1024 rows x
6.3M weights against 12.6 MB of weights read: 1,000 operations a byte,
the chip's ridge is 240), so the share of the peak is the roofline
share.

The operations come from the model's module
(``benchmarks/models/<model>.py``, ``flops_fn`` per token trained:
executed operations, recompute included, since the trace holds the
recompute's kernels too) times the tokens of the steps whose program
executions lie whole inside the trace; the time is the own time of the
operations whose HLO text contains ``match``, inside those executions."""

import importlib

from benchmarks import peaks, traceread


def reduce(ctx, match: str, flops_fn: str, program: str):
    name = ctx["config"].get("model")
    tokens = ctx["counters"].get("tokens_per_step_per_chip")
    if name is None or tokens is None or ctx["device"]["platform"] != "tpu":
        return None
    model = importlib.import_module(f"benchmarks.models.{name}")
    per_token = getattr(model, flops_fn)(
        ctx["config"], ctx["config"]["train"]["remat"]
    )
    peak = peaks.load(ctx["device"]["kind"])["bf16_flops"]
    shares = []
    for dev, runs in zip(traceread.devices(ctx["events"]),
                         traceread.program_runs(ctx["events"], program),
                         strict=True):
        ops = traceread.select(ctx["events"], dev, traceread.OPS)
        seconds, steps = 0.0, set()
        for e, own in traceread.self_times(ops):
            if match not in e.text:
                continue
            for i, run in enumerate(runs):
                if run.start <= e.start and e.start + e.dur <= run.start + run.dur:
                    seconds += own
                    steps.add(i)
                    break
        if seconds > 0:
            shares.append(
                100.0 * per_token * tokens * len(steps) / seconds / peak
            )
    return sum(shares) / len(shares) if shares else None
