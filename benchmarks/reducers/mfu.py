"""Model FLOP/s utilization: the operations the forward and backward
passes require per token (``benchmarks/flops.py``; recompute not
counted) times tokens per second per chip, over the chip's published
bf16 peak (``benchmarks/peaks.json``). The rate is that of the median
step, so that a traced run's profiler start and stop, which sit in its
window, do not lower it."""

from benchmarks import flops, peaks


def reduce(ctx):
    rate = ctx["counters"].get("median_step_tokens_per_s_per_chip")
    if rate is None or ctx["device"]["platform"] != "tpu":
        return None
    peak = peaks.load(ctx["device"]["kind"])["bf16_flops"]
    per_token = flops.train_flops_per_token(ctx["config"], ctx["counters"]["seq"])
    return 100.0 * per_token * rate / peak
