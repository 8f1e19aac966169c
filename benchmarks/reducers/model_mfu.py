"""Model FLOP/s utilization of a model that names its module
(``benchmarks/models/<model>.py``): that module's
``train_flops_per_token`` (the operations the forward and backward
passes require of the parameters a token passes through; recompute not
counted) times the median step's tokens per second per chip, over the
chip's published bf16 peak (``benchmarks/peaks.json``)."""

import importlib

from benchmarks import peaks


def reduce(ctx):
    rate = ctx["counters"].get("median_step_tokens_per_s_per_chip")
    name = ctx["config"].get("model")
    if rate is None or name is None or ctx["device"]["platform"] != "tpu":
        return None
    model = importlib.import_module(f"benchmarks.models.{name}")
    peak = peaks.load(ctx["device"]["kind"])["bf16_flops"]
    per_token = model.train_flops_per_token(ctx["config"], ctx["counters"]["seq"])
    return 100.0 * per_token * rate / peak
