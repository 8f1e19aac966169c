"""Analytic train-step memory planner: predict per-config HBM bytes and
a fits/OOM verdict *before* committing a chip to the config.

BENCH_8B found the v5e fit boundary empirically — six llama configs
died in ResourceExhausted to learn that [4 layers, batch 2] fits. This
module is the closed-form version of that search: it prices every
resident and transient category of the fused train step
(train/step.py: forward scan+remat → chunked-CE → backward → adamw)
and compares against usable capacity, so capacity questions ("does
[6,1] fit?", "what does ZeRO sharding buy?") are answered in
microseconds instead of chip-hours. The planner's verdicts are
validated against BENCH_8B's empirical boundary (all seven configs) in
tier-1 and pinned in BENCH_8B.json's ``planner`` block.

Byte model (per chip, dp replicas shard only the batch, fsdp shards
params/optimizer/grads ZeRO-3 style, zero shards the optimizer state
across dp replicas — arXiv:2004.13336, train/zero.py):

- params: fp32 master weights (models/llama.py init_params), 4 B/param
- optimizer: adamw mu (``mu_dtype``, bf16 halves it) + fp32 nu
- grads: fp32, materialized tree-wide for clip_by_global_norm
- activations: remat="full" saves only the [B,S,d] residual stream per
  scanned layer (cfg.dtype) and re-materializes one layer's working
  set in backward — priced as ``ACT_WORKING_FACTOR`` × the layer's
  widest tensor [B,S,d_ff]; remat="none" keeps every intermediate
  (~the full working set per layer); "dots" sits between
- cross-entropy: chunked-CE peaks at one [B,chunk,V] fp32 logits block
  plus its gradient (train/step.py chunked_cross_entropy)
- collective scratch: the gradient bucketer's in-flight flat payloads
  (~2 size-targeted buckets in flight) plus int8 codec temporaries
  (wire ratio ~0.26 of the bucket) when compression is on

``XLA_RESERVE_BYTES`` holds back runtime workspace + fragmentation —
the compiler never hands user code the last half-GiB.
"""

from __future__ import annotations

import dataclasses

# Resident-state byte widths (see train/step.py make_optimizer and
# models/llama.py init_params).
PARAM_BYTES = 4  # fp32 master weights
NU_BYTES = 4     # adamw second moment stays fp32
GRAD_BYTES = 4   # fp32 grads (global-norm clip materializes the tree)

# Backward working-set multiplier for remat="full": gate/up activations,
# their grads, and the attention projections' recompute, in units of the
# layer's widest tensor [B, S, d_ff] at cfg.dtype. Calibrated against
# the BENCH_8B boundary ([4,2] fits with ~1.5 GiB predicted headroom;
# every listed OOM config over-subscribes).
ACT_WORKING_FACTOR = 6.0
# remat="none" keeps ~every intermediate of every layer instead of one
# layer's recompute window.
ACT_NONE_PER_LAYER_FACTOR = 8.0
# "dots" saves matmul outputs: between the two.
ACT_DOTS_PER_LAYER_FACTOR = 4.0

# XLA workspace + allocator fragmentation held back from "usable".
XLA_RESERVE_BYTES = 512 << 20

CE_CHUNK = 1024  # train/step.py chunked_cross_entropy default


@dataclasses.dataclass(frozen=True)
class MemoryPlan:
    """One config's predicted per-chip memory bill and verdict."""

    n_layers: int
    batch: int
    seq: int
    n_params: int
    params_bytes: int
    optimizer_bytes: int
    grads_bytes: int
    activation_bytes: int
    ce_bytes: int
    scratch_bytes: int
    total_bytes: int
    capacity_bytes: int
    reserve_bytes: int
    usable_bytes: int
    headroom_bytes: int
    fits: bool

    @property
    def total_gb(self) -> float:
        return self.total_bytes / (1 << 30)

    def to_dict(self) -> dict:
        out = dataclasses.asdict(self)
        out["total_gb"] = round(self.total_gb, 2)
        out["headroom_gb"] = round(self.headroom_bytes / (1 << 30), 2)
        return out

    def breakdown(self) -> dict[str, int]:
        return {
            "params": self.params_bytes,
            "optimizer": self.optimizer_bytes,
            "grads": self.grads_bytes,
            "activations": self.activation_bytes,
            "cross_entropy": self.ce_bytes,
            "collective_scratch": self.scratch_bytes,
        }


def _dtype_bytes(dtype) -> int:
    """Width of a dtype given as a jnp dtype, numpy dtype, or name."""
    name = getattr(dtype, "__name__", None) or str(dtype)
    name = name.rsplit(".", 1)[-1]
    return {
        "bfloat16": 2, "float16": 2, "float32": 4, "float64": 8,
        "int8": 1, "float8_e4m3fn": 1, "float8_e5m2": 1,
    }.get(name, 4)


def default_capacity_bytes() -> int:
    """Detected device capacity (runtime/memory.py — chaos cap, backend
    limit, device-kind table), 16 GiB (v5e) when undetectable."""
    from ray_tpu.runtime import memory as rmem

    cap, _source = rmem.device_capacity_bytes()
    return cap if cap else 16 << 30


def plan(
    cfg,
    batch: int,
    seq: int,
    *,
    mu_dtype="bfloat16",
    hbm_gb: float | None = None,
    fsdp: int = 1,
    zero: int = 1,
    grad_bucket_mb: float | None = None,
    compression: str | None = None,
    reserve_bytes: int = XLA_RESERVE_BYTES,
) -> MemoryPlan:
    """Price one train-step config (a models.llama LlamaConfig, or a
    models.moe MoEConfig, whose ``num_params`` counts every expert; plus
    batch/seq) against a chip's HBM and return the
    :class:`MemoryPlan` verdict. ``fsdp`` divides the resident state
    (params/optimizer/grads) ZeRO-3 style; ``zero`` divides the
    OPTIMIZER state only — the cross-replica weight-update sharding of
    arXiv:2004.13336 (train/zero.py): params stay full (the allgather
    rebuilds them) and grads still materialize tree-wide in backward,
    so only the adamw moments shrink. This lever is a measured claim:
    bench_zero.py pins the ledger's resident bytes against it.
    ``hbm_gb`` overrides capacity detection;
    ``grad_bucket_mb``/``compression`` price the bucketed-overlap
    scratch when the sync path uses it."""
    n_params = int(cfg.num_params())
    shard = max(1, int(fsdp))
    opt_shard = shard * max(1, int(zero))
    params_bytes = n_params * PARAM_BYTES // shard
    mu_bytes = n_params * _dtype_bytes(mu_dtype) // opt_shard
    optimizer_bytes = mu_bytes + n_params * NU_BYTES // opt_shard
    grads_bytes = n_params * GRAD_BYTES // shard
    act_dtype = _dtype_bytes(cfg.dtype)
    boundary = cfg.n_layers * batch * seq * cfg.d_model * act_dtype
    # A sparse-expert layer runs top_k experts of width d_ff per token.
    working_unit = (
        batch * seq * cfg.d_ff * getattr(cfg, "top_k", 1) * act_dtype
    )
    remat = getattr(cfg, "remat", "full")
    if remat == "full":
        activation_bytes = boundary + int(
            ACT_WORKING_FACTOR * working_unit
        )
    elif remat == "dots":
        activation_bytes = boundary + int(
            ACT_DOTS_PER_LAYER_FACTOR * cfg.n_layers * working_unit
        )
    else:  # "none": every layer's working set stays live
        activation_bytes = boundary + int(
            ACT_NONE_PER_LAYER_FACTOR * cfg.n_layers * working_unit
        )
    chunk = min(CE_CHUNK, seq)
    # logits + their grad, fp32 (train/step.py chunked_cross_entropy)
    ce_bytes = 2 * batch * chunk * cfg.vocab_size * 4
    scratch_bytes = 0
    if grad_bucket_mb:
        bucket = int(grad_bucket_mb * (1 << 20))
        scratch_bytes = 2 * bucket  # ~2 buckets in flight
        if compression:
            scratch_bytes += int(0.26 * bucket)  # int8 wire + scales
    capacity_bytes = int(
        hbm_gb * (1 << 30) if hbm_gb else default_capacity_bytes()
    )
    usable = capacity_bytes - reserve_bytes
    total = (
        params_bytes + optimizer_bytes + grads_bytes
        + activation_bytes + ce_bytes + scratch_bytes
    )
    return MemoryPlan(
        n_layers=cfg.n_layers,
        batch=batch,
        seq=seq,
        n_params=n_params,
        params_bytes=params_bytes,
        optimizer_bytes=optimizer_bytes,
        grads_bytes=grads_bytes,
        activation_bytes=activation_bytes,
        ce_bytes=ce_bytes,
        scratch_bytes=scratch_bytes,
        total_bytes=total,
        capacity_bytes=capacity_bytes,
        reserve_bytes=reserve_bytes,
        usable_bytes=usable,
        headroom_bytes=usable - total,
        fits=total <= usable,
    )


def plan_bench8b(
    n_layers: int, batch: int, seq: int = 4096, hbm_gb: float = 16.0
) -> MemoryPlan:
    """The exact BENCH_8B recipe, priced: full-size llama3-8b layers,
    8k-row vocab shard, bf16 adamw mu, remat=full, seq 4096."""
    import dataclasses as _dc

    from ray_tpu.models import PRESETS

    cfg = _dc.replace(
        PRESETS["llama3_8b"],
        n_layers=n_layers,
        vocab_size=8192,
        attn_impl="flash",
        remat="full",
    )
    return plan(cfg, batch, seq, mu_dtype="bfloat16", hbm_gb=hbm_gb)
