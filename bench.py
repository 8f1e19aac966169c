"""Headline benchmark: flagship-model training throughput on one TPU chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Metric is tokens/sec/chip for a full train step (fwd+bwd+adamw, remat) on
the Llama-architecture `bench` preset. `vs_baseline` follows BASELINE.md's
north star (tokens/sec/chip vs TorchTrainer+NCCL on A100): the reference
publishes no committed numbers (BASELINE.json.published is empty), so we
normalize by model FLOPs utilization against a 40% MFU torch/A100 proxy —
vs_baseline = our_MFU / 0.40. Extra keys document the inputs.
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp

from ray_tpu._private import chip
from ray_tpu.models import PRESETS
from ray_tpu.train.step import (
    init_train_state,
    jit_train_step,
    make_optimizer,
)

BASELINE_MFU = 0.40  # TorchTrainer+NCCL A100 proxy (see module docstring)


def run(batch_size: int, seq: int, steps: int = 30) -> dict:
    import dataclasses

    # Flash attention + chunked cross-entropy keep HBM flat enough for
    # batch 16 at seq 2048 on one v5e chip (the dense+full-logits path
    # OOMs past batch 16). bf16 first moments measured loss-neutral and
    # marginally faster (less optimizer-state bandwidth).
    cfg = dataclasses.replace(PRESETS["bench"], attn_impl="flash")
    opt = make_optimizer(total_steps=1000, mu_dtype=jnp.bfloat16)

    from ray_tpu.parallel import make_mesh

    mesh = make_mesh({"dp": len(jax.devices())})
    step = jit_train_step(cfg, opt, mesh)

    state = init_train_state(jax.random.key(0), cfg, opt)
    tokens = jax.random.randint(
        jax.random.key(1), (batch_size, seq + 1), 0, cfg.vocab_size
    )
    batch = {"tokens": tokens}

    # One AOT compile shared by the bench loop and the profiler block.
    # lower().compile() and the jit call path do NOT share an
    # executable cache; letting the profiler recompile the flagship
    # step would double the dominant cost of this script.
    compiled = step.lower(state, batch).compile()

    # Warmup (6 post-compile steps — the first post-compile steps run a
    # slightly cold device; steady state is the meaningful training
    # number). Waiting on the whole new state, not the loss alone, keeps
    # the update tail out of the timed region.
    for _ in range(6):
        state, metrics = compiled(state, batch)
        jax.block_until_ready((state, metrics))

    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = compiled(state, batch)
    # Each step consumes the previous state, so waiting on the final
    # state and metrics covers the whole chain including the last
    # backward + adamw update.
    jax.block_until_ready((state, metrics))
    dt = time.perf_counter() - t0

    tokens_per_step = batch_size * seq
    tokens_per_sec = tokens_per_step * steps / dt
    n_chips = len(jax.devices())
    tokens_per_sec_per_chip = tokens_per_sec / n_chips
    flops_per_token = cfg.flops_per_token(seq)
    peak_flops = chip.local_chip_spec().bf16_flops
    mfu = tokens_per_sec_per_chip * flops_per_token / peak_flops
    result = {
        "metric": "llama_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec_per_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / BASELINE_MFU, 3),
        "mfu": round(mfu, 4),
        "model_params": cfg.num_params(),
        "batch_size": batch_size,
        "seq": seq,
        "n_chips": n_chips,
        "step_time_s": round(dt / steps, 4),
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
    }
    # Compiled-program profiler block: where the MFU gap goes. The
    # analytic half (HLO roofline floors) always; a short on-device
    # capture joins it into the measured decomposition.
    from ray_tpu._private import config as _config
    from ray_tpu.train import profile as _profile
    from ray_tpu.util import tracing as _tracing

    static = _profile.analyze_compiled(compiled)
    static["model_flops_per_step"] = flops_per_token * tokens_per_step
    result["profile_sig"] = static["sig"]
    result["ideal_step_s"] = round(static["ideal_step_s"], 6)
    result["analytic_floor_s"] = {
        k: round(v["floor_s"], 6)
        for k, v in static["categories"].items()
    }
    cap_steps = _config.get("PROFILE_CAPTURE_STEPS")
    t0 = time.perf_counter()
    with _tracing.jax_profile() as cap:
        for _ in range(cap_steps):
            state, metrics = compiled(state, batch)
        jax.block_until_ready((state, metrics))
    wall = time.perf_counter() - t0
    measured = _profile._read_capture(cap.path) if cap.path else None
    if measured is not None:
        rep = _profile.attribution_report(
            measured, wall, cap_steps, static=static
        )
        result["mfu_decomposition"] = rep["shares"]
        result["dominant_gap"] = rep["dominant_gap"]
    return result


def main() -> None:
    import gc
    import sys

    from ray_tpu.runtime.memory import is_resource_exhausted

    # Compile cache, and the TPU or nothing: JAX then raises where it
    # finds no chip, so a CPU run is never printed under the chip
    # metric's name.
    chip.hold_chip()
    # Back off batch size on OOM only; any other failure is the
    # program's and ends the run. Keep only the error *string*: holding
    # the exception would pin run()'s frame (and its ~GBs of device
    # buffers) via the traceback across retries.
    last_err = None
    # 8 measured fastest on v5e at head_dim 128 (33.9k tok/s vs 33.4k at
    # batch 12); the tail is monotonically smaller OOM fallbacks.
    for batch_size in (8, 6, 4, 2, 1):
        try:
            result = run(batch_size=batch_size, seq=2048)
        # tpulint: allow(broad-except reason=jaxlib has no stable OOM class; anything but RESOURCE_EXHAUSTED is re-raised)
        except Exception as e:  # noqa: BLE001
            if not is_resource_exhausted(e):
                raise
            last_err = f"{type(e).__name__}: {e}"
            del e
            gc.collect()
            continue
        print(json.dumps(result))
        return
    print(
        json.dumps(
            {
                "metric": "llama_train_tokens_per_sec_per_chip",
                "value": 0.0,
                "unit": "tokens/s/chip",
                "vs_baseline": 0.0,
                "error": (last_err or "")[:500],
            }
        )
    )
    sys.exit(1)


if __name__ == "__main__":
    main()
