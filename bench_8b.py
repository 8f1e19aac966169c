"""8B-scale single-chip memory validation (BASELINE.md north star
de-risk): run REAL Llama-3-8B layers — full d_model 4096 / d_ff 14336 /
32q+8kv heads at head_dim 128 — with the exact remat + flash +
chunked-CE recipe the pod run would use, sized to one v5e chip the way
ZeRO-3 shards it.

On a v5p-64 FSDP pod each chip holds 1/64 of params+opt state
(~16 B/param · 8B / 64 ≈ 2 GB) plus its batch shard's activations. One
v5e chip can't hold 8B params, so this bench keeps N full-size layers
plus a PER-CHIP VOCAB SHARD of the embedding/head (8k of 128k rows — the
full fp32-adamw table is ~15 GB and never sits on one chip even on the
pod) and runs real train steps at seq 4096. Passing proves the
activation/remat memory recipe for full-size layers; the full-vocab
table is only ever exercised sharded, exactly as deployed.

Prints ONE JSON line (separate from bench.py's headline metric).
"""

from __future__ import annotations

import dataclasses
import json
import time


def run(n_layers: int, batch: int, seq: int, steps: int = 5) -> dict:
    from ray_tpu._private import chip

    chip.hold_chip()  # compile cache; the TPU or an error, never the CPU
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import PRESETS
    from ray_tpu.parallel import make_mesh
    from ray_tpu.train.step import (
        init_train_state,
        jit_train_step,
        make_optimizer,
    )

    cfg = dataclasses.replace(
        PRESETS["llama3_8b"],
        n_layers=n_layers,
        # The 128k-vocab embedding/head is ZeRO-sharded on the pod
        # (~8k rows per chip on a 16-chip slice); model the per-chip
        # shard, not the full table — full-vocab fp32 adamw alone is
        # ~15 GB and can never sit on one chip.
        vocab_size=8192,
        attn_impl="flash",
        remat="full",
    )
    opt = make_optimizer(total_steps=1000, mu_dtype=jnp.bfloat16)
    mesh = make_mesh({"dp": 1})
    step = jit_train_step(cfg, opt, mesh)
    state = init_train_state(jax.random.key(0), cfg, opt)
    tokens = jax.random.randint(
        jax.random.key(1), (batch, seq + 1), 0, cfg.vocab_size
    )
    batch_d = {"tokens": tokens}
    for _ in range(2):
        state, metrics = step(state, batch_d)
        jax.block_until_ready((state, metrics))
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, batch_d)
    jax.block_until_ready((state, metrics))
    loss = float(metrics["loss"])
    dt = (time.perf_counter() - t0) / steps
    per_layer_ms = dt / n_layers * 1e3
    # Peak HBM through the memory signal plane (runtime/memory.py):
    # backend memory_stats where exposed, live-array byte accounting
    # where it isn't — that fallback reports the resident state between
    # steps (params + optimizer + batch), the floor of the true in-step
    # peak.
    from ray_tpu.runtime import memory as rmem

    samp = rmem.sample(emit=False) or {}
    hbm = samp.get("hbm") or {}
    peak = hbm.get("peak_bytes") or hbm.get("used_bytes")
    hbm_gb = round(peak / 2**30, 2) if peak else None
    return {
        "metric": "llama3_8b_layer_memory_validation",
        "n_full_layers": n_layers,
        "params": cfg.num_params(),
        "batch": batch,
        "seq": seq,
        "step_time_s": round(dt, 3),
        "per_layer_ms": round(per_layer_ms, 1),
        "tokens_per_sec": round(batch * seq / dt, 1),
        "loss": round(loss, 3),
        "peak_hbm_gb": hbm_gb,
        "peak_hbm_source": hbm.get("source"),
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "hbm_by_kind_gb": {
            k: round(v / 2**30, 2)
            for k, v in (hbm.get("by_kind") or {}).items()
            if v
        },
        "ok": True,
    }


def planner_block(
    committed: "tuple[int, int]", oom_at: "list[list[int]]"
) -> dict:
    """Predicted-vs-empirical fit verdicts for every attempted config:
    the analytic planner (ray_tpu.train.memory.plan) priced against
    the same 16 GB v5e the empirical boundary was measured on. A
    mismatch on any config means the byte model drifted from reality
    and fails tier-1 (tests/test_memory_plane.py pins this block)."""
    from ray_tpu.train.memory import plan_bench8b

    configs = []
    all_match = True
    for n_layers, batch in [tuple(c) for c in oom_at] + [committed]:
        p = plan_bench8b(n_layers, batch)
        empirical = "oom" if [n_layers, batch] in oom_at else "fits"
        predicted = "fits" if p.fits else "oom"
        match = predicted == empirical
        all_match = all_match and match
        configs.append({
            "config": [n_layers, batch],
            "predicted_gb": round(p.total_gb, 2),
            "predicted_headroom_gb": round(
                p.headroom_bytes / 2**30, 2
            ),
            "predicted": predicted,
            "empirical": empirical,
            "match": match,
        })
    return {
        "model": "analytic (ray_tpu.train.memory.plan): fp32 params + "
                 "adamw(bf16 mu) + fp32 grads + remat-full activations "
                 "+ chunked-CE logits vs 16 GiB minus XLA reserve",
        "hbm_gb": 16.0,
        "reserve_gb": 0.5,
        "configs": configs,
        "all_match": all_match,
    }


def main() -> None:
    import os
    import subprocess
    import sys

    one = os.environ.get("BENCH8B_CONFIG")
    if one:
        n_layers, batch = (int(x) for x in one.split(","))
        try:
            print(json.dumps(run(n_layers=n_layers, batch=batch, seq=4096)))
        except Exception as e:  # noqa: BLE001 - parent reads rc/stderr
            print(
                json.dumps(
                    {"ok": False, "error": f"{type(e).__name__}: {e}"[:300]}
                )
            )
            sys.exit(1)
        return

    # Full-size 8B layers; start at the LARGEST candidate and back off
    # on OOM — the first success is the committed max-that-fits. Each
    # attempt runs in a FRESH process: a TPU ResourceExhausted leaves
    # the backend unreliable for later in-process attempts.
    last_err = "no config attempted"
    oom_at = []
    for n_layers, batch in (
        (12, 1), (10, 1), (8, 2), (8, 1), (6, 2), (6, 1),
        (4, 2), (4, 1), (2, 1), (1, 1),
    ):
        env = dict(os.environ, BENCH8B_CONFIG=f"{n_layers},{batch}")
        try:
            proc = subprocess.run(
                [sys.executable, __file__],
                capture_output=True,
                text=True,
                env=env,
                timeout=560,
            )
        except subprocess.TimeoutExpired:
            # A too-big config can wedge in compile/swap; treat like an
            # OOM and keep backing off (the contract is ONE JSON line).
            oom_at.append([n_layers, batch])
            last_err = f"timeout at layers={n_layers} batch={batch}"
            continue
        lines = [
            ln for ln in proc.stdout.splitlines() if ln.startswith("{")
        ]
        if proc.returncode == 0 and lines:
            rec = json.loads(lines[-1])
            # The OOM'd larger configs ARE the headroom measurement
            # when the backend exposes no memory_stats: the fit
            # boundary sits between the committed config and these —
            # and the analytic planner must agree with every verdict.
            rec["oom_at"] = oom_at
            rec["planner"] = planner_block((n_layers, batch), oom_at)
            print(json.dumps(rec))
            return
        oom_at.append([n_layers, batch])
        last_err = (lines[-1] if lines else proc.stderr[-300:]) or "?"
    print(
        json.dumps(
            {
                "metric": "llama3_8b_layer_memory_validation",
                "ok": False,
                "error": last_err,
            }
        )
    )
    sys.exit(1)


if __name__ == "__main__":
    main()
