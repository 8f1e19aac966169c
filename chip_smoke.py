#!/usr/bin/env python3
"""The quickest proof that ray_tpu still starts on the chip.

Drives the system's two main paths once, through the entry points a user
calls, at the full widths of ``PRESETS["llama3_8b"]`` (depth cut, random
weights from ``--seed``):

- *train*: ``JaxTrainer(...).fit()`` with one worker leasing one chip;
  the loop runs ``jit_train_step`` with flash attention and full remat.
- *serve*: ``serve.run(build_llm_deployment(...))`` behind
  ``serve.start_http()`` answers a few POSTs; then, the replica gone, a
  plain ``@ray_tpu.remote(num_tpus=1)`` task builds the same
  ``LLMEngine`` and compares its logits with ``models.forward``; and
  another builds a tiny hybrid engine (``models/nemotron_h.py``: Mamba-2,
  expert and attention blocks at Nemotron-3-Nano's widths) and compares
  its chunked prefill and decode with the plain reference; and a third
  does the same for a tiny latent-attention engine
  (``models/pangu_ultra_moe.py``: MLA over a latent page pool at
  openPangu-Ultra-MoE's widths). Both also hold the kernel that reads
  the touched experts against the einsum form over all of them.
- ``--chips 4`` instead runs the sharded trainer (one worker, four
  chips, an ``{"fsdp": 4}`` mesh) against the same seed and batch on a
  one-device mesh, and no other phase.

This process never creates a JAX backend: model code runs only in leased
workers, one chip-holding process at a time. Each phase prints one line
of JSON measured inside the process that held the chip. Any failure is
an exception and a non-zero exit; the last line of a run that passed is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

    python chip_smoke.py            # one chip, a few minutes cold
    python chip_smoke.py --chips 4  # the four-chip path only
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import tempfile
import time
import urllib.request
from functools import partial

import ray_tpu

PRESET = "llama3_8b"  # d_model 4096, d_ff 14336, 32 q / 8 kv heads x 128

# Each phase's sizes. "cfg" replaces fields of the preset; every such
# replacement is a cut, repeated under "reduced" in the phase's record.
SIZES = {
    # The largest cut the memory planner (train/memory.py) says fits
    # 16 GB: fp32 params + adamw + grads are ~14 B/param before
    # activations.
    # Compiled for a described v5e: peak 15.43 of 15.75 GiB.
    "train": {
        "cfg": {"n_layers": 4, "vocab_size": 8192, "attn_impl": "flash",
                "remat": "full"},
        "reduced": {
            "n_layers": "4 of 32",
            "vocab_size": "8,192 of 128,256 rows: one chip's share of "
                          "the table on a 16-chip pod",
        },
        "batch": 2, "seq": 4096, "steps": 6,
        "runs": [{"mesh": {"dp": 1}, "devices": 1}],
    },
    # Same widths, sized so that the identical configuration also fits
    # one chip (compiled for a described v5e:2x2: 10.2 GiB peak on one
    # device); batch 4 so that fsdp=4 divides it.
    "fsdp": {
        "cfg": {"n_layers": 2, "vocab_size": 8192, "attn_impl": "flash",
                "remat": "full"},
        "reduced": {
            "n_layers": "2 of 32",
            "vocab_size": "8,192 of 128,256 rows",
        },
        "batch": 4, "seq": 4096, "steps": 5,
        "runs": [{"mesh": {"fsdp": 4}, "devices": 4},
                 {"mesh": {"dp": 1}, "devices": 1}],
    },
    # Full vocabulary; the engine holds its matmul weights in bf16 (2.1
    # GB of embedding + head, 0.44 GB a layer). 8 layers is what fit
    # while it held fp32 and each program kept a bf16 copy of the blocks
    # (prefill, decode and reference programs compiled to 14.3, 14.3 and
    # 13.7 of 15.75 GiB); the depth has not been cut again.
    "serve": {
        "cfg": {"n_layers": 8},
        "reduced": {"n_layers": "8 of 32"},
        "engine": {"max_batch": 8, "max_seq": 1024, "page_size": 64},
        "max_tokens": 12,
        # (prompt bytes, streamed): two prefill buckets, 64 and 128.
        "requests": [(12, False), (40, False), (100, False), (61, True),
                     (5, False)],
        "check_prompt": 48, "check_decode": 4, "check_pad": 128,
    },
    # One tiny hybrid engine (models/nemotron_h.py) beside the Llama
    # one: every kind of block once or twice at Nemotron-3-Nano's widths,
    # so that the kernels see their real tiles; tiny in depth, experts
    # and vocabulary (0.7 GB of weights). The 200-token prompt goes in
    # two chunks of 128, the second padded.
    "hybrid": {
        "cfg": {"pattern": "ME*EM", "num_experts": 8, "vocab_size": 8192},
        "reduced": {"pattern": "5 of 52 blocks", "num_experts": "8 of 128",
                    "vocab_size": "8,192 of 131,072 rows"},
        "engine": {"max_batch": 4, "max_seq": 1024, "page_size": 64,
                   "prefill_chunk": 128},
        "check_prompt": 200, "check_decode": 4,
    },
    # One tiny latent-attention engine (models/pangu_ultra_moe.py): a
    # dense and an expert layer at openPangu-Ultra-MoE's widths, 128
    # heads over a 640-lane latent pool, tiny in depth, experts and
    # vocabulary (1.5 GB of weights). The 600-token prompt goes in three
    # chunks of 256, the last padded, each attending the earlier chunks'
    # latent pages.
    "latent": {
        "cfg": {"n_layers": 2, "first_k_dense": 1, "experts_held": (0, 8),
                "vocab_size": 8192},
        "reduced": {"n_layers": "2 of 61 (1 dense + 1 expert)",
                    "experts_held": "8 of 256",
                    "vocab_size": "8,192 of 153,600 rows"},
        "engine": {"max_batch": 4, "max_seq": 1024, "page_size": 64,
                   "prefill_chunk": 256},
        "check_prompt": 600, "check_decode": 4,
    },
}

# Engine logits against models.forward with dense attention, max
# absolute difference over the vocabulary: both run in bf16 and differ
# in the attention path and the order of reductions. Measured on a v5e
# (PR 21): 0.035 for the prefill and 0.041-0.044 for four decode steps,
# on logits of magnitude 4.0.
LOGIT_TOLERANCE = 0.1
# Sharded against one-device loss at every step: same seed, same batch,
# another order of reductions. Measured on four v5e chips (PR 21): at
# most 7.2e-5 over five steps, on losses of 9.4 falling to 8.8.
LOSS_TOLERANCE = 1e-3
# Per-device bytes_in_use of the sharded run, largest over smallest
# (measured 1.009: the first device also holds the step counter and
# other scalars).
BYTES_SPREAD = 1.1


class SmokeFailure(AssertionError):
    """A phase ran and what came out of it is wrong."""


def _require(ok: bool, why: str) -> None:
    if not ok:
        raise SmokeFailure(why)


# ------------------------------------------------- inside leased workers
def _watch_compiles() -> dict:
    """Seconds JAX spent in backend compiles (persistent-cache reads
    included), by program name, from now on in this process."""
    import jax.monitoring

    seen: dict[str, float] = {}

    def on_event(event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            name = kw.get("fun_name", "?")
            seen[name] = seen.get(name, 0.0) + secs

    jax.monitoring.register_event_duration_secs_listener(on_event)
    return seen


def _device_record() -> dict:
    import os

    import jax

    from ray_tpu._private import chip

    dev = jax.devices()[0]
    return {
        "pid": os.getpid(),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": jax.device_count(),
        "compile_cache": chip.compile_cache_dir(),
    }


def _train_steps(sizes: dict, run: dict, seed: int, compiles: dict) -> dict:
    """A few synchronised steps of jit_train_step on ``run``'s mesh."""
    import jax
    import jax.numpy as jnp

    from ray_tpu import train
    from ray_tpu.models import PRESETS
    from ray_tpu.parallel import make_mesh
    from ray_tpu.parallel.sharding import tree_shardings
    from ray_tpu.train.step import (
        init_train_state,
        jit_train_step,
        make_optimizer,
        state_logical_axes,
    )

    t_start = time.perf_counter()
    compile_start = sum(compiles.values())
    cfg = dataclasses.replace(PRESETS[sizes["preset"]], **sizes["cfg"])
    opt = make_optimizer(total_steps=1000, mu_dtype=jnp.bfloat16)
    devices = jax.devices()[: run["devices"]]
    mesh = make_mesh(run["mesh"], devices=devices)
    step = jit_train_step(cfg, opt, mesh)
    state = init_train_state(jax.random.key(seed), cfg, opt)
    tokens = jax.random.randint(
        jax.random.key(seed + 1),
        (sizes["batch"], sizes["seq"] + 1), 0, cfg.vocab_size,
    )
    if mesh.size > 1:
        state = jax.device_put(
            state, tree_shardings(mesh, state_logical_axes(cfg, opt))
        )
        tokens = jax.device_put(
            tokens, tree_shardings(mesh, ("batch", None))
        )
    batch = {"tokens": tokens}
    compile_before = sum(compiles.values())
    compiled = step.lower(state, batch).compile()
    step_compile_s = sum(compiles.values()) - compile_before
    kernel = "tpu_custom_call" in compiled.as_text()
    losses, step_s = [], []
    for i in range(sizes["steps"]):
        t0 = time.perf_counter()
        state, metrics = compiled(state, batch)
        jax.block_until_ready((state, metrics))
        step_s.append(time.perf_counter() - t0)
        losses.append(float(metrics["loss"]))
        train.report({"step": i, "loss": losses[-1]})
    w = state.params["blocks"]["w_gate"]
    stats = [d.memory_stats() or {} for d in devices]
    return {
        "mesh": run["mesh"],
        "mesh_devices": [d.id for d in devices],
        "losses": losses,
        "step_s": step_s,
        # All programs of this run (the eager initialisers too), and
        # the train step's own.
        "compile_s": sum(compiles.values()) - compile_start,
        "step_compile_s": step_compile_s,
        "wall_s": time.perf_counter() - t_start,
        "tpu_custom_call": kernel,
        # The compiler's account of the step program. The allocator's
        # peak below does not see a program's temporaries.
        "program_peak_bytes": compiled.memory_analysis().peak_memory_in_bytes,
        "param_shard_devices": sorted(
            {s.device.id for s in w.addressable_shards}
        ),
        "param_shard_fraction": w.addressable_shards[0].data.size / w.size,
        "bytes_in_use": [s.get("bytes_in_use") for s in stats],
        "peak_bytes_in_use": [s.get("peak_bytes_in_use") for s in stats],
    }


def train_loop(config: dict) -> None:
    """The JaxTrainer loop: one run per mesh in ``config["runs"]``, each
    freed before the next starts; the last report carries the record."""
    from ray_tpu import train

    compiles = _watch_compiles()
    runs = [
        _train_steps(config, run, config["seed"], compiles)
        for run in config["runs"]
    ]
    train.report({**_device_record(), "runs": runs})


def engine_check(cfg, engine_kwargs: dict, sizes: dict, seed: int) -> dict:
    """Build the replica's engine again, drive one request through
    add_request/step, and compare the logits its prefill and decode
    programs produced with models.forward (dense attention) on the same
    weights and tokens."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.llm.engine import LLMEngine, SamplingParams
    from ray_tpu.llm.paged_kv import paged_verify
    from ray_tpu.models.llama import forward

    t_start = time.perf_counter()
    compiles = _watch_compiles()
    eng = LLMEngine(cfg, **engine_kwargs)
    engine_s = time.perf_counter() - t_start
    prefill_logits, decode_logits = [], []

    def tap(fn, pick, into):
        def tapped(*args, **kw):
            out = fn(*args, **kw)
            into.append(np.asarray(pick(out)))
            return out

        return tapped

    # Slot 0 serves the only request: prefill logits [S_pad, V], decode
    # logits [V] per step.
    eng._prefill_paged = tap(
        eng._prefill_paged, lambda out: out[0][0], prefill_logits
    )
    eng._decode_paged = tap(
        eng._decode_paged, lambda out: out[1][0], decode_logits
    )
    rng = np.random.default_rng(seed)
    prompt = rng.integers(1, cfg.vocab_size, sizes["check_prompt"]).tolist()
    n_decode = sizes["check_decode"]
    (generated,) = eng.generate(
        [prompt], SamplingParams(max_tokens=n_decode + 1)
    )
    _require(
        len(prefill_logits) == 1 and len(decode_logits) == n_decode,
        f"engine made {len(prefill_logits)} prefill and "
        f"{len(decode_logits)} decode calls for {n_decode + 1} tokens",
    )
    # One reference pass over prompt + generated tokens: causal, so row
    # i holds the logits after token i whatever follows it.
    tokens = np.zeros((1, sizes["check_pad"]), np.int32)
    seq = prompt + generated
    tokens[0, : len(seq)] = seq
    ref = np.asarray(
        jax.jit(partial(forward, cfg=cfg))(eng.params, jnp.asarray(tokens))
    )[0]
    n = len(prompt)
    errs = [float(np.abs(prefill_logits[0][n - 1] - ref[n - 1]).max())]
    for i, got in enumerate(decode_logits):
        errs.append(float(np.abs(got - ref[n + i]).max()))
    # The decode program as the engine compiled it (same shapes, so a
    # cache hit): is the paged-attention kernel in it?
    b = eng.max_batch
    decode_text = paged_verify.lower(
        eng.params,
        jnp.zeros((b, 1), jnp.int32),
        eng.cache,
        jnp.full((b, eng.max_pages_per_seq), -1, jnp.int32),
        jnp.zeros((b,), jnp.int32),
        jnp.zeros((b,), jnp.float32),
        jax.random.key(seed),
        cfg=cfg, use_kernel=eng.paged_attn_kernel, stochastic=False,
    ).compile().as_text()
    stats = jax.devices()[0].memory_stats() or {}
    return {
        **_device_record(),
        "engine_s": engine_s,
        "wall_s": time.perf_counter() - t_start,
        "compile_s": sum(compiles.values()),
        "logit_max_abs_err": errs,
        "logits_finite": bool(
            np.isfinite(prefill_logits[0][n - 1]).all()
            and all(np.isfinite(d).all() for d in decode_logits)
        ),
        "logit_scale": float(np.abs(ref[n - 1]).max()),
        "tpu_custom_call": "tpu_custom_call" in decode_text,
        "paged_attn_kernel": bool(eng.paged_attn_kernel),
        "weights_bytes": int(
            sum(x.nbytes for x in jax.tree.leaves(eng.params))
        ),
        "cache_bytes": int(sum(x.nbytes for x in jax.tree.leaves(eng.cache))),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
    }


def _check_against_reference(cfg, engine_kwargs: dict, sizes: dict, seed: int,
                             reference, ref_sizes: dict) -> dict:
    """One request through add_request/step of an engine whose programs
    keep a record of their routes, prefilled in chunks, then decode
    steps; the logits its programs produced (handed over by
    `LLMEngine.on_logits`) against the plain reference's one full pass
    on the same weights, tokens and routes."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.llm.engine import LLMEngine, SamplingParams

    t_start = time.perf_counter()
    compiles = _watch_compiles()
    eng = LLMEngine(cfg, **engine_kwargs)
    seen = []
    eng.on_logits = lambda phase, logits, record: seen.append(
        (phase, np.asarray(logits), np.asarray(record["routes"]))
    )
    rng = np.random.default_rng(seed)
    n, n_decode = sizes["check_prompt"], sizes["check_decode"]
    prompt = rng.integers(1, cfg.vocab_size, n).tolist()
    (generated,) = eng.generate(
        [prompt], SamplingParams(max_tokens=n_decode + 1)
    )
    prefills = [s for s in seen if s[0].startswith("prefill")]
    decodes = [s for s in seen if s[0] == "decode"]
    _require(
        len(decodes) == n_decode and len(prefills) >= 1,
        f"engine made {len(prefills)} prefill and {len(decodes)} decode "
        f"calls for {n_decode + 1} tokens",
    )
    # Slot 0 serves the only request.
    routes = np.concatenate([s[2] for s in prefills], axis=1)[:, :n]
    routes = np.concatenate([routes] + [s[2][:, :1] for s in decodes], axis=1)
    ref, record = reference.forward_with_record(
        eng.params, jnp.asarray(prompt + generated[:-1], jnp.int32),
        routes=jnp.asarray(routes), rows=list(range(n - 1, n + n_decode)),
        **ref_sizes,
    )
    ref = np.asarray(ref)
    got = [prefills[-1][1][0, 0]] + [s[1][0] for s in decodes]
    return {
        **_device_record(),
        "wall_s": time.perf_counter() - t_start,
        "compile_s": sum(compiles.values()),
        "prefill_calls": len(prefills),
        "logit_max_abs_err": [
            float(np.abs(g - r).max()) for g, r in zip(got, ref, strict=True)
        ],
        "logits_finite": bool(all(np.isfinite(g).all() for g in got)),
        "logit_scale": float(np.abs(ref).max()),
        "largest_route_slack": float(np.asarray(record["slack"]).max()),
        "paged_attn_kernel": bool(eng.paged_attn_kernel),
        "engine_stats": eng.stats(),
        **_expert_kernel_check(cfg, eng.params, seed),
    }


def _expert_kernel_check(cfg, params, seed: int) -> dict:
    """The touched-experts kernel (ops/pallas/expert_rows.py: what a
    decode step's expert blocks run on the chip) against the einsum form
    over every held expert, on the engine's first expert block at its
    published widths: 32 rows whose pairs fall on half of the held
    experts, so that the work list skips the other half. A tile that
    reads the wrong expert or the wrong lanes shows here, on a machine
    that has no benchmark."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu._private import chip
    from ray_tpu.models import moe
    from ray_tpu.ops.pallas.expert_rows import experts_on_rows

    p = next(b for b in params["blocks"] if "router" in b)
    first, held = cfg.experts_held or (0, cfg.num_experts)
    rng = np.random.default_rng(seed)
    rows = jax.random.normal(
        jax.random.key(seed), (32, cfg.d_model), jnp.float32
    ).astype(cfg.dtype)
    routes = jnp.asarray(
        first + 2 * rng.integers(0, held // 2, (32, 2)), jnp.int32
    )
    gates = jnp.asarray(rng.uniform(0.1, 0.5, (32, 2)), jnp.float32)
    weight, load = moe.every_row_gates(cfg, routes, gates, None)
    ids, count = moe.touched_first(load)
    gated = cfg.expert_kind == "swiglu"
    got = experts_on_rows(
        rows, p["w_gate"] if gated else None, p["w_up"], p["w_down"],
        weight, ids, count, chip.platform() != "tpu",
    )
    want = np.asarray(moe.every_row_einsum(cfg, rows, p, weight), np.float32)
    got = np.asarray(got, np.float32)
    return {
        "expert_kernel_max_abs_err": float(np.abs(got - want).max()),
        "expert_kernel_finite": bool(np.isfinite(got).all()),
        "expert_kernel_scale": float(np.abs(want).max()),
        "expert_kernel_touched": [int(count), held],
    }


def hybrid_check(cfg, engine_kwargs: dict, sizes: dict, seed: int) -> dict:
    """A hybrid engine (Mamba-2, expert and attention blocks over pages
    and per-slot state) against ``reference_nemotron_h``."""
    from benchmarks import reference_nemotron_h as reference

    return _check_against_reference(
        cfg, engine_kwargs, sizes, seed, reference, dict(
            pattern=cfg.pattern, mamba_heads=cfg.mamba_heads,
            mamba_head_dim=cfg.mamba_head_dim, n_groups=cfg.ssm_groups,
            ssm_state_size=cfg.ssm_state, conv_kernel=cfg.conv_kernel,
            num_experts_per_tok=cfg.top_k, norm_topk_prob=cfg.norm_topk_prob,
            routed_scaling_factor=cfg.routed_scaling_factor,
            num_attention_heads=cfg.n_heads,
            num_key_value_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        ),
    )


def latent_check(cfg, engine_kwargs: dict, sizes: dict, seed: int) -> dict:
    """A latent-attention engine (MLA in every layer over one latent
    page pool: expanded chunked prefill, absorbed decode through the
    kernel; a dense and an expert layer) against
    ``reference_pangu_ultra_moe``'s non-absorbed pass."""
    from benchmarks import reference_pangu_ultra_moe as reference

    return _check_against_reference(
        cfg, engine_kwargs, sizes, seed, reference, dict(
            first_k_dense_replace=cfg.first_k_dense,
            num_attention_heads=cfg.n_heads,
            qk_nope_head_dim=cfg.qk_nope_head_dim,
            qk_rope_head_dim=cfg.qk_rope_head_dim,
            kv_lora_rank=cfg.kv_lora_rank, rope_theta=cfg.rope_theta,
            num_experts_per_tok=cfg.top_k, norm_topk_prob=cfg.norm_topk_prob,
            routed_scaling_factor=cfg.routed_scaling_factor,
        ),
    )


def _phase_check(name: str, check, cfg, sizes: dict, seed: int) -> dict:
    """``check`` of an engine for ``cfg`` on a plain task's lease."""
    t0 = time.perf_counter()
    record = ray_tpu.get(
        ray_tpu.remote(num_tpus=1)(check).remote(
            cfg, {**sizes["engine"], "seed": seed}, sizes, seed
        )
    )
    return _emit({
        "phase": name,
        "cfg": sizes["cfg"],
        "reduced": sizes["reduced"],
        "tolerance": LOGIT_TOLERANCE,
        **record,
        "task_wall_s": time.perf_counter() - t0,
    })


def phase_hybrid(sizes: dict, seed: int) -> dict:
    """The hybrid engine on a plain task's lease."""
    from ray_tpu.models.nemotron_h import NEMOTRON_H_PRESETS, NemotronHConfig

    cfg = dataclasses.replace(
        NEMOTRON_H_PRESETS["nemotron_h_tiny"] if PRESET == "tiny"
        else NemotronHConfig(), **sizes["cfg"],
    )
    return _phase_check("hybrid_check", hybrid_check, cfg, sizes, seed)


def phase_latent(sizes: dict, seed: int) -> dict:
    """The latent-attention engine on a plain task's lease."""
    from ray_tpu.models.pangu_ultra_moe import (
        PANGU_PRESETS,
        PanguUltraMoEConfig,
    )

    cfg = dataclasses.replace(
        PANGU_PRESETS["pangu_tiny"] if PRESET == "tiny"
        else PanguUltraMoEConfig(), **sizes["cfg"],
    )
    return _phase_check("latent_check", latent_check, cfg, sizes, seed)


# ------------------------------------------------------ the parent's side
def _emit(record: dict) -> dict:
    print(json.dumps(record), flush=True)
    return record


def phase_train(name: str, sizes: dict, seed: int, chips: int) -> dict:
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory() as storage:
        result = JaxTrainer(
            train_loop,
            train_loop_config={**sizes, "preset": PRESET, "seed": seed},
            scaling_config=ScalingConfig(
                num_workers=1, use_tpu=True, chips_per_worker=chips
            ),
            run_config=RunConfig(name=f"chip_smoke_{name}",
                                 storage_path=storage),
        ).fit()
    if result.error is not None:
        raise result.error
    return _emit({
        "phase": name,
        "preset": PRESET,
        "cfg": sizes["cfg"],
        "reduced": sizes["reduced"],
        "batch": sizes["batch"],
        "seq": sizes["seq"],
        "seed": seed,
        **result.metrics,
        "fit_wall_s": time.perf_counter() - t0,
    })


def _post(port: int, body: dict, stream: bool = False):
    headers = {"Content-Type": "application/json"}
    if stream:
        headers["Accept"] = "text/event-stream"
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/", method="POST",
        data=json.dumps(body).encode(), headers=headers,
    )
    with urllib.request.urlopen(req, timeout=600) as resp:
        if not stream:
            return json.loads(resp.read())
        return [ln.decode().strip() for ln in resp if ln.strip()]


def phase_serve(sizes: dict, seed: int) -> list[dict]:
    from ray_tpu import serve
    from ray_tpu.llm import build_llm_deployment
    from ray_tpu.models import PRESETS

    cfg = dataclasses.replace(PRESETS[PRESET], **sizes["cfg"])
    engine_kwargs = {**sizes["engine"], "seed": seed}
    t0 = time.perf_counter()
    try:
        serve.run(
            build_llm_deployment(
                cfg,
                ray_actor_options={"num_tpus": 1},
                engine_kwargs=engine_kwargs,
            ),
            timeout_s=600,
        )
        port = serve.start_http()
        ready_s = time.perf_counter() - t0
        requests = []
        for n_bytes, stream in sizes["requests"]:
            body = {"prompt": "chip smoke "[:n_bytes].ljust(n_bytes, "x"),
                    "max_tokens": sizes["max_tokens"], "stream": stream}
            t = time.perf_counter()
            reply = _post(port, body, stream)
            took = time.perf_counter() - t
            if stream:
                _require(reply[-1] == "data: [DONE]",
                         f"stream did not end with [DONE]: {reply[-3:]}")
                frames = [json.loads(f[len("data: "):]) for f in reply[:-1]]
                n_tokens = sum(len(f["tokens"]) for f in frames)
            else:
                frames, n_tokens = None, reply["num_generated"]
            _require(
                n_tokens == sizes["max_tokens"],
                f"asked {sizes['max_tokens']} tokens of a {n_bytes}-byte "
                f"prompt, got {n_tokens}",
            )
            requests.append({
                "prompt_bytes": n_bytes, "stream": stream, "tokens": n_tokens,
                "frames": None if frames is None else len(frames),
                "wall_s": took,
            })
        stats = _post(port, {"method": "stats"})
    finally:
        serve.shutdown()
    served = _emit({
        "phase": "serve",
        "preset": PRESET,
        "cfg": sizes["cfg"],
        "reduced": sizes["reduced"],
        "engine": engine_kwargs,
        "platform": stats["platform"],
        "device_kind": stats["device_kind"],
        "paged_attn_kernel": stats["paged_attn_kernel"],
        "ready_s": ready_s,
        "requests": requests,
        "engine_stats": stats,
        "wall_s": time.perf_counter() - t0,
    })
    # The third normal way to hold a chip: a plain task.
    t1 = time.perf_counter()
    check = ray_tpu.get(
        ray_tpu.remote(num_tpus=1)(engine_check).remote(
            cfg, engine_kwargs, sizes, seed
        )
    )
    checked = _emit({
        "phase": "engine_check",
        "preset": PRESET,
        "cfg": sizes["cfg"],
        "reduced": sizes["reduced"],
        "tolerance": LOGIT_TOLERANCE,
        **check,
        "task_wall_s": time.perf_counter() - t1,
    })
    return [served, checked]


def verify(records: list[dict], chips: int) -> dict:
    """Hold what the workers reported to the contract; return the
    device they agree on."""
    import math

    by_phase = {r["phase"]: r for r in records}
    for r in records:
        _require(r["platform"] == "tpu",
                 f"phase {r['phase']} ran on platform {r['platform']!r}")
    kinds = {r["device_kind"] for r in records}
    _require(len(kinds) == 1, f"phases disagree on the device: {kinds}")
    counted = [r for r in records if "device_count" in r]
    for r in counted:
        # The node's TPU resource is what a leased worker finds.
        _require(
            r["device_count"] == chips,
            f"phase {r['phase']}: node registered TPU: {chips} but the "
            f"leased worker saw {r['device_count']} devices",
        )
    for name in ("train", "fsdp"):
        for run in by_phase.get(name, {}).get("runs", []):
            losses = run["losses"]
            _require(all(math.isfinite(x) for x in losses),
                     f"{name} {run['mesh']}: loss not finite: {losses}")
            _require(losses[-1] < losses[0],
                     f"{name} {run['mesh']}: loss did not fall: {losses}")
            _require(run["tpu_custom_call"],
                     f"{name} {run['mesh']}: no tpu_custom_call in the "
                     "compiled step (flash kernel replaced or interpreted)")
    if "fsdp" in by_phase:
        sharded, single = by_phase["fsdp"]["runs"]
        gaps = [abs(a - b) for a, b in
                zip(sharded["losses"], single["losses"], strict=True)]
        _require(max(gaps) <= LOSS_TOLERANCE,
                 f"sharded and one-device losses differ by {gaps}")
        _require(len(sharded["param_shard_devices"]) == 4,
                 "parameters are not spread over four devices: "
                 f"{sharded['param_shard_devices']}")
        _require(sharded["param_shard_fraction"] == 0.25,
                 "a parameter shard holds "
                 f"{sharded['param_shard_fraction']} of the parameter")
        used = sharded["bytes_in_use"]
        _require(max(used) <= BYTES_SPREAD * min(used),
                 f"per-device bytes_in_use uneven: {used}")
    if "serve" in by_phase:
        _require(by_phase["serve"]["paged_attn_kernel"],
                 "the replica served without the paged-attention kernel")
        check = by_phase["engine_check"]
        _require(check["tpu_custom_call"] and check["paged_attn_kernel"],
                 "no tpu_custom_call in the engine's decode program")
        _require(check["logits_finite"], "engine logits not finite")
        _require(max(check["logit_max_abs_err"]) <= LOGIT_TOLERANCE,
                 "engine logits differ from models.forward by "
                 f"{check['logit_max_abs_err']} (prefill, then decode steps)")
    for name in ("hybrid_check", "latent_check"):
        if name not in by_phase:
            continue
        check, what = by_phase[name], name.split("_")[0]
        _require(check["paged_attn_kernel"],
                 f"the {what} engine ran without its attention kernel")
        _require(check["logits_finite"], f"{what} engine logits not finite")
        _require(max(check["logit_max_abs_err"]) <= LOGIT_TOLERANCE,
                 f"{what} engine logits differ from the plain reference by "
                 f"{check['logit_max_abs_err']} (prefill, then decode steps)")
        _require(
            check["expert_kernel_finite"]
            and check["expert_kernel_max_abs_err"] <= LOGIT_TOLERANCE,
            f"the {what} model's touched-experts kernel differs from the "
            f"einsum form by {check['expert_kernel_max_abs_err']} at "
            f"magnitude {check['expert_kernel_scale']}",
        )
    return {"platform": "tpu", "kind": kinds.pop(), "count": chips}


def run_phases(chips: int, seed: int, sizes: dict = SIZES) -> list[dict]:
    if chips == 4:
        return [phase_train("fsdp", sizes["fsdp"], seed, chips=4)]
    return [
        phase_train("train", sizes["train"], seed, chips=1),
        *phase_serve(sizes["serve"], seed),
        phase_hybrid(sizes["hybrid"], seed),
        phase_latent(sizes["latent"], seed),
    ]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from ray_tpu._private import chip

    ray_tpu.init()
    try:
        found = int(ray_tpu.cluster_resources().get("TPU", 0))
        if found < args.chips:
            sys.exit(
                f"chip_smoke: this node registered TPU: {found} and "
                f"--chips {args.chips} needs {args.chips}; no accelerator "
                "to lease (ray_tpu/_private/accelerators/tpu.py counts "
                "/dev/accel*, /dev/vfio/* or TPU_VISIBLE_CHIPS)"
            )
        records = run_phases(args.chips, args.seed)
        device = verify(records, found)
        _require(not chip.holds_backend(),
                 "the parent process created a JAX backend")
    finally:
        ray_tpu.shutdown()
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
