"""The benchmark's train loop: what ``JaxTrainer`` runs in the worker
that holds the chips.

Set-up (counted as ``setup_s``): one jitted initialiser from the seed,
placed by ``out_shardings``; the step program compiled; the model
functions the step differentiates checked against
``benchmarks/reference.py``; two warm-up steps. Then the window: steps of
``jit_train_step``, each on a fresh batch of seeded random tokens that
the host made while the step before it ran, each ended with
``block_until_ready``. The record goes back through ``train.report``.
"""

from __future__ import annotations

import math
import os
import time


def watch_compiles() -> list[tuple[float, str, float]]:
    """(instant, program, seconds) of every backend compile JAX makes in
    this process from now on; a read of the persistent cache counts."""
    import jax.monitoring

    seen: list[tuple[float, str, float]] = []

    def on_event(event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append((time.time(), kw.get("fun_name", "?"), secs))

    jax.monitoring.register_event_duration_secs_listener(on_event)
    return seen


def device_record() -> dict:
    import jax

    devs = jax.devices()
    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs
    ]
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": int(max(peaks)),
    }


def compile_counters(compiles, window_start: float, window_end: float) -> dict:
    inside = [c for c in compiles if window_start <= c[0] <= window_end]
    return {
        "compile_s": sum(c[2] for c in compiles if c[0] < window_start),
        "compiles_in_window": len(inside),
        "compiled_in_window": sorted({c[1] for c in inside}),
    }


def reference_check(params, model: dict, cfg, mesh, attn_fn, seed: int,
                    tokens_per_row: int = 512) -> dict:
    """Largest absolute difference, over a seeded sample, between the
    logits of ``models.forward`` as the step runs it (flash attention,
    bf16 at use) and those of the plain float32 reference on the same
    weights. One row of 512 tokens per chip, so that the batch divides
    the mesh."""
    from functools import partial

    import jax
    import numpy as np

    from benchmarks import reference
    from ray_tpu.models.llama import forward
    from ray_tpu.parallel.sharding import tree_shardings, use_mesh

    rows = mesh.size
    rng = np.random.default_rng(seed + 7)
    tokens = rng.integers(
        0, cfg.vocab_size, (rows, tokens_per_row), dtype=np.int32
    )
    if mesh.size > 1:
        tokens = jax.device_put(tokens, tree_shardings(mesh, ("batch", None)))

    def system(p, t):
        with use_mesh(mesh):
            return forward(p, t, cfg, attn_fn=attn_fn)

    got = jax.jit(system)(params, tokens)
    want = jax.jit(partial(reference.forward, **reference.for_model(model)))(
        params, tokens
    )
    diff = jax.numpy.abs(got - want).max()
    scale = jax.numpy.abs(want).max()
    return {
        "logit_max_abs_err": float(diff),
        "logit_scale": float(scale),
        "finite": bool(jax.numpy.isfinite(got).all()),
        "tokens": int(rows * tokens_per_row),
    }


def loop(config: dict) -> None:
    first_line_at = time.time()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import modelcfg
    from ray_tpu import train
    from ray_tpu.parallel import make_mesh
    from ray_tpu.parallel.sharding import tree_shardings
    from ray_tpu.train.step import (
        TrainState,
        jit_train_step,
        make_optimizer,
        state_logical_axes,
    )
    from ray_tpu.models.llama import init_params

    compiles = watch_compiles()
    conf, traffic = config["config"], config["traffic"]
    seed, seconds = config["seed"], config["seconds"]
    tr = conf["train"]
    cfg = modelcfg.llama_config(
        conf, attn_impl=tr["attn_impl"], remat=tr["remat"],
        max_seq=traffic["seq"],
    )
    opt = make_optimizer(
        total_steps=tr["optimizer"]["total_steps"],
        mu_dtype=jnp.dtype(tr["optimizer"]["mu_dtype"]),
    )
    devices = jax.devices()[: conf["chips"]]
    mesh = make_mesh(tr["mesh"], devices=devices)
    step = jit_train_step(cfg, opt, mesh)
    batch, seq = traffic["batch_per_chip"] * mesh.size, traffic["seq"]

    def make_state(key):
        params = init_params(key, cfg)
        return TrainState(jnp.zeros((), jnp.int32), params, opt.init(params))

    shardings = None
    batch_sharding = None
    if mesh.size > 1:
        shardings = tree_shardings(mesh, state_logical_axes(cfg, opt))
        batch_sharding = tree_shardings(mesh, ("batch", None))
    # Seeds are larger than int32: fold the high bits in.
    key = jax.random.fold_in(jax.random.key(seed % (2**31)), seed >> 31)
    state = jax.jit(make_state, out_shardings=shardings)(key)

    rng = np.random.default_rng(seed)

    def make_batch():
        tokens = rng.integers(0, cfg.vocab_size, (batch, seq + 1), dtype=np.int32)
        if batch_sharding is None:
            return {"tokens": jax.device_put(tokens)}
        return {"tokens": jax.device_put(tokens, batch_sharding)}

    nxt = make_batch()
    compiled = step.lower(state, nxt).compile()
    program_peak = compiled.memory_analysis().peak_memory_in_bytes
    kernel = "tpu_custom_call" in compiled.as_text()

    attn_fn = None
    if tr["attn_impl"] == "flash":
        from ray_tpu.ops.pallas.flash_attention import make_flash_attention

        attn_fn = make_flash_attention(mesh)
    check = reference_check(state.params, conf, cfg, mesh, attn_fn, seed)

    warm_losses = []
    for _ in range(2):
        cur, nxt = nxt, make_batch()
        state, metrics = compiled(state, cur)
        jax.block_until_ready((state, metrics))
        warm_losses.append(float(metrics["loss"]))

    trace = config.get("trace")
    step_s, losses = [], []
    tracing, traced = False, None
    window_start = time.time()
    t_open = time.perf_counter()
    while True:
        now = time.perf_counter() - t_open
        if trace and not tracing and traced is None and now >= trace["start_s"]:
            jax.profiler.start_trace(trace["dir"])
            tracing, trace_from = True, time.time()
        t0 = time.perf_counter()
        cur = nxt
        state, metrics = compiled(state, cur)
        nxt = make_batch()  # the host's work, while the device runs
        jax.block_until_ready((state, metrics))
        t1 = time.perf_counter()
        step_s.append(t1 - t0)
        losses.append(metrics["loss"])
        if tracing and t1 - t_open >= trace["start_s"] + trace["seconds"]:
            jax.profiler.stop_trace()
            tracing, traced = False, (trace_from, time.time())
        if t1 - t_open >= seconds:
            break
    window_s = time.perf_counter() - t_open
    window_end = time.time()
    if tracing:
        jax.profiler.stop_trace()
        traced = (trace_from, time.time())
    losses = [float(x) for x in losses]

    train.report({
        "first_line_at": first_line_at,
        "pid": os.getpid(),
        "window_start_at": window_start,
        "window_end_at": window_end,
        "window_s": window_s,
        "steps": len(step_s),
        "tokens_per_step": batch * seq,
        "chips": mesh.size,
        "step_s": step_s,
        "first_loss": warm_losses[0],
        "last_loss": losses[-1],
        "losses_finite": all(math.isfinite(x) for x in warm_losses + losses),
        "reference_check": check,
        "tpu_custom_call": kernel,
        "program_peak_bytes": int(program_peak),
        "traced": traced,
        **compile_counters(compiles, window_start, window_end),
        "device": device_record(),
    })
