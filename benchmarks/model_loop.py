"""The train loop for models that are not Llama-shaped: what
``JaxTrainer`` runs in the worker that holds the chips for
``runners/train_model.py``.

``benchmarks/train_loop.py`` with the model taken from the module the
configuration names under ``model`` (``benchmarks/models/<model>.py``:
config from published keys, initialiser, forward, reference check), and
with the step's own counters kept: every number in the step's
``metrics`` besides the loss is reported per step, so that a counter the
model adds (``moe_pairs``) is checked in every step of the window.
"""

from __future__ import annotations

import importlib
import math
import os
import time

from benchmarks.train_loop import (
    compile_counters,
    device_record,
    watch_compiles,
)


def model_module(conf: dict):
    return importlib.import_module(f"benchmarks.models.{conf['model']}")


def program_config(conf: dict, traffic: dict):
    """The program's config for a configuration file under a traffic
    mix: the model's module reads the published keys (and what of the
    ``train`` group is the model's own), the fields of the program's own
    are passed."""
    tr = conf["train"]
    return model_module(conf).config(
        conf, attn_impl=tr["attn_impl"], remat=tr["remat"],
        max_seq=traffic["seq"],
    )


def loop(config: dict) -> None:
    first_line_at = time.time()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu import train
    from ray_tpu.parallel import make_mesh
    from ray_tpu.parallel.sharding import tree_shardings
    from ray_tpu.train.step import (
        TrainState,
        jit_train_step,
        make_optimizer,
        state_logical_axes,
    )

    compiles = watch_compiles()
    conf, traffic = config["config"], config["traffic"]
    seed, seconds = config["seed"], config["seconds"]
    tr = conf["train"]
    model = model_module(conf)
    cfg = program_config(conf, traffic)
    opt = make_optimizer(
        total_steps=tr["optimizer"]["total_steps"],
        mu_dtype=jnp.dtype(tr["optimizer"]["mu_dtype"]),
    )
    devices = jax.devices()[: conf["chips"]]
    mesh = make_mesh(tr["mesh"], devices=devices)
    step = jit_train_step(cfg, opt, mesh)
    batch, seq = traffic["batch_per_chip"] * mesh.size, traffic["seq"]

    def make_state(key):
        params = model.init(key, cfg)
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
    check = model.reference_check(state.params, conf, cfg, mesh, attn_fn, seed)

    warm_losses = []
    for _ in range(2):
        cur, nxt = nxt, make_batch()
        state, metrics = compiled(state, cur)
        jax.block_until_ready((state, metrics))
        warm_losses.append(float(metrics["loss"]))

    trace = config.get("trace")
    program_text = None
    if trace:
        # The trace names instructions without their metadata; the
        # program's text has each one's named scope (scope_time_share).
        program_text = os.path.join(trace["dir"], "step_program.txt")
        with open(program_text, "w") as f:
            f.write(compiled.as_text())
    step_s, step_metrics = [], []
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
        step_metrics.append(metrics)
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
    # Each of the step's numbers, per step of the window.
    per_step = {
        name: [float(m[name]) for m in step_metrics]
        for name in step_metrics[0]
    }
    losses = per_step.pop("loss")

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
        "step_metrics": per_step,
        "reference_check": check,
        "tpu_custom_call": kernel,
        "program_peak_bytes": int(program_peak),
        "program_text": program_text,
        "traced": traced,
        **compile_counters(compiles, window_start, window_end),
        "device": device_record(),
    })
