#!/usr/bin/env python3
"""Does a configuration's program fit the chip? Compiled here, for a
described v5e, with no chip attached (on-chip-measurement guide,
section 2); ``memory_analysis()`` gives the bytes the program needs on
each device. Used once per configuration to pick ``num_hidden_layers``;
what it printed is recorded in the configuration file under ``fit``.

    JAX_PLATFORMS=cpu python3 benchmarks/aot_fit.py --config mistral7b-train1 --traffic steady-4k --layers 3 4

A compile that passes is not a chip run and gives no time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from functools import partial

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path.insert(0, os.path.dirname(HERE))

USABLE_BYTES = 15.75 * 2**30  # what a v5e chip offers a program (PERF.md)


def _shapes(tree, sharding):
    import jax

    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, sharding,
    )


def fit_train(conf, traffic, devices):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmarks import modelcfg
    from ray_tpu.models.llama import init_params
    from ray_tpu.parallel import make_mesh
    from ray_tpu.parallel.sharding import tree_shardings
    from ray_tpu.train.step import (
        TrainState, jit_train_step, make_optimizer, state_logical_axes,
    )

    tr = conf["train"]
    cfg = modelcfg.llama_config(conf, attn_impl=tr["attn_impl"],
                                remat=tr["remat"], max_seq=traffic["seq"])
    opt = make_optimizer(total_steps=tr["optimizer"]["total_steps"],
                         mu_dtype=jnp.dtype(tr["optimizer"]["mu_dtype"]))
    mesh = make_mesh(tr["mesh"], devices=devices[: conf["chips"]])
    step = jit_train_step(cfg, opt, mesh)

    def make_state(key):
        params = init_params(key, cfg)
        return TrainState(jnp.zeros((), jnp.int32), params, opt.init(params))

    abstract = jax.eval_shape(make_state, jax.random.key(0))
    batch = traffic["batch_per_chip"] * mesh.size
    tokens = jax.ShapeDtypeStruct((batch, traffic["seq"] + 1), jnp.int32)
    if mesh.size > 1:
        state_sh = tree_shardings(mesh, state_logical_axes(cfg, opt))
        tokens_sh = tree_shardings(mesh, ("batch", None))
    else:
        tokens_sh = SingleDeviceSharding(devices[0])
        state_sh = jax.tree.map(lambda _: tokens_sh, abstract)
    state = _shapes(abstract, state_sh)
    tokens = jax.ShapeDtypeStruct(tokens.shape, tokens.dtype, sharding=tokens_sh)
    compiled = step.lower(state, {"tokens": tokens}).compile()
    return {"train_step": compiled}


def fit_serve(conf, traffic, devices):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmarks import modelcfg
    from ray_tpu.llm import paged_kv
    from ray_tpu.models.llama import init_params

    eng = conf["engine"]
    cfg = modelcfg.llama_config(conf, max_seq=eng["max_seq"])
    one = SingleDeviceSharding(devices[0])

    def on(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), tree
        )

    params = on(jax.eval_shape(partial(init_params, cfg=cfg), jax.random.key(0)))
    page = eng["page_size"]
    pool_shape = (cfg.n_layers, eng["num_pages"] + 1, cfg.n_kv_heads, page,
                  cfg.head_dim)
    pool = on({"k": jax.ShapeDtypeStruct(pool_shape, cfg.dtype),
               "v": jax.ShapeDtypeStruct(pool_shape, cfg.dtype)})
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)  # noqa: E731
    out = {}
    b = eng["max_batch"]
    max_pages = -(-eng["max_seq"] // page)
    key = on(jax.eval_shape(lambda: jax.random.key(0)))
    out["decode"] = paged_kv.paged_verify.lower(
        params, i32(b, 1), pool, i32(b, max_pages), i32(b),
        jax.ShapeDtypeStruct((b,), jnp.float32, sharding=one), key,
        cfg=cfg, use_kernel=True, stochastic=False,
    ).compile()
    chunk = eng.get("prefill_chunk")
    for pad in traffic["fit_prefill_buckets"]:
        n_pages = pad // page
        if chunk is None or pad <= chunk:
            out[f"prefill_{pad}"] = paged_kv.paged_prefill.lower(
                params, i32(1, pad), pool, i32(n_pages), cfg=cfg,
                n_write_pages=n_pages,
            ).compile()
        else:
            out[f"prefill_chunk_{chunk}_of_{pad}"] = (
                paged_kv.paged_prefill_chunk.lower(
                    params, i32(1, chunk), pool, i32(n_pages), i32(),
                    cfg=cfg, n_write_pages=n_pages, chunk_pages=chunk // page,
                ).compile()
            )
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True, nargs="+")
    ap.add_argument("--layers", type=int, nargs="+", required=True)
    ap.add_argument("--set", nargs="*", default=[],
                    help="engine.key=value overrides, to try a size")
    args = ap.parse_args()

    import jax
    from jax.experimental import topologies

    from ray_tpu._private import chip

    # Code that asks which platform it runs on must take its TPU branch
    # (kernels compiled, not interpreted): steered here, in the script.
    chip.platform = lambda: "tpu"
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    devices = list(topo.devices)

    conf = json.load(open(os.path.join(HERE, "configs", f"{args.config}.json")))
    for item in args.set:
        path, value = item.split("=")
        group, key = path.split(".")
        conf[group][key] = json.loads(value)
    for name in args.traffic:
        traffic = json.load(open(os.path.join(HERE, "traffic", f"{name}.json")))
        for n in args.layers:
            conf["num_hidden_layers"] = n
            fit = {"train": fit_train, "serve": fit_serve}[conf["runner"]]
            try:
                programs = fit(conf, traffic, devices)
            except Exception as e:  # noqa: BLE001 - the compiler's refusal is the answer
                print(json.dumps({"config": args.config, "traffic": name,
                                  "layers": n, "refused": str(e)[:400]}))
                continue
            for prog, compiled in programs.items():
                m = compiled.memory_analysis()
                print(json.dumps({
                    "config": args.config, "traffic": name, "layers": n,
                    "program": prog,
                    "peak_bytes": m.peak_memory_in_bytes,
                    "argument_bytes": m.argument_size_in_bytes,
                    "temp_bytes": m.temp_size_in_bytes,
                    "fits": m.peak_memory_in_bytes <= USABLE_BYTES,
                    "tpu_custom_call": "tpu_custom_call" in compiled.as_text(),
                }), flush=True)


if __name__ == "__main__":
    main()
