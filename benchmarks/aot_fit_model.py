#!/usr/bin/env python3
"""``aot_fit.py`` for the configurations of ``runners/train_model.py``:
does the step program of a model named under ``model`` fit the chip?
Compiled here, for a described v5e, with no chip attached;
``memory_analysis()`` gives the bytes the program needs. Used once per
configuration to pick ``num_hidden_layers``; what it printed is recorded
in the configuration file under ``fit``.

    JAX_PLATFORMS=cpu python3 benchmarks/aot_fit_model.py --config olmoe-train1 --traffic steady-4k --layers 2 3

A compile that passes is not a chip run and gives no time. ``--hlo
<file>`` also writes the last compiled program's text, to read what the
compiler made of an operation.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path.insert(0, os.path.dirname(HERE))

USABLE_BYTES = 15.75 * 2**30  # what a v5e chip offers a program (PERF.md)


def fit_train(conf, traffic, devices):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmarks import model_loop
    from ray_tpu.parallel import make_mesh
    from ray_tpu.parallel.sharding import tree_shardings
    from ray_tpu.train.step import (
        TrainState, jit_train_step, make_optimizer, state_logical_axes,
    )

    tr = conf["train"]
    model = model_loop.model_module(conf)
    cfg = model_loop.program_config(conf, traffic)
    opt = make_optimizer(total_steps=tr["optimizer"]["total_steps"],
                         mu_dtype=jnp.dtype(tr["optimizer"]["mu_dtype"]))
    mesh = make_mesh(tr["mesh"], devices=devices[: conf["chips"]])
    step = jit_train_step(cfg, opt, mesh)

    def make_state(key):
        params = model.init(key, cfg)
        return TrainState(jnp.zeros((), jnp.int32), params, opt.init(params))

    abstract = jax.eval_shape(make_state, jax.random.key(0))
    batch = traffic["batch_per_chip"] * mesh.size
    if mesh.size > 1:
        state_sh = tree_shardings(mesh, state_logical_axes(cfg, opt))
        tokens_sh = tree_shardings(mesh, ("batch", None))
    else:
        tokens_sh = SingleDeviceSharding(devices[0])
        state_sh = jax.tree.map(lambda _: tokens_sh, abstract)
    state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        abstract, state_sh,
    )
    tokens = jax.ShapeDtypeStruct((batch, traffic["seq"] + 1), jnp.int32,
                                  sharding=tokens_sh)
    return step.lower(state, {"tokens": tokens}).compile()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--layers", type=int, nargs="+", required=True)
    ap.add_argument("--hlo", help="write the last program's text here")
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
    traffic = json.load(open(os.path.join(HERE, "traffic", f"{args.traffic}.json")))
    for n in args.layers:
        conf["num_hidden_layers"] = n
        try:
            compiled = fit_train(conf, traffic, devices)
        except Exception as e:  # noqa: BLE001 - the compiler's refusal is the answer
            print(json.dumps({"config": args.config, "traffic": args.traffic,
                              "layers": n, "refused": str(e)[:400]}))
            continue
        m = compiled.memory_analysis()
        text = compiled.as_text()
        print(json.dumps({
            "config": args.config, "traffic": args.traffic, "layers": n,
            "program": "train_step",
            "peak_bytes": m.peak_memory_in_bytes,
            "argument_bytes": m.argument_size_in_bytes,
            "temp_bytes": m.temp_size_in_bytes,
            "fits": m.peak_memory_in_bytes <= USABLE_BYTES,
            "tpu_custom_call": "tpu_custom_call" in text,
        }), flush=True)
        if args.hlo:
            with open(args.hlo, "w") as f:
                f.write(text)


if __name__ == "__main__":
    main()
