#!/usr/bin/env python3
"""Do a ``serve_model`` configuration's programs fit the chip? Its
prefill buckets, its chunk and its decode program compiled here for a
described v5e with no chip attached, at the file's sizes, weights, page
pool and per-slot state included (``aot_fit.py`` does the same for the
Llama-shaped serving programs; it builds them from
``modelcfg.llama_config``). What this prints is recorded in the
configuration file under ``fit``.

    JAX_PLATFORMS=cpu python3 benchmarks/aot_fit_serve_model.py --config nemotron3nano-serve1 --traffic reason-closed

A compile that passes is not a chip run and gives no time.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path.insert(0, os.path.dirname(HERE))

USABLE_BYTES = 15.75 * 2**30  # what a v5e chip offers a program (PERF.md)


def lowered_programs(conf: dict, traffic: dict, device, use_kernel=True):
    """name -> the lowered program, as `LLMEngine` would call it for this
    configuration and mix: one prefill per bucket up to the chunk, the
    chunk program for the buckets above it, the decode program."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.llm import hybrid_kv
    from ray_tpu.models.nemotron_h import init_params

    model = importlib.import_module(f"benchmarks.models.{conf['model']}")
    eng = conf["engine"]
    cfg = model.config(conf, max_seq=eng["max_seq"])
    one = SingleDeviceSharding(device)

    def on(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), tree
        )

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)

    params = on(jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0)))
    page, b = eng["page_size"], eng["max_batch"]
    cache = on(jax.eval_shape(
        lambda: hybrid_kv.init_hybrid_cache(cfg, eng["num_pages"] + 1, page, b)
    ))
    chunk = eng.get("prefill_chunk")
    out = {}
    for pad in traffic["fit_prefill_buckets"]:
        n_pages = pad // page
        whole = chunk is None or pad <= chunk
        name = f"prefill_{pad}" if whole else f"prefill_chunk_{chunk}_of_{pad}"
        size = pad if whole else chunk
        out[name] = hybrid_kv.prefill_program(cfg, n_pages, size // page).lower(
            params, i32(1, size), cache, i32(n_pages), i32(), i32(), i32()
        )
    key = on(jax.eval_shape(lambda: jax.random.key(0)))
    out["decode"] = hybrid_kv.hybrid_decode.lower(
        params, i32(b, 1), cache, i32(b, -(-eng["max_seq"] // page)), i32(b),
        jax.ShapeDtypeStruct((b,), jnp.bool_, sharding=one),
        jax.ShapeDtypeStruct((b,), jnp.float32, sharding=one), key,
        cfg=cfg, use_kernel=use_kernel,
    )
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--text-dir", help="write each program's text there")
    args = ap.parse_args()

    import jax
    from jax.experimental import topologies

    from ray_tpu._private import chip

    # Code that asks which platform it runs on must take its TPU branch
    # (kernels compiled, not interpreted): steered here, in the script.
    chip.platform = lambda: "tpu"
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    conf = json.load(open(os.path.join(HERE, "configs", f"{args.config}.json")))
    traffic = json.load(open(os.path.join(HERE, "traffic", f"{args.traffic}.json")))
    for name, lowered in lowered_programs(conf, traffic, topo.devices[0]).items():
        try:
            compiled = lowered.compile()
        except Exception as e:  # noqa: BLE001 - the compiler's refusal is the answer
            print(json.dumps({"config": args.config, "program": name,
                              "refused": str(e)[:400]}))
            continue
        text = compiled.as_text()
        if args.text_dir:
            os.makedirs(args.text_dir, exist_ok=True)
            with open(os.path.join(args.text_dir, f"{name}.txt"), "w") as f:
                f.write(text)
        m = compiled.memory_analysis()
        print(json.dumps({
            "config": args.config, "traffic": args.traffic, "program": name,
            "peak_bytes": m.peak_memory_in_bytes,
            "argument_bytes": m.argument_size_in_bytes,
            "temp_bytes": m.temp_size_in_bytes,
            "fits": m.peak_memory_in_bytes <= USABLE_BYTES,
            "tpu_custom_call": "tpu_custom_call" in text,
        }), flush=True)


if __name__ == "__main__":
    main()
