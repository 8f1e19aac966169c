"""Motif-3-Beta's layers alone, on the chip, at the cell's widths
(motif3beta-serve1: 80 differential heads over 16 latent KV groups, a
window of 128, four residual streams, 48 of 384 PolyNorm experts of
1,280), the published layers 2-3 (a window layer and the full layer,
each with experts: letters R, A, E), a 4,096-token prompt in two chunks
and four decode steps: which part of a program is how far from the plain
reference, and what it takes.

    chiprun -- python scripts/motif_layer.py [seed [configuration file]]

Prints a JSON line a variant of the chunk program: the attention by the
two kernels or by XLA's dense scores (2.7 GB of them over 4,096 keys at
80 heads), the experts by the kernels or by XLA's forms (`moe.chip` told
"cpu"), each against `benchmarks/reference_motif.py` with the system's
routes forced: the largest |logit| difference at the last position, the
cells of the pool and of the ring against the reference's, the share of
tokens routed otherwise, and the second chunk's time. Then the decode
program the same way, and the kernels alone at the cell's shapes: the
grouped prefill kernel at three contexts, the band, the paged kernel at
80 rows, both expert kernels at ``polynorm``; ms a call.
"""

import json
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")

from benchmarks import reference_motif as reference  # noqa: E402
from benchmarks.models import motif as family  # noqa: E402
from ray_tpu._private import chip  # noqa: E402
from ray_tpu.llm import hybrid_kv  # noqa: E402
from ray_tpu.models import moe  # noqa: E402
from ray_tpu.models.motif import init_params  # noqa: E402
from ray_tpu.ops.pallas import expert_rows, grouped_rows  # noqa: E402
from ray_tpu.ops.pallas.latent_attention import (  # noqa: E402
    latent_paged_attention,
    latent_prefill_attention,
)
from ray_tpu.ops.pallas.window_attention import window_attention  # noqa: E402

CHUNK, PAGE, DECODE, SLOTS = 2048, 64, 4, 16


def timed(fn, *args, repeat=5):
    jax.block_until_ready(fn(*args))
    began = time.perf_counter()
    for _ in range(repeat):
        out = jax.block_until_ready(fn(*args))
    return round(1e3 * (time.perf_counter() - began) / repeat, 3), out


def kernels_alone(key):
    """ms a call of each changed kernel at the cell's shapes."""
    def bf16(k, *shape):
        return jax.random.normal(jax.random.fold_in(key, k), shape).astype(
            jnp.bfloat16
        )

    heads, groups, d, f, held = 80, 16, 4096, 1280, 48
    scale = 192**-0.5
    for keys in (8192, 16384, 65536):
        args = (bf16(1, heads, CHUNK, 128), bf16(2, heads, CHUNK, 128),
                bf16(3, groups, keys, 128), bf16(4, keys, 128),
                bf16(5, groups, keys, 128))
        ms, _ = timed(
            lambda *a: latent_prefill_attention(
                *a, jnp.int32(keys - CHUNK), scale=scale
            ), *args,
        )
        pairs = CHUNK * (keys - CHUNK) + CHUNK * (CHUNK + 1) // 2
        print(json.dumps({
            "kernel": "latent_prefill_80_of_16", "keys": keys, "ms": ms,
            "peak_pct": round(100 * pairs * heads * 640 / 197e12 / (ms / 1e3), 1),
        }), flush=True)
    ms, _ = timed(
        lambda q, k, v: window_attention(
            q, k, v, jnp.int32(8192), window=128, scale=scale
        ),
        bf16(6, CHUNK, heads, 256), bf16(7, groups, 128 + CHUNK, 256),
        bf16(8, groups, 128 + CHUNK, 128),
    )
    print(json.dumps({
        "kernel": "band_192_over_128", "ms": ms,
        "peak_pct": round(100 * CHUNK * 128 * heads * 640 / 197e12 / (ms / 1e3), 1),
    }), flush=True)
    pages = 16 * 260 + 1
    tables = jnp.arange(1, 16 * 260 + 1, dtype=jnp.int32).reshape(16, 260)
    positions = jnp.full((16,), 16384, jnp.int32)
    ms, _ = timed(
        lambda q, pool: latent_paged_attention(
            q, pool, tables, positions, v_width=512, scale=scale
        ),
        bf16(9, 16, 1, heads, 640), bf16(10, pages, PAGE, 640),
    )
    print(json.dumps({
        "kernel": "latent_paged_80_rows", "live_tokens": 16 * 16385, "ms": ms,
        "hbm_pct": round(100 * 16 * 16448 * 1152 / 819e9 / (ms / 1e3), 1),
    }), flush=True)
    stacks = [bf16(11 + i, held, d, f) * d**-0.5 for i in range(2)]
    down = bf16(13, held, f, d) * f**-0.5
    poly = jnp.tile(jnp.asarray([[1 / 6, 1 / 6, 1 / 6, 0.1]]), (held, 1))
    x = bf16(14, 16, d)
    weight = jnp.zeros((16, held)).at[jnp.arange(16), jnp.arange(16)].set(1.0)
    ids = jnp.minimum(jnp.arange(held), 15).astype(jnp.int32)
    ms, _ = timed(
        lambda *a: expert_rows.experts_on_rows(
            *a, weight, ids, jnp.int32(16), poly=poly, eps=1e-5
        ), x, *stacks, down,
    )
    print(json.dumps({
        "kernel": "expert_rows_polynorm", "touched": 16, "ms": ms,
        "hbm_pct": round(100 * 16 * 3 * d * f * 2 / 819e9 / (ms / 1e3), 1),
    }), flush=True)
    rows = bf16(15, 3072, d)
    sizes = jnp.full((held,), 43, jnp.int32)
    ms, _ = timed(
        lambda r, *w: grouped_rows.grouped_rows(
            r, list(w), sizes, "polynorm", 42, poly=poly, eps=1e-5
        ), rows, *stacks,
    )
    print(json.dumps({
        "kernel": "grouped_rows_polynorm", "rows": 43 * held, "ms": ms,
        "hbm_pct": round(100 * held * 2 * d * f * 2 / 819e9 / (ms / 1e3), 1),
    }), flush=True)


def main():
    seed = int(sys.argv[1]) if len(sys.argv) > 1 else 65
    device = jax.devices()[0]
    print(json.dumps({"device": device.device_kind,
                      "platform": device.platform}), flush=True)
    path = (sys.argv[2] if len(sys.argv) > 2
            else "benchmarks/configs/motif3beta-serve1.json")
    with open(path) as f:
        conf = json.load(f)
    # Published layers 2-3: R E, A E.
    conf = {**conf, "num_hidden_layers": 2, "n_dense_first_layers": 0,
            "first_layer": 2}
    n_pages = 2 * CHUNK // PAGE
    cfg = family.config(conf, max_seq=2 * CHUNK + PAGE)
    sizes = reference.for_model(conf)
    params = init_params(jax.random.key(seed), cfg)
    rng = np.random.default_rng(seed)
    n = 2 * CHUNK - 7
    prompt = rng.integers(1, cfg.vocab_size, n)
    tokens = np.zeros(2 * CHUNK, np.int32)
    tokens[:n] = prompt
    pages = np.arange(1, n_pages + 1, dtype=np.int32)

    def against(logits, cache, routes, all_tokens, rows):
        want, record = reference.forward_with_record(
            params, jnp.asarray(all_tokens, jnp.int32),
            routes=jnp.asarray(routes), rows=rows, query_block=1024,
            block_fn=lambda kind, fn: jax.jit(fn), **sizes,
        )
        held = len(all_tokens)
        full, rings = family.held_cells(
            cache, list(pages) + [n_pages + 1], 0, held, cfg.latent_dim
        )
        cells = np.asarray(record["cells"])
        same = (np.sort(np.asarray(routes), -1)
                == np.sort(np.asarray(record["routes"]), -1)).all(-1)
        return {
            "logit_max_abs_err": float(
                np.abs(np.asarray(logits) - np.asarray(want)).max()
            ),
            "logit_scale": float(np.abs(np.asarray(want)).max()),
            "cell_rel_err": family._rel(full, cells[[1]]),
            "ring_rel_err": family._rel(
                rings, cells[[0], held - cfg.sliding_window:]
            ),
            "routed_otherwise": float(1.0 - same.mean()),
            "largest_slack": float(np.asarray(record["slack"]).max()),
        }

    real = moe.chip
    for attend, experts in (("kernel", "kernels"), ("xla", "kernels"),
                            ("kernel", "xla")):
        moe.chip = real if experts == "kernels" else types.SimpleNamespace(
            platform=lambda: "cpu"
        )
        hybrid_kv._prefill_program.cache_clear()
        program = hybrid_kv.prefill_program(
            cfg, n_pages, CHUNK // PAGE, attend == "kernel"
        )

        def run():
            cache = hybrid_kv.init_hybrid_cache(cfg, n_pages + 2, PAGE, SLOTS)
            routes = []
            for start in (0, CHUNK):
                began = time.perf_counter()
                logits, cache, record = jax.block_until_ready(program(
                    params, tokens[None, start: start + CHUNK], cache, pages,
                    np.int32(start), np.int32(0), np.int32(n),
                ))
                routes.append(np.asarray(record["routes"]))
            ms = 1e3 * (time.perf_counter() - began)
            return logits, cache, np.concatenate(routes, 1)[:, :n], ms

        run()
        logits, cache, routes, ms = run()
        out = against(logits[0], cache, routes, prompt, [n - 1])
        print(json.dumps({"program": "prefill_chunk_2048_of_4096",
                          "attention": attend, "experts": experts,
                          "second_chunk_ms": round(ms, 2), **out}), flush=True)
    moe.chip = real

    # The decode program over the last variant's cache, by the kernels:
    # one slot of 16 live.
    block_tables = np.full((SLOTS, n_pages + 1), -1, np.int32)
    block_tables[0, :n_pages] = pages
    block_tables[0, n_pages] = n_pages + 1
    active = np.zeros(SLOTS, bool)
    active[0] = True
    all_routes = [routes]
    generated, got = [int(np.argmax(np.asarray(logits[0, 0])))], []
    for step in range(DECODE):
        step_tokens = np.zeros((SLOTS, 1), np.int32)
        step_tokens[0, 0] = generated[-1]
        positions = np.zeros(SLOTS, np.int32)
        positions[0] = n + step
        _, step_logits, cache, record = hybrid_kv.hybrid_decode(
            params, step_tokens, cache, block_tables, positions, active,
            np.zeros(SLOTS, np.float32), jax.random.key(0), cfg=cfg,
            use_kernel=chip.platform() == "tpu",
        )
        got.append(np.asarray(step_logits[0]))
        all_routes.append(np.asarray(record["routes"])[:, :1])
        generated.append(int(np.argmax(got[-1])))
    out = against(
        np.stack(got), cache, np.concatenate(all_routes, 1),
        list(prompt) + generated[:-1], list(range(n, n + DECODE)),
    )
    print(json.dumps({"program": "decode", **out}), flush=True)
    kernels_alone(jax.random.key(seed + 1))


if __name__ == "__main__":
    main()
