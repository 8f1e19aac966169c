"""One sparse-expert layer's grouped matmuls alone, on the chip: the
compiler's `jax.lax.ragged_dot` a 1,024-row block of the sorted order at
a time at (256, 1,024) tiles (what `models/moe.py
_experts_on_pairs_here` ran on a TPU up to PR 55) against
`ops/pallas/grouped_rows.py` at several row tiles, row blocks and weight
budgets, and the whole layer through `moe_ffn` as the tree it runs in
has it.

    chiprun -- python scripts/grouped_rows_layer.py [family ...]

A 2,048-token chunk at the four served families' widths, experts held
and top-k; a random float32 router makes the routes, as in the cells.
Prints a JSON line a (family, variant): ms a layer-chunk for the three
matmuls (or the layer), the live rows' TFLOP/s and the GB/s of every
live expert's weights read once. PERF.md section 6, PR 56, has the
table this made.
"""

import json
import sys
import time

import jax
import jax.numpy as jnp
from jax.experimental.xla_metadata import set_xla_metadata

sys.path.insert(0, ".")

from ray_tpu.models import moe  # noqa: E402

try:
    from ray_tpu.ops.pallas import grouped_rows as kernel  # noqa: E402
except ImportError:  # a tree from before PR 56: the layer alone
    kernel = None

TOKENS = 2048
# d_model, expert width, experts, held, top-k
FAMILIES = {
    "granite": (4096, 768, 72, 36, 10),
    "qwen3next": (2048, 512, 512, 256, 10),
    "laguna": (3072, 1024, 256, 128, 10),
    "pangu": (7680, 2048, 256, 16, 8),
}
# (row tile or None for the rule, row block, weight budget MiB, buffers)
KERNEL_VARIANTS = [
    (None, 512, 48, 2), (128, 512, 48, 2), (64, 512, 48, 2),
    (256, 512, 48, 2), (None, 256, 48, 2), (None, 1024, 48, 2),
    (None, 512, 24, 2), (None, 512, 72, 3), (128, 512, 72, 3),
]
BLOCK = 1024


def layer(d, f, experts, held, top_k, seed=0):
    cfg = moe.MoEConfig(
        vocab_size=512, d_model=d, n_layers=1, n_heads=4, n_kv_heads=4,
        d_ff=f, dtype=jnp.bfloat16, num_experts=experts, top_k=top_k,
        norm_topk_prob=True, experts_held=(0, held), dense_expert_rows=256,
    )
    keys = jax.random.split(jax.random.key(seed), 5)
    w = lambda key, shape, fan: (  # noqa: E731
        jax.random.normal(key, shape, jnp.float32) * fan**-0.5
    )
    p = {
        "router": w(keys[0], (d, experts), d),
        "w_gate": w(keys[1], (held, d, f), d).astype(jnp.bfloat16),
        "w_up": w(keys[2], (held, d, f), d).astype(jnp.bfloat16),
        "w_down": w(keys[3], (held, f, d), f).astype(jnp.bfloat16),
    }
    x = jax.random.normal(keys[4], (1, TOKENS, d), jnp.bfloat16)
    return cfg, p, x


def ragged_blocks(staged, p, load):
    """The parent's three matmuls: a loop over the blocks that hold a
    row, `ragged_dot` with the (256, 1,024) tiles it was handed."""
    ends = jnp.cumsum(load)
    blocks = (ends[-1] + BLOCK - 1) // BLOCK

    def grouped(a, w, sizes):
        columns = 512 if w.shape[2] % 512 == 0 else 256
        with set_xla_metadata(ragged_dot_tiling=f"256,1024,{columns}"):
            return jax.lax.ragged_dot(a, w, sizes)

    def step(i, out):
        lo = i * BLOCK
        sizes = (
            jnp.clip(ends - lo, 0, BLOCK)
            - jnp.clip(ends - load - lo, 0, BLOCK)
        )
        rows = staged[i]
        act = jax.nn.silu(grouped(rows, p["w_gate"], sizes)) * grouped(
            rows, p["w_up"], sizes
        )
        return jax.lax.dynamic_update_index_in_dim(
            out, grouped(act, p["w_down"], sizes), i, 0
        )

    return jax.lax.fori_loop(0, blocks, step, jax.lax.empty(
        staged.shape, staged.dtype
    ))


def kernel_calls(mean, staged, p, load):
    d = staged.shape[-1]
    hidden = kernel.grouped_rows(
        staged.reshape(-1, d), [p["w_gate"], p["w_up"]], load, "swiglu", mean
    )
    return kernel.grouped_rows(
        hidden, [p["w_down"]], load, None, mean
    ).reshape(staged.shape)


def timed(fn, *args, reps=20):
    out = fn(*args)
    jax.block_until_ready(out)
    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / reps, out


def main(families):
    for name in families:
        d, f, experts, held, top_k = FAMILIES[name]
        cfg, p, x = layer(d, f, experts, held, top_k)
        mean = TOKENS * top_k // experts

        def report(variant, seconds, load, **more):
            m = int(load.sum())
            live = int((load > 0).sum())
            print(json.dumps({
                "family": name, "variant": variant,
                "ms": round(seconds * 1e3, 3), "rows": m,
                "live_experts": live, "largest_group": int(load.max()),
                "tflops": round(m * 6 * d * f / seconds / 1e12, 1),
                "weights_gbps": round(live * 6 * d * f / seconds / 1e9, 1),
                **more,
            }), flush=True)

        jax.clear_caches()
        whole = jax.jit(lambda x, p: moe.moe_ffn(x, p, cfg))
        seconds, (_, aux) = timed(whole, x, p)
        load = aux["expert_load"]
        report("layer (moe_ffn as this tree has it)", seconds, load,
               sorted_rows=aux["sorted_rows"].tolist())

        staged = jax.random.normal(
            jax.random.key(7), (TOKENS * top_k // BLOCK, BLOCK, d),
            jnp.bfloat16,
        )
        jax.clear_caches()
        seconds, want = timed(jax.jit(ragged_blocks), staged, p, load)
        report("ragged_dot, 1,024-row blocks, tiles (256, 1024)", seconds,
               load)
        if kernel is None:
            continue
        m = int(load.sum())
        defaults = (
            kernel._ROW_BLOCK, kernel._WEIGHT_VMEM_BYTES, kernel._BUFFERS,
            kernel._MAX_TILE_ROWS,
        )
        for tile, block, budget, buffers in KERNEL_VARIANTS:
            jax.clear_caches()
            kernel._ROW_BLOCK = block
            kernel._WEIGHT_VMEM_BYTES = budget * 1024 * 1024
            kernel._BUFFERS = buffers
            rows = mean
            if tile is not None:
                kernel._MAX_TILE_ROWS = tile
                rows = tile
            variant = (
                f"kernel tile {kernel._tile_rows(rows, 16, block)} "
                f"block {block} budget {budget} buffers {buffers}"
            )
            try:
                seconds, got = timed(
                    jax.jit(lambda s, p, load: kernel_calls(rows, s, p, load)),
                    staged, p, load,
                )
            except Exception as e:  # noqa: BLE001: a variant the chip refuses
                report(variant, float("nan"), load, error=str(e)[:300])
                continue
            finally:
                (kernel._ROW_BLOCK, kernel._WEIGHT_VMEM_BYTES,
                 kernel._BUFFERS, kernel._MAX_TILE_ROWS) = defaults
            err = jnp.abs(
                got.reshape(-1, d)[:m].astype(jnp.float32)
                - want.reshape(-1, d)[:m].astype(jnp.float32)
            ).max()
            report(variant, seconds, load, max_abs_diff=float(err))


if __name__ == "__main__":
    main(sys.argv[1:] or list(FAMILIES))
