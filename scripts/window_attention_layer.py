"""One window layer's prefill attention alone, on the chip: the band
kernel (`ops/pallas/window_attention.py`) at several block sizes, the
dense masked scores it replaces on a program without it, and what the
context's-pages kernel (`ops/pallas/prefill_attention.py`, every key up
to the diagonal) would take for the same chunk at several starts.

    chiprun -- python scripts/window_attention_layer.py

Laguna-S-2.1's window layer: 2,048 queries of 72 heads over 8 KV heads
of 128, a window of 512, bf16. Prints a JSON line a variant: ms a call
and the share of the bf16 peak by the pairs the band needs (2,048 x 512
x 72 heads x 4 x 128 operations). PERF.md section 6, PR 55, has the
table this made.
"""

import json
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, ".")

from ray_tpu.ops.pallas.prefill_attention import prefill_attention  # noqa: E402
from ray_tpu.ops.pallas.window_attention import (  # noqa: E402
    window_attention,
    window_attention_dense,
)

C, W, H, HKV, DH, PAGE = 2048, 512, 72, 8, 128, 64
PEAK = 197e12
NEEDED = C * W * H * 4 * DH


def timed(fn, *args, reps=20):
    out = fn(*args)
    jax.block_until_ready(out)
    began = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - began) / reps * 1e3, out


def main() -> None:
    keys = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(keys[0], (C, H, DH), jnp.bfloat16)
    k = jax.random.normal(keys[1], (HKV, W + C, DH), jnp.bfloat16)
    v = jax.random.normal(keys[2], (HKV, W + C, DH), jnp.bfloat16)
    start = jnp.int32(8192)
    dense = jax.jit(lambda q, k, v, s: window_attention_dense(q, k, v, s, window=W))
    ms, want = timed(dense, q, k, v, start, reps=5)
    print(json.dumps({"variant": "dense scores [8, 9, 2048, 2560]", "ms": ms,
                      "peak_pct": 100 * NEEDED / PEAK / (ms / 1e3)}), flush=True)
    for bq, bk in ((128, 128), (256, 128), (256, 256), (512, 128), (512, 256),
                   (512, 512), (1024, 256), (1024, 512)):
        try:
            ms, got = timed(
                lambda q, k, v, s: window_attention(
                    q, k, v, s, window=W, block_q=bq, block_kv=bk
                ), q, k, v, start,
            )
        except Exception as e:  # noqa: BLE001 (a tile the compiler refuses)
            print(json.dumps({"variant": f"band {bq} x {bk}",
                              "refused": str(e)[:200]}), flush=True)
            continue
        err = float(jnp.abs(got.astype(jnp.float32)
                            - want.astype(jnp.float32)).max())
        print(json.dumps({"variant": f"band {bq} x {bk}", "ms": ms,
                          "peak_pct": 100 * NEEDED / PEAK / (ms / 1e3),
                          "computed_over_needed": (bq + W) / W,
                          "max_abs_err_vs_dense": err}), flush=True)
    # The same chunk as a layer without a window would attend it.
    for at in (0, 6144, 14336):
        pages = (at + C) // PAGE
        kp = jax.random.normal(keys[1], (pages, HKV, PAGE, DH), jnp.bfloat16)
        ms, _ = timed(prefill_attention, q, kp, kp, jnp.int32(at))
        print(json.dumps({"variant": f"every key up to the diagonal, start {at}",
                          "ms": ms}), flush=True)


if __name__ == "__main__":
    main()
