"""On-chip LLM serving benchmarks: paged decode throughput at real
batch sizes and prefill-interleave stall latency.

Run on a TPU chip (NOT CI — CI runs the interpreted kernel):

    python -m ray_tpu._private.llm_perf [--steps 50] [--json]

Measures, on the `bench` model (~430M, GQA 8/4):

1. **decode@64**: steady-state decode tokens/s at batch 64 with mixed
   sequence lengths, Pallas paged-attention kernel vs the XLA gather
   path. The gather path's HBM traffic scales with B x window x
   n_heads; the kernel's with the true page footprint x n_kv_heads
   (ops/pallas/paged_attention.py) — this prints the realized ratio.
2. **prefill stall**: per-decode-step wall times for an 8-request
   decode batch while a ~4k-token prompt is admitted mid-stream, with
   and without chunked prefill. Without chunking the admission step
   stalls every decode for the prompt's whole dense pass; with
   ``prefill_chunk`` the p99 step time stays near the chunk cost.

Floors are asserted here (not in CI: these are chip numbers).

(reference frame: vLLM's paged attention + chunked prefill, bought by
ray.llm via engine_kwargs — python/ray/llm/_internal/serve/.)
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np


def _build_engine(use_kernel: bool, **kw):
    os.environ["RAY_TPU_PAGED_ATTN"] = "1" if use_kernel else "0"
    from ray_tpu.llm.engine import LLMEngine

    return LLMEngine(**kw)


def bench_attention_op_batch64(
    steps: int = 50, heads: "tuple[int, int]" = (8, 4),
    max_pages: int = 32, long_len: int = 2047, short_len: int = 256,
    long_every: int = 4,
) -> dict:
    """Op-level paged attention at batch 64, mixed true lengths —
    amortized loop timing (one sync per ``steps`` dispatches, so the
    host round trip does not swamp the op). ``heads`` =
    (n_heads, n_kv_heads): the bench
    model's (8, 4) and llama-8B's (32, 8) — the gather path's repeat
    factor n_heads/n_kv_heads is what the kernel's GQA blocking
    removes, so the speedup grows with it."""
    import time

    import jax
    import jax.numpy as jnp
    from functools import partial

    from ray_tpu.ops.pallas.paged_attention import paged_attention

    rng = np.random.default_rng(0)
    H, Hkv = heads
    B, K, Dh, P = 64, 1, 128, 64
    maxp = max_pages
    npages = min(B * maxp, 4096)
    q = jnp.asarray(rng.normal(size=(B, K, H, Dh)), jnp.bfloat16)
    kp = jnp.asarray(
        rng.normal(size=(npages, Hkv, P, Dh)), jnp.bfloat16
    )
    vp = jnp.asarray(
        rng.normal(size=(npages, Hkv, P, Dh)), jnp.bfloat16
    )
    lens = np.where(
        np.arange(B) % long_every == 0, long_len, short_len
    )
    tables = np.full((B, maxp), -1, np.int32)
    nxt = 1
    for bi in range(B):
        need = (lens[bi] + 1 + P - 1) // P
        tables[bi, :need] = np.arange(nxt, nxt + need) % npages
        nxt += need
    positions = jnp.asarray(lens, jnp.int32)
    tables_j = jnp.asarray(tables)

    kern = partial(paged_attention, n_kv_heads=Hkv)

    # The gather baseline is the REAL fallback body (one source of
    # truth in paged_kv): the benchmark measures the code path the
    # engine actually runs, not a private re-implementation.
    from ray_tpu.llm.paged_kv import _gather_page_attention
    from ray_tpu.models.llama import LlamaConfig

    # head_dim is d_model // n_heads: pin d_model so it comes out Dh.
    cfg = LlamaConfig(
        d_model=H * Dh, n_heads=H, n_kv_heads=Hkv, dtype=jnp.bfloat16
    )

    @jax.jit
    def gather_path(q, kp, vp, tables, positions):
        window = maxp * P
        pos2d = positions[:, None] + jnp.arange(K)[None, :]
        mask = jnp.arange(window)[None, None, :] > pos2d[:, :, None]
        return _gather_page_attention(
            q, kp, vp, jnp.maximum(tables, 0), mask, cfg
        )

    def timeit(f):
        # Warm with a SHORT LOOP, not one call: the first sustained
        # dispatch burst in a process pays ~15 ms of one-time overhead
        # that a single warm-up call does not absorb (measured — it
        # inflated whichever variant ran first by up to 6x).
        for _ in range(6):
            r = f(q, kp, vp, tables_j, positions)
        jax.block_until_ready(r)
        t0 = time.perf_counter()
        for _ in range(steps):
            r = f(q, kp, vp, tables_j, positions)
        jax.block_until_ready(r)
        return (time.perf_counter() - t0) / steps

    tk = timeit(kern)
    tx = timeit(gather_path)
    return {
        "kernel_us": tk * 1e6,
        "gather_us": tx * 1e6,
        "speedup": tx / tk,
    }


def bench_decode_batch64(params, steps: int = 50) -> dict:
    from ray_tpu.llm.engine import SamplingParams
    from ray_tpu.models.llama import PRESETS

    cfg = PRESETS["bench"]
    B, max_seq, P = 64, 2048, 64
    rng = np.random.default_rng(0)
    # Mixed true lengths: a quarter long, the rest short — the shape
    # where per-slot length early-exit matters.
    lens = [1500 if i % 4 == 0 else 128 for i in range(B)]
    prompts = [
        rng.integers(1, cfg.vocab_size, size=n).tolist() for n in lens
    ]
    out = {}
    for label, use_kernel in (("kernel", True), ("gather", False)):
        eng = _build_engine(
            use_kernel,
            model=cfg, params=params, max_batch=B, max_seq=max_seq,
            kv="paged", page_size=P,
            num_pages=(B * max_seq) // P,
        )
        sp = SamplingParams(max_tokens=steps + 16)
        for p in prompts:
            eng.add_request(p, sp)
        while len(eng._active) < B:  # admit + prefill everyone
            eng.step()
        eng.step()  # one compiled-warm decode step
        t0 = time.perf_counter()
        for _ in range(steps):
            eng.step()
        dt = time.perf_counter() - t0
        out[label] = {
            "steps_per_s": steps / dt,
            "tok_per_s": steps * B / dt,
            "ms_per_step": dt / steps * 1e3,
        }
    out["speedup"] = (
        out["kernel"]["tok_per_s"] / out["gather"]["tok_per_s"]
    )
    return out


def bench_prefill_stall(params, chunk: int = 1024) -> dict:
    from ray_tpu.llm.engine import SamplingParams
    from ray_tpu.models.llama import PRESETS

    # An 8k prompt: long enough that the monolithic prefill's compute
    # dominates per-step dispatch latency, so the stall (and the
    # chunking win) is visible.
    cfg = PRESETS["bench"]
    B, max_seq, P = 9, 8192, 64
    rng = np.random.default_rng(1)
    decode_prompts = [
        rng.integers(1, cfg.vocab_size, size=64).tolist()
        for _ in range(B - 1)
    ]
    long_prompt = rng.integers(1, cfg.vocab_size, size=7936).tolist()
    out = {}
    for label, use_chunk in (("chunked", True), ("monolithic", False)):
        eng = _build_engine(
            True,
            model=cfg, params=params, max_batch=B, max_seq=max_seq,
            kv="paged", page_size=P,
            prefill_chunk=chunk if use_chunk else None,
        )
        sp = SamplingParams(max_tokens=512)
        for p in decode_prompts:
            eng.add_request(p, sp)
        while len(eng._active) < B - 1:
            eng.step()
        for _ in range(4):  # warm the decode program
            eng.step()
        # Warm the prefill program shapes out-of-band so the measured
        # stall is execution, not first-compile.
        warm = rng.integers(1, cfg.vocab_size, size=7935).tolist()
        eng.add_request(warm, SamplingParams(max_tokens=1))
        for _ in range(12):
            eng.step()
        # Admit the long prompt mid-stream and time every step until
        # it activates plus a tail of plain decode steps.
        eng.add_request(long_prompt, sp)
        times = []
        for _ in range(16):
            t0 = time.perf_counter()
            eng.step()
            times.append(time.perf_counter() - t0)
        times_ms = np.asarray(times) * 1e3
        out[label] = {
            "p50_ms": float(np.percentile(times_ms, 50)),
            "p99_ms": float(np.percentile(times_ms, 99)),
            "max_ms": float(times_ms.max()),
        }
    out["stall_ratio_p99"] = (
        out["monolithic"]["p99_ms"] / out["chunked"]["p99_ms"]
    )
    return out


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    from ray_tpu._private import chip

    chip.hold_chip()  # chip numbers only: the TPU or an error
    import jax

    from ray_tpu.models.llama import PRESETS, init_params

    params = init_params(jax.random.key(0), PRESETS["bench"])
    op_bench = bench_attention_op_batch64(steps=args.steps)
    op_8b = bench_attention_op_batch64(
        steps=args.steps, heads=(32, 8)
    )
    # Long-context serving shape: an 8k-token table width with mostly
    # short true lengths — where the kernel's per-slot early-exit pays
    # (the gather path must materialize the FULL window per slot).
    op_wide = bench_attention_op_batch64(
        steps=args.steps, heads=(32, 8), max_pages=128,
        long_len=7000, short_len=300, long_every=8,
    )
    decode = bench_decode_batch64(params, steps=args.steps)
    stall = bench_prefill_stall(params)
    results = {
        "paged_attention_op@64_h8kv4": op_bench,
        "paged_attention_op@64_h32kv8": op_8b,
        "paged_attention_op@64_8k_window": op_wide,
        "decode@64": decode,
        "prefill_stall": stall,
    }

    # Floors. After the round-5 einsum-folded fallback rewrite (GQA-
    # grouped q, no materialized window transpose or head repeat), the
    # XLA gather path itself is ~4-5x faster than round 4's (17.4 ->
    # 4.6 ms at 32/8 heads), so the kernel's RELATIVE edge at this
    # window size is 1.1-1.3x (its in-place page reads avoid
    # materializing the gathered window, which matters more at wider
    # tables). Floors therefore gate against INVERSION (kernel slower
    # than fallback) plus absolute regressions of either path; the
    # engine rows carry a host sync per step in BOTH paths (the op rows
    # are the clean attention comparison), and the chunked-prefill p99
    # must beat the monolithic stall.
    assert op_bench["speedup"] > 0.9, op_bench
    assert op_8b["speedup"] > 1.0, op_8b
    assert op_bench["kernel_us"] < 8000, op_bench
    # Absolute fallback bounds: speedup alone would PASS if the
    # einsum-folded fallback regressed (a slower gather inflates the
    # ratio). r4's fallback was ~7ms at 8/4 and ~17-21ms at 32/8.
    assert op_bench["gather_us"] < 6500, op_bench
    assert op_8b["gather_us"] < 9000, op_8b
    # The wide-window case is where the kernel's early-exit must win
    # decisively (measured ~2.1x on v5e).
    assert op_wide["speedup"] > 1.5, op_wide
    # Engine-level the two paths measured EQUIVALENT (~0.95-1.4x run
    # to run): guard only against a real inversion.
    assert decode["speedup"] > 0.8, decode
    assert stall["stall_ratio_p99"] > 1.3, stall
    print(json.dumps(results, indent=None if args.json else 2))
    return results


if __name__ == "__main__":
    main()
