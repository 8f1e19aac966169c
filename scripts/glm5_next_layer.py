"""GLM-5.3-Flash's sublayers alone, on the chip, at the cell's widths
(glm53flash-serve1: 64 KDA heads of 128 x 128, 64 latent heads over a
512-wide cell, 32 indexer heads, four streams of 4,096, 36 of 288
experts), 2,048 tokens a call: what one prefill chunk's parts take.

    chiprun -- python scripts/glm5_next_layer.py [kda | mhc]

Prints a JSON line a variant: the KDA mixer in XLA's form
(`glm5_next._kda_rule` between XLA's passes: what `kda_chunked` was on
a TPU up to PR 59 and still is off it), then with
`ops/pallas/kda_chunk.py` between the matmuls as it is called on a TPU
(and that call alone), with the convolution, silu, unit lengths and
decay (the prologue) or the head norm and output gate (the epilogue) or
both left to XLA as PR 60 left them, and at several (heads, groups) a
grid step; every token live and with the last tenth padding, with the
distance of the output from XLA's form's and of the state from the
token-a-step recurrence's (``kda`` alone stops there);
one residual mix and spread, then the residual path by variant (XLA's
form, `ops/pallas/mhc_streams.py`'s two calls) at 2,048 rows and at a
decode step's, with the kernels' distance from XLA's form (``mhc``
alone is this); the sparse latent mixer as the last chunk of a 16k and
of a 64k context at several query blocks; the dense FFN; the expert
FFN. PERF.md section 6, PRs 59, 60, 62 and 64, has the tables this
made.
"""

import dataclasses
import json
import sys
import time
import types

import jax
import jax.numpy as jnp

sys.path.insert(0, ".")

from ray_tpu.llm import hybrid_kv  # noqa: E402
from ray_tpu.models import glm5_next, mhc  # noqa: E402
from ray_tpu.models.moe import moe_ffn  # noqa: E402
from ray_tpu.ops.pallas import gdn_chunk, kda_chunk, mhc_streams  # noqa: E402

TOKENS = 2048
CFG = glm5_next.Glm5NextConfig(
    pattern="KDLE", vocab_size=1024, experts_held=(0, 36)
)
# (heads, groups of 128 tokens) a grid step of `ops/pallas/kda_chunk.py`.
KDA_SWEEP = [(1, 1), (2, 1), (4, 1), (8, 1), (2, 2)]
# (tokens a grid step, rows, lanes between a load and a store) of
# `ops/pallas/mhc_streams.py`.
MHC_SWEEP = [(128, 16, 512), (256, 16, 512), (512, 16, 512), (128, 16, 1024),
             (128, 16, 256), (128, 32, 512), (256, 32, 1024)]
# Sublayers a timed call of the residual path: a chunk program's ten.
MHC_CHAIN = 10


def timed(fn, *args, calls=10):
    out = fn(*args)
    jax.block_until_ready(out)
    began = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return round(1e3 * (time.perf_counter() - began) / calls, 3), out


@jax.jit
def recurrence(q, k, v, beta, g, state):
    def step(s, x):
        q_t, k_t, v_t, beta_t, g_t = x
        s = s * jnp.exp(g_t)[..., None]
        k_col = k_t[..., None]
        read = (s * k_col).sum(-2)
        s = s + k_col * (beta_t[..., None] * (v_t - read))[..., None, :]
        return s, None

    return jax.lax.scan(step, state, (q, k, v, beta, g))[0]


def residual_path(hc, key, u):
    """One residual mix and spread as the platform chooses it, the
    streams [1, T, n, d] a call, as PR 59 timed it
    (`mhc_mix_and_spread_ms`; a call of under ~0.6 ms is the HOST's
    dispatch on the one-chip machine, not the device: PR 64). Then the
    path by variant (XLA's form, `ops/pallas/mhc_streams.py`'s two
    calls) at a prefill chunk's rows and at a decode step's, as a program
    runs it: `MHC_CHAIN` sublayers a call, the streams handed from a
    spread to the next mix (flat [T, n d] at the call's ends, no copy
    into another layout inside), each a mix, the sublayer's output added
    and a spread; ms a sublayer; the kernels' distance from XLA's form
    after the first sublayer; and the two calls by (tile, rows, lanes)."""
    n, d = CFG.hc_mult, CFG.d_model
    on_the_chip = mhc.chip
    x = jax.random.normal(key, (1, TOKENS, n, d)).astype(CFG.dtype)

    def one_mix(p, x, y):
        hh, mix = mhc.mhc_mix(x, p, CFG)
        return mhc.mhc_spread(x, y + hh, *mix)

    ms, _ = timed(jax.jit(one_mix), hc, x, u[None])
    print(json.dumps({"mhc_mix_and_spread_ms": ms}), flush=True)

    def path(platform, sublayers):
        def chain(p, flat, y):
            mhc.chip = types.SimpleNamespace(platform=lambda: platform)
            x = flat.reshape(1, -1, n, d)
            for _ in range(sublayers):
                h, mix = mhc.mhc_mix(x, p, CFG)
                x = mhc.mhc_spread(x, y + h, *mix)
            return x.reshape(flat.shape), h, *mix
        return jax.jit(chain)

    def operands(tokens):
        return (
            jax.random.normal(key, (tokens, n * d)).astype(CFG.dtype),
            jax.random.normal(
                jax.random.fold_in(key, 1), (1, tokens, d)).astype(CFG.dtype),
        )

    def a_sublayer(platform, flat, y):
        return round(
            timed(path(platform, MHC_CHAIN), hc, flat, y)[0] / MHC_CHAIN, 4)

    by_rows = mhc._MHC_KERNEL_ROWS
    mhc._MHC_KERNEL_ROWS = 0  # the platform alone chooses, below
    for tokens in (TOKENS, 32, 16):
        flat, y = operands(tokens)
        print(json.dumps({"mhc": "xla", "tokens": tokens,
                          "ms_a_sublayer": a_sublayer("cpu", flat, y)}),
              flush=True)
        line = {"mhc": "kernels", "tokens": tokens}
        try:
            line["ms_a_sublayer"] = a_sublayer("tpu", flat, y)
            got = path("tpu", 1)(hc, flat, y)
            want = path("cpu", 1)(hc, flat, y)
            line.update({
                f"{name}_max_abs_err": float(jnp.max(jnp.abs(
                    a.astype(jnp.float32) - b.astype(jnp.float32))))
                for name, a, b in zip(
                    ("streams", "h", "h_res", "h_post"), got, want)
            })
        except Exception as e:  # noqa: BLE001 - a shape the chip refuses
            line["refused"] = repr(e)[-400:]
        print(json.dumps(line), flush=True)
    # The two calls at (tile, rows, lanes), a prefill chunk's rows.
    default = (mhc_streams._TILE, mhc_streams._ROWS, mhc_streams._LANES)
    flat, y = operands(TOKENS)
    for blocking in MHC_SWEEP:
        mhc_streams._TILE, mhc_streams._ROWS, mhc_streams._LANES = blocking
        jax.clear_caches()
        line = {"mhc": "kernels, {} tokens a step, {} rows x {} lanes".format(
            *blocking)}
        try:
            line["ms_a_sublayer"] = a_sublayer("tpu", flat, y)
        except Exception as e:  # noqa: BLE001
            line["refused"] = repr(e)[-400:]
        print(json.dumps(line), flush=True)
    mhc_streams._TILE, mhc_streams._ROWS, mhc_streams._LANES = default
    mhc._MHC_KERNEL_ROWS = by_rows
    mhc.chip = on_the_chip


def main():
    parts = set(sys.argv[1:]) or {"kda", "rest"}
    device = jax.devices()[0]
    print(json.dumps({"device": device.device_kind,
                      "platform": device.platform}), flush=True)
    keys = jax.random.split(jax.random.key(59), 8)
    params = glm5_next.init_params(keys[0], CFG)
    kda, dense, dsa, experts = params["blocks"]
    u = jax.random.normal(keys[1], (TOKENS, CFG.d_model)).astype(CFG.dtype)
    h, dk = CFG.kda_heads, CFG.kda_head_dim
    state0 = jnp.zeros((h, dk, dk))
    conv0 = jnp.zeros((CFG.conv_kernel - 1, CFG.kda_conv_dim), CFG.dtype)

    # ------------------------------------------------------------- KDA
    def rule_operands(u, p, length):
        """q, k, v, beta, g [T, H, dk] as XLA's passes make them of the
        matmuls' results (`kda_chunked` off the TPU): what `_kda_rule`
        and the recurrence take."""
        qkv, g, beta, _ = glm5_next._kda_in(u, p, CFG)
        live = jnp.arange(len(u)) < length
        return (*glm5_next._kda_conv(qkv, conv0, p, CFG, length)[0],
                jnp.where(live[:, None], beta, 0.0),
                jnp.where(live[:, None, None], g, 0.0))

    def kernel_call(p, qkv, low, beta, gate, state, length, dtype):
        return kda_chunk.kda_chunk_rule(
            qkv, conv0, p["conv_w"], low, p["dt_bias"], p["A_log"], beta,
            gate, p["gate_norm"], state, length, chunk=CFG.kda_chunk,
            sub=glm5_next._KDA_SUBCHUNK, lower=CFG.kda_lower,
            l2_eps=glm5_next._L2_EPS, norm_eps=CFG.norm_eps, dtype=dtype,
        )

    def split_mixer(prologue_in, epilogue_in):
        """`kda_chunked` on a TPU with the passes of either end made by
        XLA as the parent (PR 60) made them and the kernel's own stage
        passed through (`kda_chunk._prologue` / `_epilogue` replaced
        while this is traced): q, k, v come as one [T, 3 H dk] array in
        the in-projection's place and ``g`` in ``low``'s, ``o`` leaves
        float32. The kernel still fetches the operands its stage would
        have read (``gate``'s 67 MB where the epilogue is out)."""
        def mixer(p, u, state, conv, length):
            if prologue_in:
                qkv, low, beta, gate = glm5_next._kda_projections(u, p, CFG)
            else:
                q, k, v, beta, g = rule_operands(u, p, length)
                gate = glm5_next._kda_in(u, p, CFG)[3]
                qkv = jnp.concatenate(
                    [a.reshape(TOKENS, -1) for a in (q, k, v)], axis=1)
                low = g.reshape(TOKENS, -1)
            o, end = kernel_call(
                p, qkv, low, beta, gate, state, length,
                CFG.dtype if epilogue_in else jnp.float32)
            if epilogue_in:
                return o @ p["out_proj"], end
            return glm5_next._kda_out(o.reshape(TOKENS, h, dk), gate, p, CFG), end
        return mixer

    stages = (kda_chunk._prologue, kda_chunk._epilogue)
    passed = (
        lambda raw, before, w, low, *_: (*raw, low),
        lambda o, *_: o,
    )

    def kda_line(name, length, mixer, rule=None):
        """A mixer (and what of it is the rule's call alone) timed, with
        the distance of its output over the live tokens from XLA's form's
        and of its state from the token-a-step recurrence's."""
        # (The weights go in as arguments: closed over, they would be
        # constants of the program, 0.3 GB of them in its executable.)
        line = {"kda": name, "length": length}
        try:
            if rule is not None:
                line["rule_ms"] = timed(rule, kda, *rule_ops[length])[0]
            ms, (out, end) = timed(
                jax.jit(mixer), kda, u, state0, conv0, jnp.int32(length))
            want_out, want_end = wants[length]
            line.update({
                "mixer_ms": ms,
                "state_rel_err": float(
                    jnp.linalg.norm(end - want_end) / jnp.linalg.norm(want_end)),
                "out_rel_err": float(
                    jnp.linalg.norm((out - want_out)[:length].astype(jnp.float32))
                    / jnp.linalg.norm(want_out[:length].astype(jnp.float32))),
                "out_finite": bool(jnp.isfinite(out).all()),
            })
        except Exception as e:  # noqa: BLE001 - a variant the chip refuses
            line["refused"] = repr(e)[-400:]
        print(json.dumps(line), flush=True)

    def as_on(platform, cfg=CFG):
        def mixer(p, u, s, c, n):
            glm5_next.chip = types.SimpleNamespace(platform=lambda: platform)
            return glm5_next.kda_chunked(u, p, cfg, s, c, n)[:2]
        return mixer

    if "kda" in parts:
        lengths = (TOKENS, TOKENS * 9 // 10)
        on_the_chip = glm5_next.chip
        # The oracle: XLA's form's output, and the recurrence's state.
        wants, rule_ops = {}, {}
        for n in lengths:
            out = jax.jit(as_on("cpu"))(kda, u, state0, conv0, jnp.int32(n))[0]
            ops = jax.jit(rule_operands)(u, kda, n)
            wants[n] = (out, recurrence(*ops, state0))
            rule_ops[n] = (
                *jax.jit(lambda u, p: glm5_next._kda_projections(u, p, CFG))(u, kda),
                state0, jnp.int32(n),
            )
        # XLA's form (`_kda_rule` between XLA's passes), on the chip.
        for size in (32, 64):
            cfg = dataclasses.replace(CFG, kda_chunk=size)
            for n in lengths if size == CFG.kda_chunk else lengths[:1]:
                kda_line(f"xla, chunk {size}", n, as_on("cpu", cfg))
        # The kernel as `kda_chunked` calls it on a TPU (its call alone:
        # the matmuls' results to the gated output), then with either
        # stage, and both, left to XLA as the parent left them.
        whole = jax.jit(lambda p, *a: kernel_call(p, *a, CFG.dtype))
        for n in lengths:
            kda_line("kernel, both stages in", n, as_on("tpu"), whole)
        for name, prologue_in, epilogue_in in (
            ("kernel, the parent's form: neither stage in", False, False),
            ("kernel, the epilogue in", False, True),
            ("kernel, the prologue in", True, False),
        ):
            kda_chunk._prologue = stages[0] if prologue_in else passed[0]
            kda_chunk._epilogue = stages[1] if epilogue_in else passed[1]
            jax.clear_caches()
            for n in lengths:
                kda_line(name, n, split_mixer(prologue_in, epilogue_in))
        kda_chunk._prologue, kda_chunk._epilogue = stages
        # Both stages in, at (heads, groups of 128 tokens) a grid step.
        default = (kda_chunk._HEADS_A_STEP, gdn_chunk._GROUPS_A_STEP)
        for blocking in [b for b in KDA_SWEEP if b != default]:
            kda_chunk._HEADS_A_STEP, gdn_chunk._GROUPS_A_STEP = blocking
            jax.clear_caches()
            kda_line(
                "kernel, both stages in, {} heads x {} groups a step".format(
                    *blocking),
                lengths[0], as_on("tpu"), whole)
        kda_chunk._HEADS_A_STEP, gdn_chunk._GROUPS_A_STEP = default
        glm5_next.chip = on_the_chip
    if parts & {"mhc", "rest"}:
        residual_path(dense["hc"], keys[4], u)
    if "rest" not in parts:
        return

    # ------------------------------------------------ sparse latent mixer
    page, pool = 64, CFG.index_kpool
    for context in (16384, 65536):
        n_pages = context // page
        latent = jax.random.normal(
            keys[2], (n_pages + 1, page, CFG.kv_lora_rank)
        ).astype(CFG.dtype)
        index = jax.random.normal(
            keys[3], (n_pages + 1, page // pool, CFG.index_head_dim)
        ).astype(CFG.dtype)
        tail = jnp.zeros((1, 1, pool - 1, CFG.index_head_dim))
        pages = 1 + jnp.arange(n_pages, dtype=jnp.int32)
        start = context - TOKENS
        for q_block in (64, 128, 256):
            glm5_next._DSA_QUERY_BLOCK = q_block  # read when traced
            fn = jax.jit(lambda p, x, latent, index, tail: (
                glm5_next.dsa_prefill(
                    x, p, CFG, (latent, index, tail), (0, 0), 0, pages,
                    pages[start // page:], jnp.int32(start),
                    jnp.int32(TOKENS),
                )[0]
            ))
            try:
                ms, _ = timed(fn, dsa, u, latent, index, tail)
                print(json.dumps({"dsa_context": context,
                                  "query_block": q_block, "ms": ms}),
                      flush=True)
            except Exception as e:  # noqa: BLE001
                print(json.dumps({"dsa_context": context,
                                  "query_block": q_block,
                                  "refused": str(e)[:200]}), flush=True)

    # ---------------------------------------------------------- the FFNs
    x = jax.random.normal(
        keys[4], (1, TOKENS, CFG.hc_mult, CFG.d_model)
    ).astype(CFG.dtype)
    ms, _ = timed(
        jax.jit(lambda p, x: hybrid_kv._dense_ffn(x, p, CFG)), dense, x
    )
    print(json.dumps({"dense_sublayer_ms": ms}), flush=True)
    live = jnp.ones((TOKENS,), bool)
    ms, _ = timed(jax.jit(
        lambda p, t: moe_ffn(t, p, CFG, rows_live=live)[0]), experts, u[None])
    print(json.dumps({"expert_ffn_ms": ms}), flush=True)


if __name__ == "__main__":
    main()
