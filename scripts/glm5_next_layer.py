"""GLM-5.3-Flash's sublayers alone, on the chip, at the cell's widths
(glm53flash-serve1: 64 KDA heads of 128 x 128, 64 latent heads over a
512-wide cell, 32 indexer heads, four streams of 4,096, 36 of 288
experts), 2,048 tokens a call: what one prefill chunk's parts take.

    chiprun -- python scripts/glm5_next_layer.py [kda]

Prints a JSON line a variant: the KDA mixer and its rule alone, XLA's
form (`glm5_next._kda_rule`, what `kda_chunked` was on a TPU up to PR
59 and still is off it) at several (chunk, sub-chunk) pairs and
`ops/pallas/kda_chunk.py` at several (heads, groups) a grid step, every
token live and with the last tenth padding, with the distance of the
state from the token-a-step recurrence's (``kda`` alone stops there);
the sparse latent mixer as the last chunk
of a 16k and of a 64k context at several query blocks; one residual mix
and spread; the expert FFN; the dense FFN. PERF.md section 6, PRs 59
and 60, has the tables this made.
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
from ray_tpu.models import glm5_next  # noqa: E402
from ray_tpu.models.moe import moe_ffn  # noqa: E402
from ray_tpu.ops.pallas import gdn_chunk, kda_chunk  # noqa: E402

TOKENS = 2048
CFG = glm5_next.Glm5NextConfig(
    pattern="KDLE", vocab_size=1024, experts_held=(0, 36)
)
# (heads, groups of 128 tokens) a grid step of `ops/pallas/kda_chunk.py`.
KDA_SWEEP = [(1, 1), (2, 1), (4, 1), (8, 1), (2, 2)]


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
    def operands(u, p, length):
        """What `kda_chunked` hands its rule, [T, H x dk] as they lie."""
        qkv, g, beta, _ = glm5_next._kda_in(u, p, CFG)
        seq = jnp.concatenate([jnp.zeros((3, qkv.shape[1])), qkv])
        conv = sum(seq[j: j + len(u)] * p["conv_w"][j] for j in range(4))
        live = jnp.arange(len(u)) < length
        flat = [a.reshape(len(u), -1) for a in (
            *glm5_next._kda_split(jax.nn.silu(conv), CFG),
            jnp.where(live[:, None, None], g, 0.0),
        )]
        return (*flat[:3], jnp.where(live[:, None], beta, 0.0), flat[3])

    def heads_of(q, k, v, beta, g):
        return (*(a.reshape(TOKENS, h, dk) for a in (q, k, v)), beta,
                g.reshape(TOKENS, h, dk))

    def kda_line(name, length, rule, cfg, platform):
        """A rule (flat operands, so that no call pays for laying them
        out again: in the program they come from fusions as they are
        wanted) and the mixer around it, timed."""
        glm5_next.chip = types.SimpleNamespace(platform=lambda: platform)
        want = wants[length]
        # (The weights go in as arguments: closed over, they would be
        # constants of the program, 0.3 GB of them in its executable.)
        mixer = jax.jit(lambda p, u, s, c, n: glm5_next.kda_chunked(
            u, p, cfg, s, c, n))
        line = {"kda": name, "length": length}
        try:
            rule_ms, (o, end) = timed(rule, *ops[length], state0)
            mixer_ms, _ = timed(mixer, kda, u, state0, conv0, jnp.int32(length))
            line.update({
                "rule_ms": rule_ms, "mixer_ms": mixer_ms,
                "state_rel_err": float(
                    jnp.linalg.norm(end - want) / jnp.linalg.norm(want)),
                "o_finite": bool(jnp.isfinite(o).all()),
            })
        except Exception as e:  # noqa: BLE001 - a variant the chip refuses
            line["refused"] = repr(e)[-400:]
        print(json.dumps(line), flush=True)

    if "kda" in parts:
        lengths = (TOKENS, TOKENS * 9 // 10)
        ops = {n: jax.jit(operands)(u, kda, n) for n in lengths}
        wants = {n: recurrence(*heads_of(*ops[n]), state0) for n in lengths}
        on_the_chip = glm5_next.chip
        # XLA's form (`_kda_rule`), on the chip: the program's own
        # sub-chunk of 16 in the mixer, whatever the rule alone is given.
        for size, sub in ((32, 16), (64, 16), (64, 32), (128, 16)):
            cfg = dataclasses.replace(CFG, kda_chunk=size)
            rule = jax.jit(lambda *a, size=size, sub=sub: glm5_next._kda_rule(
                *heads_of(*a[:5]), a[5], size, sub))
            for n in lengths if size == CFG.kda_chunk else lengths[:1]:
                kda_line(f"xla, chunk {size} sub {sub}", n, rule, cfg, "cpu")
        # The kernel at (heads, groups of 128 tokens) a grid step.
        default = (kda_chunk._HEADS_A_STEP, gdn_chunk._GROUPS_A_STEP)
        for blocking in [default] + [b for b in KDA_SWEEP if b != default]:
            kda_chunk._HEADS_A_STEP, gdn_chunk._GROUPS_A_STEP = blocking
            jax.clear_caches()
            for n in lengths if blocking == default else lengths[:1]:
                rule = jax.jit(lambda *a, n=n: kda_chunk.kda_chunk_rule(
                    *heads_of(*a[:5]), a[5], jnp.int32(n),
                    chunk=CFG.kda_chunk, sub=glm5_next._KDA_SUBCHUNK))
                kda_line("kernel, {} heads x {} groups a step".format(*blocking),
                         n, rule, CFG, "tpu")
        kda_chunk._HEADS_A_STEP, gdn_chunk._GROUPS_A_STEP = default
        glm5_next.chip = on_the_chip
    if parts == {"kda"}:
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

    # ------------------------------------------ residual path, the FFNs
    x = jax.random.normal(
        keys[4], (1, TOKENS, CFG.hc_mult, CFG.d_model)
    ).astype(CFG.dtype)

    def one_mix(p, x, y):
        hh, mix = glm5_next.mhc_mix(x, p, CFG)
        return glm5_next.mhc_spread(x, y + hh, *mix)

    ms, _ = timed(jax.jit(one_mix), dense["hc"], x, u[None])
    print(json.dumps({"mhc_mix_and_spread_ms": ms}), flush=True)
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
