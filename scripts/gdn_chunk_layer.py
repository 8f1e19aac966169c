"""One Gated DeltaNet layer's mixer alone, on the chip: XLA's chunked
form (what `gdn_chunked` was on a TPU up to PR 57, and still is off it)
against `ops/pallas/gdn_chunk.py` at several blockings.

    chiprun -- python scripts/gdn_chunk_layer.py

The cell's widths (qwen3next-80b-serve1: 16 key heads, 32 value heads of
128 x 128, chunk 32), 2,048 tokens a call, all of them live and with the
last tenth padding. Prints a JSON line a variant: ms of the whole mixer
(`gdn_chunked`), ms of the rule alone (the `gdn:scan` scope's work, from
q, k, v, beta, g to o and the state), and the distance of the state
after the live tokens from the token-a-step recurrence's, as a share of
its norm. PERF.md section 6, PR 58, has the table this made.
"""

import json
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")

from ray_tpu.models import qwen3_next  # noqa: E402
from ray_tpu.ops.pallas import gdn_chunk  # noqa: E402

TOKENS = 2048
CFG = qwen3_next.Qwen3NextConfig(pattern="GE")
# (groups of 128 tokens, key heads) a grid step.
SWEEP = [(1, 1), (1, 2), (2, 2), (4, 2), (1, 4)]


def operands(u, p, length):
    """What `gdn_chunked` hands its rule: q, k, v, beta, g."""
    qkv, _, ba = qwen3_next._project_in(u, p, CFG)
    taps = CFG.conv_kernel
    seq = jnp.concatenate([jnp.zeros((taps - 1, qkv.shape[1])), qkv])
    conv = sum(seq[j: j + len(u)] * p["conv_w"][j] for j in range(taps))
    q, k, v = qwen3_next._split_qkv(jax.nn.silu(conv), CFG)
    beta, g = qwen3_next._gates(ba, p, CFG)
    live = (jnp.arange(len(u)) < length)[:, None, None]
    return q, k, v, jnp.where(live, beta, 0.0), jnp.where(live, g, 0.0)


@jax.jit
def recurrence(q, k, v, beta, g, state):
    """The rule a token a step, float32 elementwise (`gdn_step`'s)."""

    def step(s, x):
        q_t, k_t, v_t, beta_t, g_t = x
        s = s * jnp.exp(g_t)[..., None, None]
        k_col = k_t[:, None, :, None]
        read = (s * k_col).sum(-2)
        s = s + k_col * (beta_t[..., None] * (v_t - read))[..., None, :]
        return s, None

    return jax.lax.scan(step, state, (q, k, v, beta, g))[0]


def timed(fn, *args, calls=20):
    out = fn(*args)
    jax.block_until_ready(out)
    began = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - began) / calls, out


def main():
    device = jax.devices()[0]
    print(json.dumps({"device": device.device_kind,
                      "platform": device.platform}), flush=True)
    keys = jax.random.split(jax.random.key(58), 3)
    p = qwen3_next._init_gdn(keys[0], cfg=CFG)
    u = jax.random.normal(keys[1], (TOKENS, CFG.d_model)).astype(CFG.dtype)
    hk, rep = CFG.gdn_key_heads, CFG.gdn_value_heads // CFG.gdn_key_heads
    state0 = jnp.zeros((hk, rep, CFG.gdn_key_dim, CFG.gdn_value_dim))
    conv0 = jnp.zeros((CFG.conv_kernel - 1, CFG.gdn_conv_dim), CFG.dtype)

    default = (gdn_chunk._GROUPS_A_STEP, gdn_chunk._HEADS_A_STEP)
    sweep = [default] + [b for b in SWEEP if b != default]
    for length in (TOKENS, TOKENS * 9 // 10):
        ops = jax.jit(operands)(u, p, length)
        want = recurrence(*ops, state0)
        norm = float(jnp.linalg.norm(want))
        # The sweep with every token live; the padded call as served.
        variants = {"xla": None} | {
            f"kernel, {groups} groups x {heads} heads a step": (groups, heads)
            for groups, heads in (sweep if length == TOKENS else [default])
        }
        for name, blocking in variants.items():
            jax.clear_caches()
            if blocking is None:
                platform = "cpu"  # XLA's form, on the chip
                rule = jax.jit(lambda *a: qwen3_next._chunked_rule(
                    *a, CFG.gdn_chunk))
                rule_args = (*ops, state0)
            else:
                platform = "tpu"
                gdn_chunk._GROUPS_A_STEP, gdn_chunk._HEADS_A_STEP = blocking
                rule = jax.jit(lambda *a: gdn_chunk.gdn_chunk_rule(
                    *a, chunk=CFG.gdn_chunk))
                rule_args = (*ops, state0, jnp.int32(length))
            qwen3_next.chip = types.SimpleNamespace(platform=lambda: platform)
            mixer = jax.jit(lambda u, s, c, n: qwen3_next.gdn_chunked(
                u, p, CFG, s, c, n))
            line = {"length": length, "variant": name}
            try:
                rule_ms, (o, end) = timed(rule, *rule_args)
                mixer_ms, _ = timed(
                    mixer, u, state0.reshape(-1, *state0.shape[2:]), conv0,
                    jnp.int32(length),
                )
                line.update({
                    "rule_ms": round(rule_ms, 4),
                    "mixer_ms": round(mixer_ms, 4),
                    "state_off_recurrence_pct": round(
                        100 * float(jnp.linalg.norm(end - want)) / norm, 4),
                    "o_finite": bool(np.isfinite(np.asarray(o)).all()),
                    "o_max_abs": float(jnp.abs(o[:length]).max()),
                })
            # tpulint: allow(broad-except reason=a blocking the compiler refuses is a line of the table, not the end of the sweep)
            except Exception as e:  # noqa: BLE001
                line["error"] = repr(e)[-400:]
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
