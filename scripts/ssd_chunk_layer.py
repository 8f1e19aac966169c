"""One Mamba-2 mixer alone, on the chip: XLA's state-space dual form
(what `mamba_chunked` was on a TPU up to PR 66, and still is off it)
against `ops/pallas/ssd_chunk.py`.

    chiprun -- python scripts/ssd_chunk_layer.py

Two cells' widths: granite4hsmall-serve1 (128 heads of 64 in one group,
state 128, chunk 256, 2,048 tokens a call) and nemotron3nano-serve1 (64
heads of 64 in 8 groups, chunk 128, 512 and 64 tokens a call), every
token live and with the last tenth padding. Prints a JSON line a
variant: ms of the rule alone (the `ssm:scan` scope's work, from x, B, C,
dt to y and the state), ms of the whole mixer (`mamba_chunked`), and
the distance of the state after the live tokens from the token-a-step
recurrence's, as a share of its norm. A timed call is a chain of `CHAIN`
rules (each with an input of its own and the state the one before left)
or mixers (each fed the one before's output and state), so that it
holds milliseconds of device work (the one-chip machine's dispatch is
~0.1 ms a call: PERF.md section 6, PR 64); the line gives ms a link.
`ops/pallas/ssd_chunk.py`'s docstring has the table this made.
"""

import json
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")

from ray_tpu.models import granite_hybrid, nemotron_h  # noqa: E402
from ray_tpu.ops.pallas import ssd_chunk  # noqa: E402

# A chunk program's Mamba layers in granite-longdoc-16.
CHAIN = 9
# name: (config, tokens a call, heads a grid step to sweep)
SHAPES = {
    "granite4hsmall-serve1": (
        granite_hybrid.GraniteHybridConfig(pattern="ME"), 2048, (8, 16, 32, 64),
    ),
    "nemotron3nano-serve1": (
        nemotron_h.NemotronHConfig(pattern="ME"), 512, (8,),
    ),
    "nemotron3nano-serve1, a program of 64 tokens": (
        nemotron_h.NemotronHConfig(pattern="ME"), 64, (8,),
    ),
}


def operands(cfg, u, p, length):
    """What `mamba_chunked` hands its rule: [x | B | C], dt, A."""
    _, xbc, dt_raw = nemotron_h._project_in(u, p, cfg)
    taps = cfg.conv_kernel
    seq = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1])), xbc])
    conv = p["conv_b"] + sum(
        seq[j: j + len(u)] * p["conv_w"][j] for j in range(taps)
    )
    dt, a = nemotron_h._steps(dt_raw, p)
    live = jnp.arange(len(u))[:, None] < length
    return jax.nn.silu(conv), jnp.where(live, dt, 0.0), a


@jax.jit
def recurrence(x, b, dt, a, state):
    """The rule a token a step, float32 elementwise (`mamba_step`'s)."""
    rep = x.shape[1] // b.shape[1]

    def step(s, now):
        x_t, b_t, dt_t = now
        b_h = jnp.repeat(b_t, rep, axis=0)  # [H, N]
        s = s * jnp.exp(dt_t * a)[:, None, None]
        return s + (x_t * dt_t[:, None])[..., None] * b_h[:, None, :], None

    return jax.lax.scan(step, state, (x, b, dt))[0]


def timed(fn, *args, calls=10):
    out = fn(*args)
    jax.block_until_ready(out)
    began = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - began) / calls / CHAIN


def main():
    device = jax.devices()[0]
    print(json.dumps({"device": device.device_kind,
                      "platform": device.platform}), flush=True)
    by_default = ssd_chunk._HEADS_A_STEP
    for shape, (cfg, tokens, sweep) in SHAPES.items():
        keys = jax.random.split(jax.random.key(67), 3)
        p = nemotron_h._init_block(keys[0], kind="M", cfg=cfg)
        u = jax.random.normal(keys[1], (tokens, cfg.d_model)).astype(cfg.dtype)
        state0 = jnp.zeros(
            (cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state)
        )
        conv0 = jnp.zeros((cfg.conv_kernel - 1, cfg.conv_dim), cfg.dtype)
        size = min(cfg.chunk_size, tokens)
        heads = [by_default] + [h for h in sweep if h != by_default]
        for length in (tokens, tokens * 9 // 10):
            xbc, dt, a = jax.jit(
                lambda u, n: operands(cfg, u, p, n)
            )(u, length)
            x, b, _ = nemotron_h._split_xbc(xbc, cfg)
            want = recurrence(x, b, dt, a, state0)
            norm = float(jnp.linalg.norm(want))
            # A link's own input each, so that no link's work is another's.
            links = [xbc * (1 + i / 64) for i in range(1, CHAIN)]
            # The sweep with every token live; the padded call as served.
            variants = {"xla": None} | {
                f"kernel, {h} heads a step": h
                for h in (heads if length == tokens else heads[:1])
            }
            for name, heads_a_step in variants.items():
                jax.clear_caches()
                if heads_a_step is None:
                    platform = "cpu"  # XLA's form, on the chip

                    def rule(xbc, s, n):
                        return nemotron_h._dual_form(
                            *nemotron_h._split_xbc(xbc, cfg), dt, a, p["D"],
                            s, size)
                else:
                    platform = "tpu"
                    ssd_chunk._HEADS_A_STEP = heads_a_step
                    nemotron_h._SCAN_KERNEL_TOKENS = 0  # whatever the length

                    def rule(xbc, s, n):
                        return ssd_chunk.ssd_chunk_rule(
                            xbc, dt, a, p["D"], s, n,
                            groups=cfg.ssm_groups, chunk=size)
                nemotron_h.chip = types.SimpleNamespace(
                    platform=lambda platform=platform: platform)

                @jax.jit
                def rules(inputs, s, n):
                    ys = []
                    for xbc in inputs:
                        y, s = rule(xbc, s, n)
                        ys.append(y)
                    return ys, s

                @jax.jit
                def mixers(u, s, tail, n):
                    for _ in range(CHAIN):
                        u, s, tail = nemotron_h.mamba_chunked(
                            u, p, cfg, s, tail, n)
                    return u, s, tail

                line = {"shape": shape, "tokens": tokens, "length": length,
                        "variant": name}
                try:
                    y, end = jax.jit(rule)(xbc, state0, jnp.int32(length))
                    line.update({
                        "rule_ms": round(timed(
                            rules, [xbc, *links], state0,
                            jnp.int32(length)), 4),
                        "mixer_ms": round(timed(
                            mixers, u, state0, conv0, jnp.int32(length)), 4),
                        "state_off_recurrence_pct": round(
                            100 * float(jnp.linalg.norm(end - want)) / norm,
                            4),
                        "y_finite": bool(np.isfinite(np.asarray(y)).all()),
                        "y_max_abs": float(jnp.abs(y[:length]).max()),
                    })
                # tpulint: allow(broad-except reason=a blocking the compiler refuses is a line of the table, not the end of the sweep)
                except Exception as e:  # noqa: BLE001
                    line["error"] = repr(e)[-400:]
                print(json.dumps(line), flush=True)
    ssd_chunk._HEADS_A_STEP = by_default


if __name__ == "__main__":
    main()
