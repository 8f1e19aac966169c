"""ops/pallas/ssd_chunk.py interpreted, against what it replaces on a
TPU: the state-space dual form of `models/nemotron_h.py mamba_chunked`
in XLA's own operations (`_dual_form`), and the recurrence a token a
step (`mamba_step`).

`mamba_chunked` is run twice on the same input, as it traces here (XLA's
form, tier 1's path) and as it traces on a TPU (the kernel, interpreted).
A CPU's einsums multiply in float32 where a TPU's take bfloat16 operands,
and the kernel follows `jax.default_matmul_precision` as the einsums do
on a TPU, so it is held to both: asked for float32 products, to the
tolerance of float32 sums, and as it runs by default to what rounding
five arrays to bfloat16 moves (the same five XLA's form rounds on a TPU).
Compiled for a described v5e at the served shapes in
tests/test_tpu_aot_compile.py.
"""

import dataclasses
import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import hybrid_kv
from ray_tpu.models import nemotron_h
from ray_tpu.ops.pallas import ssd_chunk

TINY = nemotron_h.NEMOTRON_H_PRESETS["nemotron_h_tiny"]
# Heads of 64 over a state of 128, the served models' own; few of them.
WIDE = dataclasses.replace(
    TINY, pattern="M", mamba_head_dim=64, ssm_state=128
)
# What `jax.default_matmul_precision` is asked for (the kernel follows it
# as XLA's einsums do on a TPU: `ssd_chunk._operands`) and how close the
# results then lie: float32's own sums, or 2^-9 a rounding to bfloat16.
TOL = {"float32": 3e-5, "default": 2e-2}


def _cfg(base, groups, rep, chunk):
    return dataclasses.replace(
        base, ssm_groups=groups, mamba_heads=groups * rep, chunk_size=chunk
    )


def _as_on_a_tpu(monkeypatch):
    """`mamba_chunked` takes the kernel, interpreted, as it does on a TPU
    for a long program, here whatever the length (nobody else is asked:
    `moe_ffn` would take its kernels compiled)."""
    monkeypatch.setattr(
        nemotron_h, "chip", types.SimpleNamespace(platform=lambda: "tpu")
    )
    monkeypatch.setattr(nemotron_h, "_SCAN_KERNEL_TOKENS", 0)
    monkeypatch.setattr(
        nemotron_h, "ssd_chunk_rule",
        functools.partial(ssd_chunk.ssd_chunk_rule, interpret=True),
    )


@pytest.fixture
def full_products():
    """Every product in float32, the CPU's einsums' own."""
    with jax.default_matmul_precision("float32"):
        yield


def _case(cfg, tokens, seed, zero_state):
    """A mixer's parameters, a normed input, the state and the tail
    before it: (p, u, ssm0, conv0)."""
    keys = jax.random.split(jax.random.key(seed), 4)
    p = nemotron_h._init_block(keys[0], kind="M", cfg=cfg)
    u = jax.random.normal(keys[1], (tokens, cfg.d_model))
    shape = (cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state)
    ssm0 = jax.random.normal(keys[2], shape)
    conv0 = jax.random.normal(keys[3], (cfg.conv_kernel - 1, cfg.conv_dim))
    if zero_state:
        ssm0, conv0 = jnp.zeros_like(ssm0), jnp.zeros_like(conv0)
    return p, u, ssm0, conv0


@functools.partial(jax.jit, static_argnames=("cfg", "n"))
def _recurrence(u, p, cfg, ssm0, conv0, n):
    """`mamba_step` on the first ``n`` of u's tokens, one a step: (out
    [n, d], the state after them)."""

    def step(carry, u_t):
        out, ssm, conv = nemotron_h.mamba_step(u_t[None], p, cfg, *carry)
        return (ssm, conv), out[0]

    (ssm, _), out = jax.lax.scan(step, (ssm0[None], conv0[None]), u[:n])
    return out, ssm[0]


# name: (base config, groups, heads a group, chunk, tokens, live tokens,
# zero state before)
CALLS = {
    # Granite's form: one group, chunks of 256, T a multiple of it.
    "one_group_every_token_live": (WIDE, 1, 4, 256, 512, 512, True),
    "one_group_a_length_inside_a_chunk": (WIDE, 1, 4, 256, 512, 300, False),
    "one_group_a_last_chunk_wholly_dead": (WIDE, 1, 4, 256, 768, 500, False),
    # Nemotron's: several groups, chunks of 128.
    "groups_every_token_live": (WIDE, 2, 2, 128, 256, 256, False),
    "groups_two_chunks_wholly_dead": (WIDE, 4, 2, 128, 384, 90, False),
    # A program shorter than a chunk: one chunk of its own length.
    "shorter_than_a_chunk": (WIDE, 2, 2, 128, 64, 64, True),
    "shorter_than_a_chunk_and_padded": (WIDE, 2, 2, 128, 64, 40, False),
    # Two grid steps over a group's heads, eight pairs each.
    "two_head_blocks_a_group": (WIDE, 1, 32, 128, 128, 100, False),
    # Heads that do not fill a tile, chunks that are no sub-block's
    # multiple: the tiny preset's own shapes.
    "tiny_heads_and_chunks": (TINY, 2, 4, 8, 32, 19, False),
}


@pytest.mark.parametrize("products", list(TOL))
@pytest.mark.parametrize("call", list(CALLS))
def test_kernel_is_the_xla_form_and_the_recurrence(
    call, products, monkeypatch
):
    base, groups, rep, chunk, tokens, length, zero_state = CALLS[call]
    cfg = _cfg(base, groups, rep, chunk)
    p, u, ssm0, conv0 = _case(cfg, tokens, groups + rep, zero_state)
    args = (u, p, cfg, ssm0, conv0, jnp.int32(length))
    want = nemotron_h.mamba_chunked(*args)
    _as_on_a_tpu(monkeypatch)
    with jax.default_matmul_precision(products):
        got = nemotron_h.mamba_chunked(*args)
    rule_out, rule_state = _recurrence(u, p, cfg, ssm0, conv0, length)

    assert float(np.abs(rule_out).max()) > 0.3
    assert np.isfinite(np.asarray(got[0])).all()  # the dead rows too
    scale = float(np.abs(rule_state).max())
    for tol, (out, state, _) in ((TOL["float32"], want),
                                 (TOL[products], got)):
        np.testing.assert_allclose(out[:length], rule_out, atol=tol, rtol=0)
        np.testing.assert_allclose(
            state, rule_state, atol=tol * scale, rtol=0
        )
    tol = TOL[products]
    np.testing.assert_allclose(
        got[0][:length], want[0][:length], atol=tol, rtol=0
    )
    np.testing.assert_allclose(got[1], want[1], atol=tol * scale, rtol=0)
    np.testing.assert_array_equal(got[2], want[2])


def test_no_token_live_leaves_the_state_as_it_was(monkeypatch, full_products):
    """``length`` 0 (a chunk program past its prompt's end): no chunk is
    computed, the state comes back bit for bit, the outputs are finite."""
    cfg = _cfg(WIDE, 2, 2, 128)
    p, u, ssm0, conv0 = _case(cfg, 256, 3, False)
    _as_on_a_tpu(monkeypatch)
    out, state, _ = nemotron_h.mamba_chunked(
        u, p, cfg, ssm0, conv0, jnp.int32(0)
    )
    np.testing.assert_array_equal(state, ssm0)
    assert np.isfinite(np.asarray(out)).all()


def test_two_calls_that_carry_the_state_are_one(monkeypatch, full_products):
    """Two calls of 256 tokens, the second from what the first left (of
    its 256 the last 56 padding), are one call of 456 live tokens."""
    cfg = _cfg(WIDE, 2, 2, 128)
    p, u, ssm0, conv0 = _case(cfg, 512, 7, False)
    _as_on_a_tpu(monkeypatch)
    whole, state, tail = nemotron_h.mamba_chunked(
        u, p, cfg, ssm0, conv0, jnp.int32(456)
    )
    first, s1, t1 = nemotron_h.mamba_chunked(
        u[:256], p, cfg, ssm0, conv0, jnp.int32(256)
    )
    second, s2, t2 = nemotron_h.mamba_chunked(
        u[256:], p, cfg, s1, t1, jnp.int32(200)
    )
    tol = TOL["float32"]
    np.testing.assert_allclose(
        jnp.concatenate([first, second[:200]]), whole[:456], atol=tol, rtol=0
    )
    np.testing.assert_allclose(
        s2, state, atol=tol * float(np.abs(state).max()), rtol=0
    )
    np.testing.assert_array_equal(t2, tail)


def test_the_call_refuses_channels_that_are_not_its_views():
    """x, B and C are block views of one array: the widths must be the
    state's, and N must divide H x P."""
    state = jnp.zeros((4, 8, 16))
    with pytest.raises(ValueError, match="channels"):
        ssd_chunk.ssd_chunk_rule(
            jnp.zeros((8, 4 * 8 + 2 * 16 + 1)), jnp.zeros((8, 4)),
            -jnp.ones(4), jnp.ones(4), state, jnp.int32(8), groups=1,
            chunk=8, interpret=True,
        )
    with pytest.raises(ValueError, match="chunks"):
        ssd_chunk.ssd_chunk_rule(
            jnp.zeros((12, 4 * 8 + 2 * 16)), jnp.zeros((12, 4)),
            -jnp.ones(4), jnp.ones(4), state, jnp.int32(8), groups=1,
            chunk=8, interpret=True,
        )


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_prefill_program_as_on_a_tpu_and_its_counter(
    platform, monkeypatch, full_products
):
    """A whole prefill program with the scan in the kernel gives the
    logits and the cache of the program tier 1 runs, and
    ``ssm_scan_tokens`` counts the live tokens of the Mamba layers either
    way: what passed through, whatever implements it. No chunk of 8 lies
    wholly past the 27 tokens: one that does leaves zeros where XLA's
    form leaves numbers, both of which mean nothing, and the attention
    block after it writes its keys from them."""
    cfg = TINY
    params = nemotron_h.init_params(jax.random.key(0), cfg)
    tokens = np.zeros((1, 32), np.int32)
    tokens[0, :27] = np.arange(1, 28)

    def call(serving):  # the program takes the cache for its own
        return serving.prefill(
            params, tokens, serving.init_cache(4, 16, 1),
            np.asarray([1, 2], np.int32), n_write_pages=2, slot=0, length=27,
        )

    hybrid_kv._prefill_program.cache_clear()
    want = call(cfg.serving())
    if platform == "tpu":
        _as_on_a_tpu(monkeypatch)
        hybrid_kv._prefill_program.cache_clear()
    serving = cfg.serving()
    got = call(serving)
    hybrid_kv._prefill_program.cache_clear()

    tol = TOL["float32"]
    np.testing.assert_allclose(got[0], want[0], atol=10 * tol, rtol=0)
    for name, leaf in want[1].items():
        np.testing.assert_allclose(
            got[1][name], leaf, atol=tol, rtol=0, err_msg=name
        )
    assert serving.counters()["ssm_scan_tokens"] == cfg.count("M") * 27


@pytest.mark.parametrize("tokens", [512, 1024])
def test_a_short_program_keeps_xlas_form_on_a_tpu(tokens, monkeypatch):
    """Under `_SCAN_KERNEL_TOKENS` a TPU scans by XLA's form too
    (Nemotron-3-Nano's programs of 64 to 512 tokens are no faster with
    the call); from it on by the call (Granite's 2,048)."""
    cfg = _cfg(TINY, 2, 2, 8)
    p, u, ssm0, conv0 = _case(cfg, tokens, 5, False)
    called = []
    monkeypatch.setattr(
        nemotron_h, "chip", types.SimpleNamespace(platform=lambda: "tpu")
    )

    def rule(*args, **kwargs):
        called.append(args[0].shape[0])
        return ssd_chunk.ssd_chunk_rule(*args, **kwargs, interpret=True)

    monkeypatch.setattr(nemotron_h, "ssd_chunk_rule", rule)
    nemotron_h.mamba_chunked(u, p, cfg, ssm0, conv0, jnp.int32(tokens))
    assert called == ([tokens] if tokens >= 1024 else [])
