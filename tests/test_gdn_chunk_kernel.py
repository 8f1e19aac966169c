"""ops/pallas/gdn_chunk.py interpreted, against what it replaces on a
TPU: the chunked gated delta rule of `models/qwen3_next.py gdn_chunked`
in XLA's own operations, and the rule a token a step.

`gdn_chunked` is run twice on the same input, as it traces here (XLA's
form, tier 1's path) and as it traces on a TPU (the kernel, interpreted):
the live positions' outputs, the state after the last live token and the
convolution tail must agree to the tolerance tests/test_qwen3_next.py
holds the XLA form to against the recurrence, and both must lie that
close to the recurrence itself. Compiled for a described v5e at the
served shape in tests/test_tpu_aot_compile.py.
"""

import dataclasses
import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import hybrid_kv
from ray_tpu.models import qwen3_next
from ray_tpu.ops.pallas import gdn_chunk

TINY = qwen3_next.QWEN3_NEXT_PRESETS["qwen3_next_tiny"]
TOL = 2e-5  # tests/test_qwen3_next.py's, for the XLA form


def _cfg(chunk, rep):
    return dataclasses.replace(
        TINY, gdn_chunk=chunk, gdn_value_heads=rep * TINY.gdn_key_heads
    )


def _as_on_a_tpu(monkeypatch):
    """`gdn_chunked` takes the kernel, interpreted, as it does on a TPU
    (nobody else is asked: `moe_ffn` would take its kernels compiled)."""
    monkeypatch.setattr(
        qwen3_next, "chip", types.SimpleNamespace(platform=lambda: "tpu")
    )
    monkeypatch.setattr(
        qwen3_next, "gdn_chunk_rule",
        functools.partial(gdn_chunk.gdn_chunk_rule, interpret=True),
    )


def _case(cfg, tokens, seed, zero_state):
    """A mixer's parameters, a normed input, the state and the tail
    before it: (p, u, state0, conv0)."""
    keys = jax.random.split(jax.random.key(seed), 4)
    p = qwen3_next._init_gdn(keys[0], cfg=cfg)
    u = jax.random.normal(keys[1], (tokens, cfg.d_model))
    shape = (cfg.gdn_value_heads, cfg.gdn_key_dim, cfg.gdn_value_dim)
    state0 = jax.random.normal(keys[2], shape)
    conv0 = jax.random.normal(keys[3], (cfg.conv_kernel - 1, cfg.gdn_conv_dim))
    if zero_state:
        state0, conv0 = jnp.zeros_like(state0), jnp.zeros_like(conv0)
    return p, u, state0, conv0


def _recurrence(u, p, cfg, state0, conv0, n):
    """The mixer's rule on the first ``n`` of u's tokens, a token a step
    in a Python loop: (out [n, d], the state after them)."""
    qkv, z, ba = qwen3_next._project_in(u, p, cfg)
    taps = cfg.conv_kernel
    seq = jnp.concatenate([conv0, qkv])
    conv = sum(seq[j: j + len(u)] * p["conv_w"][j] for j in range(taps))
    q, k, v = qwen3_next._split_qkv(jax.nn.silu(conv), cfg)
    beta, g = qwen3_next._gates(ba, p, cfg)
    hk = cfg.gdn_key_heads
    state = state0.reshape(hk, -1, cfg.gdn_key_dim, cfg.gdn_value_dim)
    outs = []
    for t in range(n):
        state = state * jnp.exp(g[t])[..., None, None]
        read = jnp.einsum("hrkv,hk->hrv", state, k[t])
        delta = beta[t][..., None] * (v[t] - read)
        state = state + k[t][:, None, :, None] * delta[..., None, :]
        outs.append(jnp.einsum("hrkv,hk->hrv", state, q[t]))
    out = qwen3_next._project_out(
        jnp.stack(outs).reshape(n, -1), z[:n], p, cfg
    )
    return out, state.reshape(state0.shape)


# name: (tokens, live tokens, zero state before)
CALLS = {
    "every_token_live": (37, 37, True),
    "a_length_no_chunk_divides": (37, 29, True),
    "from_a_state_and_a_tail": (37, 29, False),
    # Four groups of 128 tokens, two a grid step: the first holds whole
    # rule chunks past the length, computed under the mask; the second
    # lies past it and is left out; the second step's block is never
    # fetched.
    "whole_chunks_dead": (2 * gdn_chunk._GROUPS_A_STEP * 128 - 40, 83, False),
}


@pytest.mark.parametrize("call", list(CALLS))
@pytest.mark.parametrize("rep", [1, 2])
@pytest.mark.parametrize("chunk", [4, 16, 32])
def test_kernel_is_the_xla_form_and_the_recurrence(
    chunk, rep, call, monkeypatch
):
    tokens, length, zero_state = CALLS[call]
    cfg = _cfg(chunk, rep)
    p, u, state0, conv0 = _case(cfg, tokens, chunk + rep, zero_state)
    args = (u, p, cfg, state0, conv0, jnp.int32(length))
    want = qwen3_next.gdn_chunked(*args)
    _as_on_a_tpu(monkeypatch)
    got = qwen3_next.gdn_chunked(*args)
    rule_out, rule_state = _recurrence(u, p, cfg, state0, conv0, length)

    assert float(np.abs(rule_out).max()) > 0.3
    assert np.isfinite(np.asarray(got[0])).all()  # the dead rows too
    for out, state, tail in (want, got):
        np.testing.assert_allclose(out[:length], rule_out, atol=TOL, rtol=0)
        np.testing.assert_allclose(state, rule_state, atol=TOL, rtol=0)
    np.testing.assert_allclose(
        got[0][:length], want[0][:length], atol=TOL, rtol=0
    )
    np.testing.assert_allclose(got[1], want[1], atol=TOL, rtol=0)
    np.testing.assert_array_equal(got[2], want[2])


def test_no_token_live_leaves_the_state_as_it_was(monkeypatch):
    """``length`` 0 (a chunk program past its prompt's end): no group is
    computed, the state comes back bit for bit, the outputs are finite."""
    cfg = _cfg(8, 2)
    p, u, state0, conv0 = _case(cfg, 24, 3, False)
    _as_on_a_tpu(monkeypatch)
    out, state, _ = qwen3_next.gdn_chunked(
        u, p, cfg, state0, conv0, jnp.int32(0)
    )
    np.testing.assert_array_equal(state, state0)
    assert np.isfinite(np.asarray(out)).all()


def test_two_calls_that_carry_the_state_are_one(monkeypatch):
    """Two calls of 24 tokens, the second from what the first left (of
    its 24 the last 5 padding), are one call of 43."""
    cfg = _cfg(8, 2)
    p, u, state0, conv0 = _case(cfg, 48, 7, False)
    _as_on_a_tpu(monkeypatch)
    whole, state, tail = qwen3_next.gdn_chunked(
        u, p, cfg, state0, conv0, jnp.int32(43)
    )
    first, s1, t1 = qwen3_next.gdn_chunked(
        u[:24], p, cfg, state0, conv0, jnp.int32(24)
    )
    second, s2, t2 = qwen3_next.gdn_chunked(
        u[24:], p, cfg, s1, t1, jnp.int32(19)
    )
    np.testing.assert_allclose(
        jnp.concatenate([first, second[:19]]), whole[:43], atol=TOL, rtol=0
    )
    np.testing.assert_allclose(s2, state, atol=TOL, rtol=0)
    np.testing.assert_array_equal(t2, tail)


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_prefill_program_as_on_a_tpu_and_its_counter(platform, monkeypatch):
    """A whole prefill program with the rule in the kernel gives the
    logits and the cache of the program tier 1 runs, and
    ``gdn_kernel_tokens`` counts the live tokens of the Gated DeltaNet
    layers where the rule ran in the kernel: all of them as on a TPU,
    none here."""
    cfg = TINY
    params = qwen3_next.init_params(jax.random.key(0), cfg)
    tokens = np.zeros((1, 32), np.int32)
    tokens[0, :21] = np.arange(1, 22)

    def call(serving):  # the program takes the cache for its own
        return serving.prefill(
            params, tokens, serving.init_cache(4, 16, 1),
            np.asarray([1, 2], np.int32), n_write_pages=2, slot=0, length=21,
        )

    hybrid_kv._prefill_program.cache_clear()
    want = call(cfg.serving())
    if platform == "tpu":
        _as_on_a_tpu(monkeypatch)
        monkeypatch.setattr(hybrid_kv, "chip", qwen3_next.chip)
        hybrid_kv._prefill_program.cache_clear()
    serving = cfg.serving()
    got = call(serving)
    hybrid_kv._prefill_program.cache_clear()

    np.testing.assert_allclose(got[0], want[0], atol=10 * TOL, rtol=0)
    for name, leaf in want[1].items():
        np.testing.assert_allclose(
            got[1][name], leaf, atol=TOL, rtol=0, err_msg=name
        )
    counters = serving.counters()
    assert counters["gdn_scan_tokens"] == cfg.count("G") * 21
    assert counters["gdn_kernel_tokens"] == (
        counters["gdn_scan_tokens"] if platform == "tpu" else 0
    )
