"""ops/pallas/kda_chunk.py interpreted, against what it replaces on a
TPU: the chunked per-channel delta rule of `models/glm5_next.py
kda_chunked` in XLA's own operations (`_kda_rule`), and the rule a token
a step (`kda_step`'s).

The rule is called alone, on operands as `kda_chunked` hands them
(unit-length keys, ``q`` times ``dk^-0.5``, ``g`` in ``[lower, 0]`` a
channel, ``beta`` and ``g`` 0 from ``length`` on): the live positions'
outputs and the state after the last live token must agree with XLA's
form and with the recurrence to the tolerance tests/test_glm5_next.py
holds the XLA form to. Compiled for a described v5e at the served shape
in tests/test_tpu_aot_compile.py.
"""

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import hybrid_kv
from ray_tpu.models import glm5_next
from ray_tpu.ops.pallas import kda_chunk

TINY = glm5_next.GLM5_NEXT_PRESETS["glm5_next_tiny"]
LOWER = TINY.kda_lower
TOL = 2e-5
SUB = glm5_next._KDA_SUBCHUNK


def _as_on_a_tpu(monkeypatch):
    """`kda_chunked` takes the kernel, interpreted, as it does on a TPU
    (nobody else is asked: `moe_ffn` would take its kernels compiled)."""
    monkeypatch.setattr(
        glm5_next, "chip", types.SimpleNamespace(platform=lambda: "tpu")
    )
    monkeypatch.setattr(
        glm5_next, "kda_chunk_rule",
        functools.partial(kda_chunk.kda_chunk_rule, interpret=True),
    )


def _operands(tokens, heads, dim, length, seed, gates, zero_state):
    """q, k, v, beta, g, state0 as `kda_chunked` hands them to its rule.
    ``gates``: "random" (g uniform in [lower, 0] a channel), "lower" (g
    at the lower bound on every channel of every live token: the case the
    sub-chunk reference exists for) or "none" (g 0: no decay)."""
    keys = jax.random.split(jax.random.key(seed), 6)
    shape = (tokens, heads, dim)
    q = glm5_next._unit(jax.random.normal(keys[0], shape)) * dim**-0.5
    k = glm5_next._unit(jax.random.normal(keys[1], shape))
    v = jax.random.normal(keys[2], shape)
    beta = jax.nn.sigmoid(jax.random.normal(keys[3], (tokens, heads)))
    g = {
        "random": LOWER * jax.random.uniform(keys[4], shape),
        "lower": jnp.full(shape, LOWER),
        "none": jnp.zeros(shape),
    }[gates]
    live = jnp.arange(tokens) < length
    beta = jnp.where(live[:, None], beta, 0.0)
    g = jnp.where(live[:, None, None], g, 0.0)
    state0 = jax.random.normal(keys[5], (heads, dim, dim))
    if zero_state:
        state0 = jnp.zeros_like(state0)
    return q, k, v, beta, g, state0


@jax.jit
def _recurrence(q, k, v, beta, g, state):
    """The rule a token a step, float32 elementwise (`kda_step`'s):
    (o [T, H, dv], the state after the last token)."""

    def step(s, x):
        q_t, k_t, v_t, beta_t, g_t = x
        s = s * jnp.exp(g_t)[..., None]
        k_col = k_t[..., None]
        read = (s * k_col).sum(-2)
        s = s + k_col * (beta_t[..., None] * (v_t - read))[..., None, :]
        return s, (s * q_t[..., None]).sum(-2)

    end, o = jax.lax.scan(step, state, (q, k, v, beta, g))
    return o, end


# name: (tokens, live tokens, the gates, zero state before)
CALLS = {
    "every_token_live": (64, 64, "random", True),
    "the_last_tenth_padding": (128, 115, "random", True),
    "a_length_inside_a_group": (2 * 128, 128 + 45, "random", True),
    "a_length_at_a_groups_edge": (3 * 128, 128, "random", True),
    "no_token_live": (128, 0, "random", False),
    "from_a_state": (64, 64, "random", False),
    "a_state_and_padding_no_chunk_divides": (128 + 37, 128 + 29, "random", False),
    "every_gate_at_its_lower_bound": (128, 128, "lower", False),
    "the_lower_bound_and_padding": (64, 50, "lower", False),
    "no_decay": (128, 128, "none", False),
}


def _check(ops, length, chunk):
    """The kernel, interpreted, against XLA's form and the recurrence on
    the same operands."""
    sub = min(SUB, chunk)
    want_o, want_state = glm5_next._kda_rule(*ops, chunk, sub)
    got_o, got_state = kda_chunk.kda_chunk_rule(
        *ops, jnp.int32(length), chunk=chunk, sub=sub, interpret=True
    )
    got_o = got_o.reshape(want_o.shape)
    assert np.isfinite(np.asarray(got_o)).all()  # the dead rows too
    if length == 0:
        np.testing.assert_array_equal(got_state, ops[5])
        return
    rule_o, rule_state = _recurrence(*(a[:length] for a in ops[:5]), ops[5])
    assert float(np.abs(rule_o).max()) > 0.02  # a thousand times TOL
    for o, state in ((want_o, want_state), (got_o, got_state)):
        np.testing.assert_allclose(o[:length], rule_o, atol=TOL, rtol=0)
        np.testing.assert_allclose(state, rule_state, atol=TOL, rtol=0)
    np.testing.assert_allclose(got_o[:length], want_o[:length], atol=TOL, rtol=0)
    np.testing.assert_allclose(got_state, want_state, atol=TOL, rtol=0)


# chunk 16 is one sub-chunk a chunk (one score product), 32 the served
# two, 64 four (a product a distance 0..3, which no configuration asks
# for today and `Glm5NextConfig.kda_chunk` may). "served": ONE head of
# 128 x 128, the published width, where a group is the matrix unit's 128
# rows, the last tenth padding; 2,048 tokens cross sixteen groups.
CASES = [
    *((chunk, "tiny", call) for chunk in (16, 32) for call in CALLS),
    (64, "tiny", "a_length_inside_a_group"),
    (64, "tiny", "the_lower_bound_and_padding"),
    (32, "tiny", (2048, 1843, "random", False)),
    *((32, "served", (tokens, tokens * 9 // 10, gates, False))
      for tokens in (64, 128, 2048) for gates in ("random", "lower")),
]


def _case_id(case):
    chunk, width, call = case
    name = call if isinstance(call, str) else "{}_of_{}_{}".format(*call[1::-1], call[2])
    return f"{chunk}-{width}-{name}"


@pytest.mark.parametrize("case", CASES, ids=_case_id)
def test_kernel_is_the_xla_form_and_the_recurrence(case):
    chunk, width, call = case
    tokens, length, gates, zero_state = CALLS.get(call, call)
    heads, dim = (TINY.kda_heads, TINY.kda_head_dim) if width == "tiny" else (1, 128)
    ops = _operands(
        tokens, heads, dim, length, chunk + tokens, gates, zero_state
    )
    _check(ops, length, chunk)


def test_no_exponent_passes_what_float32_holds():
    """`_kda_rule`'s overflow argument is the kernel's: with every gate
    at the lower bound for a whole chunk of 32 the running sum reaches
    -160 a channel, ``exp(-gamma)`` alone would be e^160, and every
    factor the kernel forms is an exponential of at most ``sub x |lower|
    / 2``; the outputs are finite and the recurrence's. A bound a config
    could pass is refused where the config is made."""
    assert SUB * -LOWER / 2 <= 85.0
    ops = _operands(128, 2, 16, 128, 3, "lower", False)
    got_o, got_state = kda_chunk.kda_chunk_rule(
        *ops, jnp.int32(128), chunk=32, sub=SUB, interpret=True
    )
    rule_o, rule_state = _recurrence(*ops)
    assert np.isfinite(np.asarray(got_o)).all()
    np.testing.assert_allclose(
        got_o.reshape(rule_o.shape), rule_o, atol=TOL, rtol=0
    )
    np.testing.assert_allclose(got_state, rule_state, atol=TOL, rtol=0)
    with pytest.raises(ValueError, match="float32's exp"):
        glm5_next.Glm5NextConfig(kda_lower=-12.0)


def test_two_calls_that_carry_the_state_are_one():
    """Two calls of 64 tokens, the second from what the first left (of
    its 64 the last 9 padding), are one call of 119."""
    ops = _operands(128, TINY.kda_heads, TINY.kda_head_dim, 119, 7, "random", False)
    rule = functools.partial(
        kda_chunk.kda_chunk_rule, chunk=32, sub=SUB, interpret=True
    )
    whole, state = rule(*ops, jnp.int32(119))
    first, s1 = rule(*(a[:64] for a in ops[:5]), ops[5], jnp.int32(64))
    second, s2 = rule(*(a[64:] for a in ops[:5]), s1, jnp.int32(55))
    np.testing.assert_allclose(
        jnp.concatenate([first, second[:55]]), whole[:119], atol=TOL, rtol=0
    )
    np.testing.assert_allclose(s2, state, atol=TOL, rtol=0)


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_the_mixer_and_a_prefill_program_as_on_a_tpu(platform, monkeypatch):
    """`kda_chunked` and a whole prefill program with the rule in the
    kernel give the mixer's output, the logits and the cache of the
    program tier 1 runs; ``kda_scan_tokens`` counts the same live tokens
    either way (the kernel has no counter of its own: its witness on the
    chip is the trace)."""
    cfg = TINY
    params = glm5_next.init_params(jax.random.key(0), cfg)
    tokens = np.zeros((1, 32), np.int32)
    tokens[0, :21] = np.arange(1, 22)
    block = params["blocks"][0]
    u = jax.random.normal(jax.random.key(1), (40, cfg.d_model))
    state0 = jax.random.normal(
        jax.random.key(2), (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_head_dim)
    )
    conv0 = jax.random.normal(
        jax.random.key(3), (cfg.conv_kernel - 1, cfg.kda_conv_dim)
    )

    def call(serving):  # the program takes the cache for its own
        return serving.prefill(
            params, tokens, serving.init_cache(4, 16, 1),
            np.asarray([1, 2], np.int32), n_write_pages=2, slot=0, length=21,
        )

    hybrid_kv._prefill_program.cache_clear()
    want = call(cfg.serving())
    want_mixer = glm5_next.kda_chunked(u, block, cfg, state0, conv0, jnp.int32(33))
    if platform == "tpu":
        _as_on_a_tpu(monkeypatch)
        hybrid_kv._prefill_program.cache_clear()
    serving = cfg.serving()
    got = call(serving)
    got_mixer = glm5_next.kda_chunked(u, block, cfg, state0, conv0, jnp.int32(33))
    hybrid_kv._prefill_program.cache_clear()

    np.testing.assert_allclose(
        got_mixer[0][:33], want_mixer[0][:33], atol=10 * TOL, rtol=0
    )
    np.testing.assert_allclose(got_mixer[1], want_mixer[1], atol=TOL, rtol=0)
    np.testing.assert_array_equal(got_mixer[2], want_mixer[2])
    np.testing.assert_allclose(got[0], want[0], atol=10 * TOL, rtol=0)
    for name, leaf in want[1].items():
        np.testing.assert_allclose(
            got[1][name], leaf, atol=10 * TOL, rtol=0, err_msg=name
        )
    assert serving.counters()["kda_scan_tokens"] == cfg.count("K") * 21


def test_off_the_tpu_the_mixer_lowers_without_the_kernel():
    """Here `kda_chunked` traces `_kda_rule`: no kernel call in its
    lowered text, the scan over the rule chunks in it."""
    cfg = TINY
    block = jax.eval_shape(
        lambda: glm5_next._init_kda(jax.random.key(0), cfg=cfg)
    )
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    text = jax.jit(
        functools.partial(glm5_next.kda_chunked, cfg=cfg)
    ).lower(
        f32((64, cfg.d_model)), block,
        state0=f32((cfg.kda_heads, cfg.kda_head_dim, cfg.kda_head_dim)),
        conv0=f32((cfg.conv_kernel - 1, cfg.kda_conv_dim)),
        length=jax.ShapeDtypeStruct((), jnp.int32),
    ).as_text()
    assert "tpu_custom_call" not in text and "stablehlo.while" in text
