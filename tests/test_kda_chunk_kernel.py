"""ops/pallas/kda_chunk.py interpreted, against what it replaces on a
TPU: everything of `models/glm5_next.py kda_chunked` between the
in-projections' matmuls and the out-projection's, in XLA's own
operations (`_kda_decay`, `_kda_conv`, `_kda_rule`, `_kda_gated`), and
the rule a token a step (`kda_step`'s).

The kernel is called alone, on operands as the matmuls leave them (``[q
| k | v]`` before the convolution with the rows before them and the
taps, the decay's pre-activation with ``dt_bias`` and ``A_log``,
``beta``, the output gate's pre-activation and the head norm's weight):
the live positions' gated, normed outputs and the state after the last
live token must agree with XLA's chain and with the recurrence between
the same passes, the state to the tolerance tests/test_glm5_next.py
holds the XLA form to and the output to that over the size of ``o``,
which the head norm divides by. Compiled for a described v5e at the
served shape in tests/test_tpu_aot_compile.py.
"""

import dataclasses
import functools
import types
from typing import NamedTuple

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
TAIL = TINY.conv_kernel - 1


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


class Operands(NamedTuple):
    """What `kda_chunked` hands the kernel, in its order."""

    qkv: jax.Array  # [T, 3 H dk]
    conv0: jax.Array  # [K - 1, 3 H dk]
    conv_w: jax.Array  # [K, 3 H dk]
    low: jax.Array  # [T, H dk]
    dt_bias: jax.Array  # [H, dk]
    A_log: jax.Array  # [H]
    beta: jax.Array  # [T, H]
    gate: jax.Array  # [T, H dk]
    gate_norm: jax.Array  # [dk]
    state0: jax.Array  # [H, dk, dk]

    def rows(self, start, stop):
        """The same sequence's tokens start..stop (not its conv0 or
        state0, which are what lies before token 0)."""
        return self._replace(**{
            name: getattr(self, name)[start:stop]
            for name in ("qkv", "low", "beta", "gate")
        })


def _operands(tokens, heads, dim, seed, gates, zero_state, zero_tail=False):
    """Operands as the matmuls of `kda_chunked` leave them. ``gates``:
    "random" (the decay's pre-activation normal: g over ``[lower, 0]`` a
    channel), "lower" (so far up that the sigmoid is 1 and g the lower
    bound on every channel of every live token: the case the sub-chunk
    reference exists for) or "none" (so far down that g is 0: no
    decay)."""
    keys = jax.random.split(jax.random.key(seed), 10)
    width = heads * dim
    low = {
        "random": 2.0 * jax.random.normal(keys[3], (tokens, width)),
        "lower": jnp.full((tokens, width), 1e4),
        "none": jnp.full((tokens, width), -1e4),
    }[gates]
    conv0 = jax.random.normal(keys[1], (TAIL, 3 * width))
    state0 = jax.random.normal(keys[9], (heads, dim, dim))
    return Operands(
        qkv=3.0 * jax.random.normal(keys[0], (tokens, 3 * width)),
        conv0=jnp.zeros_like(conv0) if zero_tail else conv0,
        conv_w=jax.random.uniform(
            keys[2], (TAIL + 1, 3 * width), minval=-0.5, maxval=0.5
        ),
        low=low,
        dt_bias=0.5 * jax.random.normal(keys[4], (heads, dim)),
        A_log=jnp.log(jax.random.uniform(keys[5], (heads,), minval=1.0, maxval=16.0)),
        beta=jax.nn.sigmoid(jax.random.normal(keys[6], (tokens, heads))),
        gate=jax.random.normal(keys[7], (tokens, width)),
        gate_norm=1.0 + 0.1 * jax.random.normal(keys[8], (dim,)),
        state0=jnp.zeros_like(state0) if zero_state else state0,
    )


def _config(ops, dtype=jnp.float32):
    heads, dim, _ = ops.state0.shape
    return dataclasses.replace(
        TINY, kda_heads=heads, kda_head_dim=dim, dtype=dtype
    )


def _rule_operands(ops, length):
    """q, k, v, beta, g as XLA's passes make them for `_kda_rule` (what
    `kda_chunked` does off the TPU), and the convolution's tail."""
    cfg = _config(ops)
    p = ops._asdict()
    (q, k, v), conv_end = glm5_next._kda_conv(ops.qkv, ops.conv0, p, cfg, length)
    live = jnp.arange(len(ops.qkv)) < length
    beta = jnp.where(live[:, None], ops.beta, 0.0)
    g = jnp.where(live[:, None, None], glm5_next._kda_decay(ops.low, p, cfg), 0.0)
    return (q, k, v, beta, g), conv_end


def _kernel(ops, length, chunk, dtype=jnp.float32):
    return kda_chunk.kda_chunk_rule(
        *ops, jnp.int32(length), chunk=chunk, sub=min(SUB, chunk),
        lower=LOWER, l2_eps=glm5_next._L2_EPS, norm_eps=TINY.norm_eps,
        dtype=dtype, interpret=True,
    )


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
    # The convolution's window: fewer live tokens than it reaches back,
    # one token past a token block's edge (its taps meet the block
    # before's last rows), and fewer tokens than a group may hold.
    "a_length_under_the_window": (64, TAIL - 1, "random", False),
    "one_live_token": (32, 1, "random", False),
    "a_window_across_a_block_edge": (2 * 128, 128 + 1, "random", False),
    "fewer_tokens_than_a_tile": (5, 3, "random", False),
}


def _check(ops, length, chunk):
    """The kernel, interpreted, against XLA's chain and the recurrence
    between the same passes, on the same operands."""
    cfg = _config(ops)
    p = ops._asdict()
    sub = min(SUB, chunk)
    rule_ops, _ = _rule_operands(ops, length)
    want_o, want_state = glm5_next._kda_rule(*rule_ops, ops.state0, chunk, sub)
    want_out = glm5_next._kda_gated(want_o, ops.gate, p, cfg)
    got_out, got_state = _kernel(ops, length, chunk)
    assert got_out.shape == want_out.shape and got_out.dtype == want_out.dtype
    assert np.isfinite(np.asarray(got_out)).all()  # the dead rows too
    if length == 0:
        np.testing.assert_array_equal(got_state, ops.state0)
        return
    rule_o, rule_state = _recurrence(*(a[:length] for a in rule_ops), ops.state0)
    assert float(np.abs(rule_o).max()) > 0.02  # a thousand times TOL
    rule_out = glm5_next._kda_gated(rule_o, ops.gate[:length], p, cfg)
    # The head norm divides o by its size over a head's channels: an
    # error of o is that many times larger in the output.
    size = jnp.repeat(
        jnp.sqrt(jnp.mean(rule_o * rule_o, axis=-1)), cfg.kda_head_dim, axis=-1
    )  # [length, H dk]

    def as_in_o(a, b):
        return float(jnp.max(jnp.abs(a[:length] - b[:length]) * size))

    for out, state in ((want_out, want_state), (got_out, got_state)):
        assert as_in_o(out, rule_out) < 2 * TOL
        np.testing.assert_allclose(state, rule_state, atol=TOL, rtol=0)
    assert as_in_o(got_out, want_out) < 2 * TOL
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
    (32, "served", (2 * 128, 128 + 2, "random", False)),
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
    ops = _operands(tokens, heads, dim, chunk + tokens, gates, zero_state)
    _check(ops, length, chunk)


def test_no_rows_before_the_sequence_are_rows_of_zeros():
    """A sequence's first chunk has a zero tail: the same as the taps
    meeting nothing."""
    ops = _operands(64, TINY.kda_heads, TINY.kda_head_dim, 11, "random", True,
                    zero_tail=True)
    _check(ops, 50, 32)


def test_the_output_leaves_in_the_models_dtype():
    """bfloat16, as `_kda_gated` casts before ``W_o``: the float32
    result rounded once (a neighbouring value where the two float32
    results straddle a rounding edge)."""
    ops = _operands(128, TINY.kda_heads, TINY.kda_head_dim, 5, "random", False)
    exact, state = _kernel(ops, 100, 32)
    rounded, same = _kernel(ops, 100, 32, dtype=jnp.bfloat16)
    assert rounded.dtype == jnp.bfloat16
    np.testing.assert_array_equal(rounded[:100], exact[:100].astype(jnp.bfloat16))
    np.testing.assert_array_equal(same, state)
    rule_ops, _ = _rule_operands(ops, 100)
    want = glm5_next._kda_gated(
        glm5_next._kda_rule(*rule_ops, ops.state0, 32, SUB)[0], ops.gate,
        ops._asdict(), _config(ops, jnp.bfloat16),
    )
    np.testing.assert_allclose(
        rounded[:100].astype(jnp.float32), want[:100].astype(jnp.float32),
        rtol=2**-7, atol=1e-4,
    )


def test_no_exponent_passes_what_float32_holds():
    """`_kda_rule`'s overflow argument is the kernel's: with every gate
    at the lower bound for a whole chunk of 32 the running sum reaches
    -160 a channel, ``exp(-gamma)`` alone would be e^160, and every
    factor the kernel forms is an exponential of at most ``sub x |lower|
    / 2``; the outputs are finite and the recurrence's. A bound a config
    could pass is refused where the config is made."""
    assert SUB * -LOWER / 2 <= 85.0
    ops = _operands(128, 2, 16, 3, "lower", False)
    rule_ops, _ = _rule_operands(ops, 128)
    np.testing.assert_array_equal(rule_ops[4], LOWER)  # g, every channel
    _check(ops, 128, 32)
    with pytest.raises(ValueError, match="float32's exp"):
        glm5_next.Glm5NextConfig(kda_lower=-12.0)


def test_two_calls_that_carry_the_state_and_the_tail_are_one():
    """Two calls of 64 tokens, the second from the state AND the
    convolution's tail the first left (of its 64 the last 9 padding),
    are one call of 119."""
    ops = _operands(128, TINY.kda_heads, TINY.kda_head_dim, 7, "random", False)
    whole, state = _kernel(ops, 119, 32)
    first, s1 = _kernel(ops.rows(0, 64), 64, 32)
    tail = glm5_next._conv_tail(ops.qkv[:64], ops.conv0, jnp.int32(64))
    second, s2 = _kernel(
        ops.rows(64, 128)._replace(state0=s1, conv0=tail), 55, 32
    )
    np.testing.assert_allclose(
        jnp.concatenate([first, second[:55]]), whole[:119], atol=TOL, rtol=0
    )
    np.testing.assert_allclose(s2, state, atol=TOL, rtol=0)


@pytest.mark.parametrize("tokens", [2, 3, 64])
def test_the_tail_is_the_concatenations_without_it(tokens):
    """`_conv_tail` (a slice of K - 1 rows of qkv beside conv0) is
    `_kda_conv`'s slice of ``[conv0; qkv]`` at every length, under K - 1
    tokens too."""
    ops = _operands(tokens, 2, 16, tokens, "random", True)
    for length in range(tokens + 1):
        np.testing.assert_array_equal(
            glm5_next._conv_tail(ops.qkv, ops.conv0, jnp.int32(length)),
            _rule_operands(ops, length)[1], err_msg=str(length),
        )


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
