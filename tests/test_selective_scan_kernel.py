"""Mamba-1's two kernels (ops/pallas/selective_scan.py) in Pallas
interpret mode against ``lax.scan`` a token a step in float32: a carried
state, padding positions, a chunk that is all padding, inactive slots;
and Phi-4-mini-flash's differential attention through each of the three
attention kernels (the band, the prefill and the paged decode kernel, as
`models/phi4_flash.py` hands them paired heads) against dense scores
written out a head at a time.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import phi4_flash
from ray_tpu.models.phi4_flash import PHI4_FLASH_PRESETS
from ray_tpu.ops.pallas.paged_attention import paged_attention
from ray_tpu.ops.pallas.prefill_attention import prefill_attention
from ray_tpu.ops.pallas.selective_scan import (
    selective_scan_chunk,
    selective_scan_reference,
    selective_state_step,
)
from ray_tpu.ops.pallas.state_step import live_order
from ray_tpu.ops.pallas.window_attention import window_attention

T, WIDTH, N = 32, 256, 16


def _operands(seed, t=T, width=WIDTH, n=N, dtype=jnp.float32):
    keys = jax.random.split(jax.random.key(seed), 8)
    tile = (n, width // 128, 128)
    return {
        "x": jax.random.normal(keys[0], (t, width)).astype(dtype),
        "dt": jax.random.normal(keys[1], (t, width)).astype(dtype),
        "b": jax.random.normal(keys[2], (t, n)).astype(dtype),
        "c": jax.random.normal(keys[3], (t, n)).astype(dtype),
        "a": -jnp.exp(jax.random.normal(keys[4], tile)),
        "d": jax.random.normal(keys[5], (width,)),
        "dt_bias": jax.random.normal(keys[6], (width,)),
        "h0": jax.random.normal(keys[7], tile),
    }


def _token_by_token(ops, length):
    """The recurrence written out with the state as the published
    description has it, [d_inner, N], a Python loop a token."""
    a = np.asarray(ops["a"]).reshape(N, -1).T  # [d_inner, N]
    h = np.asarray(ops["h0"]).reshape(N, -1).T.copy()
    x, b, c = (np.asarray(ops[k], np.float32) for k in ("x", "b", "c"))
    dt = np.asarray(jax.nn.softplus(
        ops["dt"].astype(jnp.float32) + ops["dt_bias"]
    ))
    ys = []
    for t in range(length):
        h = np.exp(dt[t][:, None] * a) * h + (dt[t] * x[t])[:, None] * b[t]
        ys.append((h * c[t]).sum(-1) + np.asarray(ops["d"]) * x[t])
    return np.stack(ys) if ys else np.zeros((0, x.shape[1])), h


@pytest.mark.parametrize("length", [T, 20, 7, 0],
                         ids=["full", "padded", "one-block", "all-padding"])
def test_chunk_kernel_is_the_recurrence(length):
    """Blocks of 8 tokens: the state carried across four grid steps from
    a state that is not zero; positions from ``length`` on take no step
    (a block that is all padding is skipped); every row of y is
    finite."""
    ops = _operands(1)
    y, end = selective_scan_chunk(
        **ops, length=jnp.int32(length), block_t=8, interpret=True
    )
    want_y, want_end = _token_by_token(ops, length)
    np.testing.assert_allclose(y[:length], want_y, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(
        np.asarray(end).reshape(N, -1).T, want_end, atol=2e-5, rtol=2e-5
    )
    assert bool(jnp.isfinite(y).all())
    ref_y, ref_end = selective_scan_reference(**ops, length=jnp.int32(length))
    np.testing.assert_allclose(y[:length], ref_y[:length], atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(end, ref_end, atol=2e-5, rtol=2e-5)


def test_chunk_kernel_takes_bfloat16_at_its_edge():
    """x, dt, B and C as the serving program hands them (bfloat16), the
    state float32 throughout: the kernel and XLA's form read the same
    rounded inputs."""
    ops = _operands(2, dtype=jnp.bfloat16)
    y, end = selective_scan_chunk(
        **ops, length=jnp.int32(27), block_t=16, interpret=True
    )
    ref_y, ref_end = selective_scan_reference(**ops, length=jnp.int32(27))
    assert y.dtype == jnp.bfloat16 and end.dtype == jnp.float32
    np.testing.assert_allclose(
        y[:27].astype(jnp.float32), ref_y[:27].astype(jnp.float32),
        atol=0.05, rtol=0.02,
    )
    np.testing.assert_allclose(end, ref_end, atol=2e-5, rtol=2e-5)


def test_two_chunks_are_one():
    ops = _operands(3)
    whole_y, whole_end = selective_scan_chunk(
        **ops, length=jnp.int32(T), block_t=8, interpret=True
    )
    first = {k: (v[:16] if k in ("x", "dt", "b", "c") else v)
             for k, v in ops.items()}
    y1, mid = selective_scan_chunk(
        **first, length=jnp.int32(16), block_t=8, interpret=True
    )
    second = {k: (v[16:] if k in ("x", "dt", "b", "c") else v)
              for k, v in ops.items()} | {"h0": mid}
    y2, end = selective_scan_chunk(
        **second, length=jnp.int32(16), block_t=8, interpret=True
    )
    np.testing.assert_allclose(
        jnp.concatenate([y1, y2]), whole_y, atol=1e-5, rtol=1e-5
    )
    np.testing.assert_allclose(end, whole_end, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("active", [
    [True, False, True, False], [False, False, False, False],
    [True, True, True, True], [False, False, False, True],
], ids=["some", "none", "all", "last"])
def test_step_kernel_steps_the_decoding_slots_in_place(active):
    """The layer's state of the slots that decode is the recurrence's
    next state; every other slot's and every other layer's stays bit for
    bit; skipped slots' rows of y are zeros."""
    ops = _operands(4, t=4)
    stack = jax.random.normal(jax.random.key(9), (3, 4, N, WIDTH // 128, 128))
    active = jnp.asarray(active)
    dt = jax.nn.softplus(ops["dt"] + ops["dt_bias"])
    new, y = selective_state_step(
        stack, jnp.int32(1), *live_order(active), ops["x"], dt, ops["b"],
        ops["c"], ops["a"], interpret=True,
    )
    a = ops["a"].reshape(N, WIDTH)
    h = stack[1].reshape(4, N, WIDTH) * jnp.exp(dt[:, None, :] * a) + (
        (dt * ops["x"])[:, None, :] * ops["b"][:, :, None]
    )
    want_y = jnp.where(active[:, None], (h * ops["c"][:, :, None]).sum(1), 0.0)
    np.testing.assert_allclose(y, want_y, atol=1e-5, rtol=1e-5)
    live = np.asarray(active)
    np.testing.assert_allclose(
        np.asarray(new[1])[live], np.asarray(h.reshape(stack[1].shape))[live],
        atol=1e-5, rtol=1e-5,
    )
    np.testing.assert_array_equal(np.asarray(new[1])[~live],
                                  np.asarray(stack[1])[~live])
    np.testing.assert_array_equal(new[0], stack[0])
    np.testing.assert_array_equal(new[2], stack[2])


def test_the_mixers_step_is_its_chunk(monkeypatch):
    """`mamba1_step_live` (the kernel, interpreted) after `mamba1_chunked`
    over a prefix is `mamba1_chunked` over the prefix and one token."""
    cfg = PHI4_FLASH_PRESETS["phi4_flash_tiny"]
    p = phi4_flash.init_params(jax.random.key(0), cfg)["blocks"][0]
    u = jax.random.normal(jax.random.key(1), (9, cfg.d_model))
    zero = (jnp.zeros((cfg.ssm_state, cfg.ssm_rows, 128)),
            jnp.zeros((cfg.conv_kernel - 1, cfg.d_inner)))
    (want, want_y), want_state, want_tail = phi4_flash.mamba1_chunked(
        jnp.pad(u, ((0, 7), (0, 0))), p, cfg, *zero, jnp.int32(9)
    )
    _, state, tail = phi4_flash.mamba1_chunked(
        jnp.pad(u[:8], ((0, 8), (0, 0))), p, cfg, *zero, jnp.int32(8)
    )
    monkeypatch.setattr(
        phi4_flash, "selective_state_step",
        lambda *a, **kw: selective_state_step(*a, **kw, interpret=True),
    )
    stack = jnp.zeros((2, 3, *state.shape)).at[1, 2].set(state)
    active = jnp.asarray([False, False, True])
    (out, y), stack, new_tail = phi4_flash.mamba1_step_live(
        jnp.zeros((3, cfg.d_model)).at[2].set(u[8]), p, cfg, stack, 1,
        jnp.zeros((3, *tail.shape)).at[2].set(tail), *live_order(active),
    )
    np.testing.assert_allclose(out[2], want[8], atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(y[2], want_y[8], atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(stack[1, 2], want_state, atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(new_tail[2], want_tail, atol=1e-6)
    assert bool(jnp.isfinite(out).all())


# ---------------------------------------------- differential attention
HEADS, HALF = 8, 64  # 8 query heads of 64 over 4 key/value heads: 2 pairs


def _paired(seed, queries, keys):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (queries, HEADS, HALF))
    k = jax.random.normal(ks[1], (keys, HEADS // 2, HALF))
    v = jax.random.normal(ks[2], (keys, HEADS // 2, HALF))
    return q, k, v


def _dense_pairs(q, k, v, hidden, lam=0.3):
    """The published form a head at a time: pair j's two maps over key
    heads 2g and 2g + 1 (g = j // 2), both on V = [v[2g]; v[2g+1]], the
    first less ``lam`` times the second. hidden [Q, T]. -> [Q, H / 2,
    2 w]."""
    out = []
    for j in range(HEADS // 2):
        g = j // 2
        value = jnp.concatenate([v[:, 2 * g], v[:, 2 * g + 1]], axis=-1)
        maps = []
        for s in range(2):
            scores = q[:, 2 * j + s] @ k[:, 2 * g + s].T / HALF**0.5
            probs = jax.nn.softmax(jnp.where(hidden, -jnp.inf, scores), -1)
            maps.append(probs @ value)
        out.append(maps[0] - lam * maps[1])
    return jnp.stack(out, axis=1)


def _subtract(attn, lam=0.3):
    """A kernel's [Q, H, 2 w] (head 2j pair j's first map) -> [Q, H / 2,
    2 w]."""
    maps = attn.reshape(attn.shape[0], HEADS // 2, 2, -1)
    return maps[:, :, 0] - lam * maps[:, :, 1]


def _as_pairs(q, k, v):
    """What `models/phi4_flash.py` hands a kernel: queries held a pair
    wide, keys and values as pairs [T, H / 4, 2 w]."""
    cfg = PHI4_FLASH_PRESETS["phi4_flash_tiny"]
    padded = phi4_flash._pad_queries(q[None], cfg)[0]
    pairs = (k.shape[0], HEADS // 4, 2 * HALF)
    return padded, k.reshape(pairs), v.reshape(pairs)


@pytest.mark.parametrize("start", [0, 16, 64])
def test_differential_attention_through_the_band_kernel(start):
    window, c = 16, 32
    q, k, v = _paired(start, c, window + c)
    padded, kp, vp = _as_pairs(q, k, v)
    got = window_attention(
        padded, kp.transpose(1, 0, 2), vp.transpose(1, 0, 2),
        jnp.int32(start), window=window, scale=HALF**-0.5, block_q=16,
        block_kv=16, interpret=True,
    )
    ahead = jnp.arange(window + c)[None, :] - jnp.arange(c)[:, None]
    hidden = (ahead < 1) | (ahead > window) | (
        jnp.arange(window + c)[None, :] < window - start
    )
    np.testing.assert_allclose(
        _subtract(got), _dense_pairs(q, k, v, hidden), atol=2e-5, rtol=2e-5
    )


@pytest.mark.parametrize("start", [0, 128])
def test_differential_attention_through_the_prefill_kernel(start):
    page, c = 64, 128
    keys = start + c
    q, k, v = _paired(start + 1, c, keys)
    padded, kp, vp = _as_pairs(q, k, v)

    def cells(a):  # [T, Hkv, Dh] -> [n, Hkv, P, Dh]
        return a.reshape(-1, page, *a.shape[1:]).transpose(0, 2, 1, 3)

    got = prefill_attention(
        padded, cells(kp), cells(vp), jnp.int32(start), interpret=True,
        scale=HALF**-0.5,
    )
    hidden = jnp.arange(keys)[None, :] > (start + jnp.arange(c))[:, None]
    np.testing.assert_allclose(
        _subtract(got), _dense_pairs(q, k, v, hidden), atol=2e-5, rtol=2e-5
    )


def test_differential_attention_through_the_paged_kernel():
    """A decode step's (or a cross block's) one query a slot over its
    pages, two slots of different lengths."""
    page, slots, keys = 16, 2, 64
    positions = jnp.asarray([40, 9], jnp.int32)
    pools_k, pools_v, want, qs = [], [], [], []
    for b in range(slots):
        q, k, v = _paired(20 + b, 1, keys)
        padded, kp, vp = _as_pairs(q, k, v)
        qs.append(padded)
        pools_k.append(kp.reshape(-1, page, *kp.shape[1:]).transpose(0, 2, 1, 3))
        pools_v.append(vp.reshape(-1, page, *vp.shape[1:]).transpose(0, 2, 1, 3))
        hidden = jnp.arange(keys)[None, :] > positions[b]
        want.append(_dense_pairs(q, k, v, hidden)[0])
    per = keys // page
    tables = jnp.arange(slots * per, dtype=jnp.int32).reshape(slots, per)
    got = paged_attention(
        jnp.stack(qs), jnp.concatenate(pools_k), jnp.concatenate(pools_v),
        tables, positions, n_kv_heads=HEADS // 4, interpret=True,
        scale=HALF**-0.5,
    )
    for b in range(slots):
        np.testing.assert_allclose(
            _subtract(got[b]), want[b][None], atol=2e-5, rtol=2e-5
        )
