"""Flash-attention Pallas kernel tests (interpret mode on CPU).

Mirrors the reference's pattern of testing device kernels with CPU
stand-ins (reference: channel/conftest.py mocks NCCL; here Pallas
interpret mode runs the real kernel logic on CPU).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import causal_attention
from ray_tpu.ops.pallas import flash_attention


def _rand(shape, key, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype)


@pytest.mark.parametrize("s,block", [(128, 64), (256, 128)])
def test_flash_matches_dense_causal(s, block):
    key = jax.random.key(0)
    b, h, d = 2, 4, 64
    q = _rand((b, s, h, d), jax.random.fold_in(key, 1))
    k = _rand((b, s, h, d), jax.random.fold_in(key, 2))
    v = _rand((b, s, h, d), jax.random.fold_in(key, 3))
    ref = causal_attention(q, k, v)
    out = flash_attention(
        q, k, v, block_q=block, block_kv=block, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3
    )


def test_flash_gqa():
    """Grouped-query: q heads share kv heads via index mapping."""
    key = jax.random.key(1)
    b, s, h, hkv, d = 1, 128, 8, 2, 32
    q = _rand((b, s, h, d), jax.random.fold_in(key, 1))
    k = _rand((b, s, hkv, d), jax.random.fold_in(key, 2))
    v = _rand((b, s, hkv, d), jax.random.fold_in(key, 3))
    ref = causal_attention(q, k, v)
    out = flash_attention(q, k, v, block_q=64, block_kv=64, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3
    )


def test_flash_non_causal():
    key = jax.random.key(2)
    b, s, h, d = 1, 128, 2, 32
    q = _rand((b, s, h, d), jax.random.fold_in(key, 1))
    k = _rand((b, s, h, d), jax.random.fold_in(key, 2))
    v = _rand((b, s, h, d), jax.random.fold_in(key, 3))
    # Full (bidirectional) attention reference.
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (d**-0.5)
    probs = jax.nn.softmax(logits, axis=-1)
    ref = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    out = flash_attention(
        q, k, v, causal=False, block_q=64, block_kv=64, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3
    )


def test_flash_backward_matches_dense():
    """custom-VJP gradients == autodiff through the dense path (incl.
    GQA head-group summation)."""
    key = jax.random.key(3)
    b, s, h, hkv, d = 1, 128, 4, 2, 32
    q = _rand((b, s, h, d), jax.random.fold_in(key, 1))
    k = _rand((b, s, hkv, d), jax.random.fold_in(key, 2))
    v = _rand((b, s, hkv, d), jax.random.fold_in(key, 3))

    def loss_flash(q, k, v):
        return (
            flash_attention(
                q, k, v, block_q=64, block_kv=64, interpret=True
            )
            ** 2
        ).sum()

    def loss_dense(q, k, v):
        return (causal_attention(q, k, v) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gd):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=5e-3, atol=5e-3
        )


def test_flash_train_step_runs():
    """attn_impl='flash' wires through jit_train_step (interpret on CPU)."""
    import dataclasses

    from ray_tpu.models import PRESETS
    from ray_tpu.parallel import make_mesh
    from ray_tpu.train.step import (
        init_train_state,
        jit_train_step,
        make_optimizer,
    )

    cfg = dataclasses.replace(
        PRESETS["tiny"], attn_impl="flash", max_seq=128
    )
    opt = make_optimizer(total_steps=10)
    # 8-device dp mesh: exercises the shard_map path around the kernel.
    mesh = make_mesh({"dp": 8})
    step = jit_train_step(cfg, opt, mesh)
    state = init_train_state(jax.random.key(0), cfg, opt)
    tokens = jax.random.randint(
        jax.random.key(1), (8, 129), 0, cfg.vocab_size
    )
    state, metrics = step(state, {"tokens": tokens})
    assert np.isfinite(float(metrics["loss"]))


def test_flash_rejects_bad_shapes():
    k = jnp.zeros((1, 128, 3, 32))
    with pytest.raises(ValueError):
        flash_attention(
            jnp.zeros((1, 128, 4, 32)), k, k, interpret=True
        )


def test_flash_non_divisible_seq_uses_smaller_blocks():
    """Sequence lengths that don't divide the requested blocks clamp to
    the gcd instead of erroring — correctness checked against dense."""
    key = jax.random.key(7)
    b, s, h, d = 1, 100, 2, 32  # gcd(64, 100) = 4
    q = _rand((b, s, h, d), jax.random.fold_in(key, 1))
    k = _rand((b, s, h, d), jax.random.fold_in(key, 2))
    v = _rand((b, s, h, d), jax.random.fold_in(key, 3))
    ref = causal_attention(q, k, v)
    out = flash_attention(q, k, v, block_q=64, block_kv=64, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3
    )


def test_flash_backward_partials_fallback_matches_dense(monkeypatch):
    """Long-seq mode: when the whole-head dq VMEM slab exceeds budget,
    the backward switches to HBM fp32 partials — same gradients."""
    import sys

    fa_mod = sys.modules["ray_tpu.ops.pallas.flash_attention"]
    monkeypatch.setattr(fa_mod, "_DQ_SLAB_VMEM_BYTES", 1024)  # force it
    key = jax.random.key(11)
    b, s, h, hkv, d = 1, 128, 4, 2, 32
    q = _rand((b, s, h, d), jax.random.fold_in(key, 1))
    k = _rand((b, s, hkv, d), jax.random.fold_in(key, 2))
    v = _rand((b, s, hkv, d), jax.random.fold_in(key, 3))

    def loss_flash(q, k, v):
        # block_kv=32 is a combo no other test uses: the jit cache would
        # otherwise replay a slab-mode trace and skip the fallback.
        return (
            flash_attention(
                q, k, v, block_q=64, block_kv=32, interpret=True
            )
            ** 2
        ).sum()

    def loss_dense(q, k, v):
        return (causal_attention(q, k, v) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gd):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=5e-3, atol=5e-3
        )
