import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import PRESETS, forward, init_params, param_logical_axes
from ray_tpu.parallel import make_mesh
from ray_tpu.parallel.sharding import shard_pytree, tree_shardings
from ray_tpu.train.step import (
    init_train_state,
    jit_train_step,
    make_optimizer,
    make_train_step,
    state_logical_axes,
)

CFG = PRESETS["tiny"]


def _batch(key, b=2, s=32):
    return {
        "tokens": jax.random.randint(key, (b, s + 1), 0, CFG.vocab_size)
    }


def test_forward_shapes():
    params = init_params(jax.random.key(0), CFG)
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = forward(params, tokens, CFG)
    assert logits.shape == (2, 16, CFG.vocab_size)
    assert logits.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_param_axes_match_structure():
    params = init_params(jax.random.key(0), CFG)
    axes = param_logical_axes(CFG)
    flat_p = jax.tree.flatten(params)[1]
    flat_a = jax.tree.flatten(axes, is_leaf=lambda x: isinstance(x, tuple))[1]
    assert flat_p == flat_a
    for p, a in zip(
        jax.tree.leaves(params),
        jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple)),
    ):
        assert p.ndim == len(a)


def test_causality():
    """Changing future tokens must not change past logits."""
    params = init_params(jax.random.key(0), CFG)
    t1 = jax.random.randint(jax.random.key(1), (1, 16), 0, CFG.vocab_size)
    t2 = t1.at[0, 10:].set((t1[0, 10:] + 1) % CFG.vocab_size)
    l1 = forward(params, t1, CFG)
    l2 = forward(params, t2, CFG)
    np.testing.assert_allclose(l1[0, :10], l2[0, :10], atol=1e-5)
    assert not np.allclose(l1[0, 10:], l2[0, 10:])


def test_loss_decreases():
    opt = make_optimizer(lr=1e-2, warmup=1, total_steps=50)
    state = init_train_state(jax.random.key(0), CFG, opt)
    step = jax.jit(make_train_step(CFG, opt))
    batch = _batch(jax.random.key(1))
    first = None
    for _ in range(8):
        state, metrics = step(state, batch)
        if first is None:
            first = float(metrics["loss"])
    assert float(metrics["loss"]) < first


def test_opt_state_axes_mirror_params():
    """Adam moments must carry their own param's axes — in particular wo
    [L, hq, d] with hq==d must NOT inherit wq's transposed axes."""
    from collections import Counter

    from ray_tpu.parallel.sharding import is_axes_leaf

    opt = make_optimizer()
    axes = state_logical_axes(CFG, opt)
    opt_leaves = Counter(
        jax.tree.leaves(axes.opt_state, is_leaf=is_axes_leaf)
    )
    # wo's axes tuple is unique among params; mu and nu each mirror it.
    assert opt_leaves[("layers", "heads", "embed")] == 2
    assert opt_leaves[("layers", "embed", "heads")] == 2


def test_sharded_train_step(mesh8):
    """Full train step under dp=2 fsdp=2 tp=2 on the virtual mesh."""
    opt = make_optimizer()
    step = jit_train_step(CFG, opt, mesh8)
    state = init_train_state(jax.random.key(0), CFG, opt)
    axes = state_logical_axes(CFG, opt)
    state = jax.device_put(state, tree_shardings(mesh8, axes))
    batch = jax.device_put(
        _batch(jax.random.key(1), b=4),
        tree_shardings(mesh8, {"tokens": ("batch", "act_seq")}),
    )
    state, metrics = step(state, batch)
    assert int(state.step) == 1
    assert np.isfinite(float(metrics["loss"]))
    # fsdp axis shards wq's embed dim: verify it is actually distributed.
    wq_sh = state.params["blocks"]["wq"].sharding
    assert wq_sh.spec == tree_shardings(
        mesh8, param_logical_axes(CFG)
    )["blocks"]["wq"].spec


def test_sharded_matches_single_device(mesh8):
    """Sharded forward == single-device forward (collectives correct)."""
    params = init_params(jax.random.key(0), CFG)
    tokens = jax.random.randint(jax.random.key(1), (4, 16), 0, CFG.vocab_size)
    ref = forward(params, tokens, CFG)
    sp = shard_pytree(params, mesh8, param_logical_axes(CFG))
    st = jax.device_put(
        tokens, tree_shardings(mesh8, ("batch", "act_seq"))
    )
    out = jax.jit(lambda p, t: forward(p, t, CFG))(sp, st)
    np.testing.assert_allclose(ref, out, atol=2e-4, rtol=1e-4)


def test_ffn_checkpoint_remat_modes_match_full():
    """flash_qkv_ffn / flash_qkv_ffn8 numerics: the saved-activation
    (and int8-quantized) FFN paths must match remat=full to bf16-level
    (exact for bf16-saved; small bounded quantization error for int8)."""
    import dataclasses

    from ray_tpu.models.llama import forward_with_aux

    params = init_params(jax.random.key(0), CFG)
    tokens = jax.random.randint(
        jax.random.key(1), (2, 32), 0, CFG.vocab_size
    )

    def loss_and_grad(remat):
        cfg = dataclasses.replace(CFG, remat=remat)

        def loss(p):
            logits, aux = forward_with_aux(p, tokens, cfg)
            tgt = jnp.roll(tokens, -1, axis=1)
            lp = jax.nn.log_softmax(logits)
            return (
                -jnp.take_along_axis(lp, tgt[..., None], axis=-1).mean()
                + aux.sum()
            )

        return jax.jit(jax.value_and_grad(loss))(params)

    l_full, g_full = loss_and_grad("full")
    l_bf16, g_bf16 = loss_and_grad("flash_qkv_ffn")
    l_q8, g_q8 = loss_and_grad("flash_qkv_ffn8")

    # bf16-saved: identical math, only the residual set differs.
    np.testing.assert_allclose(float(l_full), float(l_bf16), rtol=1e-6)
    # int8-saved: bounded quantization error through the STE.
    assert abs(float(l_full) - float(l_q8)) / float(l_full) < 0.02

    def gnorm(g):
        return float(
            jax.tree_util.tree_reduce(
                lambda a, b: a + jnp.sum(b.astype(jnp.float32) ** 2), g, 0.0
            )
        ) ** 0.5

    np.testing.assert_allclose(gnorm(g_full), gnorm(g_bf16), rtol=1e-5)
    np.testing.assert_allclose(gnorm(g_full), gnorm(g_q8), rtol=0.05)
