"""What LLMEngine holds: its matmul weights in cfg.dtype, cast once.

A bfloat16 config's engine must give, from the tree it holds, exactly
what its programs give from the caller's fp32 tree (they round each
weight to cfg.dtype before multiplying either way), hold half the bytes,
and leave no fp32 stack for a program to convert.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.engine import LLMEngine, SamplingParams
from ray_tpu.llm.paged_kv import (
    _MATMUL_BLOCK_LEAVES,
    init_paged_kv,
    matmul_weights,
    paged_prefill,
    paged_prefill_chunk,
    paged_verify,
)
from ray_tpu.models.llama import PRESETS, init_params

BF16 = dataclasses.replace(PRESETS["tiny"], dtype=jnp.bfloat16)
FP32 = PRESETS["tiny"]
PAGE = 16
MATMUL_LEAVES = ("tok_emb", "lm_head") + tuple(
    f"blocks.{name}" for name in _MATMUL_BLOCK_LEAVES
)
NORM_LEAVES = ("final_norm", "blocks.attn_norm", "blocks.mlp_norm")


def leaf(tree, path):
    for part in path.split("."):
        tree = tree[part]
    return tree


@pytest.fixture(scope="module")
def raw():
    return init_params(jax.random.key(0), BF16)


@pytest.fixture(scope="module")
def engine(raw):
    return LLMEngine(
        BF16, max_batch=2, max_seq=64, params=raw, page_size=PAGE
    )


def tokens(shape, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(1, BF16.vocab_size, shape), jnp.int32)


def run_prefill(params):
    pool = init_paged_kv(BF16, num_pages=8, page_size=PAGE)
    return paged_prefill(
        params, tokens((1, 32)), pool, jnp.asarray([1, 2], jnp.int32),
        cfg=BF16, n_write_pages=2,
    )


def prefilled_pool(params):
    """Pages 1 and 2 hold a 32-token prompt's K/V."""
    return run_prefill(params)[1]


def run_prefill_chunk(params):
    # The second 16-token chunk of a 32-token prompt.
    pool = prefilled_pool(params)
    return paged_prefill_chunk(
        params, tokens((1, 32))[:, 16:], pool,
        jnp.asarray([1, 2], jnp.int32), jnp.int32(16),
        cfg=BF16, n_write_pages=2, chunk_pages=1,
    )


def run_verify(params, k, temperature):
    pool = prefilled_pool(params)
    b = 2
    return paged_verify(
        params, tokens((b, k), seed=1), pool,
        jnp.asarray([[1, 2, 3], [1, 2, 4]], jnp.int32),
        jnp.full((b,), 32, jnp.int32),
        jnp.full((b,), temperature, jnp.float32),
        jax.random.key(7), cfg=BF16, use_kernel=False,
    )


PROGRAMS = {
    "paged_prefill": run_prefill,
    "paged_prefill_chunk": run_prefill_chunk,
    "paged_verify_k1_greedy": lambda p: run_verify(p, 1, 0.0),
    "paged_verify_k4_greedy": lambda p: run_verify(p, 4, 0.0),
    "paged_verify_k1_sampled": lambda p: run_verify(p, 1, 0.8),
    "paged_verify_k4_sampled": lambda p: run_verify(p, 4, 0.8),
}


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_held_tree_gives_what_the_fp32_tree_gives(program, raw, engine):
    """Logits, sampled ids, acceptance and the pool: equal bit for bit, because either way the program multiplies by the weight
    rounded to bfloat16 once."""
    from_raw = jax.tree.leaves(PROGRAMS[program](raw))
    from_held = jax.tree.leaves(PROGRAMS[program](engine.params))
    assert len(from_raw) == len(from_held) >= 2
    for a, b in zip(from_raw, from_held):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(
            np.asarray(a.astype(jnp.float32)),
            np.asarray(b.astype(jnp.float32)),
        )
    assert np.isfinite(np.asarray(from_held[0], np.float32)).all()


def test_engine_generates_the_same_ids_from_either_tree(raw, engine):
    """An engine handed the held tree (nothing left to cast) and one
    handed the fp32 tree generate the same tokens."""
    prompts = [[5, 9, 2, 7, 3] * 4, [11, 4, 8]]
    sampling = SamplingParams(max_tokens=6)
    kw = dict(max_batch=2, max_seq=64)
    a = LLMEngine(BF16, params=raw, **kw)
    b = LLMEngine(BF16, params=engine.params, **kw)
    assert b.params["lm_head"] is engine.params["lm_head"]
    assert a.generate(prompts, sampling) == b.generate(prompts, sampling)


@pytest.mark.parametrize("path", MATMUL_LEAVES)
def test_matmul_leaf_is_held_in_the_compute_dtype(path, raw, engine):
    held, given = leaf(engine.params, path), leaf(raw, path)
    assert held.dtype == jnp.bfloat16 and held.shape == given.shape
    # The caller's tree is alive and as it was.
    assert given.dtype == jnp.float32 and not given.is_deleted()
    np.testing.assert_array_equal(
        np.asarray(held.astype(jnp.float32)),
        np.asarray(given.astype(jnp.bfloat16).astype(jnp.float32)),
    )


@pytest.mark.parametrize("path", NORM_LEAVES)
def test_norm_leaf_stays_as_given(path, raw, engine):
    held = leaf(engine.params, path)
    assert held.dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(held), np.asarray(leaf(raw, path))
    )


def test_every_leaf_is_either_cast_or_a_norm(raw):
    paths = {
        ".".join(str(k.key) for k in path)
        for path, _ in jax.tree_util.tree_leaves_with_path(raw)
    }
    assert paths == set(MATMUL_LEAVES) | set(NORM_LEAVES)


def test_param_bytes_counts_the_held_tree(raw, engine):
    held = sum(x.nbytes for x in jax.tree.leaves(engine.params))
    given = sum(x.nbytes for x in jax.tree.leaves(raw))
    norms = sum(leaf(raw, p).nbytes for p in NORM_LEAVES)
    assert engine.stats()["param_bytes"] == held
    assert held == (given - norms) // 2 + norms


def test_float32_config_holds_the_callers_arrays():
    given = init_params(jax.random.key(0), FP32)
    eng = LLMEngine(FP32, max_batch=2, max_seq=64, params=given)
    for a, b in zip(jax.tree.leaves(eng.params), jax.tree.leaves(given)):
        assert a is b
    assert eng.stats()["param_bytes"] == sum(
        x.nbytes for x in jax.tree.leaves(given)
    )


@pytest.mark.parametrize("cfg", [FP32, BF16], ids=["float32", "bfloat16"])
def test_engines_own_tree_is_init_params_rounded_once(cfg):
    """With no `params=` the engine makes its tree as it holds it: the
    values `init_params` gives for the seed, the matmul leaves rounded."""
    eng = LLMEngine(cfg, max_batch=2, max_seq=64, seed=3)
    want = matmul_weights(init_params(jax.random.key(3), cfg), cfg)
    for a, b in zip(jax.tree.leaves(eng.params), jax.tree.leaves(want)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(
            np.asarray(a.astype(jnp.float32)),
            np.asarray(b.astype(jnp.float32)),
        )


def verify_text(params):
    b = 2
    return paged_verify.lower(
        params, jnp.zeros((b, 1), jnp.int32),
        init_paged_kv(BF16, num_pages=8, page_size=PAGE),
        jnp.full((b, 4), -1, jnp.int32), jnp.zeros((b,), jnp.int32),
        jnp.zeros((b,), jnp.float32), jax.random.key(0),
        cfg=BF16, use_kernel=False, stochastic=False,
    ).as_text()


def test_no_fp32_stack_is_left_for_verify_to_convert(raw, engine):
    """The lowered decode program on the held tree names no f32 tensor
    of a weight's shape; on the fp32 tree it names every one (so the
    pattern is not blind)."""
    shapes = {
        "x".join(map(str, leaf(raw, p).shape)) for p in MATMUL_LEAVES
    }
    def f32_weights(text):
        return shapes & set(re.findall(r"tensor<([0-9x]+)xf32>", text))

    assert f32_weights(verify_text(raw)) == shapes
    assert f32_weights(verify_text(engine.params)) == set()


def test_cast_keeps_each_leafs_sharding(raw, mesh8):
    """Under a mesh the cast follows shard_pytree: the held bf16 leaf
    lies as the fp32 leaf was laid."""
    from ray_tpu.models.llama import param_logical_axes
    from ray_tpu.parallel.sharding import shard_pytree

    eng = LLMEngine(BF16, max_batch=2, max_seq=64, params=raw, mesh=mesh8)
    laid = shard_pytree(raw, mesh8, param_logical_axes(BF16))
    for path in MATMUL_LEAVES + NORM_LEAVES:
        held, want = leaf(eng.params, path), leaf(laid, path)
        assert held.sharding.is_equivalent_to(want.sharding, held.ndim), path
    assert leaf(eng.params, "blocks.w_gate").dtype == jnp.bfloat16
