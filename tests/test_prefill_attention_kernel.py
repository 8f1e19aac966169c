"""Serving prefill's attention kernel, interpreted, against the plain
paths it replaces.

The kernel (ops/pallas/prefill_attention.py) against
``ops.attention.causal_attention`` at a query offset, over the shape
classes the prefill programs dispatch: a chunk at the start of a context,
one block in, several blocks in; chunks smaller than, equal to and larger
than a key block; 4 and 1 query heads a key head; tables wider than the
context, with NaNs in the pages nobody wrote. Then the two programs that
call it (llm/paged_kv.py): chunk by chunk by the kernel against the whole
prompt by dense attention, logits and written pages; the engine's greedy
streams with the kernel forced on and off; the counter of attended pairs.

Tolerances, not equality: a change of program structure moves bf16
results in the last bit (ROADMAP, PRs 28-34).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.engine import LLMEngine, SamplingParams
from ray_tpu.llm.paged_kv import (
    init_paged_kv,
    paged_prefill,
    paged_prefill_chunk,
)
from ray_tpu.models.llama import PRESETS, init_params
from ray_tpu.ops.attention import causal_attention
from ray_tpu.ops.pallas.prefill_attention import _fit_rows, prefill_attention

P, DH = 16, 32
CFG = PRESETS["tiny"]


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.key(0), CFG)


def _inputs(seed, c, n_pages, h, hkv, dtype=jnp.bfloat16):
    """Queries ``[C, H, Dh]`` and a context of ``n_pages`` pages, both
    as tokens ``[T, Hkv, Dh]`` and as the pool's cells."""
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (c, h, DH), dtype)
    k = jax.random.normal(ks[1], (n_pages * P, hkv, DH), dtype)
    v = jax.random.normal(ks[2], (n_pages * P, hkv, DH), dtype)
    return q, k, v


def _cells(a):
    t, hkv, dh = a.shape
    return a.reshape(t // P, P, hkv, dh).transpose(0, 2, 1, 3)


def _dense(q, k, v, start):
    n = start + q.shape[0]
    return causal_attention(q[None], k[None, :n], v[None, :n], q_offset=start)[0]


def _max_err(a, b):
    return float(
        jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max()
    )


# (queries, start, pages in the table, H, Hkv, block_q, block_kv)
KERNEL_CASES = {
    "start0_one_block": (32, 0, 2, 4, 1, 32, 32),
    "start0_chunk_smaller_than_key_block": (16, 0, 4, 4, 1, 16, 64),
    "one_block_in": (32, 32, 4, 4, 1, 32, 32),
    "one_block_in_chunk_larger_than_key_block": (64, 32, 6, 8, 2, 32, 16),
    "several_blocks_in": (32, 96, 8, 4, 1, 32, 32),
    "several_blocks_in_chunk_smaller": (16, 112, 8, 4, 1, 16, 32),
    "several_blocks_in_chunk_larger": (96, 64, 10, 4, 1, 32, 32),
    "diagonal_inside_a_wide_key_block": (32, 48, 8, 4, 1, 16, 64),
    "mha_start0": (32, 0, 2, 2, 2, 16, 16),
    "mha_several_blocks_in": (48, 80, 8, 2, 2, 16, 32),
    "blocks_that_do_not_divide": (48, 32, 5, 4, 1, 32, 32),
    "float32_pool": (32, 32, 4, 4, 2, 32, 32),
    # A model's own score scale (models/granite_hybrid.py: 1/128, not
    # Dh**-0.5), float32 so that scaling the oracle's queries is exact.
    "scale_1_128_start0": (32, 0, 2, 4, 1, 32, 32),
    "scale_1_128_several_blocks_in": (48, 80, 8, 4, 2, 16, 32),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernel_matches_causal_attention_at_an_offset(case):
    c, start, n_pages, h, hkv, bq, bkv = KERNEL_CASES[case]
    scale = 1 / 128 if case.startswith("scale_1_128") else None
    in_f32 = case == "float32_pool" or scale is not None
    dtype = jnp.float32 if in_f32 else jnp.bfloat16
    q, k, v = _inputs(1, c, n_pages, h, hkv, dtype)
    got = prefill_attention(
        q, _cells(k), _cells(v), jnp.int32(start),
        block_q=bq, block_kv=bkv, interpret=True, scale=scale,
    )
    assert got.shape == q.shape and got.dtype == k.dtype
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    if scale is not None:
        # The oracle scales by Dh**-0.5: hand it queries that make up
        # the difference, and see that the scale moved the result.
        assert _max_err(got, _dense(q, k, v, start)) > 1e-2
        q = q * (scale * DH**0.5)
    assert _max_err(got, _dense(q, k, v, start)) < tol


@pytest.mark.parametrize(
    "case",
    {
        # (queries, start, pages, H, Hkv, block_q, block_kv): the table
        # is wider than start + C in each.
        "first_chunk_of_a_wide_table": (32, 0, 8, 4, 1, 32, 32),
        "context_ends_inside_a_key_block": (32, 16, 8, 4, 1, 16, 64),
        "context_ends_inside_a_key_block_mha": (16, 48, 8, 2, 2, 16, 128),
    }.items(),
    ids=lambda case: case[0],
)
def test_pages_past_the_context_do_not_reach_the_result(case):
    """What lies past ``start + C`` (pages of the bucket that nobody
    wrote yet) is masked or not fetched: NaNs there change nothing."""
    c, start, n_pages, h, hkv, bq, bkv = case[1]
    q, k, v = _inputs(2, c, n_pages, h, hkv)
    want = _dense(q, k, v, start)
    end = start + c
    k, v = k.at[end:].set(jnp.nan), v.at[end:].set(jnp.nan)
    got = prefill_attention(
        q, _cells(k), _cells(v), jnp.int32(start),
        block_q=bq, block_kv=bkv, interpret=True,
    )
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
    assert _max_err(got, want) < 2e-2


def test_fitted_blocks_divide_and_keep_the_tile():
    assert _fit_rows(512, 2048, 16) == 512
    assert _fit_rows(512, 448, 16) == 448  # fits whole
    assert _fit_rows(512, 1984, 16) == 496  # 64 x 31: no power of two
    assert _fit_rows(16, 132, 1) == 12  # max_seq 8448: 132 pages
    for rows in range(64, 2049, 64):
        block = _fit_rows(512, rows, 16)
        assert rows % block == 0 and (block % 16 == 0 or block == rows)


# ------------------------------------------------------------ the programs
PAGES = np.asarray([3, 1, 4, 7, 2, 9, 11, 12], np.int32)  # table of 8
N_CTX_PAGES, CHUNK_PAGES = 6, 2  # a 96-token prompt in chunks of 32


def _pool(nan_pages=()):
    pool = init_paged_kv(CFG, num_pages=16, page_size=P)
    for name in ("k", "v"):
        for page in nan_pages:
            pool[name] = pool[name].at[:, page].set(jnp.nan)
    return pool


@pytest.fixture(scope="module")
def whole_prompt(params):
    """The 96-token prompt by ``paged_prefill`` with dense attention:
    every position's logits and the pool it wrote."""
    tokens = np.random.default_rng(5).integers(
        1, CFG.vocab_size, (1, N_CTX_PAGES * P)
    ).astype(np.int32)
    logits, pool = paged_prefill(
        params, tokens, _pool(), PAGES[:N_CTX_PAGES], cfg=CFG,
        n_write_pages=N_CTX_PAGES, use_kernel=False,
    )
    return tokens, np.asarray(logits), jax.tree.map(np.asarray, pool)


def test_chunks_by_the_kernel_match_the_whole_prompt(params, whole_prompt):
    """``paged_prefill_chunk(use_kernel=True)`` chunk by chunk over a
    table wider than the prompt whose tail pages hold NaNs, against the
    whole prompt's dense pass: logits and the pages written."""
    tokens, want_logits, want_pool = whole_prompt
    pool = _pool(nan_pages=PAGES[N_CTX_PAGES:])
    c = CHUNK_PAGES * P
    for start in range(0, N_CTX_PAGES * P, c):
        logits, pool = paged_prefill_chunk(
            params, tokens[:, start:start + c], pool, PAGES,
            np.int32(start), cfg=CFG, n_write_pages=len(PAGES),
            chunk_pages=CHUNK_PAGES, use_kernel=True,
        )
        np.testing.assert_allclose(
            np.asarray(logits), want_logits[:, start:start + c],
            atol=2e-4, rtol=0,
        )
    written = PAGES[:N_CTX_PAGES]
    for name in ("k", "v"):
        np.testing.assert_allclose(
            np.asarray(pool[name])[:, written], want_pool[name][:, written],
            atol=1e-5, rtol=0,
        )


def test_whole_prompt_by_the_kernel_matches_dense(params, whole_prompt):
    tokens, want_logits, want_pool = whole_prompt
    logits, pool = paged_prefill(
        params, tokens, _pool(), PAGES[:N_CTX_PAGES], cfg=CFG,
        n_write_pages=N_CTX_PAGES, use_kernel=True,
    )
    np.testing.assert_allclose(
        np.asarray(logits), want_logits, atol=2e-4, rtol=0
    )
    for name in ("k", "v"):
        np.testing.assert_allclose(
            np.asarray(pool[name]), want_pool[name], atol=1e-5, rtol=0
        )


def test_programs_without_the_kernel_are_the_default(params, whole_prompt):
    """``use_kernel`` defaults to False in both programs, as in
    ``paged_verify``: a caller that does not pass it (a ``tp`` mesh, the
    benchmark's fit script) compiles what it always compiled."""
    tokens, want_logits, _ = whole_prompt
    logits, _ = paged_prefill(
        params, tokens, _pool(), PAGES[:N_CTX_PAGES], cfg=CFG,
        n_write_pages=N_CTX_PAGES,
    )
    np.testing.assert_array_equal(np.asarray(logits), want_logits)
    text = paged_prefill_chunk.lower(
        params, tokens[:, :32], _pool(), PAGES, np.int32(0), cfg=CFG,
        n_write_pages=len(PAGES), chunk_pages=CHUNK_PAGES,
    ).as_text()
    assert "prefill_attention" not in text


ENGINE_PROMPTS = [
    list(range(1, 71)),  # three chunks of 32
    [7, 8, 9],  # whole, by paged_prefill
    [5, 6] * 20,  # two chunks
]


def _engine(params, monkeypatch, kernel: bool):
    monkeypatch.setenv("RAY_TPU_PAGED_ATTN", "1" if kernel else "0")
    engine = LLMEngine(
        CFG, max_batch=2, max_seq=128, params=params, page_size=P,
        prefill_chunk=32,
    )
    assert engine.paged_attn_kernel == kernel
    return engine


def test_greedy_streams_identical_with_the_kernel_on_and_off(
    params, monkeypatch
):
    outs = {}
    for kernel in (False, True):
        engine = _engine(params, monkeypatch, kernel)
        outs[kernel] = engine.generate(
            ENGINE_PROMPTS, SamplingParams(max_tokens=6)
        )
        assert engine.stats()["prefill_chunks"] == 5
    assert outs[True] == outs[False]


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
def test_prefill_attn_pairs_is_the_hand_count(params, monkeypatch, kernel):
    """A 50-token prompt in two chunks of 32 (32 and 18 live queries,
    the second at start 32) and a 3-token prompt whole: the pairs a
    causal mask leaves, a layer, whatever path computed them."""
    engine = _engine(params, monkeypatch, kernel)
    assert engine.stats()["prefill_attn_pairs"] == 0
    engine.generate([list(range(1, 51))], SamplingParams(max_tokens=2))
    two_chunks = 32 * 33 // 2 + (18 * 32 + 18 * 19 // 2)
    assert two_chunks == 50 * 51 // 2
    assert engine.stats()["prefill_attn_pairs"] == CFG.n_layers * two_chunks
    engine.generate([[4, 5, 6]], SamplingParams(max_tokens=2))
    assert engine.stats()["prefill_attn_pairs"] == CFG.n_layers * (
        two_chunks + 6
    )
