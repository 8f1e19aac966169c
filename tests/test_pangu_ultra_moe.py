"""Pangu Ultra MoE (models/pangu_ultra_moe.py, llm/latent_kv.py,
ops/pallas/latent_attention.py) against the plain reference
(benchmarks/reference_pangu_ultra_moe.py) at a tiny size, float32,
seeded weights, on the CPU: the two forms of latent attention, the
expert share, the decode kernel against the gather path, and
prefill-then-decode through `LLMEngine`'s latent pages.

Tolerances: everything here is float32 on both sides, so differences
are summation order only. 2e-4 absolute on values of magnitude ~1-4
leaves an order of magnitude over what float32 reassociation gives
across four layers (measured 1e-6 to 3e-6), and is a hundred times under
what any mathematical difference (a missing norm, a rope at the wrong
position, a dropped pair, a stale page) produces."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_pangu_ultra_moe as reference
from benchmarks.models import pangu_ultra_moe as bench_model
from ray_tpu.llm import latent_kv, serving
from ray_tpu.llm.engine import LLMEngine, SamplingParams
from ray_tpu.llm.paged_kv import _decode_geometry
from ray_tpu.models.moe import moe_ffn
from ray_tpu.models import moe
from ray_tpu.models.pangu_ultra_moe import (
    PANGU_PRESETS,
    PanguUltraMoEConfig,
    init_params,
    pad_to_cell,
    project_latent,
    project_q,
)
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.pallas.latent_attention import (
    keys_expanded,
    latent_expand,
    latent_paged_attention,
)
from ray_tpu.ops.rope import rope_frequencies

TOL = 2e-4

# The published keys (the catalog's) at a tiny size: what a
# configuration file carries, so that `config` and `for_model` are under
# test too.
TINY = {
    "model_type": "pangu_ultra_moe", "hidden_size": 64, "vocab_size": 256,
    "num_hidden_layers": 4, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 24,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "rope_theta": 10000, "intermediate_size": 96,
    "n_routed_experts": 8, "num_experts_per_tok": 3,
    "moe_intermediate_size": 32, "n_shared_experts": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "sandwich_norm": True, "hidden_act": "silu", "attention_bias": False,
    "tie_word_embeddings": False, "rms_norm_eps": 1e-5,
    "num_nextn_predict_layers": 0, "max_position_embeddings": 256,
}
# Rows up to 8 take `moe_ffn`'s every-row form and more its sorted one,
# so that an engine's decode steps (4 slots) run the first and its
# prefills the second, as the two meet in a replica.
CFG = bench_model.config(
    TINY, dtype=jnp.float32, dense_expert_rows=8, cell_lanes=16,
    prefill_key_block=16,
)
REF = reference.for_model(TINY)


@pytest.fixture(scope="module")
def params():
    p = init_params(jax.random.key(3), CFG)
    # Norm gains that are not 1 and a selection bias that is not zero,
    # so that a missing sandwich norm and a choice by score alone show.
    blocks = []
    for i, block in enumerate(p["blocks"]):
        block = dict(block)
        for j, name in enumerate(
            ("norm1", "norm2", "norm3", "norm4", "q_norm", "kv_norm")
        ):
            block[name] = 0.3 * jax.random.normal(
                jax.random.key(100 * i + j), block[name].shape
            )
        if "router_bias" in block:
            block["router_bias"] = 0.2 * jax.random.normal(
                jax.random.key(i), (CFG.num_experts,)
            )
        blocks.append(block)
    return {**p, "blocks": tuple(blocks)}


def _x(seed, t):
    return jax.random.normal(jax.random.key(seed), (t, CFG.d_model))


def test_the_tiny_preset_is_the_tiny_file():
    assert PANGU_PRESETS["pangu_tiny"] == CFG
    assert CFG.pattern == "DEEE" and CFG.latent_dim == 40 and CFG.cell_width == 48


# ----------------------------------------------- the two forms of attention
def _attention_both_forms(p, x):
    """One layer's attention sublayer over x [T, d] from an empty pool of
    one page of T cells: (the expanded form's heads [T, H, v], the
    absorbed form's for the last token alone [H, v], the cells)."""
    t = x.shape[0]
    cos, sin = rope_frequencies(CFG.qk_rope_head_dim, t, CFG.rope_theta)
    pos = jnp.arange(t, dtype=jnp.int32)[None]
    h = rms_norm(x, p["norm1"])[None]
    q_nope, q_pe = project_q(h, p, CFG, cos, sin, pos)
    cells = project_latent(h, p, CFG, cos, sin, pos)[0]  # [T, cell]
    pool = cells[None]  # one page of T cells
    expanded = latent_kv._attend_expanded(
        q_nope[0], q_pe[0], pool, jnp.zeros((1,), jnp.int32), jnp.int32(0),
        p, CFG,
    )
    q = pad_to_cell(jnp.concatenate(
        [jnp.einsum("bkhd,hcd->bkhc", q_nope[:, -1:], p["w_uk"]),
         q_pe[:, -1:]], -1,
    ), CFG)
    hidden = jnp.zeros((1, 1, t), bool)
    weighted = latent_kv._gather_latent_attention(
        q, pool, jnp.zeros((1, 1), jnp.int32), hidden, CFG
    )
    absorbed = jnp.einsum("bkhc,hcd->bkhd", weighted, p["w_uv"])[0, 0]
    return expanded, absorbed, cells


def test_absorbed_and_expanded_attention_are_one_function(params):
    """(b) of the issue: the decode's absorbed form (`q_nope Wuk^T`
    against the cells, `Wuv` after the weighted sum) and the prefill's
    expanded form (keys and values made from the cells) give the last
    token the same heads; and both equal the reference's non-absorbed
    layer, which is checked through the sublayer's output."""
    p = params["blocks"][1]
    x = _x(0, 16)
    expanded, absorbed, cells = _attention_both_forms(p, x)
    np.testing.assert_allclose(absorbed, expanded[-1], atol=TOL, rtol=0)
    want, latent = reference.attention(p, x, **REF)
    got = latent_kv._attn_out(x[None], expanded[None], p)[0]
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    np.testing.assert_allclose(cells[:, : CFG.latent_dim], latent, atol=TOL, rtol=0)
    assert not np.asarray(cells[:, CFG.latent_dim:]).any()


def test_a_sandwich_norm_left_out_is_seen(params):
    p = params["blocks"][1]
    x = _x(1, 12)
    want, _ = reference.attention(p, x, **REF)
    expanded, _, _ = _attention_both_forms(p, x)
    no_n2 = latent_kv._attn_out(x[None], expanded[None], {**p, "norm2": 0 * p["norm2"]})
    assert np.abs(np.asarray(no_n2[0] - want)).max() > 100 * TOL


# --------------------------------------------------------------- the experts
@pytest.fixture(params=[0, 64], ids=["sorted", "every_row"])
def path_cfg(request):
    return dataclasses.replace(CFG, dense_expert_rows=request.param)


def _ffn(p, x, cfg, kind="E"):
    """The sublayer as a program calls it: with the mask of the rows
    that carry a token (here all), so the sorted form is the bounded one."""
    record = serving._new_record()
    live = jnp.ones(len(x), bool)
    return latent_kv._ffn(x[None], kind, p, cfg, live, record)[0], record


def test_expert_layer_equals_the_reference(params, path_cfg):
    """`moe_ffn` as this family calls it (sigmoid scores chosen by score
    + bias, gates renormalised and scaled by 2.5, SwiGLU experts, the
    shared expert with its gate matrix) between the two norms, against
    the reference's plain loop."""
    p = params["blocks"][2]
    x = _x(5, 24)
    want, record = reference.expert_ffn(p, x, **REF)
    got, rec = _ffn(p, x, path_cfg)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    assert (np.sort(rec["routes"][0], -1) == np.sort(record["routes"], -1)).all()
    assert int(rec["pairs_here"][0]) == 24 * CFG.top_k


def test_dense_layer_equals_the_reference(params):
    p = params["blocks"][0]
    x = _x(6, 24)
    np.testing.assert_allclose(
        _ffn(p, x, CFG, "D")[0], reference.dense_ffn(p, x, **REF),
        atol=TOL, rtol=0,
    )


def test_the_shares_add_up_to_the_uncut_layer(params, path_cfg):
    """(c) of the issue. Expert parallelism over four chips: each share
    holds 2 of the 8 experts, routes over all 8 and computes its own
    experts' part. The four routed parts plus the shared expert ONCE are
    the uncut layer (model-configs guide, section 4); each share also
    equals the reference given the same share."""
    p = params["blocks"][3]
    x = _x(7, 24)
    h = rms_norm(x, p["norm3"])[None]
    whole, aux = moe_ffn(h, p, path_cfg)
    no_shared = {k: v for k, v in p.items() if not k.startswith("shared")}
    shared = whole - moe_ffn(h, no_shared, path_cfg)[0]
    parts, pairs = [], 0
    for first in (0, 2, 4, 6):
        cfg = dataclasses.replace(path_cfg, experts_held=(first, 2))
        mine = {**p, **{k: p[k][first: first + 2]
                        for k in ("w_gate", "w_up", "w_down")}}
        out, part_aux = moe_ffn(h, mine, cfg)
        want, _ = reference.expert_ffn(
            mine, x, **{**REF, "first_expert_held": first}
        )
        np.testing.assert_allclose(
            x + rms_norm(out[0], p["norm4"]), want, atol=TOL, rtol=0
        )
        assert (part_aux["routes"] == aux["routes"]).all()
        parts.append(out - shared)
        pairs += int(part_aux["expert_load"].sum())
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=TOL, rtol=0)
    uncut, _ = reference.expert_ffn(p, x, **REF)
    np.testing.assert_allclose(
        x + rms_norm(whole[0], p["norm4"]), uncut, atol=TOL, rtol=0
    )
    assert pairs == 24 * CFG.top_k  # every pair fell to exactly one share


def test_router_norms_and_cells_are_held_in_their_precision():
    """(d) of the issue: the tree as it is held at a bfloat16 config has
    its router and every norm in float32, and the cache's cells in the
    config's dtype: a router or a latent in a lower precision is a
    different dtype here, whatever a tolerance would let through."""
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    shapes = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))
    for block in shapes["blocks"]:
        for name, leaf in block.items():
            want = jnp.float32 if (
                "norm" in name or name.startswith("router")
            ) else jnp.bfloat16
            assert leaf.dtype == want, name
    cache = jax.eval_shape(lambda: latent_kv.init_latent_cache(cfg, 3, 8))
    assert cache["latent"].dtype == jnp.bfloat16
    assert cache["latent"].shape == (4, 3, 8, cfg.cell_width)
    # The router's product is float32 at highest precision in `moe_ffn`
    # whatever the activations' dtype: equal scores for equal inputs.
    p = init_params(jax.random.key(1), cfg)["blocks"][1]
    x = _x(8, 8).astype(jnp.bfloat16)
    _, aux = moe_ffn(x[None], p, cfg)
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), p["router"],
        precision=jax.lax.Precision.HIGHEST,
    ))
    want = jax.lax.top_k(scores + p["router_bias"], cfg.top_k)[1]
    assert (np.asarray(aux["routes"]) == np.asarray(want)).all()


# ---------------------------------------------------------- the decode kernel
def _kernel_case(positions, kk, page_size=8, max_pages=6, heads=4, seed=0):
    """Random cells and queries for ``positions`` [B]; the kernel
    (interpreted) and the gather path over the same block tables."""
    b = len(positions)
    width, v_width = CFG.cell_width, CFG.kv_lora_rank
    rng = np.random.default_rng(seed)
    pool = jnp.asarray(
        rng.normal(size=(1 + b * max_pages, page_size, width)), jnp.float32
    )
    q = jnp.asarray(rng.normal(size=(b, kk, heads, width)), jnp.float32)
    tables = np.full((b, max_pages), -1, np.int32)
    for i, pos in enumerate(positions):
        n = -(-(pos + kk) // page_size)
        tables[i, :n] = 1 + i * max_pages + rng.permutation(max_pages)[:n]
    positions = jnp.asarray(positions, jnp.int32)
    got = latent_paged_attention(
        q, pool, jnp.asarray(tables), positions, v_width=v_width,
        scale=CFG.softmax_scale, block_pages=2, interpret=True,
    )
    _, mask, _, _, clamped = _decode_geometry(
        jnp.asarray(tables), positions, kk, page_size
    )
    want = latent_kv._gather_latent_attention(q, pool, clamped, mask, CFG)
    return np.asarray(got), np.asarray(want)


@pytest.mark.parametrize(
    "positions, kk",
    [
        ([5, 0, 23, 47], 1),  # ragged, an empty slot, a full table
        ([7], 1),  # the write fills a page's last cell
        ([8], 1),  # the write opens a new page
        ([15, 16, 17], 1),  # a block boundary (2 pages a block)
        ([0, 0], 1),  # only empty slots
        ([6, 30], 3),  # K = 3: drafts span a page boundary
    ],
    ids=["ragged", "page_end", "page_start", "block_boundary", "all_empty", "k3"],
)
def test_latent_kernel_equals_the_gather_path(positions, kk):
    got, want = _kernel_case(positions, kk)
    assert got.shape == want.shape == (len(positions), kk, 4, CFG.kv_lora_rank)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def test_latent_kernel_reads_values_from_the_cells_first_columns():
    """One pool, read once: the values are the keys' first `v_width`
    columns. With the rotary and padding columns of every cell changed
    the scores change; with a uniform soft-max (zero queries) the output
    is the plain mean of the live cells' first columns."""
    b, page_size, width = 1, 8, CFG.cell_width
    pool = jnp.asarray(
        np.random.default_rng(1).normal(size=(4, page_size, width)), jnp.float32
    )
    tables = jnp.asarray([[2, 3, -1]], jnp.int32)
    out = latent_paged_attention(
        jnp.zeros((b, 1, 4, width)), pool, tables, jnp.asarray([10], jnp.int32),
        v_width=CFG.kv_lora_rank, scale=1.0, interpret=True,
    )
    live = np.concatenate([pool[2], pool[3]])[:11, : CFG.kv_lora_rank]
    np.testing.assert_allclose(
        out[0, 0], np.broadcast_to(live.mean(0), (4, CFG.kv_lora_rank)),
        atol=1e-5, rtol=0,
    )


@pytest.mark.parametrize(
    "start, chunk, table", [(0, 32, 32), (0, 16, 64), (16, 16, 64), (48, 16, 64)],
    ids=["whole", "first_chunk", "second_chunk", "last_chunk"],
)
def test_prefill_kernel_equals_the_blockwise_loop(params, start, chunk, table):
    """The flash-style prefill kernel (interpreted; blocks of 8 queries
    and 16 keys, so that blocks are skipped, crossed and whole) against
    the XLA loop over key blocks, on the same pages: a chunk of queries
    at `start` over a table of `table` cells of which the ones past the
    chunk are stale."""
    from ray_tpu.ops.pallas.latent_attention import latent_prefill_attention

    p = params["blocks"][1]
    rng = np.random.default_rng(start + chunk)
    pages = jnp.asarray(1 + rng.permutation(table // 8), jnp.int32)
    pool = jnp.asarray(
        rng.normal(size=(1 + table // 8, 8, CFG.cell_width)), jnp.float32
    ).at[..., CFG.latent_dim:].set(0.0)
    q_nope = jnp.asarray(rng.normal(size=(chunk, 4, 16)), jnp.float32)
    q_pe = jnp.asarray(rng.normal(size=(chunk, 4, 8)), jnp.float32)
    want = latent_kv._attend_expanded(
        q_nope, q_pe, pool, pages, jnp.int32(start), p, CFG
    )
    cells = pool[pages].reshape(table, -1)
    rank = CFG.kv_lora_rank
    got = latent_prefill_attention(
        q_nope.transpose(1, 0, 2),
        pad_to_cell(q_pe, CFG).transpose(1, 0, 2),
        jnp.einsum("tc,hcd->htd", cells[:, :rank], p["w_uk"]),
        cells[:, rank:],
        jnp.einsum("tc,hcd->htd", cells[:, :rank], p["w_uv"]),
        jnp.int32(start), scale=CFG.softmax_scale, block_q=8, block_kv=16,
        interpret=True,
    ).transpose(1, 0, 2)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    # And the engine's own wrapper of it.
    via = latent_kv._attend_expanded_kernel(
        q_nope, q_pe, pool, pages, jnp.int32(start), p, CFG
    )
    np.testing.assert_allclose(via, want, atol=2e-5, rtol=0)


# ------------------------------------------------- the expansion's bound
@pytest.mark.parametrize(
    "start, chunk, table, want",
    [(0, 2048, 16384, 2048), (6144, 2048, 16384, 8192),
     (14336, 2048, 16384, 16384), (0, 2048, 2048, 2048), (0, 16, 64, 64),
     (512, 512, 2048, 1024), (2048, 2048, 3072, 3072)],
    ids=["first_chunk", "middle", "last_chunk", "whole_prompt", "one_block",
         "half_a_block", "blocks_of_three_quarters"],
)
def test_keys_expanded_are_whole_key_blocks_up_to_the_chunks_end(
    start, chunk, table, want
):
    """The counters' rule, on the host: the kernels' key block is 1,024
    (the table where it holds fewer, the largest divisor under it where
    1,024 does not divide it), and a chunk expands the blocks up to the
    one that holds its last position."""
    assert keys_expanded(start, chunk, table) == want


def _expansion(p, start, chunk, table, seed=0):
    """(keys, values) of `latent_expand` (interpreted, key blocks of 16,
    two heads a step), the two einsums over the whole table, and the
    keys the chunk at ``start`` needs."""
    cells = jnp.asarray(
        np.random.default_rng(seed).normal(size=(table, CFG.cell_width)),
        jnp.float32,
    )
    got = latent_expand(
        cells, p["w_uk"], p["w_uv"], jnp.int32(start), n_queries=chunk,
        block_kv=16, block_groups=2, interpret=True,
    )
    rank = CFG.kv_lora_rank
    want = (jnp.einsum("tc,hcd->htd", cells[:, :rank], p["w_uk"]),
            jnp.einsum("tc,hcd->htd", cells[:, :rank], p["w_uv"]))
    return got, want, keys_expanded(start, chunk, table, 16)


@pytest.mark.parametrize(
    "start, chunk, table",
    [(0, 16, 64), (16, 16, 64), (24, 8, 64), (48, 16, 64), (0, 16, 16),
     (0, 64, 64)],
    ids=["first_chunk", "middle", "ends_in_a_block", "last_chunk",
         "one_block", "whole_prompt"],
)
def test_bounded_expansion_is_the_einsums_up_to_the_chunks_last_block(
    params, start, chunk, table
):
    """`latent_expand` (a key and a value a head: G == H) writes the key
    blocks up to the one that holds the chunk's last position, and they
    are the two einsums'; no step writes a block past it (the
    interpreter hands out NaN for what nothing wrote; on a chip it is
    whatever the buffer held)."""
    got, want, live = _expansion(params["blocks"][1], start, chunk, table)
    assert live == -(-(start + chunk) // 16) * 16
    for mine, whole in zip(got, want, strict=True):
        assert mine.shape == whole.shape == (4, table, 16)
        np.testing.assert_allclose(
            mine[:, :live], whole[:, :live], atol=2e-5, rtol=0
        )
        assert np.isnan(np.asarray(mine[:, live:])).all()


def _chunk_over_a_long_table(p, start, dead_page=None):
    """A chunk of 1,024 queries at ``start`` over a table of 3,072 cells
    (three key blocks of the kernels' 1,024; pages of 64) by the XLA
    loop and by the two kernels; with ``dead_page`` the kernels' table
    points at that page wherever a page lies past the chunk's end."""
    chunk, table, page = 1024, 3072, 64
    rng = np.random.default_rng(start)
    n = table // page
    pages = np.asarray(2 + rng.permutation(n), np.int32)
    pool = jnp.asarray(
        rng.normal(size=(2 + n, page, CFG.cell_width)), jnp.float32
    ).at[..., CFG.latent_dim:].set(0.0).at[1].set(jnp.nan)
    q_nope = jnp.asarray(rng.normal(size=(chunk, 4, 16)), jnp.float32)
    q_pe = jnp.asarray(rng.normal(size=(chunk, 4, 8)), jnp.float32)
    want = latent_kv._attend_expanded(
        q_nope, q_pe, pool, jnp.asarray(pages), jnp.int32(start), p, CFG
    )
    if dead_page is not None:
        pages[(start + chunk) // page:] = dead_page
    got = latent_kv._attend_expanded_kernel(
        q_nope, q_pe, pool, jnp.asarray(pages), jnp.int32(start), p, CFG
    )
    return np.asarray(got), np.asarray(want)


@pytest.mark.parametrize("start", [0, 1024, 2048], ids=["first", "middle", "last"])
def test_a_chunk_through_the_bounded_expansion_equals_the_xla_path(
    params, start
):
    """The engine's kernel path (`latent_expand`, then the prefill
    kernel, both interpreted at their own blocks of 1,024 keys) against
    the XLA loop over key blocks, which is bounded by the chunk's end
    itself: at `start` 0 two of the table's three key blocks are never
    expanded, and never read."""
    got, want = _chunk_over_a_long_table(params["blocks"][1], start)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


@pytest.mark.parametrize("start", [0, 1024], ids=["first", "middle"])
def test_pages_of_nan_past_the_chunks_end_change_nothing(params, start):
    """The table's pages past the chunk's end pointed at a page of NaN:
    neither kernel touches a key block past the chunk's last, so the
    output is finite and is the one of the table as it was."""
    p = params["blocks"][1]
    clean, _ = _chunk_over_a_long_table(p, start)
    got, want = _chunk_over_a_long_table(p, start, dead_page=1)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, clean)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


# ------------------------------------------------------------- the engine
def _engine(params, **kw):
    kw = {"max_batch": 4, "max_seq": 192, "page_size": 8, **kw}
    return LLMEngine(CFG, params=params, **kw)


def _tapped(eng):
    seen = []
    eng.on_logits = lambda phase, logits, record: seen.append(
        (phase, np.asarray(logits), jax.tree.map(np.asarray, record))
    )
    return seen


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(1, CFG.vocab_size, n).tolist()


@pytest.mark.parametrize("chunk", [None, 16], ids=["whole", "chunked"])
@pytest.mark.parametrize("kernel", ["0", "1"], ids=["gather", "kernel"])
def test_prefill_then_decode_equals_the_reference_pass(
    params, chunk, kernel, monkeypatch
):
    """(a) of the issue. A 45-token prompt (a padded bucket of 64; with
    `chunk` 16, three chunks, the last with 3 tokens of padding, each
    attending the earlier chunks' latent pages), then 5 decode steps in
    the absorbed form through the pages: the logits of the last prompt
    position and of every decoded one against the reference's ONE full
    non-absorbed pass over prompt plus generated tokens, its routes
    forced to the system's (they are equal anyway in float32, which is
    asserted); and the slot's pages against the reference's latents."""
    monkeypatch.setenv("RAY_TPU_PAGED_ATTN", kernel)
    eng = _engine(params, prefill_chunk=chunk)
    seen = _tapped(eng)
    prompt = _prompt(0, 45)
    rid = eng.add_request(prompt, SamplingParams(max_tokens=6))
    req, generated, pages = eng._queue[-1], None, []
    while generated is None:
        for fin in eng.step():
            assert fin["request_id"] == rid
            generated = fin["tokens"]
        pages = req.pages or pages
    tokens = prompt + generated[:-1]
    prefills = [s for s in seen if s[0].startswith("prefill")]
    decodes = [s for s in seen if s[0] == "decode"]
    assert len(prefills) == (1 if chunk is None else 3) and len(decodes) == 5
    routes = np.concatenate([s[2]["routes"] for s in prefills], 1)[:, :45]
    routes = np.concatenate(
        [routes] + [s[2]["routes"][:, :1] for s in decodes], 1
    )
    want, record = reference.forward_with_record(
        params, jnp.asarray(tokens, jnp.int32), routes=jnp.asarray(routes),
        **REF,
    )
    assert (np.sort(routes, -1) == np.sort(record["routes"], -1)).all()
    got = [prefills[-1][1][0, 0]] + [s[1][0] for s in decodes]
    np.testing.assert_allclose(
        np.stack(got), np.asarray(want)[44:], atol=TOL, rtol=0
    )
    cells = np.asarray(eng.cache["latent"])[:, pages].reshape(
        CFG.n_layers, -1, CFG.cell_width
    )[:, : len(tokens), : CFG.latent_dim]
    np.testing.assert_allclose(cells, record["latents"], atol=TOL, rtol=0)
    stats = eng.stats()
    assert stats["moe_pairs_here"] == stats["moe_pairs_routed"] == (
        len(tokens) * CFG.top_k * 3
    )
    assert stats["pool_bytes"] == eng.cache["latent"].nbytes
    assert stats["state_bytes"] == 0
    assert stats["latent_bytes_per_token"] == 4 * 40 * 4
    # Whole key blocks up to each chunk's end, the kernel path's (1,024
    # keys, so the table of 64 is one) or the XLA path's (16); four
    # layers.
    chunks = [(0, 64)] if chunk is None else [(0, 16), (16, 16), (32, 16)]
    assert stats["latent_tokens_expanded"] == 4 * sum(
        keys_expanded(s, c, 64) if kernel == "1" else s + c for s, c in chunks
    )
    assert stats["latent_prefill_programs"] == len(chunks)
    assert stats["latent_prefill_pairs"] == 4 * sum(
        c * s + c * (c + 1) // 2 for s, c in chunks
    )


def test_a_chunked_prefill_with_a_share_held_equals_the_reference_pass(
    monkeypatch,
):
    """Experts 4 and 5 of the 8 held, a quarter, as one chip of four
    holds them: a 45-token prompt in three 16-row chunks whose three
    expert layers take the sorted form under its row bound (blocks of 8
    rows here: a chunk's 48 pairs a layer are six), then 5 decode steps
    in the every-row form. Logits against the reference's one pass with
    the same share, and each program's ``counts``: the rows the grouped
    matmuls ran over lie between the pairs computed here and the pairs
    given, whole blocks of them."""
    monkeypatch.setattr(moe, "_PAIR_BLOCK", 8)
    tiny = {**TINY, "n_routed_experts": 2, "first_expert_held": 4,
            "published": {"n_routed_experts": 8}}
    cfg = bench_model.config(
        tiny, dtype=jnp.float32, dense_expert_rows=8, cell_lanes=16,
        prefill_key_block=16,
    )
    assert cfg.experts_held == (4, 2) and cfg.num_experts == 8
    held = init_params(jax.random.key(3), cfg)
    eng = LLMEngine(cfg, params=held, max_batch=4, max_seq=192, page_size=8,
                    prefill_chunk=16)
    seen = _tapped(eng)
    prompt = _prompt(0, 45)
    (generated,) = eng.generate([prompt], SamplingParams(max_tokens=6))
    tokens = prompt + generated[:-1]
    prefills = [s for s in seen if s[0].startswith("prefill")]
    decodes = [s for s in seen if s[0] == "decode"]
    assert len(prefills) == 3 and len(decodes) == 5
    routes = np.concatenate([s[2]["routes"] for s in prefills], 1)[:, :45]
    routes = np.concatenate(
        [routes] + [s[2]["routes"][:, :1] for s in decodes], 1
    )
    want, record = reference.forward_with_record(
        held, jnp.asarray(tokens, jnp.int32), routes=jnp.asarray(routes),
        **reference.for_model(tiny),
    )
    assert (np.sort(routes, -1) == np.sort(record["routes"], -1)).all()
    got = [prefills[-1][1][0, 0]] + [s[1][0] for s in decodes]
    np.testing.assert_allclose(
        np.stack(got), np.asarray(want)[44:], atol=TOL, rtol=0
    )

    computed = given = 0
    for live, (_, _, rec) in zip((16, 16, 13), prefills):
        here, _, rows, pairs = (int(v) for v in rec["counts"])
        in_share = (rec["routes"][:, :live] >= 4) & (rec["routes"][:, :live] < 6)
        assert here == in_share.sum()
        assert pairs == 16 * cfg.top_k * 3  # the padded rows' pairs too
        assert here <= rows < pairs and rows % 8 == 0
        assert rows - here < 8 * 3  # under a block a layer
        computed, given = computed + rows, given + pairs
    for _, _, rec in decodes:
        assert rec["counts"][2:].tolist() == [0, 0]  # the every-row form
    stats = eng.stats()
    assert 0 < stats["moe_pairs_here"] < stats["moe_pairs_routed"]
    assert stats["moe_rows_computed"] == computed
    assert stats["moe_sorted_rows_pct"] == 100.0 * computed / given


def test_kernel_and_gather_paths_give_identical_greedy_streams(
    params, monkeypatch
):
    prompts = [_prompt(4, 30), _prompt(5, 18), _prompt(6, 41)]
    sampling = SamplingParams(max_tokens=8)
    monkeypatch.setenv("RAY_TPU_PAGED_ATTN", "0")
    want = _engine(params, prefill_chunk=16).generate(prompts, sampling)
    monkeypatch.setenv("RAY_TPU_PAGED_ATTN", "1")
    eng = _engine(params, prefill_chunk=16)
    assert eng.paged_attn_kernel
    assert eng.generate(prompts, sampling) == want
    # A decode step ran under the host's work on the step before it.
    assert eng.stats()["decode_in_flight_pct"] > 0


def test_a_long_prompt_expands_key_blocks_up_to_each_chunks_end(
    params, monkeypatch
):
    """2,100 tokens in three chunks of 1,024 over a table of 4,096 cells
    (the bucket's 64 pages of 64: four key blocks of the kernels'
    1,024), through the engine's kernel path: the counter is the key
    blocks up to each chunk's end, 1 + 2 + 3 of the 3 x 4 the whole
    table would be, in each of the four layers, and the first token's
    logits are the XLA path's."""
    kw = {"max_seq": 4096, "page_size": 64, "num_pages": 80,
          "prefill_chunk": 1024, "max_batch": 1}
    last = {}
    for kernel in ("0", "1"):
        monkeypatch.setenv("RAY_TPU_PAGED_ATTN", kernel)
        eng = _engine(params, **kw)
        seen = _tapped(eng)
        eng.generate([_prompt(9, 2100)], SamplingParams(max_tokens=1))
        prefills = [s for s in seen if s[0].startswith("prefill")]
        assert len(prefills) == 3
        last[kernel] = prefills[-1][1][0, 0]
    np.testing.assert_allclose(last["1"], last["0"], atol=TOL, rtol=0)
    stats = eng.stats()
    assert stats["latent_prefill_programs"] == 3
    assert stats["latent_tokens_expanded"] == 4 * (1024 + 2048 + 3072)


def test_a_preemption_by_recompute_changes_nothing(params):
    """A pool too small for both requests' growth: the younger one is
    preempted, its pages freed, and prefilled again from its whole
    context; both streams are what each request gives alone."""
    prompts = [_prompt(7, 30), _prompt(8, 30)]
    sampling = SamplingParams(max_tokens=20)
    want = [
        _engine(params).generate([p], sampling)[0] for p in prompts
    ]
    eng = _engine(params, num_pages=10)
    assert eng.generate(prompts, sampling) == want
    assert eng.stats()["preemptions"] >= 1
    assert eng.alloc.free_pages == eng.alloc.num_pages


def test_chunked_prefill_beside_decoding_slots_changes_nothing(params):
    """A long prompt goes in chunks while another slot decodes: both
    streams are what each request gives alone."""
    sampling = SamplingParams(max_tokens=12)
    a, b = _prompt(9, 12), _prompt(10, 70)
    alone = [_engine(params, prefill_chunk=16).generate([p], sampling)[0]
             for p in (a, b)]
    eng = _engine(params, prefill_chunk=16)
    ids = [eng.add_request(a, sampling)]
    eng.step()
    ids.append(eng.add_request(b, sampling))
    done = {}
    while eng.has_unfinished():
        for fin in eng.step():
            done[fin["request_id"]] = fin["tokens"]
    assert [done[i] for i in ids] == alone
    assert eng.stats()["prefill_chunks"] >= 5


def test_speculation_and_a_mesh_are_refused_with_a_sentence(params):
    with pytest.raises(ValueError, match="one token a slot"):
        _engine(params, speculate=2)
    with pytest.raises(NotImplementedError, match="one chip's share"):
        CFG.serving().logical_axes()


def test_config_counts_the_published_model():
    """The program's config at the published sizes holds what the issue
    counted: 196.6M of attention a layer, 621.3M a dense layer, 12.3B an
    expert layer whole and 1,000.7M with 16 experts held, 718B in all
    (without the multi-token-prediction module)."""
    cfg = PanguUltraMoEConfig()
    shapes = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))
    sizes = [
        sum(int(np.prod(x.shape)) for x in jax.tree.leaves(b))
        for b in shapes["blocks"]
    ]
    assert cfg.pattern == "DDD" + "E" * 58
    assert round(sizes[0] / 1e6, 1) == 621.3
    assert round(sizes[3] / 1e9, 1) == 12.3
    total = sum(sizes) + 2 * cfg.vocab_size * cfg.d_model + cfg.d_model
    assert 700e9 < total < 720e9
    held = dataclasses.replace(cfg, experts_held=(0, 16))
    shapes = jax.eval_shape(lambda k: init_params(k, held), jax.random.key(0))
    assert round(sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(shapes["blocks"][3])
    ) / 1e6, 1) == 1000.7
    assert cfg.latent_dim == 576 and cfg.cell_width == 640
