"""Laguna (models/laguna.py through llm/hybrid_kv.py) against the plain
reference (benchmarks/reference_laguna.py) at a tiny size, float32,
seeded weights, on the CPU: the dense layer and two whole periods (`*D`,
then `WE WE WE *E` twice), window 8, pages of 4, chunks of 16:
prefill-then-decode through `LLMEngine`'s pages (full layers) and
per-slot windows (window layers), the band kernel interpreted against
dense masked attention, the expert share, and each thing the family
adds dropped in turn.

Tolerances: everything here is float32 on both sides, so differences
are summation order only. 2e-4 absolute on logits of magnitude ~3 leaves
several times what float32 reassociation gives across eighteen sublayers
(measured 3e-6 to 3e-5), and is many times under what any mathematical
difference produces: the smallest of those below moves logits by 0.01
and more."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_laguna as reference
from benchmarks.models import laguna as bench_model
from ray_tpu.llm import hybrid_kv
from ray_tpu.llm.engine import LLMEngine, SamplingParams
from ray_tpu.models import laguna, moe
from ray_tpu.models.laguna import LagunaConfig, init_params
from ray_tpu.models.moe import moe_ffn
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.pallas.window_attention import (
    band_blocks,
    window_attention,
    window_attention_dense,
)
from ray_tpu.ops.rope import yarn_inv_freq

TOL = 2e-4
W, PAGE, CHUNK = 8, 4, 16

# The published keys (the catalog's) at a tiny size: what a
# configuration file carries (the per-layer lists longer than the layers
# run, as the file carries the published 48), so that `config` and
# `for_model` are under test too.
TINY = {
    "model_type": "laguna", "hidden_size": 64, "vocab_size": 256,
    "intermediate_size": 96, "num_hidden_layers": 9,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "max_position_embeddings": 256, "attention_bias": False,
    "rms_norm_eps": 1e-6, "num_experts": 8, "num_experts_per_tok": 3,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 48,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
    "tie_word_embeddings": False, "gating": "per-head", "sliding_window": W,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 100, "rope_type": "yarn", "factor": 8,
            "original_max_position_embeddings": 32, "beta_slow": 1,
            "beta_fast": 4, "attention_factor": 1.2079441541679836,
            "partial_rotary_factor": 0.5,
        },
        "sliding_attention": {
            "rope_type": "default", "rope_theta": 10000,
            "partial_rotary_factor": 1,
        },
    },
    "layer_types": [
        "sliding_attention" if layer % 4 else "full_attention"
        for layer in range(12)
    ],
    "moe_apply_router_weight_on_input": False,
    "mlp_layer_types": ["dense"] + ["sparse"] * 11,
    "gating_types": ["per_head"] * 12, "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [6 if layer % 4 else 4 for layer in range(12)],
    "moe_router_logit_softcapping": 0,
}
# Rows up to 8 take `moe_ffn`'s every-row form and more its sorted one,
# so that an engine's decode steps (2 slots) run the first and its
# prefills (16 rows and more) the second, as the two meet in a replica.
CFG = bench_model.config(TINY, dtype=jnp.float32, dense_expert_rows=8)
REF = reference.for_model(TINY)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.key(3), CFG)


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(1, CFG.vocab_size, n).tolist()


def _tapped(eng):
    """Every program's logits and record, as `on_logits` hands them over."""
    seen = []
    eng.on_logits = lambda phase, logits, record: seen.append(
        (phase, np.asarray(logits), jax.tree.map(np.asarray, record))
    )
    return seen


def _engine(params, cfg=CFG, **kw):
    kw = {"max_batch": 2, "max_seq": 192, "page_size": PAGE, **kw}
    return LLMEngine(cfg, params=params, **kw)


def _split(seen):
    return ([s for s in seen if s[0].startswith("prefill")],
            [s for s in seen if s[0] == "decode"])


def _routes(prefills, decodes, n, slot=0):
    routes = np.concatenate([s[2]["routes"] for s in prefills], axis=1)[:, :n]
    return np.concatenate(
        [routes] + [s[2]["routes"][:, slot: slot + 1] for s in decodes], axis=1
    )


def test_the_config_is_the_published_layer_pattern():
    assert CFG.pattern == "*D" + "WEWEWE*E" * 2
    assert [CFG.count(kind) for kind in "*WDE"] == [3, 6, 1, 8]
    assert (CFG.n_heads, CFG.window_heads, CFG.sliding_window) == (4, 6, W)
    assert (CFG.rotary_dim, CFG.window_rotary_dim, CFG.norm_eps) == (8, 16, 1e-6)
    assert laguna.LAGUNA_PRESETS["laguna_tiny"] == CFG
    whole = LagunaConfig()
    assert whole.pattern == "*D" + "WEWEWE*E" * 11 + "WEWEWE"
    assert (whole.count("*"), whole.count("W")) == (12, 36)
    assert whole.rope_yarn == (128.0, 8192, 32.0, 1.0, 1.4852030263919618)
    with pytest.raises(ValueError, match="its attention"):
        LagunaConfig(pattern="*DEW")
    with pytest.raises(ValueError, match="blocks are of"):
        LagunaConfig(pattern="MEWE")
    with pytest.raises(ValueError, match="gating"):
        bench_model.config({**TINY, "gating": True})
    with pytest.raises(ValueError, match="a full layer of"):
        bench_model.config({**TINY, "num_attention_heads": 6})


def test_yarn_frequencies_keep_the_fast_pairs_and_stretch_the_slow():
    """Laguna-S-2.1's full layers: 32 pairs, the ramp between the pairs
    that make 32 turns and 1 turn in 8,192 positions (9 and 18)."""
    theta, factor = 500000.0, 128.0
    got = yarn_inv_freq(64, theta, factor, 8192, 32.0, 1.0)
    plain = theta ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(got[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(got[18:], plain[18:] / factor, rtol=1e-6)
    assert (got[10:18] < plain[10:18]).all()
    assert (got[10:18] > plain[10:18] / factor).all()
    want, scale = reference._frequencies(
        {"rope_type": "yarn", "rope_theta": theta, "factor": factor,
         "original_max_position_embeddings": 8192, "beta_fast": 32,
         "beta_slow": 1, "attention_factor": 1.25}, 64,
    )
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert scale == 1.25


@pytest.mark.parametrize(
    "chunk, calls", [(None, 1), (CHUNK, 5)], ids=["whole", "five_chunks"]
)
def test_prefill_then_decode_equals_the_reference_pass(params, chunk, calls):
    """A 75-token prompt (nine windows; with `chunk` 16, five chunks, the
    last with 5 tokens of padding: the windows carried four times, the
    full layers reading earlier chunks' pages at their true positions),
    then 29 decode steps (more than three windows: every ring index is
    overwritten three times) through the pages and the slot's windows:
    the LOGITS of the last prompt position and of every decoded one
    against the reference's ONE full pass over prompt plus generated
    tokens, its routes forced to the system's (they are equal anyway in
    float32, which is asserted); and each window layer's carried keys
    and values against the reference's at the last 8 positions."""
    eng = _engine(params, prefill_chunk=chunk)
    seen = _tapped(eng)
    prompt = _prompt(0, 75)
    (generated,) = eng.generate([prompt], SamplingParams(max_tokens=30))
    tokens = prompt + generated
    prefills, decodes = _split(seen)
    assert len(prefills) == calls and len(decodes) == 29
    routes = _routes(prefills, decodes, 75)
    want, record = reference.forward_with_record(
        params, jnp.asarray(tokens[:-1], jnp.int32), routes=routes, **REF
    )
    assert routes.shape == (8, 104, 3)
    assert (np.sort(routes, -1) == np.sort(record["routes"], -1)).all()
    assert float(np.abs(want).max()) > 0.3  # logits of a size to compare
    np.testing.assert_allclose(prefills[-1][1][0, 0], want[74], atol=TOL, rtol=0)
    for i, step in enumerate(decodes):
        np.testing.assert_allclose(step[1][0], want[75 + i], atol=TOL, rtol=0)
    # The request is over; the windows it left are still the slot's.
    assert record["windows"].shape == (6, 2, W, 2, 16)
    np.testing.assert_allclose(
        bench_model.carried_windows(eng.cache, 0, 104), record["windows"],
        atol=TOL, rtol=0,
    )
    stats = eng.stats()
    assert stats["moe_pairs_routed"] == (75 + 29) * CFG.top_k * 8
    assert stats["prefill_programs"] == calls
    # Full layers: every key at or before each query. Window layers:
    # min(t + 1, 8) keys a query.
    assert stats["prefill_attn_pairs"] == 3 * 75 * 76 // 2
    assert stats["prefill_window_pairs"] == 6 * (W * (W + 1) // 2 + (75 - W) * W)
    assert stats["window_tokens"] == 6 * 75
    assert stats["ssm_scan_tokens"] == stats["gdn_scan_tokens"] == 0


def test_a_slot_reused_after_a_longer_request_sees_none_of_its_keys(params):
    """One slot: the second request is shorter than a window's worth of
    chunks, so its rings still hold the first one's keys at the indices
    it has not reached, and it prefills and decodes over them. Its
    logits are the reference's for its own tokens alone."""
    eng = _engine(params, max_batch=1, prefill_chunk=CHUNK)
    eng.generate([_prompt(1, 50)], SamplingParams(max_tokens=4))
    seen = _tapped(eng)
    prompt = _prompt(2, 5)
    (generated,) = eng.generate([prompt], SamplingParams(max_tokens=3))
    prefills, decodes = _split(seen)
    assert len(prefills) == 1 and len(decodes) == 2
    want = reference.forward(
        params, jnp.asarray((prompt + generated)[:-1], jnp.int32),
        routes=_routes(prefills, decodes, 5), **REF,
    )
    np.testing.assert_allclose(prefills[-1][1][0, 0], want[4], atol=TOL, rtol=0)
    for i, step in enumerate(decodes):
        np.testing.assert_allclose(step[1][0], want[5 + i], atol=TOL, rtol=0)


def test_a_chunked_prefill_with_a_share_held_equals_the_reference_pass(
    monkeypatch,
):
    """Experts 2-5 of the 8 held, as a chip of an expert-parallel pair
    holds them: a 75-token prompt in five 16-row chunks, whose expert
    sublayers take the sorted form, then 5 decode steps in the every-row
    form. Logits against the reference's one pass with the same share."""
    monkeypatch.setattr(moe, "_PAIR_BLOCK", 16)
    tiny = {**TINY, "num_experts": 4, "first_expert_held": 2,
            "published": {"num_experts": 8}}
    cfg = bench_model.config(tiny, dtype=jnp.float32, dense_expert_rows=8)
    assert cfg.experts_held == (2, 4) and cfg.num_experts == 8
    held = init_params(jax.random.key(3), cfg)
    eng = _engine(held, cfg, prefill_chunk=CHUNK)
    seen = _tapped(eng)
    prompt = _prompt(0, 75)
    (generated,) = eng.generate([prompt], SamplingParams(max_tokens=6))
    prefills, decodes = _split(seen)
    routes = _routes(prefills, decodes, 75)
    want, record = reference.forward_with_record(
        held, jnp.asarray((prompt + generated)[:-1], jnp.int32),
        routes=routes, **reference.for_model(tiny),
    )
    assert (np.sort(routes, -1) == np.sort(record["routes"], -1)).all()
    np.testing.assert_allclose(prefills[-1][1][0, 0], want[74], atol=TOL, rtol=0)
    for i, step in enumerate(decodes):
        np.testing.assert_allclose(step[1][0], want[75 + i], atol=TOL, rtol=0)
    stats = eng.stats()
    assert 0 < stats["moe_pairs_here"] < stats["moe_pairs_routed"]


def test_kernel_and_xla_attention_paths_agree(params, monkeypatch):
    """Greedy streams are equal between the Pallas paths (the band kernel
    and the prefill kernel in the chunk programs, the paged kernel in
    the decode program, at this family's two head counts, interpreted
    here) and XLA's dense paths. A window of 16 in chunks of 32: the
    smallest the band kernel's blocks divide."""
    cfg = dataclasses.replace(CFG, sliding_window=16)
    assert band_blocks(32, 16) == (32, 16)
    prompts = [_prompt(4, 70), _prompt(5, 18)]
    sampling = SamplingParams(max_tokens=20)
    kw = {"page_size": 16, "prefill_chunk": 32}
    # The prefill kernel is for tables wider than a tiny engine has.
    monkeypatch.setattr(hybrid_kv, "_DENSE_ATTENTION_KEYS", 0)
    hybrid_kv._prefill_program.cache_clear()
    monkeypatch.setenv("RAY_TPU_PAGED_ATTN", "0")
    want = _engine(params, cfg, **kw).generate(prompts, sampling)
    monkeypatch.setenv("RAY_TPU_PAGED_ATTN", "1")
    eng = _engine(params, cfg, **kw)
    assert eng.paged_attn_kernel
    assert eng.generate(prompts, sampling) == want
    hybrid_kv._prefill_program.cache_clear()


# ------------------------------------------------------ the band kernel
@pytest.mark.parametrize("start", [0, 32, 8192], ids=[
    "first_chunk", "inside_the_first_window", "many_windows_in"])
@pytest.mark.parametrize("n_rep", [9, 6])
def test_band_kernel_equals_dense_masked_attention(n_rep, start):
    """128 queries over a window of 64 at heads of 128, nine and six
    query heads a KV head, bf16, interpreted: at `start` 0 the carried
    keys are all of positions before 0 (NaN here: they must reach
    nothing), at 32 half of them are, far in none. Blocks of 32 x 16 (six
    key tiles a query block, of which two hold no masked pair), and 64
    x 64 (two, both masked)."""
    c, w, hkv, dh = 128, 64, 2, 128
    keys = jax.random.split(jax.random.key(start + n_rep), 3)
    q = jax.random.normal(keys[0], (c, hkv * n_rep, dh)).astype(jnp.bfloat16)
    k = jax.random.normal(keys[1], (hkv, w + c, dh)).astype(jnp.bfloat16)
    v = jax.random.normal(keys[2], (hkv, w + c, dh)).astype(jnp.bfloat16)
    want = window_attention_dense(q, k, v, jnp.int32(start), window=w)
    unwritten = max(w - start, 0)  # keys of positions before 0
    k = k.at[:, :unwritten].set(jnp.nan)
    for blocks in ((32, 16), (64, 64)):
        got = window_attention(
            q, k, v, jnp.int32(start), window=w, block_q=blocks[0],
            block_kv=blocks[1], interpret=True,
        )
        np.testing.assert_allclose(
            got.astype(jnp.float32), want.astype(jnp.float32), atol=0.02,
            rtol=0,
        )
    # The definition itself: query i of the chunk sees exactly the keys
    # at positions max(start + i - w + 1, 0) .. start + i.
    i = 70
    lo = max(w + i - w + 1, unwritten)
    scores = (q[i].astype(jnp.float32).reshape(hkv, n_rep, dh)
              @ k[:, lo: w + i + 1].astype(jnp.float32).transpose(0, 2, 1))
    probs = jax.nn.softmax(scores * dh**-0.5, axis=-1)
    row = probs @ v[:, lo: w + i + 1].astype(jnp.float32)
    np.testing.assert_allclose(
        want[i].astype(jnp.float32), row.reshape(hkv * n_rep, dh), atol=0.02,
        rtol=0,
    )


def test_band_blocks_divide_the_chunk_and_the_window():
    assert band_blocks(2048, 512) == (256, 256)
    assert band_blocks(2048, 512, 128, 128) == (128, 128)
    assert band_blocks(1024, 512, 512, 512) == (512, 512)
    assert band_blocks(64, 512) == (64, 64)
    assert band_blocks(CHUNK, W) is None  # a window of 8: dense scores
    assert band_blocks(2048, 500) is None


# ------------------------------------------- what the family adds, held
def _last_logits(cfg, tree, tokens):
    """The whole-prompt program alone, on the XLA path: logits of the
    last token."""
    n_pages = -(-len(tokens) // PAGE)
    padded = np.zeros((1, n_pages * PAGE), np.int32)
    padded[0, : len(tokens)] = tokens
    cache = hybrid_kv.init_hybrid_cache(cfg, n_pages + 1, PAGE, 1)
    logits, _, _ = hybrid_kv.prefill_program(cfg, n_pages, n_pages, False)(
        tree, padded, cache, np.arange(1, n_pages + 1, dtype=np.int32),
        np.int32(0), np.int32(0), np.int32(len(tokens)),
    )
    return np.asarray(logits[0, 0])


def _fewer_window_heads(tree, cfg, heads: int):
    """The tree with each window block cut to the first ``heads / Hkv``
    query heads of every KV head's group."""
    hkv, dh, d = cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    group, keep = cfg.window_heads // hkv, heads // hkv

    def cut(p):
        return {
            **p,
            "wq": p["wq"].reshape(d, hkv, group, dh)[:, :, :keep].reshape(d, -1),
            "wg": p["wg"].reshape(d, hkv, group)[:, :, :keep].reshape(d, -1),
            "wo": p["wo"].reshape(hkv, group, dh, d)[:, :keep].reshape(-1, d),
        }

    return {**tree, "blocks": tuple(
        cut(p) if kind == "W" else p
        for kind, p in zip(cfg.pattern, tree["blocks"])
    )}


# The dense layer and ONE period, for the cases below: every kind of
# sublayer at half the depth.
CFG5 = dataclasses.replace(CFG, pattern=CFG.pattern[:10])
REF5 = reference.for_model({**TINY, "num_hidden_layers": 5})
TOKENS = _prompt(9, 27)


@pytest.fixture(scope="module")
def params5(params):
    return {**params, "blocks": params["blocks"][:10]}


@pytest.fixture(scope="module")
def published_logits(params5):
    return np.asarray(reference.forward(
        params5, jnp.asarray(TOKENS, jnp.int32), **REF5
    ))[-1]


@pytest.mark.parametrize(
    "dropped",
    [None, "no_window", "no_gate", "no_yarn_factor", "no_partial_rotary",
     "no_routed_scaling", "full_heads_in_window"],
)
def test_each_part_the_family_adds_is_held(params5, published_logits, dropped):
    """The program as published is within the limit of the reference;
    with a window layer attending everything, without the gate, without
    YaRN's factor on cos and sin, with the whole head rotated in a full
    layer, with gates not times 2.5, or with 4 heads where a window layer
    has 6, it is not: each is held by the comparison. And the reference
    asked for the same departure (`lower`, what the chip check reads its
    failing limits with) agrees with the program that has it."""
    cfg, tree = {
        None: (CFG5, params5),
        "no_window": (dataclasses.replace(CFG5, sliding_window=64), params5),
        "no_gate": (dataclasses.replace(CFG5, head_gate=False), params5),
        "no_yarn_factor": (dataclasses.replace(
            CFG5, rope_yarn=(*CFG5.rope_yarn[:4], 1.0)), params5),
        "no_partial_rotary": (dataclasses.replace(CFG5, rotary_dim=16), params5),
        "no_routed_scaling": (
            dataclasses.replace(CFG5, routed_scaling_factor=1.0), params5),
        "full_heads_in_window": (
            dataclasses.replace(CFG5, window_heads=4),
            _fewer_window_heads(params5, CFG5, 4)),
    }[dropped]
    got = _last_logits(cfg, tree, TOKENS)
    worst = float(np.abs(got - published_logits).max())
    assert (worst <= TOL) == (dropped is None), worst
    if dropped is not None:
        assert worst > 50 * TOL
        departed = np.asarray(reference.forward(
            params5, jnp.asarray(TOKENS, jnp.int32), **REF5, lower=dropped
        ))[-1]
        np.testing.assert_allclose(got, departed, atol=TOL, rtol=0)


def _lowered_text(cfg, init):
    tree = jax.eval_shape(lambda k: init(k, cfg), jax.random.key(0))
    cache = jax.eval_shape(lambda: hybrid_kv.init_hybrid_cache(cfg, 4, 16, 2))
    i32 = jax.ShapeDtypeStruct
    prefill = hybrid_kv.prefill_program(cfg, 2, 2, False).lower(
        tree, i32((1, 32), jnp.int32), cache, i32((2,), jnp.int32),
        np.int32(0), np.int32(0), np.int32(9),
    ).as_text(debug_info=True)
    decode = hybrid_kv.hybrid_decode.lower(
        tree, i32((2, 1), jnp.int32), cache, i32((2, 4), jnp.int32),
        i32((2,), jnp.int32), i32((2,), jnp.bool_), i32((2,), jnp.float32),
        jax.eval_shape(lambda: jax.random.key(0)), cfg=cfg, use_kernel=False,
    ).as_text(debug_info=True)
    return sorted(cache), prefill + decode


@pytest.mark.parametrize("family", ["nemotron_h", "granite_hybrid", "qwen3_next"])
def test_the_other_families_programs_hold_none_of_it(family):
    """Nemotron-H's, Granite's and Qwen3-Next's programs and caches have
    no window leaf, no scope of a window or of a second attention kind,
    no dense FFN and no head-wise gate matrix; this family's have each."""
    from ray_tpu.models import granite_hybrid, nemotron_h, qwen3_next

    cfg, init = {
        "nemotron_h": (
            nemotron_h.NEMOTRON_H_PRESETS["nemotron_h_tiny"],
            nemotron_h.init_params),
        "granite_hybrid": (
            granite_hybrid.GraniteHybridConfig(
                vocab_size=256, d_model=64, pattern="MEME*EME", n_heads=4,
                n_kv_heads=2, head_dim=16, mamba_heads=8, mamba_head_dim=16,
                ssm_groups=1, ssm_state=16, chunk_size=8, num_experts=8,
                top_k=3, d_ff=32, shared_d_ff=48, max_seq=256,
                dtype=jnp.float32, dense_expert_rows=8),
            granite_hybrid.init_params),
        "qwen3_next": (
            qwen3_next.QWEN3_NEXT_PRESETS["qwen3_next_tiny"],
            qwen3_next.init_params),
    }[family]
    marks = ("attn:window", "attn:window_write", "attn:full", "ffn:dense")
    leaves, text = _lowered_text(cfg, init)
    assert not [leaf for leaf in leaves if leaf.startswith("win_")]
    assert not [mark for mark in marks if mark in text]
    assert not (cfg.head_gate or cfg.rope_yarn)
    mine, text = _lowered_text(CFG, init_params)
    assert [leaf for leaf in mine if leaf.startswith("win_")] == ["win_k", "win_v"]
    assert [mark for mark in marks if mark in text] == list(marks)


# ------------------------------------------------------------- the cache
def test_a_window_layers_cache_is_the_window_and_pages_are_full_layers_only(
    params,
):
    """What a slot keeps for a window layer is `sliding_window` keys and
    values whatever `max_seq`; the page pool has the full layers alone,
    and a request's pages are its tokens over the page size."""
    short = _engine(params, max_seq=64, num_pages=40)
    long = _engine(params, max_seq=192, num_pages=40)
    per_slot = 2 * CFG.n_kv_heads * W * CFG.head_dim * 4
    for eng in (short, long):
        assert eng.cache["win_k"].shape == (6, 2, W, 2, 16)
        assert eng.cache["k"].shape[:2] == (3, 41)
        stats = eng.stats()
        assert stats["window_bytes"] == stats["state_bytes"] == 6 * 2 * per_slot
        assert stats["pool_bytes"] == 2 * 3 * 41 * 2 * PAGE * 16 * 4
    long.add_request(_prompt(3, 30), SamplingParams(max_tokens=2))
    long.step()
    stats = long.stats()
    assert stats["pages_total"] - stats["pages_free"] == 32 // PAGE


def test_router_norms_and_rings_are_held_in_their_precision():
    """The tree as it is held at a bfloat16 config has its routers and
    every norm in float32 and the cache's rings and pages in the config's
    dtype; and `moe_ffn`'s router product is float32 whatever the
    activations' dtype, which is what holds the router to float32: the
    chip check cannot tell a bfloat16 router from it
    (`benchmarks/models/laguna.py check_problems`)."""
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    shapes = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))
    for block in shapes["blocks"]:
        for name, leaf in block.items():
            want = jnp.float32 if (
                "norm" in name or name == "router"
            ) else jnp.bfloat16
            assert leaf.dtype == want, name
    cache = jax.eval_shape(lambda: hybrid_kv.init_hybrid_cache(cfg, 3, PAGE, 2))
    assert {leaf.dtype for leaf in cache.values()} == {jnp.dtype(jnp.bfloat16)}
    p = init_params(jax.random.key(1), cfg)["blocks"][3]
    x = jax.random.normal(jax.random.key(2), (8, cfg.d_model)).astype(jnp.bfloat16)
    _, aux = moe_ffn(x[None], p, cfg)
    logits = jnp.dot(
        x.astype(jnp.float32), p["router"], precision=jax.lax.Precision.HIGHEST
    )
    want = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.top_k)[1]
    assert (np.asarray(aux["routes"]) == np.asarray(want)).all()


# ------------------------------------------------------------ the share
@pytest.fixture(params=["sorted_pairs", "every_row"])
def path_cfg(request):
    """`moe_ffn`'s two ways to apply the experts, each forced in turn,
    over 4 experts of which 2 a token."""
    rows = 0 if request.param == "sorted_pairs" else 10**6
    return dataclasses.replace(
        CFG, num_experts=4, top_k=2, dense_expert_rows=rows
    )


def test_the_shares_add_up_to_the_uncut_layer(params, path_cfg):
    """Expert parallelism over two chips: each share holds 2 of 4
    experts (0-1 and 2-3, as the deployment's chips hold 0-127 and
    128-255), routes over all 4 (the gates renormalised over the chosen
    two and times 2.5, wherever they live) and computes its own experts'
    part. The two routed parts plus the shared expert ONCE are the uncut
    reference's layer (model-configs guide, section 4); each share also
    equals the reference given the same share."""
    whole = params["blocks"][3]
    p = {**whole, "router": whole["router"][:, :4],
         **{k: whole[k][:4] for k in ("w_gate", "w_up", "w_down")}}
    sizes = {**REF, "num_experts_per_tok": 2}
    x = jax.random.normal(jax.random.key(6), (24, CFG.d_model))
    normed = rms_norm(x, p["norm"], CFG.norm_eps)
    with jax.default_matmul_precision("highest"):
        shared = reference._gated(
            normed, p["shared_gate"], p["shared_up"], p["shared_down"], None
        )
    uncut, record = reference.expert_sublayer(p, x, **sizes)
    parts, pairs = [], 0
    for first in (0, 2):
        cfg = dataclasses.replace(path_cfg, experts_held=(first, 2))
        mine = {**p, **{k: p[k][first: first + 2]
                        for k in ("w_gate", "w_up", "w_down")}}
        out, aux = moe_ffn(normed[None], mine, cfg)
        want, _ = reference.expert_sublayer(
            mine, x, **{**sizes, "first_expert_held": first}
        )
        np.testing.assert_allclose(x + out[0], want, atol=TOL, rtol=0)
        assert (np.sort(aux["routes"], -1)
                == np.sort(record["routes"], -1)).all()
        parts.append(out[0] - shared)
        pairs += int(aux["expert_load"].sum())
    np.testing.assert_allclose(
        x + parts[0] + parts[1] + shared, uncut, atol=TOL, rtol=0
    )
    assert float(np.abs(shared).max()) > 0.01  # a shared part to count once
    assert pairs == 24 * 2  # every pair fell to exactly one share


def test_config_counts_the_published_model():
    """The program's config at the published sizes holds what the issue
    counted: 117.5B parameters uncut, a full attention block 44.19M, a
    window block 63.14M, the dense FFN 113.25M, an expert FFN with 128
    of 256 held 1,218.2M, 5.572B held in all; and the benchmark's own
    count of the configuration it runs agrees with the tree's."""

    def sizes(c):
        shapes = jax.eval_shape(lambda k: init_params(k, c), jax.random.key(0))
        return shapes, dict(zip(c.pattern, (
            sum(int(np.prod(x.shape)) for x in jax.tree.leaves(b))
            for b in shapes["blocks"]
        )))

    assert round(LagunaConfig().num_params() / 1e9, 1) == 117.6
    here = os.path.join(os.path.dirname(__file__), "..", "benchmarks")
    with open(os.path.join(here, "configs", "laguna-s21-serve1.json")) as f:
        conf = json.load(f)
    served = bench_model.config(conf, max_seq=conf["engine"]["max_seq"])
    shapes, by_kind = sizes(served)
    assert round(by_kind["*"] / 1e6, 2) == 44.19
    assert round(by_kind["W"] / 1e6, 2) == 63.14
    assert round(by_kind["D"] / 1e6, 2) == 113.25
    assert round(by_kind["E"] / 1e6, 1) == 1218.2
    assert served.pattern == "*DWEWEWE*E" and served.experts_held == (0, 128)
    assert (served.n_heads, served.window_heads, served.n_kv_heads,
            served.head_dim, served.sliding_window) == (48, 72, 8, 128, 512)
    assert (served.rotary_dim, served.window_rotary_dim, served.top_k,
            served.routed_scaling_factor) == (64, 128, 10, 2.5)
    total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert total == bench_model.held_parameters(conf) == served.num_params()
    assert round(total / 1e9, 3) == 5.572
    cache = jax.eval_shape(lambda: hybrid_kv.init_hybrid_cache(served, 2, 64, 1))
    assert cache["win_k"].shape == (3, 1, 512, 8, 128)
    assert cache["k"].shape == (2, 2, 8, 64, 128)
    assert (int(np.prod(cache["win_k"].shape[2:])) * 2 * 2
            == bench_model.window_bytes_per_slot(conf) == 2_097_152)
