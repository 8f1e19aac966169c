"""Granite 4.0-H (models/granite_hybrid.py through llm/hybrid_kv.py)
against the plain reference (benchmarks/reference_granite_hybrid.py) at a
tiny size, float32, seeded weights, on the CPU: a whole period of ten
layers (`mmmmm*mmmm`, each a mixer and an expert FFN), prefill-then-decode
through `LLMEngine`'s cache of pages and per-slot state, the expert
share, and each of the family's multipliers.

Tolerances: everything here is float32 on both sides, so differences
are summation order only. 2e-4 absolute on logits of magnitude ~0.3 and
states of magnitude ~1 leaves an order of magnitude over what float32
reassociation gives across twenty sublayers (measured 2e-7 to 1e-5),
and is many times under what any mathematical difference produces: the
smallest of those below, a dropped `logits_scaling`, moves logits by
0.2 and more."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_granite_hybrid as reference
from benchmarks.models import granite_hybrid as bench_model
from ray_tpu.llm import hybrid_kv
from ray_tpu.models import moe
from ray_tpu.llm.engine import LLMEngine, SamplingParams
from ray_tpu.models.granite_hybrid import GraniteHybridConfig, init_params
from ray_tpu.models.moe import moe_ffn
from ray_tpu.ops.norms import rms_norm

TOL = 2e-4

# The published keys (the catalog's) at a tiny size: what a
# configuration file carries, so that `config` and `for_model` are under
# test too.
TINY = {
    "model_type": "granitemoehybrid", "hidden_size": 64, "vocab_size": 256,
    "num_hidden_layers": 10,
    "layer_types": ["mamba"] * 5 + ["attention"] + ["mamba"] * 4,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_n_groups": 1,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_chunk_size": 8,
    "mamba_expand": 2, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "num_local_experts": 8, "num_experts_per_tok": 3,
    "intermediate_size": 32, "shared_intermediate_size": 48,
    "embedding_multiplier": 12, "attention_multiplier": 0.1,
    "residual_multiplier": 0.22, "logits_scaling": 16,
    "attention_bias": False, "hidden_act": "silu",
    "normalization_function": "rmsnorm", "position_embedding_type": "nope",
    "rms_norm_eps": 1e-5, "rope_scaling": None, "rope_theta": 10000,
    "tie_word_embeddings": True, "max_position_embeddings": 256,
}
# Rows up to 8 take `moe_ffn`'s every-row form and more its sorted one,
# so that an engine's decode steps (4 slots) run the first and its
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


def _engine(params, **kw):
    kw = {"max_batch": 4, "max_seq": 192, "page_size": 16, **kw}
    return LLMEngine(CFG, params=params, **kw)


def test_the_config_is_the_published_layer_pattern():
    assert CFG.pattern == "MEMEMEMEME*EMEMEMEME" and CFG.layers == "MMMMM*MMMM"
    assert (CFG.count("M"), CFG.count("*"), CFG.count("E")) == (9, 1, 10)
    with pytest.raises(ValueError, match="a mixer"):
        GraniteHybridConfig(pattern="ME*M")
    with pytest.raises(ValueError, match="position_embedding_type"):
        bench_model.config({**TINY, "position_embedding_type": "rope"})


@pytest.mark.parametrize(
    "chunk, calls", [(None, 1), (32, 3)], ids=["whole", "three_chunks"]
)
def test_prefill_then_decode_equals_the_reference_pass(params, chunk, calls):
    """A 75-token prompt (a padded bucket; with `chunk` 32, three chunks,
    the last with 21 tokens of padding, the state and the convolution
    tail carried twice and the attention layer reading earlier chunks'
    pages), then 5 decode steps through the pages and the slot's state:
    the LOGITS of the last prompt position and of every decoded one
    against the reference's ONE full pass over prompt plus generated
    tokens, its routes forced to the system's (they are equal anyway in
    float32, which is asserted); and each Mamba layer's state as the
    slot holds it against the token-by-token recurrence's."""
    eng = _engine(params, prefill_chunk=chunk)
    seen = _tapped(eng)
    prompt = _prompt(0, 75)
    (generated,) = eng.generate([prompt], SamplingParams(max_tokens=6))
    tokens = prompt + generated
    prefills = [s for s in seen if s[0].startswith("prefill")]
    decodes = [s for s in seen if s[0] == "decode"]
    assert len(prefills) == calls and len(decodes) == 5
    routes = np.concatenate([s[2]["routes"] for s in prefills], axis=1)[:, :75]
    routes = np.concatenate(
        [routes] + [s[2]["routes"][:, :1] for s in decodes], axis=1
    )
    want, record = reference.forward_with_record(
        params, jnp.asarray(tokens[:-1], jnp.int32), routes=routes, **REF
    )
    assert routes.shape == (10, 80, 3)
    assert (np.sort(routes, -1) == np.sort(record["routes"], -1)).all()
    assert float(np.abs(want).max()) > 0.03  # logits of a size to compare
    np.testing.assert_allclose(prefills[-1][1][0, 0], want[74], atol=TOL, rtol=0)
    for i, step in enumerate(decodes):
        np.testing.assert_allclose(step[1][0], want[75 + i], atol=TOL, rtol=0)
    # The request is over; the state it left is still the slot's.
    assert record["states"].shape[0] == 9
    np.testing.assert_allclose(
        eng.cache["ssm"][:, 0], record["states"], atol=TOL, rtol=0
    )
    stats = eng.stats()
    assert stats["moe_pairs_routed"] == (75 + 5) * CFG.top_k * 10
    assert stats["moe_pairs_here"] == stats["moe_pairs_routed"]  # all held
    # The serving object's own counters: programs, the scan's live
    # tokens over the nine Mamba layers, the one attention layer's
    # causal pairs (75 x 76 / 2 whatever the chunking).
    assert stats["prefill_programs"] == calls
    assert stats["ssm_scan_tokens"] == 9 * 75
    assert stats["prefill_attn_pairs"] == 75 * 76 // 2


def test_a_chunked_prefill_with_a_share_held_equals_the_reference_pass(
    monkeypatch,
):
    """Experts 2-5 of the 8 held, as a chip of an expert-parallel pair
    holds them: a 75-token prompt in three 32-row chunks, whose expert
    sublayers take the sorted form under its row bound (blocks of 16
    rows here: a chunk's 96 pairs a layer are six), then 5 decode steps
    in the every-row form. Logits against the reference's one pass with
    the same share, and each program's ``counts``: the rows the grouped
    matmuls ran over lie between the pairs computed here and the pairs
    given, whole blocks of them."""
    monkeypatch.setattr(moe, "_PAIR_BLOCK", 16)
    tiny = {**TINY, "num_local_experts": 4, "first_expert_held": 2,
            "published": {"num_local_experts": 8}}
    cfg = bench_model.config(tiny, dtype=jnp.float32, dense_expert_rows=8)
    assert cfg.experts_held == (2, 4) and cfg.num_experts == 8
    held = init_params(jax.random.key(3), cfg)
    eng = LLMEngine(cfg, params=held, max_batch=4, max_seq=192, page_size=16,
                    prefill_chunk=32)
    seen = _tapped(eng)
    prompt = _prompt(0, 75)
    (generated,) = eng.generate([prompt], SamplingParams(max_tokens=6))
    tokens = prompt + generated
    prefills = [s for s in seen if s[0].startswith("prefill")]
    decodes = [s for s in seen if s[0] == "decode"]
    assert len(prefills) == 3 and len(decodes) == 5
    routes = np.concatenate([s[2]["routes"] for s in prefills], axis=1)[:, :75]
    routes = np.concatenate(
        [routes] + [s[2]["routes"][:, :1] for s in decodes], axis=1
    )
    want, record = reference.forward_with_record(
        held, jnp.asarray(tokens[:-1], jnp.int32), routes=routes,
        **reference.for_model(tiny),
    )
    assert (np.sort(routes, -1) == np.sort(record["routes"], -1)).all()
    assert float(np.abs(want).max()) > 0.03
    np.testing.assert_allclose(prefills[-1][1][0, 0], want[74], atol=TOL, rtol=0)
    for i, step in enumerate(decodes):
        np.testing.assert_allclose(step[1][0], want[75 + i], atol=TOL, rtol=0)

    computed = given = 0
    for live, (_, _, rec) in zip((32, 32, 11), prefills):
        here, _, rows, pairs = (int(v) for v in rec["counts"])
        in_share = (rec["routes"][:, :live] >= 2) & (rec["routes"][:, :live] < 6)
        assert here == in_share.sum()
        assert pairs == 32 * cfg.top_k * 10  # the padded rows' pairs too
        assert here <= rows < pairs and rows % 16 == 0
        assert rows - here < 16 * 10  # under a block a layer
        computed, given = computed + rows, given + pairs
    for _, _, rec in decodes:
        assert rec["counts"][2:].tolist() == [0, 0]  # the every-row form
    stats = eng.stats()
    assert 0 < stats["moe_pairs_here"] < stats["moe_pairs_routed"]
    assert stats["moe_rows_computed"] == computed
    assert stats["moe_sorted_rows_pct"] == 100.0 * computed / given


def test_kernel_and_gather_attention_paths_agree(params, monkeypatch):
    """Greedy streams are equal between the Pallas paths (the prefill
    kernel in the chunk programs and the paged kernel in the decode
    program, both at this family's score scale, interpreted here) and
    XLA's gather path."""
    prompts = [_prompt(4, 70), _prompt(5, 18)]
    sampling = SamplingParams(max_tokens=6)
    # The prefill kernel is for tables wider than a tiny engine has.
    monkeypatch.setattr(hybrid_kv, "_DENSE_ATTENTION_KEYS", 0)
    hybrid_kv._prefill_program.cache_clear()
    monkeypatch.setenv("RAY_TPU_PAGED_ATTN", "0")
    want = _engine(params, prefill_chunk=32).generate(prompts, sampling)
    monkeypatch.setenv("RAY_TPU_PAGED_ATTN", "1")
    eng = _engine(params, prefill_chunk=32)
    assert eng.paged_attn_kernel
    assert eng.generate(prompts, sampling) == want
    lowered = hybrid_kv.prefill_program(CFG, 8, 2, True).lower(
        params, np.zeros((1, 32), np.int32), eng.cache,
        np.zeros((8,), np.int32), np.int32(0), np.int32(0), np.int32(9),
    )
    assert "prefill_attention" in lowered.as_text()
    hybrid_kv._prefill_program.cache_clear()


def _last_logits(cfg, params, tokens):
    """The whole-prompt program's logits of the last position."""
    cache = hybrid_kv.init_hybrid_cache(cfg, 4, 16, 1)
    program = hybrid_kv.prefill_program(cfg, 2, 2, False)
    padded = np.zeros((1, 32), np.int32)
    padded[0, : len(tokens)] = tokens
    logits, _, _ = program(
        params, padded, cache, np.asarray([1, 2], np.int32), np.int32(0),
        np.int32(0), np.int32(len(tokens)),
    )
    return np.asarray(logits[0, 0])


@pytest.mark.parametrize(
    "dropped",
    [None, "embedding_multiplier", "residual_multiplier", "attention_scale",
     "logits_scaling", "tied_head", "softmax_over_the_chosen"],
)
def test_each_multiplier_the_tied_head_and_the_gates_are_held(params, dropped):
    """The program as published is within the limit of the reference;
    with any one of the four multipliers at 1 (the attention scale at
    head_dim**-0.5), a head that is not the embedding, or gates that are
    the softmax over all experts and not over the chosen three, it is
    not: each is held by the comparison."""
    tokens = _prompt(9, 27)
    want = np.asarray(reference.forward(
        params, jnp.asarray(tokens, jnp.int32), **REF
    ))[-1]
    cfg, tree = CFG, params
    if dropped == "tied_head":
        cfg = dataclasses.replace(CFG, tie_word_embeddings=False)
        head = jax.random.normal(jax.random.key(1), (64, 256)) / 8.0
        tree = {**params, "lm_head": head}
    elif dropped == "softmax_over_the_chosen":
        cfg = dataclasses.replace(CFG, norm_topk_prob=False)
    elif dropped == "attention_scale":
        cfg = dataclasses.replace(CFG, attention_scale=None)
    elif dropped is not None:
        cfg = dataclasses.replace(CFG, **{dropped: 1.0})
    worst = float(np.abs(_last_logits(cfg, tree, tokens) - want).max())
    assert (worst <= TOL) == (dropped is None), worst


@pytest.fixture(params=["sorted_pairs", "every_row"])
def path_cfg(request):
    """`moe_ffn`'s two ways to apply the experts, each forced in turn."""
    rows = 0 if request.param == "sorted_pairs" else 10**6
    return dataclasses.replace(CFG, dense_expert_rows=rows)


def test_the_shares_add_up_to_the_uncut_layer(params, path_cfg):
    """Expert parallelism over two chips: each share holds 4 of the 8
    experts, routes over all 8 (the gates a softmax over the chosen
    three, wherever they live) and computes its own experts' part. The
    two routed parts plus the shared expert ONCE are the uncut layer
    (model-configs guide, section 4); each share also equals the
    reference given the same share."""
    p = params["blocks"][1]
    x = jax.random.normal(jax.random.key(6), (24, CFG.d_model))
    h = rms_norm(x, p["norm"])[None]
    whole, aux = moe_ffn(h, p, path_cfg)
    no_shared = {k: v for k, v in p.items() if not k.startswith("shared")}
    shared = whole - moe_ffn(h, no_shared, path_cfg)[0]
    parts, pairs = [], 0
    for first in (0, 4):
        cfg = dataclasses.replace(path_cfg, experts_held=(first, 4))
        mine = {**p, **{k: p[k][first: first + 4]
                        for k in ("w_gate", "w_up", "w_down")}}
        out, part_aux = moe_ffn(h, mine, cfg)
        want, _ = reference.expert_sublayer(
            mine, x, **{**REF, "first_expert_held": first}
        )
        np.testing.assert_allclose(
            x + CFG.residual_multiplier * out[0], want, atol=TOL, rtol=0
        )
        assert (part_aux["routes"] == aux["routes"]).all()
        parts.append(out - shared)
        pairs += int(part_aux["expert_load"].sum())
    np.testing.assert_allclose(
        parts[0] + parts[1] + shared, whole, atol=TOL, rtol=0
    )
    assert pairs == 24 * CFG.top_k  # every pair fell to exactly one share


def test_config_counts_the_published_model():
    """The program's config at the published sizes holds what the issue
    counted: a Mamba mixer 102.3M, attention 41.9M, an expert FFN 698.65M
    whole and 358.9M with 36 of 72 held; and the benchmark's own count
    of the configuration it runs agrees with the tree's."""
    import json
    import os

    cfg = GraniteHybridConfig()
    assert cfg.pattern.count("M") == 36 and cfg.pattern.count("*") == 4
    assert cfg.d_inner == 8192 and cfg.conv_dim == 8448

    def sizes(c):
        shapes = jax.eval_shape(lambda k: init_params(k, c), jax.random.key(0))
        return shapes, dict(zip(c.pattern, (
            sum(int(np.prod(x.shape)) for x in jax.tree.leaves(b))
            for b in shapes["blocks"]
        )))

    _, by_kind = sizes(cfg)
    assert round(by_kind["M"] / 1e6, 1) == 102.3
    assert round(by_kind["*"] / 1e6, 1) == 41.9
    assert round(by_kind["E"] / 1e6, 2) == 698.65
    here = os.path.join(os.path.dirname(__file__), "..", "benchmarks")
    with open(os.path.join(here, "configs", "granite4hsmall-serve1.json")) as f:
        conf = json.load(f)
    served = bench_model.config(conf, max_seq=conf["engine"]["max_seq"])
    shapes, by_kind = sizes(served)
    assert round(by_kind["E"] / 1e6, 1) == 358.9
    assert served.layers == "MMMMM*MMMM" and served.experts_held == (0, 36)
    total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert total == bench_model.held_parameters(conf)
    assert round(total / 1e9, 3) == 4.757
