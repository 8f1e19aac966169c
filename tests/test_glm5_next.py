"""GLM-5.3-Flash's language model (models/glm5_next.py through
llm/hybrid_kv.py) against the plain reference
(benchmarks/reference_glm5_next.py) at a tiny size, float32, seeded
weights, on the CPU: the dense layer and two whole periods (`KD`, then
`LE KE KE KE` and `LE KE`), four residual streams, blocks of 2 positions
of which a query picks 4, pages of 8, chunks of 16:
prefill-then-decode through `LLMEngine`'s latent and index pools, the
slot's matrix states and tails; the chunked rule against its recurrence
with every gate at its lower bound; the kernels' new operands
interpreted; the expert share; each thing the family adds dropped in
turn.

Tolerances: everything here is float32 on both sides, so differences
are summation order only. 2e-4 absolute on logits of magnitude ~3 leaves
several times what float32 reassociation gives across fourteen
sublayers (measured 4e-6 to 1e-5), and is many times under what any
mathematical difference produces: the smallest of those below moves
logits by 0.002 and more."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_glm5_next as reference
from benchmarks.models import glm5_next as bench_model
from ray_tpu.llm import hybrid_kv
from ray_tpu.llm.engine import LLMEngine, SamplingParams
from ray_tpu.models import glm5_next, mhc, moe
from ray_tpu.models.glm5_next import Glm5NextConfig, init_params
from ray_tpu.models.moe import moe_ffn
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.pallas import expert_rows, grouped_rows, state_step

TOL = 2e-4
PAGE, CHUNK, POOL, TOPK = 8, 16, 2, 8

# The published keys (the catalog's) at a tiny size: what a
# configuration file carries, so that `config` and `for_model` are under
# test too.
_KINDS = ["linear_attention", "deepseek_sparse_attention"] + [
    "linear_attention"] * 3 + ["deepseek_sparse_attention", "linear_attention"]
TINY = {
    "model_type": "glm5_next_text", "hidden_size": 64, "vocab_size": 256,
    "intermediate_size": 96, "num_hidden_layers": 7,
    "first_k_dense_replace": 1, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 0, "max_position_embeddings": 256,
    "attention_bias": False, "rms_norm_eps": 1e-5, "hidden_act": "silu",
    "hc_eps": 1e-6, "hc_mult": 4, "hc_sinkhorn_iters": 20, "mhc": True,
    "index_head_dim": 16, "index_kpool": POOL,
    "index_kpool_always_select_tail": True, "index_kpool_compress": True,
    "index_n_heads": 2, "index_topk": TOPK,
    "index_share_for_mtp_iteration": True, "indexer_rope_interleave": True,
    "indexer_types": ["full"] * 7, "kv_lora_rank": 16, "layer_types": _KINDS,
    "linear_attn_config": {
        "num_heads": 4, "gate_lower_bound": -5, "head_dim": 16,
        "short_conv_kernel_size": 4,
    },
    "mla_use_nope": True, "mlp_layer_types": ["dense"] + ["sparse"] * 6,
    "moe_intermediate_size": 32, "n_group": 1, "n_routed_experts": 8,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_experts_per_tok": 3,
    "num_nextn_predict_layers": 0, "q_lora_rank": 32, "qk_head_dim": 16,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 0,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "swiglu_limit": 10, "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 16,
    "assumed_values": {
        "kda_gate_rank": 8, "index_rotary_dim": 8, "index_rope_theta": 10000,
    },
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
    eng = LLMEngine(cfg, params=params, **kw)
    eng.pages_of_last = []
    return eng


def _split(seen):
    return ([s for s in seen if s[0].startswith("prefill")],
            [s for s in seen if s[0] == "decode"])


def _per_token(prefills, decodes, key, n, slot=0):
    first = np.concatenate([s[2][key] for s in prefills], axis=1)[:, :n]
    return np.concatenate(
        [first] + [s[2][key][:, slot: slot + 1] for s in decodes], axis=1
    )


def _run(eng, prompt, new):
    """One request to its end: (tokens the model saw, prefills, decodes).
    Its pages (which outlive it: a page is not cleared when it is freed)
    are left in ``eng.pages_of_last``."""
    seen = _tapped(eng)
    eng.add_request(prompt, SamplingParams(max_tokens=new))
    req = eng._queue[-1]
    done = None
    while done is None:
        for fin in eng.step():
            done = fin
        eng.pages_of_last = list(req.pages or eng.pages_of_last)
    return prompt + done["tokens"][:-1], *_split(seen)


def test_the_config_is_the_published_layer_pattern():
    """Layer l is sparse latent attention where (l + 1) % 4 == 0, the
    first three FFNs are dense; the tiny config is published layers 2-8."""
    full = Glm5NextConfig()
    assert len(full.pattern) == 90
    assert full.pattern[:8] == "KDKDKDLE" and full.pattern[8:16] == "KEKEKELE"
    assert full.count("L") == 11 and full.count("K") == 34
    assert full.count("D") == 3 and full.count("E") == 42
    assert CFG.pattern == "KDLEKEKEKELEKE"
    assert (CFG.index_blocks, CFG.hc_mult, CFG.swiglu_limit) == (4, 4, 10.0)
    with pytest.raises(ValueError, match="kda_chunk"):
        dataclasses.replace(CFG, kda_chunk=48)


# ---------------------------------------------- prefill, decode: the reference
@pytest.mark.parametrize(
    "chunk,calls", [(None, 1), (CHUNK, 5)], ids=["whole", "five_chunks"]
)
def test_prefill_then_decode_equals_the_reference_pass(params, chunk, calls):
    """A 75-token prompt (nine times the 8 keys a query may pick; with a
    chunk of 16 its last chunk holds 11 real tokens and 5 of padding,
    and the prompt ends inside a block) and six decode steps through the
    engine's own programs against the reference's one pass: logits at
    the last prompt position and at each step, every token's routes and
    selected blocks, each KDA layer's state and the request's cells and
    pooled keys as the cache holds them after the last step (blocks that
    decode steps completed among them)."""
    n, new = 75, 7
    eng = _engine(params, prefill_chunk=chunk)
    tokens, prefills, decodes = _run(eng, _prompt(1, n), new)
    assert len(prefills) == calls and len(decodes) == new - 1
    want, record = reference.forward_with_record(
        params, jnp.asarray(tokens, jnp.int32), **REF
    )
    got = np.stack(
        [prefills[-1][1][0, 0]] + [s[1][0] for s in decodes]
    )
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want[n - 1:], atol=TOL, rtol=0)
    held = n + new - 1
    for key in ("routes", "selected"):
        mine = np.sort(_per_token(prefills, decodes, key, n), -1)
        assert (mine == np.sort(np.asarray(record[key]), -1)).all(), key
    assert float(np.asarray(record["select_slack"]).max()) == 0.0
    # A query late in the prompt had 37 candidate blocks to pick 4 of.
    assert (np.asarray(record["selected"])[:, -1] >= 0).all()
    np.testing.assert_allclose(
        eng.cache["kda"][:, 0], record["states"], atol=TOL, rtol=0
    )
    cells, pooled = bench_model.held_cells(
        eng.cache, eng.pages_of_last, held, POOL
    )
    np.testing.assert_allclose(cells, record["cells"], atol=TOL, rtol=0)
    np.testing.assert_allclose(pooled, record["pooled"], atol=TOL, rtol=0)


def test_the_reference_in_token_blocks_is_the_reference(params):
    """The pass the chip's check makes of its longest prompt
    (`bench_model.LONG_PASS`): blocks of tokens with the rule's state and
    the convolution's tail carried, a sparse layer's queries a block at a
    time against every position's cell and key, a few heads at a time;
    here 75 tokens in blocks of 32 (the last of 11), 8 queries and 2
    heads at a time, with forced routes and selections as the check
    gives them: the same logits and the same record."""
    tokens = jnp.asarray(_prompt(7, 75), jnp.int32)
    want, record = reference.forward_with_record(params, tokens, **REF)
    forced = {"routes": record["routes"], "selected": record["selected"]}
    for kw in ({}, forced):
        got, blocked = reference.forward_with_record(
            params, tokens, rows=[40, 74], token_block=32, query_block=8,
            head_block=2, **kw, **REF,
        )
        np.testing.assert_allclose(got, np.asarray(want)[[40, 74]], atol=1e-5, rtol=0)
        assert sorted(blocked) == sorted(record)
        for key, value in record.items():
            np.testing.assert_allclose(
                blocked[key], value, atol=1e-5, rtol=0, err_msg=key
            )


def test_two_slots_decode_together_each_as_if_alone(params):
    """Two requests live at once, one whose prompt ends inside a block
    of the indexer's pool (21 tokens) and one whose prompt ends with a
    block (38), so that the decode steps they share close a block in one
    slot while the other's is open: every slot's open block lives in its
    own tail. Each request's tokens are the reference's greedy choice on
    its own tokens, and its KDA states, cells and pooled keys (the
    blocks decode steps closed among them) are the reference's of it
    alone."""
    eng = _engine(params, prefill_chunk=CHUNK)
    lengths, new = {0: 21, 1: 38}, 7
    reqs, slots, pages, done = {}, {}, {}, {}
    for seed, n in lengths.items():
        rid = eng.add_request(_prompt(10 + seed, n), SamplingParams(max_tokens=new))
        reqs[rid] = (eng._queue[-1], n)
    together = 0
    while len(done) < 2:
        for fin in eng.step():
            done[fin["request_id"]] = fin["tokens"]
        live = 0
        for rid, (req, _) in reqs.items():
            if rid not in done and eng.slot_of(rid) is not None:
                slots[rid], live = eng.slot_of(rid), live + 1
                pages[rid] = list(req.pages or pages.get(rid, []))
        together += live == 2
    assert together >= new - 2 and sorted(slots.values()) == [0, 1]
    for rid, (req, n) in reqs.items():
        tokens = list(req.prompt) + done[rid][:-1]
        want, record = reference.forward_with_record(
            params, jnp.asarray(tokens, jnp.int32), **REF
        )
        assert done[rid] == np.asarray(want)[n - 1:].argmax(-1).tolist()
        np.testing.assert_allclose(
            eng.cache["kda"][:, slots[rid]], record["states"], atol=TOL, rtol=0
        )
        cells, pooled = bench_model.held_cells(
            eng.cache, pages[rid], len(tokens), POOL
        )
        np.testing.assert_allclose(cells, record["cells"], atol=TOL, rtol=0)
        np.testing.assert_allclose(pooled, record["pooled"], atol=TOL, rtol=0)


def test_a_slot_reused_after_a_longer_request_sees_none_of_its_state(params):
    """Slot 0 serves a 90-token request and then a 21-token one: the
    second starts from zero state and an empty tail, whatever the slot
    holds, and its logits are the reference's of its own tokens."""
    eng = _engine(params, prefill_chunk=CHUNK, max_batch=1)
    _run(eng, _prompt(2, 90), 3)
    tokens, prefills, decodes = _run(eng, _prompt(4, 21), 4)
    want = reference.forward(params, jnp.asarray(tokens, jnp.int32), **REF)
    got = np.stack([prefills[-1][1][0, 0]] + [s[1][0] for s in decodes])
    np.testing.assert_allclose(got, want[20:], atol=TOL, rtol=0)


# ------------------------------------------------------------ the KDA rule
def _recurrence(q, k, v, beta, g, state):
    outs = []
    for t in range(q.shape[0]):
        state = state * np.exp(g[t])[:, :, None]
        read = np.einsum("hkv,hk->hv", state, k[t])
        state = state + k[t][:, :, None] * (
            beta[t][:, None] * (v[t] - read)
        )[:, None, :]
        outs.append(np.einsum("hkv,hk->hv", state, q[t]))
    return np.stack(outs), state


@pytest.mark.parametrize("gate", ["random", "at_the_lower_bound"])
def test_the_chunked_rule_equals_its_recurrence(gate):
    """`_kda_rule` at the published chunk of 64 in sub-chunks of 16
    against the rule a token a step, float64: with random gates, and
    with EVERY gate at its lower bound of -5 for a whole chunk and more,
    where the running sum reaches -320 a chunk and ``exp`` of its
    negative is past float32: the sub-chunks' reference points keep every
    exponent within 40, the result is finite and the same."""
    t, h, d = 150, 2, 8
    keys = jax.random.split(jax.random.key(5), 5)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(np.asarray(jax.random.normal(keys[0], (t, h, d)))) * d**-0.5
    k = unit(np.asarray(jax.random.normal(keys[1], (t, h, d))))
    v = np.asarray(jax.random.normal(keys[2], (t, h, d)))
    beta = np.asarray(jax.nn.sigmoid(jax.random.normal(keys[3], (t, h))))
    if gate == "random":
        g = -5.0 * np.asarray(jax.random.uniform(keys[4], (t, h, d)))
    else:
        g = np.full((t, h, d), -5.0, np.float32)
    state0 = np.asarray(jax.random.normal(keys[4], (h, d, d)))
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    o, end = glm5_next._kda_rule(
        f32(q), f32(k), f32(v), f32(beta), f32(g), f32(state0), 64, 16
    )
    want_o, want_end = _recurrence(
        *(np.asarray(a, np.float64) for a in (q, k, v, beta, g, state0))
    )
    assert np.isfinite(o).all() and np.isfinite(end).all()
    np.testing.assert_allclose(o, want_o, atol=2e-5, rtol=0)
    np.testing.assert_allclose(end, want_end, atol=2e-5, rtol=0)
    assert np.abs(want_o).max() > 0.05


def test_the_state_kernel_steps_a_decay_a_channel(params):
    """`kda_state_step` interpreted against `kda_step`'s arithmetic: the
    live slots' state decays a key channel and is corrected in place,
    the others' is not touched and their output is zero."""
    b, h, d = 3, 8, 8
    keys = jax.random.split(jax.random.key(9), 6)
    stack = jax.random.normal(keys[0], (2, b, h, d, d))
    decay = jax.random.uniform(keys[1], (b, h, d), minval=0.01)
    beta = jax.random.uniform(keys[2], (b, h))
    q, k, v = (jax.random.normal(key, (b, h, d)) for key in keys[3:])
    active = jnp.asarray([True, False, True])
    new, o = state_step.kda_state_step(
        stack, 1, *state_step.live_order(active), decay, beta, q, k, v,
        interpret=True,
    )
    s = stack[1] * decay[..., None]
    k_col = k[..., None]
    s = s + k_col * (beta[..., None] * (v - (s * k_col).sum(-2)))[..., None, :]
    want_o = (s * q[..., None]).sum(-2)
    live = np.asarray(active)
    np.testing.assert_allclose(new[1][live], s[live], atol=1e-5, rtol=0)
    np.testing.assert_allclose(o[live], want_o[live], atol=1e-5, rtol=0)
    assert (np.asarray(new[1][1]) == np.asarray(stack[1][1])).all()
    assert (np.asarray(new[0]) == np.asarray(stack[0])).all()
    assert not np.asarray(o[1]).any()


# ------------------------------------------------------------- the clamp
def _hot_experts(key, held=3, d=16, f=128, n=12):
    """Rows and stacks whose gated products pass the limit of 2."""
    keys = jax.random.split(key, 4)
    x = jax.random.normal(keys[0], (n, d))
    w_gate, w_up = (
        jax.random.normal(key, (held, d, f)) for key in keys[1:3]
    )
    w_down = jax.random.normal(keys[3], (held, f, d)) * f**-0.5
    return x, w_gate, w_up, w_down


def test_the_every_row_kernel_clamps(params):
    x, w_gate, w_up, w_down = _hot_experts(jax.random.key(11))
    weight = jax.random.uniform(jax.random.key(12), (x.shape[0], 3))
    ids, count = jnp.arange(3, dtype=jnp.int32), jnp.int32(3)

    def want(limit):
        act = moe.clamped_swiglu(
            x, w_gate, w_up, limit,
            lambda a, w: jnp.einsum("nd,edf->enf", a, w),
        )
        return jnp.einsum(
            "end,ne->nd", jnp.einsum("enf,efd->end", act, w_down), weight
        )

    got = expert_rows.experts_on_rows(
        x, w_gate, w_up, w_down, weight, ids, count, interpret=True, limit=2.0
    )
    np.testing.assert_allclose(got, want(2.0), atol=1e-4, rtol=1e-5)
    assert float(jnp.abs(want(2.0) - want(None)).max()) > 1.0


def test_the_grouped_kernel_clamps(params):
    x, w_gate, w_up, _ = _hot_experts(jax.random.key(13), n=24)
    sizes = jnp.asarray([10, 0, 14], jnp.int32)
    group = np.repeat(np.arange(3), np.asarray(sizes))
    got = grouped_rows.grouped_rows(
        x, [w_gate, w_up], sizes, "swiglu", 8, interpret=True, limit=2.0
    )
    want = moe.clamped_swiglu(
        x, w_gate[group], w_up[group], 2.0,
        lambda a, w: jnp.einsum("nd,ndf->nf", a, w),
    )
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-5)
    # silu(2) * 2 at the most, where the products reach 10 and more.
    assert float(jnp.abs(got).max()) <= float(jax.nn.silu(2.0)) * 2.0 + 1e-5
    assert float(jnp.abs(jnp.einsum("nd,ndf->nf", x, w_up[group])).max()) > 8


# ---------------------------------------------- each part dropped in turn
@pytest.fixture(scope="module")
def hot_params(params):
    """The tree with every FFN's first two matrices four times as large,
    so that the clamp at 10 bites."""
    def hot(block):
        return {
            name: leaf * 4.0 if name in (
                "w_gate", "w_up", "shared_gate", "shared_up") else leaf
            for name, leaf in block.items()
        }

    return {**params, "blocks": tuple(hot(b) for b in params["blocks"])}


@pytest.fixture(scope="module")
def served_logits(hot_params):
    eng = _engine(hot_params, prefill_chunk=CHUNK)
    tokens, prefills, decodes = _run(eng, _prompt(7, 41), 3)
    return tokens, np.stack(
        [prefills[-1][1][0, 0]] + [s[1][0] for s in decodes]
    )


@pytest.mark.parametrize("dropped", [
    None, "one_decay_a_head", "unbounded_gate", "attend_all", "recent_keys",
    "no_pooling", "no_tail", "static_h", "no_sinkhorn", "one_stream",
    "no_clamp", "no_routed_scaling",
])
def test_each_part_the_family_adds_is_held(hot_params, served_logits, dropped):
    """The engine's logits equal the reference's, and differ by ten times
    the tolerance and more from the reference with any one part of the
    architecture computed otherwise."""
    tokens, got = served_logits
    want = reference.forward(
        hot_params, jnp.asarray(tokens, jnp.int32), **REF, lower=dropped
    )[40:]
    worst = float(np.abs(got - np.asarray(want)).max())
    if dropped is None:
        assert worst < TOL
    else:
        assert worst > 10 * TOL, (dropped, worst)


# ----------------------------------------------------- the other families
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


MARKS = ("kda:scan", "kda:step", "dsa:index", "dsa:select", "dsa:attend",
         "dsa:index_write", "mhc:mix", "mhc:spread", "ffn:dense")


@pytest.mark.parametrize(
    "family", ["nemotron_h", "granite_hybrid", "qwen3_next", "laguna"]
)
def test_the_other_families_programs_hold_none_of_it(family):
    """The other pattern families' programs and caches have no latent or
    index pool, no third recurrence, no scope of the residual mixing and
    no clamp; this family's have each. (That their lowered text is the
    parent commit's, source lines apart, was compared once when this
    came and again when the rule became a kernel: CHANGES.md, PRs 59
    and 60.)"""
    from ray_tpu.models import granite_hybrid, laguna, nemotron_h, qwen3_next

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
        "laguna": (laguna.LAGUNA_PRESETS["laguna_tiny"], laguna.init_params),
    }[family]
    mine = {"latent", "index", "index_tail", "kda", "kda_conv"}
    leaves, text = _lowered_text(cfg, init)
    assert not mine & set(leaves)
    assert not [m for m in MARKS if m in text and m != "ffn:dense"]
    assert cfg.hc_mult == 0 and cfg.swiglu_limit is None
    leaves, text = _lowered_text(CFG, init_params)
    assert mine <= set(leaves)
    assert [m for m in MARKS if m in text] == list(MARKS)


# ------------------------------------------------------------- the cache
def test_the_cache_is_pools_on_one_table_and_state_a_slot(params):
    """One sparse layer's cells and pooled keys grow with the pages; the
    KDA layers' state and the tails do not; `cache_bytes` counts the
    pools as pools. At the published widths a token costs 1,024 + 64
    bytes a sparse layer and a slot 4.19 MB a KDA layer."""
    small = hybrid_kv.init_hybrid_cache(CFG, 9, PAGE, 2)
    large = hybrid_kv.init_hybrid_cache(CFG, 17, PAGE, 2)
    assert small["latent"].shape == (2, 9, PAGE, 16)
    assert small["index"].shape == (2, 9, PAGE // POOL, 16)
    assert small["index_tail"].shape == (2, 2, POOL - 1, 16)
    assert small["kda"].shape == (5, 2, 4, 16, 16)
    assert small["kda_conv"].shape == (5, 2, 3, 3 * 4 * 16)
    assert small["k"].shape[0] == 0  # no layer attends by `paged_kv`'s pages
    pool_s, state_s = CFG.serving().cache_bytes(small)
    pool_l, state_l = CFG.serving().cache_bytes(large)
    assert state_s == state_l and pool_l * 9 == pool_s * 17
    here = os.path.join(os.path.dirname(__file__), "..", "benchmarks")
    with open(os.path.join(here, "configs", "glm53flash-serve1.json")) as f:
        conf = json.load(f)
    eng = conf["engine"]
    served = bench_model.config(conf, max_seq=eng["max_seq"])
    cache = jax.eval_shape(lambda: hybrid_kv.init_hybrid_cache(
        served, eng["num_pages"] + 1, eng["page_size"], eng["max_batch"]
    ))
    nbytes = lambda x: int(np.prod(x.shape)) * x.dtype.itemsize  # noqa: E731
    assert eng["num_pages"] * eng["page_size"] >= eng["max_batch"] * eng["max_seq"]
    assert nbytes(cache["latent"]) == 16449 * 64 * 512 * 2
    assert nbytes(cache["index"]) == 16449 * 16 * 128 * 2
    assert cache["kda"].shape == (4, 16, 64, 128, 128)
    assert nbytes(cache["kda"]) // 64 == bench_model.kda_state_bytes_per_slot(conf)
    assert cache["kda_conv"].shape == (4, 16, 3, 24576)


def test_router_h_states_and_scores_are_float32():
    """With bfloat16 weights and streams: the residual mixing's three H,
    the indexer's scores, the state and the tails are float32, and the
    router's logits are (`moe_ffn`'s casts)."""
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    tree = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))
    for block in tree["blocks"]:
        assert {leaf.dtype for leaf in jax.tree.leaves(block["hc"])} == {
            jnp.dtype("float32")}
    assert tree["blocks"][3]["router"].dtype == jnp.float32
    cache = jax.eval_shape(lambda: hybrid_kv.init_hybrid_cache(cfg, 4, 8, 2))
    assert cache["kda"].dtype == cache["index_tail"].dtype == jnp.float32
    assert cache["latent"].dtype == cache["index"].dtype == jnp.bfloat16
    x = jax.ShapeDtypeStruct((5, 4, 64), jnp.bfloat16)
    h, (res, post) = jax.eval_shape(
        lambda x, p: mhc.mhc_mix(x, p, cfg), x, tree["blocks"][0]["hc"]
    )
    assert (h.dtype, res.dtype, post.dtype) == (
        jnp.bfloat16, jnp.float32, jnp.float32)
    scores = jax.eval_shape(
        lambda q, w, k: glm5_next._index_scores(q, w, k, cfg),
        jax.ShapeDtypeStruct((5, 2, 16), jnp.float32),
        jax.ShapeDtypeStruct((5, 2), jnp.float32),
        jax.ShapeDtypeStruct((7, 16), jnp.bfloat16),
    )
    assert scores.dtype == jnp.float32 and scores.shape == (5, 7)


def test_sinkhorn_leaves_a_doubly_stochastic_matrix():
    m = jnp.exp(jax.random.normal(jax.random.key(2), (6, 4, 4)) * 2.0)
    out = np.asarray(mhc.sinkhorn(m, 20, 1e-6))
    np.testing.assert_allclose(out.sum(-1), 1.0, atol=1e-3)
    np.testing.assert_allclose(out.sum(-2), 1.0, atol=1e-5)


# ------------------------------------------------------------ the share
@pytest.fixture(params=["sorted_pairs", "every_row"])
def path_cfg(request):
    """`moe_ffn`'s two ways to apply the experts, each forced in turn."""
    rows = 0 if request.param == "sorted_pairs" else 10**6
    return dataclasses.replace(CFG, dense_expert_rows=rows)


def test_the_shares_add_up_to_the_uncut_layer(params, path_cfg):
    """Expert parallelism as the deployment states it, at a tiny size:
    four chips each hold 2 of 8 experts (the published eight hold 36 of
    288), route over all 8 (sigmoid scores, the three largest
    renormalised and times 2.5, wherever they live) and compute their own
    experts' part behind the clamp. The four routed parts plus the shared
    expert ONCE are the uncut reference's layer; each share also equals
    the reference given the same share."""
    p = params["blocks"][3]
    x = jnp.broadcast_to(
        jax.random.normal(jax.random.key(6), (24, 1, CFG.d_model)),
        (24, CFG.hc_mult, CFG.d_model),
    )
    one = {**REF, "lower": "one_stream"}  # the layer alone: x + F(x)
    normed = rms_norm(x[:, 0], p["norm"], CFG.norm_eps)
    with jax.default_matmul_precision("highest"):
        shared = reference._gated(
            normed, p["shared_gate"], p["shared_up"], p["shared_down"],
            10.0, None,
        )
    uncut, record = reference.expert_sublayer(p, x, **one)
    parts, pairs = [], 0
    for first in range(0, 8, 2):
        cfg = dataclasses.replace(path_cfg, experts_held=(first, 2))
        mine = {**p, **{k: p[k][first: first + 2]
                        for k in ("w_gate", "w_up", "w_down")}}
        out, aux = moe_ffn(normed[None], mine, cfg)
        want, _ = reference.expert_sublayer(
            mine, x, **{**one, "first_expert_held": first}
        )
        np.testing.assert_allclose(x[:, 0] + out[0], want[:, 0], atol=TOL, rtol=0)
        assert (np.sort(aux["routes"], -1)
                == np.sort(record["routes"], -1)).all()
        parts.append(out[0] - shared)
        pairs += int(aux["expert_load"].sum())
    np.testing.assert_allclose(
        x[:, 0] + sum(parts) + shared, uncut[:, 0], atol=TOL, rtol=0
    )
    assert float(np.abs(shared).max()) > 0.01  # a shared part to count once
    assert pairs == 24 * 3  # every pair fell to exactly one share


def test_config_counts_the_published_model():
    """The program's config at the published sizes holds what the issue
    counted: 313.3B parameters uncut (the multi-token-prediction module
    apart), a KDA mixer 137.7M, a sparse latent mixer 124.4M, the dense
    FFN 151.0M, an expert FFN with 36 of 288 held 932M (each beside its
    0.39M of residual mixing), 4.718B held in all; and the benchmark's own
    count of the configuration it runs agrees with the tree's."""
    assert round(Glm5NextConfig().num_params() / 1e9, 1) == 313.3
    here = os.path.join(os.path.dirname(__file__), "..", "benchmarks")
    with open(os.path.join(here, "configs", "glm53flash-serve1.json")) as f:
        conf = json.load(f)
    served = bench_model.config(conf, max_seq=conf["engine"]["max_seq"])
    shapes = jax.eval_shape(lambda k: init_params(k, served), jax.random.key(0))
    by_kind = dict(zip(served.pattern, (
        sum(int(np.prod(x.shape)) for x in jax.tree.leaves(b))
        for b in shapes["blocks"]
    )))
    hc = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(
        shapes["blocks"][0]["hc"]))
    assert hc == 4 * 4096 * 24 + 3 + 4 + 4 + 16
    assert round((by_kind["K"] - hc) / 1e6, 1) == 137.7
    assert round((by_kind["L"] - hc) / 1e6, 1) == 124.4
    assert round((by_kind["D"] - hc) / 1e6, 1) == 151.0
    assert round((by_kind["E"] - hc) / 1e6) == 932
    assert served.pattern == "KDLEKEKEKE" and served.experts_held == (0, 36)
    assert (served.kda_heads, served.kda_head_dim, served.n_heads,
            served.index_blocks, served.top_k) == (64, 128, 64, 512, 8)
    total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert total == bench_model.held_parameters(conf) == served.num_params()
    assert round(total / 1e9, 2) == 4.72


def test_refusals_say_why(params):
    with pytest.raises(ValueError, match="recurrent blocks"):
        _engine(params, speculate=2)
    with pytest.raises(NotImplementedError, match="a mesh"):
        CFG.serving().logical_axes()
