"""Qwen3-Next (models/qwen3_next.py through llm/hybrid_kv.py) against the
plain reference (benchmarks/reference_qwen3_next.py) at a tiny size,
float32, seeded weights, on the CPU: a whole period of four layers
(`GGG*`, each a mixer and an expert FFN), prefill-then-decode through
`LLMEngine`'s cache of pages and per-slot matrix state, the chunked
delta rule against the token-by-token recurrence, the expert share, and
each thing the attention block and the shared expert add.

Tolerances: everything here is float32 on both sides, so differences
are summation order only. 2e-4 absolute on logits of magnitude ~1 and
states of magnitude ~0.5 leaves two orders of magnitude over what
float32 reassociation gives across eight sublayers (measured 1e-7 to
2e-6), and is many times under what any mathematical difference
produces: the smallest of those below, a dropped shared-expert gate,
moves logits by 0.01 and more."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_qwen3_next as reference
from benchmarks.models import qwen3_next as bench_model
from ray_tpu.llm import hybrid_kv
from ray_tpu.llm.engine import LLMEngine, SamplingParams
from ray_tpu.models import moe, qwen3_next
from ray_tpu.models.moe import moe_ffn
from ray_tpu.models.qwen3_next import (
    Qwen3NextConfig,
    gdn_chunked,
    gdn_step,
    init_params,
)
from ray_tpu.ops.norms import rms_norm

TOL = 2e-4

# The published keys (the catalog's) at a tiny size: what a
# configuration file carries, so that `config` and `for_model` are under
# test too.
TINY = {
    "model_type": "qwen3_next", "hidden_size": 64, "vocab_size": 256,
    "num_hidden_layers": 4, "full_attention_interval": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "partial_rotary_factor": 0.25, "rope_theta": 10000000,
    "linear_num_key_heads": 2, "linear_key_head_dim": 8,
    "linear_num_value_heads": 4, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "num_experts": 8, "num_experts_per_tok": 3,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 48,
    "intermediate_size": 160, "norm_topk_prob": True, "hidden_act": "silu",
    "decoder_sparse_step": 1, "mlp_only_layers": [], "rms_norm_eps": 1e-6,
    "rope_scaling": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "max_position_embeddings": 256,
}
# Rows up to 8 take `moe_ffn`'s every-row form and more its sorted one,
# so that an engine's decode steps (4 slots) run the first and its
# prefills (16 rows and more) the second, as the two meet in a replica.
CFG = bench_model.config(
    TINY, dtype=jnp.float32, dense_expert_rows=8, gdn_chunk=8
)
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
    kw = {"max_batch": 4, "max_seq": 192, "page_size": 16, **kw}
    return LLMEngine(cfg, params=params, **kw)


def _routes(prefills, decodes, n, slot=0):
    routes = np.concatenate([s[2]["routes"] for s in prefills], axis=1)[:, :n]
    return np.concatenate(
        [routes] + [s[2]["routes"][:, slot: slot + 1] for s in decodes], axis=1
    )


def test_the_config_is_the_published_layer_pattern():
    assert CFG.pattern == "GEGEGE*E"
    assert (CFG.count("G"), CFG.count("*"), CFG.count("E")) == (3, 1, 4)
    assert CFG.rotary_dim == 4 and CFG.norm_eps == 1e-6
    assert qwen3_next.QWEN3_NEXT_PRESETS["qwen3_next_tiny"] == CFG
    whole = Qwen3NextConfig()
    assert whole.pattern == "GEGEGE*E" * 12 and whole.gdn_conv_dim == 8192
    with pytest.raises(ValueError, match="a mixer"):
        Qwen3NextConfig(pattern="GE*G")
    with pytest.raises(ValueError, match="blocks are of"):
        Qwen3NextConfig(pattern="MEGE")
    with pytest.raises(ValueError, match="use_sliding_window"):
        bench_model.config({**TINY, "use_sliding_window": True})


@pytest.mark.parametrize(
    "chunk, calls", [(None, 1), (32, 3)], ids=["whole", "three_chunks"]
)
def test_prefill_then_decode_equals_the_reference_pass(params, chunk, calls):
    """A 75-token prompt (a padded bucket; with `chunk` 32, three chunks,
    the last with 21 tokens of padding, the matrix state and the
    convolution tail carried twice and the attention layer reading
    earlier chunks' pages at their true positions), then 5 decode steps
    through the pages and the slot's state: the LOGITS of the last
    prompt position and of every decoded one against the reference's
    ONE full pass over prompt plus generated tokens, its routes forced
    to the system's (they are equal anyway in float32, which is
    asserted); and each Gated DeltaNet layer's state as the slot holds
    it against the token-by-token recurrence's."""
    eng = _engine(params, prefill_chunk=chunk)
    seen = _tapped(eng)
    prompt = _prompt(0, 75)
    (generated,) = eng.generate([prompt], SamplingParams(max_tokens=6))
    tokens = prompt + generated
    prefills = [s for s in seen if s[0].startswith("prefill")]
    decodes = [s for s in seen if s[0] == "decode"]
    assert len(prefills) == calls and len(decodes) == 5
    routes = _routes(prefills, decodes, 75)
    want, record = reference.forward_with_record(
        params, jnp.asarray(tokens[:-1], jnp.int32), routes=routes, **REF
    )
    assert routes.shape == (4, 80, 3)
    assert (np.sort(routes, -1) == np.sort(record["routes"], -1)).all()
    assert float(np.abs(want).max()) > 0.3  # logits of a size to compare
    np.testing.assert_allclose(prefills[-1][1][0, 0], want[74], atol=TOL, rtol=0)
    for i, step in enumerate(decodes):
        np.testing.assert_allclose(step[1][0], want[75 + i], atol=TOL, rtol=0)
    # The request is over; the state it left is still the slot's.
    assert record["states"].shape == (3, 4, 8, 16)
    np.testing.assert_allclose(
        eng.cache["gdn"][:, 0], record["states"], atol=TOL, rtol=0
    )
    assert "ssm" not in eng.cache  # no Mamba block, no leaf for one
    stats = eng.stats()
    assert stats["moe_pairs_routed"] == (75 + 5) * CFG.top_k * 4
    assert stats["prefill_programs"] == calls
    assert stats["gdn_scan_tokens"] == 3 * 75
    assert stats["ssm_scan_tokens"] == 0
    assert stats["prefill_attn_pairs"] == 75 * 76 // 2


def test_a_slot_reused_after_another_request_starts_from_zero_state(params):
    """One slot: the second request decodes through the state, the tail
    and the pages the first one left behind. Its logits are the
    reference's for its own tokens alone."""
    eng = _engine(params, max_batch=1, prefill_chunk=32)
    eng.generate([_prompt(1, 50)], SamplingParams(max_tokens=4))
    seen = _tapped(eng)
    prompt = _prompt(2, 41)
    (generated,) = eng.generate([prompt], SamplingParams(max_tokens=4))
    prefills = [s for s in seen if s[0].startswith("prefill")]
    decodes = [s for s in seen if s[0] == "decode"]
    assert len(prefills) == 2 and len(decodes) == 3
    want = reference.forward(
        params, jnp.asarray((prompt + generated)[:-1], jnp.int32),
        routes=_routes(prefills, decodes, 41), **REF,
    )
    np.testing.assert_allclose(prefills[-1][1][0, 0], want[40], atol=TOL, rtol=0)
    for i, step in enumerate(decodes):
        np.testing.assert_allclose(step[1][0], want[41 + i], atol=TOL, rtol=0)


def test_a_chunked_prefill_with_a_share_held_equals_the_reference_pass(
    monkeypatch,
):
    """Experts 2-5 of the 8 held, as a chip of an expert-parallel pair
    holds them: a 75-token prompt in three 32-row chunks, whose expert
    sublayers take the sorted form, then 5 decode steps in the every-row
    form. Logits against the reference's one pass with the same share."""
    monkeypatch.setattr(moe, "_PAIR_BLOCK", 16)
    tiny = {**TINY, "num_experts": 4, "first_expert_held": 2,
            "published": {"num_experts": 8}}
    cfg = bench_model.config(
        tiny, dtype=jnp.float32, dense_expert_rows=8, gdn_chunk=8
    )
    assert cfg.experts_held == (2, 4) and cfg.num_experts == 8
    held = init_params(jax.random.key(3), cfg)
    eng = _engine(held, cfg, prefill_chunk=32)
    seen = _tapped(eng)
    prompt = _prompt(0, 75)
    (generated,) = eng.generate([prompt], SamplingParams(max_tokens=6))
    prefills = [s for s in seen if s[0].startswith("prefill")]
    decodes = [s for s in seen if s[0] == "decode"]
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


def test_kernel_and_gather_attention_paths_agree(params, monkeypatch):
    """Greedy streams are equal between the Pallas paths (the prefill
    kernel in the chunk programs and the paged kernel in the decode
    program, with this family's rotated, normed queries and keys,
    interpreted here) and XLA's gather path."""
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
    hybrid_kv._prefill_program.cache_clear()


# ------------------------------------------------ the rule's two forms
def _recurrence(u, p, cfg, n):
    """The mixer's rule on the first ``n`` of u's tokens, a token a
    step in a Python loop, from zero state: (out [n, d], state)."""
    qkv, z, ba = qwen3_next._project_in(u, p, cfg)
    taps = cfg.conv_kernel
    seq = jnp.concatenate([jnp.zeros((taps - 1, qkv.shape[1])), qkv])
    conv = sum(seq[j: j + len(u)] * p["conv_w"][j] for j in range(taps))
    q, k, v = qwen3_next._split_qkv(jax.nn.silu(conv), cfg)
    beta, g = qwen3_next._gates(ba, p, cfg)
    hk = cfg.gdn_key_heads
    state = jnp.zeros((hk, cfg.gdn_value_heads // hk, cfg.gdn_key_dim,
                       cfg.gdn_value_dim))
    outs = []
    for t in range(n):
        state = state * jnp.exp(g[t])[..., None, None]
        read = jnp.einsum("hrkv,hk->hrv", state, k[t])
        delta = beta[t][..., None] * (v[t] - read)
        state = state + k[t][:, None, :, None] * delta[..., None, :]
        outs.append(jnp.einsum("hrkv,hk->hrv", state, q[t]))
    out = qwen3_next._project_out(
        jnp.stack(outs).reshape(n, -1), z[:n], p, cfg
    )
    return out, state.reshape(cfg.gdn_value_heads, cfg.gdn_key_dim, -1)


def _zero_state(cfg):
    return (
        jnp.zeros((cfg.gdn_value_heads, cfg.gdn_key_dim, cfg.gdn_value_dim)),
        jnp.zeros((cfg.conv_kernel - 1, cfg.gdn_conv_dim)),
    )


@pytest.mark.parametrize("chunk", [4, 16])
@pytest.mark.parametrize("length", [37, 29])
def test_gdn_chunked_equals_the_recurrence(params, chunk, length):
    """37 tokens, which no chunk size divides (the last chunk is padded
    inside), all real or only the first 29: outputs of the real
    positions, the state after the last real one and the convolution
    tail (the last three real inputs) against the rule a token a step."""
    cfg = dataclasses.replace(CFG, gdn_chunk=chunk)
    p = params["blocks"][0]
    u = jax.random.normal(jax.random.key(1), (37, CFG.d_model))
    out, state, tail = gdn_chunked(u, p, cfg, *_zero_state(cfg), jnp.int32(length))
    want, want_state = _recurrence(u, p, cfg, length)
    assert float(np.abs(want).max()) > 0.3
    np.testing.assert_allclose(out[:length], want, atol=2e-5, rtol=0)
    np.testing.assert_allclose(state, want_state, atol=2e-5, rtol=0)
    qkv = qwen3_next._project_in(u, p, cfg)[0]
    np.testing.assert_allclose(tail, qkv[length - 3: length], atol=0, rtol=0)


def test_gdn_chunked_carries_state_and_tail_between_calls(params):
    """Two calls of 16 tokens, the second from what the first left, are
    one call of 32."""
    p = params["blocks"][2]
    u = jax.random.normal(jax.random.key(2), (32, CFG.d_model))
    whole, state, tail = gdn_chunked(u, p, CFG, *_zero_state(CFG), jnp.int32(32))
    first, s1, t1 = gdn_chunked(u[:16], p, CFG, *_zero_state(CFG), jnp.int32(16))
    second, s2, t2 = gdn_chunked(u[16:], p, CFG, s1, t1, jnp.int32(16))
    np.testing.assert_allclose(
        jnp.concatenate([first, second]), whole, atol=2e-5, rtol=0
    )
    np.testing.assert_allclose(s2, state, atol=2e-5, rtol=0)
    np.testing.assert_allclose(t2, tail, atol=0, rtol=0)


def test_gdn_step_after_gdn_chunked_equals_the_recurrence_run_on(params):
    """29 tokens chunked, then three single steps (a batch of two slots,
    the other one's state garbage that must not leak): each step's
    output and the state after the last against the recurrence over all
    32."""
    p = params["blocks"][4]
    u = jax.random.normal(jax.random.key(5), (32, CFG.d_model))
    _, state, tail = gdn_chunked(
        jnp.pad(u[:29], ((0, 3), (0, 0))), p, CFG, *_zero_state(CFG),
        jnp.int32(29),
    )
    want, want_state = _recurrence(u, p, CFG, 32)
    states = jnp.stack([state, jnp.full_like(state, 7.0)])
    tails = jnp.stack([tail, jnp.full_like(tail, -3.0)])
    for t in range(29, 32):
        out, states, tails = gdn_step(
            jnp.stack([u[t], u[0]]), p, CFG, states, tails
        )
        np.testing.assert_allclose(out[0], want[t], atol=2e-5, rtol=0)
    np.testing.assert_allclose(states[0], want_state, atol=2e-5, rtol=0)


def test_unit_lower_inverse_where_the_keys_of_a_chunk_are_alike():
    """Keys that are all alike make ``L`` one number below the diagonal,
    where the series ``I - L + L^2 - ...`` has terms of 1e9 that cancel:
    the inverse by halves stays exact to float32."""
    c = 64
    lower = jnp.tril(jnp.full((c, c), 0.5), -1)
    got = qwen3_next._unit_lower_inverse(lower[None])[0]
    want = np.linalg.inv(np.eye(c) + np.asarray(lower, np.float64))
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=0)


# ------------------------------------------------- what each part adds
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
    [None, "rotary", "rotary_of_every_dimension", "qk_norm", "output_gate",
     "shared_expert_gate", "gates_renormalised", "norm_eps"],
)
def test_each_part_of_the_attention_block_and_the_gates_are_held(
    params, dropped
):
    """The program as published is within the limit of the reference;
    without the rotary embedding, with it on all 16 dimensions of a head
    and not the first 4, without the norms of q and k, without the
    heads' output gate, without the shared expert's scalar gate, with
    gates that are the probabilities as they are, or with the other
    families' norm epsilon, it is not: each is held by the comparison."""
    tokens = _prompt(9, 27)
    want = np.asarray(reference.forward(
        params, jnp.asarray(tokens, jnp.int32), **REF
    ))[-1]
    cfg, tree = CFG, params
    if dropped == "rotary":
        cfg = dataclasses.replace(CFG, rotary_dim=0)
    elif dropped == "rotary_of_every_dimension":
        cfg = dataclasses.replace(CFG, rotary_dim=16)
    elif dropped == "qk_norm":
        cfg = dataclasses.replace(CFG, qk_norm=False)
        # Norm weights are zero (1 + w = 1): only the division is lost.
    elif dropped == "output_gate":
        cfg = dataclasses.replace(CFG, attn_output_gate=False)
        attn = dict(params["blocks"][6])
        attn["wq"] = attn["wq"].reshape(64, 4, 2, 16)[:, :, 0].reshape(64, 64)
        tree = {**params, "blocks": (*params["blocks"][:6], attn,
                                     params["blocks"][7])}
    elif dropped == "shared_expert_gate":
        tree = {**params, "blocks": tuple(
            {k: v for k, v in b.items() if k != "shared_expert_gate"}
            for b in params["blocks"]
        )}
    elif dropped == "gates_renormalised":
        cfg = dataclasses.replace(CFG, norm_topk_prob=False)
    elif dropped == "norm_eps":
        cfg = dataclasses.replace(CFG, norm_eps=1e-3)
    worst = float(np.abs(_last_logits(cfg, tree, tokens) - want).max())
    assert (worst <= TOL) == (dropped is None), worst


def test_the_other_families_programs_hold_none_of_it():
    """Nemotron-H's and Granite's attention blocks lower as the three
    projections they were: no rotation, no per-head norm, no gate."""
    from ray_tpu.models.nemotron_h import NEMOTRON_H_PRESETS
    from ray_tpu.models.nemotron_h import init_params as init_nemotron

    cfg = NEMOTRON_H_PRESETS["nemotron_h_tiny"]
    tree = jax.eval_shape(lambda k: init_nemotron(k, cfg), jax.random.key(0))
    cache = jax.eval_shape(lambda: hybrid_kv.init_hybrid_cache(cfg, 4, 16, 1))
    assert sorted(cache) == ["conv", "k", "ssm", "v"]
    text = hybrid_kv.prefill_program(cfg, 2, 2, False).lower(
        tree, jax.ShapeDtypeStruct((1, 32), jnp.int32), cache,
        jax.ShapeDtypeStruct((2,), jnp.int32), np.int32(0), np.int32(0),
        np.int32(9),
    ).as_text()
    assert "cosine" not in text and "sine" not in text
    assert "attn:gate" not in text and "gdn:" not in text


@pytest.fixture(params=["sorted_pairs", "every_row"])
def path_cfg(request):
    """`moe_ffn`'s two ways to apply the experts, each forced in turn."""
    rows = 0 if request.param == "sorted_pairs" else 10**6
    return dataclasses.replace(CFG, dense_expert_rows=rows)


def test_the_shares_add_up_to_the_uncut_layer(params, path_cfg):
    """Expert parallelism over two chips: each share holds 4 of the 8
    experts (0-3 and 4-7, as the deployment's chips hold 0-255 and
    256-511), routes over all 8 (the gates renormalised over the chosen
    three, wherever they live) and computes its own experts' part. The
    two routed parts plus the gated shared expert ONCE are the uncut
    reference's layer (model-configs guide, section 4); each share also
    equals the reference given the same share."""
    p = params["blocks"][1]
    x = jax.random.normal(jax.random.key(6), (24, CFG.d_model))
    normed = rms_norm(x, p["norm"], CFG.norm_eps)
    shared = reference.shared_expert(p, normed)
    uncut, record = reference.expert_sublayer(p, x, **REF)
    parts, pairs = [], 0
    for first in (0, 4):
        cfg = dataclasses.replace(path_cfg, experts_held=(first, 4))
        mine = {**p, **{k: p[k][first: first + 4]
                        for k in ("w_gate", "w_up", "w_down")}}
        out, aux = moe_ffn(normed[None], mine, cfg)
        want, _ = reference.expert_sublayer(
            mine, x, **{**REF, "first_expert_held": first}
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
    assert pairs == 24 * CFG.top_k  # every pair fell to exactly one share


def test_config_counts_the_published_model():
    """The program's config at the published sizes holds what the issue
    counted: a Gated DeltaNet mixer 33.7M, a gated attention mixer
    27.3M, an expert FFN 1.61G whole and 0.81G with 256 of 512 held;
    and the benchmark's own count of the configuration it runs agrees
    with the tree's."""
    import json
    import os

    def sizes(c):
        shapes = jax.eval_shape(lambda k: init_params(k, c), jax.random.key(0))
        return shapes, dict(zip(c.pattern, (
            sum(int(np.prod(x.shape)) for x in jax.tree.leaves(b))
            for b in shapes["blocks"]
        )))

    _, by_kind = sizes(Qwen3NextConfig())
    assert round(by_kind["G"] / 1e6, 1) == 33.7
    assert round(by_kind["*"] / 1e6, 1) == 27.3
    assert round(by_kind["E"] / 1e9, 2) == 1.61
    here = os.path.join(os.path.dirname(__file__), "..", "benchmarks")
    with open(os.path.join(here, "configs", "qwen3next-80b-serve1.json")) as f:
        conf = json.load(f)
    served = bench_model.config(conf, max_seq=conf["engine"]["max_seq"])
    shapes, by_kind = sizes(served)
    assert round(by_kind["E"] / 1e6, 1) == 809.5
    assert served.pattern == "GEGEGE*E" and served.experts_held == (0, 256)
    assert (served.head_dim, served.rotary_dim, served.top_k) == (256, 64, 10)
    total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert total == bench_model.held_parameters(conf)
    cache = jax.eval_shape(lambda: hybrid_kv.init_hybrid_cache(served, 2, 64, 1))
    assert cache["gdn"].shape == (3, 1, 32, 128, 128)
    assert cache["gdn"].dtype == jnp.float32
    assert cache["gdn_conv"].shape == (3, 1, 3, 8192)
    assert (int(np.prod(cache["gdn"].shape[2:])) * 4
            == bench_model.gdn_state_bytes_per_slot(conf) == 2_097_152)
