"""Nemotron-H (models/nemotron_h.py, llm/hybrid_kv.py) against the plain
reference (benchmarks/reference_nemotron_h.py) at a tiny size, float32,
seeded weights, on the CPU: each mixer, the expert share, and
prefill-then-decode through `LLMEngine`'s cache of pages and per-slot
state.

Tolerances: everything here is float32 on both sides, so differences
are summation order only. 2e-4 absolute on values of magnitude ~1-4
(mixer outputs, logits) leaves an order of magnitude over what float32
reassociation gives across five blocks (measured 1e-6 to 2e-5), and is
a hundred times under what any mathematical difference (a stale state,
one token's step skipped, a dropped pair) produces."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_nemotron_h as reference
from benchmarks.models import nemotron_h as bench_model
from ray_tpu.llm.engine import LLMEngine, SamplingParams
from ray_tpu.models.moe import moe_ffn
from ray_tpu.models.nemotron_h import (
    NemotronHConfig,
    init_params,
    mamba_chunked,
    mamba_step,
)
from ray_tpu.ops.norms import rms_norm

TOL = 2e-4

# The published keys (the catalog's) at a tiny size: what a
# configuration file carries, so that `config` and `for_model` are under
# test too.
TINY = {
    "model_type": "nemotron_h", "hidden_size": 64, "vocab_size": 256,
    "hybrid_override_pattern": "ME*EM", "num_hidden_layers": 5,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "mamba_num_heads": 8, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "n_routed_experts": 8, "num_experts_per_tok": 3,
    "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 48,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "n_group": 1, "topk_group": 1,
    "attention_bias": False, "mlp_bias": False, "use_bias": False,
    "mamba_proj_bias": False, "use_conv_bias": True,
    "tie_word_embeddings": False, "sliding_window": None,
    "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
    "norm_eps": 1e-5, "layer_norm_epsilon": 1e-5,
    "max_position_embeddings": 256,
}
# Rows up to 8 take `moe_ffn`'s every-row form and more its sorted one,
# so that an engine's decode steps (4 slots) run the first and its
# prefills (16 rows and more) the second, as the two meet in a replica.
CFG = dataclasses.replace(
    bench_model.config(TINY, dtype=jnp.float32), dense_expert_rows=8
)
REF = reference.for_model(TINY)


@pytest.fixture(scope="module")
def params():
    p = init_params(jax.random.key(3), CFG)
    # A selection bias that is not zero, so that choosing by score + bias
    # and gating by score are told apart.
    blocks = list(p["blocks"])
    for i, kind in enumerate(CFG.pattern):
        if kind == "E":
            bias = 0.2 * jax.random.normal(jax.random.key(i), (CFG.num_experts,))
            blocks[i] = {**blocks[i], "router_bias": bias}
    return {**p, "blocks": tuple(blocks)}


def _x(seed, t):
    return jax.random.normal(jax.random.key(seed), (t, CFG.d_model))


def _zero_state():
    return (
        jnp.zeros((CFG.mamba_heads, CFG.mamba_head_dim, CFG.ssm_state)),
        jnp.zeros((CFG.conv_kernel - 1, CFG.conv_dim)),
    )


def _mixer(p, x, ssm0, conv0, length):
    out, ssm, conv = mamba_chunked(
        rms_norm(x, p["norm"]), p, CFG, ssm0, conv0, length
    )
    return x + out, ssm, conv


@pytest.mark.parametrize("case", ["whole", "padded_tail", "carried_state"])
def test_chunked_mamba_equals_the_scan(params, case):
    """The chunked form (matrix products within chunks of 8, the state
    between) against the reference's scan over time: with zero state
    over 32 tokens; with 11 tokens of padding behind 21 real ones (their
    state and tail must be token 21's); from the state an earlier chunk
    left (16 tokens, then 13 real of 16)."""
    p = params["blocks"][0]
    x = _x(0, 32)
    if case == "whole":
        real, pieces = 32, [(0, 32, 32)]
    elif case == "padded_tail":
        real, pieces = 21, [(0, 32, 21)]
    else:
        real, pieces = 29, [(0, 16, 16), (16, 32, 13)]
    want, want_state = reference.mamba_block(p, x[:real], **REF)
    ssm, conv = _zero_state()
    got = []
    for lo, hi, length in pieces:
        out, ssm, conv = _mixer(p, x[lo:hi], ssm, conv, jnp.int32(length))
        got.append(out[:length])
    np.testing.assert_allclose(np.concatenate(got), want, atol=TOL, rtol=0)
    np.testing.assert_allclose(ssm, want_state, atol=TOL, rtol=0)
    # The tail is the last three real inputs of the convolution.
    u = rms_norm(x[:real], p["norm"]) @ p["in_proj"]
    tail = u[-3:, CFG.d_inner: CFG.d_inner + CFG.conv_dim]
    np.testing.assert_allclose(conv, tail, atol=TOL, rtol=0)


def test_one_token_mamba_equals_the_scan(params):
    """The decode form, a token at a time over two sequences at once,
    against the scan; then it goes on from a state the chunked form
    left."""
    p = params["blocks"][4]
    xs = jnp.stack([_x(1, 12), _x(2, 12)])  # [B, T, d]
    want = [reference.mamba_block(p, x, **REF) for x in xs]
    ssm = jnp.zeros((2, *_zero_state()[0].shape))
    conv = jnp.zeros((2, *_zero_state()[1].shape))
    for t in range(12):
        out, ssm, conv = mamba_step(
            rms_norm(xs[:, t], p["norm"]), p, CFG, ssm, conv
        )
        for b in range(2):
            np.testing.assert_allclose(
                xs[b, t] + out[b], want[b][0][t], atol=TOL, rtol=0
            )
    for b in range(2):
        np.testing.assert_allclose(ssm[b], want[b][1], atol=TOL, rtol=0)
    # Chunked over the first 8, then one token at a time.
    _, ssm8, conv8 = _mixer(p, xs[0, :8], *_zero_state(), jnp.int32(8))
    out, _, _ = mamba_step(
        rms_norm(xs[:1, 8], p["norm"]), p, CFG, ssm8[None], conv8[None]
    )
    np.testing.assert_allclose(
        xs[0, 8] + out[0], want[0][0][8], atol=TOL, rtol=0
    )


@pytest.fixture(params=["sorted_pairs", "every_row"])
def path_cfg(request):
    """`moe_ffn`'s two ways to apply the experts (grouped matmuls over
    sorted pairs; every held expert on every row, for few rows), each
    forced in turn."""
    rows = 0 if request.param == "sorted_pairs" else 10**6
    return dataclasses.replace(CFG, dense_expert_rows=rows)


def test_expert_layer_kinds_equal_the_reference(params, path_cfg):
    """`moe_ffn` with a sigmoid router (chosen by score + bias, gated by
    score, renormalised, scaled by 2.5), relu^2 experts without a gate
    matrix and the shared expert, against the reference's plain loop."""
    p = params["blocks"][1]
    x = _x(5, 24)
    want, record = reference.expert_block(p, x, **REF)
    out, aux = moe_ffn(rms_norm(x, p["norm"])[None], p, path_cfg)
    np.testing.assert_allclose(x + out[0], want, atol=TOL, rtol=0)
    assert (np.sort(aux["routes"], -1) == np.sort(record["routes"], -1)).all()
    assert int(aux["expert_load"].sum()) == 24 * CFG.top_k
    # The bias moved some choice: the test tells score + bias from score.
    scores = jax.nn.sigmoid(rms_norm(x, p["norm"]) @ p["router"])
    by_score = jax.lax.top_k(scores, CFG.top_k)[1]
    assert (np.sort(by_score, -1) != np.sort(aux["routes"], -1)).any()


def test_the_shares_add_up_to_the_uncut_layer(params, path_cfg):
    """Expert parallelism over two chips: each share holds 4 of the 8
    experts, routes over all 8 and computes its own experts' part. The
    two routed parts plus the shared expert ONCE are the uncut layer
    (model-configs guide, section 4); each share also equals the
    reference given the same share."""
    p = params["blocks"][3]
    x = _x(6, 24)
    h = rms_norm(x, p["norm"])[None]
    whole, aux = moe_ffn(h, p, path_cfg)
    no_shared = {k: v for k, v in p.items() if not k.startswith("shared")}
    shared = whole - moe_ffn(h, no_shared, path_cfg)[0]
    parts, pairs = [], 0
    for first in (0, 4):
        cfg = dataclasses.replace(path_cfg, experts_held=(first, 4))
        mine = {**p, "w_up": p["w_up"][first: first + 4],
                "w_down": p["w_down"][first: first + 4]}
        out, part_aux = moe_ffn(h, mine, cfg)
        want, _ = reference.expert_block(
            mine, x, **{**REF, "first_expert_held": first}
        )
        np.testing.assert_allclose(x + out[0], want, atol=TOL, rtol=0)
        assert (part_aux["routes"] == aux["routes"]).all()
        parts.append(out - shared)
        pairs += int(part_aux["expert_load"].sum())
    np.testing.assert_allclose(
        parts[0] + parts[1] + shared, whole, atol=TOL, rtol=0
    )
    assert pairs == 24 * CFG.top_k  # every pair fell to exactly one share


def test_rows_that_carry_no_token_are_left_out(params, path_cfg):
    p = params["blocks"][1]
    h = rms_norm(_x(7, 16), p["norm"])[None]
    live = jnp.arange(16) < 10
    out, aux = moe_ffn(h, p, path_cfg, rows_live=live)
    want, _ = moe_ffn(h[:, :10], p, path_cfg)
    np.testing.assert_allclose(out[0, :10], want[0], atol=TOL, rtol=0)
    assert int(aux["expert_load"].sum()) == 10 * CFG.top_k


# ------------------------------------------------------------- the engine
def _engine(params, **kw):
    kw = {"max_batch": 4, "max_seq": 192, "page_size": 16, **kw}
    return LLMEngine(CFG, params=params, **kw)


def _tapped(eng):
    """Every program's logits and record, as `on_logits` hands them over."""
    seen = []
    eng.on_logits = lambda phase, logits, record: seen.append(
        (phase, np.asarray(logits), jax.tree.map(np.asarray, record))
    )
    return seen


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(1, CFG.vocab_size, n).tolist()


def _reference_logits(params, tokens, routes=None):
    return np.asarray(reference.forward_with_record(
        params, jnp.asarray(tokens, jnp.int32), routes=routes, **REF
    )[0])


@pytest.mark.parametrize("chunk", [None, 32])
def test_prefill_then_decode_equals_the_reference_pass(params, chunk):
    """A 53-token prompt (a padded bucket of 64; with `chunk` 32, two
    chunks, the second with 11 tokens of padding), then 5 decode steps
    through the pages and the slot's state: the logits of the last
    prompt position and of every decoded one against the reference's ONE
    full pass over prompt plus generated tokens, its routes forced to
    the system's (they are equal anyway in float32, which is asserted);
    and the slot's SSM state against the scan's."""
    eng = _engine(params, prefill_chunk=chunk)
    seen = _tapped(eng)
    prompt = _prompt(0, 53)
    (generated,) = eng.generate([prompt], SamplingParams(max_tokens=6))
    tokens = prompt + generated
    prefills = [s for s in seen if s[0].startswith("prefill")]
    decodes = [s for s in seen if s[0] == "decode"]
    assert len(prefills) == (2 if chunk else 1) and len(decodes) == 5
    routes = np.concatenate(
        [s[2]["routes"] for s in prefills], axis=1
    )[:, :53]
    routes = np.concatenate(
        [routes] + [s[2]["routes"][:, :1] for s in decodes], axis=1
    )
    want, record = reference.forward_with_record(
        params, jnp.asarray(tokens[:-1], jnp.int32), routes=routes, **REF
    )
    assert (np.sort(routes, -1) == np.sort(record["routes"], -1)).all()
    np.testing.assert_allclose(
        prefills[-1][1][0, 0], want[52], atol=TOL, rtol=0
    )
    for i, step in enumerate(decodes):
        np.testing.assert_allclose(step[1][0], want[53 + i], atol=TOL, rtol=0)
    # The request is over; the state it left is still the slot's.
    np.testing.assert_allclose(
        eng.cache["ssm"][:, 0], record["states"], atol=TOL, rtol=0
    )
    stats = eng.stats()
    assert stats["state_bytes"] == sum(
        eng.cache[k].nbytes for k in ("ssm", "conv")
    )
    assert stats["pool_bytes"] == eng.cache["k"].nbytes * 2
    assert stats["moe_pairs_routed"] == (53 + 5) * CFG.top_k * 2
    assert stats["moe_pairs_here"] == stats["moe_pairs_routed"]  # all held
    assert 0 < stats["experts_touched"] <= 5 * 2 * CFG.top_k


def test_chunked_prefill_beside_decoding_slots_changes_nothing(params):
    """A prompt prefilled in three chunks while three other slots decode
    (the decode program runs over all four slots between its chunks)
    returns the tokens and logits it returns alone: the decode steps do
    not touch the state of a slot that is mid-prefill."""
    long_prompt = _prompt(1, 88)
    alone = _engine(params, prefill_chunk=32)
    seen_alone = _tapped(alone)
    (want,) = alone.generate([long_prompt], SamplingParams(max_tokens=4))

    eng = _engine(params, prefill_chunk=32)
    others = [
        eng.add_request(_prompt(10 + i, 20), SamplingParams(max_tokens=24))
        for i in range(3)
    ]
    eng.step()  # the three are admitted and decode
    assert len(eng._active) == 3
    seen = _tapped(eng)
    rid = eng.add_request(long_prompt, SamplingParams(max_tokens=4))
    done = {}
    while eng.has_unfinished():
        for fin in eng.step():
            done[fin["request_id"]] = fin["tokens"]
    assert done[rid] == want and set(others) <= set(done)
    phases = [s[0] for s in seen]
    first, last = phases.index("prefill_chunk"), (
        len(phases) - 1 - phases[::-1].index("prefill_chunk")
    )
    assert phases[first: last + 1].count("decode") >= 2  # interleaved
    got_last = [s for s in seen if s[0] == "prefill_chunk"][-1][1]
    want_last = [s for s in seen_alone if s[0] == "prefill_chunk"][-1][1]
    np.testing.assert_allclose(got_last, want_last, atol=TOL, rtol=0)


def test_a_reused_slot_carries_nothing_over(params):
    """State never leaks between a slot's successive requests: with one
    slot, request B after request A answers what it answers on a fresh
    engine; and a request preempted mid-decode (the pool too small for
    two) resumes by recompute from its tokens and answers the same."""
    a, b = _prompt(2, 40), _prompt(3, 27)
    sampling = SamplingParams(max_tokens=8)
    (fresh,) = _engine(params, max_batch=1).generate([b], sampling)
    eng = _engine(params, max_batch=1)
    assert eng.generate([a], sampling) != [fresh]
    assert eng.generate([b], sampling) == [fresh]

    long = SamplingParams(max_tokens=40)
    wants = [
        _engine(params).generate([p], long)[0] for p in (a, b)
    ]
    # 8 pages of 16: both prompts fit (4 + 2), growth forces a preemption.
    tight = _engine(params, num_pages=8)
    assert tight.generate([a, b], long) == wants
    assert tight.stats()["preemptions"] >= 1


@pytest.mark.parametrize(
    "temperature", [0.0, 0.8], ids=["greedy", "sampled"]
)
def test_lag_1_emits_what_lag_0_emits(temperature, params, generate_at_lag0):
    """The decode step dispatched from the ids the step before it left
    on the device (state, pages and expert routes carried the same)
    emits token for token what it emits from the host's copy of them,
    and ends known early cost no slot-step: the state a finished
    request leaves is the one its last token was computed from."""
    sampling = SamplingParams(max_tokens=7, temperature=temperature)
    prompts = [_prompt(4, 21), _prompt(5, 37), _prompt(6, 9)]
    lag1, lag0 = _engine(params, seed=2), _engine(params, seed=2)
    assert lag1.generate(prompts, sampling) == generate_at_lag0(
        lag0, prompts, sampling
    )
    np.testing.assert_array_equal(lag1.cache["ssm"], lag0.cache["ssm"])
    np.testing.assert_array_equal(lag1.cache["conv"], lag0.cache["conv"])
    s1, s0 = lag1.stats(), lag0.stats()
    assert s0["decode_steps_in_flight"] == 0
    assert s1["decode_steps"] == s0["decode_steps"] == 6
    assert s1["decode_steps_in_flight"] == 5
    assert s1["slot_steps"] == s0["slot_steps"] == 18
    assert s1["experts_touched"] == s0["experts_touched"]
    assert s1["overrun_slot_steps"] == 0


def test_a_stop_tokens_overrun_step_leaves_nothing_behind(params):
    """A stop token is learnt a step late, and a recurrent slot's state
    cannot be rolled back: the overrun step advances a state that nobody
    reads again. The next request in that slot and those pages starts
    from `start = 0` and answers what it answers on a fresh engine."""
    a, b = _prompt(7, 30), _prompt(8, 45)
    sampling = SamplingParams(max_tokens=10)
    (free,) = _engine(params, max_batch=1).generate([a], sampling)
    (fresh,) = _engine(params, max_batch=1).generate([b], sampling)
    k = next(i for i in range(2, 10) if free[i] not in free[:i])
    eng = _engine(params, max_batch=1)
    stopped = SamplingParams(max_tokens=10, stop_token_ids=(free[k],))
    assert eng.generate([a], stopped) == [free[:k]]
    stats = eng.stats()
    assert stats["decode_steps"] == k + 1
    assert stats["overrun_slot_steps"] == 1
    assert eng.alloc.free_pages == eng.alloc.num_pages
    overrun = np.asarray(eng.cache["ssm"][:, 0])
    assert eng.generate([b], sampling) == [fresh]
    assert not np.array_equal(overrun, np.asarray(eng.cache["ssm"][:, 0]))


def test_speculation_is_refused_for_recurrent_blocks(params):
    with pytest.raises(ValueError, match="rolled back"):
        _engine(params, speculate=2)


def test_kernel_and_gather_attention_paths_agree(params, monkeypatch):
    """Greedy streams are equal between the Pallas paged-attention path
    (interpreted here) and XLA's gather path."""
    prompts = [_prompt(4, 30), _prompt(5, 18)]
    sampling = SamplingParams(max_tokens=6)
    monkeypatch.setenv("RAY_TPU_PAGED_ATTN", "0")
    want = _engine(params).generate(prompts, sampling)
    monkeypatch.setenv("RAY_TPU_PAGED_ATTN", "1")
    eng = _engine(params)
    assert eng.paged_attn_kernel
    assert eng.generate(prompts, sampling) == want


def test_config_counts_the_published_model():
    """The program's config at the published sizes holds what the issue
    counted: 38.7M a Mamba block, 1,297.5M an expert block, 23.4M an
    attention block, 31.6B in all."""
    cfg = NemotronHConfig()
    shapes = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))
    sizes = [
        sum(int(np.prod(x.shape)) for x in jax.tree.leaves(b))
        for b in shapes["blocks"]
    ]
    by_kind = dict(zip(cfg.pattern, sizes))
    # The expert stacks are held 1920 wide (zeros from 1856 on).
    by_kind["E"] -= cfg.num_experts * 2 * cfg.d_model * (cfg.d_ff_held - cfg.d_ff)
    assert cfg.d_ff_held == 1920
    assert round(by_kind["M"] / 1e6, 1) == 38.7
    assert round(by_kind["E"] / 1e6, 1) == 1297.5
    assert round(by_kind["*"] / 1e6, 1) == 23.4
    total = (
        cfg.count("M") * by_kind["M"] + cfg.count("E") * by_kind["E"]
        + cfg.count("*") * by_kind["*"]
        + 2 * cfg.vocab_size * cfg.d_model + cfg.d_model
    )
    assert round(total / 1e9, 1) == 31.6
