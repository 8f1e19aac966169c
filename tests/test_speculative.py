"""Speculative decoding (prompt-lookup drafts + paged verify) —
reference capability: vLLM's speculative/prompt-lookup decoding behind
ray.llm. The invariant under greedy sampling: speculation must produce
EXACTLY the tokens the plain engine produces, just in fewer dispatches.
"""

import numpy as np
import pytest

from ray_tpu.llm.engine import LLMEngine, SamplingParams
from ray_tpu.llm.paged_kv import propose_ngram_draft
from ray_tpu.models import PRESETS


@pytest.fixture(scope="module")
def tiny():
    return PRESETS["tiny"]


# -------------------------------------------------------------- drafting


def test_ngram_draft_proposes_repetition():
    # "the cat sat on [the cat]" → after "the cat", propose "sat on ..."
    ctx = [5, 9, 3, 7, 5, 9]
    assert propose_ngram_draft(ctx, 2) == [3, 7]
    # Rightmost match wins: prefer the most recent repetition.
    ctx2 = [5, 9, 1, 5, 9, 2, 4, 5, 9]
    assert propose_ngram_draft(ctx2, 2) == [2, 4]


def test_ngram_draft_no_match_is_empty():
    assert propose_ngram_draft([1, 2, 3, 4], 3) == []
    assert propose_ngram_draft([1], 3) == []
    assert propose_ngram_draft([], 3) == []


# ------------------------------------------------------------- greedy eq


def _gen(tiny, prompts, speculate, **kw):
    eng = LLMEngine(
        tiny, max_batch=4, kv="paged", page_size=8,
        speculate=speculate, seed=0, **kw,
    )
    return eng.generate(
        prompts, SamplingParams(max_tokens=24, temperature=0.0)
    )


def test_speculative_matches_plain_greedy(tiny):
    """The core correctness property: identical outputs, every prompt,
    with drafts crossing page boundaries (page_size 8 < 24 tokens)."""
    rng = np.random.default_rng(0)
    prompts = [
        # Highly repetitive — drafts accept often.
        [7, 8, 9, 7, 8, 9, 7, 8, 9, 7, 8],
        # Random — drafts mostly reject.
        list(rng.integers(1, tiny.vocab_size, 13)),
        # Short prompt, below the n-gram window.
        [3],
        # Repetition of a 2-gram with diverging continuations.
        [4, 5, 1, 4, 5, 2, 4, 5],
    ]
    plain = _gen(tiny, prompts, speculate=0)
    spec = _gen(tiny, prompts, speculate=3)
    for i, (a, b) in enumerate(zip(plain, spec)):
        assert a == b, f"prompt {i}: {a} != {b}"


def test_speculative_fewer_steps_on_repetitive_output(tiny):
    """When the model emits repetitive text, drafts accept and the
    engine finishes in fewer step() calls than tokens generated."""
    eng = LLMEngine(
        tiny, max_batch=2, kv="paged", page_size=8, speculate=3, seed=0
    )
    # A prompt with strong repetition seeds the n-gram table.
    rid = eng.add_request(
        [2, 3, 4, 2, 3, 4, 2, 3, 4],
        SamplingParams(max_tokens=32, temperature=0.0),
    )
    steps = 0
    tokens = None
    while eng.has_unfinished():
        for fin in eng.step():
            if fin["request_id"] == rid:
                tokens = fin["tokens"]
        steps += 1
        assert steps < 200
    assert tokens is not None and len(tokens) == 32
    # Plain decoding needs 1 step per token (+1 prefill); speculation
    # must beat that on SOME step for this to mean anything. The tiny
    # random-weight model still repeats enough to accept drafts.
    plain_steps = 1 + len(tokens)
    assert steps < plain_steps, (
        f"{steps} steps for {len(tokens)} tokens — no draft ever accepted"
    )


def test_speculative_mixed_batch_with_sampling(tiny):
    """Stochastic slots ride the same verify dispatch with no draft;
    greedy slots still accept. Both finish correctly."""
    eng = LLMEngine(
        tiny, max_batch=4, kv="paged", page_size=8, speculate=2, seed=0
    )
    greedy_id = eng.add_request(
        [2, 3, 4, 2, 3, 4, 2, 3], SamplingParams(max_tokens=12, temperature=0.0)
    )
    warm_id = eng.add_request(
        [5, 6, 7, 8], SamplingParams(max_tokens=12, temperature=0.8)
    )
    out = {}
    while eng.has_unfinished():
        for fin in eng.step():
            out[fin["request_id"]] = fin["tokens"]
    assert len(out[greedy_id]) == 12
    assert len(out[warm_id]) == 12
    assert all(0 <= t < tiny.vocab_size for t in out[warm_id])

    # The greedy slot's tokens equal the plain engine's.
    plain = LLMEngine(
        tiny, max_batch=4, kv="paged", page_size=8, speculate=0, seed=0
    ).generate(
        [[2, 3, 4, 2, 3, 4, 2, 3]],
        SamplingParams(max_tokens=12, temperature=0.0),
    )[0]
    assert out[greedy_id] == plain


def test_speculative_at_max_seq_boundary(tiny):
    """A K-wide step reaching past max_seq must not crash the batch or
    corrupt live pages: overflow writes route to the dump page and the
    request finishes at the capacity edge (review regression)."""
    eng = LLMEngine(
        tiny, max_batch=2, kv="paged", page_size=8, max_seq=32,
        speculate=2, seed=0,
    )
    rid = eng.add_request(
        [2, 3, 4, 2, 3, 4, 2, 3, 4, 2, 3, 4],
        SamplingParams(max_tokens=64, temperature=0.0),  # > capacity
    )
    out = None
    steps = 0
    while eng.has_unfinished():
        for fin in eng.step():
            if fin["request_id"] == rid:
                out = fin["tokens"]
        steps += 1
        assert steps < 100
    assert out is not None
    # Finished at the capacity edge, not max_tokens.
    assert 0 < len(out) < 64
    # And matches the plain engine run into the same wall.
    plain = LLMEngine(
        tiny, max_batch=2, kv="paged", page_size=8, max_seq=32,
        speculate=0, seed=0,
    ).generate(
        [[2, 3, 4, 2, 3, 4, 2, 3, 4, 2, 3, 4]],
        SamplingParams(max_tokens=64, temperature=0.0),
    )[0]
    assert out == plain


# --------------------------------------------------------- stochastic

def test_stochastic_speculation_near_zero_temp_matches_greedy(tiny):
    """temp=1e-4 makes the softmax a near-delta: rejection sampling
    accepts exactly the argmax-agreeing drafts and the residual sample
    is the argmax, so the stochastic path must reproduce the greedy
    stream token for token — a deterministic end-to-end check of the
    acceptance plumbing."""
    from ray_tpu.models.llama import init_params
    import jax

    params = init_params(jax.random.key(0), tiny)
    prompt = [7, 8, 9, 7, 8, 9, 7, 8, 9, 7, 8]
    greedy = LLMEngine(
        tiny, max_batch=1, kv="paged", page_size=8, params=params,
    ).generate([prompt], SamplingParams(max_tokens=16))
    spec = LLMEngine(
        tiny, max_batch=1, kv="paged", page_size=8, params=params,
        speculate=3,
    ).generate(
        [prompt], SamplingParams(max_tokens=16, temperature=1e-4)
    )
    assert spec == greedy


@pytest.mark.parametrize("draft_kind", ["likely", "unlikely"])
def test_rejection_sampling_preserves_distribution(tiny, draft_kind):
    """The exactness property of speculative sampling: the token
    emitted through accept-or-residual must be distributed identically
    to a plain sample from the model (Leviathan et al.). Checked
    empirically at one position over many rng keys, with the draft
    chosen to stress the accept path (argmax draft) and the reject
    path (a low-probability draft)."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.llm.paged_kv import (
        init_paged_kv, paged_prefill, paged_verify,
    )
    from ray_tpu.models.llama import init_params

    params = init_params(jax.random.key(0), tiny)
    P, B = 16, 64
    pool = init_paged_kv(tiny, num_pages=8, page_size=P)
    ctx = [(5 * i + 2) % tiny.vocab_size for i in range(20)]
    pad = 32
    toks = np.zeros((1, pad), np.int32)
    toks[0, : len(ctx)] = ctx
    logits, pool = paged_prefill(
        params, jnp.asarray(toks), pool,
        jnp.asarray([1, 2], jnp.int32), cfg=tiny, n_write_pages=2,
    )
    last = np.asarray(logits[0, len(ctx) - 1])
    t0 = int(last.argmax())
    probe = np.asarray(
        jax.nn.softmax(jnp.asarray(last))
    )
    draft = (
        t0 if draft_kind == "likely" else int(probe.argmin())
    )
    # All B slots share the same two pages and write identical cells —
    # 64 independent acceptance samples per call.
    tables = jnp.asarray(np.tile([1, 2], (B, 1)).astype(np.int32))
    positions = jnp.full((B,), len(ctx), jnp.int32)
    temps = jnp.ones((B,), jnp.float32)
    vt = np.zeros((B, 2), np.int32)
    vt[:, 0] = t0
    vt[:, 1] = draft
    vt = jnp.asarray(vt)

    spec_emitted, plain_sampled = [], []
    analytic = None
    for trial in range(32):
        sampled, pos0_logits, pool, accept, rej = paged_verify(
            params, vt, pool, tables, positions, temps,
            jax.random.key(100 + trial), cfg=tiny,
        )
        if analytic is None:
            # Position-0 logits are input-determined (identical for
            # every slot and trial): the exact distribution the
            # emitted stream must follow.
            analytic = np.asarray(
                jax.nn.softmax(pos0_logits[0].astype(jnp.float64))
            )
        sampled = np.asarray(sampled)
        accept = np.asarray(accept)
        rej = np.asarray(rej)
        spec_emitted.extend(
            np.where(accept[:, 0], draft, rej[:, 0]).tolist()
        )
        plain_sampled.extend(sampled[:, 0].tolist())

    v = tiny.vocab_size
    h_spec = np.bincount(spec_emitted, minlength=v) / len(spec_emitted)
    h_plain = np.bincount(plain_sampled, minlength=v) / len(plain_sampled)
    tv_spec = 0.5 * np.abs(h_spec - analytic).sum()
    tv_plain = 0.5 * np.abs(h_plain - analytic).sum()
    # Both histograms carry the same finite-sample noise vs the
    # analytic distribution (~0.25 at n=2048 over a near-flat 512-way
    # softmax); a biased acceptance (e.g. always-accept on the argmax
    # draft) pushes tv_spec toward 1 while tv_plain stays at noise.
    assert tv_spec < tv_plain * 1.5 + 0.05, (
        f"spec TV {tv_spec:.3f} vs plain TV {tv_plain:.3f} "
        f"(draft={draft_kind})"
    )


def test_stochastic_speculation_accepts_drafts(tiny):
    """Speculation must actually fire on stochastic slots now: a
    repetitive prompt at a low temperature advances more than one
    token in some steps (acceptance > 0, so fewer decode steps than
    decoded tokens), and all tokens are in-vocab. (Counted by the
    engine: a step() returns the step BEFORE the one it dispatched, so
    what one call adds to a request says nothing about one program.)"""
    prompt = [7, 8, 9, 7, 8, 9, 7, 8, 9, 7, 8]
    eng = LLMEngine(
        tiny, max_batch=1, kv="paged", page_size=8, speculate=3, seed=0,
    )
    (out,) = eng.generate(
        [prompt], SamplingParams(max_tokens=64, temperature=0.05)
    )
    assert len(out) == 64 and all(0 <= t < tiny.vocab_size for t in out)
    stats = eng.stats()
    assert stats["draft_tokens_accepted"] > 0
    # 63 tokens come of decode steps (the first is the prefill's).
    assert stats["decode_steps"] < 63
