"""Paged KV cache: memory-bound admission, prefix sharing, preemption,
on-device sampling.

(reference capability model: vLLM's paged attention + prefix caching +
recompute preemption, which ray.llm inherits through engine_kwargs —
python/ray/llm/_internal/serve/deployments/llm/vllm/vllm_models.py:234.)
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.engine import LLMEngine, SamplingParams
from ray_tpu.llm.paged_kv import PageAllocator, paged_verify, prefix_hashes
from ray_tpu.models.llama import PRESETS, forward

CFG = PRESETS["tiny"]


@pytest.fixture(scope="module")
def params():
    from ray_tpu.models.llama import init_params

    return init_params(jax.random.key(0), CFG)


# ---------------------------------------------------------- allocator
def test_allocator_refcount_and_free():
    a = PageAllocator(num_pages=4, page_size=8)
    assert a.free_pages == 4
    p1 = a.alloc()
    p2 = a.alloc()
    assert a.free_pages == 2 and p1 != p2 and 0 not in (p1, p2)
    a.share(p1)
    a.release(p1)
    assert a.free_pages == 2  # still one ref held
    a.release(p1)
    assert a.free_pages == 3
    a.release(p2)
    assert a.free_pages == 4


def test_prefix_hash_only_full_pages():
    assert prefix_hashes([1, 2, 3], 4) == []
    h1 = prefix_hashes([1, 2, 3, 4, 5], 4)
    assert len(h1) == 1
    # Same first page, different tail → same page-0 hash.
    h2 = prefix_hashes([1, 2, 3, 4, 9, 9, 9, 9], 4)
    assert h2[0] == h1[0] and len(h2) == 2


def test_prefix_registry_evicted_on_release():
    a = PageAllocator(num_pages=2, page_size=4)
    p = a.alloc()
    a.register_prefix(1234, p)
    assert a.lookup_prefix(1234) == p
    a.release(p)
    assert a.lookup_prefix(1234) is None  # dead pages must not be shared


# ------------------------------------------------- engine: correctness
# The last is long enough to be chunked and repeats itself, so that
# prompt lookup finds drafts.
PROMPTS = [[1, 2, 3, 4, 5], [7, 8], [9, 10, 11, 12, 13] * 8]
ENGINE_PATHS = {
    "plain": {},
    "chunked": {"prefill_chunk": 16},
    "speculative": {"speculate": 3},
    "kernel": {},  # RAY_TPU_PAGED_ATTN=1: the interpreted Pallas kernels
}


@pytest.fixture(scope="module")
def greedy_reference(params):
    """Greedy continuations by the model's whole forward pass over the
    growing sequence: no cache, and no helper of the serving programs."""
    outs = []
    for prompt in PROMPTS:
        seq = list(prompt)
        for _ in range(6):
            logits = forward(params, jnp.asarray([seq], jnp.int32), CFG)
            seq.append(int(np.asarray(logits[0, -1]).argmax()))
        outs.append(seq[len(prompt):])
    return outs


@pytest.mark.parametrize("path", sorted(ENGINE_PATHS))
def test_engine_matches_full_forward(
    path, params, greedy_reference, monkeypatch
):
    """Every way through the engine emits the full forward's greedy
    tokens, and the path the case names was really taken."""
    monkeypatch.setenv("RAY_TPU_PAGED_ATTN", "1" if path == "kernel" else "0")
    engine = LLMEngine(
        CFG, max_batch=2, max_seq=64, params=params, page_size=16,
        **ENGINE_PATHS[path],
    )
    assert engine.paged_attn_kernel == (path == "kernel")
    outs = engine.generate(PROMPTS, SamplingParams(max_tokens=6))
    assert outs == greedy_reference
    stats = engine.stats()
    assert (stats["prefill_chunks"] >= 2) == (path == "chunked")
    assert (stats["draft_tokens_proposed"] > 0) == (path == "speculative")
    assert engine.alloc.free_pages == engine.alloc.num_pages


def test_kv_keyword_has_one_legal_value(params):
    """Configurations still pass ``kv="paged"``; the slab is gone and
    asking for it says so."""
    engine = LLMEngine(CFG, max_batch=1, max_seq=32, params=params, kv="paged")
    assert not hasattr(engine, "kv")
    with pytest.raises(ValueError, match="dense slab cache was removed"):
        LLMEngine(CFG, max_batch=1, max_seq=32, params=params, kv="dense")


def test_one_decode_program_whatever_the_temperatures(params):
    """Without speculation a greedy batch and a sampled one run the same
    compiled decode program: `stochastic` is a static argument that
    changes nothing at K = 1, so the engine never varies it there."""
    # A shape no other test of this module decodes at.
    engine = LLMEngine(CFG, max_batch=3, max_seq=40, params=params,
                       page_size=8)
    before = paged_verify._cache_size()
    engine.generate([[1, 2, 3]], SamplingParams(max_tokens=4))
    # Two signatures of one compiled program: `tokens` from the host
    # (the first step) and as the step in flight left them on the device
    # (tests/test_tpu_aot_programs.py counts the compiles).
    assert paged_verify._cache_size() == before + 2
    outs = engine.generate(
        [[4, 5, 6], [7, 8]], SamplingParams(max_tokens=4, temperature=0.8)
    )
    assert [len(o) for o in outs] == [4, 4]
    assert paged_verify._cache_size() == before + 2


@pytest.mark.parametrize("speculate", [0, 3])
def test_decode_program_is_called_once_a_step(speculate, params):
    """What a logits tap around `_decode_paged` may count on (the
    benchmark's reference check is one): exactly one call per decode
    step, whose second result is position 0's logits for every slot."""
    engine = LLMEngine(CFG, max_batch=2, max_seq=64, params=params,
                       page_size=16, speculate=speculate)
    real, calls = engine._decode_paged, []

    def tapped(*args, **kw):
        out = real(*args, **kw)
        calls.append(out)
        return out

    engine._decode_paged = tapped
    engine.generate(PROMPTS, SamplingParams(max_tokens=6))
    assert len(calls) == engine.stats()["decode_steps"] > 0
    for sampled, logits, pool, accept, rej in calls:
        assert logits.shape == (2, CFG.vocab_size)
        assert logits.dtype == jnp.float32
        assert sampled.shape == (2, 1 + speculate)
        assert accept.shape == rej.shape == (2, speculate)
        assert set(pool) == {"k", "v"}


def test_memory_bound_admission_beyond_dense_capacity(params):
    """64 variable-length requests share a page budget the dense slab
    provably cannot hold: dense needs max_batch*max_seq cache tokens
    (64*64 = 4096) while this pool holds 24 pages * 16 = 384 token
    cells — ~9% — yet every request completes because admission is
    by actual page demand and pages recycle as requests finish."""
    n = 64
    prompts = [[(7 * i + j) % CFG.vocab_size for j in range(2 + i % 11)]
               for i in range(n)]
    engine = LLMEngine(
        CFG, max_batch=8, max_seq=64, params=params,
        kv="paged", page_size=16, num_pages=24,
    )
    outs = engine.generate(prompts, SamplingParams(max_tokens=3))
    assert len(outs) == n and all(len(o) == 3 for o in outs)
    # The pool was the constraint, not slots: budget < dense equivalent.
    assert 24 * 16 < 8 * 64  # pool tokens < dense slab for same batch
    # All pages returned after the run.
    assert engine.alloc.free_pages == 24


def test_prefix_sharing_reuses_pages(params):
    """Two requests with an identical 32-token head share its pages."""
    head = [(3 * i) % CFG.vocab_size for i in range(32)]
    p1 = head + [5, 6]
    p2 = head + [9]
    engine = LLMEngine(
        CFG, max_batch=2, max_seq=64, params=params,
        kv="paged", page_size=16,
    )
    engine.add_request(p1, SamplingParams(max_tokens=24))
    engine.step()  # admit r1 (registers head pages)
    used_after_r1 = engine.alloc.num_pages - engine.alloc.free_pages
    engine.add_request(p2, SamplingParams(max_tokens=24))
    engine.step()  # admit r2 (shares the 2 full head pages)
    used_after_r2 = engine.alloc.num_pages - engine.alloc.free_pages
    # Both prompts bucket to 64 tokens = 4 pages; r2 shares the 2 full
    # head pages and allocates only its 2 tail/decode pages.
    assert used_after_r1 == 4
    assert used_after_r2 - used_after_r1 == 2
    while engine.has_unfinished():
        engine.step()
    assert engine.alloc.free_pages == engine.alloc.num_pages


def test_prefix_sharing_output_parity(params):
    """Shared-prefix decoding must not change results."""
    head = [(3 * i) % CFG.vocab_size for i in range(32)]
    prompts = [head + [5, 6], head + [9], head[:16] + [1]]
    sp = SamplingParams(max_tokens=5)
    shared = LLMEngine(CFG, max_batch=3, max_seq=64, params=params,
                       kv="paged", page_size=16)
    outs = shared.generate(prompts, sp)
    solo_engine = LLMEngine(CFG, max_batch=1, max_seq=64, params=params,
                            kv="paged", page_size=16)
    for p, o in zip(prompts, outs):
        assert solo_engine.generate([p], sp)[0] == o


def test_preemption_under_pool_pressure(params):
    """A pool too small for all active requests' growth preempts the
    youngest (recompute-style) and still finishes everything right."""
    sp = SamplingParams(max_tokens=20)
    prompts = [[1, 2, 3, 4, 5, 6, 7, 8], [9, 10, 11, 12, 13, 14]]
    tight = LLMEngine(
        CFG, max_batch=2, max_seq=64, params=params,
        kv="paged", page_size=8, num_pages=4,  # one request's full growth
    )
    outs = tight.generate(prompts, sp)
    roomy = LLMEngine(CFG, max_batch=2, max_seq=64, params=params,
                      kv="paged", page_size=8)
    assert outs == roomy.generate(prompts, sp)
    assert tight.alloc.free_pages == 4


def test_double_preemption_resumes_correctly(params):
    """A request preempted TWICE must not duplicate context (regression:
    folding out_tokens into prompt on each preemption re-folded tokens)
    and must report its ORIGINAL prompt when finished."""
    sp = SamplingParams(max_tokens=24)
    prompts = [[1, 2, 3, 4, 5, 6], [7, 8, 9, 10], [11, 12, 13]]
    tight = LLMEngine(
        CFG, max_batch=3, max_seq=64, params=params,
        kv="paged", page_size=8, num_pages=6,
    )
    order = {tight.add_request(p, sp): i for i, p in enumerate(prompts)}
    outs: list = [None] * 3
    reported_prompts: list = [None] * 3
    while tight.has_unfinished():
        for fin in tight.step():
            outs[order[fin["request_id"]]] = fin["tokens"]
            reported_prompts[order[fin["request_id"]]] = fin["prompt"]
    assert reported_prompts == prompts  # prompts never mutated
    roomy = LLMEngine(CFG, max_batch=3, max_seq=64, params=params,
                      kv="paged", page_size=8)
    assert outs == roomy.generate(prompts, sp)


def test_pool_too_small_rejected_at_submission(params):
    engine = LLMEngine(CFG, max_batch=1, max_seq=64, params=params,
                       kv="paged", page_size=8, num_pages=1)
    with pytest.raises(ValueError, match="pages"):
        engine.add_request(list(range(1, 30)), SamplingParams(max_tokens=2))
    # A request that fits prompt-wise but not with its growth is also
    # rejected up front (admitting it would crash mid-decode).
    engine2 = LLMEngine(CFG, max_batch=1, max_seq=64, params=params,
                        kv="paged", page_size=8, num_pages=3)
    with pytest.raises(ValueError, match="pages"):
        engine2.add_request([1, 2, 3, 4, 5, 6, 7, 8],
                            SamplingParams(max_tokens=30))


def test_on_device_temperature_sampling(params):
    """temperature>0 runs the on-device categorical path end to end and
    produces tokens in-vocab; greedy (t=0) stays deterministic."""
    engine = LLMEngine(CFG, max_batch=2, max_seq=64, params=params,
                      kv="paged", page_size=16)
    outs = engine.generate(
        [[1, 2, 3], [4, 5, 6]],
        SamplingParams(max_tokens=8, temperature=0.9),
    )
    assert all(0 <= t < CFG.vocab_size for o in outs for t in o)
    g1 = engine.generate([[1, 2, 3]], SamplingParams(max_tokens=8))
    g2 = engine.generate([[1, 2, 3]], SamplingParams(max_tokens=8))
    assert g1 == g2


def test_top_k_sampling_host_fallback(params):
    """top_k uses the host path but still completes (and respects k=1 ==
    greedy determinism)."""
    engine = LLMEngine(CFG, max_batch=1, max_seq=64, params=params,
                       kv="paged", page_size=16)
    greedy = engine.generate([[1, 2, 3]], SamplingParams(max_tokens=6))[0]
    topk1 = engine.generate(
        [[1, 2, 3]],
        SamplingParams(max_tokens=6, temperature=1.0, top_k=1),
    )[0]
    assert topk1 == greedy


def test_abort_releases_pages(params):
    engine = LLMEngine(CFG, max_batch=2, max_seq=64, params=params,
                       kv="paged", page_size=16)
    rid = engine.add_request(list(range(1, 20)),
                             SamplingParams(max_tokens=50))
    engine.step()
    assert engine.alloc.free_pages < engine.alloc.num_pages
    assert engine.abort_request(rid)
    assert engine.alloc.free_pages == engine.alloc.num_pages


def test_abort_of_an_ended_request_does_not_wait_for_the_lock(params):
    """Every stream ends with `abort_request` on the replica's event
    loop; for a request that has ended it answers while a step holds
    `_lock` (a step that ends a prefill holds it for the prefill's
    length), and a live request's abort still takes it."""
    engine = LLMEngine(CFG, max_batch=2, max_seq=64, params=params,
                       kv="paged", page_size=16)
    sampling = SamplingParams(max_tokens=3)
    ended = engine.add_request(list(range(1, 20)), sampling, stream=True)
    while engine.has_unfinished():
        engine.step()
    live = engine.add_request(list(range(1, 20)), sampling)
    assert engine._live == {live}
    with engine._lock:  # not re-entrant: a wait here would never end
        assert engine.abort_request(ended) is False
        assert engine.abort_request("never-added") is False
    assert engine.abort_request(live)
    assert not engine._live and not engine.has_unfinished()


def test_on_logits_hands_over_every_programs_logits():
    """The public tap (`LLMEngine.on_logits`): each prefill's and each
    decode step's logits as the program returned them, no record for a
    model without expert blocks; `slot_of` names the request's row; the
    cache's bytes are all pages."""
    eng = LLMEngine("tiny", max_batch=2, max_seq=64, page_size=16)
    seen = []
    eng.on_logits = lambda phase, logits, record: seen.append(
        (phase, logits.shape, record)
    )
    rid = eng.add_request([5, 6, 7, 8, 9], SamplingParams(max_tokens=3))
    eng.step()
    assert eng.slot_of(rid) == 0 and eng.slot_of("nobody") is None
    while eng.has_unfinished():
        eng.step()
    v = eng.cfg.vocab_size
    assert seen == [("prefill", (1, 16, v), None)] + [("decode", (2, v), None)] * 2
    stats = eng.stats()
    assert stats["state_bytes"] == 0 and stats["moe_pairs_routed"] == 0
    assert stats["pool_bytes"] == eng.cache["k"].nbytes * 2


# ------------------------------------------- one decode step in flight
def _engine(params, **kw):
    kw = {"max_batch": 2, "max_seq": 64, "page_size": 16, **kw}
    return LLMEngine(CFG, params=params, **kw)


def _count_decode_calls(engine) -> list:
    real, calls = engine._decode_paged, []

    def counted(*args, **kw):
        calls.append(1)
        return real(*args, **kw)

    engine._decode_paged = counted
    return calls


def test_the_chain_of_keys_is_the_split_chain():
    """A block of links made by one program is link for link what
    ``key, sub = jax.random.split(key)`` makes a step at a time, over a
    block's end too: seeded streams stay what they were."""
    from ray_tpu.llm.engine import _KEY_BLOCK

    engine = LLMEngine("tiny", max_batch=1, max_seq=32, seed=11)
    key = jax.random.key(11)
    for _ in range(_KEY_BLOCK + 3):
        key, sub = jax.random.split(key)
        got = engine._next_key()
        assert (jax.random.key_data(got) == jax.random.key_data(sub)).all()


@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "sampled"])
def test_lag_1_emits_what_lag_0_emits(temperature, params, generate_at_lag0):
    """Same programs, same inputs, same chain of keys: a step dispatched
    from the ids the step before left on the device emits token for
    token what it emits from the host's copy of them."""
    sampling = SamplingParams(max_tokens=9, temperature=temperature)
    prompts = PROMPTS[:2]
    lag1, lag0 = _engine(params, seed=5), _engine(params, seed=5)
    assert lag1.generate(prompts, sampling) == generate_at_lag0(
        lag0, prompts, sampling
    )
    s1, s0 = lag1.stats(), lag0.stats()
    assert s0["decode_steps_in_flight"] == 0 == s0["decode_in_flight_pct"]
    # Of 8 decode steps all but the first follow one still in flight.
    assert s1["decode_steps"] == s0["decode_steps"] == 8
    assert s1["decode_steps_in_flight"] == 7
    assert s1["decode_in_flight_pct"] == pytest.approx(87.5)
    assert s1["slot_steps"] == s0["slot_steps"] == 16
    assert s1["overrun_slot_steps"] == 0
    assert not any(s1["pipeline_drains"].values())


def test_greedy_streams_do_not_depend_on_when_a_slot_frees(
    params, generate_at_lag0
):
    """Three requests over two slots: at lag 1 an end is reported one
    call later, so the third is admitted into another step than at lag
    0; greedy tokens are the same, and the admission read the step in
    flight back first."""
    samplings = [SamplingParams(max_tokens=n) for n in (4, 12, 6)]
    outs = generate_at_lag0(_engine(params), PROMPTS, samplings)
    lag1 = _engine(params)
    order = [lag1.add_request(p, s) for p, s in zip(PROMPTS, samplings)]
    done = {}
    while lag1.has_unfinished():
        done.update((f["request_id"], f["tokens"]) for f in lag1.step())
    assert [done[rid] for rid in order] == outs
    assert [len(o) for o in outs] == [4, 12, 6]
    stats = lag1.stats()
    assert stats["pipeline_drains"]["admit"] == 1
    assert stats["decode_steps_in_flight"] > 0
    assert lag1.alloc.free_pages == lag1.alloc.num_pages


@pytest.mark.parametrize(
    "max_tokens,max_seq,decodes",
    [(1, 64, 0), (2, 64, 1), (6, 64, 5), (30, 16, 10)],
    ids=["n1", "n2", "n6", "max_seq"],
)
def test_an_end_known_early_costs_no_extra_decode_call(
    max_tokens, max_seq, decodes, params
):
    """An end by `max_tokens` or by `max_seq` is known before the last
    token's value is: the slot is not in the next dispatch, and a
    request costs the decode calls it cost at lag 0 (n - 1 for n
    tokens; `benchmarks/server.py check` counts them)."""
    engine = _engine(params, max_seq=max_seq, page_size=8)
    calls = _count_decode_calls(engine)
    (out,) = engine.generate(
        [[1, 2, 3, 4, 5]], SamplingParams(max_tokens=max_tokens)
    )
    assert len(out) == decodes + 1
    stats = engine.stats()
    assert len(calls) == stats["decode_steps"] == decodes
    assert stats["slot_steps"] == decodes
    assert stats["overrun_slot_steps"] == 0
    assert engine._in_flight is None and not engine.has_unfinished()


def test_a_stop_token_costs_one_overrun_slot_step(params):
    """An end by a stop token is learnt a step late: the slot's one
    extra slot-step is computed and thrown away, nothing after the stop
    token is emitted, every page comes back, and the next request, in
    the freed slot and pages, reads what it reads alone."""
    prompt, other = [1, 2, 3], [9, 10, 11, 12]
    free = _engine(params, max_batch=1).generate(
        [prompt], SamplingParams(max_tokens=8)
    )[0]
    k = next(i for i in range(2, 8) if free[i] not in free[:i])
    (alone,) = _engine(params, max_batch=1).generate(
        [other], SamplingParams(max_tokens=6)
    )
    engine = _engine(params, max_batch=1)
    calls = _count_decode_calls(engine)
    rid = engine.add_request(
        prompt, SamplingParams(max_tokens=8, stop_token_ids=(free[k],)),
        stream=True,
    )
    streamed, finished = [], []
    while engine.has_unfinished():
        finished += engine.step()
        streamed += engine.drain_deltas().get(rid, [])
    assert finished[0]["tokens"] == streamed == free[:k]
    stats = engine.stats()
    # k decode steps reach the stop token; one more was in flight.
    assert len(calls) == stats["decode_steps"] == k + 1
    assert stats["overrun_slot_steps"] == 1
    assert engine.alloc.free_pages == engine.alloc.num_pages
    assert engine.generate([other], SamplingParams(max_tokens=6)) == [alone]


def test_abort_with_a_step_in_flight(params):
    """The step in flight still names the aborted request's slot: its
    token is dropped, its pages are back at once, and the other slot's
    stream is what it is alone."""
    sampling = SamplingParams(max_tokens=10)
    (alone,) = _engine(params).generate([PROMPTS[0]], sampling)
    engine = _engine(params)
    keep = engine.add_request(PROMPTS[0], sampling)
    drop = engine.add_request(PROMPTS[1], sampling, stream=True)
    engine.step()
    engine.step()
    assert engine._in_flight is not None
    assert drop in {r.request_id for r in engine._in_flight.slots.values()}
    assert engine.abort_request(drop)
    finished = []
    while engine.has_unfinished():
        finished += engine.step()
    assert [f["request_id"] for f in finished] == [keep]
    assert finished[0]["tokens"] == alone
    assert drop not in engine.drain_deltas()
    stats = engine.stats()
    assert stats["requests_aborted"] == 1 and stats["overrun_slot_steps"] == 0
    assert engine.alloc.free_pages == engine.alloc.num_pages


def test_a_preemption_reads_the_step_in_flight_back_first(params):
    """A victim's context has to hold its token in flight before it is
    requeued: the pool's exhaustion drains, by name, and the streams are
    a roomy pool's."""
    sampling = SamplingParams(max_tokens=20)
    prompts = [[1, 2, 3, 4, 5, 6, 7, 8], [9, 10, 11, 12, 13, 14]]
    tight = _engine(params, page_size=8, num_pages=4)
    outs = tight.generate(prompts, sampling)
    assert outs == _engine(params, page_size=8).generate(prompts, sampling)
    stats = tight.stats()
    assert stats["preemptions"] >= 1
    assert stats["pipeline_drains"]["preempt"] >= 1
    assert tight.alloc.free_pages == 4


@pytest.mark.parametrize(
    "cause,engine_kw,sampling",
    [
        ("speculate", {"speculate": 2}, SamplingParams(max_tokens=8)),
        ("host_sampled", {},
         SamplingParams(max_tokens=8, temperature=1.0, top_k=4)),
    ],
    ids=["speculate", "top_k"],
)
def test_a_step_that_needs_token_values_runs_at_lag_0(
    cause, engine_kw, sampling, params
):
    """Drafts come from the tokens emitted so far, and a top-k slot
    samples on the host: such a step reads the one in flight back
    before it is dispatched, and `pipeline_drains` says why."""
    engine = _engine(params, **engine_kw)
    (out,) = engine.generate([PROMPTS[2]], sampling)
    assert len(out) == 8
    stats = engine.stats()
    assert stats["decode_steps_in_flight"] == 0
    drains = stats["pipeline_drains"]
    assert drains[cause] == stats["decode_steps"] - 1 > 0
    assert sum(drains.values()) == drains[cause]


def test_the_last_step_in_flight_is_not_lost(params):
    """`has_unfinished` holds while a step is in flight, so the loop
    that drives step() reads the last one back: every streamed delta
    and every finished list arrive, and nothing is left on the device."""
    engine = _engine(params)
    sampling = SamplingParams(max_tokens=5)
    rids = [engine.add_request(p, sampling, stream=True) for p in PROMPTS[:2]]
    streamed = {rid: [] for rid in rids}
    finished = {}
    while engine.has_unfinished():
        for fin in engine.step():
            finished[fin["request_id"]] = fin["tokens"]
        for rid, toks in engine.drain_deltas().items():
            streamed[rid] += toks
    assert engine._in_flight is None
    assert streamed == finished and all(len(t) == 5 for t in finished.values())
    assert finished == dict(
        zip(rids, _engine(params).generate(PROMPTS[:2], sampling))
    )


def test_add_request_does_not_wait_for_a_device_step(params):
    """step() lets go of `_lock` while it waits for a decode program:
    a caller on the replica's event loop gets in during the wait."""
    engine = _engine(params)
    real, waiting, held = engine._fetch, threading.Event(), 1.0

    def slow_fetch(step):
        waiting.set()
        time.sleep(held)
        return real(step)

    engine._fetch = slow_fetch
    engine.add_request(PROMPTS[0], SamplingParams(max_tokens=3))
    stepper = threading.Thread(
        target=lambda: [engine.step() for _ in range(2)]
    )
    stepper.start()
    assert waiting.wait(30)
    began = time.perf_counter()
    rid = engine.add_request(PROMPTS[1], SamplingParams(max_tokens=2))
    waited = time.perf_counter() - began
    stepper.join(30)
    assert not stepper.is_alive()
    assert waited < 0.5 * held
    engine._fetch = real
    done = {}
    while engine.has_unfinished():
        done.update((f["request_id"], f["tokens"]) for f in engine.step())
    assert len(done) == 2 and len(done[rid]) == 2
