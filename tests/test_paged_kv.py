"""Paged KV cache: memory-bound admission, prefix sharing, preemption,
on-device sampling.

(reference capability model: vLLM's paged attention + prefix caching +
recompute preemption, which ray.llm inherits through engine_kwargs —
python/ray/llm/_internal/serve/deployments/llm/vllm/vllm_models.py:234.)
"""

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
    assert paged_verify._cache_size() == before + 1
    outs = engine.generate(
        [[4, 5, 6], [7, 8]], SamplingParams(max_tokens=4, temperature=0.8)
    )
    assert [len(o) for o in outs] == [4, 4]
    assert paged_verify._cache_size() == before + 1


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
