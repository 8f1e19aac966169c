"""Chunked prefill: long-prompt admission interleaved with decode.

Without chunking, one long prompt's admission runs its whole dense
prefill inside the step loop, stalling every in-flight decode for its
full duration. With ``prefill_chunk``, the engine prefills one
page-aligned chunk per step — decodes advance between chunks and the
prompt's first token lands after ceil(ctx_pages / chunk_pages) steps.

(reference capability: vLLM's chunked prefill, inherited by ray.llm
through engine_kwargs — python/ray/llm/_internal/serve/.)
"""

import jax
import pytest

from ray_tpu.llm.engine import LLMEngine, SamplingParams
from ray_tpu.models.llama import PRESETS, init_params

CFG = PRESETS["tiny"]


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.key(0), CFG)


def test_chunked_matches_single_shot(params):
    """Greedy token streams are identical with chunking on and off —
    chunking is mathematically exact (K/V at position i depend only on
    tokens <= i) and argmax absorbs fp reduction-order noise."""
    long_prompt = [(13 * i + 1) % CFG.vocab_size for i in range(70)]
    prompts = [[1, 2, 3], long_prompt, [9, 10, 11, 12]]
    sp = SamplingParams(max_tokens=6)
    single = LLMEngine(CFG, max_batch=3, max_seq=128, params=params,
                       kv="paged", page_size=16)
    chunked = LLMEngine(CFG, max_batch=3, max_seq=128, params=params,
                        kv="paged", page_size=16, prefill_chunk=32)
    assert single.generate(prompts, sp) == chunked.generate(prompts, sp)


def test_decode_advances_during_chunked_prefill(params):
    """While a long prompt prefills chunk by chunk, an already-active
    request gains one token per step — the stall chunking exists to
    remove — and the long prompt activates only after its last chunk."""
    eng = LLMEngine(CFG, max_batch=2, max_seq=128, params=params,
                    kv="paged", page_size=16, prefill_chunk=32)
    eng.add_request([1, 2, 3], SamplingParams(max_tokens=40))
    eng.step()  # admit the short request; it starts decoding
    short = next(iter(eng._active.values()))
    long_prompt = [(7 * i + 2) % CFG.vocab_size for i in range(70)]
    eng.add_request(long_prompt, SamplingParams(max_tokens=4))
    # 70 tokens -> ctx_pad 80 -> chunks of 32: 32 + 32 + 16 = 3 steps.
    for expect_active in (False, False, True):
        before = len(short.out_tokens)
        eng.step()
        assert len(short.out_tokens) == before + 1  # decode advanced
        assert (len(eng._active) == 2) == expect_active
    assert eng._prefilling is None


def test_abort_mid_chunked_prefill_frees_slot_and_pages(params):
    eng = LLMEngine(CFG, max_batch=1, max_seq=128, params=params,
                    kv="paged", page_size=16, prefill_chunk=32)
    rid = eng.add_request(
        [(3 * i) % CFG.vocab_size for i in range(70)],
        SamplingParams(max_tokens=4),
    )
    eng.step()  # first chunk only
    assert eng._prefilling is not None
    assert eng.abort_request(rid)
    assert eng._prefilling is None
    assert eng.alloc.free_pages == eng.alloc.num_pages
    assert len(eng._free) == 1
    assert not eng.has_unfinished()


def test_chunked_prefill_with_prefix_sharing(params):
    """Shared prefix pages + chunked rewrite stay consistent: outputs
    match the unchunked engine for requests sharing a 32-token head."""
    head = [(5 * i + 3) % CFG.vocab_size for i in range(48)]
    prompts = [head + [5, 6], head + [9]]
    sp = SamplingParams(max_tokens=5)
    plain = LLMEngine(CFG, max_batch=2, max_seq=128, params=params,
                      kv="paged", page_size=16)
    chunked = LLMEngine(CFG, max_batch=2, max_seq=128, params=params,
                        kv="paged", page_size=16, prefill_chunk=32)
    assert plain.generate(prompts, sp) == chunked.generate(prompts, sp)
    assert chunked.alloc.free_pages == chunked.alloc.num_pages


def test_short_prompts_skip_chunking(params):
    """Prompts at or under the chunk threshold use the single-shot
    path — no chunk state is ever created."""
    eng = LLMEngine(CFG, max_batch=1, max_seq=64, params=params,
                    kv="paged", page_size=16, prefill_chunk=32)
    eng.add_request([1, 2, 3], SamplingParams(max_tokens=8))
    eng.step()
    assert eng._prefilling is None and len(eng._active) == 1


def test_chunked_prefill_composes_with_speculation(params):
    """Chunked prefill + speculative decoding together must stay
    bit-identical to the plain engine on greedy streams (the two
    features share the step loop: chunk first, then verify-decode)."""
    long_prompt = (
        [7, 8, 9] * 20 + [7, 8]  # repetitive: drafts accept
    )
    prompts = [[1, 2, 3], long_prompt]
    sp = SamplingParams(max_tokens=8)
    plain = LLMEngine(CFG, max_batch=2, max_seq=128, params=params,
                      kv="paged", page_size=16)
    combo = LLMEngine(CFG, max_batch=2, max_seq=128, params=params,
                      kv="paged", page_size=16, prefill_chunk=32,
                      speculate=3)
    assert plain.generate(prompts, sp) == combo.generate(prompts, sp)


def test_chunked_prefill_through_serve(params):
    """engine_kwargs carry prefill_chunk+speculate through the serve
    deployment: a long-prompt SSE stream completes normally."""
    import json as _json
    import urllib.request

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm import build_llm_deployment

    ray_tpu.init(num_cpus=4)
    try:
        serve.run(
            build_llm_deployment(
                CFG,
                engine_kwargs={
                    "max_batch": 2,
                    "max_seq": 128,
                    "params": params,
                    "page_size": 16,
                    "prefill_chunk": 32,
                    "speculate": 3,
                },
            )
        )
        port = serve.start_http()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/", method="POST",
            data=_json.dumps(
                {"prompt": "ab" * 40, "max_tokens": 6, "stream": True}
            ).encode(),
            headers={
                "Accept": "text/event-stream",
                "Content-Type": "application/json",
            },
        )
        with urllib.request.urlopen(req, timeout=120) as r:
            frames = [ln.decode().strip() for ln in r if ln.strip()]
        assert frames[-1] == "data: [DONE]"
        assert len(frames) >= 2  # streamed at least one token delta
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
