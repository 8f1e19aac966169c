"""The engine loop's own account of a step: `jax.profiler.TraceAnnotation`
spans in `LLMEngine` and the `LLMServer` pump, read back from a profiler
trace by the benchmark's reader, and the counters `stats()` returns.

CPU, `tiny` model. One profiler session around two streamed requests (one
long enough to be prefilled in chunks) feeds every test of the spans."""

import asyncio
import os
import signal
import sys
import threading
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from ray_tpu.llm.engine import LLMEngine, SamplingParams  # noqa: E402

TIMEOUT_S = 180
DELAY_S = 0.05
LONG, SHORT = list(range(1, 81)), list(range(3, 13))
SPAN_NAMES = [
    "engine:step", "engine:prefill_chunk", "engine:admit",
    "engine:first_token", "engine:grow_tables", "engine:decode_dispatch",
    "engine:decode_sync", "engine:emit", "engine:add_request",
    "engine:abort_request", "pump:deliver",
]


@pytest.fixture(autouse=True)
def timeout():
    """Every test here ends or fails within TIMEOUT_S."""
    if threading.current_thread() is not threading.main_thread():
        yield
        return

    def alarm(signum, frame):
        raise TimeoutError(f"test exceeded {TIMEOUT_S}s")

    old = signal.signal(signal.SIGALRM, alarm)
    signal.alarm(TIMEOUT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(spans, stats): the host spans of one profiler session around two
    concurrent streams through `LLMServer`, and the engine's counters
    afterwards. The same traffic runs once before the session, so that
    nothing compiles inside it."""
    import jax

    from benchmarks import hostspans, traceread
    from ray_tpu.llm.serve_integration import LLMServer

    server = LLMServer("tiny", {
        "max_batch": 2, "page_size": 16, "num_pages": 32,
        "prefill_chunk": 32, "prefill_delay_s": DELAY_S,
    })

    async def one(prompt):
        return [d async for d in server.stream(prompt, max_tokens=6)]

    async def both():
        long = asyncio.ensure_future(one(LONG))
        await asyncio.sleep(0)  # the long request is ahead in the queue
        return await asyncio.gather(long, one(SHORT))

    async def main(trace_dir):
        await both()
        jax.profiler.start_trace(trace_dir)
        try:
            await both()
        finally:
            jax.profiler.stop_trace()

    trace_dir = str(tmp_path_factory.mktemp("trace"))
    asyncio.run(asyncio.wait_for(main(trace_dir), TIMEOUT_S))
    spans = hostspans.read_spans(traceread.find_trace_file(trace_dir))
    return spans, server.engine.stats()


def by_name(spans, name):
    return sorted((s for s in spans if s.name == name), key=lambda s: s.start)


@pytest.mark.parametrize("name", SPAN_NAMES)
def test_a_session_records_every_span(traced, name):
    assert by_name(traced[0], name), f"no {name} span in the trace"


def test_children_lie_inside_their_step(traced):
    from benchmarks import hostspans

    outside = {"engine:add_request", "engine:abort_request", "pump:deliver"}
    for span, in_step, own in hostspans.own_intervals(traced[0]):
        assert in_step == (span.name not in outside), span
    # A step's own instants (the lock, the gaps between its children)
    # are next to nothing: the children account for the step.
    steps = [(s, own) for s, _, own in hostspans.own_intervals(traced[0])
             if s.name == "engine:step"]
    assert sum(hi - lo for _, own in steps for lo, hi in own) < 0.05 * sum(
        s.dur for s, _ in steps
    )


def test_step_attributes(traced):
    steps = by_name(traced[0], "engine:step")
    counts = [s.attrs["step"] for s in steps]
    assert counts == list(range(counts[0], counts[0] + len(steps)))
    assert {s.attrs["max_batch"] for s in steps} == {2}
    # The first step's thread starts while the event loop adds the short
    # request: under load it may find only the long one.
    assert steps[0].attrs["queued"] in (1, 2) and steps[0].attrs["active"] == 0
    assert any(s.attrs["prefilling"] for s in steps)
    assert steps[-1].attrs["active"] == 2
    chunks = by_name(traced[0], "engine:prefill_chunk")
    assert [(c.attrs["start"], c.attrs["tokens"]) for c in chunks] == [
        (0, 32), (32, 32), (64, 16)
    ]
    emits = by_name(traced[0], "engine:emit")
    assert sum(e.attrs["finished"] for e in emits) == 2
    # 2 x 6 tokens, less the two that the prefills sampled.
    assert sum(e.attrs["tokens"] for e in emits) == 10
    delivered = by_name(traced[0], "pump:deliver")
    assert sum(d.attrs["finished"] for d in delivered) == 2
    assert sum(d.attrs["frames"] for d in delivered) >= 10


def test_rid_joins_a_requests_spans(traced):
    spans = traced[0]
    added = by_name(spans, "engine:add_request")
    assert [a.attrs["prompt_len"] for a in added] == [len(LONG), len(SHORT)]
    for a in added:
        rid = a.attrs["rid"]
        for name in ("engine:admit", "engine:first_token",
                     "engine:abort_request"):
            mine = [s for s in by_name(spans, name) if s.attrs["rid"] == rid]
            assert len(mine) == 1, (name, rid)
            assert mine[0].start > a.start
        assert a.attrs["lock_wait_ms"] >= 0
    long_rid = added[0].attrs["rid"]
    assert {c.attrs["rid"] for c in by_name(spans, "engine:prefill_chunk")} == {
        long_rid
    }


def test_queue_ms_counts_the_wait_behind_the_request_ahead(traced):
    admits = by_name(traced[0], "engine:admit")
    long, short = admits
    assert long.attrs["prompt_len"] == len(LONG)
    assert long.attrs["pages"] >= len(LONG) // 16
    assert long.attrs["pages_shared"] == 0
    # The short request sat in the queue through the long one's injected
    # prefill delay and its three chunks.
    assert short.attrs["queue_ms"] >= DELAY_S * 1e3
    assert long.attrs["queue_ms"] < short.attrs["queue_ms"]


def run_to_the_end(engine, prompts, max_tokens=5):
    for p in prompts:
        engine.add_request(p, SamplingParams(max_tokens=max_tokens))
    while engine.has_unfinished():
        engine.step()
    return engine.stats()


@pytest.mark.parametrize("kwargs", [
    {"kv": "paged", "page_size": 16, "prefill_chunk": 32},
    {"kv": "paged", "page_size": 16, "speculate": 2},
    {},
], ids=["paged-chunked", "paged-speculative", "paged-plain"])
def test_counters_are_consistent_at_drain(kwargs):
    engine = LLMEngine("tiny", max_batch=2, **kwargs)
    stats = run_to_the_end(engine, [LONG, SHORT, SHORT[:5]])
    assert stats["init_s"] > 0
    assert stats["admitted"] == stats["requests_submitted"] == 3
    assert stats["requests_finished"] == 3
    assert 0 < stats["decode_steps"] <= stats["steps"]
    assert stats["decode_steps"] <= stats["slot_steps"]
    assert stats["slot_steps"] <= stats["decode_steps"] * engine.max_batch
    # Three requests through two slots: the third waited for one.
    assert stats["queue_wait_s_sum"] > 0
    assert stats["lock_wait_s_sum"] >= 0
    assert engine.occupancy() == {
        "active": 0, "max_batch": 2,
        "pages_free": stats["pages_free"],
        "pages_total": stats["pages_total"],
    }


@pytest.mark.parametrize("speculate", [0, 2], ids=["k1", "k3"])
def test_attended_pages_equal_the_hand_count(speculate):
    """`attn_pages_live` / `attn_pages_table`: how much of the decode
    steps' block tables the attention had to read. One request at a
    time, so every step's length is known: of 5 tokens from a 30-token
    prompt the first is the prefill's, the others come of decode steps
    at positions 30, 31, 32, 33 (at K = 1), each attending the pages up
    to position + K - 1, of 16 tokens each."""
    engine = LLMEngine(
        "tiny", max_batch=2, max_seq=64, kv="paged", page_size=16,
        speculate=speculate,
    )
    width = 64 // 16
    assert engine.stats()["attn_pages_live"] == 0
    assert engine.stats()["attn_pages_table"] == 0
    for prompt_len, max_tokens in ((30, 5), (47, 4), (12, 9)):
        run_to_the_end(
            engine, [list(range(1, 1 + prompt_len))], max_tokens=max_tokens
        )
        # A vacated slot is at position 0 again: the decode program
        # attends one page for it (the dump page), not as many as its
        # last request had.
        assert engine._positions.tolist() == [0, 0]
    stats = engine.stats()
    assert stats["attn_pages_table"] == stats["decode_steps"] * 2 * width
    if speculate:
        # How many steps depends on which drafts were accepted.
        assert stats["decode_steps"] <= stats["attn_pages_live"]
        assert stats["attn_pages_live"] <= stats["decode_steps"] * width
        return
    assert stats["decode_steps"] == 4 + 3 + 8
    # Positions 30..33 (32 opens the third page), 47..49 (48 the
    # fourth), 12..19 (16 the second).
    assert stats["attn_pages_live"] == (
        (2 + 2 + 3 + 3) + (3 + 4 + 4) + (4 * 1 + 4 * 2)
    )


def test_a_preempted_request_is_admitted_once():
    # A pool too small for both requests' growth: one is preempted and
    # prefilled again, which is neither a second admission nor more
    # queue wait.
    engine = LLMEngine("tiny", max_batch=2, page_size=16, num_pages=3)
    stats = run_to_the_end(engine, [list(range(1, 15)), list(range(2, 16))],
                           max_tokens=12)
    assert stats["preemptions"] >= 1
    assert stats["admitted"] == stats["requests_submitted"] == 2


def test_submit_is_stamped_before_the_wait_for_the_lock():
    """`add_request` on the event loop waits for the lock that `step()`
    holds; the request's queue_s and ttft_s count that wait, and the
    engine sums it."""
    engine = LLMEngine("tiny", max_batch=2, page_size=16)
    held = 0.2
    taken = threading.Event()

    def hold():
        with engine._lock:
            taken.set()
            time.sleep(held)

    holder = threading.Thread(target=hold)
    holder.start()
    assert taken.wait(10)
    engine.add_request(SHORT, SamplingParams(max_tokens=2))
    holder.join(10)
    assert not holder.is_alive()
    assert engine.stats()["lock_wait_s_sum"] >= 0.5 * held
    finished = []
    while engine.has_unfinished():
        finished += engine.step()
    timing = finished[0]["timing"]
    assert timing["queue_s"] >= 0.5 * held
    assert timing["ttft_s"] >= timing["queue_s"]
    assert engine.stats()["queue_wait_s_sum"] == pytest.approx(
        timing["queue_s"]
    )
