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
# Each call that puts a program on the device, and the span it lies in.
LAUNCH_PARENTS = {
    "launch:decode": "engine:decode_dispatch",
    "launch:prefill": "engine:admit",
    "launch:prefill_chunk": "engine:prefill_chunk",
    "launch:key_block": "engine:decode_dispatch",
    "launch:first_token_row": "engine:first_token",
}


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
    """(spans, stats, launches, calls): the host spans of one profiler
    session around two concurrent streams through `LLMServer`, the
    engine's counters afterwards, the session's `launch:*` spans, and
    how often each program was called inside it (a tap on each). The
    same traffic runs once before the session, so that nothing compiles
    inside it."""
    import jax

    from benchmarks import hostspans, traceread
    from benchmarks.reducers import idle_by_enqueue
    from ray_tpu.llm import engine as engine_mod
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

    calls = dict.fromkeys(LAUNCH_PARENTS, 0)

    def tap(holder, attr, name):
        fn = getattr(holder, attr)

        def tapped(*args, **kw):
            calls[name] += 1
            return fn(*args, **kw)

        setattr(holder, attr, tapped)
        return fn

    async def main(trace_dir):
        await both()
        eng = server.engine
        eng._keys.clear()  # the session's first decode step makes a block
        tap(eng, "_decode_paged", "launch:decode")
        tap(eng, "_prefill_paged", "launch:prefill")
        tap(eng, "_prefill_chunk_fn", "launch:prefill_chunk")
        key_block = tap(engine_mod, "_key_block", "launch:key_block")
        logits_row = tap(engine_mod, "_logits_row", "launch:first_token_row")
        jax.profiler.start_trace(trace_dir)
        try:
            await both()
        finally:
            jax.profiler.stop_trace()
            engine_mod._key_block = key_block
            engine_mod._logits_row = logits_row

    trace_dir = str(tmp_path_factory.mktemp("trace"))
    asyncio.run(asyncio.wait_for(main(trace_dir), TIMEOUT_S))
    path = traceread.find_trace_file(trace_dir)
    spans = hostspans.read_spans(path)
    launches = idle_by_enqueue.read_launches(path)
    return spans, server.engine.stats(), launches, calls


def by_name(spans, name):
    return sorted((s for s in spans if s.name == name), key=lambda s: s.start)


@pytest.mark.parametrize("name", SPAN_NAMES)
def test_a_session_records_every_span(traced, name):
    assert by_name(traced[0], name), f"no {name} span in the trace"


@pytest.mark.parametrize("name", sorted(LAUNCH_PARENTS))
def test_a_launch_is_a_span_inside_its_parent(traced, name):
    """Every call that puts a program on the device lies in one
    `launch:*` span, as many spans as calls, each inside the phase that
    makes the call and on its thread."""
    spans, _, launches, calls = traced
    mine = by_name(launches, name)
    assert mine, f"no {name} span in the trace"
    assert len(mine) == calls[name]
    parents = by_name(spans, LAUNCH_PARENTS[name])
    for launch in mine:
        assert any(
            p.thread == launch.thread
            and p.start <= launch.start and launch.end <= p.end
            for p in parents
        ), launch
    if name == "launch:decode":
        assert {s.attrs["ahead"] for s in mine} == {0, 1}
        assert {s.attrs["starved"] for s in mine} <= {0, 1}
    if name in ("launch:prefill", "launch:prefill_chunk",
                "launch:first_token_row"):
        rids = {a.attrs["rid"] for a in by_name(spans, "engine:add_request")}
        assert {s.attrs["rid"] for s in mine} <= rids


def test_launches_are_the_programs_called(traced):
    _, _, launches, calls = traced
    assert {s.name for s in launches} == set(LAUNCH_PARENTS)
    assert len(launches) == sum(calls.values())
    # 3 chunks of the long prompt, the short one whole, a row each.
    assert calls["launch:prefill_chunk"] == 3
    assert calls["launch:prefill"] == 1
    assert calls["launch:first_token_row"] == 2
    assert calls["launch:key_block"] == 1


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
    # How long the step waited for `_lock` and how long its thread ran:
    # on every step, neither more than the step.
    for s in steps:
        assert 0 <= s.attrs["lock_wait_ms"] <= s.dur * 1e3 + 1e-3
        assert 0 <= s.attrs["cpu_ms"]
    assert sum(s.attrs["cpu_ms"] for s in steps) > 0
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


def host_seconds(stats):
    return {k: v for k, v in stats.items() if k.startswith("host_s_sum.")}


def test_host_phases_add_up_to_the_step():
    """`host_s_sum.*` are OWN seconds: with the step's wait for `_lock`
    they are `step_s_sum`, after whole and chunked prefills, decode
    steps and a preemption, with no profiler session."""
    engine = LLMEngine(
        "tiny", max_batch=2, page_size=16, num_pages=4, prefill_chunk=16
    )
    stats = run_to_the_end(
        engine, [list(range(1, 21)), list(range(2, 16))], max_tokens=14
    )
    assert stats["preemptions"] >= 1 and stats["prefill_chunks"] >= 2
    own = host_seconds(stats)
    assert set(own) == {
        f"host_s_sum.{phase}" for phase in (
            "step", "admit", "prefill_chunk", "first_token", "grow_tables",
            "decode_dispatch", "decode_sync", "emit", "launch", "readback",
        )
    }
    assert all(v >= 0 for v in own.values()), own
    for phase in ("admit", "prefill_chunk", "first_token", "grow_tables",
                  "decode_dispatch", "decode_sync", "emit", "launch"):
        assert own[f"host_s_sum.{phase}"] > 0, phase
    assert sum(own.values()) + stats["step_lock_wait_s_sum"] == pytest.approx(
        stats["step_s_sum"], rel=0.01
    )
    assert 0 < stats["step_cpu_s_sum"]
    assert stats["step_lock_wait_s_sum"] < 0.01 * stats["step_s_sum"]
    assert not engine._open_phases


def test_a_fold_of_the_expert_counters_is_a_readback():
    """The serving object's `fold` from `_account` (every 512 programs)
    lies in `readback:moe_counts`, whose own seconds go to `host_s_sum.readback`;
    from `stats()` it is nobody's phase."""
    from ray_tpu.models.nemotron_h import NEMOTRON_H_PRESETS

    engine = LLMEngine(
        NEMOTRON_H_PRESETS["nemotron_h_tiny"], max_batch=2, page_size=16
    )
    engine.add_request(SHORT, SamplingParams(max_tokens=3))
    engine.step()
    assert engine.serving._backlog
    engine.serving._backlog *= 512
    engine.step()
    assert len(engine.serving._backlog) < 512
    assert engine._stats["host_s_sum.readback"] > 0
    booked = engine._stats["host_s_sum.readback"]
    while engine.has_unfinished():
        engine.step()
    assert engine.stats()["host_s_sum.readback"] == booked


@pytest.mark.parametrize("ready", [False, True], ids=["never", "always"])
def test_starved_counts_the_steps_that_found_the_device_done(
    monkeypatch, ready
):
    """`decode_steps_starved`: of the in-flight decode dispatches of
    steps that launched no prefill first (`decode_steps_alone`), those
    that found the step in flight complete. A step that launched a chunk
    counts in neither, whatever `is_ready()` says."""
    import jax.numpy as jnp

    monkeypatch.setattr(
        type(jnp.zeros(1)), "is_ready", lambda self: ready, raising=True
    )
    engine = LLMEngine("tiny", max_batch=2, page_size=16, prefill_chunk=16)
    seen = {"launched": False, "alone": 0, "with_prefill": 0}

    def tap(attr, prefill):
        fn = getattr(engine, attr)

        def tapped(*args, **kw):
            if prefill:
                seen["launched"] = True
            elif engine._in_flight is not None:
                seen["with_prefill" if seen["launched"] else "alone"] += 1
            return fn(*args, **kw)

        setattr(engine, attr, tapped)

    tap("_prefill_paged", True)
    tap("_prefill_chunk_fn", True)
    tap("_decode_paged", False)
    engine.add_request(SHORT, SamplingParams(max_tokens=24))
    steps = 0
    while engine.has_unfinished():
        seen["launched"] = False
        engine.step()
        steps += 1
        if steps == 4:  # in chunks, under the short request's decode
            engine.add_request(LONG, SamplingParams(max_tokens=4))
    stats = engine.stats()
    assert seen["with_prefill"] >= 2 and seen["alone"] >= 8
    assert stats["decode_steps_alone"] == seen["alone"]
    assert stats["decode_steps_in_flight"] == (
        seen["alone"] + seen["with_prefill"]
    )
    assert stats["decode_steps_starved"] == (seen["alone"] if ready else 0)
    assert stats["decode_starved_pct"] == (100.0 if ready else 0.0)


def test_between_counts_only_a_running_pump():
    """`between_s_sum`: from one step's return to the next one's entry,
    where the later step found work in hand. Across a drained engine it
    does not grow."""
    engine = LLMEngine("tiny", max_batch=2, page_size=16)
    engine.add_request(SHORT, SamplingParams(max_tokens=4))
    engine.step()
    time.sleep(0.2)  # a slow pump: the request is in hand
    while engine.has_unfinished():
        engine.step()
    running = engine.stats()["between_s_sum"]
    assert running >= 0.2
    time.sleep(1.0)  # drained: nobody's
    stats = run_to_the_end(engine, [SHORT], max_tokens=4)
    assert stats["between_s_sum"] - running < 0.5
