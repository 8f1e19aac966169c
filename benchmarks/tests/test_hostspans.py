"""Host spans against device events, on made-up spans: the list ->
numbers half of ``benchmarks/hostspans.py`` and the three reducers that
read it."""

import json
import os

import pytest

from benchmarks import hostspans as hs
from benchmarks.hostspans import Span
from benchmarks.reducers import engine_stat, idle_by_span, span_stat
from benchmarks.traceread import OPS, PROGRAMS, Event

D = "/device:TPU:0"
EXEC, LOOP = "python/0", "python/1"


def op(start, dur):
    return Event(D, OPS, "fusion", start, dur, "fusion")


def prog(start, dur):
    return Event(D, PROGRAMS, "jit_paged_verify", start, dur)


def span(name, start, dur, thread=EXEC, **attrs):
    return Span(thread, name, float(start), float(dur), attrs)


def step(start, active, children=(), **attrs):
    """An ``engine:step`` of 10 s with the usual children inside."""
    attrs = {"max_batch": 4, "active": active, **attrs}
    return [span("engine:step", start, 10, **attrs), *children]


def two_steps():
    """Two steps, 0-10 and 12-22, on a chip that is busy 4-9 and 15-21
    in a window 0-22. Idle: 0-4, 9-15, 21-22 = 11 of 22."""
    events = [prog(0, 9), prog(15, 7), op(4, 5), op(15, 6)]
    spans = [
        *step(0, 2, [
            span("engine:admit", 0, 2, rid="a", queue_ms=30.0),
            span("engine:first_token", 1, 1, rid="a"),  # inside the admit
            span("engine:grow_tables", 2, 1),
            span("engine:decode_dispatch", 3, 2),
            span("engine:decode_sync", 5, 4),
            span("engine:emit", 9, 1),
        ], step=0),
        span("pump:deliver", 10.5, 1, LOOP, frames=2, finished=0),
        span("engine:add_request", 13, 2, LOOP, rid="b", lock_wait_ms=1500.0),
        *step(12, 3, [
            span("engine:admit", 12, 1, rid="b", queue_ms=10.0),
            span("engine:grow_tables", 13, 1),
            span("engine:decode_dispatch", 14, 1),
            span("engine:decode_sync", 15, 6),
            span("engine:emit", 21, 1),
        ], step=1),
    ]
    return {"events": events, "spans": spans, "counters": {}}


def test_idle_is_split_over_the_innermost_spans():
    ctx = two_steps()
    split = hs.idle_split(ctx)
    assert split.idle == pytest.approx(11.0)
    assert split.steps == 2
    # Step 0: idle 0-4 is the admit's own second (0-1), the first_token
    # nested in it (1-2), grow_tables (2-3) and a second of the dispatch
    # (3-4); 9-10 is the emit. Step 1: 12-15 is admit, grow_tables,
    # dispatch; 21-22 the emit.
    assert split.in_steps == pytest.approx({
        "engine:step": 0.0, "engine:admit": 2.0, "engine:first_token": 1.0,
        "engine:grow_tables": 2.0, "engine:decode_dispatch": 2.0,
        "engine:decode_sync": 0.0, "engine:emit": 2.0,
    })
    # Between the steps, 10-12: a second of it inside pump:deliver. The
    # add_request overlaps step 1 in time, on another thread, and takes
    # nothing from the step's spans.
    assert split.between == pytest.approx(2.0)
    assert split.between_by == pytest.approx(
        {"pump:deliver": 1.0, "engine:add_request": 0.0})
    assert split.seconds("*") == pytest.approx(11.0)


def test_idle_by_span_per_step_and_as_a_share():
    ctx = two_steps()
    prepare = ["engine:admit", "engine:prefill_chunk", "engine:grow_tables",
               "engine:decode_dispatch"]
    readback = ["engine:first_token", "engine:decode_sync", "engine:emit"]

    def read(spans, per="step"):
        return idle_by_span.reduce(ctx, spans=spans, per=per)

    assert read(prepare) == pytest.approx(3000.0)
    assert read(readback) == pytest.approx(1500.0)
    assert read(["between"]) == pytest.approx(1000.0)
    assert read("pump:deliver") == pytest.approx(500.0)
    assert read("*", per="idle") == pytest.approx(100.0)


def test_idle_outside_a_running_pump_belongs_to_nobody():
    # The second step found nothing in hand, only the queue: the engine
    # had drained, the pump had stopped, and the 2 s before it are
    # nobody's, of 11.
    ctx = two_steps()
    ctx["spans"] = [
        s._replace(attrs={**s.attrs, "active": 0, "queued": 1})
        if s.name == "engine:step" and s.start == 12 else s
        for s in ctx["spans"]
    ]
    share = idle_by_span.reduce(ctx, spans="*", per="idle")
    assert share == pytest.approx(100.0 * 9 / 11)
    assert idle_by_span.reduce(ctx, spans=["between"]) == pytest.approx(0.0)
    chunking = two_steps()
    chunking["spans"] = [
        s._replace(attrs={**s.attrs, "active": 0, "prefilling": 1})
        if s.name == "engine:step" and s.start == 12 else s
        for s in chunking["spans"]
    ]
    assert idle_by_span.reduce(
        chunking, spans="*", per="idle") == pytest.approx(100.0)
    # Idle time before the first step and after the last is nobody's too.
    late = two_steps()
    late["events"] += [prog(-5, 1), op(-5, 1), prog(30, 1), op(30, 1)]
    split = hs.idle_split(late)
    assert split.idle == pytest.approx(11.0 + 4.0 + 8.0)
    assert split.seconds("*") == pytest.approx(11.0)


def test_span_statistics():
    ctx = two_steps()
    assert span_stat.reduce(ctx, span="engine:admit", attr="queue_ms",
                            stat="median") == pytest.approx(20.0)
    assert span_stat.reduce(ctx, span="engine:admit", attr="queue_ms",
                            stat="p90") == pytest.approx(30.0)
    assert span_stat.reduce(ctx, span="engine:decode_sync",
                            stat="mean") == pytest.approx(5000.0)
    waits = span_stat.reduce(
        ctx, span=["engine:add_request", "engine:abort_request"],
        attr="lock_wait_ms", stat="sum_per_step")
    assert waits == pytest.approx(750.0)
    # A span without the attribute is left out, not counted as 0.
    assert span_stat.reduce(ctx, span="engine:emit", attr="queue_ms",
                            stat="median") is None


def test_occupancy_counts_only_steps_that_ran_a_decode():
    ctx = two_steps()
    # A step that admitted nothing and decoded nothing (an empty queue's
    # last turn) would pull the mean to 1/3 of the slots.
    ctx["spans"] += step(23, 0, step=2)
    occupancy = span_stat.reduce(
        ctx, span="engine:step", attr="active", over="max_batch",
        holding="engine:decode_dispatch", stat="mean", scale=100.0)
    assert occupancy == pytest.approx(100.0 * (2 / 4 + 3 / 4) / 2)


@pytest.mark.parametrize("ctx", [
    {"events": [], "spans": [], "counters": {}},
    {"events": two_steps()["events"], "spans": [], "counters": {}},
    {"events": [], "spans": two_steps()["spans"], "counters": {}},
], ids=["empty", "no-spans", "no-device-events"])
def test_an_empty_trace_gives_none(ctx):
    assert idle_by_span.reduce(ctx, spans=["between"]) is None
    assert idle_by_span.reduce(ctx, spans="*", per="idle") is None
    if not ctx["spans"]:
        assert span_stat.reduce(ctx, span="engine:admit", attr="queue_ms",
                                stat="median") is None
        assert span_stat.reduce(ctx, span="engine:add_request",
                                attr="lock_wait_ms",
                                stat="sum_per_step") is None
    assert engine_stat.reduce(ctx, key="init_s") is None


def test_engine_stat_reads_the_engines_own_counter():
    ctx = {"events": [], "counters": {"engine": {"init_s": 1.25}}}
    assert engine_stat.reduce(ctx, key="init_s") == 1.25
    assert engine_stat.reduce(ctx, key="absent") is None


def test_spans_of_another_runs_file_are_not_this_runs(tmp_path, monkeypatch):
    """Without ``ctx["spans"]`` the reader takes the newest trace file
    under the output directory, and nothing if its spans do not overlap
    the device events' window."""
    from benchmarks.runners import common

    monkeypatch.setattr(common, "OUT", str(tmp_path))
    ctx = {"events": two_steps()["events"]}
    assert hs.spans_of(ctx) == []  # no file at all
    old = tmp_path / "a" / "trace" / "old.xplane.pb"
    new = tmp_path / "b" / "trace" / "new.xplane.pb"
    for path, mtime in ((old, 100), (new, 200)):
        path.parent.mkdir(parents=True)
        path.write_bytes(b"")
        os.utime(path, (mtime, mtime))
    assert hs.newest_trace(str(tmp_path)) == str(new)
    spans = two_steps()["spans"]
    monkeypatch.setattr(hs, "_read_once", lambda path, mtime: tuple(spans))
    assert hs.spans_of(ctx) == spans
    shifted = tuple(s._replace(start=s.start + 1000) for s in spans)
    monkeypatch.setattr(hs, "_read_once", lambda path, mtime: shifted)
    assert hs.spans_of(ctx) == []


def test_every_new_metric_names_a_reducer_and_its_cells():
    """The metrics this reader feeds: each has its file, names one of
    the three reducers, and is listed for serving cells only."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        listing = json.load(f)
    serving = {c["name"] for c in listing["workloads"]
               if c["config"] == "mistral7b-serve1"}
    seen = 0
    for metric in listing["per_layer"]:
        path = os.path.join(root, "benchmarks", "layer_metrics",
                            f"{metric['name']}.json")
        with open(path) as f:
            spec = json.load(f)
        if spec["reducer"] not in ("idle_by_span", "span_stat", "engine_stat"):
            continue
        seen += 1
        assert set(metric["workloads"]) <= serving, metric["name"]
        reducer = {"idle_by_span": idle_by_span, "span_stat": span_stat,
                   "engine_stat": engine_stat}[spec["reducer"]]
        # The arguments are the reducer's own: it runs on made-up spans.
        ctx = two_steps()
        ctx["counters"] = {"engine": {"init_s": 2.0}}
        assert reducer.reduce(ctx, **spec["args"]) is not None, metric["name"]
    assert seen == 10
