"""The list -> numbers half of the trace reduction, on made-up events."""

import pytest

from benchmarks import traceread as tr
from benchmarks.reducers import (
    collective_time, idle_share, op_time_share, program_time,
)
from benchmarks.traceread import OPS, PROGRAMS, Event

D = "/device:TPU:0"


def op(name, start, dur, device=D):
    return Event(device, OPS, name, start, dur, name)


def prog(name, start, dur, device=D):
    return Event(device, PROGRAMS, name, start, dur, name)


def test_union_and_subtract():
    assert tr.union([(0, 2), (1, 3), (5, 6), (6, 7)]) == [(0, 3), (5, 7)]
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6)]) == [(0, 1), (2, 4), (6, 10)]
    assert tr.subtract([(0, 2), (3, 5)], [(1, 4)]) == [(0, 1), (4, 5)]


def test_idle_share_with_overlapping_intervals():
    # Window 0..10 from the programs; ops cover 0-3 (two overlapping),
    # 4-5 and 8-10: busy 6 of 10.
    events = [
        prog("jit_step", 0, 5), prog("jit_step", 8, 2),
        op("a", 0, 2), op("b", 1, 2), op("c", 4, 1), op("d", 8, 2),
    ]
    busy, window = tr.busy_and_window(events)
    assert (busy, window) == (pytest.approx(6.0), pytest.approx(10.0))
    assert idle_share.reduce({"events": events}) == pytest.approx(40.0)


def test_idle_share_is_averaged_over_chips():
    events = [prog("p", 0, 10), op("a", 0, 10),
              prog("p", 0, 10, "/device:TPU:1"), op("a", 0, 5, "/device:TPU:1")]
    assert idle_share.reduce({"events": events}) == pytest.approx(25.0)


def test_self_time_of_a_container():
    # A scan's while (0-10) holds two body ops; its own time is 10 - 7.
    events = [prog("p", 0, 10), op("while.1", 0, 10),
              op("fusion.1", 1, 3), op("flash_custom-call.2", 5, 4)]
    own = {e.name: t for e, t in tr.self_times(tr.select(events, D, OPS))}
    assert own == {"while.1": pytest.approx(3.0), "fusion.1": 3.0,
                   "flash_custom-call.2": 4.0}
    # Busy counts only operations that nest nothing: 3 + 4.
    assert tr.busy_and_window(events)[0] == pytest.approx(7.0)
    share = op_time_share.reduce({"events": events}, match="flash", over="busy")
    assert share == pytest.approx(100 * 4 / 7)


def test_gaps_are_named_by_the_programs_around_them():
    events = [
        prog("jit_a", 0, 4), op("x", 0, 1), op("y", 2, 2),   # 1s inside a
        prog("jit_b", 6, 2), op("z", 6, 2),                  # 2s a -> b
        prog("jit_a", 8.5, 1.5), op("x", 8.5, 1.5),          # 0.5s b -> a
    ]
    assert dict(map(tuple, tr.idle_gaps(events))) == {
        "jit_a -> jit_b": pytest.approx(2.0),
        "inside jit_a": pytest.approx(1.0),
        "jit_b -> jit_a": pytest.approx(0.5),
    }


def test_exposed_collective_subtracts_overlapped_compute():
    events = [
        prog("jit_step", 0, 10),
        op("all-gather-start.1", 0, 0.1),   # async: under way 0..4
        op("fusion.1", 0.1, 2.9),           # compute hides it until 3
        op("all-gather-done.1", 3, 1),      # the exposed wait
        op("fusion.2", 4, 4),
        op("reduce-scatter.3", 8, 2),       # synchronous: all exposed
    ]
    ctx = {"events": events}
    assert collective_time.reduce(ctx) == pytest.approx(60.0)
    assert collective_time.reduce(ctx, exposed=True) == pytest.approx(31.0)


def test_collective_kinds_as_a_tpu_trace_names_them():
    gather = ("%all-gather.331 = bf16[4096,32768]{1,0} all-gather(bf16[1024,32768]"
              "{1,0} %p), channel_id=54, dimensions={0}")
    scatter = ("%fusion.381 = bf16[14336,1024]{1,0} fusion(bf16[14336,4096]{1,0} "
               "%g), kind=kCustom, calls=%all-reduce-scatter.1.clone.clone")
    consumer = ("%fusion.317 = bf16[2,1024]{1,0} fusion(bf16[4096,32768]{1,0} "
                "%all-gather.331, s32[] %i), kind=kOutput, calls=%fused_computation.3")
    done = "%async-collective-done.6 = bf16[4096,4096]{0,1} async-collective-done(%s)"
    assert tr.collective_kind(gather) == "all-gather"
    assert tr.collective_kind(scatter) == "reduce-scatter"
    assert tr.collective_kind(done) == "async-collective"
    assert tr.collective_kind(consumer) is None
    assert tr.collective_kind("fusion.2") is None


def test_program_times():
    events = [prog("jit_paged_verify", t, 0.03) for t in (0, 0.04, 0.08, 1.0)]
    events += [prog("jit_paged_prefill", 0.12, 0.02), op("x", 0, 1.03)]
    ctx = {"events": events}
    kw = {"match": "paged_verify"}
    assert program_time.reduce(ctx, stat="median_device_ms", **kw) == pytest.approx(30)
    # The 0.92 s pause is no step: left out by max_gap_ms.
    assert program_time.reduce(
        ctx, stat="median_start_to_start_ms", max_gap_ms=500, **kw
    ) == pytest.approx(40)
    assert program_time.reduce(
        ctx, match="paged_prefill", stat="window_share_pct"
    ) == pytest.approx(100 * 0.02 / 1.03)
    assert program_time.reduce(ctx, match="absent", stat="median_device_ms") is None


def test_short_op_name():
    text = ("%convert_element_type.157 = bf16[32768,4096]{1,0:T(8,128)(2,1)} "
            "convert(f32[32768,4096]{1,0:T(8,128)} %p.1), metadata={}")
    assert tr.short_op_name(text) == (
        "convert_element_type convert bf16[32768,4096]"
    )
    assert tr.short_op_name("dot_general.1") == "dot_general"
    pair = ("%fusion.3 = (f32[8]{0}, /*index=1*/bf16[2,4]{1,0}) fusion(f32[8]{0} "
            "%a), kind=kLoop")
    assert tr.short_op_name(pair) == "fusion fusion (f32[8], bf16[2,4])"


def test_nothing_to_read_returns_none():
    ctx = {"events": []}
    assert idle_share.reduce(ctx) is None
    assert collective_time.reduce(ctx) is None
    assert op_time_share.reduce(ctx, match="x") is None
