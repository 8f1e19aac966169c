"""Device-idle time by who the chip waited for, on made-up spans and
events: ``reducers/idle_by_enqueue.py`` (the pairing of launches with
programs and the three names) and ``reducers/engine_ratio.py``."""

import json
import os

import pytest

from benchmarks.hostspans import Span
from benchmarks.reducers import engine_ratio, idle_by_enqueue
from benchmarks.traceread import OPS, PROGRAMS, Event

D = "/device:TPU:0"
EXEC = "python/0"
KINDS = ("launch", "host", "empty")


def program(start, dur, name="jit_hybrid_decode"):
    """A program that holds the chip for all of its length."""
    return [Event(D, PROGRAMS, name, start, dur),
            Event(D, OPS, "fusion", start, dur, "fusion")]


def launch(start, dur, name="launch:decode", **attrs):
    return Span(EXEC, name, float(start), float(dur), attrs)


def step(start, dur, active, **attrs):
    return Span(EXEC, "engine:step", float(start), float(dur),
                {"active": active, "prefilling": 0, **attrs})


def ctx_of(programs, launches, steps):
    return {"events": [e for p in programs for e in p], "launches": launches,
            "spans": steps, "counters": {}}


def seconds(ctx):
    split = idle_by_enqueue.idle_split(ctx)
    return {kind: getattr(split, kind) for kind in KINDS}


def running(programs, launches):
    """One pump that runs through the whole window: two steps, the later
    of which found a request in hand."""
    return ctx_of(programs, launches, [step(0, 50, 1), step(50, 50, 1)])


def test_a_launch_that_ended_under_the_program_before_names_the_whole_gap():
    # Programs 0-10 and 14-20; the second was launched 6-8, under the
    # first: the chip stood 10-14 with the launch made.
    ctx = running([program(0, 10), program(14, 6)],
                  [launch(-2, 1), launch(6, 2)])
    assert seconds(ctx) == pytest.approx({"launch": 4, "host": 0, "empty": 0})


def test_a_launch_that_ended_inside_the_gap_splits_it_at_its_end():
    # The launch 9-12 ends two seconds into the gap 10-14: until then
    # the host had not launched, after it the launch had not begun.
    ctx = running([program(0, 10), program(14, 6)],
                  [launch(-2, 1), launch(9, 3)])
    assert seconds(ctx) == pytest.approx({"launch": 2, "host": 2, "empty": 0})


def test_a_program_that_began_under_its_launch_leaves_the_gap_to_the_host():
    ctx = running([program(0, 10), program(14, 6)],
                  [launch(-2, 1), launch(12, 5)])
    assert seconds(ctx) == pytest.approx({"launch": 0, "host": 4, "empty": 0})
    # Of the host's four seconds, the two since 12 it was in the call.
    assert idle_by_enqueue.idle_split(ctx).call == pytest.approx(2.0)
    assert idle_by_enqueue.reduce(ctx, "call", per="idle") == pytest.approx(50)


def test_an_unpaired_program_is_the_hosts():
    # The trace's first launch span is at 16: the programs at 0 and 14
    # began before it, were launched before the trace began and are set
    # aside; the gap in front of the second has no launch to its name.
    ctx = running([program(0, 10), program(14, 6), program(24, 6)],
                  [launch(16, 2)])
    split = idle_by_enqueue.idle_split(ctx)
    assert split.aside == 2
    # 10-14 the host's; 20-24 the launch's, made 16-18.
    assert seconds(ctx) == pytest.approx({"launch": 4, "host": 4, "empty": 0})


def test_programs_queued_at_the_start_are_set_aside_by_order():
    # The program at 12 began after the trace's first launch span
    # (11-12) did, but was launched before the trace began, behind the
    # one at 0: paired with that launch it would leave the chunk at 13
    # beginning before ITS launch (20-21). Two programs are set aside.
    programs = [program(0, 12)[0], program(12, 1)[0],
                program(13, 9, "jit_chunk")[0], program(30, 5)[0]]
    launches = [launch(11, 1, "launch:prefill_chunk"), launch(20, 1)]
    pairs, aside = idle_by_enqueue.pair(launches, programs)
    assert aside == 2
    assert [(l.name, p.name, p.start) for l, p in pairs] == [
        ("launch:prefill_chunk", "jit_chunk", 13),
        ("launch:decode", "jit_hybrid_decode", 30),
    ]
    # As few as do: with nothing beginning before its launch, none.
    assert idle_by_enqueue.pair(launches[1:], programs[3:])[1] == 0


def test_a_few_programs_may_begin_before_their_launch():
    # The file's two clocks lie apart: one pair in ten reads as if the
    # program began before its launch did. No program is set aside for
    # that; with one launch too many in front, every pair reads so.
    programs = [program(10 * k, 4)[0] for k in range(10)]
    launches = [launch(10 * k - 3, 2) for k in range(10)]
    launches[4] = launch(40.5, 2)
    pairs, aside = idle_by_enqueue.pair(launches, programs)
    assert aside == 0 and len(pairs) == 10
    assert idle_by_enqueue.pair(launches[1:], programs)[1] == 1


def sync(start, dur):
    return Span(EXEC, "engine:decode_sync", float(start), float(dur), {})


def skewed(by):
    """Ten decode steps of 4 s, one every 10 s, each launched 3 s before
    it begins (the call returns a second later) and read back 1 s after
    it ends, on a device whose clock is `by` seconds behind the host's."""
    programs = [program(10 * k - by, 4) for k in range(20)]
    launches = [launch(10 * k - 3, 1) for k in range(20)]
    reads = [sync(10 * k + 4.5, 0.5) for k in range(20)]
    steps = [step(10 * k - 4, 10, 1) for k in range(20)]
    return ctx_of(programs, launches, steps + reads)


@pytest.mark.parametrize("by", [0.0, 1.5, -0.75])
def test_the_devices_clock_is_set_between_what_cannot_be(by):
    # On one clock a program begins 3 s after its launch did and its
    # read-back returns 1 s after it ended: the clocks can lie -3 to +1
    # apart, and the middle, -1, is what is taken (the two latencies as
    # equal). A device clock that is `by` behind is moved by `by` more.
    split = idle_by_enqueue.idle_split(skewed(by))
    assert split.aside == 0
    assert split.offset == pytest.approx(by - 1.0)
    # Whatever the file's clocks, the same split: of the 6 idle seconds
    # before a program, moved to begin 2 s after its launch did, the last
    # is the launch's (the call had returned), the others the host's.
    assert split.idle == pytest.approx(19 * 6.0)
    assert split.launch == pytest.approx(19 * 1.0)
    assert split.host == pytest.approx(19 * 5.0)
    assert split.empty == pytest.approx(0.0, abs=1e-9)


def test_without_read_backs_no_program_begins_before_its_launch():
    # Ten programs 10 s apart, launched 3 s ahead; one reads as begun
    # half a second before its launch: the device's clock is moved by
    # that half second and no more.
    programs = [program(10 * k, 4) for k in range(10)]
    launches = [launch(10 * k - 3, 2) for k in range(10)]
    launches[4] = launch(40.5, 2)
    ctx = ctx_of(programs, launches, [step(-5, 50, 1), step(45, 50, 1)])
    assert idle_by_enqueue.idle_split(ctx).offset == pytest.approx(0.5)


def test_a_gap_before_a_step_that_found_nothing_is_empty():
    # Steps 0-10 and 30-40; the second found nothing in hand: 10-30 the
    # engine had drained. Idle 6-32: 6-10 in the first step (host),
    # 10-30 empty, 30-31 the host's in the second step, 31-32 the
    # launch's (made 30-31).
    ctx = ctx_of([program(0, 6), program(32, 6)],
                 [launch(-2, 1), launch(30, 1)],
                 [step(0, 10, 1), step(30, 10, 0, queued=1)])
    assert seconds(ctx) == pytest.approx({"launch": 1, "host": 5, "empty": 20})


def test_the_three_kinds_are_all_of_the_idle_time():
    ctx = ctx_of(
        [program(0, 6), program(9, 2), program(32, 6), program(45, 1)],
        [launch(-2, 1), launch(7, 1), launch(30, 1), launch(41, 2)],
        [step(0, 10, 1), step(12, 3, 1), step(30, 10, 0), step(40, 6, 1)],
    )
    split = idle_by_enqueue.idle_split(ctx)
    assert split.idle == pytest.approx(46 - 6 - 2 - 6 - 1)
    assert split.launch + split.host + split.empty == pytest.approx(split.idle)
    assert (split.steps, split.launches, split.programs) == (4, 3, 4)
    shares = [idle_by_enqueue.reduce(ctx, kind, per="idle") for kind in KINDS]
    assert sum(shares) == pytest.approx(100.0)
    assert idle_by_enqueue.reduce(ctx, "host", per="step") == pytest.approx(
        1e3 * split.host / 4
    )
    with pytest.raises(ValueError):
        idle_by_enqueue.reduce(ctx, "nobody")


def test_a_trace_without_launch_spans_reads_nothing():
    # The parent's program: steps and programs, no launch:* span.
    ctx = running([program(0, 10), program(14, 6)], [])
    for kind in KINDS:
        assert idle_by_enqueue.reduce(ctx, kind) is None
    assert idle_by_enqueue.reduce(
        {"events": [], "spans": [], "launches": [], "counters": {}}, "host"
    ) is None


# ----------------------------------------------------------- engine_ratio
def engine(traced, **life):
    return {"counters": {"engine": {**life, "traced": traced}}}


def test_engine_ratio_reads_the_traced_counters():
    ctx = engine({"a": 2.0, "b": 1.0, "steps": 4}, a=100.0, b=100.0, steps=5)
    assert engine_ratio.reduce(ctx, ["a", "b"], ["steps"], scale=1e3) == 750.0


@pytest.mark.parametrize("traced_only, want", [(True, None), (False, 25.0)])
def test_engine_ratio_without_a_snapshot(traced_only, want):
    ctx = engine(None, starved=1, alone=4)
    got = engine_ratio.reduce(ctx, ["starved"], ["alone"], scale=100.0,
                              traced_only=traced_only)
    assert got == want


def test_engine_ratio_reads_nothing_from_a_program_without_the_counters():
    # The parent's side: a snapshot, none of the new keys; no engine at
    # all (a training cell); a window without one such step.
    assert engine_ratio.reduce(engine({"steps": 4}), ["a"], ["steps"]) is None
    assert engine_ratio.reduce({"counters": {}}, ["a"], ["steps"]) is None
    assert engine_ratio.reduce(
        engine({"starved": 0, "alone": 0}), ["starved"], ["alone"]
    ) is None


# ------------------------------------------------------------- the entries
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))
NEW = {
    "idle_host_late_ms_per_step.family", "idle_host_late_ms_per_step.chat",
    "idle_launch_ms_per_step.family", "idle_launch_ms_per_step.chat",
    "idle_empty_pct.chat", "host_idle_ms_per_step.between.family",
    "host_idle_ms_per_step.prepare.family",
    "host_idle_ms_per_step.readback.family", "idle_attributed_pct.family",
    "host_work_ms_per_step.family", "host_wait_ms_per_step.family",
    "decode_starved_pct.family", "host_cpu_share_pct.family",
    "decode_starved_pct.chat",
}


def listed(path):
    with open(os.path.join(ROOT, path)) as f:
        return {m["name"]: m for m in json.load(f)["per_layer"]}


def spec_of(name):
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                           f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(NEW))
def test_an_entry_names_its_reducer_and_its_cells(name):
    entry = listed("BENCHMARK.json")[name]
    spec = spec_of(name)
    assert os.path.exists(os.path.join(
        ROOT, "benchmarks", "reducers", f"{spec['reducer']}.py"))
    chat = name.endswith(".chat")
    assert entry["moves"] == ("itl_p90_ms" if chat else "serve_tokens_per_s")
    if chat:
        assert entry["workloads"] == ["chat-open"]
    else:
        # `serve_family`'s four cells, and `nemotron-reason-32` where the
        # reading needs no snapshot of the counters around the trace
        # (`server_model.py` takes none).
        family = ["pangu-longdoc-16", "granite-longdoc-16",
                  "qwen3next-longdoc-16", "laguna-longdoc-16"]
        needs_snapshot = spec.get("args", {}).get("traced_only", False)
        assert entry["workloads"] == (
            family if needs_snapshot else ["nemotron-reason-32", *family]
        )
    # The rehearsal listing carries the same entry for its tiny cells.
    tiny = listed("benchmarks/tests/rehearsal-launch.json")[name]
    assert {k: tiny[k] for k in tiny if k != "workloads"} == {
        k: entry[k] for k in entry if k != "workloads"
    }


def test_the_family_entries_read_what_the_chat_entries_read():
    for phase in ("between", "prepare", "readback"):
        assert spec_of(f"host_idle_ms_per_step.{phase}.family")["args"] == (
            spec_of(f"host_idle_ms_per_step.{phase}.chat")["args"]
        )
    assert spec_of("idle_attributed_pct.family")["args"] == (
        spec_of("idle_attributed_pct.chat")["args"]
    )
    # What the host does and what it waits for are all of the step.
    work = spec_of("host_work_ms_per_step.family")["args"]["num"]
    wait = spec_of("host_wait_ms_per_step.family")["args"]["num"]
    assert not set(work) & set(wait)
    from ray_tpu.llm import engine as engine_mod

    assert set(work) | set(wait) == {
        *(f"host_s_sum.{phase}" for phase in engine_mod._HOST_PHASES),
        "between_s_sum", "step_lock_wait_s_sum",
    }
    assert spec_of("host_cpu_share_pct.family")["args"]["den"] == [
        k for k in work if k != "between_s_sum"
    ]
