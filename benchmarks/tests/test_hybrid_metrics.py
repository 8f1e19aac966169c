"""The reducers behind the per-layer metrics of the hybrid serving cell,
on made-up events and counters: an instruction is looked up in the text
of the program it ran in (two programs both have a ``fusion.1``), and a
share of the HBM peak counts the bytes of the executions the trace
holds whole; the bytes functions against counts made by hand."""

import json
import os

import pytest

from benchmarks.models import nemotron_h
from benchmarks.reducers import hbm_share, program_scope_share
from benchmarks.traceread import OPS, PROGRAMS, Event

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
D = "/device:TPU:0"
TPU = {"platform": "tpu", "kind": "TPU v5 lite"}

DECODE = """
HloModule jit_hybrid_decode
ENTRY %main {
  %fusion.1 = bf16[32,2688]{1,0} fusion(%p), kind=kOutput, metadata={op_name="jit(hybrid_decode)/moe:experts/dot_general"}
  %fusion.2 = f32[7,32,64,64,128]{4,3,2,1,0} fusion(%s), kind=kLoop, metadata={op_name="jit(hybrid_decode)/ssm:update/mul"}
  ROOT %fusion.3 = f32[32,65536]{1,0} fusion(%x), kind=kOutput, metadata={op_name="jit(hybrid_decode)/dot_general"}
}
"""
PREFILL = """
HloModule jit_hybrid_prefill_8_of_8
ENTRY %main {
  %fusion.1 = f32[512,64,64]{2,1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(hybrid_prefill_8_of_8)/ssm:scan/mul"}
  ROOT %fusion.2 = bf16[512,2688]{1,0} fusion(%s), kind=kOutput, metadata={op_name="jit(hybrid_prefill_8_of_8)/moe:shared/dot_general"}
}
"""


def op(text, start, dur):
    return Event(D, OPS, text.split(" ")[0], start, dur, text)


def prog(name, start, dur):
    return Event(D, PROGRAMS, name, start, dur, name)


def config():
    with open(os.path.join(BENCH, "configs", "nemotron3nano-serve1.json")) as f:
        return json.load(f)


@pytest.fixture
def ctx(tmp_path):
    paths = {}
    for name, text in (("jit_hybrid_decode", DECODE),
                       ("jit_hybrid_prefill_8_of_8", PREFILL)):
        paths[name] = str(tmp_path / f"{name}.txt")
        with open(paths[name], "w") as f:
            f.write(text)
    events = [
        prog("jit_hybrid_decode", 0, 10),
        op("%fusion.1 = bf16[32,2688]{1,0} fusion(%p), kind=kOutput", 0, 6),
        op("%fusion.2 = f32[7,32,64,64,128]{4,3,2,1,0} fusion(%s)", 6, 2),
        op("%fusion.3 = f32[32,65536]{1,0} fusion(%x), kind=kOutput", 8, 2),
        prog("jit_hybrid_prefill_8_of_8", 10, 10),
        op("%fusion.1 = f32[512,64,64]{2,1,0} fusion(%p), kind=kLoop", 10, 4),
        op("%fusion.2 = bf16[512,2688]{1,0} fusion(%s), kind=kOutput", 14, 6),
        prog("jit_hybrid_decode", 20, 10),
        op("%fusion.1 = bf16[32,2688]{1,0} fusion(%p), kind=kOutput", 20, 6),
        op("%fusion.2 = f32[7,32,64,64,128]{4,3,2,1,0} fusion(%s)", 26, 2),
        # The trace ends inside this execution: its last operation is cut.
    ]
    engine = {"decode_steps": 100, "slot_steps": 3200, "experts_touched": 22400}
    return {"events": events, "device": TPU, "config": config(),
            "counters": {"program_texts": paths, "engine": engine}}


def test_an_instruction_is_read_in_its_own_programs_text(ctx):
    """`fusion.1` is the experts' in the decode program and the scan's
    in the prefill program; busy time is 28 of a window of 28."""
    moe = ["moe:experts", "moe:shared"]
    assert program_scope_share.reduce(ctx, scopes=moe) == pytest.approx(
        100 * (6 + 6 + 6) / 28
    )
    ssm = ["ssm:scan", "ssm:update"]
    assert program_scope_share.reduce(ctx, scopes=ssm) == pytest.approx(
        100 * (2 + 4 + 2) / 28
    )
    assert program_scope_share.reduce(
        ctx, scopes=ssm, program="hybrid_decode"
    ) == pytest.approx(100 * 4 / 28)
    assert program_scope_share.reduce(ctx, scopes=["no:such"]) is None
    ctx["counters"]["program_texts"] = {}
    assert program_scope_share.reduce(ctx, scopes=moe) is None


def test_share_of_the_hbm_peak_counts_the_executions_it_timed(ctx):
    conf, engine = ctx["config"], ctx["counters"]["engine"]
    # 224 of the 448 held experts a step, two matrices of 2688 x 1856 bf16.
    per_step = nemotron_h.expert_weights_read_per_decode_step(conf, engine)
    assert per_step == 224 * 2 * 2688 * 1856 * 2
    got = hbm_share.reduce(
        ctx, scopes=["moe:experts"], program="hybrid_decode",
        bytes_fn="expert_weights_read_per_decode_step",
    )
    assert got == pytest.approx(100 * per_step * 2 / 12 / 819e9)
    # 32 slots, 7 Mamba blocks, float32 [64, 64, 128] and a bf16
    # [3, 6144] tail, read once and written once.
    state = nemotron_h.ssm_state_bytes_per_decode_step(conf, engine)
    assert state == 2 * 32 * 7 * (64 * 64 * 128 * 4 + 3 * 6144 * 2)
    ctx["device"] = {"platform": "cpu", "kind": "cpu"}
    assert hbm_share.reduce(
        ctx, scopes=["ssm:update"], program="hybrid_decode",
        bytes_fn="ssm_state_bytes_per_decode_step",
    ) is None


def test_counts_of_the_configuration():
    """5.28B parameters as the issue counted them (the padding of the
    expert stacks to 1920 columns is not among them); 448 expert slots."""
    conf = config()
    assert round(nemotron_h.held_parameters(conf) / 1e9, 2) == 5.28
    assert nemotron_h.held_expert_slots(conf) == 64 * 7
    assert nemotron_h.scan_flops_per_token(conf) > 0
    cfg = nemotron_h.config(conf, max_seq=2560)
    assert cfg.experts_held == (0, 64) and cfg.num_experts == 128
    assert cfg.pattern == "MEMEM*EMEMEM*EME" and cfg.vocab_size == 65536
