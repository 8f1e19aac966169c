"""The reducers behind the per-layer metrics of the sparse-expert cell,
on made-up events and counters; the operations count against a count
made by hand; and the rehearsal listing that holds the tiny MoE cell."""

import importlib
import json
import os

import pytest

from benchmarks.models import olmoe
from benchmarks.reducers import matmul_roofline, model_mfu, scope_time_share
from benchmarks.traceread import OPS, PROGRAMS, Event

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
D = "/device:TPU:0"
TPU = {"platform": "tpu", "kind": "TPU v5 lite"}


def op(text, start, dur, device=D):
    return Event(device, OPS, text.split(" ")[0], start, dur, text)


def prog(name, start, dur, device=D):
    return Event(device, PROGRAMS, name, start, dur, name)


def config():
    with open(os.path.join(BENCH, "configs", "olmoe-train1.json")) as f:
        return json.load(f)


PROGRAM = """
HloModule jit_train_step
%fused_computation.7 (p: bf16[8]) -> bf16[8] {
  %mul.3 = bf16[8]{0} multiply(%p, %p), metadata={op_name="jit(train_step)/jvp()/while/body/moe:experts/mul"}
}
ENTRY %main {
  %fusion.1 = f32[8,4]{1,0} fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/moe:route/dot_general" stack_frame_id=9}
  %sort.2 = s32[16]{0} sort(%k), dimensions={0}, metadata={op_name="jit(train_step)/transpose(jvp())/while/body/checkpoint/rematted_computation/moe:dispatch/sort"}
  %ragged-dot-none.4 = bf16[16,8]{1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %fusion.5 = bf16[16,8]{1,0} fusion(%y, %i), kind=kCustom, calls=%fused_computation.5, metadata={op_name="jit(train_step)/jvp()/while/body/moe:combine/gather"}
  ROOT %flash_attention.3 = bf16[8]{0} custom-call(%q), metadata={op_name="jit(train_step)/jvp()/while/body/flash_attention"}
}
"""


def traced(tmp_path, text=PROGRAM):
    path = tmp_path / "step_program.txt"
    path.write_text(text)
    # Instructions as the trace names them: no metadata.
    events = [
        prog("jit_train_step", 0, 10),
        op("%fusion.1 = f32[8,4]{1,0} fusion(f32[8]{0} %p), kind=kLoop", 0, 1),
        op("%sort.2 = s32[16]{0} sort(s32[16]{0} %k), dimensions={0}", 1, 1),
        op("%ragged-dot-none.4 = bf16[16,8]{1,0} custom-call(%a, %b)", 2, 4),
        op("%fusion.5 = bf16[16,8]{1,0} fusion(%y, %i), kind=kCustom", 6, 1),
        op("%flash_attention.3 = bf16[8]{0} custom-call(%q)", 7, 2),
    ]
    return {"events": events, "counters": {"step_program_text": str(path)}}


def test_scopes_are_read_from_the_program_text(tmp_path):
    ctx = traced(tmp_path)
    four = ["moe:route", "moe:dispatch", "moe:experts", "moe:combine"]
    assert scope_time_share.instruction_scopes(PROGRAM, four) == {
        "mul.3", "fusion.1", "sort.2", "fusion.5",
    }
    # The compiler's kernel has no scope: counted by its name.
    assert scope_time_share.reduce(ctx, scopes=four) == pytest.approx(
        100 * 3 / 9
    )
    kernels = {"instructions": ["ragged-dot"]}
    assert scope_time_share.reduce(
        ctx, scopes=four, **kernels
    ) == pytest.approx(100 * 7 / 9)
    assert scope_time_share.reduce(
        ctx, scopes=four, over="window", **kernels
    ) == pytest.approx(70.0)
    assert scope_time_share.reduce(
        ctx, scopes=["moe:route", "moe:dispatch", "moe:combine"]
    ) == pytest.approx(100 * 3 / 9)


def test_a_program_without_the_scopes_has_nothing_to_read(tmp_path):
    events = [prog("jit_train_step", 0, 4),
              op("%fusion.2 = bf16[8] fusion(%p)", 0, 4)]
    none = {"events": events, "counters": {}}
    assert scope_time_share.reduce(none, scopes=["moe:"]) is None
    dense = traced(tmp_path, PROGRAM.replace("moe:", "ffn:"))
    assert scope_time_share.reduce(dense, scopes=["moe:route"]) is None
    ctx = {"events": events, "config": {"hidden_size": 8}, "device": TPU,
           "counters": {"median_step_tokens_per_s_per_chip": 1.0, "seq": 8}}
    # A Llama-shaped configuration names no model module.
    assert model_mfu.reduce(ctx) is None
    assert matmul_roofline.reduce(
        ctx, match="ragged-dot", flops_fn="expert_matmul_flops_per_token",
        program="train_step",
    ) is None


def test_operations_by_hand():
    conf = config()
    d, f, v = 2048, 1024, 50304
    per_layer = 4 * d * d + d * 64 + 8 * 3 * d * f
    assert per_layer == 67_239_936
    assert olmoe.matmul_params(conf) == 2 * per_layer + d * v == 237_502_464
    attention = 2 * 4 * d * (4096 + 1) / 2
    assert olmoe.train_flops_per_token(conf, 4096) == 3 * (
        2 * 237_502_464 + attention
    )
    assert olmoe.train_flops_per_token(conf, 4096) == pytest.approx(
        1.5257e9, rel=1e-4
    )
    # Expert matmuls: 40% of what the arithmetic requires.
    required = 3 * 2 * 2 * 8 * 3 * d * f
    assert required / olmoe.train_flops_per_token(conf, 4096) == pytest.approx(
        0.396, abs=0.002
    )
    assert olmoe.expert_matmul_flops_per_token(conf, "full") == 4 / 3 * required
    assert olmoe.expert_matmul_flops_per_token(conf, "none") == required
    assert olmoe.total_params(conf) == 1_045_186_560
    assert olmoe.pairs_per_step(conf, 8192) == 131_072


def test_mfu_of_a_model_that_names_its_module():
    conf = config()
    ctx = {"events": [], "config": conf, "device": TPU,
           "counters": {"median_step_tokens_per_s_per_chip": 40_000.0,
                        "seq": 4096}}
    want = 100 * olmoe.train_flops_per_token(conf, 4096) * 40_000 / 197e12
    assert model_mfu.reduce(ctx) == pytest.approx(want)
    assert 30 < want < 32
    assert model_mfu.reduce({**ctx, "device": {"platform": "cpu", "kind": "cpu"}}) is None


def test_expert_matmul_roofline_counts_whole_steps_only():
    conf = config()
    per_step = olmoe.expert_matmul_flops_per_token(conf, "full") * 8192
    kernel = '%ragged-dot-none.4 = bf16[65536,1024] custom-call(%a, %b)'
    # Two whole steps of 0.2 s, each with 0.05 s of kernels; a third
    # whose program began before the trace did is not in it at all, and
    # a kernel outside any program is not counted.
    events = [
        prog("jit_train_step", 1.0, 0.2), op(kernel, 1.0, 0.03),
        op(kernel, 1.1, 0.02), op("%fusion.9 = f32[8] fusion(%p)", 1.15, 0.05),
        prog("jit_train_step", 1.3, 0.2), op(kernel, 1.3, 0.05),
        op(kernel, 0.5, 0.04),
    ]
    ctx = {"events": events, "config": conf, "device": TPU,
           "counters": {"tokens_per_step_per_chip": 8192}}
    got = matmul_roofline.reduce(
        ctx, match="ragged-dot", flops_fn="expert_matmul_flops_per_token",
        program="train_step",
    )
    assert got == pytest.approx(100 * 2 * per_step / 0.1 / 197e12)
    assert got < 100  # 6.6 TFLOP a step in 0.05 s is 67% of the peak


@pytest.mark.parametrize("cell", ["tiny-moe", "tiny-burst"])
def test_the_rehearsal_listing_holds_cells_that_can_be_found(cell):
    with open(os.path.join(HERE, "rehearsal-moe.json")) as f:
        listing = json.load(f)
    entry = next(c for c in listing["workloads"] if c["name"] == cell)
    with open(os.path.join(HERE, "configs", f"{entry['config']}.json")) as f:
        conf = json.load(f)
    with open(os.path.join(HERE, "traffic", f"{entry['traffic']}.json")) as f:
        traffic = json.load(f)
    importlib.import_module(f"benchmarks.runners.{conf['runner']}")
    if "kind" in traffic and traffic["kind"] != "train_steps":
        importlib.import_module(f"benchmarks.arrivals.{traffic['kind']}")
    mine = [m for m in listing["per_layer"]
            if cell in m.get("workloads", [cell])]
    assert len(mine) >= 8
    for metric in mine:
        path = os.path.join(BENCH, "layer_metrics", f"{metric['name']}.json")
        with open(path) as f:
            importlib.import_module(
                f"benchmarks.reducers.{json.load(f)['reducer']}"
            )


def test_the_official_listing_names_the_new_cell_and_its_files():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        listing = json.load(f)
    cells = {c["name"]: c for c in listing["workloads"]}
    assert cells["olmoe-train-4k"]["traffic"] == "steady-4k"
    # chat-burst's mix is built and kept, but is no cell: its file says why.
    assert "chat-burst" not in cells
    assert sum(c["chips"] == 4 for c in cells.values()) == 1
    conf = config()
    entry = next(c for c in listing["configs"] if c["name"] == "olmoe-train1")
    assert entry["reduced"] == list(conf["reduced"]) == ["num_hidden_layers"]
    cfg = olmoe.config(conf)
    assert (cfg.num_experts, cfg.top_k, cfg.d_ff, cfg.d_model) == (64, 8, 1024, 2048)
    assert cfg.qk_norm and not cfg.norm_topk_prob
