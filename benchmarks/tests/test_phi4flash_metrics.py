"""What the Phi-4-mini-flash serving cell adds to the benchmark, on
made-up events and counters: the bytes and operations of its kernels
against counts made by hand at the published widths, the accepted readers
the cell is appended to on a trace made by hand (both prefill programs
and the decode program), `check_problems` either side of each limit, the
configuration against the published keys, and the rehearsal listing that
holds the tiny cell."""

import importlib
import json
import os

import pytest

from benchmarks import peaks
from benchmarks.models import phi4flash as family
from benchmarks.traceread import OPS, PROGRAMS, Event

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
D = "/device:TPU:0"
TPU = {"platform": "tpu", "kind": "TPU v5 lite"}
CELL = "phi4flash-reasonctx-32"
# Entries the benchmark had whose readers read this cell's programs as
# they are (scopes, a pattern program's name, a span, a counter of
# `stats()`, the family module's function names): the cell is appended
# to their lists, since the per-layer list is full at 128.
SHARED = (
    "ssm_time_pct.reason", "ssm_scan_time_pct.granite",
    "ssm_scan_roofline_pct.granite", "ssm_state_hbm_pct.reason",
    "window_attn_time_pct.laguna", "window_attn_roofline_pct.laguna",
    "full_attn_time_pct.laguna", "device_idle_pct.laguna",
    "prefill_device_share_pct.laguna", "decode_device_ms.laguna",
    "batch_occupancy_pct.laguna", "engine_init_s", "replica_ready_lag_s",
    "http_start_s", "idle_host_late_ms_per_step.family",
    "idle_launch_ms_per_step.family", "host_idle_ms_per_step.between.family",
    "host_idle_ms_per_step.prepare.family",
    "host_idle_ms_per_step.readback.family", "idle_attributed_pct.family",
    "host_work_ms_per_step.family", "host_wait_ms_per_step.family",
    "decode_starved_pct.family", "host_cpu_share_pct.family",
)
# The catalog's `config` for the model: every number under its key.
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064,
}

SELF = "jit_hybrid_prefill_self_32_of_256"
CROSS = "jit_hybrid_prefill_cross_32_of_256"


def _prefill(name: str, cross: bool) -> str:
    scope = name.removeprefix("jit_")
    lines = [
        ("fusion.1", "bf16[2048,10240]", "ssm:in_proj/dot_general"),
        ("fusion.2", "bf16[2048,5120]", "ssm:conv/mul"),
        ("custom-call.1", "bf16[2048,40,128]",
         "ssm:scan/jit(selective_scan_chunk)/pallas_call"),
        ("fusion.3", "bf16[2048,2560]", "ssm:out/dot_general"),
        ("custom-call.2", "bf16[2048,5120]",
         "attn:window/jit(window_attention)/pallas_call"),
        ("fusion.4", "bf16[8,257,10,64,128]", "attn:window_write/dynamic_update_slice"),
        ("custom-call.3", "bf16[2048,40,128]",
         "attn:full/self/jit(prefill_attention)/pallas_call"),
        ("fusion.5", "bf16[2048,2560]", "attn:diff/sub"),
        ("fusion.6", "bf16[2048,2560]", "ffn:dense/dot_general"),
    ]
    if cross:
        lines += [
            ("fusion.7", "bf16[1,1,5120]", "gmu:gate/mul"),
            ("fusion.8", "bf16[1,1,2560]", "gmu:out/dot_general"),
            ("custom-call.4", "bf16[1,1,40,128]",
             "attn:full/cross/jit(paged_attention)/pallas_call"),
            ("fusion.9", "f32[1,1,200064]", "dot_general"),
        ]
    body = "\n".join(
        f'  %{op} = {shape}{{0}} fusion(%x), metadata={{op_name="jit({scope})/{at}"}}'
        for op, shape, at in lines
    )
    return f"HloModule {name}\nENTRY %main {{\n{body}\n}}\n"


DECODE = """
HloModule jit_hybrid_decode
ENTRY %main {
  %custom-call.5 = f32[32,40,128]{2,1,0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(hybrid_decode)/ssm:update/jit(selective_state_step)/pallas_call"}
  %custom-call.6 = bf16[32,1,40,128]{3,2,1,0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(hybrid_decode)/attn:window/jit(paged_attention)/pallas_call"}
  %custom-call.7 = bf16[32,1,40,128]{3,2,1,0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(hybrid_decode)/attn:full/self/jit(paged_attention)/pallas_call"}
  %custom-call.8 = bf16[32,1,40,128]{3,2,1,0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(hybrid_decode)/attn:full/cross/jit(paged_attention)/pallas_call"}
  %fusion.1 = bf16[32,1,5120]{2,1,0} fusion(%m), kind=kLoop, metadata={op_name="jit(hybrid_decode)/gmu:gate/mul"}
  ROOT %fusion.2 = f32[32,200064]{1,0} fusion(%x), kind=kOutput, metadata={op_name="jit(hybrid_decode)/dot_general"}
}
"""


def config():
    with open(os.path.join(BENCH, "configs", "phi4miniflash-serve1.json")) as f:
        return json.load(f)


def op(text, start, dur):
    return Event(D, OPS, text.split(" ")[0], start, dur, text)


def prog(name, start, dur):
    return Event(D, PROGRAMS, name, start, dur, name)


@pytest.fixture
def ctx(tmp_path):
    """A prefill program that stops before the cross-decoder (9 s: one
    second an operation), one that runs it (13 s) and two decode programs
    of 6 s (state update 1, ring attend 1, the pool's owner 1, the cross
    attends 1, the gate 1, the head 1), 6 idle seconds: a window of 40 s,
    busy 34."""
    texts = {SELF: _prefill(SELF, False), CROSS: _prefill(CROSS, True),
             "jit_hybrid_decode": DECODE}
    paths = {}
    for name, text in texts.items():
        paths[name] = str(tmp_path / f"{name}.txt")
        with open(paths[name], "w") as f:
            f.write(text)

    def ops_of(name, start):
        lines = [ln for ln in texts[name].splitlines() if ln.startswith("  %")
                 or ln.startswith("  ROOT")]
        return [prog(name, start, len(lines))] + [
            op(ln.strip().removeprefix("ROOT ").split(", metadata")[0],
               start + i, 1) for i, ln in enumerate(lines)
        ]

    events = (ops_of(SELF, 0) + ops_of("jit_hybrid_decode", 9)
              + ops_of(CROSS, 17) + ops_of("jit_hybrid_decode", 34))
    # Over the traced steps: 4 prefill programs of 2,048 live tokens at
    # positions 4,096 to 6,143, one of them a prompt's last; 10 decode
    # steps of 30 slots at 12,000 tokens of context a slot.
    tokens = 4 * 2048
    traced = {
        "prefill_programs": 4, "ssm_scan_tokens": 9 * tokens,
        "window_tokens": 8 * tokens, "prefill_window_pairs": 8 * tokens * 512,
        "prefill_attn_pairs": 4 * sum(t + 1 for t in range(4096, 6144)),
        "prefill_self_only_chunks": 3, "cross_decoder_rows": 301,
        "decode_steps": 10, "slot_steps": 300,
        "shared_kv_bytes": 10 * 30 * 12000 * 8 * 5120,
    }
    engine = {**{k: v * 10 for k, v in traced.items()}, "traced": traced}
    return {"events": events, "device": TPU, "config": config(),
            "traffic": {}, "counters": {"program_texts": paths,
                                        "engine": engine}}


def test_the_bytes_and_operations_by_hand(ctx):
    """At the published shapes. A token costs a Mamba layer's scan 3 x 2
    x 5,120 + 2 x 4 x 16 bytes and 5,120 x (7 x 16 + 8) element
    operations; a layer's state is 4 x 16 x 5,120 B and its tail 2 x 3 x
    5,120 B; a (query, key) pair costs a head 2 x (64 + 128) operations,
    40 heads; a token's keys and values are 5,120 B a layer."""
    conf, engine = ctx["config"], ctx["counters"]["engine"]
    assert (family.mamba_layers(conf), family.window_layers(conf),
            family.cross_layers(conf)) == (9, 8, 7)
    assert family.d_inner(conf) == 5120 and family.kv_token_bytes(conf) == 5120
    state, tail = 4 * 16 * 5120, 2 * 3 * 5120
    assert family.scan_bytes_per_program(conf, engine) == (
        9 * 2048 * (3 * 2 * 5120 + 128) + 9 * (2 * state + tail)
    )
    assert family.scan_flops_per_program(conf, engine) == (
        9 * 2048 * 5120 * 120.0
    )
    assert family.ssm_state_bytes_per_decode_step(conf, engine) == (
        30 * 9 * 2.0 * (state + tail)
    )
    assert family.window_attn_flops_per_program(conf, engine) == (
        8 * 2048 * 512 * 40 * 384.0
    )
    assert family.window_bytes_per_slot(conf) == 512 * 5120
    assert family.window_attn_bytes_per_program(conf, engine) == (
        8 * 2048 * (2 * 40 * 192 + 5120) + 2 * 8 * 512 * 5120
    )
    assert family.shared_kv_bytes_per_decode_step(conf, engine) == (
        30 * 12000 * 8 * 5120
    )
    assert family.shared_kv_flops_per_decode_step(conf, engine) == (
        30 * 12000 * 8 * 40 * 384.0
    )
    assert family.held_expert_slots(conf) == 0
    # A replica's life where no traced counters were taken.
    life = {k: v for k, v in engine.items() if k != "traced"}
    assert family.scan_flops_per_program(conf, life) == 9 * 2048 * 5120 * 120.0
    # A program without the counters (this PR's parent), or no program run.
    for fn in ("scan_bytes_per_program", "scan_flops_per_program",
               "window_attn_bytes_per_program",
               "window_attn_flops_per_program"):
        assert getattr(family, fn)(conf, {"prefill_programs": 3}) == 0.0
        assert getattr(family, fn)(conf, {"traced": None}) == 0.0
    for fn in ("ssm_state_bytes_per_decode_step",
               "shared_kv_bytes_per_decode_step",
               "shared_kv_flops_per_decode_step"):
        assert getattr(family, fn)(conf, {"decode_steps": 3}) == 0.0
        assert getattr(family, fn)(conf, {"traced": None}) == 0.0


def _metric(ctx, name):
    with open(os.path.join(BENCH, "layer_metrics", f"{name}.json")) as f:
        spec = json.load(f)
    reducer = importlib.import_module(f"benchmarks.reducers.{spec['reducer']}")
    return reducer.reduce(ctx, **spec.get("args", {}))


def test_the_accepted_readers_read_this_cells_programs(ctx):
    """The shares of busy time (34 s) by scope as the accepted entries'
    files name them, over BOTH prefill programs (their names begin
    ``hybrid_prefill_``) and the decode program."""
    busy = 34.0
    # Four Mamba scopes in each prefill program, the update in each decode.
    assert _metric(ctx, "ssm_time_pct.reason") == pytest.approx(
        100.0 * (4 + 4 + 2) / busy)
    assert _metric(ctx, "ssm_scan_time_pct.granite") == pytest.approx(
        100.0 * 2 / busy)
    # The band kernel and the ring's write in the prefills, the ring's
    # write and attend (one call each way of the pool's kernels) in the
    # decodes.
    assert _metric(ctx, "window_attn_time_pct.laguna") == pytest.approx(
        100.0 * (2 + 2 + 2) / busy)
    # All eight attends of the pool layer, self and cross: the chunk's
    # own in each prefill, the last row's cross attend, and both in each
    # decode.
    assert _metric(ctx, "full_attn_time_pct.laguna") == pytest.approx(
        100.0 * (1 + 2 + 4) / busy)
    assert _metric(ctx, "prefill_device_share_pct.laguna") == pytest.approx(
        100.0 * 22 / 40)
    assert _metric(ctx, "decode_device_ms.laguna") == pytest.approx(6000.0)
    assert _metric(ctx, "device_idle_pct.laguna") == pytest.approx(
        100.0 * 6 / 40)
    conf, engine = ctx["config"], ctx["counters"]["engine"]
    peak = peaks.load(TPU["kind"])
    # Two prefill programs in the trace, 1 s each under `ssm:scan`: the
    # scan is read against its bytes (its element operations over the
    # matmul peak are the smaller).
    scan = family.scan_bytes_per_program(conf, engine) / peak["hbm_bytes_per_s"]
    assert scan > family.scan_flops_per_program(conf, engine) / peak["bf16_flops"]
    assert _metric(ctx, "ssm_scan_roofline_pct.granite") == pytest.approx(
        100.0 * scan * 2 / 2.0)
    band = family.window_attn_flops_per_program(conf, engine) / peak["bf16_flops"]
    assert band > (family.window_attn_bytes_per_program(conf, engine)
                   / peak["hbm_bytes_per_s"])
    assert _metric(ctx, "window_attn_roofline_pct.laguna") == pytest.approx(
        100.0 * band * 2 / 2.0)
    state = family.ssm_state_bytes_per_decode_step(conf, engine)
    assert _metric(ctx, "ssm_state_hbm_pct.reason") == pytest.approx(
        100.0 * state / peak["hbm_bytes_per_s"] * 2 / 2.0)
    # The parent of this PR has no such program: nothing to read.
    bare = {**ctx, "events": [], "counters": {"engine": {}}}
    for name in ("ssm_scan_roofline_pct.granite", "ssm_state_hbm_pct.reason",
                 "window_attn_roofline_pct.laguna",
                 "full_attn_time_pct.laguna"):
        assert _metric(bare, name) is None


def _passing():
    return {
        "logit_max_abs_err": [family.LOGIT_TOLERANCE * 0.9] * 5,
        "finite": True, "cell_rel_err": family.CELL_TOLERANCE * 0.9,
        "ring_rel_err": family.CELL_TOLERANCE * 0.5,
        "state_rel_err": family.STATE_TOLERANCE * 0.9,
    }


@pytest.mark.parametrize("broken,word", [
    ({"logit_max_abs_err": [family.LOGIT_TOLERANCE * 1.1]}, "logits"),
    ({"finite": False}, "logits"),
    ({"cell_rel_err": family.CELL_TOLERANCE * 1.1}, "pages"),
    ({"ring_rel_err": family.CELL_TOLERANCE * 1.1}, "rings"),
    ({"state_rel_err": family.STATE_TOLERANCE * 1.1}, "state"),
])
def test_check_problems_either_side_of_each_limit(broken, word):
    assert family.check_problems(_passing()) == []
    problems = family.check_problems({**_passing(), **broken})
    assert len(problems) == 1 and word in problems[0]


def test_the_configuration_is_the_published_model_whole():
    """Every published key under its name and nothing reduced; every
    assumed size under `assumed`; the program's config counts what the
    file says; the fit holds."""
    conf = config()
    assert {k: conf[k] for k in PUBLISHED} == PUBLISHED
    assert conf["reduced"] == {}
    assert conf["assumed_values"] == {
        "d_state": 16, "d_conv": 4, "expand": 2, "dt_rank": 160}
    assert set(conf["assumed_values"]) <= set(conf["assumed"])
    cfg = family.config(conf, max_seq=conf["engine"]["max_seq"])
    assert len(cfg.pattern) == 64 and cfg.cross_from == 36
    assert [cfg.count(k) for k in "SW*UC"] == [9, 8, 1, 7, 7]
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (40, 10, 128)
    assert cfg.vocab_size == 200064 and cfg.tie_word_embeddings
    held = family.held_parameters(conf)
    assert 3.84e9 < held < 3.86e9
    eng = conf["engine"]
    # Every slot can reach max_seq.
    assert eng["num_pages"] * eng["page_size"] == eng["max_batch"] * eng["max_seq"]
    fit = conf["fit"]
    assert max(fit["peak_bytes"].values()) < fit["usable_bytes"]
    # The fullest device holds at least 60% of one chip's memory.
    assert fit["argument_bytes"] > 0.6 * 16 * 2**30
    with pytest.raises(ValueError, match="mlp_bias"):
        family.config({**conf, "mlp_bias": True})
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        family.config({**conf, "tie_word_embeddings": False})


def test_the_benchmark_holds_the_cell_and_adds_no_per_layer_entry():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        listing = json.load(f)
    cell = listing["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, "phi4miniflash-serve1", "reasonctx-closed", 1)
    assert listing["configs"][-1]["name"] == "phi4miniflash-serve1"
    assert listing["configs"][-1]["reduced"] == []
    assert len(listing["per_layer"]) == 128
    by_name = {m["name"]: m for m in listing["per_layer"]}
    for name in SHARED:
        assert by_name[name]["workloads"][-1] == CELL, name
    assert sum(CELL in m.get("workloads", ()) for m in listing["per_layer"]) == (
        len(SHARED))
    tokens = next(m for m in listing["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    assert tokens["workloads"][-1] == CELL
    with open(os.path.join(BENCH, "traffic", "reasonctx-closed.json")) as f:
        traffic = json.load(f)
    assert (traffic["kind"], traffic["clients"], traffic["requests"]) == (
        "closed_loop", 32, 128)
    assert traffic["prompt"] == {"dist": "lognormal", "median": 12288,
                                 "sigma": 0.6, "lo": 4096, "hi": 24576,
                                 "snap": 2048}
    assert traffic["output"] == {"dist": "lognormal", "median": 512,
                                 "sigma": 0.5, "lo": 256, "hi": 1536}
    assert traffic["warm_prompt_lengths"] == [4096, 8192, 16384, 24576]


def test_the_rehearsal_listing_holds_the_tiny_cell():
    with open(os.path.join(HERE, "rehearsal-phi4flash.json")) as f:
        listing = json.load(f)
    assert [c["name"] for c in listing["workloads"]] == ["tiny-phi4flash"]
    with open(os.path.join(HERE, "configs", "tiny-phi4flash.json")) as f:
        tiny = json.load(f)
    cfg = family.config(tiny, max_seq=tiny["engine"]["max_seq"])
    assert cfg.pattern == "SDWDSD*DUDCDUDCD"
    names = {m["name"] for m in listing["per_layer"]}
    assert set(SHARED) <= names
    for name in names:
        path = os.path.join(BENCH, "layer_metrics", f"{name}.json")
        assert os.path.exists(path), name
