"""What the Motif-3-Beta serving cell adds to the benchmark, on made-up
events and counters: the operations and bytes of the changed kernels
against counts made by hand at the published widths, the accepted
readers the cell is appended to on a trace made by hand, `check_problems`
either side of each limit, the configuration's counts and published
numbers, and the rehearsal listing that holds the tiny cell."""

import importlib
import json
import os

import pytest

from benchmarks import peaks
from benchmarks.models import motif as family
from benchmarks.traceread import OPS, PROGRAMS, Event

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
D = "/device:TPU:0"
TPU = {"platform": "tpu", "kind": "TPU v5 lite"}
CELL = "motif3-longctx-16"
# Entries the benchmark had whose readers read this cell's programs as
# they are (scopes, a pattern program's name, a span, a counter of
# `stats()`, the family module's function names): the cell is appended
# to their lists, since the per-layer list is full at 128.
SHARED = (
    "mla_time_pct.longdoc", "mhc_time_pct.glm53flash",
    "moe_time_pct.glm53flash", "window_attn_time_pct.laguna",
    "window_attn_roofline_pct.laguna", "device_idle_pct.laguna",
    "prefill_device_share_pct.laguna", "decode_device_ms.laguna",
    "batch_occupancy_pct.laguna", "experts_touched_pct.laguna",
    "moe_dispatch_time_pct.laguna", "moe_sorted_rows_pct.laguna",
    "engine_init_s", "replica_ready_lag_s", "http_start_s",
    "host_work_ms_per_step.family", "decode_starved_pct.family",
)

PREFILL = """
HloModule jit_hybrid_prefill_32_of_256
ENTRY %main {
  %custom-call.1 = bf16[2048,16384]{1,0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(hybrid_prefill_32_of_256)/mhc:mix/jit(mhc_mix)/pallas_call"}
  %fusion.1 = bf16[2048,15360]{1,0} fusion(%u, %w), kind=kOutput, metadata={op_name="jit(hybrid_prefill_32_of_256)/mla:q/dot_general"}
  %fusion.2 = bf16[16,2176,128]{2,1,0} fusion(%c, %w), kind=kOutput, metadata={op_name="jit(hybrid_prefill_32_of_256)/attn:window/mla:expand/tc,gcd->gtd/dot_general"}
  %custom-call.2 = bf16[2048,10240]{1,0} custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", metadata={op_name="jit(hybrid_prefill_32_of_256)/attn:window/jit(window_attention)/pallas_call"}
  %fusion.3 = bf16[4,16,128,640]{3,2,1,0} fusion(%ring, %last), kind=kLoop, metadata={op_name="jit(hybrid_prefill_32_of_256)/attn:window_write/scatter"}
  %custom-call.3 = bf16[80,2048,128]{2,1,0} custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", metadata={op_name="jit(hybrid_prefill_32_of_256)/mla:attend/jit(latent_prefill_attention)/pallas_call"}
  %fusion.4 = bf16[2048,64,128]{2,1,0} fusion(%a, %lam), kind=kLoop, metadata={op_name="jit(hybrid_prefill_32_of_256)/gdla:diff/sub"}
  %custom-call.4 = bf16[16384,1280]{1,0} custom-call(%rows, %w), custom_call_target="tpu_custom_call", metadata={op_name="jit(hybrid_prefill_32_of_256)/moe:experts/jit(_grouped_rows)/pallas_call"}
  ROOT %fusion.5 = f32[1,1,27520]{2,1,0} fusion(%x), kind=kOutput, metadata={op_name="jit(hybrid_prefill_32_of_256)/dot_general"}
}
"""
DECODE = """
HloModule jit_hybrid_decode
ENTRY %main {
  %custom-call.5 = bf16[16,80,512]{2,1,0} custom-call(%q, %pool), custom_call_target="tpu_custom_call", metadata={op_name="jit(hybrid_decode)/mla:attend/jit(latent_paged_attention)/pallas_call"}
  %fusion.1 = f32[16,80,128]{2,1,0} fusion(%q, %ring), kind=kOutput, metadata={op_name="jit(hybrid_decode)/attn:window/bhw,btw->bht/dot_general"}
  %custom-call.6 = bf16[16,4096]{1,0} custom-call(%x, %w), custom_call_target="tpu_custom_call", metadata={op_name="jit(hybrid_decode)/moe:experts/pallas_call"}
  ROOT %fusion.2 = f32[16,27520]{1,0} fusion(%x), kind=kOutput, metadata={op_name="jit(hybrid_decode)/dot_general"}
}
"""


def config():
    with open(os.path.join(BENCH, "configs", "motif3beta-serve1.json")) as f:
        return json.load(f)


def op(text, start, dur):
    return Event(D, OPS, text.split(" ")[0], start, dur, text)


def prog(name, start, dur):
    return Event(D, PROGRAMS, name, start, dur, name)


@pytest.fixture
def ctx(tmp_path):
    """Two prefill chunk programs of 12 s (mix 1, queries 1, the band's
    expansion 1 and its kernel 2, the ring's write 1, the full attend 3,
    the subtraction 1, experts 1, head 1) and two decode programs of 4 s
    (attend 1, ring attend 1, experts 1, head 1), four idle seconds
    between the pairs: a window of 36 s, busy 32."""
    paths = {}
    for name, text in (("jit_hybrid_prefill_32_of_256", PREFILL),
                       ("jit_hybrid_decode", DECODE)):
        paths[name] = str(tmp_path / f"{name}.txt")
        with open(paths[name], "w") as f:
            f.write(text)
    events = []
    for start in (0, 20):
        events += [
            prog("jit_hybrid_prefill_32_of_256", start, 12),
            op("%custom-call.1 = bf16[2048,16384]{1,0} custom-call(%x)",
               start, 1),
            op("%fusion.1 = bf16[2048,15360]{1,0} fusion(%u, %w)",
               start + 1, 1),
            op("%fusion.2 = bf16[16,2176,128]{2,1,0} fusion(%c, %w)",
               start + 2, 1),
            op("%custom-call.2 = bf16[2048,10240]{1,0} custom-call(%q, %k, "
               "%v)", start + 3, 2),
            op("%fusion.3 = bf16[4,16,128,640]{3,2,1,0} fusion(%ring, %last)",
               start + 5, 1),
            op("%custom-call.3 = bf16[80,2048,128]{2,1,0} custom-call(%q, "
               "%k, %v)", start + 6, 3),
            op("%fusion.4 = bf16[2048,64,128]{2,1,0} fusion(%a, %lam)",
               start + 9, 1),
            op("%custom-call.4 = bf16[16384,1280]{1,0} custom-call(%rows, "
               "%w)", start + 10, 1),
            op("%fusion.5 = f32[1,1,27520]{2,1,0} fusion(%x)", start + 11, 1),
            prog("jit_hybrid_decode", start + 12, 4),
            op("%custom-call.5 = bf16[16,80,512]{2,1,0} custom-call(%q, "
               "%pool)", start + 12, 1),
            op("%fusion.1 = f32[16,80,128]{2,1,0} fusion(%q, %ring)",
               start + 13, 1),
            op("%custom-call.6 = bf16[16,4096]{1,0} custom-call(%x, %w)",
               start + 14, 1),
            op("%fusion.2 = f32[16,27520]{1,0} fusion(%x)", start + 15, 1),
        ]
    # Over the traced steps: 4 prefill programs of 2,048 live tokens, the
    # third chunk of a prompt each (positions 4,096 to 6,143), and 10
    # decode steps of 14 slots at 100 live pages a slot.
    tokens = 4 * 2048
    traced = {
        "prefill_programs": 4, "mhc_tokens": 10 * tokens,
        "window_tokens": 4 * tokens,
        "prefill_attn_pairs": 4 * sum(t + 1 for t in range(4096, 6144)),
        "prefill_window_pairs": 4 * tokens * 128,
        "latent_cells_expanded": 4 * (16384 + 4 * 2176),
        "moe_pairs_here": 4 * 4 * 2048, "experts_touched": 10 * 4 * 12,
        "decode_steps": 10, "slot_steps": 140, "attn_pages_live": 10 * 1400,
    }
    engine = {**{k: v * 10 for k, v in traced.items()}, "traced": traced}
    return {"events": events, "device": TPU, "config": config(),
            "traffic": {}, "counters": {"program_texts": paths,
                                        "engine": engine}}


def test_the_operations_and_bytes_by_hand(ctx):
    """At the published shapes. An expanded (query, key) pair costs a
    head 2 x (192 + 128) = 640 operations, 80 heads; a cached token costs
    a decode step 2 x 80 x (576 + 512) operations and 1,152 B in the one
    full layer; a cell of a band costs its expansion 2 x 512 x 16 x 256;
    an expert's three matrices are 31,457,280 B and a pair costs 3 x 2 x
    4,096 x 1,280."""
    conf, engine = ctx["config"], ctx["counters"]["engine"]
    assert family.full_layers(conf) == 1 and family.window_layers(conf) == 4
    assert family.latent_dim(conf) == 576
    pairs = sum(t + 1 for t in range(4096, 6144))
    assert family.latent_prefill_flops_per_program(conf, engine) == (
        pairs * 80 * 640.0
    )
    key = 16 * 256 + 64
    assert family.latent_prefill_bytes_per_program(conf, engine) == 2.0 * (
        2048 * 80 * 320 + 2.0 * (pairs / 2048) * key
    )
    live = 1400 * 64
    assert family.latent_decode_flops_per_step(conf, engine) == (
        live * 2.0 * 80 * 1088
    )
    assert family.latent_decode_bytes_per_step(conf, engine) == live * 1152.0
    assert family.window_bytes_per_slot(conf) == 128 * 576 * 2
    assert family.window_attn_flops_per_program(conf, engine) == (
        4 * 2048 * 128 * 80 * 640.0 + 4 * 2176 * 2.0 * 512 * 16 * 256
    )
    assert family.window_attn_bytes_per_program(conf, engine) == (
        4 * 2048 * 2 * (80 * 320 + 576) + 2 * 4 * 147456
    )
    assert family.expert_rows_bytes_per_step(conf, engine) == 48 * 31457280.0
    assert family.expert_rows_flops_per_step(conf, engine) == (
        48 * 16 * 6.0 * 4096 * 1280
    )
    assert family.grouped_rows_flops_per_program(conf, engine) == (
        4 * 2048 * 6.0 * 4096 * 1280
    )
    assert family.grouped_rows_bytes_per_program(conf, engine) == (
        4 * 48 * 31457280.0 + 4 * 2048 * 2.0 * (2 * 4096 + 2 * 1280)
    )
    # A replica's life where no traced counters were taken.
    life = {k: v for k, v in engine.items() if k != "traced"}
    assert family.latent_prefill_flops_per_program(conf, life) == (
        pairs * 80 * 640.0
    )
    # A program without the counters (this PR's parent), or no program run.
    for fn in ("latent_prefill_flops_per_program",
               "latent_prefill_bytes_per_program",
               "window_attn_flops_per_program",
               "window_attn_bytes_per_program",
               "grouped_rows_flops_per_program",
               "grouped_rows_bytes_per_program"):
        assert getattr(family, fn)(conf, {"prefill_programs": 3}) == 0.0
        assert getattr(family, fn)(conf, {"traced": None}) == 0.0
    for fn in ("latent_decode_flops_per_step", "latent_decode_bytes_per_step",
               "expert_rows_bytes_per_step", "expert_rows_flops_per_step"):
        assert getattr(family, fn)(conf, {"decode_steps": 3}) == 0.0
        assert getattr(family, fn)(conf, {"traced": None}) == 0.0


def _metric(ctx, name):
    with open(os.path.join(BENCH, "layer_metrics", f"{name}.json")) as f:
        spec = json.load(f)
    reducer = importlib.import_module(f"benchmarks.reducers.{spec['reducer']}")
    return reducer.reduce(ctx, **spec.get("args", {}))


def test_the_accepted_readers_read_this_cells_programs(ctx):
    """The shares of busy time (32 s) by scope as the accepted entries'
    files name them: latent attention's six scopes wherever they lie
    (the queries 1, the full attend 3 and, under the window's scope, the
    band's expansion 1, twice; the decode's attend 1, twice: 12 s), the
    window layers' two scopes (expansion, kernel, ring write and the
    decode's ring attend: 10 s), the residual mixes (2 s), the experts
    (4 s); and the band's share of its roofline with this family's
    functions."""
    assert _metric(ctx, "mla_time_pct.longdoc") == pytest.approx(
        100.0 * 12 / 32)
    assert _metric(ctx, "window_attn_time_pct.laguna") == pytest.approx(
        100.0 * 10 / 32)
    assert _metric(ctx, "mhc_time_pct.glm53flash") == pytest.approx(
        100.0 * 2 / 32)
    assert _metric(ctx, "moe_time_pct.glm53flash") == pytest.approx(
        100.0 * 4 / 32)
    conf, engine = ctx["config"], ctx["counters"]["engine"]
    # Two whole programs in the trace, 3 s each under `attn:window/`. At
    # a window of 128 keys the bytes bound it (a token's 80 queries in
    # and outputs out are 51 KB for 6.6 M operations), not the matmul
    # unit as at Laguna's 512.
    peak = peaks.load(TPU["kind"])
    least = max(
        family.window_attn_flops_per_program(conf, engine) / peak["bf16_flops"],
        family.window_attn_bytes_per_program(conf, engine)
        / peak["hbm_bytes_per_s"],
    )
    assert least == family.window_attn_bytes_per_program(
        conf, engine) / peak["hbm_bytes_per_s"]
    assert _metric(ctx, "window_attn_roofline_pct.laguna") == pytest.approx(
        100.0 * least * 2 / 6.0)
    # The parent of this PR has no such program: nothing to read.
    bare = {**ctx, "events": [], "counters": {"engine": {}}}
    assert _metric(bare, "window_attn_roofline_pct.laguna") is None


def _passing():
    return {
        "logit_max_abs_err": [family.LOGIT_TOLERANCE * 0.9] * 5,
        "finite": True, "largest_slack": family.MARGIN_EPSILON * 0.9,
        "routes_beyond_epsilon": 0,
        "cell_rel_err": family.CELL_TOLERANCE * 0.9,
        "ring_rel_err": family.CELL_TOLERANCE * 0.5,
    }


@pytest.mark.parametrize("broken,word", [
    ({"logit_max_abs_err": [family.LOGIT_TOLERANCE * 1.1]}, "logits"),
    ({"finite": False}, "logits"),
    ({"largest_slack": family.MARGIN_EPSILON * 1.1}, "cut"),
    ({"cell_rel_err": family.CELL_TOLERANCE * 1.1}, "cells"),
    ({"ring_rel_err": family.CELL_TOLERANCE * 1.1}, "rings"),
])
def test_check_problems_either_side_of_each_limit(broken, word):
    assert family.check_problems(_passing()) == []
    problems = family.check_problems({**_passing(), **broken})
    assert len(problems) == 1 and word in problems[0]


def test_the_configuration_is_the_published_model_cut_to_a_chip():
    """Every width as published; depth, dense layers, experts held,
    vocabulary and the draft module are the only keys cut; the program's
    config counts what the file says; the tree as held is what the fit's
    arguments hold."""
    import jax

    from ray_tpu.models.motif import init_params

    conf = config()
    assert set(conf["reduced"]) == {
        "num_hidden_layers", "n_dense_first_layers", "num_experts",
        "vocab_size", "num_nextn_predict_layers",
    }
    for key, published in conf["published"].items():
        if key in conf["reduced"]:
            assert conf[key] != published
    widths = {
        "hidden_size": 4096, "intermediate_size": 12288,
        "moe_intermediate_size": 1280, "num_attention_heads": 80,
        "num_noise_heads": 16, "num_key_value_heads": 16, "head_dim": 192,
        "v_head_dim": 128, "qk_rope_head_dim": 64, "kv_lora_rank": 512,
        "q_lora_rank": 1024, "experts_top_k": 8, "sliding_window": 128,
        "mhc_expansion_rate": 4, "mhc_sinkhorn_iters": 20,
    }
    assert {k: conf[k] for k in widths} == widths
    cfg = family.config(conf, max_seq=conf["engine"]["max_seq"])
    assert cfg.pattern == "RDREAERERE"
    assert (cfg.num_experts, cfg.experts_held, cfg.top_k) == (384, (0, 48), 8)
    assert (cfg.cell_width, cfg.group_heads, cfg.expert_kind) == (
        640, 5, "polynorm")
    tree = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))
    held = sum(leaf.size for leaf in jax.tree.leaves(tree))
    assert held == family.held_parameters(conf)
    assert 3.9e9 < held < 4.0e9
    assert family.held_expert_slots(conf) == 4 * 48
    fit = conf["fit"]
    assert max(fit["peak_bytes"]["5"].values()) < 15.75 * 2**30
    assert "refused" in [v for k, v in fit["peak_bytes"].items() if k != "5"][0]
    with pytest.raises(ValueError, match="score_before_experts"):
        family.config({**conf, "score_before_experts": True})
    with pytest.raises(ValueError, match="YaRN"):
        family.config({**conf, "rope_scaling": {"apply_yarn_scaling": True}})


def test_the_benchmark_holds_the_cell_and_adds_no_per_layer_entry():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        listing = json.load(f)
    cell = listing["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, "motif3beta-serve1", "longctx-closed", 1)
    assert listing["configs"][-1]["name"] == "motif3beta-serve1"
    assert len(listing["per_layer"]) == 128
    by_name = {m["name"]: m for m in listing["per_layer"]}
    for name in SHARED:
        assert by_name[name]["workloads"][-1] == CELL, name
    tokens = next(m for m in listing["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    assert tokens["workloads"][-1] == CELL
    # Readers that name another family's program or functions are not
    # this cell's: they wait for an entry of their own (ROADMAP W13).
    for name in ("latent_attn_roofline_pct.longdoc",
                 "latent_prefill_roofline_pct.longdoc",
                 "kda_time_pct.glm53flash", "dsa_time_pct.glm53flash"):
        assert CELL not in by_name[name]["workloads"]


def test_the_rehearsal_listing_holds_the_tiny_cell():
    with open(os.path.join(HERE, "rehearsal-motif.json")) as f:
        listing = json.load(f)
    assert [c["name"] for c in listing["workloads"]] == ["tiny-motif"]
    with open(os.path.join(HERE, "configs", "tiny-motif.json")) as f:
        tiny = json.load(f)
    cfg = family.config(tiny, max_seq=tiny["engine"]["max_seq"])
    assert cfg.pattern == "RDREAERERE" and cfg.experts_held == (0, 4)
    for metric in listing["per_layer"]:
        path = os.path.join(BENCH, "layer_metrics", f"{metric['name']}.json")
        assert os.path.exists(path), metric["name"]
