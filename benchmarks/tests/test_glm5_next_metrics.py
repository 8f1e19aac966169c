"""What the GLM-5.3-Flash serving cell adds to the benchmark, on made-up
events and counters: the operations and bytes of the per-channel delta
rule, the indexer, the sparse attention and the residual mixing against
counts made by hand at the published widths, each new metric's reducer
on a trace made by hand, `check_problems` either side of each limit, the
configuration's counts and published numbers, and the rehearsal listing
that holds the tiny cell."""

import importlib
import json
import os

import pytest

from benchmarks.models import glm5_next as family
from benchmarks.traceread import OPS, PROGRAMS, Event

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
D = "/device:TPU:0"
TPU = {"platform": "tpu", "kind": "TPU v5 lite"}
CELL = "glm53flash-longctx-16"
NEW = (
    "kda_time_pct", "kda_scan_roofline_pct", "dsa_time_pct",
    "dsa_attend_roofline_pct", "dsa_select_time_pct", "mhc_time_pct",
    "moe_time_pct",
)
# Entries the benchmark had whose reducers take nothing of a cell (a
# pattern program's name, a span, a counter of `stats()`): the cell is
# appended to their lists, since the per-layer list is full at 128.
SHARED = (
    "device_idle_pct.laguna", "prefill_device_share_pct.laguna",
    "decode_device_ms.laguna", "batch_occupancy_pct.laguna",
    "experts_touched_pct.laguna", "moe_dispatch_time_pct.laguna",
    "moe_sorted_rows_pct.laguna",
)

PREFILL = """
HloModule jit_hybrid_prefill_32_of_256
ENTRY %main {
  %fusion.1 = f32[2048,4,4]{2,1,0} fusion(%x), kind=kLoop, metadata={op_name="jit(hybrid_prefill_32_of_256)/mhc:mix/while"}
  %fusion.2 = f32[32,64,64,64]{3,2,1,0} fusion(%q, %k), kind=kOutput, metadata={op_name="jit(hybrid_prefill_32_of_256)/kda:scan/nhaik,nhajk->nhaij/dot_general"}
  %fusion.3 = bf16[2048,4096]{1,0} fusion(%o), kind=kOutput, metadata={op_name="jit(hybrid_prefill_32_of_256)/kda:out/dot_general"}
  %fusion.4 = f32[2048,4096]{1,0} fusion(%q, %k), kind=kOutput, metadata={op_name="jit(hybrid_prefill_32_of_256)/dsa:index/while/body/dot_general"}
  %sort.1 = (f32[2048,4096]{1,0}, s32[2048,4096]{1,0}) sort(%s, %i), metadata={op_name="jit(hybrid_prefill_32_of_256)/dsa:select/top_k"}
  %fusion.5 = bf16[128,2048,512]{2,1,0} fusion(%pool, %ids), kind=kLoop, metadata={op_name="jit(hybrid_prefill_32_of_256)/dsa:attend/while/body/gather"}
  %fusion.6 = bf16[2048,4,4096]{2,1,0} fusion(%x, %y), kind=kLoop, metadata={op_name="jit(hybrid_prefill_32_of_256)/mhc:spread/add"}
  %custom-call.1 = bf16[16384,2048]{1,0} custom-call(%rows, %w), custom_call_target="tpu_custom_call", metadata={op_name="jit(hybrid_prefill_32_of_256)/moe:experts/jit(_grouped_rows)/pallas_call"}
  ROOT %fusion.7 = f32[1,1,19360]{2,1,0} fusion(%x), kind=kOutput, metadata={op_name="jit(hybrid_prefill_32_of_256)/dot_general"}
}
"""
DECODE = """
HloModule jit_hybrid_decode
ENTRY %main {
  %custom-call.2 = f32[4,16,64,128,128]{4,3,2,1,0} custom-call(%s), custom_call_target="tpu_custom_call", metadata={op_name="jit(hybrid_decode)/kda:step/jit(kda_state_step)/pallas_call"}
  %fusion.1 = bf16[16,64,2052]{2,1,0} fusion(%q, %c), kind=kOutput, metadata={op_name="jit(hybrid_decode)/dsa:attend/qhr,qnr->qhn/dot_general"}
  %custom-call.3 = bf16[16,4096]{1,0} custom-call(%x, %w), custom_call_target="tpu_custom_call", metadata={op_name="jit(hybrid_decode)/moe:experts/pallas_call"}
  ROOT %fusion.2 = f32[16,19360]{1,0} fusion(%x), kind=kOutput, metadata={op_name="jit(hybrid_decode)/dot_general"}
}
"""


def config():
    with open(os.path.join(BENCH, "configs", "glm53flash-serve1.json")) as f:
        return json.load(f)


def op(text, start, dur):
    return Event(D, OPS, text.split(" ")[0], start, dur, text)


def prog(name, start, dur):
    return Event(D, PROGRAMS, name, start, dur, name)


@pytest.fixture
def ctx(tmp_path):
    """Two prefill chunk programs of 12 s (mix 1, the rule 3, KDA's
    output 1, indexer 1, top-k 1, attend 2, spread 1, experts 1, head 1)
    and two decode programs of 4 s (state 1, attend 1, experts 1, head
    1), four idle seconds between the pairs: a window of 36 s, busy 32."""
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
            op("%fusion.1 = f32[2048,4,4]{2,1,0} fusion(%x)", start, 1),
            op("%fusion.2 = f32[32,64,64,64]{3,2,1,0} fusion(%q, %k)",
               start + 1, 3),
            op("%fusion.3 = bf16[2048,4096]{1,0} fusion(%o)", start + 4, 1),
            op("%fusion.4 = f32[2048,4096]{1,0} fusion(%q, %k)", start + 5, 1),
            op("%sort.1 = (f32[2048,4096]{1,0}, s32[2048,4096]{1,0}) "
               "sort(%s, %i)", start + 6, 1),
            op("%fusion.5 = bf16[128,2048,512]{2,1,0} fusion(%pool, %ids)",
               start + 7, 2),
            op("%fusion.6 = bf16[2048,4,4096]{2,1,0} fusion(%x, %y)",
               start + 9, 1),
            op("%custom-call.1 = bf16[16384,2048]{1,0} custom-call(%rows, "
               "%w)", start + 10, 1),
            op("%fusion.7 = f32[1,1,19360]{2,1,0} fusion(%x)", start + 11, 1),
            prog("jit_hybrid_decode", start + 12, 4),
            op("%custom-call.2 = f32[4,16,64,128,128]{4,3,2,1,0} "
               "custom-call(%s)", start + 12, 1),
            op("%fusion.1 = bf16[16,64,2052]{2,1,0} fusion(%q, %c)",
               start + 13, 1),
            op("%custom-call.3 = bf16[16,4096]{1,0} custom-call(%x, %w)",
               start + 14, 1),
            op("%fusion.2 = f32[16,19360]{1,0} fusion(%x)", start + 15, 1),
        ]
    # Over the traced steps: 4 prefill programs of 2,048 live tokens, the
    # third chunk of a prompt each (positions 4,096 to 6,143).
    tokens = 4 * 2048
    positions = range(4096, 6144)
    traced = {
        "prefill_programs": 4, "kda_scan_tokens": 4 * tokens,
        "dsa_tokens": tokens, "mhc_tokens": 10 * tokens,
        "dsa_index_pairs": 4 * sum(t // 4 for t in positions),
        "dsa_selected_pairs": 4 * sum(
            min(t // 4, 512) * 4 + t % 4 + 1 for t in positions),
        "dsa_causal_pairs": 4 * sum(t + 1 for t in positions),
        "decode_steps": 10, "slot_steps": 140,
    }
    engine = {**{k: v * 10 for k, v in traced.items()}, "traced": traced}
    return {"events": events, "device": TPU, "config": config(),
            "traffic": {}, "counters": {"program_texts": paths,
                                        "engine": engine}}


def test_the_operations_and_bytes_by_hand(ctx):
    """At the published shapes. The rule at the file's chunk of 32, a
    head of 128 and 64 heads: a token meets 15.5 before it and 16.5 up to
    it, so per head 32 x 256 (k.k and q.k) + 15.5 x 512 (the solve's
    rows) + 3 x 32,768 (W S, Q S, K^T V') + 16.5 x 256 (scores x V') =
    118,656 operations (139,136 at a chunk of 64);
    it moves q, k, v, o in bf16 (8,192 wide each), g in float32 (8,192)
    and beta (64): 98,560 B. A selected pair costs 64 heads x 4 x 512, a
    query 64 x 2 x 512 x 512 for the absorption and W_uv. An indexer pair
    32 heads x (2 x 128 + 2). A token's streams in a sublayer: 4 x 4,096
    read and written, 4,096 out and in, bf16: 81,920 B."""
    conf, engine = ctx["config"], ctx["counters"]["engine"]
    assert family.kda_layers(conf) == 4 and family.sparse_layers(conf) == 1
    assert family.kda_scan_flops_per_token(conf) == 64 * 118656.0
    assert family.kda_scan_flops_per_token(
        {**conf, "program": {"kda_chunk": 64}}) == 64 * 139136.0
    per = 4 * 2048  # a program's tokens over its four KDA layers
    assert family.kda_scan_flops_per_program(conf, engine) == per * 64 * 118656.0
    assert family.kda_state_bytes_per_slot(conf) == 64 * 128 * 128 * 4
    assert family.kda_scan_bytes_per_program(conf, engine) == (
        per * 98560 + 2 * 4 * 4194304
    )
    positions = range(4096, 6144)
    selected = sum(min(t // 4, 512) * 4 + t % 4 + 1 for t in positions)
    assert selected == 2048 * 2048 + 2048 * 2.5
    assert family.dsa_attend_flops_per_program(conf, engine) == (
        selected * 64 * 2048.0 + 2048 * 64 * 2.0 * 512 * 512
    )
    index_pairs = sum(t // 4 for t in positions)
    assert family.dsa_index_flops_per_program(conf, engine) == (
        index_pairs * 32 * 258.0
    )
    # The context's cells once: the mean candidates x 4 and half a chunk.
    context = index_pairs / 2048 * 4 + 1024
    assert family.dsa_attend_bytes_per_program(conf, engine) == (
        2048 * (64 * 512 * 2 + 512 * 4) + context * 512 * 2
    )
    assert family.dsa_index_bytes_per_program(conf, engine) == (
        2048 * (32 * 128 * 2 + 32 * 4) + (index_pairs / 2048 + 256) * 128 * 2
    )
    assert family.mhc_bytes_per_program(conf, engine) == (
        10 * 2048 * 81920 + 10 * 4 * 4096 * 24 * 4
    )
    assert family.mhc_flops_per_program(conf, engine) == 10 * 2048 * 983040.0
    # 14 decoding slots: each state and tail read and written, 4 layers.
    assert family.kda_state_bytes_per_decode_step(conf, engine) == (
        2.0 * 14 * 4 * (4194304 + 3 * 24576 * 2)
    )
    # A replica's life where no traced counters were taken.
    life = {k: v for k, v in engine.items() if k != "traced"}
    assert family.kda_scan_flops_per_program(conf, life) == per * 64 * 118656.0
    # A program without the counters (this PR's parent), or no program run.
    for fn in ("kda_scan_flops_per_program", "kda_scan_bytes_per_program",
               "dsa_attend_flops_per_program", "dsa_attend_bytes_per_program",
               "dsa_index_flops_per_program", "dsa_index_bytes_per_program",
               "mhc_bytes_per_program", "mhc_flops_per_program"):
        assert getattr(family, fn)(conf, {"prefill_programs": 3}) == 0.0
        assert getattr(family, fn)(conf, {"traced": None}) == 0.0
    assert family.kda_state_bytes_per_decode_step(
        conf, {"decode_steps": 3, "slot_steps": 9}) == 0.0


def _metric(ctx, name):
    with open(os.path.join(BENCH, "layer_metrics", f"{name}.json")) as f:
        spec = json.load(f)
    reducer = importlib.import_module(f"benchmarks.reducers.{spec['reducer']}")
    return reducer.reduce(ctx, **spec.get("args", {}))


def test_each_new_metric_reads_a_number(ctx):
    conf, engine = ctx["config"], ctx["counters"]["engine"]
    read = {name: _metric(ctx, f"{name}.glm53flash") for name in NEW}
    assert read["kda_time_pct"] == pytest.approx(100 * (3 + 1 + 1) * 2 / 32)
    assert read["dsa_time_pct"] == pytest.approx(100 * (1 + 1 + 2 + 1) * 2 / 32)
    assert read["mhc_time_pct"] == pytest.approx(100 * (1 + 1) * 2 / 32)
    assert read["moe_time_pct"] == pytest.approx(100 * (1 + 1) * 2 / 32)
    assert read["dsa_select_time_pct"] == pytest.approx(100 * 1 * 2 / 32)
    # What the list has no room for stays a counter of `stats()`.
    traced = engine["traced"]
    selected = traced["dsa_selected_pairs"] / traced["dsa_causal_pairs"]
    assert 0.35 < selected < 0.45  # 2,050 of a mean 5,120
    # The entries the cell shares with `laguna-longdoc-16`: the pattern
    # programs' names and the engine's spans are the same.
    assert _metric(ctx, "device_idle_pct.laguna") == pytest.approx(100 * 4 / 36)
    assert _metric(ctx, "prefill_device_share_pct.laguna") == pytest.approx(
        100 * 24 / 36)
    assert _metric(ctx, "decode_device_ms.laguna") == pytest.approx(4000.0)
    # The rule: bytes bound it at a v5e's peaks; two executions of 3 s.
    by_bytes = family.kda_scan_bytes_per_program(conf, engine) / 819e9
    by_flops = family.kda_scan_flops_per_program(conf, engine) / 197e12
    assert by_bytes > by_flops
    assert read["kda_scan_roofline_pct"] == pytest.approx(
        100 * by_bytes * 2 / 6)
    # The sparse attention: compute bounds it; two executions of 2 s (the
    # decode program's dsa:attend is not read).
    by_flops = family.dsa_attend_flops_per_program(conf, engine) / 197e12
    assert by_flops > family.dsa_attend_bytes_per_program(conf, engine) / 819e9
    assert read["dsa_attend_roofline_pct"] == pytest.approx(
        100 * by_flops * 2 / 4)
    # A program that lacks the spans and counters (the parent's): nothing,
    # no raise.
    ctx["counters"]["program_texts"] = {}
    ctx["counters"]["engine"] = {"traced": {"prefill_programs": 4}}
    for name in NEW:
        assert _metric(ctx, f"{name}.glm53flash") is None


def _reading(**what):
    passing = {"logit_max_abs_err": [0.01, 0.02], "finite": True,
               "largest_slack": 0.0, "routes_beyond_epsilon": 0,
               "select_slack": 0.0, "select_same_min": 1.0,
               "state_rel_err": 0.0, "first_state_rel_err": 0.0,
               "cell_rel_err": 0.0, "index_rel_err": 0.0}
    return {**passing, **what}


@pytest.mark.parametrize("key, limit, word", [
    ("logit_max_abs_err", family.LOGIT_TOLERANCE, "logits differ"),
    ("largest_slack", family.MARGIN_EPSILON, "sent to an expert"),
    ("select_slack", family.SELECT_MARGIN, "attended a block"),
    ("state_rel_err", family.STATE_TOLERANCE, "delta-rule state differs"),
    ("first_state_rel_err", family.FIRST_STATE_TOLERANCE, "first layer"),
    ("cell_rel_err", family.CELL_TOLERANCE, "latent cells"),
    ("index_rel_err", family.CELL_TOLERANCE, "pooled index keys"),
])
def test_check_problems_either_side_of_each_limit(key, limit, word):
    def reading(value):
        return _reading(**{
            key: [0.0, value] if key == "logit_max_abs_err" else value
        })

    assert family.check_problems(_reading()) == []
    assert family.check_problems(reading(limit * 0.99)) == []
    (problem,) = family.check_problems(reading(limit * 1.01))
    assert word in problem
    if key == "logit_max_abs_err":
        (problem,) = family.check_problems(_reading(finite=False))
        assert word in problem


def test_check_problems_holds_the_share_of_blocks_kept():
    low = family.SELECT_SAME
    assert family.check_problems(_reading(select_same_min=low * 1.01)) == []
    (problem,) = family.check_problems(_reading(select_same_min=low * 0.99))
    assert "picked only" in problem


def test_every_new_metric_names_a_reducer_a_function_and_the_cell():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        official = json.load(f)
    listed = {m["name"]: m for m in official["per_layer"]}
    for name in NEW:
        metric = listed[f"{name}.glm53flash"]
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "serve_tokens_per_s"
        with open(os.path.join(BENCH, "layer_metrics",
                               f"{name}.glm53flash.json")) as f:
            spec = json.load(f)
        reducer = importlib.import_module(
            f"benchmarks.reducers.{spec['reducer']}"
        )
        assert callable(reducer.reduce)
        for key in ("bytes_fn", "flops_fn"):
            if key in spec["args"]:
                assert callable(getattr(family, spec["args"][key]))
    assert sorted(
        m for m in listed if m.endswith(".glm53flash")
    ) == sorted(f"{name}.glm53flash" for name in NEW)
    (cell,) = [c for c in official["workloads"] if c["name"] == CELL]
    assert cell == {**cell, "config": "glm53flash-serve1",
                    "traffic": "longctx-closed", "chips": 1}
    assert len(cell["why"]) <= 200
    on = [m["name"] for m in official["end_to_end"] + official["per_layer"]
          if CELL in m.get("workloads", [])]
    # serve_tokens_per_s, the thirteen lists ISSUE 59 names, seven of
    # `laguna-longdoc-16`'s own that read the same programs and spans,
    # and the seven of its own; 128 per-layer metrics is the most a
    # `BENCHMARK.json` may hold (the contract every PR of this round is
    # given: "`per_layer`: 1 to 128 metrics of single layers").
    assert len(on) == 1 + 13 + len(SHARED) + len(NEW)
    for name in SHARED:
        assert listed[name]["workloads"] == ["laguna-longdoc-16", CELL]
    assert len(official["per_layer"]) <= 128
    assert [c["name"] for c in official["workloads"] if c["chips"] == 4] == [
        "train-4k-fsdp4"]
    with open(os.path.join(BENCH, "traffic", "longctx-closed.json")) as f:
        traffic = json.load(f)
    assert (traffic["kind"], traffic["clients"], traffic["requests"],
            traffic["ramp_s"], traffic["schedule_seed"]) == (
        "closed_loop", 16, 96, 15.0, 20261003)
    assert traffic["prompt"] == {"dist": "lognormal", "median": 16384,
                                 "sigma": 0.6, "lo": 8192, "hi": 65536,
                                 "snap": 2048}
    assert traffic["output"] == {"dist": "lognormal", "median": 128,
                                 "sigma": 0.5, "lo": 64, "hi": 256}
    assert traffic["fit_prefill_buckets"] == traffic["warm_prompt_lengths"] == [
        8192, 16384, 32768, 65536]


def test_counts_of_the_configuration():
    """4.718B parameters (the dense layer, four sparse-FFN layers of 36
    held experts, an eighth of the embedding and of the untied head), 144
    expert slots, every published width in the program's config."""
    conf = config()
    assert round(family.held_parameters(conf) / 1e9, 3) == 4.718
    assert family.held_expert_slots(conf) == 36 * 4
    eng = conf["engine"]
    cfg = family.config(conf, max_seq=eng["max_seq"])
    assert cfg.experts_held == (0, 36) and cfg.num_experts == 288
    assert cfg.pattern == "KDLEKEKEKE" and cfg.vocab_size == 19360
    assert (cfg.d_model, cfg.kda_heads, cfg.kda_head_dim, cfg.conv_kernel,
            cfg.kda_lower, cfg.kda_gate_rank) == (4096, 64, 128, 4, -5.0, 128)
    assert (cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_head_dim,
            cfg.v_head_dim) == (64, 1536, 512, 256, 256)
    assert (cfg.index_heads, cfg.index_head_dim, cfg.index_topk,
            cfg.index_kpool, cfg.index_rotary_dim) == (32, 128, 2048, 4, 64)
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps) == (4, 20, 1e-6)
    assert (cfg.dense_d_ff, cfg.d_ff, cfg.shared_d_ff, cfg.top_k,
            cfg.routed_scaling_factor, cfg.swiglu_limit) == (
        12288, 2048, 2048, 8, 2.5, 10.0)
    assert cfg.router_kind == "sigmoid" and cfg.norm_topk_prob
    assert cfg.kda_chunk == type(cfg).kda_chunk == 32
    assert eng["num_pages"] * eng["page_size"] == eng["max_batch"] * eng["max_seq"]
    pools = (eng["num_pages"] + 1) * eng["page_size"] * (512 + 128 // 4) * 2
    state = 4 * eng["max_batch"] * (4194304 + 3 * 24576 * 2)
    total = family.held_parameters(conf) * 2 + pools + state
    # The float32 leaves (routers, norms, the residual mixing) are 16 MB more.
    assert abs(total - conf["fit"]["argument_bytes"]) < 3.2e7
    assert 0.25 * 16e9 < total < 15.75 * 2**30
    assert max(conf["fit"]["peak_bytes"]["5"].values()) > 0.25 * 16 * 2**30
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        family.config({**conf, "tie_word_embeddings": True})
    with pytest.raises(ValueError, match="num_nextn_predict_layers"):
        family.config({**conf, "num_nextn_predict_layers": 1})
    with pytest.raises(ValueError, match="mhc"):
        family.config({**conf, "mhc": False})


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog's entry is in the file with the same
    value (nested groups whole), but those that `reduced` lists, whose
    published values stand under `published`; every reading the issue
    lists as assumed is under `assumed`."""
    row = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        for line in f:
            entry = json.loads(line)
            if entry["name"] == "GLM-5.3-Flash":
                row = entry
    conf = config()
    assert conf["source"] == row["source_url"]
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        listed = {c["name"]: c for c in json.load(f)["configs"]}
    assert listed["glm53flash-serve1"]["source"] == row["source_url"]
    reduced = listed["glm53flash-serve1"]["reduced"]
    assert sorted(reduced) == sorted(conf["reduced"]) == sorted(conf["published"])
    lists = ("layer_types", "mlp_layer_types", "indexer_types")
    for key, value in row["config"].items():
        if key in lists:
            assert conf[key] == value[2:7]  # published layers 2-6
        elif key in reduced:
            assert conf["published"][key] == value and conf[key] != value
        else:
            assert conf[key] == value, key
    assert conf["num_hidden_layers"] == 1 + 4
    assert conf["n_routed_experts"] * 8 == conf["published"]["n_routed_experts"]
    assert conf["vocab_size"] * 8 == conf["published"]["vocab_size"]
    for item in ("kda_gate", "kda_gate_rank", "kda_init", "index_pooling",
                 "index_topk_unit", "index_rope", "index_query",
                 "index_cache_dtype", "index_tail", "indexer_types",
                 "swiglu_limit", "mhc", "router", "vision"):
        assert conf["assumed"][item]
    assert "EIGHT v5e chips" in conf["deployment"]


def test_the_rehearsal_listing_holds_the_tiny_cell():
    with open(os.path.join(HERE, "rehearsal-glm5-next.json")) as f:
        listing = json.load(f)
    (cell,) = listing["workloads"]
    with open(os.path.join(HERE, "configs", f"{cell['config']}.json")) as f:
        conf = json.load(f)
    assert os.path.exists(
        os.path.join(HERE, "traffic", f"{cell['traffic']}.json")
    )
    importlib.import_module(f"benchmarks.runners.{conf['runner']}")
    assert conf["runner"] == "serve_family" and conf["model"] == "glm5_next"
    cfg = family.config(conf)
    assert cfg.pattern == "KDLEKEKEKE" and cfg.experts_held == (0, 4)
    assert sorted(f"{name}.glm53flash" for name in NEW) == sorted(
        m["name"] for m in listing["per_layer"] if "workloads" in m
    )
