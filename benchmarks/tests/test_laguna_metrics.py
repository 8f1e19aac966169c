"""What the Laguna serving cell adds to the benchmark, on made-up events
and counters: the band's operations and bytes against counts made by
hand at the published widths, each new metric's reducer on a trace made
by hand, `check_problems` either side of each limit, the
configuration's counts and published numbers, and the rehearsal listing
that holds the tiny cell."""

import importlib
import json
import os

import pytest

from benchmarks.models import laguna as family
from benchmarks.traceread import OPS, PROGRAMS, Event

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
D = "/device:TPU:0"
TPU = {"platform": "tpu", "kind": "TPU v5 lite"}
CELL = "laguna-longdoc-16"
NEW = (
    "window_attn_time_pct", "window_attn_roofline_pct", "full_attn_time_pct",
    "moe_time_pct", "moe_dispatch_time_pct", "moe_sorted_rows_pct",
    "experts_touched_pct", "prefill_device_share_pct", "decode_device_ms",
    "device_idle_pct", "batch_occupancy_pct",
)

PREFILL = """
HloModule jit_hybrid_prefill_32_of_128
ENTRY %main {
  %fusion.1 = bf16[8,2560,128]{2,1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(hybrid_prefill_32_of_128)/attn:window/concatenate"}
  %custom-call.1 = bf16[2048,9216]{1,0} custom-call(%s, %q, %k, %v), custom_call_target="tpu_custom_call", metadata={op_name="jit(hybrid_prefill_32_of_128)/attn:window/jit(window_attention)/pallas_call"}
  %fusion.2 = bf16[16,512,8,128]{3,2,1,0} fusion(%a), kind=kLoop, metadata={op_name="jit(hybrid_prefill_32_of_128)/attn:window_write/dynamic_update_slice"}
  %custom-call.2 = bf16[2048,6144]{1,0} custom-call(%s, %q, %k, %v), custom_call_target="tpu_custom_call", metadata={op_name="jit(hybrid_prefill_32_of_128)/attn:full/jit(prefill_attention)/pallas_call"}
  %ragged-dot.1 = bf16[1024,1024]{1,0} custom-call(%rows, %w), custom_call_target="ragged_dot"
  %fusion.4 = bf16[2048,3072]{1,0} fusion(%x), kind=kLoop, metadata={op_name="jit(hybrid_prefill_32_of_128)/moe:combine/add"}
  ROOT %fusion.3 = f32[1,1,50176]{2,1,0} fusion(%x), kind=kOutput, metadata={op_name="jit(hybrid_prefill_32_of_128)/dot_general"}
}
"""
DECODE = """
HloModule jit_hybrid_decode
ENTRY %main {
  %fusion.1 = f32[16,8,9,512]{3,2,1,0} fusion(%s), kind=kOutput, metadata={op_name="jit(hybrid_decode)/attn:window/bgrd,bwgd->bgrw/dot_general"}
  %custom-call.3 = bf16[16,8,6,128]{3,2,1,0} custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", metadata={op_name="jit(hybrid_decode)/attn:full/jit(paged_attention)/pallas_call"}
  %custom-call.2 = bf16[16,3072]{1,0} custom-call(%x, %w), custom_call_target="tpu_custom_call", metadata={op_name="jit(hybrid_decode)/moe:experts/pallas_call"}
  ROOT %fusion.2 = f32[16,50176]{1,0} fusion(%x), kind=kOutput, metadata={op_name="jit(hybrid_decode)/dot_general"}
}
"""


def config():
    with open(os.path.join(BENCH, "configs", "laguna-s21-serve1.json")) as f:
        return json.load(f)


def op(text, start, dur):
    return Event(D, OPS, text.split(" ")[0], start, dur, text)


def prog(name, start, dur):
    return Event(D, PROGRAMS, name, start, dur, name)


@pytest.fixture
def ctx(tmp_path):
    """Two prefill chunk programs of 10 s (the band's keys 1, the band
    kernel 2, the ring's write 1, full attention 2, grouped matmul 1,
    combine 1, head 2) and two decode programs of 5 s (window scores 1,
    paged attention 1, experts 1, head 2), five idle seconds between the
    pairs: a window of 35 s, busy 30."""
    paths = {}
    for name, text in (("jit_hybrid_prefill_32_of_128", PREFILL),
                       ("jit_hybrid_decode", DECODE)):
        paths[name] = str(tmp_path / f"{name}.txt")
        with open(paths[name], "w") as f:
            f.write(text)
    events = []
    for start in (0, 20):
        events += [
            prog("jit_hybrid_prefill_32_of_128", start, 10),
            op("%fusion.1 = bf16[8,2560,128]{2,1,0} fusion(%p)", start, 1),
            op("%custom-call.1 = bf16[2048,9216]{1,0} custom-call(%s, %q, "
               "%k, %v)", start + 1, 2),
            op("%fusion.2 = bf16[16,512,8,128]{3,2,1,0} fusion(%a)",
               start + 3, 1),
            op("%custom-call.2 = bf16[2048,6144]{1,0} custom-call(%s, %q, "
               "%k, %v)", start + 4, 2),
            op("%ragged-dot.1 = bf16[1024,1024]{1,0} custom-call(%rows, %w)",
               start + 6, 1),
            op("%fusion.4 = bf16[2048,3072]{1,0} fusion(%x)", start + 7, 1),
            op("%fusion.3 = f32[1,1,50176]{2,1,0} fusion(%x)", start + 8, 2),
            prog("jit_hybrid_decode", start + 10, 5),
            op("%fusion.1 = f32[16,8,9,512]{3,2,1,0} fusion(%s)",
               start + 10, 1),
            op("%custom-call.3 = bf16[16,8,6,128]{3,2,1,0} custom-call(%q, "
               "%k, %v)", start + 11, 1),
            op("%custom-call.2 = bf16[16,3072]{1,0} custom-call(%x, %w)",
               start + 12, 1),
            op("%fusion.2 = f32[16,50176]{1,0} fusion(%x)", start + 13, 2),
        ]
    # Over the traced steps: 4 prefill programs of 2,048 live tokens,
    # each many windows into its prompt (x 3 window layers), 10 decode
    # steps of 14 decoding slots.
    traced = {"prefill_programs": 4, "window_tokens": 4 * 3 * 2048,
              "prefill_window_pairs": 4 * 3 * 2048 * 512,
              "decode_steps": 10, "slot_steps": 140}
    engine = {"prefill_programs": 40, "window_tokens": 40 * 3 * 1000,
              "prefill_window_pairs": 40 * 3 * 1000 * 500,
              "decode_steps": 100, "slot_steps": 1400,
              "moe_sorted_rows_pct": 52.5, "traced": traced}
    return {"events": events, "device": TPU, "config": config(),
            "traffic": {},
            "counters": {"program_texts": paths, "engine": engine,
                         "experts_touched_pct": 41.5}}


def test_the_bands_operations_and_bytes_by_hand(ctx):
    """At the published shape (72 query heads of 128 over 8 KV heads, a
    window of 512) a (query, key) pair costs q.k and p v for each of 72
    heads, 2 x 128 operations each: 36,864. A token moves its q and its
    result (72 x 128 bf16 each) and its own key and value (8 x 128 bf16
    each): 40,960 B; a program reads each of three layers' carried
    windows once, 2,097,152 B (512 x 8 x 128 x 2 bf16)."""
    conf, engine = ctx["config"], ctx["counters"]["engine"]
    assert family.window_layers(conf) == 3
    assert family.window_bytes_per_slot(conf) == 512 * 8 * 128 * 2 * 2 == 2097152
    pairs = 3 * 2048 * 512  # a traced program's, over its three layers
    assert family.window_attn_flops_per_program(conf, engine) == pairs * 36864.0
    assert family.window_attn_bytes_per_program(conf, engine) == (
        3 * 2048 * 40960 + 3 * 2097152
    )
    # A replica's life where no traced counters were taken.
    life = {k: v for k, v in engine.items() if k != "traced"}
    assert family.window_attn_flops_per_program(conf, life) == (
        3 * 1000 * 500 * 36864.0
    )
    # A program without the counters (this PR's parent), or no program run.
    assert family.window_attn_flops_per_program(conf, {"prefill_programs": 3}) == 0.0
    assert family.window_attn_bytes_per_program(conf, {"prefill_programs": 3}) == 0.0
    assert family.window_attn_bytes_per_program(conf, {"traced": None}) == 0.0


def _metric(ctx, name):
    with open(os.path.join(BENCH, "layer_metrics", f"{name}.json")) as f:
        spec = json.load(f)
    reducer = importlib.import_module(f"benchmarks.reducers.{spec['reducer']}")
    return reducer.reduce(ctx, **spec.get("args", {}))


def test_each_new_metric_reads_a_number(ctx):
    conf, engine = ctx["config"], ctx["counters"]["engine"]
    read = {name: _metric(ctx, f"{name}.laguna") for name in NEW
            if name != "batch_occupancy_pct"}  # host spans: test_hostspans
    assert read["window_attn_time_pct"] == pytest.approx(
        100 * (1 + 2 + 1 + 1) * 2 / 30)
    assert read["full_attn_time_pct"] == pytest.approx(100 * (2 + 1) * 2 / 30)
    assert read["moe_time_pct"] == pytest.approx(100 * (1 + 1 + 1) * 2 / 30)
    assert read["moe_dispatch_time_pct"] == pytest.approx(100 * 1 * 2 / 30)
    assert read["prefill_device_share_pct"] == pytest.approx(100 * 20 / 35)
    assert read["decode_device_ms"] == pytest.approx(5000.0)
    assert read["device_idle_pct"] == pytest.approx(100 * 5 / 35)
    assert read["experts_touched_pct"] == 41.5
    assert read["moe_sorted_rows_pct"] == 52.5
    # The band: compute bounds it at a v5e's peaks (0.59 ms of arithmetic
    # to 0.31 ms of traffic a chunk); two executions, attn:window's 3 s
    # each in the prefill programs (the decode program's is not read).
    by_bytes = family.window_attn_bytes_per_program(conf, engine) / 819e9
    by_flops = family.window_attn_flops_per_program(conf, engine) / 197e12
    assert by_flops > by_bytes
    assert read["window_attn_roofline_pct"] == pytest.approx(
        100 * by_flops * 2 / 6
    )
    # A program that lacks the spans (the parent's): nothing, no raise.
    ctx["counters"]["program_texts"] = {}
    for name in ("window_attn_roofline_pct", "window_attn_time_pct",
                 "full_attn_time_pct"):
        assert _metric(ctx, f"{name}.laguna") is None


def _reading(**what):
    passing = {"logit_max_abs_err": [0.01, 0.02], "finite": True,
               "largest_slack": 0.0, "routes_beyond_epsilon": 0,
               "window_rel_err": 0.0}
    return {**passing, **what}


@pytest.mark.parametrize("key, limit, word", [
    ("logit_max_abs_err", family.LOGIT_TOLERANCE, "logits differ"),
    ("largest_slack", family.MARGIN_EPSILON, "below the reference's cut"),
    ("window_rel_err", family.WINDOW_TOLERANCE, "carried keys or values"),
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


def test_every_new_metric_names_a_reducer_a_function_and_the_cell():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        official = json.load(f)
    listed = {m["name"]: m for m in official["per_layer"]}
    for name in NEW:
        metric = listed[f"{name}.laguna"]
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "serve_tokens_per_s"
        with open(os.path.join(BENCH, "layer_metrics",
                               f"{name}.laguna.json")) as f:
            spec = json.load(f)
        reducer = importlib.import_module(
            f"benchmarks.reducers.{spec['reducer']}"
        )
        assert callable(reducer.reduce)
        for key in ("bytes_fn", "flops_fn"):
            if key in spec["args"]:
                assert callable(getattr(family, spec["args"][key]))
    (cell,) = [c for c in official["workloads"] if c["name"] == CELL]
    assert cell == {**cell, "config": "laguna-s21-serve1",
                    "traffic": "longdoc-closed", "chips": 1}
    assert len(cell["why"]) <= 200
    for metric in official["end_to_end"] + official["per_layer"]:
        if metric["name"] in ("serve_tokens_per_s", "engine_init_s",
                              "replica_ready_lag_s", "http_start_s"):
            assert metric["workloads"][-1] == CELL
    assert len(official["workloads"]) == 10
    assert [c["name"] for c in official["workloads"] if c["chips"] == 4] == [
        "train-4k-fsdp4"]


def test_counts_of_the_configuration():
    """5.572B parameters (the dense layer, four sparse layers of 128 held
    experts, half of the embedding and of the untied head), 512 expert
    slots, every published width in the program's config."""
    conf = config()
    assert round(family.held_parameters(conf) / 1e9, 3) == 5.572
    assert family.held_expert_slots(conf) == 128 * 4
    cfg = family.config(conf, max_seq=16640)
    assert cfg.experts_held == (0, 128) and cfg.num_experts == 256
    assert cfg.pattern == "*DWEWEWE*E" and cfg.vocab_size == 50176
    assert (cfg.d_model, cfg.n_heads, cfg.window_heads, cfg.n_kv_heads,
            cfg.head_dim) == (3072, 48, 72, 8, 128)
    assert (cfg.sliding_window, cfg.dense_d_ff, cfg.norm_eps) == (512, 12288, 1e-6)
    assert (cfg.rotary_dim, cfg.rope_theta) == (64, 500000.0)
    assert cfg.rope_yarn == (128.0, 8192, 32.0, 1.0, 1.4852030263919618)
    assert (cfg.window_rotary_dim, cfg.window_rope_theta) == (128, 10000.0)
    assert cfg.head_gate and not cfg.attn_output_gate and not cfg.qk_norm
    assert (cfg.d_ff, cfg.shared_d_ff, cfg.top_k,
            cfg.routed_scaling_factor) == (1024, 1024, 10, 2.5)
    assert cfg.router_kind == "softmax" and cfg.norm_topk_prob
    assert cfg.expert_kind == "swiglu" and not cfg.tie_word_embeddings
    assert cfg.dense_expert_rows == conf["program"]["dense_expert_rows"]
    eng = conf["engine"]
    assert eng["num_pages"] * eng["page_size"] == eng["max_batch"] * eng["max_seq"]
    # Pages for the two full layers alone; a window a slot for the three.
    pool = 2 * 2 * (eng["num_pages"] + 1) * 8 * eng["page_size"] * 128 * 2
    windows = 3 * eng["max_batch"] * 2097152
    total = family.held_parameters(conf) * 2 + pool + windows
    # The float32 leaves (routers, norms) are 4 MB more.
    assert abs(total - conf["fit"]["argument_bytes"]) < 8e6
    assert 0.25 * 16e9 < total < 15.75 * 2**30
    assert max(conf["fit"]["peak_bytes"]["5"].values()) > 0.25 * 16 * 2**30
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        family.config({**conf, "tie_word_embeddings": True})
    with pytest.raises(ValueError, match="yarn"):
        family.config({**conf, "rope_parameters": {
            **conf["rope_parameters"],
            "full_attention": conf["rope_parameters"]["sliding_attention"],
        }})


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog's entry is in the file with the same
    value (the per-layer lists whole), but the three that `reduced`
    lists, whose published values stand under `published`."""
    row = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        for line in f:
            entry = json.loads(line)
            if entry["name"] == "Laguna-S-2.1":
                row = entry
    conf = config()
    assert conf["source"] == row["source_url"]
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        listed = {c["name"]: c for c in json.load(f)["configs"]}
    assert listed["laguna-s21-serve1"]["source"] == row["source_url"]
    reduced = listed["laguna-s21-serve1"]["reduced"]
    assert sorted(reduced) == sorted(conf["reduced"]) == sorted(conf["published"])
    assert sorted(reduced) == ["num_experts", "num_hidden_layers", "vocab_size"]
    for key, value in row["config"].items():
        if key in reduced:
            assert conf["published"][key] == value and conf[key] != value
        else:
            assert conf[key] == value, key
    # The floors: the dense layer and a whole period after it, at least
    # 8 experts, at least an eighth of the vocabulary.
    assert conf["num_hidden_layers"] == 1 + 4
    assert conf["num_experts"] >= 8
    assert conf["vocab_size"] * 8 >= conf["published"]["vocab_size"]


def test_the_rehearsal_listing_holds_the_tiny_laguna_cell():
    with open(os.path.join(HERE, "rehearsal-laguna.json")) as f:
        listing = json.load(f)
    (cell,) = listing["workloads"]
    with open(os.path.join(HERE, "configs", f"{cell['config']}.json")) as f:
        conf = json.load(f)
    assert os.path.exists(
        os.path.join(HERE, "traffic", f"{cell['traffic']}.json")
    )
    importlib.import_module(f"benchmarks.runners.{conf['runner']}")
    assert conf["runner"] == "serve_family" and conf["model"] == "laguna"
    cfg = family.config(conf)
    assert cfg.pattern == "*DWEWEWE*E" and cfg.experts_held == (0, 4)
    assert sorted(f"{name}.laguna" for name in NEW) == sorted(
        m["name"] for m in listing["per_layer"] if "workloads" in m
    )
