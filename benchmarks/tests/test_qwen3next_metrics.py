"""What the Qwen3-Next serving cell adds to the benchmark, on made-up
events and counters: the chunked delta rule's operations and bytes
against counts made by hand at the published widths, each new metric's
reducer on a trace made by hand, the configuration's counts and
published numbers, and the rehearsal listing that holds the tiny cell."""

import importlib
import json
import os

import pytest

from benchmarks.models import qwen3_next as family
from benchmarks.traceread import OPS, PROGRAMS, Event

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
D = "/device:TPU:0"
TPU = {"platform": "tpu", "kind": "TPU v5 lite"}
CELL = "qwen3next-longdoc-16"
NEW = (
    "gdn_time_pct", "gdn_scan_time_pct", "gdn_scan_roofline_pct",
    "gdn_state_hbm_pct", "moe_time_pct", "moe_dispatch_time_pct",
    "moe_sorted_rows_pct", "experts_touched_pct", "prefill_attn_time_pct",
    "prefill_device_share_pct", "decode_device_ms", "device_idle_pct",
    "batch_occupancy_pct",
)

PREFILL = """
HloModule jit_hybrid_prefill_32_of_128
ENTRY %main {
  %fusion.1 = bf16[2048,12288]{1,0} fusion(%p), kind=kOutput, metadata={op_name="jit(hybrid_prefill_32_of_128)/gdn:in_proj/dot_general"}
  %fusion.2 = f32[64,16,2,32,32]{4,3,2,1,0} fusion(%a), kind=kLoop, metadata={op_name="jit(hybrid_prefill_32_of_128)/gdn:scan/exp"}
  %custom-call.1 = bf16[2048,4096]{1,0} custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", metadata={op_name="jit(hybrid_prefill_32_of_128)/jit(prefill_attention)/pallas_call"}
  %ragged-dot.1 = bf16[1024,512]{1,0} custom-call(%rows, %w), custom_call_target="ragged_dot"
  %fusion.4 = bf16[2048,2048]{1,0} fusion(%x), kind=kLoop, metadata={op_name="jit(hybrid_prefill_32_of_128)/moe:combine/add"}
  ROOT %fusion.3 = f32[1,1,75968]{2,1,0} fusion(%x), kind=kOutput, metadata={op_name="jit(hybrid_prefill_32_of_128)/dot_general"}
}
"""
DECODE = """
HloModule jit_hybrid_decode
ENTRY %main {
  %fusion.1 = f32[32,32,128,128]{3,2,1,0} fusion(%s), kind=kLoop, metadata={op_name="jit(hybrid_decode)/gdn:update/mul"}
  %custom-call.2 = bf16[32,2048]{1,0} custom-call(%x, %w), custom_call_target="tpu_custom_call", metadata={op_name="jit(hybrid_decode)/moe:experts/pallas_call"}
  ROOT %fusion.2 = f32[32,75968]{1,0} fusion(%x), kind=kOutput, metadata={op_name="jit(hybrid_decode)/dot_general"}
}
"""


def config():
    with open(os.path.join(BENCH, "configs", "qwen3next-80b-serve1.json")) as f:
        return json.load(f)


def op(text, start, dur):
    return Event(D, OPS, text.split(" ")[0], start, dur, text)


def prog(name, start, dur):
    return Event(D, PROGRAMS, name, start, dur, name)


@pytest.fixture
def ctx(tmp_path):
    """Two prefill chunk programs of 10 s (in_proj 2, the rule 3,
    attention 1, grouped matmul 1, combine 1, head 2) and two decode
    programs of 5 s (state update 2, experts 1, head 2), five idle
    seconds between the pairs: a window of 35 s, busy 30."""
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
            op("%fusion.1 = bf16[2048,12288]{1,0} fusion(%p)", start, 2),
            op("%fusion.2 = f32[64,16,2,32,32]{4,3,2,1,0} fusion(%a)",
               start + 2, 3),
            op('%custom-call.1 = bf16[2048,4096]{1,0} custom-call(%q, %k, %v), '
               'custom_call_target="tpu_custom_call", metadata={op_name="'
               'jit(hybrid_prefill_32_of_128)/jit(prefill_attention)/pallas_call"}',
               start + 5, 1),
            op("%ragged-dot.1 = bf16[1024,512]{1,0} custom-call(%rows, %w)",
               start + 6, 1),
            op("%fusion.4 = bf16[2048,2048]{1,0} fusion(%x)", start + 7, 1),
            op("%fusion.3 = f32[1,1,75968]{2,1,0} fusion(%x)", start + 8, 2),
            prog("jit_hybrid_decode", start + 10, 5),
            op("%fusion.1 = f32[32,32,128,128]{3,2,1,0} fusion(%s)",
               start + 10, 2),
            op("%custom-call.2 = bf16[32,2048]{1,0} custom-call(%x, %w)",
               start + 12, 1),
            op("%fusion.2 = f32[32,75968]{1,0} fusion(%x)", start + 13, 2),
        ]
    # Over the traced steps: 4 prefill programs of 2,048 live tokens
    # (x 3 Gated DeltaNet layers), 10 decode steps of 16 decoding slots.
    traced = {"prefill_programs": 4, "gdn_scan_tokens": 4 * 3 * 2048,
              "decode_steps": 10, "slot_steps": 160}
    engine = {"prefill_programs": 40, "gdn_scan_tokens": 40 * 3 * 1000,
              "decode_steps": 100, "slot_steps": 3200,
              "moe_sorted_rows_pct": 52.5, "traced": traced}
    return {"events": events, "device": TPU, "config": config(),
            "traffic": {},
            "counters": {"program_texts": paths, "engine": engine,
                         "experts_touched_pct": 31.5}}


def test_the_rules_operations_and_bytes_by_hand(ctx):
    """At the published shape (16 key and 32 value heads of 128, chunk
    32) one token in one layer meets 15.5 tokens before it in its chunk
    and 16.5 at or before it. A key head: 15.5 k.k and 16.5 q.k scores
    of 2 x 128. A value head: its row of the solve, 15.5 x 2 x (128 +
    128); W S, Q S and K^T V', 2 x 128 x 128 each; 16.5 weighted rows of
    V', 2 x 128. Bytes: q and k 2,048 bf16 each, v and o 4,096 bf16
    each, beta and g 32 float32 each; a program reads and writes three
    float32 states of 2,097,152 B (32 heads x 128 x 128 x 4)."""
    conf, engine = ctx["config"], ctx["counters"]["engine"]
    assert family.gdn_state_bytes_per_slot(conf) == 32 * 128 * 128 * 4 == 2097152
    per_token = (
        16 * 32 * 256
        + 32 * (15.5 * 512 + 3 * 32768 + 16.5 * 256)
    )
    assert family.gdn_scan_flops_per_token(conf) == per_token == 3665920.0
    tokens = 3 * 2048  # a traced program's live tokens x its three layers
    assert family.gdn_scan_flops_per_program(conf, engine) == tokens * per_token
    assert family.gdn_scan_bytes_per_program(conf, engine) == (
        tokens * (2 * 4096 + 2 * 8192 + 256) + 2 * 3 * 2097152
    )
    # A replica's life where no traced counters were taken: 1,000 a layer.
    life = {k: v for k, v in engine.items() if k != "traced"}
    assert family.gdn_scan_flops_per_program(conf, life) == 3000 * per_token
    # A program without the counter (this PR's parent), or no program run.
    assert family.gdn_scan_flops_per_program(conf, {"prefill_programs": 3}) == 0.0
    assert family.gdn_scan_bytes_per_program(conf, {"traced": None}) == 0.0
    # 16 decoding slots x 3 layers x (2.10 MB + a 49,152 B tail), twice.
    assert family.gdn_state_bytes_per_decode_step(conf, engine) == (
        2 * 16 * 3 * (2097152 + 3 * 8192 * 2)
    )
    assert family.gdn_state_bytes_per_decode_step(conf, {}) == 0.0


def _metric(ctx, name):
    with open(os.path.join(BENCH, "layer_metrics", f"{name}.json")) as f:
        spec = json.load(f)
    reducer = importlib.import_module(f"benchmarks.reducers.{spec['reducer']}")
    return reducer.reduce(ctx, **spec.get("args", {}))


def test_each_new_metric_reads_a_number(ctx):
    conf, engine = ctx["config"], ctx["counters"]["engine"]
    read = {name: _metric(ctx, f"{name}.qwen3next") for name in NEW
            if name != "batch_occupancy_pct"}  # host spans: test_hostspans
    assert read["gdn_time_pct"] == pytest.approx(100 * (2 + 3 + 2) * 2 / 30)
    assert read["gdn_scan_time_pct"] == pytest.approx(100 * 3 * 2 / 30)
    assert read["moe_time_pct"] == pytest.approx(100 * (1 + 1 + 1) * 2 / 30)
    assert read["moe_dispatch_time_pct"] == pytest.approx(100 * 1 * 2 / 30)
    assert read["prefill_attn_time_pct"] == pytest.approx(100 * 1 * 2 / 30)
    assert read["prefill_device_share_pct"] == pytest.approx(100 * 20 / 35)
    assert read["decode_device_ms"] == pytest.approx(5000.0)
    assert read["device_idle_pct"] == pytest.approx(100 * 5 / 35)
    assert read["experts_touched_pct"] == 31.5
    assert read["moe_sorted_rows_pct"] == 52.5
    # The rule: bytes bound it at a v5e's peaks (62 us of traffic to 38
    # us of arithmetic a layer and chunk); two executions, 3 s each.
    by_bytes = family.gdn_scan_bytes_per_program(conf, engine) / 819e9
    by_flops = family.gdn_scan_flops_per_program(conf, engine) / 197e12
    assert by_bytes > by_flops
    assert read["gdn_scan_roofline_pct"] == pytest.approx(
        100 * by_bytes * 2 / 6
    )
    assert read["gdn_state_hbm_pct"] == pytest.approx(
        100 * family.gdn_state_bytes_per_decode_step(conf, engine) * 2 / 4
        / 819e9
    )
    # A program that lacks the spans (the parent's): nothing, no raise.
    ctx["counters"]["program_texts"] = {}
    assert _metric(ctx, "gdn_scan_roofline_pct.qwen3next") is None
    assert _metric(ctx, "gdn_state_hbm_pct.qwen3next") is None
    assert _metric(ctx, "gdn_time_pct.qwen3next") is None


def test_every_new_metric_names_a_reducer_a_function_and_the_cell():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        official = json.load(f)
    listed = {m["name"]: m for m in official["per_layer"]}
    for name in NEW:
        metric = listed[f"{name}.qwen3next"]
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "serve_tokens_per_s"
        with open(os.path.join(BENCH, "layer_metrics",
                               f"{name}.qwen3next.json")) as f:
            spec = json.load(f)
        reducer = importlib.import_module(
            f"benchmarks.reducers.{spec['reducer']}"
        )
        assert callable(reducer.reduce)
        for key in ("bytes_fn", "flops_fn"):
            if key in spec["args"]:
                assert callable(getattr(family, spec["args"][key]))
    (cell,) = [c for c in official["workloads"] if c["name"] == CELL]
    assert cell == {**cell, "config": "qwen3next-80b-serve1",
                    "traffic": "longdoc-closed", "chips": 1}
    for metric in official["end_to_end"] + official["per_layer"]:
        if metric["name"] in ("serve_tokens_per_s", "engine_init_s",
                              "replica_ready_lag_s", "http_start_s"):
            assert CELL in metric["workloads"]


def test_counts_of_the_configuration():
    """3.68B parameters (four layers of 256 held experts, half of the
    embedding and of the untied head), 1,024 expert slots, every
    published width in the program's config."""
    conf = config()
    assert round(family.held_parameters(conf) / 1e9, 3) == 3.678
    assert family.held_expert_slots(conf) == 256 * 4
    cfg = family.config(conf, max_seq=16640)
    assert cfg.experts_held == (0, 256) and cfg.num_experts == 512
    assert cfg.pattern == "GEGEGE*E" and cfg.vocab_size == 75968
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (
        2048, 16, 2, 256)
    assert (cfg.rotary_dim, cfg.rope_theta, cfg.norm_eps) == (64, 1e7, 1e-6)
    assert cfg.qk_norm and cfg.attn_output_gate
    assert (cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim,
            cfg.gdn_value_dim, cfg.conv_kernel, cfg.gdn_conv_dim) == (
        16, 32, 128, 128, 4, 8192)
    assert (cfg.d_ff, cfg.shared_d_ff, cfg.top_k) == (512, 512, 10)
    assert cfg.router_kind == "softmax" and cfg.norm_topk_prob
    assert cfg.expert_kind == "swiglu" and not cfg.tie_word_embeddings
    assert cfg.dense_expert_rows == conf["program"]["dense_expert_rows"]
    assert cfg.gdn_chunk == conf["program"]["gdn_chunk"]
    eng = conf["engine"]
    assert eng["num_pages"] * eng["page_size"] == eng["max_batch"] * eng["max_seq"]
    pool = 2 * (eng["num_pages"] + 1) * 2 * eng["page_size"] * 256 * 2
    state = 3 * eng["max_batch"] * (2097152 + 3 * 8192 * 2)
    total = family.held_parameters(conf) * 2 + pool + state
    # The float32 leaves (routers, norms, convolutions) are 9 MB more.
    assert abs(total - conf["fit"]["argument_bytes"]) < 16e6
    assert 0.25 * 16e9 < total < 15.75 * 2**30
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        family.config({**conf, "tie_word_embeddings": True})


def test_the_configuration_keeps_every_published_number():
    """Every number of the catalog's entry is in the file under the same
    key, but the three that `reduced` lists, whose published values
    stand under `published`."""
    row = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        for line in f:
            entry = json.loads(line)
            if entry["name"] == "Qwen3-Next-80B-A3B-Instruct":
                row = entry
    conf = config()
    assert conf["source"] == row["source_url"]
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        listed = {c["name"]: c for c in json.load(f)["configs"]}
    reduced = listed["qwen3next-80b-serve1"]["reduced"]
    assert sorted(reduced) == sorted(conf["reduced"]) == sorted(conf["published"])
    assert sorted(reduced) == ["num_experts", "num_hidden_layers", "vocab_size"]
    for key, value in row["config"].items():
        if key in reduced:
            assert conf["published"][key] == value and conf[key] != value
        else:
            assert conf[key] == value, key
    assert conf["num_hidden_layers"] % conf["full_attention_interval"] == 0
    assert conf["num_experts"] >= 8
    assert conf["vocab_size"] * 8 >= conf["published"]["vocab_size"]


def test_the_rehearsal_listing_holds_the_tiny_qwen3next_cell():
    with open(os.path.join(HERE, "rehearsal-qwen3next.json")) as f:
        listing = json.load(f)
    (cell,) = listing["workloads"]
    with open(os.path.join(HERE, "configs", f"{cell['config']}.json")) as f:
        conf = json.load(f)
    assert os.path.exists(
        os.path.join(HERE, "traffic", f"{cell['traffic']}.json")
    )
    importlib.import_module(f"benchmarks.runners.{conf['runner']}")
    assert conf["runner"] == "serve_family" and conf["model"] == "qwen3_next"
    assert family.config(conf).pattern == "GEGEGE*E"
    assert sorted(f"{name}.qwen3next" for name in NEW) == sorted(
        m["name"] for m in listing["per_layer"] if "workloads" in m
    )
