"""What the Granite 4.0-H serving cell adds to the benchmark, on made-up
events and counters: the chunked scan's operations and bytes against
counts made by hand at one shape, each new metric's reducer on a trace
made by hand, the configuration's counts and published numbers, and the
rehearsal listing that holds the tiny cell."""

import importlib
import json
import os

import pytest

from benchmarks.models import granite_hybrid as family
from benchmarks.traceread import OPS, PROGRAMS, Event

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
D = "/device:TPU:0"
TPU = {"platform": "tpu", "kind": "TPU v5 lite"}
CELL = "granite-longdoc-16"

PREFILL = """
HloModule jit_hybrid_prefill_32_of_128
ENTRY %main {
  %fusion.1 = bf16[2048,16768]{1,0} fusion(%p), kind=kOutput, metadata={op_name="jit(hybrid_prefill_32_of_128)/ssm:in_proj/dot_general"}
  %fusion.2 = f32[8,1,128,256,256]{4,3,2,1,0} fusion(%a), kind=kLoop, metadata={op_name="jit(hybrid_prefill_32_of_128)/ssm:scan/exp"}
  %custom-call.1 = bf16[2048,4096]{1,0} custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", metadata={op_name="jit(hybrid_prefill_32_of_128)/jit(prefill_attention)/pallas_call"}
  %ragged-dot.1 = bf16[20480,768]{1,0} custom-call(%rows, %w), custom_call_target="ragged_dot"
  ROOT %fusion.3 = f32[1,1,50176]{2,1,0} fusion(%x), kind=kOutput, metadata={op_name="jit(hybrid_prefill_32_of_128)/dot_general"}
}
"""
DECODE = """
HloModule jit_hybrid_decode
ENTRY %main {
  %fusion.1 = f32[32,128,64,128]{3,2,1,0} fusion(%s), kind=kLoop, metadata={op_name="jit(hybrid_decode)/ssm:update/mul"}
  %custom-call.2 = bf16[32,4096]{1,0} custom-call(%x, %w), custom_call_target="tpu_custom_call", metadata={op_name="jit(hybrid_decode)/moe:experts/pallas_call"}
  ROOT %fusion.2 = f32[32,50176]{1,0} fusion(%x), kind=kOutput, metadata={op_name="jit(hybrid_decode)/dot_general"}
}
"""


def config():
    with open(os.path.join(BENCH, "configs", "granite4hsmall-serve1.json")) as f:
        return json.load(f)


def op(text, start, dur):
    return Event(D, OPS, text.split(" ")[0], start, dur, text)


def prog(name, start, dur):
    return Event(D, PROGRAMS, name, start, dur, name)


@pytest.fixture
def ctx(tmp_path):
    """Two prefill chunk programs of 10 s (in_proj 2, scan 3, attention
    1, grouped matmul 2, head 2) and two decode programs of 5 s (state
    update 2, experts 1, head 2), five idle seconds between the
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
            op("%fusion.1 = bf16[2048,16768]{1,0} fusion(%p)", start, 2),
            op("%fusion.2 = f32[8,1,128,256,256]{4,3,2,1,0} fusion(%a)",
               start + 2, 3),
            op('%custom-call.1 = bf16[2048,4096]{1,0} custom-call(%q, %k, %v), '
               'custom_call_target="tpu_custom_call", metadata={op_name="'
               'jit(hybrid_prefill_32_of_128)/jit(prefill_attention)/pallas_call"}',
               start + 5, 1),
            op("%ragged-dot.1 = bf16[20480,768]{1,0} custom-call(%rows, %w)",
               start + 6, 2),
            op("%fusion.3 = f32[1,1,50176]{2,1,0} fusion(%x)", start + 8, 2),
            prog("jit_hybrid_decode", start + 10, 5),
            op("%fusion.1 = f32[32,128,64,128]{3,2,1,0} fusion(%s)",
               start + 10, 2),
            op("%custom-call.2 = bf16[32,4096]{1,0} custom-call(%x, %w)",
               start + 12, 1),
            op("%fusion.2 = f32[32,50176]{1,0} fusion(%x)", start + 13, 2),
        ]
    # Over the traced steps: 4 prefill programs of 2,048 live tokens
    # (x 9 Mamba layers), 10 decode steps of 16 decoding slots.
    traced = {"prefill_programs": 4, "ssm_scan_tokens": 4 * 9 * 2048,
              "decode_steps": 10, "slot_steps": 160}
    engine = {"prefill_programs": 40, "ssm_scan_tokens": 40 * 9 * 1000,
              "decode_steps": 100, "slot_steps": 3200, "traced": traced}
    return {"events": events, "device": TPU, "config": config(),
            "traffic": {},
            "counters": {"program_texts": paths, "engine": engine,
                         "experts_touched_pct": 31.5}}


def test_the_scans_operations_and_bytes_by_hand(ctx):
    """At the published shape (128 heads of 64, state 128, one group,
    chunk 256) one token in one Mamba layer: 128.5 pairs within its
    chunk, each a 2 x 128 score and 2 x 64 x 128 of weighted x; the
    chunk's state and the read of the carried state 2 x 64 x 128 x 128
    each. Bytes: x and y 8,192 bf16 each, B and C 128 bf16 each, dt 128
    float32; a program reads and writes nine float32 states."""
    conf, engine = ctx["config"], ctx["counters"]["engine"]
    per_token = 128.5 * (256 + 16384) + 2 * 2097152
    assert family.scan_flops_per_token(conf) == per_token == 6332544.0
    tokens = 9 * 2048  # a traced program's live tokens x Mamba layers
    assert family.scan_flops_per_program(conf, engine) == tokens * per_token
    assert family.scan_bytes_per_program(conf, engine) == (
        tokens * (2 * 16384 + 2 * 256 + 512) + 2 * 9 * 4194304
    )
    # A replica's life where no traced counters were taken: 1,000 a layer.
    life = {k: v for k, v in engine.items() if k != "traced"}
    assert family.scan_flops_per_program(conf, life) == 9000 * per_token
    assert family.scan_flops_per_program(conf, {}) == 0.0
    assert family.scan_bytes_per_program(conf, {"traced": None}) == 0.0
    # 16 decoding slots x 9 layers x (4.19 MB + a 50,688 B tail), twice.
    assert family.ssm_state_bytes_per_decode_step(conf, engine) == (
        2 * 16 * 9 * (4194304 + 3 * 8448 * 2)
    )


def _metric(ctx, name):
    with open(os.path.join(BENCH, "layer_metrics", f"{name}.json")) as f:
        spec = json.load(f)
    reducer = importlib.import_module(f"benchmarks.reducers.{spec['reducer']}")
    return reducer.reduce(ctx, **spec.get("args", {}))


def test_each_new_metric_reads_a_number(ctx):
    conf, engine = ctx["config"], ctx["counters"]["engine"]
    read = {name: _metric(ctx, f"{name}.granite") for name in (
        "ssm_time_pct", "ssm_scan_time_pct", "ssm_scan_roofline_pct",
        "moe_time_pct", "prefill_attn_time_pct", "prefill_device_share_pct",
        "decode_device_ms", "ssm_state_hbm_pct", "device_idle_pct",
        "experts_touched_pct",
    )}
    assert read["ssm_time_pct"] == pytest.approx(100 * (2 + 3 + 2) * 2 / 30)
    assert read["ssm_scan_time_pct"] == pytest.approx(100 * 3 * 2 / 30)
    assert read["moe_time_pct"] == pytest.approx(100 * (2 + 1) * 2 / 30)
    assert read["prefill_attn_time_pct"] == pytest.approx(100 * 1 * 2 / 30)
    assert read["prefill_device_share_pct"] == pytest.approx(100 * 20 / 35)
    assert read["decode_device_ms"] == pytest.approx(5000.0)
    assert read["device_idle_pct"] == pytest.approx(100 * 5 / 35)
    assert read["experts_touched_pct"] == 31.5
    # The scan: bytes bound it at a v5e's peaks (95 us of traffic to
    # 64 us of arithmetic a layer and chunk); two executions, 3 s each.
    by_bytes = family.scan_bytes_per_program(conf, engine) / 819e9
    by_flops = family.scan_flops_per_program(conf, engine) / 197e12
    assert by_bytes > by_flops
    assert read["ssm_scan_roofline_pct"] == pytest.approx(
        100 * by_bytes * 2 / 6
    )
    assert read["ssm_state_hbm_pct"] == pytest.approx(
        100 * family.ssm_state_bytes_per_decode_step(conf, engine) * 2 / 4
        / 819e9
    )
    # A program that lacks the spans (the parent's): nothing, no raise.
    ctx["counters"]["program_texts"] = {}
    assert _metric(ctx, "ssm_scan_roofline_pct.granite") is None
    assert _metric(ctx, "ssm_state_hbm_pct.granite") is None
    assert _metric(ctx, "ssm_time_pct.granite") is None


def test_counts_of_the_configuration():
    """4.757B parameters as the issue counted them (ten layers of 36 held
    experts, half the tied vocabulary), 360 expert slots, every
    published width in the program's config."""
    conf = config()
    assert round(family.held_parameters(conf) / 1e9, 3) == 4.757
    assert family.held_expert_slots(conf) == 36 * 10
    cfg = family.config(conf, max_seq=16640)
    assert cfg.experts_held == (0, 36) and cfg.num_experts == 72
    assert cfg.pattern == "MEMEMEMEME*EMEMEMEME" and cfg.vocab_size == 50176
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (
        4096, 32, 8, 128)
    assert (cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_groups,
            cfg.ssm_state, cfg.conv_kernel, cfg.chunk_size) == (
        128, 64, 1, 128, 4, 256)
    assert (cfg.d_ff, cfg.shared_d_ff, cfg.top_k) == (768, 1536, 10)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_scale, cfg.logits_scaling) == (12.0, 0.22, 1 / 128, 16.0)
    assert cfg.router_kind == "softmax" and cfg.norm_topk_prob
    assert cfg.expert_kind == "swiglu" and cfg.tie_word_embeddings
    assert cfg.dense_expert_rows == conf["program"]["dense_expert_rows"]
    eng = conf["engine"]
    assert eng["num_pages"] * eng["page_size"] == eng["max_batch"] * eng["max_seq"]
    pool = 2 * (eng["num_pages"] + 1) * 8 * eng["page_size"] * 128 * 2
    state = 9 * eng["max_batch"] * (128 * 64 * 128 * 4 + 3 * 8448 * 2)
    total = family.held_parameters(conf) * 2 + pool + state
    # The float32 leaves (routers, norms, convolutions) are 3 MB more.
    assert abs(total - conf["fit"]["argument_bytes"]) < 8e6
    assert 0.25 * 16e9 < total < 15.75 * 2**30
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        family.config({**conf, "tie_word_embeddings": False})


def test_the_configuration_keeps_every_published_number():
    """Every number of the catalog's entry is in the file under the same
    key, but the four that `reduced` lists, whose published values stand
    under `published`."""
    row = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        for line in f:
            entry = json.loads(line)
            if entry["name"] == "granite-4.0-h-small":
                row = entry
    conf = config()
    assert conf["source"] == row["source_url"]
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        listed = {c["name"]: c for c in json.load(f)["configs"]}
    reduced = listed["granite4hsmall-serve1"]["reduced"]
    assert sorted(reduced) == sorted(conf["reduced"]) == sorted(conf["published"])
    for key, value in row["config"].items():
        if key in reduced:
            assert conf["published"][key] == value and conf[key] != value
        else:
            assert conf[key] == value, key
    assert conf["layer_types"] == row["config"]["layer_types"][:10]


def test_the_rehearsal_listing_holds_the_tiny_granite_cell():
    with open(os.path.join(HERE, "rehearsal-granite.json")) as f:
        listing = json.load(f)
    (cell,) = listing["workloads"]
    with open(os.path.join(HERE, "configs", f"{cell['config']}.json")) as f:
        conf = json.load(f)
    assert os.path.exists(
        os.path.join(HERE, "traffic", f"{cell['traffic']}.json")
    )
    importlib.import_module(f"benchmarks.runners.{conf['runner']}")
    assert conf["runner"] == "serve_family" and conf["model"] == "granite_hybrid"
    assert family.config(conf).layers == "MMMMM*MMMM"
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        official = json.load(f)
    new = [m["name"] for m in official["per_layer"]
           if m.get("workloads") == [CELL]]
    assert len(new) == 11
    assert sorted(new) == sorted(
        m["name"] for m in listing["per_layer"] if "workloads" in m
    )
    for name in new:
        assert os.path.exists(
            os.path.join(BENCH, "layer_metrics", f"{name}.json")
        )
    for metric in official["end_to_end"] + official["per_layer"]:
        if metric["name"] in ("serve_tokens_per_s", "engine_init_s",
                              "replica_ready_lag_s", "http_start_s"):
            assert metric["workloads"][-1] == CELL
