"""What the LongCat-Flash serving cell adds to the benchmark, on made-up
events and counters: the family module's bytes and operations (two
attention sublayers a layer, 64 heads) against counts made by hand, the
configuration's counts and published numbers, `moe:zero` counted once
under `moe:combine` by the scope reducer, and the rehearsal listing that
holds the tiny cell."""

import importlib
import json
import os

import pytest

from benchmarks.models import longcat_flash as family
from benchmarks.reducers import program_scope_share, roofline_share
from benchmarks.traceread import OPS, PROGRAMS, Event

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
D = "/device:TPU:0"
TPU = {"platform": "tpu", "kind": "TPU v5 lite"}
CELL, CONFIG = "longcat-agent-32", "longcat-flash-omni-serve1"

DECODE = """
HloModule jit_latent_decode
ENTRY %main {
  %fusion.1 = bf16[32,1536]{1,0} fusion(%p), kind=kOutput, metadata={op_name="jit(latent_decode)/mla:q/dot_general"}
  %custom-call.1 = bf16[32,64,512]{2,1,0} custom-call(%q, %pool), custom_call_target="tpu_custom_call", metadata={op_name="jit(latent_decode)/mla:attend/jit(latent_paged_attention)/pallas_call"}
  %fusion.2 = bf16[32,6144]{1,0} fusion(%u), kind=kOutput, metadata={op_name="jit(latent_decode)/dense:mlp/dot_general"}
  %fusion.3 = f32[32,768]{1,0} fusion(%u), kind=kOutput, metadata={op_name="jit(latent_decode)/moe:route/dot_general"}
  %fusion.4 = bf16[32,6144]{1,0} fusion(%rows), kind=kLoop, metadata={op_name="jit(latent_decode)/moe:combine/add"}
  %fusion.5 = bf16[32,6144]{1,0} fusion(%u, %g), kind=kLoop, metadata={op_name="jit(latent_decode)/moe:combine/moe:zero/mul"}
  ROOT %fusion.6 = f32[32,16384]{1,0} fusion(%x), kind=kOutput, metadata={op_name="jit(latent_decode)/dot_general"}
}
"""
NAMES = ("fusion.1", "custom-call.1", "fusion.2", "fusion.3", "fusion.4",
         "fusion.5", "fusion.6")


def config():
    with open(os.path.join(BENCH, "configs", f"{CONFIG}.json")) as f:
        return json.load(f)


def op(name, start, dur):
    return Event(D, OPS, name, start, dur, f"%{name} = bf16[1]{{0}} fusion()")


def prog(name, start, dur):
    return Event(D, PROGRAMS, name, start, dur, name)


@pytest.fixture
def ctx(tmp_path):
    path = str(tmp_path / "jit_latent_decode.txt")
    with open(path, "w") as f:
        f.write(DECODE)
    events = []
    for start in (0, 20):
        events.append(prog("jit_latent_decode", start, 14))
        events += [op(name, start + 2 * i, 2) for i, name in enumerate(NAMES)]
    # 100 decode steps that attended 2,000 live pages each.
    engine = {"decode_steps": 100, "attn_pages_live": 200_000}
    return {"events": events, "device": TPU, "config": config(),
            "counters": {"program_texts": {"jit_latent_decode": path},
                         "engine": engine}}


def test_the_kernels_bytes_and_operations_count_eight_sublayers_of_64_heads(ctx):
    conf, engine = ctx["config"], ctx["counters"]["engine"]
    assert family.attention_sublayers(conf) == 8
    tokens = 2000 * 64  # live pages a step x page size, one sublayer
    assert family.latent_attn_bytes_per_decode_step(conf, engine) == (
        tokens * 8 * 576 * 2
    )
    assert family.latent_attn_flops_per_decode_step(conf, engine) == (
        tokens * 8 * 2 * 64 * (576 + 512)
    )
    assert family.latent_attn_bytes_per_decode_step(conf, {}) == 0.0
    # 121 operations a byte, half a v5e's ridge (197e12 / 819e9 = 240.5):
    # at 64 heads the bytes bound the decode kernel.
    ratio = (family.latent_attn_flops_per_decode_step(conf, engine)
             / family.latent_attn_bytes_per_decode_step(conf, engine))
    assert round(ratio, 1) == 120.9
    args = dict(scopes=["mla:attend"], program="latent_decode",
                bytes_fn="latent_attn_bytes_per_decode_step",
                flops_fn="latent_attn_flops_per_decode_step")
    by_bytes = family.latent_attn_bytes_per_decode_step(conf, engine) / 819e9
    # Two executions whole in the trace, 2 s of the kernel's own time each.
    assert roofline_share.reduce(ctx, **args) == pytest.approx(
        100 * by_bytes * 2 / 4
    )


def test_counters_of_the_traced_steps_come_before_a_replicas_life():
    conf = config()
    life = {"decode_steps": 100, "attn_pages_live": 200_000,
            "latent_prefill_programs": 10, "latent_prefill_pairs": 10_000}
    traced = {"decode_steps": 10, "attn_pages_live": 40_000,
              "latent_prefill_programs": 2, "latent_prefill_pairs": 6_000}
    assert family.latent_attn_bytes_per_decode_step(
        conf, {**life, "traced": traced}
    ) == 2 * family.latent_attn_bytes_per_decode_step(conf, life)
    # The serving object's pairs are summed over the sublayers already.
    assert family.prefill_attn_flops_per_program(conf, life) == (
        1_000 * 64 * 2 * (192 + 128)
    )
    assert family.prefill_attn_flops_per_program(
        conf, {**life, "traced": traced}
    ) == 3_000 * 64 * 2 * 320
    assert family.prefill_attn_bytes_per_program(conf, life) == 1_000 * 64
    assert family.prefill_attn_flops_per_program(conf, {"traced": None}) == 0.0


def test_moe_zero_is_counted_once_under_moe_combine(ctx):
    """An operation under ``moe:combine/moe:zero`` carries both scopes in
    its one ``op_name``: the readers that list ``moe:combine`` count it
    once, and a reader of ``moe:zero`` alone finds it."""
    busy = 2 * 14.0

    def share(scopes):
        return program_scope_share.reduce(ctx, scopes=scopes, over="busy")

    assert share(["moe:combine"]) == pytest.approx(100 * 2 * 4 / busy)
    assert share(["moe:zero"]) == pytest.approx(100 * 2 * 2 / busy)
    assert share(["moe:combine", "moe:zero"]) == share(["moe:combine"])
    with open(os.path.join(BENCH, "layer_metrics",
                           "moe_time_pct.longdoc.json")) as f:
        moe = json.load(f)["args"]
    assert program_scope_share.reduce(ctx, **moe) == pytest.approx(
        100 * 2 * 6 / busy
    )
    # What waits for room in the list (PERF.md section 7): the dense
    # FFNs' share, by the reducer that is there.
    assert share(["dense:mlp"]) == pytest.approx(100 * 2 * 2 / busy)


def test_counts_of_the_configuration():
    """5.17B parameters held (4 double layers of 16 held experts, an
    eighth of the vocabulary), 64 expert slots, every published width in
    the program's config, the pool two rows a layer."""
    conf = config()
    assert round(family.held_parameters(conf) / 1e9, 2) == 5.17
    assert family.held_expert_slots(conf) == 16 * 4
    eng = conf["engine"]
    cfg = family.config(conf, max_seq=eng["max_seq"])
    assert cfg.experts_held == (0, 16) and cfg.num_experts == 512
    assert cfg.zero_experts == 256 and cfg.top_k == 12
    assert cfg.pattern == "SSSS" and cfg.attn_sublayers == 8
    assert cfg.vocab_size == 16384
    assert (cfg.d_model, cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank) == (
        6144, 64, 1536, 512)
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (
        128, 64, 128)
    assert (cfg.dense_d_ff, cfg.d_ff) == (12288, 2048)
    assert cfg.routed_scaling_factor == 6.0 and not cfg.norm_topk_prob
    assert cfg.q_latent_scale == 2.0
    assert cfg.kv_latent_scale == pytest.approx(3.4641, abs=1e-4)
    assert cfg.rope_theta == 1e7 and cfg.router_kind == "softmax"
    assert cfg.dense_expert_rows == conf["program"]["dense_expert_rows"]
    pool = 8 * (eng["num_pages"] + 1) * eng["page_size"] * cfg.cell_width * 2
    assert eng["num_pages"] * eng["page_size"] == eng["max_batch"] * eng["max_seq"]
    # Arguments: over the 12 GB the issue asks of the fullest device,
    # under what a program may use.
    total = family.held_parameters(conf) * 2 + pool
    assert 12e9 < total < 15.75 * 2**30
    assert max(conf["fit"]["peak_bytes"]["4"].values()) < conf["fit"]["usable_bytes"]
    for key, value in (("attention_method", "MHA"), ("zero_expert_type", "copy")):
        with pytest.raises(ValueError):
            family.config({**conf, key: value})


def test_the_configuration_keeps_every_published_number():
    """Every number of the catalog's entry is in the file under the same
    key, but the three that `reduced` lists, whose published values stand
    under `published`."""
    row = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        for line in f:
            entry = json.loads(line)
            if entry["name"] == "LongCat-Flash-Omni":
                row = entry
    conf = config()
    assert conf["source"] == row["source_url"]
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        listed = {c["name"]: c for c in json.load(f)["configs"]}
    assert listed[CONFIG]["source"] == row["source_url"]
    reduced = listed[CONFIG]["reduced"]
    assert sorted(reduced) == sorted(conf["reduced"]) == sorted(conf["published"])
    for key, value in row["config"].items():
        if key in reduced:
            assert conf["published"][key] == value and conf[key] != value
        else:
            assert conf[key] == value, key


def test_the_cell_and_its_traffic_are_the_issues():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        official = json.load(f)
    (cell,) = [c for c in official["workloads"] if c["name"] == CELL]
    assert cell == {**cell, "config": CONFIG, "traffic": "agent-closed",
                    "chips": 1}
    assert official["workloads"][-1] == cell  # at the end of its list
    assert official["configs"][-1]["name"] == CONFIG
    assert len(official["per_layer"]) == 128  # the list was full
    with open(os.path.join(BENCH, "traffic", "agent-closed.json")) as f:
        traffic = json.load(f)
    assert (traffic["kind"], traffic["clients"], traffic["requests"]) == (
        "closed_loop", 32, 256)
    assert traffic["prompt"] == {"dist": "lognormal", "median": 3072,
                                 "sigma": 0.6, "lo": 1024, "hi": 8192,
                                 "snap": 1024}
    assert traffic["output"] == {"dist": "lognormal", "median": 384,
                                 "sigma": 0.5, "lo": 128, "hi": 1024}
    assert traffic["ramp_s"] == 8 and traffic["schedule_seed"] == 20261004
    assert traffic["permute_block"] == 1
    assert traffic["warm_prompt_lengths"] == traffic["fit_prefill_buckets"] == [
        1024, 2048, 4096, 8192]
    conf = config()
    assert conf["engine"]["max_seq"] == 8192 + 1024
    # Every metric that lists the cell has a reader, and the end-to-end
    # metric each moves is one the cell reports.
    reported = {m["name"] for m in official["end_to_end"]
                if "workloads" not in m or CELL in m["workloads"]}
    assert reported == {"serve_tokens_per_s", "setup_s"}
    mine = [m for m in official["per_layer"] if CELL in m.get("workloads", [])]
    assert len(mine) == 26
    for metric in mine:
        assert metric["moves"] in reported
        assert metric["workloads"][-1] == CELL
        with open(os.path.join(BENCH, "layer_metrics",
                               f"{metric['name']}.json")) as f:
            spec = json.load(f)
        importlib.import_module(f"benchmarks.reducers.{spec['reducer']}")
        for fn in ("bytes_fn", "flops_fn"):
            if fn in spec.get("args", {}):
                assert callable(getattr(family, spec["args"][fn]))


def test_the_rehearsal_listing_holds_the_tiny_longcat_cell():
    with open(os.path.join(HERE, "rehearsal-longcat.json")) as f:
        listing = json.load(f)
    (cell,) = listing["workloads"]
    with open(os.path.join(HERE, "configs", f"{cell['config']}.json")) as f:
        conf = json.load(f)
    assert os.path.exists(
        os.path.join(HERE, "traffic", f"{cell['traffic']}.json")
    )
    importlib.import_module(f"benchmarks.runners.{conf['runner']}")
    assert conf["runner"] == "serve_family" and conf["model"] == "longcat_flash"
    cfg = family.config(conf)
    assert cfg.pattern == "SS" and cfg.experts_held == (0, 4)
    assert cfg.num_experts == 8 and cfg.zero_experts == 4
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        official = json.load(f)
    mine = [m["name"] for m in official["per_layer"]
            if CELL in m.get("workloads", [])]
    assert sorted(mine) == sorted(
        m["name"] for m in listing["per_layer"] if "workloads" in m
    )
    for metric in listing["per_layer"]:
        assert metric.get("workloads", [cell["name"]]) == [cell["name"]]
