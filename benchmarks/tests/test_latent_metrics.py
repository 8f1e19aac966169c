"""What the latent-attention serving cell adds to the benchmark, on
made-up events and counters: the kernel's operations and bytes against
counts made by hand, the reducer that takes the larger of its two
roofline shares, the configuration's counts, and the rehearsal listing
that holds the tiny latent cell."""

import json
import os

import pytest

from benchmarks.models import pangu_ultra_moe as family
from benchmarks.reducers import roofline_share
from benchmarks.traceread import OPS, PROGRAMS, Event

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
D = "/device:TPU:0"
TPU = {"platform": "tpu", "kind": "TPU v5 lite"}

DECODE = """
HloModule jit_latent_decode
ENTRY %main {
  %fusion.1 = bf16[32,1536]{1,0} fusion(%p), kind=kOutput, metadata={op_name="jit(latent_decode)/mla:q/dot_general"}
  %custom-call.1 = bf16[32,128,512]{2,1,0} custom-call(%q, %pool), custom_call_target="tpu_custom_call", metadata={op_name="jit(latent_decode)/mla:attend/jit(latent_paged_attention)/pallas_call"}
  ROOT %fusion.2 = f32[32,19200]{1,0} fusion(%x), kind=kOutput, metadata={op_name="jit(latent_decode)/dot_general"}
}
"""


def config():
    with open(os.path.join(BENCH, "configs", "pangu-ultra-moe-serve1.json")) as f:
        return json.load(f)


def op(text, start, dur):
    return Event(D, OPS, text.split(" ")[0], start, dur, text)


def prog(name, start, dur):
    return Event(D, PROGRAMS, name, start, dur, name)


@pytest.fixture
def ctx(tmp_path):
    path = str(tmp_path / "jit_latent_decode.txt")
    with open(path, "w") as f:
        f.write(DECODE)
    events = []
    for start in (0, 10):
        events += [
            prog("jit_latent_decode", start, 10),
            op("%fusion.1 = bf16[32,1536]{1,0} fusion(%p), kind=kOutput", start, 4),
            op("%custom-call.1 = bf16[32,128,512]{2,1,0} custom-call(%q, %pool)",
               start + 4, 2),
            op("%fusion.2 = f32[32,19200]{1,0} fusion(%x), kind=kOutput",
               start + 6, 4),
        ]
    # 100 decode steps that attended 2,000 live pages each.
    engine = {"decode_steps": 100, "attn_pages_live": 200_000}
    return {"events": events, "device": TPU, "config": config(),
            "counters": {"program_texts": {"jit_latent_decode": path},
                         "engine": engine}}


def test_the_kernels_bytes_and_operations_are_the_live_cells(ctx):
    conf, engine = ctx["config"], ctx["counters"]["engine"]
    tokens = 2000 * 64  # live pages a step x page size, one layer
    assert family.latent_attn_bytes_per_decode_step(conf, engine) == (
        tokens * 5 * 576 * 2
    )
    assert family.latent_attn_flops_per_decode_step(conf, engine) == (
        tokens * 5 * 2 * 128 * (576 + 512)
    )
    assert family.latent_attn_bytes_per_decode_step(conf, {}) == 0.0
    # 242 operations a byte: a v5e's ridge (197e12 / 819e9 = 240.5).
    ratio = (family.latent_attn_flops_per_decode_step(conf, engine)
             / family.latent_attn_bytes_per_decode_step(conf, engine))
    assert round(ratio, 1) == 241.8


def test_counters_of_the_traced_steps_come_before_a_replicas_life(ctx):
    """Where the server took the engine's counters over the traced steps
    (`server_family`), the kernels' bytes and operations are those
    steps'; the prefill kernel's are its causal pairs x 128 heads."""
    conf = ctx["config"]
    life = {"decode_steps": 100, "attn_pages_live": 200_000,
            "latent_prefill_programs": 10, "latent_prefill_pairs": 10_000}
    traced = {"decode_steps": 10, "attn_pages_live": 40_000,
              "latent_prefill_programs": 2, "latent_prefill_pairs": 6_000}
    assert family.latent_attn_bytes_per_decode_step(
        conf, {**life, "traced": traced}
    ) == 2 * family.latent_attn_bytes_per_decode_step(conf, life)
    assert family.prefill_attn_flops_per_program(conf, life) == (
        1_000 * 128 * 2 * (192 + 128)
    )
    assert family.prefill_attn_flops_per_program(
        conf, {**life, "traced": traced}
    ) == 3_000 * 128 * 2 * 320
    assert family.prefill_attn_bytes_per_program(conf, life) == 1_000 * 128
    assert family.prefill_attn_flops_per_program(conf, {"traced": None}) == 0.0


def test_the_roofline_share_takes_the_larger_bound(ctx):
    conf, engine = ctx["config"], ctx["counters"]["engine"]
    args = dict(scopes=["mla:attend"], program="latent_decode",
                bytes_fn="latent_attn_bytes_per_decode_step",
                flops_fn="latent_attn_flops_per_decode_step")
    by_flops = family.latent_attn_flops_per_decode_step(conf, engine) / 197e12
    by_bytes = family.latent_attn_bytes_per_decode_step(conf, engine) / 819e9
    assert by_flops > by_bytes  # by half a percent, at the table's peaks
    # Two executions whole in the trace, 2 s of the kernel's own time each.
    assert roofline_share.reduce(ctx, **args) == pytest.approx(
        100 * by_flops * 2 / 4
    )
    # Where the bytes bound it (a device with a higher ridge), they count.
    ctx["device"] = {"platform": "tpu", "kind": "TPU v6 lite"}
    assert roofline_share.reduce(ctx, **args) == pytest.approx(
        100 * (family.latent_attn_bytes_per_decode_step(conf, engine) / 1638e9)
        * 2 / 4
    )
    ctx["device"] = {"platform": "cpu", "kind": "cpu"}
    assert roofline_share.reduce(ctx, **args) is None
    ctx["device"] = TPU
    assert roofline_share.reduce(ctx, **{**args, "scopes": ["no:such"]}) is None
    ctx["counters"]["program_texts"] = {}
    assert roofline_share.reduce(ctx, **args) is None


def test_counts_of_the_configuration():
    """4.92B parameters as the issue counted them (1 dense + 4 expert
    layers of 16 held experts, an eighth of the vocabulary), 64 expert
    slots, every published width in the program's config."""
    conf = config()
    assert round(family.held_parameters(conf) / 1e9, 2) == 4.92
    assert family.held_expert_slots(conf) == 16 * 4
    cfg = family.config(conf, max_seq=16640)
    assert cfg.experts_held == (0, 16) and cfg.num_experts == 256
    assert cfg.pattern == "DEEEE" and cfg.vocab_size == 19200
    assert (cfg.d_model, cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank) == (
        7680, 128, 1536, 512)
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (
        128, 64, 128)
    assert (cfg.dense_d_ff, cfg.d_ff, cfg.shared_d_ff, cfg.top_k) == (
        18432, 2048, 2048, 8)
    assert cfg.routed_scaling_factor == 2.5 and cfg.norm_topk_prob
    assert cfg.dense_expert_rows == conf["program"]["dense_expert_rows"]
    eng = conf["engine"]
    pool = 5 * (eng["num_pages"] + 1) * eng["page_size"] * cfg.cell_width * 2
    assert eng["num_pages"] * eng["page_size"] == eng["max_batch"] * eng["max_seq"]
    # Arguments: over the 25% floor of a chip's memory, under what a
    # program may use.
    total = family.held_parameters(conf) * 2 + pool
    assert 0.25 * 16e9 < total < 15.75 * 2**30
    with pytest.raises(ValueError, match="multi-token-prediction"):
        family.config({**conf, "num_nextn_predict_layers": 1})
    with pytest.raises(ValueError, match="sublayer's output"):
        family.config({**conf, "sandwich_norm": False})


def test_the_configuration_keeps_every_published_number():
    """Every number of the catalog's entry is in the file under the same
    key, but the five that `reduced` lists, whose published values stand
    under `published`."""
    row = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        for line in f:
            entry = json.loads(line)
            if entry["name"] == "openPangu-Ultra-MoE-718B":
                row = entry
    conf = config()
    assert conf["source"] == row["source_url"]
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        listed = {c["name"]: c for c in json.load(f)["configs"]}
    reduced = listed["pangu-ultra-moe-serve1"]["reduced"]
    assert sorted(reduced) == sorted(conf["reduced"]) == sorted(conf["published"])
    for key, value in row["config"].items():
        if key in reduced:
            assert conf["published"][key] == value and conf[key] != value
        else:
            assert conf[key] == value, key


def test_the_rehearsal_listing_holds_the_tiny_latent_cell():
    import importlib

    with open(os.path.join(HERE, "rehearsal-latent.json")) as f:
        listing = json.load(f)
    (cell,) = listing["workloads"]
    with open(os.path.join(HERE, "configs", f"{cell['config']}.json")) as f:
        conf = json.load(f)
    assert os.path.exists(
        os.path.join(HERE, "traffic", f"{cell['traffic']}.json")
    )
    importlib.import_module(f"benchmarks.runners.{conf['runner']}")
    assert conf["runner"] == "serve_family" and conf["model"] == "pangu_ultra_moe"
    assert family.config(conf).pattern == "DEEE"
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        official = json.load(f)
    new = [m["name"] for m in official["per_layer"]
           if m.get("workloads") == ["pangu-longdoc-16"]]
    assert sorted(new) == sorted(
        m["name"] for m in listing["per_layer"] if "workloads" in m
    )
    for name in new:
        assert os.path.exists(
            os.path.join(BENCH, "layer_metrics", f"{name}.json")
        )
