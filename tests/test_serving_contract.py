"""The contract between `LLMEngine` and a model (`llm/serving.py`).

Three things hold it: (a) the engine reads nothing of its serving
object but `Serving`'s public names, names no family's mechanism, and
the imports of ``llm/`` point one way; (b) a fresh engine's `stats()`
has, for each of the eight served families at its tiny configuration,
exactly the keys written below (taken at the commit before the expert
counters left the engine: a key the benchmark reads cannot go missing
in a move); (c) the expert counters `stats()` carries are the plain sum
of the records the programs themselves returned.
"""

import ast
import os
import re

import numpy as np
import pytest

from ray_tpu.llm import engine as engine_module
from ray_tpu.llm.engine import LLMEngine, SamplingParams
from ray_tpu.llm.hybrid_kv import HybridServing
from ray_tpu.llm.latent_kv import LatentServing
from ray_tpu.llm.paged_kv import LlamaServing
from ray_tpu.llm.serving import Serving
from ray_tpu.models.glm5_next import GLM5_NEXT_PRESETS
from ray_tpu.models.laguna import LAGUNA_PRESETS
from ray_tpu.models.llama import PRESETS
from ray_tpu.models.longcat_flash import LONGCAT_PRESETS
from ray_tpu.models.motif import MOTIF_PRESETS
from ray_tpu.models.nemotron_h import NEMOTRON_H_PRESETS
from ray_tpu.models.pangu_ultra_moe import PANGU_PRESETS
from ray_tpu.models.qwen3_next import QWEN3_NEXT_PRESETS

LLM = os.path.dirname(engine_module.__file__)
CONTRACT = {name for name in dir(Serving) if not name.startswith("_")}


def _tree(name):
    with open(os.path.join(LLM, name)) as f:
        return ast.parse(f.read())


# ------------------------------------------------------------ (a) static
def _read_of_serving(tree) -> set:
    """Every attribute `engine.py` takes of `serving` or `self.serving`."""
    read = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Attribute):
            continue
        of = node.value
        if isinstance(of, ast.Name) and of.id == "serving":
            read.add(node.attr)
        elif isinstance(of, ast.Attribute) and of.attr == "serving":
            read.add(node.attr)
    return read


def test_the_engine_reads_only_the_contract():
    read = _read_of_serving(_tree("engine.py"))
    assert len(read) >= 12, read  # the walk finds them
    assert read <= CONTRACT, read - CONTRACT
    assert {"note", "fold", "counters"} <= read


@pytest.mark.parametrize("cls", [LlamaServing, HybridServing, LatentServing])
def test_a_serving_class_adds_no_public_name(cls):
    """A family says what differs under `Serving`'s names; what else it
    needs is its own (underscored), so the engine cannot come to read
    it."""
    assert issubclass(cls, Serving)
    added = {n for n in vars(cls) if not n.startswith("_")} - CONTRACT
    assert not added, added


def test_the_engine_names_no_familys_mechanism():
    """No identifier of `engine.py` names an expert, a router, a
    recurrence or a state kernel; the span `readback:moe_counts` is a
    string, and the one left."""
    family = re.compile(r"expert|router|moe|recurr|state_step|state_kernel")
    tree = _tree("engine.py")
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            names.add(node.id)
        elif isinstance(node, ast.Attribute):
            names.add(node.attr)
        elif isinstance(node, (ast.FunctionDef, ast.ClassDef)):
            names.add(node.name)
        elif isinstance(node, ast.arg):
            names.add(node.arg)
        elif isinstance(node, ast.keyword) and node.arg:
            names.add(node.arg)
    assert not {n for n in names if family.search(n)}
    strings = {
        node.value for node in ast.walk(tree)
        if isinstance(node, ast.Constant) and isinstance(node.value, str)
        and "\n" not in node.value and family.search(node.value)
    }
    assert strings == {"readback:moe_counts"}


@pytest.mark.parametrize(
    "module, below",
    [
        ("serving.py", ()),
        ("paged_kv.py", ("serving",)),
        ("hybrid_kv.py", ("serving", "paged_kv")),
        ("latent_kv.py", ("serving", "paged_kv")),
    ],
)
def test_imports_in_llm_point_down(module, below):
    """`serving.py` at the bottom, `paged_kv.py` on it, `hybrid_kv.py`
    and `latent_kv.py` side by side on that, the engine on top."""
    layers = {"serving", "paged_kv", "hybrid_kv", "latent_kv", "engine"}
    found = set()
    for node in ast.walk(_tree(module)):
        if isinstance(node, ast.ImportFrom) and node.module:
            parts = node.module.split(".")
            if parts[:2] == ["ray_tpu", "llm"]:
                found |= {parts[2]} if len(parts) > 2 else {
                    alias.name for alias in node.names
                }
        elif isinstance(node, ast.Import):
            found |= {
                alias.name.split(".")[2] for alias in node.names
                if alias.name.startswith("ray_tpu.llm.")
            }
    assert found & layers <= set(below), found


def test_the_head_takes_what_it_needs():
    """`_head` is `paged_kv.py`'s, with no config to ask."""
    import inspect

    from ray_tpu.llm import hybrid_kv, latent_kv, paged_kv

    assert "cfg" not in inspect.signature(paged_kv._head).parameters
    assert hybrid_kv._head is paged_kv._head is latent_kv._head


# ------------------------------------------------- (b) the keys of stats()
ENGINE_KEYS = """
active_requests admitted attn_pages_live attn_pages_table between_s_sum
decode_in_flight_pct decode_starved_pct decode_steps decode_steps_alone
decode_steps_in_flight decode_steps_starved device_kind
draft_tokens_accepted draft_tokens_proposed host_s_sum.admit
host_s_sum.decode_dispatch host_s_sum.decode_sync host_s_sum.emit
host_s_sum.first_token host_s_sum.grow_tables host_s_sum.launch
host_s_sum.prefill_chunk host_s_sum.readback host_s_sum.step init_s
kv_write_kernel lock_wait_s_sum overrun_slot_steps paged_attn_kernel
pages_free pages_total param_bytes pipeline_drains platform pool_bytes
preemptions prefill_chunks prefilling queue_wait_s_sum queued_requests
requests_aborted requests_finished requests_submitted slot_steps
state_bytes step_cpu_s_sum step_lock_wait_s_sum step_s_sum steps
tokens_generated
""".split()
# What every serving object says, a Llama's zeros among them.
EXPERT_KEYS = """
experts_touched moe_pairs_here moe_pairs_routed moe_rows_computed
moe_rows_sorted moe_sorted_rows_pct moe_zero_pairs
""".split()
HYBRID_KEYS = """
dsa_causal_pairs dsa_index_pairs dsa_selected_pairs dsa_tokens
gdn_kernel_tokens gdn_scan_tokens index_bytes kda_scan_tokens
latent_bytes latent_cells_expanded mhc_tokens moe_combine_kernel
prefill_attn_pairs
prefill_programs prefill_window_pairs ssm_scan_tokens window_bytes
window_tokens
""".split()
LATENT_KEYS = """
latent_bytes_per_token latent_prefill_pairs latent_prefill_programs
latent_tokens_expanded moe_combine_kernel
""".split()


def _granite_tiny():
    from test_granite_hybrid import CFG

    return CFG


FAMILIES = {
    "llama": (lambda: PRESETS["tiny"], ["prefill_attn_pairs"]),
    "nemotron_h": (
        lambda: NEMOTRON_H_PRESETS["nemotron_h_tiny"],
        HYBRID_KEYS + ["state_step_kernel"],
    ),
    "granite": (_granite_tiny, HYBRID_KEYS + ["state_step_kernel"]),
    "qwen3_next": (
        lambda: QWEN3_NEXT_PRESETS["qwen3_next_tiny"],
        HYBRID_KEYS + ["state_step_kernel"],
    ),
    # No recurrence: windows and pages.
    "laguna": (lambda: LAGUNA_PRESETS["laguna_tiny"], HYBRID_KEYS),
    "glm5_next": (
        lambda: GLM5_NEXT_PRESETS["glm5_next_tiny"],
        HYBRID_KEYS + ["state_step_kernel"],
    ),
    # No recurrence: latent cells in pages and in rings.
    "motif": (lambda: MOTIF_PRESETS["motif_tiny"], HYBRID_KEYS),
    "pangu": (lambda: PANGU_PRESETS["pangu_tiny"], LATENT_KEYS),
    # `zero_expert_*` / `real_experts_*` come with the first routed pair.
    "longcat": (lambda: LONGCAT_PRESETS["longcat_tiny"], LATENT_KEYS),
}


@pytest.mark.parametrize("family", FAMILIES)
def test_a_fresh_engines_stats_have_the_familys_keys(family):
    config, own = FAMILIES[family]
    stats = LLMEngine(config(), max_batch=2, page_size=16).stats()
    want = ENGINE_KEYS + EXPERT_KEYS + own
    assert len(want) == len(set(want))
    assert sorted(stats) == sorted(want)
    assert all(stats[key] == 0 for key in EXPERT_KEYS)


# ------------------------------- (c) the counters are the records' plain sum
@pytest.mark.parametrize("family", ["llama", "nemotron_h", "longcat"])
def test_expert_counters_are_the_plain_sum_of_the_records(family):
    """One short request with every program's record tapped: what
    `stats()` says of the expert blocks is those records' ``counts``
    rows added up here, the decode programs' held-experts column apart,
    and the pairs routed are live tokens x blocks x ``top_k``."""
    cfg = FAMILIES[family][0]()
    eng = LLMEngine(cfg, max_batch=2, page_size=16)
    rows, routed = [], 0
    prompt = np.random.default_rng(7).integers(1, cfg.vocab_size, 21).tolist()

    def tap(phase, logits, record):
        nonlocal routed
        if record is None:
            return
        blocks, _, top_k = record["routes"].shape
        live = 1 if phase == "decode" else len(prompt)
        routed += live * blocks * top_k
        rows.append((phase, np.asarray(record["counts"]).tolist()))

    eng.on_logits = tap
    (out,) = eng.generate([prompt], SamplingParams(max_tokens=4))
    assert len(out) == 4
    stats = eng.stats()

    if family == "llama":
        assert not rows  # its programs make no record
        assert all(stats[key] == 0 for key in EXPERT_KEYS)
        assert "moe_combine_kernel" not in stats
        assert "zero_expert_pairs_pct" not in stats
        return
    assert [phase for phase, _ in rows] == ["prefill"] + ["decode"] * 3
    width = 6 if family == "longcat" else 4
    assert {len(row) for _, row in rows} == {width}
    total = np.sum([row for _, row in rows], axis=0)
    assert stats["moe_pairs_routed"] == routed > 0
    assert stats["moe_pairs_here"] == total[0] > 0
    assert stats["experts_touched"] == sum(
        row[1] for phase, row in rows if phase == "decode"
    )
    assert stats["moe_rows_computed"] == total[2]
    assert stats["moe_rows_sorted"] == total[3]
    assert stats["moe_sorted_rows_pct"] == (
        100.0 * total[2] / total[3] if total[3] else 0.0
    )
    assert stats["moe_combine_kernel"] is False  # a CPU: XLA's scatter-add
    if family == "longcat":
        assert stats["moe_zero_pairs"] == total[4] > 0
        assert stats["zero_expert_pairs_pct"] == 100.0 * total[4] / routed
        assert stats["real_experts_per_token_mean"] == pytest.approx(
            cfg.top_k * (1.0 - total[4] / routed)
        )
        assert stats["real_experts_per_token_max"] == max(
            row[5] for _, row in rows
        )
    else:
        assert stats["moe_zero_pairs"] == 0
        assert "zero_expert_pairs_pct" not in stats
