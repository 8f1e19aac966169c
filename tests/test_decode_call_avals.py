"""The decode call with a step in flight is the program the benchmark
lowers.

`LLMEngine` hands step t's sampled ids to step t+1 as they lie on the
device. The benchmark lowers the decode programs itself, from shapes
(`benchmarks/aot_fit.py fit_serve`, `benchmarks/aot_fit_serve_model.py
lowered_programs`), and its per-layer readers look each traced
instruction up in THAT text: a call with other argument types than it
lowers would be another program than the one it reads. So: the engine's
call, first from the host's ids and then from the device's, by the
abstract value of every argument, against what those two scripts hand to
`.lower`, for one configuration file shrunk to a size the CPU runs; and
the second kind of call compiles nothing that the first had not.

(The scripts' `.lower` is stood in for, so that nothing is traced for a
TPU here; tests/test_tpu_aot_programs.py compiles the real programs.)
"""

import json
import os

import jax
import numpy as np
import pytest
from jax.api_util import shaped_abstractify

from benchmarks import train_loop
from ray_tpu.llm.engine import LLMEngine, SamplingParams

HERE = os.path.join(os.path.dirname(__file__), "..", "benchmarks")
# Engine sizes no other test compiles at; every dimension its own number.
ENGINE = {"max_batch": 3, "max_seq": 80, "page_size": 16, "num_pages": 11,
          "prefill_chunk": 32}
TRAFFIC = {"fit_prefill_buckets": [32, 64]}
SMALL = {
    "mistral7b-serve1": {
        "hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
        "vocab_size": 256,
    },
    "nemotron3nano-serve1": {
        "hidden_size": 64, "vocab_size": 256, "hybrid_override_pattern": "ME*",
        "num_hidden_layers": 3, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "mamba_num_heads": 8,
        "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16,
        "chunk_size": 8, "n_routed_experts": 8, "num_experts_per_tok": 3,
        "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 48,
        "published": {},
    },
}


class _Lowering:
    """Stands in for a jitted program where a script lowers it: keeps
    the arguments, traces and compiles nothing."""

    def __init__(self, seen: dict, name: str):
        self.seen, self.name = seen, name

    def lower(self, *args, **kw):
        self.seen[self.name] = args
        return self

    def compile(self):
        return self


def _llama(conf, monkeypatch):
    """(engine config, the module and name of the decode program, what
    `aot_fit.fit_serve` lowers it with)."""
    from benchmarks import aot_fit, modelcfg
    from ray_tpu.llm import paged_kv

    cfg = modelcfg.llama_config(conf, max_seq=conf["engine"]["max_seq"])
    seen = {}
    with monkeypatch.context() as patch:
        for name in ("paged_verify", "paged_prefill", "paged_prefill_chunk"):
            patch.setattr(paged_kv, name, _Lowering(seen, name))
        aot_fit.fit_serve(conf, TRAFFIC, jax.devices())
    return cfg, (paged_kv, "paged_verify"), seen["paged_verify"]


def _hybrid(conf, monkeypatch):
    from benchmarks import aot_fit_serve_model
    from benchmarks.models import nemotron_h as model
    from ray_tpu.llm import hybrid_kv

    cfg = model.config(conf, max_seq=conf["engine"]["max_seq"])
    seen = {}
    with monkeypatch.context() as patch:
        patch.setattr(hybrid_kv, "hybrid_decode", _Lowering(seen, "decode"))
        patch.setattr(
            hybrid_kv, "prefill_program",
            lambda *a: _Lowering(seen, "prefill"),
        )
        aot_fit_serve_model.lowered_programs(conf, TRAFFIC, jax.devices()[0])
    return cfg, (hybrid_kv, "hybrid_decode"), seen["decode"]


def _avals(args) -> list:
    """(shape, dtype, weak_type) of every leaf: what a jitted call is
    keyed by, whether the leaf is a shape, a host array or a device
    array."""
    return [
        (a.shape, a.dtype, a.weak_type)
        for a in map(shaped_abstractify, jax.tree.leaves(args))
    ]


@pytest.mark.parametrize(
    "config,build",
    [("mistral7b-serve1", _llama), ("nemotron3nano-serve1", _hybrid)],
    ids=["paged_verify", "hybrid_decode"],
)
def test_the_lag_1_decode_call_is_the_program_the_benchmark_lowers(
    config, build, monkeypatch
):
    with open(os.path.join(HERE, "configs", f"{config}.json")) as f:
        conf = {**json.load(f), **SMALL[config], "engine": ENGINE}
    cfg, (module, name), lowered = build(conf, monkeypatch)

    calls = []
    program = getattr(module, name)

    def recorded(*args, **kw):
        calls.append((_avals(args), type(args[1])))
        return program(*args, **kw)

    monkeypatch.setattr(module, name, recorded)
    engine = LLMEngine(cfg, **ENGINE)
    compiles = train_loop.watch_compiles()
    sampling = SamplingParams(max_tokens=4)
    engine.generate([[5, 6, 7, 8, 9]], sampling)
    warm = len(compiles)
    assert [kind is np.ndarray for _, kind in calls] == [True, False, False]
    # The window: a request like the warm-up's, both kinds of call.
    engine.generate([[9, 8, 7]], sampling)
    assert engine.stats()["decode_steps_in_flight"] == 4
    assert len(compiles) == warm, compiles[warm:]

    want = _avals(lowered)
    n_params = len(jax.tree.leaves(lowered[0]))
    for got, _ in calls:
        if name == "paged_verify":
            # aot_fit.py lowers the float32 tree; the engine holds the
            # matmul weights in cfg.dtype (PERF.md, section 7).
            assert [a[0] for a in got[:n_params]] == [
                a[0] for a in want[:n_params]
            ]
            assert got[n_params:] == want[n_params:]
        else:
            assert got == want
