"""ops/pallas/state_step.py interpreted, against what it replaces on a
TPU: `mamba_step` / `gdn_step` over every slot and `hybrid_decode`'s
masked write-back of the state.

The oracle is the models' own step (tier 1's path), not a second
writing of the algebra: `mamba_step_live` / `gdn_step_live` with the
kernel interpreted must leave the decoding slots' state and output where
the step leaves them (to the tolerance tests/test_nemotron_h.py and
tests/test_qwen3_next.py hold the step to: the sum over the minor
dimension may be taken in another order), every other slot's state and
every other layer of the stack bit for bit as they were, and the
skipped slots' output finite. Compiled for a described v5e at the
served shapes in tests/test_tpu_aot_compile.py.
"""

import dataclasses
import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import hybrid_kv
from ray_tpu.models import nemotron_h, qwen3_next
from ray_tpu.ops.pallas import state_step

SLOTS, LAYERS, LAYER = 6, 3, 1
LIVE = {
    "none": [],
    "one": [2],
    "all": list(range(SLOTS)),
    "scattered": [0, 3, 4],
    "last_only": [SLOTS - 1],
}
# The step's own tolerances in tests/test_nemotron_h.py (TOL) and
# tests/test_qwen3_next.py.
TOLERANCE = {"mamba": 2e-4, "gdn": 2e-5}

_MAMBA_TINY = nemotron_h.NEMOTRON_H_PRESETS["nemotron_h_tiny"]
_GDN_TINY = qwen3_next.QWEN3_NEXT_PRESETS["qwen3_next_tiny"]
CONFIGS = {
    # (config, bytes a state block may take: None = the kernel's own)
    ("mamba", "small"): (_MAMBA_TINY, None),
    # Heads of 64 x 128 as served, eight heads a group (Nemotron's
    # ratio), two heads a block: four head tiles a slot.
    ("mamba", "served_ratio"): (
        dataclasses.replace(
            _MAMBA_TINY, mamba_heads=8, mamba_head_dim=64, ssm_state=128,
            ssm_groups=1,
        ),
        2 * 64 * 128 * 4,
    ),
    ("gdn", "small"): (_GDN_TINY, None),
    # Heads of 128 x 128 as served, two value heads a key head, one
    # head a block.
    ("gdn", "served_ratio"): (
        dataclasses.replace(
            _GDN_TINY, gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=128,
            gdn_value_dim=128,
        ),
        128 * 128 * 4,
    ),
}
RULES = {
    "mamba": ("M", nemotron_h, "mamba_state_step"),
    "gdn": ("G", qwen3_next, "gdn_state_step"),
}


def _interpreted(monkeypatch, rule, block_bytes=None):
    """The model's ``*_step_live`` runs the kernel interpreted."""
    _, module, name = RULES[rule]
    monkeypatch.setattr(
        module, name,
        functools.partial(
            getattr(state_step, name), interpret=True,
            block_bytes=block_bytes,
        ),
    )


def _case(rule, cfg, seed=0):
    """A mixer's parameters, a normed input a slot, a stack of states
    and the tails, all random: (p, u, stack, conv)."""
    block = hybrid_kv._RECURRENT[RULES[rule][0]]
    keys = jax.random.split(jax.random.key(seed), 4)
    if rule == "mamba":
        p = nemotron_h._init_block(keys[0], kind="M", cfg=cfg)
    else:
        p = qwen3_next._init_gdn(keys[0], cfg=cfg)
    state_shape, channels = block.shapes(cfg)
    u = jax.random.normal(keys[1], (SLOTS, cfg.d_model))
    stack = jax.random.normal(keys[2], (LAYERS, SLOTS, *state_shape))
    conv = jax.random.normal(
        keys[3], (SLOTS, cfg.conv_kernel - 1, channels)
    )
    return p, u, stack, conv


@pytest.mark.parametrize("live", list(LIVE))
@pytest.mark.parametrize("shape", ["small", "served_ratio"])
@pytest.mark.parametrize("rule", ["mamba", "gdn"])
def test_kernel_steps_the_live_slots_as_the_models_step_does(
    rule, shape, live, monkeypatch
):
    cfg, block_bytes = CONFIGS[rule, shape]
    block = hybrid_kv._RECURRENT[RULES[rule][0]]
    p, u, stack, conv = _case(rule, cfg)
    mask = np.zeros(SLOTS, bool)
    mask[LIVE[live]] = True
    active = jnp.asarray(mask)

    # The oracle: every slot stepped, written back under the mask.
    want_out, new, want_conv = block.step(u, p, cfg, stack[LAYER], conv)
    want = stack.at[LAYER].set(
        jnp.where(active[:, None, None, None], new, stack[LAYER])
    )

    _interpreted(monkeypatch, rule, block_bytes)
    order, count = state_step.live_order(active)
    assert int(count[0]) == len(LIVE[live])
    assert list(np.asarray(order[: len(LIVE[live])])) == LIVE[live]
    out, got, got_conv = block.step_live(
        u, p, cfg, stack, LAYER, conv, order, count
    )

    tol = TOLERANCE[rule]
    got, want, out = np.asarray(got), np.asarray(want), np.asarray(out)
    was = np.asarray(stack)
    np.testing.assert_allclose(
        got[LAYER, mask], want[LAYER, mask], atol=tol, rtol=0
    )
    np.testing.assert_allclose(
        out[mask], np.asarray(want_out)[mask], atol=tol, rtol=0
    )
    if mask.any():  # not a comparison of nothing with nothing
        assert np.abs(got[LAYER, mask] - was[LAYER, mask]).max() > 0.01
        assert np.abs(out[mask]).max() > 1e-3
    # A slot that does not decode: its state as it was, bit for bit,
    # its output defined.
    np.testing.assert_array_equal(got[LAYER, ~mask], was[LAYER, ~mask])
    assert np.isfinite(out).all()
    # The other layers of the stack are not touched.
    others = [at for at in range(LAYERS) if at != LAYER]
    np.testing.assert_array_equal(got[others], was[others])
    # The tail is the model's own on both paths.
    np.testing.assert_array_equal(got_conv, want_conv)


@pytest.mark.parametrize("rule", ["mamba", "gdn"])
def test_skipped_slots_are_not_read(rule, monkeypatch):
    """NaN in the state of every slot that does not decode: none of it
    reaches a decoding slot's state or any slot's output, and it is
    still there afterwards."""
    cfg, block_bytes = CONFIGS[rule, "served_ratio"]
    block = hybrid_kv._RECURRENT[RULES[rule][0]]
    p, u, stack, conv = _case(rule, cfg, seed=1)
    mask = np.zeros(SLOTS, bool)
    mask[LIVE["scattered"]] = True
    stack = stack.at[:, ~mask].set(jnp.nan)
    _interpreted(monkeypatch, rule, block_bytes)
    order, count = state_step.live_order(jnp.asarray(mask))
    out, got, _ = block.step_live(u, p, cfg, stack, LAYER, conv, order, count)
    assert np.isfinite(np.asarray(out)).all()
    assert np.isfinite(np.asarray(got)[:, mask]).all()
    assert np.isnan(np.asarray(got)[:, ~mask]).all()


@pytest.mark.parametrize("family", ["nemotron_h", "qwen3_next"])
def test_decode_program_as_on_a_tpu_is_the_cpu_programs(family, monkeypatch):
    """`hybrid_decode` traced as for a TPU (the state kernel interpreted;
    the expert blocks, whose own kernels are not this file's, as on the
    CPU) against the program tier 1 runs: same tokens, logits and cache,
    the slots that do not decode untouched."""
    if family == "nemotron_h":
        rule, cfg = "mamba", _MAMBA_TINY
        params = nemotron_h.init_params(jax.random.key(0), cfg)
    else:
        rule, cfg = "gdn", _GDN_TINY
        params = qwen3_next.init_params(jax.random.key(0), cfg)
    slots, page = 4, 16
    keys = jax.random.split(jax.random.key(1), 3)
    cache = hybrid_kv.init_hybrid_cache(cfg, 8, page, slots)
    cache = {
        name: jax.random.normal(keys[0], leaf.shape, leaf.dtype)
        if name not in ("k", "v") else leaf
        for name, leaf in cache.items()
    }
    tokens = jax.random.randint(keys[1], (slots, 1), 0, cfg.vocab_size)
    tables = jnp.asarray([[1, -1], [-1, -1], [2, -1], [3, -1]], jnp.int32)
    positions = jnp.asarray([3, 0, 5, 1], jnp.int32)
    active = jnp.asarray([True, False, True, True])
    args = (
        params, tokens, cache, tables, positions, active,
        jnp.zeros((slots,), jnp.float32), jax.random.key(2),
    )
    program = functools.partial(
        hybrid_kv.hybrid_decode.__wrapped__, cfg=cfg, use_kernel=False
    )
    want = program(*args)
    # What `hybrid_decode` asks, and nobody else (`moe_ffn` would take
    # its TPU kernels, uninterpreted).
    monkeypatch.setattr(
        hybrid_kv, "chip", types.SimpleNamespace(platform=lambda: "tpu")
    )
    _interpreted(monkeypatch, rule)
    got = program(*args)

    tol = TOLERANCE[rule]
    np.testing.assert_array_equal(got[0][active], want[0][active])
    np.testing.assert_allclose(
        got[1][active], want[1][active], atol=10 * tol, rtol=0
    )
    assert np.isfinite(np.asarray(got[1])).all()
    for name, leaf in want[2].items():
        # Page 0 of a layer is the dump page: the slot that does not
        # decode writes its cell there, whatever it computed.
        rest = (slice(None), slice(1, None)) if name in ("k", "v") else ()
        np.testing.assert_allclose(
            got[2][name][rest], leaf[rest], atol=tol, rtol=0, err_msg=name
        )
    state = hybrid_kv._RECURRENT[RULES[rule][0]].state
    np.testing.assert_array_equal(got[2][state][:, 1], cache[state][:, 1])
    assert np.abs(got[2][state][:, 0] - cache[state][:, 0]).max() > 0.01


@pytest.mark.parametrize("model", ["llama", "hybrid", "hybrid_as_on_a_tpu"])
def test_stats_name_the_state_update_the_programs_were_compiled_with(
    model, monkeypatch
):
    """``stats()['state_step_kernel']``: true where the engine's
    programs are compiled for a TPU, false on the CPU, and no key for a
    model without recurrent blocks; no option of its own."""
    from ray_tpu._private import chip
    from ray_tpu.llm.engine import LLMEngine, SamplingParams

    if model == "llama":
        from ray_tpu.models.llama import PRESETS, init_params

        cfg = PRESETS["tiny"]
    else:
        from ray_tpu.models.nemotron_h import init_params

        cfg = _MAMBA_TINY
    params = init_params(jax.random.key(0), cfg)
    if model == "hybrid_as_on_a_tpu":
        # What the engine asks when it is built; the attention kernels
        # are held off, and no program is compiled for a chip that is
        # not there.
        monkeypatch.setenv("RAY_TPU_PAGED_ATTN", "0")
        monkeypatch.setattr(chip, "platform", lambda: "tpu")
    eng = LLMEngine(cfg, max_batch=2, max_seq=64, page_size=16, params=params)
    if model != "hybrid_as_on_a_tpu":
        eng.generate([[1, 2, 3] * 6], SamplingParams(max_tokens=2))
    stats = eng.stats()
    if model == "llama":
        assert "state_step_kernel" not in stats
    else:
        assert stats["state_step_kernel"] is (model == "hybrid_as_on_a_tpu")
        assert stats["state_step_kernel"] == (stats["platform"] == "tpu")
