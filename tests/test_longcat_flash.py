"""LongCat-Flash's language model (models/longcat_flash.py, the kind ``S``
of llm/latent_kv.py, the identity outputs of models/moe.py) against the
plain reference (benchmarks/reference_longcat_flash.py) at a tiny size,
float32, seeded weights, a non-zero selection bias, on the CPU: the
double layer with its shortcut, two cache cells a token a layer, the
expert share with the identity part counted once, and prefill-then-decode
through `LLMEngine`'s latent pages.

Tolerances: everything here is float32 on both sides, so differences
are summation order only. 2e-4 absolute on values of magnitude ~1-4
leaves an order of magnitude over what float32 reassociation gives
across two double layers (measured 2e-6 to 3e-6), and is a hundred times
under what any mathematical difference (an unscaled latent, a dropped
identity sum, a shortcut added a sublayer early, a cell in the other
sublayer's row) produces, which the reference's own switches show."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_longcat_flash as reference
from benchmarks.models import longcat_flash as bench_model
from ray_tpu.llm import latent_kv
from ray_tpu.llm.engine import LLMEngine, SamplingParams
from ray_tpu.models import moe
from ray_tpu.models.longcat_flash import (
    LONGCAT_PRESETS,
    init_params,
)
from ray_tpu.models.moe import MOE_PRESETS, init_moe_params, moe_ffn
from ray_tpu.models.pangu_ultra_moe import PANGU_PRESETS
from ray_tpu.models.pangu_ultra_moe import init_params as pangu_init_params
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.pallas.latent_attention import keys_expanded

TOL = 2e-4

# The published keys (the catalog's) at a tiny size: what a
# configuration file carries, so that `config` and `for_model` are under
# test too.
TINY = {
    "attention_method": "MLA", "hidden_size": 64, "vocab_size": 256,
    "num_layers": 2, "num_attention_heads": 4, "q_lora_rank": 24,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "rope_theta": 10000, "ffn_hidden_size": 96,
    "expert_ffn_hidden_size": 32, "n_routed_experts": 8, "moe_topk": 3,
    "zero_expert_num": 4, "zero_expert_type": "identity",
    "routed_scaling_factor": 6, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "attention_bias": False,
    "rms_norm_eps": 1e-5, "max_position_embeddings": 256,
}
# Rows up to 8 take `moe_ffn`'s every-row form and more its sorted one,
# so that an engine's decode steps (4 slots) run the first and its
# prefills the second, as the two meet in a replica.
CFG = bench_model.config(
    TINY, dtype=jnp.float32, dense_expert_rows=8, cell_lanes=16,
    prefill_key_block=16,
)
REF = reference.for_model(TINY)
E, Z, K = CFG.num_experts, CFG.zero_experts, CFG.top_k


def _seeded(p, bias=0.004):
    """Norm gains that are not 1 and a selection bias that is not zero
    (of the size of the gaps between the 768^-1-sized probabilities it is
    added to), so that a missing norm and a choice by probability alone
    show."""
    def noise(key, leaf, scale):
        return scale * jax.random.normal(jax.random.key(key), leaf.shape)

    blocks = []
    for i, block in enumerate(p["blocks"]):
        attn = tuple(
            {**a, **{name: noise(100 * i + 10 * j + n, a[name], 0.3)
                     for n, name in enumerate(("norm1", "q_norm", "kv_norm"))}}
            for j, a in enumerate(block["attn"])
        )
        ffn = tuple(
            {**f, "norm": noise(100 * i + 50 + j, f["norm"], 0.3)}
            for j, f in enumerate(block["ffn"])
        )
        experts = {**block["moe"], "router_bias": noise(
            100 * i + 70, block["moe"]["router_bias"], bias
        )}
        blocks.append({"attn": attn, "ffn": ffn, "moe": experts})
    return {**p, "blocks": tuple(blocks)}


@pytest.fixture(scope="module")
def params():
    return _seeded(init_params(jax.random.key(3), CFG))


def _x(seed, t):
    return jax.random.normal(jax.random.key(seed), (t, CFG.d_model))


def test_the_tiny_preset_is_the_tiny_file():
    assert LONGCAT_PRESETS["longcat_tiny"] == CFG
    assert CFG.pattern == "SS" and CFG.attn_sublayers == 4
    assert CFG.latent_dim == 40 and CFG.cell_width == 48
    assert CFG.q_latent_scale == (64 / 24) ** 0.5
    assert CFG.kv_latent_scale == 2.0**0.5
    assert PANGU_PRESETS["pangu_tiny"].attn_sublayers == 4  # one a layer


def test_a_file_that_states_another_model_is_refused():
    for key, value in (("attention_method", "MHA"), ("zero_expert_type", "copy"),
                       ("attention_bias", True), ("rms_norm_eps", 1e-6)):
        with pytest.raises(ValueError):
            bench_model.config({**TINY, key: value})
    plain = bench_model.config({**TINY, "mla_scale_q_lora": False,
                                "mla_scale_kv_lora": False})
    assert plain.q_latent_scale == plain.kv_latent_scale == 1.0


# ------------------------------------------------------ the expert layer
@pytest.fixture(params=[0, 64], ids=["sorted", "every_row"])
def path_cfg(request):
    return dataclasses.replace(CFG, dense_expert_rows=request.param)


def _branch(p, x, cfg, live=None):
    """The shortcut branch as `_layer` calls it: `moe_ffn` on the first
    dense FFN's normed input, with the mask of the rows that carry a
    token (here all unless given)."""
    u = rms_norm(x, p["ffn"][0]["norm"])[None]
    live = jnp.ones(len(x), bool) if live is None else live
    out, aux = moe_ffn(u, p["moe"], cfg, rows_live=live)
    return out[0], aux


def test_expert_branch_equals_the_reference(params, path_cfg):
    """`moe_ffn` as this family calls it (a softmax over experts and
    identity outputs, chosen by probability + bias, gates 6 x p not
    renormalised, identity routes as ``g * u``) against the reference's
    plain loop; the bias moves the choice and not the gates."""
    p = params["blocks"][1]
    x = _x(5, 24)
    want, record = reference.expert_branch(
        p["ffn"][0]["norm"], p["moe"], x, **REF
    )
    got, aux = _branch(p, x, path_cfg)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    routes = np.asarray(aux["routes"])
    assert (np.sort(routes, -1) == np.sort(record["routes"], -1)).all()
    unbiased = {**p["moe"], "router_bias": 0 * p["moe"]["router_bias"]}
    by_p, _ = reference.expert_branch(p["ffn"][0]["norm"], unbiased, x, **REF)
    assert np.abs(np.asarray(by_p) - np.asarray(want)).max() > 100 * TOL
    # An identity route is never a pair: in no load, no touched expert.
    to_zero = routes >= E
    assert 0 < to_zero.sum() < routes.size
    assert int(aux["zero_pairs"]) == to_zero.sum()
    assert int(aux["expert_load"].sum()) == (~to_zero).sum()
    assert aux["expert_load"].shape == (E,)
    assert int(aux["real_max"]) == (K - to_zero.sum(-1)).max()
    other, _ = reference.expert_branch(
        p["ffn"][0]["norm"], p["moe"], x, **{**REF, "lower": "no_identity"}
    )
    assert np.abs(np.asarray(other) - np.asarray(want)).max() > 100 * TOL


@pytest.mark.parametrize("toward", ["identity", "experts"])
def test_rows_whose_routes_are_all_of_one_kind(params, path_cfg, toward):
    """A bias that pushes every choice to the identity outputs: the
    layer is ``(sum of the chosen gates) * u``, no expert is touched and
    no pair computed; one that pushes them all to experts: no identity
    route, and the result is the reference's."""
    p = params["blocks"][0]
    sign = 1.0 if toward == "identity" else -1.0
    bias = jnp.where(jnp.arange(E + Z) >= E, sign, -sign)
    p = {**p, "moe": {**p["moe"], "router_bias": bias}}
    x = _x(9, 24)
    got, aux = _branch(p, x, path_cfg)
    want, _ = reference.expert_branch(p["ffn"][0]["norm"], p["moe"], x, **REF)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    routes = np.asarray(aux["routes"])
    if toward == "identity":
        assert (routes >= E).all()
        assert int(aux["zero_pairs"]) == 24 * K and int(aux["real_max"]) == 0
        assert int(aux["expert_load"].sum()) == 0
        u = rms_norm(x, p["ffn"][0]["norm"])
        probs = jax.nn.softmax(u @ p["moe"]["router"], -1)
        gate = 6.0 * jnp.take_along_axis(probs, aux["routes"], -1).sum(-1)
        np.testing.assert_allclose(got, gate[:, None] * u, atol=TOL, rtol=0)
    else:
        assert (routes < E).all()
        assert int(aux["zero_pairs"]) == 0 and int(aux["real_max"]) == K
        assert int(aux["expert_load"].sum()) == 24 * K


def test_dead_rows_count_for_nothing(params, path_cfg):
    p = params["blocks"][0]
    x = _x(10, 24)
    live = jnp.arange(24) < 17
    _, aux = _branch(p, x, path_cfg, live)
    routes = np.asarray(aux["routes"])[:17]
    assert int(aux["zero_pairs"]) == (routes >= E).sum()
    assert int(aux["expert_load"].sum()) == (routes < E).sum()
    assert int(aux["real_max"]) == (K - (routes >= E).sum(-1)).max()


def test_the_shares_add_up_to_the_uncut_layer(params, path_cfg):
    """Expert parallelism over four chips: each share holds 2 of the 8
    experts, routes over all 8 + 4 outputs and computes its own experts'
    part; the identity part is what every chip computes alike for its
    own rows. The four routed parts plus the identity part ONCE are the
    uncut layer (model-configs guide, section 4); each share also equals
    the reference given the same share."""
    p = params["blocks"][1]
    x = _x(7, 24)
    norm = p["ffn"][0]["norm"]
    whole, aux = _branch(p, x, path_cfg)
    u = rms_norm(x, norm)
    to_zero = aux["routes"] >= E
    probs = jax.nn.softmax(u @ p["moe"]["router"], -1)
    gates = 6.0 * jnp.take_along_axis(probs, aux["routes"], -1)
    identity = jnp.where(to_zero, gates, 0.0).sum(-1)[:, None] * u
    parts, pairs = [], 0
    for first in (0, 2, 4, 6):
        cfg = dataclasses.replace(path_cfg, experts_held=(first, 2))
        mine = {**p["moe"], **{k: p["moe"][k][first: first + 2]
                               for k in ("w_gate", "w_up", "w_down")}}
        out, part_aux = _branch({**p, "moe": mine}, x, cfg)
        want, _ = reference.expert_branch(
            norm, mine, x, **{**REF, "first_expert_held": first}
        )
        np.testing.assert_allclose(out, want, atol=TOL, rtol=0)
        assert (part_aux["routes"] == aux["routes"]).all()
        assert part_aux["expert_load"].shape == (2,)
        assert int(part_aux["zero_pairs"]) == int(aux["zero_pairs"])
        parts.append(out - identity)
        pairs += int(part_aux["expert_load"].sum())
    np.testing.assert_allclose(sum(parts) + identity, whole, atol=TOL, rtol=0)
    uncut, _ = reference.expert_branch(norm, p["moe"], x, **REF)
    np.testing.assert_allclose(whole, uncut, atol=TOL, rtol=0)
    # Every real pair fell to exactly one share, no identity route to any.
    assert pairs == 24 * K - int(aux["zero_pairs"])


# Other families' `moe_ffn`, as it was before the identity outputs: the
# same inputs give the same bits, and nothing of `moe:zero` is in their
# programs.
def _olmoe_case():
    cfg = MOE_PRESETS["moe_tiny"]
    p = init_moe_params(jax.random.key(0), cfg)["blocks"]
    p = jax.tree.map(lambda a: a[0], p)
    x = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model))
    return cfg, p, x


def _pangu_case():
    cfg = PANGU_PRESETS["pangu_tiny"]
    p = dict(pangu_init_params(jax.random.key(0), cfg)["blocks"][1])
    p["router_bias"] = 0.2 * jax.random.normal(
        jax.random.key(2), (cfg.num_experts,)
    )
    x = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model))
    return cfg, p, x


@pytest.mark.parametrize("case", [_olmoe_case, _pangu_case],
                         ids=["olmoe", "pangu"])
def test_no_identity_outputs_leaves_a_family_as_it_was(case):
    cfg, p, x = case()
    assert cfg.zero_experts == 0
    out, aux = moe_ffn(x, p, cfg)
    assert "zero_pairs" not in aux and "real_max" not in aux
    text = jax.jit(lambda x, p: moe_ffn(x, p, cfg)).lower(x, p).as_text(
        debug_info=True
    )
    assert "moe:zero" not in text and "moe:combine" in text
    # The routes and gates by the family's rule, written out here.
    flat = x.reshape(-1, cfg.d_model)
    logits = jnp.dot(flat, p["router"], precision=jax.lax.Precision.HIGHEST)
    if cfg.router_kind == "softmax":
        gates, routes = jax.lax.top_k(jax.nn.softmax(logits, -1), cfg.top_k)
    else:
        scores = jax.nn.sigmoid(logits)
        _, routes = jax.lax.top_k(scores + p["router_bias"], cfg.top_k)
        gates = jnp.take_along_axis(scores, routes, -1)
        gates = 2.5 * gates / gates.sum(-1, keepdims=True)
    assert (np.asarray(aux["routes"]) == np.asarray(routes)).all()
    # And the sum over every expert, one at a time, by those gates.
    dense = jnp.zeros_like(flat)
    for e in range(cfg.num_experts):
        w = jnp.where(routes == e, gates, 0.0).sum(-1)[:, None]
        act = jax.nn.silu(flat @ p["w_gate"][e]) * (flat @ p["w_up"][e])
        dense = dense + w * (act @ p["w_down"][e])
    if "shared_up" in p:
        dense = dense + (
            jax.nn.silu(flat @ p["shared_gate"]) * (flat @ p["shared_up"])
        ) @ p["shared_down"]
    np.testing.assert_allclose(out.reshape(flat.shape), dense, atol=TOL, rtol=0)


def test_router_norms_and_cells_are_held_in_their_precision():
    """The tree as it is held at a bfloat16 config has its router, its
    selection bias and every norm in float32, and the cache's cells in
    the config's dtype, two rows of the pool a layer; the router's
    product, the soft-max and the choice are float32 whatever the
    activations' dtype."""
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    shapes = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))
    leaves = jax.tree_util.tree_flatten_with_path(shapes["blocks"])[0]
    assert len(leaves) == 2 * (2 * 9 + 2 * 4 + 5)
    for path, leaf in leaves:
        name = path[-1].key
        want = jnp.float32 if (
            "norm" in name or name.startswith("router")
        ) else jnp.bfloat16
        assert leaf.dtype == want, path
    assert shapes["blocks"][0]["moe"]["router"].shape == (64, E + Z)
    cache = jax.eval_shape(lambda: latent_kv.init_latent_cache(cfg, 3, 8))
    assert cache["latent"].dtype == jnp.bfloat16
    assert cache["latent"].shape == (4, 3, 8, cfg.cell_width)
    p = _seeded(init_params(jax.random.key(1), cfg))["blocks"][1]["moe"]
    x = _x(8, 8).astype(jnp.bfloat16)
    out, aux = moe_ffn(x[None], p, cfg)
    assert out.dtype == jnp.bfloat16
    probs = jax.nn.softmax(jnp.dot(
        x.astype(jnp.float32), p["router"],
        precision=jax.lax.Precision.HIGHEST,
    ), -1)
    want = jax.lax.top_k(probs + p["router_bias"], cfg.top_k)[1]
    assert (np.asarray(aux["routes"]) == np.asarray(want)).all()
    text = jax.jit(lambda x, p: moe_ffn(x, p, cfg)).lower(x[None], p).as_text(
        debug_info=True
    )
    assert "moe:combine/moe:zero" in text


# ------------------------------------------------------------- the engine
def _engine(params, **kw):
    kw = {"max_batch": 4, "max_seq": 192, "page_size": 8, **kw}
    return LLMEngine(CFG, params=params, **kw)


def _tapped(eng):
    seen = []
    eng.on_logits = lambda phase, logits, record: seen.append(
        (phase, np.asarray(logits), jax.tree.map(np.asarray, record))
    )
    return seen


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(1, CFG.vocab_size, n).tolist()


def _run(eng, prompt, max_tokens):
    """One request alone: its tokens, the pages it held, what each
    program returned."""
    seen = _tapped(eng)
    rid = eng.add_request(prompt, SamplingParams(max_tokens=max_tokens))
    req, generated, pages = eng._queue[-1], None, []
    while generated is None:
        for fin in eng.step():
            assert fin["request_id"] == rid
            generated = fin["tokens"]
        pages = req.pages or pages
    prefills = [s for s in seen if s[0].startswith("prefill")]
    decodes = [s for s in seen if s[0] == "decode"]
    n = len(prompt)
    routes = np.concatenate([s[2]["routes"] for s in prefills], 1)[:, :n]
    routes = np.concatenate(
        [routes] + [s[2]["routes"][:, :1] for s in decodes], 1
    )
    logits = np.stack([prefills[-1][1][0, 0]] + [s[1][0] for s in decodes])
    return prompt + generated[:-1], pages, prefills, decodes, routes, logits


@pytest.mark.parametrize("chunk", [None, 16], ids=["whole", "chunked"])
@pytest.mark.parametrize("kernel", ["0", "1"], ids=["gather", "kernel"])
def test_prefill_then_decode_equals_the_reference_pass(
    params, chunk, kernel, monkeypatch
):
    """A 45-token prompt (a padded bucket of 64; with `chunk` 16, three
    chunks, the last with 3 tokens of padding, each attending the
    earlier chunks' latent pages of both sublayers), then 5 decode steps
    in the absorbed form through the pages: the logits of the last
    prompt position and of every decoded one against the reference's ONE
    full non-absorbed pass over prompt plus generated tokens, its routes
    forced to the system's (they are equal anyway in float32, which is
    asserted); the slot's pages in each sublayer's row of the pool
    against the reference's latents; and the counters."""
    monkeypatch.setenv("RAY_TPU_PAGED_ATTN", kernel)
    eng = _engine(params, prefill_chunk=chunk)
    tokens, pages, prefills, decodes, routes, got = _run(eng, _prompt(0, 45), 6)
    assert len(prefills) == (1 if chunk is None else 3) and len(decodes) == 5
    want, record = reference.forward_with_record(
        params, jnp.asarray(tokens, jnp.int32), routes=jnp.asarray(routes),
        **REF,
    )
    assert (np.sort(routes, -1) == np.sort(record["routes"], -1)).all()
    np.testing.assert_allclose(got, np.asarray(want)[44:], atol=TOL, rtol=0)
    # Two cells a token a layer, sublayer j of layer i in row 2 i + j,
    # at the token's page and offset.
    pool = np.asarray(eng.cache["latent"])
    assert pool.shape[0] == 4
    cells = pool[:, pages].reshape(4, -1, CFG.cell_width)
    np.testing.assert_allclose(
        cells[:, : len(tokens), : CFG.latent_dim], record["latents"],
        atol=TOL, rtol=0,
    )
    assert (cells[:, : len(tokens), CFG.latent_dim:] == 0).all()
    for t in (0, 9, 44, 49):
        at = pool[:, pages[t // 8], t % 8, : CFG.latent_dim]
        np.testing.assert_allclose(at, record["latents"][:, t], atol=TOL, rtol=0)
    assert np.abs(record["latents"][0] - record["latents"][1]).max() > 0.1

    stats = eng.stats()
    n, to_zero = len(tokens), routes >= E
    assert stats["moe_pairs_routed"] == n * K * 2
    assert stats["moe_zero_pairs"] == to_zero.sum()
    assert stats["moe_pairs_here"] == n * K * 2 - to_zero.sum()
    assert stats["zero_expert_pairs_pct"] == 100.0 * to_zero.mean()
    assert stats["real_experts_per_token_mean"] == pytest.approx(
        K * (1 - to_zero.mean())
    )
    assert stats["real_experts_per_token_max"] == (K - to_zero.sum(-1)).max()
    touched = sum(
        len(set(s[2]["routes"][layer, 0].tolist()) - set(range(E, E + Z)))
        for s in decodes for layer in range(2)
    )
    assert stats["experts_touched"] == touched
    assert stats["pool_bytes"] == eng.cache["latent"].nbytes
    assert stats["state_bytes"] == 0
    assert stats["latent_bytes_per_token"] == 4 * 40 * 4
    # Per attention sublayer, four of them: whole key blocks up to each
    # chunk's end, the kernel path's (1,024 keys, so the table of 64 is
    # one) or the XLA path's (16).
    chunks = [(0, 64)] if chunk is None else [(0, 16), (16, 16), (32, 16)]
    assert stats["latent_tokens_expanded"] == 4 * sum(
        keys_expanded(s, c, 64) if kernel == "1" else s + c for s, c in chunks
    )
    assert stats["latent_prefill_programs"] == len(chunks)
    assert stats["latent_prefill_pairs"] == 4 * sum(
        c * s + c * (c + 1) // 2 for s, c in chunks
    )


@pytest.mark.parametrize(
    "switch", ["no_identity", "latent_unscaled", "shortcut_early"]
)
def test_the_reference_computed_otherwise_is_another_model(params, switch):
    """What the chip check's limits have to fail, here at float32: each
    of the reference's switches moves the logits (and, for the latent's
    factor, the cells) by far more than the tolerance."""
    tokens = jnp.asarray(_prompt(1, 40), jnp.int32)
    want, record = reference.forward_with_record(params, tokens, **REF)
    got, other = reference.forward_with_record(
        params, tokens, **{**REF, "lower": switch}
    )
    assert np.abs(np.asarray(got) - np.asarray(want)).max() > 100 * TOL
    moved = np.abs(other["latents"][0] - record["latents"][0]).max()
    assert (moved > 100 * TOL) == (switch == "latent_unscaled")


def test_a_chunked_prefill_with_a_share_held_equals_the_reference_pass(
    monkeypatch,
):
    """Experts 4 and 5 of the 8 held, a quarter, as one chip of four
    holds them: a 45-token prompt in three 16-row chunks whose two
    expert layers take the sorted form under its row bound (blocks of 8
    rows here), then 5 decode steps in the every-row form. Logits
    against the reference's one pass with the same share; each program's
    ``counts`` have the identity routes and the most real experts of a
    row behind the four every family has."""
    monkeypatch.setattr(moe, "_PAIR_BLOCK", 8)
    tiny = {**TINY, "n_routed_experts": 2, "first_expert_held": 4,
            "published": {"n_routed_experts": 8}}
    cfg = bench_model.config(
        tiny, dtype=jnp.float32, dense_expert_rows=8, cell_lanes=16,
        prefill_key_block=16,
    )
    assert cfg.experts_held == (4, 2) and cfg.num_experts == 8
    held = _seeded(init_params(jax.random.key(3), cfg))
    assert held["blocks"][0]["moe"]["router"].shape == (64, 12)
    assert held["blocks"][0]["moe"]["w_up"].shape[0] == 2
    eng = LLMEngine(cfg, params=held, max_batch=4, max_seq=192, page_size=8,
                    prefill_chunk=16)
    tokens, _, prefills, decodes, routes, got = _run(eng, _prompt(0, 45), 6)
    assert len(prefills) == 3 and len(decodes) == 5
    want, record = reference.forward_with_record(
        held, jnp.asarray(tokens, jnp.int32), routes=jnp.asarray(routes),
        **reference.for_model(tiny),
    )
    assert (np.sort(routes, -1) == np.sort(record["routes"], -1)).all()
    np.testing.assert_allclose(got, np.asarray(want)[44:], atol=TOL, rtol=0)
    for live, (_, _, rec) in zip((16, 16, 13), prefills):
        here, _, rows, pairs, zero, real = (int(v) for v in rec["counts"])
        mine = rec["routes"][:, :live]
        assert here == ((mine >= 4) & (mine < 6)).sum()
        assert zero == (mine >= 8).sum()
        assert real == (K - (mine >= 8).sum(-1)).max()
        assert pairs == 16 * K * 2  # the padded rows' pairs too
        assert here <= rows < pairs and rows % 8 == 0
    for _, _, rec in decodes:
        assert rec["counts"][2:4].tolist() == [0, 0]  # the every-row form
    stats = eng.stats()
    assert 0 < stats["moe_pairs_here"] < (
        stats["moe_pairs_routed"] - stats["moe_zero_pairs"]
    )


def test_kernel_and_gather_paths_give_identical_greedy_streams(
    params, monkeypatch
):
    prompts = [_prompt(4, 30), _prompt(5, 18), _prompt(6, 41)]
    sampling = SamplingParams(max_tokens=8)
    monkeypatch.setenv("RAY_TPU_PAGED_ATTN", "0")
    want = _engine(params, prefill_chunk=16).generate(prompts, sampling)
    monkeypatch.setenv("RAY_TPU_PAGED_ATTN", "1")
    eng = _engine(params, prefill_chunk=16)
    assert eng.paged_attn_kernel
    assert eng.generate(prompts, sampling) == want


def test_a_preemption_by_recompute_changes_nothing(params):
    """A pool too small for both requests' growth: the younger one is
    preempted, its pages freed, and prefilled again from its whole
    context, both sublayers' cells with it; both streams are what each
    request gives alone."""
    prompts = [_prompt(7, 30), _prompt(8, 30)]
    sampling = SamplingParams(max_tokens=20)
    want = [_engine(params).generate([p], sampling)[0] for p in prompts]
    eng = _engine(params, num_pages=10)
    assert eng.generate(prompts, sampling) == want
    assert eng.stats()["preemptions"] >= 1
    assert eng.alloc.free_pages == eng.alloc.num_pages


def test_chunked_prefill_beside_decoding_slots_changes_nothing(params):
    sampling = SamplingParams(max_tokens=12)
    a, b = _prompt(9, 12), _prompt(10, 70)
    alone = [_engine(params, prefill_chunk=16).generate([p], sampling)[0]
             for p in (a, b)]
    eng = _engine(params, prefill_chunk=16)
    ids = [eng.add_request(a, sampling)]
    eng.step()
    ids.append(eng.add_request(b, sampling))
    done = {}
    while eng.has_unfinished():
        for fin in eng.step():
            done[fin["request_id"]] = fin["tokens"]
    assert [done[i] for i in ids] == alone


def test_speculation_and_a_mesh_are_refused_with_a_sentence(params):
    with pytest.raises(ValueError, match="one token a slot"):
        _engine(params, speculate=2)
    with pytest.raises(NotImplementedError, match="one chip's share"):
        CFG.serving().logical_axes()


def test_the_programs_carry_the_scopes_the_metrics_read():
    """`moe:zero` inside `moe:combine`, `dense:mlp` and the six `mla:*`
    scopes are in the decode program and in a prefill program; the
    programs keep Pangu's names."""
    shapes = jax.eval_shape(lambda k: init_params(k, CFG), jax.random.key(0))
    cache = jax.eval_shape(lambda: latent_kv.init_latent_cache(CFG, 9, 8))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    prefill = latent_kv.prefill_program(CFG, 4, 2, False)
    texts = {
        "prefill": prefill.lower(
            shapes, i32(1, 16), cache, i32(4), i32(), i32()
        ).as_text(debug_info=True),
        "decode": latent_kv.latent_decode.lower(
            shapes, i32(3, 1), cache, i32(3, 4), i32(3),
            jax.ShapeDtypeStruct((3,), jnp.bool_),
            jax.ShapeDtypeStruct((3,), jnp.float32),
            jax.eval_shape(lambda: jax.random.key(0)), cfg=CFG,
        ).as_text(debug_info=True),
    }
    assert "latent_prefill_2_of_4" in texts["prefill"]
    assert "latent_decode" in texts["decode"]
    common = ["moe:combine/moe:zero", "dense:mlp", "moe:route", "moe:experts",
              "mla:q", "mla:latent", "mla:attend", "mla:out"]
    for name, scopes in (("prefill", ["mla:expand"]), ("decode", ["mla:absorb"])):
        for scope in common + scopes:
            assert scope in texts[name], (name, scope)
