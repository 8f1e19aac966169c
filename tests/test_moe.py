"""MoE / expert-parallelism tests on the virtual 8-device mesh.

The reference ships no MoE (SURVEY.md §2.3: EP "not implemented in Ray
itself"); these tests pin the native implementation: dropless dispatch
against the plain reference (``benchmarks/reference_olmoe.py``), the
router's switches, EP sharding, and a full sharded train step.
"""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_olmoe
from benchmarks.models import olmoe
from ray_tpu.models import moe
from ray_tpu.models.moe import (
    MOE_PRESETS,
    MoEConfig,
    _experts_on_sorted_pairs,
    _take_rows,
    init_moe_params,
    moe_ffn,
    moe_forward,
    moe_param_logical_axes,
)
from ray_tpu.parallel import make_mesh
from ray_tpu.parallel.sharding import shard_pytree, use_mesh
from ray_tpu.train.step import (
    init_train_state,
    jit_train_step,
    loss_fn,
    make_optimizer,
)

CFG = MOE_PRESETS["moe_tiny"]
REF_KW = {"n_heads": CFG.n_heads, "rope_theta": CFG.rope_theta,
          "top_k": CFG.top_k, "norm_topk_prob": CFG.norm_topk_prob}
# OLMoE-1B-7B-0125-Instruct's published keys (the catalog's), at 2 of
# its 16 layers: the benchmark's configuration.
OLMOE_2L = {
    "model_type": "olmoe", "hidden_size": 2048, "intermediate_size": 1024,
    "num_attention_heads": 16, "num_key_value_heads": 16,
    "num_hidden_layers": 2, "num_experts": 64, "num_experts_per_tok": 8,
    "norm_topk_prob": False, "vocab_size": 50304, "rope_theta": 10000,
    "max_position_embeddings": 4096, "rms_norm_eps": 1e-05,
    "tie_word_embeddings": False, "hidden_act": "silu",
    "attention_bias": False, "clip_qkv": None, "rope_scaling": None,
}


def seeded(cfg=CFG, batch=2, seq=32):
    """Seeded weights with every norm's scale moved off its initial 0,
    or the reading of the weight as 1 + scale is not exercised."""
    params = init_moe_params(jax.random.key(0), cfg)
    keys = iter(jax.random.split(jax.random.key(3), 8))
    for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm"):
        if name in params["blocks"]:
            shape = params["blocks"][name].shape
            params["blocks"][name] = 0.1 * jax.random.normal(next(keys), shape)
    params["final_norm"] = 0.1 * jax.random.normal(
        next(keys), params["final_norm"].shape
    )
    tokens = jax.random.randint(
        jax.random.key(1), (batch, seq + 1), 0, cfg.vocab_size
    )
    return params, tokens


def test_moe_forward_shapes_and_finite():
    params, tokens = seeded()
    logits, aux = moe_forward(params, tokens[:, :-1], CFG)
    assert logits.shape == (2, 32, CFG.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()
    assert aux["routes"].shape == (CFG.n_layers, 64, CFG.top_k)
    assert aux["expert_load"].shape == (CFG.n_layers, CFG.num_experts)
    assert (np.asarray(aux["balance_loss"]) > 0.0).all()


def test_logits_match_the_reference_in_two_parts():
    """Routing is discrete: (a) on the system's routes the logits agree
    to float32 rounding; (b) the reference's own routes are the system's
    wherever its margin is not itself rounding. On the CPU both sides
    are float32, so the epsilon is a rounding error's size."""
    params, tokens = seeded()
    got, aux = moe_forward(params, tokens[:, :-1], CFG)
    want, record = reference_olmoe.forward_with_router(
        params, tokens[:, :-1], routes=aux["routes"], **REF_KW
    )
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=1e-5)
    clear = np.asarray(record["margin"]) > 1e-5
    same = (np.sort(aux["routes"], -1) == np.sort(record["routes"], -1)).all(-1)
    assert clear.mean() > 0.99 and same[clear].all()
    assert float(record["slack"].max()) <= 1e-5
    # The benchmark's own form of the same check, on its own sample.
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    model = {**OLMOE_2L, "num_attention_heads": CFG.n_heads,
             "num_experts_per_tok": CFG.top_k, "rope_theta": CFG.rope_theta}
    check = olmoe.reference_check(params, model, CFG, mesh, None, seed=5,
                                  tokens_per_row=32, epsilon=1e-5)
    assert check["logit_max_abs_err"] < 3e-5
    assert not olmoe.check_problems(check)


def test_loss_and_gradients_match_the_reference():
    params, tokens = seeded()
    (got_loss, metrics), got = jax.value_and_grad(loss_fn, has_aux=True)(
        params, {"tokens": tokens}, CFG
    )
    routes = moe_forward(params, tokens[:, :-1], CFG)[1]["routes"]
    want_loss, want = jax.value_and_grad(reference_olmoe.loss)(
        params, tokens, routes=routes,
        aux_loss_weight=CFG.aux_loss_weight,
        z_loss_weight=CFG.z_loss_weight, **REF_KW,
    )
    assert float(got_loss) == pytest.approx(float(want_loss), abs=2e-5)
    assert float(metrics["router_z_loss"]) > 0.0
    flat_got = dict(jax.tree.leaves_with_path(got))
    for path, w in jax.tree.leaves_with_path(want):
        np.testing.assert_allclose(flat_got[path], w, atol=1e-5, rtol=2e-4,
                                   err_msg=str(path))


def test_dropless_under_a_router_forced_onto_one_expert():
    """A router of zeros gives every expert the same probability and
    top-k takes the lowest ids: every token goes to experts 0 and 1.
    Nothing is dropped: the pairs computed are tokens x top_k x layers
    and the output is the reference's."""
    params, tokens = seeded()
    params["blocks"]["router"] = jnp.zeros_like(params["blocks"]["router"])
    got, aux = moe_forward(params, tokens[:, :-1], CFG)
    n = 2 * 32
    load = np.asarray(aux["expert_load"])
    assert (load == [[n, n, 0, 0]] * CFG.n_layers).all()
    assert int(load.sum()) == n * CFG.top_k * CFG.n_layers
    want = reference_olmoe.forward(params, tokens[:, :-1], **REF_KW)
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=1e-5)


@pytest.mark.parametrize("norm_topk_prob", [False, True])
def test_moe_ffn_matches_dense_ensemble(norm_topk_prob):
    """MoE output == gate-weighted sum of each selected expert's dense
    FFN, every pair computed; the gates are the router's probabilities
    as they are unless the model's config says to renormalise them."""
    cfg = dataclasses.replace(CFG, norm_topk_prob=norm_topk_prob)
    params = init_moe_params(jax.random.key(0), cfg)
    layer = jax.tree.map(lambda x: x[0], params["blocks"])  # layer 0
    x = jax.random.normal(jax.random.key(2), (1, 8, cfg.d_model), jnp.float32)

    out, aux = moe_ffn(x, layer, cfg)

    tokens = x.reshape(-1, cfg.d_model)
    probs = jax.nn.softmax(tokens @ layer["router"], -1)
    gv, gi = jax.lax.top_k(probs, cfg.top_k)
    assert float(gv.sum(-1).max()) < 0.9  # far from summing to 1
    if norm_topk_prob:
        gv = gv / gv.sum(-1, keepdims=True)
    expect = np.zeros_like(np.asarray(tokens))
    for t in range(tokens.shape[0]):
        for j in range(cfg.top_k):
            e = int(gi[t, j])
            h = np.asarray(tokens[t])
            gate = np.asarray(
                jax.nn.silu(h @ layer["w_gate"][e])
            ) * np.asarray(h @ layer["w_up"][e])
            expect[t] += float(gv[t, j]) * (gate @ np.asarray(layer["w_down"][e]))
    np.testing.assert_allclose(
        np.asarray(out).reshape(-1, cfg.d_model), expect, rtol=2e-3, atol=2e-3
    )
    assert int(aux["expert_load"].sum()) == 8 * cfg.top_k


@pytest.mark.parametrize("qk_norm", [True, False])
def test_qk_norm_is_a_field_of_the_shape(qk_norm):
    cfg = dataclasses.replace(CFG, qk_norm=qk_norm)
    params, tokens = seeded(cfg)
    axes = moe_param_logical_axes(cfg)
    for name, width in (("q_norm", cfg.n_heads), ("k_norm", cfg.n_kv_heads)):
        assert (name in params["blocks"]) == qk_norm
        assert (name in axes["blocks"]) == qk_norm
        if qk_norm:
            assert params["blocks"][name].shape == (
                cfg.n_layers, width * cfg.head_dim
            )
    logits, _ = moe_forward(params, tokens[:, :-1], cfg)
    with_norm, _ = moe_forward(seeded()[0], tokens[:, :-1], CFG)
    # Off, the same weights give other logits: the norm is not a no-op.
    assert (np.abs(np.asarray(logits - with_norm)).max() < 1e-6) == qk_norm


def test_dense_forward_is_unchanged_by_the_block_edit():
    """Bitwise, on the ``tiny`` preset: ``_block`` as it was before
    QK-norm and the stacked aux, through the same scan."""
    from ray_tpu.models import PRESETS
    from ray_tpu.models.llama import _embed, forward, init_params
    from ray_tpu.ops.attention import causal_attention
    from ray_tpu.ops.norms import rms_norm
    from ray_tpu.ops.rope import apply_rope, rope_frequencies

    cfg = PRESETS["tiny"]
    params = init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 32), 0, cfg.vocab_size)

    def before(params, tokens):
        cos, sin = rope_frequencies(cfg.head_dim, 32, cfg.rope_theta)

        def block(carry, p):
            x, aux_sum = carry
            b, s, _ = x.shape
            h = rms_norm(x, p["attn_norm"])
            q = (h @ p["wq"]).reshape(b, s, cfg.n_heads, cfg.head_dim)
            k = (h @ p["wk"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
            v = (h @ p["wv"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
            x = x + causal_attention(q, k, v).reshape(b, s, -1) @ p["wo"]
            h = rms_norm(x, p["mlp_norm"])
            gate = jax.nn.silu(h @ p["w_gate"])
            x = x + (gate * (h @ p["w_up"])) @ p["w_down"]
            return (x, aux_sum + jnp.float32(0.0)), None

        x = _embed(params["tok_emb"], tokens, cfg)
        (x, _), _ = jax.lax.scan(block, (x, jnp.float32(0.0)), params["blocks"])
        x = rms_norm(x, params["final_norm"])
        return (x @ params["lm_head"]).astype(jnp.float32)

    want = jax.jit(before)(params, tokens)
    got = jax.jit(partial(forward, cfg=cfg))(params, tokens)
    assert "q_norm" not in params["blocks"]
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_take_rows_cotangent_is_the_gathers_own():
    """The hand-written transpose (a gather by the inverse permutation
    and a sum over copies) equals the scatter-add JAX would derive."""
    x = jax.random.normal(jax.random.key(0), (6, 5))
    order = jax.random.permutation(jax.random.key(1), 18)
    inverse = jnp.argsort(order)
    w = jax.random.normal(jax.random.key(2), (18, 5))
    got = jax.grad(lambda x: (_take_rows(x, order, inverse, 3) * w).sum())(x)
    want = jax.grad(lambda x: (x[order // 3] * w).sum())(x)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_full_remat_gives_the_same_loss_and_gradients():
    params, tokens = seeded()
    full = dataclasses.replace(CFG, remat="full")
    (a, _), ga = jax.value_and_grad(loss_fn, has_aux=True)(
        params, {"tokens": tokens}, CFG
    )
    (b, _), gb = jax.value_and_grad(loss_fn, has_aux=True)(
        params, {"tokens": tokens}, full
    )
    assert float(a) == pytest.approx(float(b), abs=1e-6)
    for x, y in zip(jax.tree.leaves(ga), jax.tree.leaves(gb), strict=True):
        np.testing.assert_allclose(x, y, atol=1e-6, rtol=1e-5)


def test_num_params_counts_every_expert():
    cfg = olmoe.config(OLMOE_2L)
    one = dataclasses.replace(cfg, n_layers=1).num_params()
    none = dataclasses.replace(cfg, n_layers=0).num_params()
    assert one - none == 419_569_664  # a layer: 402.7M of it in experts
    assert none == 206_047_232  # embedding, head, final norm
    assert cfg.num_params() == 1_045_186_560 == olmoe.total_params(OLMOE_2L)
    shapes = jax.eval_shape(partial(init_moe_params, cfg=cfg), jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == cfg.num_params()


def test_flops_per_token_counts_the_active_experts():
    cfg = olmoe.config(OLMOE_2L)
    # 237.5M active matmul parameters; 1.526 GFLOP a token trained.
    assert olmoe.matmul_params(OLMOE_2L) == 2 * 67_239_936 + 103_022_592
    assert cfg.flops_per_token(4096) == pytest.approx(1.5257e9, rel=1e-4)
    assert cfg.flops_per_token(4096) == olmoe.train_flops_per_token(
        OLMOE_2L, 4096
    )
    # Read as dense, the same model would be priced 6.2 times higher.
    dense_read = 6.0 * (cfg.num_params() - cfg.vocab_size * cfg.d_model)
    assert dense_read / cfg.flops_per_token(4096) > 3.5


def test_memory_plan_is_priced_on_total_parameters():
    from ray_tpu.train import memory

    cfg = olmoe.config(OLMOE_2L)
    plan = memory.plan(cfg, batch=2, seq=4096, mu_dtype="bfloat16", hbm_gb=16)
    assert plan.n_params == 1_045_186_560
    resident = plan.params_bytes + plan.optimizer_bytes
    assert resident == 1_045_186_560 * 10  # fp32 weights, bf16 + fp32 moments
    assert plan.grads_bytes == 1_045_186_560 * 4
    # The layer's working set is top_k experts wide, not one.
    one = memory.plan(dataclasses.replace(cfg, top_k=1), batch=2, seq=4096,
                      mu_dtype="bfloat16", hbm_gb=16)
    assert plan.activation_bytes > one.activation_bytes


def test_train_step_metrics_prove_nothing_is_dropped():
    opt = make_optimizer(total_steps=10)
    step = jit_train_step(CFG, opt, None)
    state = init_train_state(jax.random.key(0), CFG, opt)
    tokens = jax.random.randint(jax.random.key(1), (4, 33), 0, CFG.vocab_size)
    _, metrics = step(state, {"tokens": tokens})
    assert int(metrics["moe_pairs"]) == 4 * 32 * CFG.top_k * CFG.n_layers
    assert float(metrics["expert_load_max_over_mean"]) >= 1.0
    assert float(metrics["router_z_loss"]) > 0.0
    assert float(metrics["aux_loss"]) > 0.0


def test_moe_expert_sharding_over_ep(mesh8):
    """Params shard over the ep axis; forward under the mesh matches the
    unsharded forward."""
    mesh = make_mesh({"ep": 4, "dp": 2})
    params = init_moe_params(jax.random.key(0), CFG)
    tokens = jax.random.randint(jax.random.key(1), (2, 32), 0, CFG.vocab_size)
    ref_logits, ref_aux = moe_forward(params, tokens, CFG)

    sharded = shard_pytree(params, mesh, moe_param_logical_axes(CFG))
    # Expert dim (size 4) is split over ep=4.
    assert sharded["blocks"]["w_gate"].sharding.spec[1] == "ep"

    with use_mesh(mesh):
        logits, aux = jax.jit(
            lambda p, t: moe_forward(p, t, CFG)
        )(sharded, tokens)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref_logits), rtol=2e-3, atol=2e-3
    )
    np.testing.assert_allclose(
        aux["balance_loss"], ref_aux["balance_loss"], rtol=1e-4
    )
    assert np.array_equal(aux["routes"], ref_aux["routes"])


def test_moe_train_step_on_mesh():
    """Full fwd+bwd+adamw with experts over ep and data over dp/fsdp."""
    mesh = make_mesh({"dp": 2, "fsdp": 2, "ep": 2})
    opt = make_optimizer(total_steps=10)
    step = jit_train_step(CFG, opt, mesh)
    state = init_train_state(jax.random.key(0), CFG, opt)
    tokens = jax.random.randint(
        jax.random.key(1), (4, 33), 0, CFG.vocab_size
    )
    state, metrics = step(state, {"tokens": tokens})
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["aux_loss"]) > 0.0
    assert int(metrics["moe_pairs"]) == 4 * 32 * CFG.top_k * CFG.n_layers
    state, metrics2 = step(state, {"tokens": tokens})
    assert float(metrics2["loss"]) < float(metrics["loss"]) + 1.0


# What `moe_ffn` gave for OLMoE's kinds (softmax router, SwiGLU experts,
# no shared expert, every expert held) before it learnt Nemotron-H's
# (PR 31): routes, loads, the two router losses and the output's first
# three columns, computed by the parent commit's file on the same seeds.
_BEFORE = {
    "routes": [[2, 0], [3, 1], [0, 2], [1, 2], [0, 2], [0, 2], [0, 2], [1, 0]],
    "load": [6, 3, 6, 1],
    "losses": (2.3233108520507812, 3.6123547554016113),
    False: ([[-0.1048563, -0.1609883, 0.0263345], [-0.1432213, 0.127712, -0.3534738],
             [-0.2707842, -0.1416937, -0.4770932], [-0.0711449, -0.0734339, -0.1273737],
             [0.1117236, -0.2846368, 0.2849216], [-0.5840258, 0.0743914, -0.607134],
             [0.01759, 0.6218014, -0.3806386], [-0.3415798, 0.3757877, -0.7133414]],
            111.80332946777344),
    True: ([[-0.1610655, -0.2472876, 0.0404513], [-0.1969674, 0.175638, -0.4861206],
            [-0.3252349, -0.1701862, -0.5730295], [-0.1244904, -0.1284956, -0.2228804],
            [0.1543252, -0.3931722, 0.3935656], [-0.6976726, 0.0888674, -0.7252774],
            [0.0191932, 0.6784747, -0.4153315], [-0.4045245, 0.4450361, -0.8447925]],
           141.677734375),
}


@pytest.mark.parametrize("norm_topk_prob", [False, True])
@pytest.mark.parametrize("path", ["sorted_pairs", "every_row"])
def test_olmoe_path_through_generalised_moe_ffn_gives_what_it_gave(
    norm_topk_prob, path
):
    """The shared edit pinned from OLMoE's side: same routes and loads,
    outputs within this file's tolerance for float32 against float32
    (3e-5), by the sorted grouped matmul (OLMoE's train step) and by
    the few-rows form alike. tests/test_nemotron_h.py pins the other
    model's side."""
    cfg = dataclasses.replace(
        CFG, norm_topk_prob=norm_topk_prob,
        dense_expert_rows=0 if path == "sorted_pairs" else 10**6,
    )
    params = init_moe_params(jax.random.key(0), cfg)
    layer = {k: v[0] for k, v in params["blocks"].items()}
    x = jax.random.normal(jax.random.key(11), (1, 8, cfg.d_model))
    out, aux = moe_ffn(x, layer, cfg)
    assert np.asarray(aux["routes"]).tolist() == _BEFORE["routes"]
    assert np.asarray(aux["expert_load"]).tolist() == _BEFORE["load"]
    np.testing.assert_allclose(
        (aux["balance_loss"], aux["z_loss"]), _BEFORE["losses"], rtol=1e-6
    )
    head, total = _BEFORE[norm_topk_prob]
    np.testing.assert_allclose(out[0, :, :3], head, atol=3e-5, rtol=1e-5)
    np.testing.assert_allclose(jnp.abs(out).sum(), total, rtol=1e-5)


# ------------------------------------------- the sorted form's row bound
# 16 experts, so that a sixteenth can be held; 64 rows x 2 = 128 pairs.
BOUND_CFG = dataclasses.replace(CFG, num_experts=16, dtype=jnp.float32)
_BOUND_CASES = {
    # held (first, count), rows that carry a token (of 64), top_k,
    # router forced onto the lowest ids, d_model
    "all_pairs_live": ((0, 16), 64, 2, False, 64),
    "a_half_held": ((4, 8), 64, 2, False, 64),
    "a_sixteenth_held": ((5, 1), 64, 2, False, 64),
    "no_pair_live": ((4, 8), 0, 2, False, 64),
    "a_padded_tail": ((0, 8), 51, 2, False, 64),
    "a_padded_tail_all_held": (None, 51, 2, False, 64),
    "one_held_expert_takes_every_pair": ((0, 4), 64, 1, True, 64),
    # Widths that are no power of two: 2.5 tiles of 128 lanes, and no
    # whole tile.
    "a_sum_kept_in_pieces": ((4, 8), 64, 2, False, 320),
    "a_last_piece_padded": ((4, 8), 64, 2, False, 176),
}


def _bound_layer(held, top_k, forced, d_model=64):
    cfg = dataclasses.replace(BOUND_CFG, top_k=top_k, d_model=d_model)
    layer = {
        k: v[0] for k, v in init_moe_params(jax.random.key(4), cfg)["blocks"].items()
    }
    if forced:
        layer["router"] = jnp.zeros_like(layer["router"])
    x = jax.random.normal(jax.random.key(12), (1, 64, cfg.d_model))
    first, count = held or (0, cfg.num_experts)
    mine = {**layer, **{k: layer[k][first: first + count]
                        for k in ("w_gate", "w_up", "w_down")}}
    return dataclasses.replace(cfg, experts_held=held), layer, mine, x


@pytest.mark.parametrize("case", list(_BOUND_CASES))
def test_sorted_form_under_a_row_bound_gives_every_pairs_sums(
    case, monkeypatch
):
    """Where only some pairs are computed here the sorted form works on
    those, rounded up to a block (32 rows here, so that 128 pairs are
    four blocks), and gives what the every-row form gives (the oracle,
    `every_row_einsum`) and what the unbounded sorted form gives over
    ALL experts when the gates of the pairs not computed here are zero:
    whatever share is held, with a padded tail, with no pair at all, and
    with every pair on one held expert (dropless: one group of 64 rows,
    two whole blocks)."""
    held, n_live, top_k, forced, d_model = _BOUND_CASES[case]
    monkeypatch.setattr(moe, "_PAIR_BLOCK", 32)
    cfg, layer, mine, x = _bound_layer(held, top_k, forced, d_model)
    rows_live = jnp.arange(64) < n_live

    got, aux = moe_ffn(x, mine, cfg, rows_live=rows_live)

    oracle, oracle_aux = moe_ffn(
        x, mine, dataclasses.replace(cfg, dense_expert_rows=10**6),
        rows_live=rows_live,
    )
    np.testing.assert_allclose(got, oracle, atol=3e-5, rtol=1e-5)
    assert (aux["expert_load"] == oracle_aux["expert_load"]).all()
    assert (aux["routes"] == oracle_aux["routes"]).all()
    assert (oracle_aux["sorted_rows"] == 0).all()

    # Every pair computed, the dead ones weighted by zero.
    routes = aux["routes"]
    first, count = held or (0, cfg.num_experts)
    here = (routes >= first) & (routes < first + count) & rows_live[:, None]
    probs = jax.nn.softmax(x[0] @ layer["router"], -1)
    gates = jnp.take_along_axis(probs, routes, -1)
    unbounded, _ = _experts_on_sorted_pairs(
        x[0], layer, cfg, routes, jnp.where(here, gates, 0.0)
    )
    np.testing.assert_allclose(got[0], unbounded, atol=3e-5, rtol=1e-5)

    pairs_here = int(here.sum())
    assert int(aux["expert_load"].sum()) == pairs_here
    if forced:
        assert aux["expert_load"].tolist() == [64, 0, 0, 0]
    computed, given = (int(v) for v in aux["sorted_rows"])
    assert given == 64 * top_k
    assert computed == -(-pairs_here // 32) * 32 <= given


def _primitives(jaxpr, found):
    for eqn in jaxpr.eqns:
        found.append(eqn.primitive.name)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _primitives(sub, found)
    return found


def test_with_every_pair_computed_the_sorted_form_is_the_program_it_was():
    """The train step's form (every expert held, every row a token)
    shares nothing with the row bound: no loop, switch or conditional,
    the three grouped matmuls, two sorts and the two `_take_rows`; and
    `moe_ffn` reports no bounded rows for it. The bounded form is the
    one with the loop."""
    cfg, layer, mine, x = _bound_layer(None, 2, False)
    routes = jnp.zeros((64, 2), jnp.int32)
    gates = jnp.ones((64, 2))
    found = _primitives(jax.make_jaxpr(
        lambda t, g: _experts_on_sorted_pairs(t, layer, cfg, routes, g)
    )(x[0], gates).jaxpr, [])
    assert found.count("ragged_dot_general") == 3
    assert found.count("sort") == 2 and found.count("custom_vjp_call") == 2
    assert not {"while", "cond", "scan", "dynamic_slice"} & set(found)
    whole = _primitives(
        jax.make_jaxpr(lambda v: moe_ffn(v, layer, cfg))(x).jaxpr, []
    )
    assert "while" not in whole and "cond" not in whole
    assert "sorted_rows" not in moe_ffn(x, layer, cfg)[1]
    half = dataclasses.replace(cfg, experts_held=(0, 8))
    bounded = _primitives(jax.make_jaxpr(
        lambda v: moe_ffn(v, {**layer, **{
            k: layer[k][:8] for k in ("w_gate", "w_up", "w_down")
        }}, half)
    )(x).jaxpr, [])
    assert bounded.count("while") == 1
    assert bounded.count("ragged_dot_general") == 3
