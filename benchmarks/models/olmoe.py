"""OLMoE (``model_type: olmoe``) for ``runners/train_model.py``: the
program's config from the published keys, its initialiser and forward,
the plain reference and the comparison with it, and the operations its
arithmetic requires. Everything the runner, the loop, ``aot_fit_model``
and the reducers need of a model family is one of the names below, so
another family is another module."""

from __future__ import annotations

from functools import partial

# Largest |logit| difference between moe_forward as the step runs it
# (bf16 weights and activations at use, flash attention, grouped expert
# matmuls) and the float32 reference *on the same routes*, on logits of
# magnitude ~4. bf16 keeps 8 bits, so two layers of matmuls land within
# a few hundredths: 0.034-0.039 over 16 seeds on the chip (PR 27;
# runners/train.py has the same limit for Mistral). A dropped pair, a
# renormalised gate (gates sum to ~0.3 here, so renormalising triples
# the expert term), a missing QK-norm or an 8-bit matmul moves logits by
# tenths and fails: the reference with its weights rounded to e4m3
# reads 0.27 against itself in float32 (PERF.md section 6).
LOGIT_TOLERANCE = 0.12
# Every route the system chose must lie within this of the reference's
# own cut, as a share of the reference's 8th probability: a token may go
# to the reference's 9th expert for its 8th only where the two
# probabilities are this close. The router runs in float32 on both
# sides, but its input is the residual stream, which the system carries
# in bf16, so the two sides' router logits differ by rounding noise:
# over 16 seeds on the chip 3-6% of (token, layer) pairs swapped and
# the furthest lay 0.012-0.021 below the cut (PR 27; with e4m3 weights,
# 0.13-0.19). With 64 experts the 8th and 9th logits of a token lie a
# mean 0.08 apart, so some tokens always swap.
# A router that ranked by anything else (another norm, bf16 logits'
# ties, a missing softmax input) would choose experts far below the
# cut. The share of tokens closer than this is printed with each run.
MARGIN_EPSILON = 0.05


def config(model: dict, **program):
    """``MoEConfig`` for the published keys in ``model``, with the two
    loss weights its ``train`` group states (the paper's, where it states
    none); ``program`` are fields of the program's own (``attn_impl``,
    ``remat``, ``max_seq``, ``dtype``)."""
    from ray_tpu.models.moe import MoEConfig

    if model["model_type"] != "olmoe":
        raise ValueError(f"not an OLMoE configuration: {model['model_type']}")
    if model["hidden_size"] % model["num_attention_heads"]:
        raise ValueError("hidden_size does not divide into the heads")
    if model["rms_norm_eps"] != 1e-5:
        raise ValueError("ops/norms.py fixes rms_norm eps at 1e-5")
    if model["tie_word_embeddings"]:
        raise ValueError("models/llama.py keeps an untied output head")
    if model["hidden_act"] != "silu":
        raise ValueError("models/moe.py's experts are SwiGLU")
    for key in ("attention_bias", "clip_qkv", "rope_scaling"):
        if model[key]:
            raise ValueError(f"models/llama.py has no {key}")
    program.setdefault("max_seq", model["max_position_embeddings"])
    for key in ("aux_loss_weight", "z_loss_weight"):
        if key in model.get("train", {}):
            program.setdefault(key, model["train"][key])
    return MoEConfig(
        vocab_size=model["vocab_size"],
        d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        d_ff=model["intermediate_size"],
        rope_theta=float(model["rope_theta"]),
        num_experts=model["num_experts"],
        top_k=model["num_experts_per_tok"],
        norm_topk_prob=bool(model["norm_topk_prob"]),
        qk_norm=True,  # modeling_olmoe.py: always, no key of its own
        **program,
    )


def init(key, cfg):
    from ray_tpu.models.moe import init_moe_params

    return init_moe_params(key, cfg)


def forward(params, tokens, cfg, attn_fn=None):
    """(logits [B, S, V], routes [layers, B * S, top_k]) of the model
    function the train step differentiates, one call."""
    from ray_tpu.models.moe import moe_forward

    logits, aux = moe_forward(params, tokens, cfg, attn_fn=attn_fn)
    return logits, aux["routes"]


# ------------------------------------------------------------- operations
def matmul_params(model: dict) -> int:
    """Parameters that take part in a matrix multiplication *per token*:
    projections, router, ``num_experts_per_tok`` experts, output head."""
    d, f = model["hidden_size"], model["intermediate_size"]
    per_layer = (
        4 * d * d + d * model["num_experts"]
        + model["num_experts_per_tok"] * 3 * d * f
    )
    return model["num_hidden_layers"] * per_layer + d * model["vocab_size"]


def total_params(model: dict) -> int:
    d, f = model["hidden_size"], model["intermediate_size"]
    per_layer = (
        4 * d * d + d * model["num_experts"]
        + model["num_experts"] * 3 * d * f + 4 * d  # four norm weights
    )
    return (model["num_hidden_layers"] * per_layer
            + 2 * d * model["vocab_size"] + d)


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward plus backward over the *active* parameters, and causal
    attention (a query at position t reads t + 1 keys); recompute is not
    counted, a multiply-add is two operations (``benchmarks/flops.py``
    has the same rules for the dense model)."""
    attention = (
        model["num_hidden_layers"] * 4 * model["hidden_size"] * (seq + 1) / 2
    )
    return 3.0 * (2.0 * matmul_params(model) + attention)


def expert_matmul_flops_per_token(model: dict, remat: str) -> float:
    """Operations the expert matmuls *execute* per token trained: three
    matmuls of ``num_experts_per_tok`` pairs a layer, forward, the
    backward's two, and the forward again where the layer is
    recomputed."""
    d, f = model["hidden_size"], model["intermediate_size"]
    forward = (
        model["num_hidden_layers"] * model["num_experts_per_tok"]
        * 3 * 2 * d * f
    )
    passes = 4 if remat == "full" else 3
    return float(passes * forward)


def pairs_per_step(model: dict, tokens: int) -> int:
    """(token, expert) pairs a dropless step computes."""
    return tokens * model["num_experts_per_tok"] * model["num_hidden_layers"]


def step_problems(model: dict, per_step: dict, tokens_per_step: int):
    """What of the step's own numbers, one list per name with an entry
    per step of the window, makes a run not correct. Dropless: every
    step computed every pair the router chose."""
    want = pairs_per_step(model, tokens_per_step)
    wrong = [p for p in per_step["moe_pairs"] if p != want]
    if not wrong:
        return []
    return [
        f"{len(wrong)} of {len(per_step['moe_pairs'])} steps computed "
        f"another number of pairs than tokens x top_k x layers = {want}, "
        f"e.g. {wrong[0]:.0f}"
    ]


def step_counters(per_step: dict) -> dict:
    """The counters the per-layer metrics read, from the same lists."""
    return {
        # The worst step's: a skew that comes and goes still shows.
        "expert_load_max_over_mean": max(
            per_step["expert_load_max_over_mean"]
        ),
        "moe_pairs_per_step": max(per_step["moe_pairs"]),
    }


# ------------------------------------------------------ against the reference
def reference_check(params, model: dict, cfg, mesh, attn_fn, seed: int,
                    tokens_per_row: int = 512,
                    epsilon: float = MARGIN_EPSILON) -> dict:
    """The system against ``benchmarks/reference_olmoe.py`` on the same
    weights, one row of 512 seeded tokens per chip, in two parts because
    routing is discrete: (a) with the system's own routes forced on the
    reference, the largest absolute difference of logits; (b) in that
    same run (so that every layer's router saw the input the system's
    saw), each token's routes against the reference's own: they may
    differ only by experts whose reference probability lies within
    ``epsilon`` of the reference's cut, which covers every token whose
    margin exceeds ``epsilon`` and holds the others to near-ties."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import reference_olmoe as reference
    from ray_tpu.parallel.sharding import tree_shardings, use_mesh

    rows = mesh.size
    rng = np.random.default_rng(seed + 7)
    tokens = rng.integers(
        0, cfg.vocab_size, (rows, tokens_per_row), dtype=np.int32
    )
    if mesh.size > 1:
        tokens = jax.device_put(tokens, tree_shardings(mesh, ("batch", None)))

    def system(p, t):
        with use_mesh(mesh):
            return forward(p, t, cfg, attn_fn=attn_fn)

    got, routes = jax.jit(system)(params, tokens)
    want, record = jax.jit(
        partial(reference.forward_with_router, **reference.for_model(model))
    )(params, tokens, routes=routes)
    same = (jnp.sort(routes, -1) == jnp.sort(record["routes"], -1)).all(-1)
    return {
        "logit_max_abs_err": float(jnp.abs(got - want).max()),
        "logit_scale": float(jnp.abs(want).max()),
        "finite": bool(jnp.isfinite(got).all()),
        "tokens": int(rows * tokens_per_row),
        "margin_epsilon": epsilon,
        # Tokens (per layer) the reference itself calls this close.
        "share_under_epsilon": float((record["margin"] <= epsilon).mean()),
        "share_routed_otherwise": float(1.0 - same.mean()),
        # How far below the reference's cut the system's lowest choice
        # lies, worst token: what the epsilon has to stay above.
        "largest_slack": float(record["slack"].max()),
        "routes_beyond_epsilon": int((record["slack"] > epsilon).sum()),
    }


def check_problems(check: dict, logit_tolerance: float = LOGIT_TOLERANCE):
    """What of ``reference_check``'s record makes a run not correct."""
    problems = []
    if not check["finite"] or check["logit_max_abs_err"] > logit_tolerance:
        problems.append(
            f"logits differ from the reference on the same routes by "
            f"{check['logit_max_abs_err']:.4f} (tolerance {logit_tolerance})"
        )
    if check["routes_beyond_epsilon"]:
        problems.append(
            f"{check['routes_beyond_epsilon']} tokens were sent to an expert "
            f"more than {check['margin_epsilon']} below the reference's cut "
            f"(furthest {check['largest_slack']:.3f})"
        )
    return problems
