"""Nemotron-H (``model_type: nemotron_h``) for ``runners/serve_model.py``:
the program's config from the published keys, the comparison with the
plain reference and its limits, and the bytes and operations that the
per-layer metrics divide by time. Everything the runner, the server,
``aot_fit_serve_model`` and the reducers need of a model family is one
of the names below, so another family is another module."""

from __future__ import annotations

# Largest |logit| difference between the timed programs' logits (bf16
# weights and activations at use; chunked scan, batched or grouped expert
# matmuls, paged attention; float32 state and router) and the float32
# reference *on the same routes*, over the last prompt position and four
# decode steps of two prompts, on logits of magnitude 4.0-5.1. Sixteen
# blocks of bf16 matmuls land within a few hundredths: 0.049-0.062 over
# 46 runs on the chip, a seed each (PR 31). A dropped pair, an unscaled or
# unnormalised gate, a missing shared expert, a stale convolution tail
# or state, a rotary embedding on one side, or an 8-bit matmul moves
# logits by tenths and fails: the reference with its weights rounded to
# e4m3 reads 0.72-0.75 against itself in float32 (PERF.md section 6).
LOGIT_TOLERANCE = 0.12
# Every route the system chose must lie within this of the reference's
# own cut, as a share of the reference's 6th selection score: a token
# may go to the reference's 7th expert for its 6th only where the two
# scores are this close. The router runs in float32 on both sides, but
# its input is the residual stream, which the system carries in bf16:
# over the same 46 runs the furthest swap lay 0.0061-0.0100 below the
# cut, 5.1-6.9% of (token, block) pairs swapped (PR 31). With e4m3
# weights the reference's own routes lie 0.098-0.101 below. (A router
# computed in bfloat16 reads 0.0055-0.0066, under what the bf16 residual
# stream already costs: no limit on routes can tell it, PERF.md.)
MARGIN_EPSILON = 0.03
# Each Mamba block's state after the last decode step, as the cache
# holds it for the slot, against the reference's scan: largest over
# blocks of |S - S_ref|_F / |S_ref|_F. The program's inputs to the
# recurrence are bf16 activations, the state itself is float32:
# 0.011-0.016 over the 46 runs (PR 31); with e4m3 weights
# 0.17-0.18. A state that is stale, not carried across chunks, or read
# past the true length is off by its whole norm.
STATE_TOLERANCE = 0.05


# What of the program this family needs beyond what every serving cell
# needs: the runner looks before it starts anything, so that a checkout
# that lacks them (this cell's parent commit) fails at once and not when
# a replica cannot be built.
PROGRAM_FILES = ("models/nemotron_h.py", "llm/hybrid_kv.py")


def config(model: dict, **program):
    """``NemotronHConfig`` for the published keys in ``model``;
    ``program`` are fields of the program's own (``max_seq``,
    ``dtype``). A file that states a switch the program does not have is
    refused here, so that it cannot state a model the program does not
    run."""
    from ray_tpu.models.nemotron_h import NemotronHConfig

    if model["model_type"] != "nemotron_h":
        raise ValueError(f"not a Nemotron-H configuration: {model['model_type']}")
    pattern = model["hybrid_override_pattern"]
    if len(pattern) != model["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern is not num_hidden_layers long")
    for key in ("attention_bias", "mlp_bias", "use_bias", "mamba_proj_bias",
                "tie_word_embeddings", "sliding_window"):
        if model[key]:
            raise ValueError(f"models/nemotron_h.py has no {key}")
    if not model["use_conv_bias"]:
        raise ValueError("models/nemotron_h.py's convolution has a bias")
    if model["mlp_hidden_act"] != "relu2" or model["mamba_hidden_act"] != "silu":
        raise ValueError("experts are relu^2 and the Mamba mixer's act is silu")
    if model["n_group"] != 1 or model["topk_group"] != 1:
        raise ValueError("group-limited expert selection is not written")
    if model["n_shared_experts"] != 1:
        raise ValueError("models/moe.py applies one shared expert")
    if model["norm_eps"] != 1e-5 or model["layer_norm_epsilon"] != 1e-5:
        raise ValueError("ops/norms.py fixes rms_norm eps at 1e-5")
    published = model.get("published", {})
    program.setdefault("max_seq", model["max_position_embeddings"])
    return NemotronHConfig(
        vocab_size=model["vocab_size"],
        d_model=model["hidden_size"],
        pattern=pattern,
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        head_dim=model["head_dim"],
        mamba_heads=model["mamba_num_heads"],
        mamba_head_dim=model["mamba_head_dim"],
        ssm_groups=model["n_groups"],
        ssm_state=model["ssm_state_size"],
        conv_kernel=model["conv_kernel"],
        chunk_size=model["chunk_size"],
        time_step_min=model["time_step_min"],
        time_step_max=model["time_step_max"],
        time_step_floor=model["time_step_floor"],
        # The router is as wide as the model's experts; the file's own
        # count is how many of them are held here.
        num_experts=published.get("n_routed_experts", model["n_routed_experts"]),
        experts_held=(
            (model.get("first_expert_held", 0), model["n_routed_experts"])
            if "n_routed_experts" in published else None
        ),
        top_k=model["num_experts_per_tok"],
        d_ff=model["moe_intermediate_size"],
        shared_d_ff=model["moe_shared_expert_intermediate_size"],
        norm_topk_prob=bool(model["norm_topk_prob"]),
        routed_scaling_factor=float(model["routed_scaling_factor"]),
        **program,
    )


# ------------------------------------------------------- bytes and operations
def _blocks(model: dict, kind: str) -> int:
    return model["hybrid_override_pattern"].count(kind)


def held_parameters(model: dict) -> int:
    """Parameters of the tree as the configuration holds it."""
    d = model["hidden_size"]
    di = model["mamba_num_heads"] * model["mamba_head_dim"]
    conv = di + 2 * model["n_groups"] * model["ssm_state_size"]
    h = model["mamba_num_heads"]
    mamba = (d + d * (di + conv + h) + (model["conv_kernel"] + 1) * conv
             + 3 * h + di + di * d)
    f, fs = model["moe_intermediate_size"], model["moe_shared_expert_intermediate_size"]
    routed_all = model.get("published", {}).get(
        "n_routed_experts", model["n_routed_experts"]
    )
    expert = (d + d * routed_all + routed_all
              + model["n_routed_experts"] * 2 * d * f + 2 * d * fs)
    hq = model["num_attention_heads"] * model["head_dim"]
    hkv = model["num_key_value_heads"] * model["head_dim"]
    attn = d + 2 * d * hq + 2 * d * hkv
    return (_blocks(model, "M") * mamba + _blocks(model, "E") * expert
            + _blocks(model, "*") * attn + 2 * model["vocab_size"] * d + d)


def held_expert_slots(model: dict) -> int:
    """Held experts over all expert blocks: what a decode step could
    touch at most."""
    return model["n_routed_experts"] * _blocks(model, "E")


def expert_weight_bytes(model: dict, experts: float) -> float:
    """Bytes of ``experts`` routed experts' two matrices, as held."""
    return experts * 2 * model["hidden_size"] * model["moe_intermediate_size"] * 2


def expert_weights_read_per_decode_step(model: dict, engine: dict) -> float:
    """Bytes of routed experts' weights that a decode step's grouped
    matmuls have to read: the weights of the held experts that got a row
    in that step (``experts_touched``, summed by the engine over expert
    blocks and decode steps), NOT of every held expert: a grouped matmul
    need not read a group of no rows, and with 32 slots routing ~96 pairs
    to 64 held experts about a fifth get none. The shared expert's are
    not among them (its products are under ``moe:shared``)."""
    if not engine.get("decode_steps"):
        return 0.0
    return expert_weight_bytes(
        model, engine["experts_touched"] / engine["decode_steps"]
    )


def ssm_state_bytes_per_decode_step(model: dict, engine: dict) -> float:
    """Bytes of recurrent state a decode step has to move: each decoding
    slot's SSM state (float32) and convolution tail (bfloat16), read once
    and written once in each Mamba block. The program computes all
    ``max_batch`` slots; the slots that were not decoding are not
    counted."""
    if not engine.get("decode_steps"):
        return 0.0
    slots = engine["slot_steps"] / engine["decode_steps"]
    di = model["mamba_num_heads"] * model["mamba_head_dim"]
    conv_dim = di + 2 * model["n_groups"] * model["ssm_state_size"]
    per_slot = (
        di * model["ssm_state_size"] * 4
        + (model["conv_kernel"] - 1) * conv_dim * 2
    )
    return 2.0 * slots * _blocks(model, "M") * per_slot


def scan_flops_per_token(model: dict) -> float:
    """Operations of the chunked scan per prefilled token, all Mamba
    blocks, a multiply-add as two: within a chunk of Q tokens C.B^T (per
    group) and the masked product with x (per head) are 2 Q N and 2 Q P
    a token; the chunk's state and the read of the carried state are 2 P
    N a token and head each."""
    q = model["chunk_size"]
    h, p = model["mamba_num_heads"], model["mamba_head_dim"]
    g, n = model["n_groups"], model["ssm_state_size"]
    per_block = 2.0 * q * n * g + 2.0 * q * p * h + 2 * (2.0 * p * n * h)
    return _blocks(model, "M") * per_block


# ------------------------------------------------------ against the reference
def check_problems(check: dict, logit_tolerance: float = LOGIT_TOLERANCE,
                   epsilon: float = MARGIN_EPSILON,
                   state_tolerance: float = STATE_TOLERANCE) -> list[str]:
    """What of the server's ``check`` record makes a run not correct."""
    problems = []
    worst = max(check["logit_max_abs_err"])
    if not check["finite"] or worst > logit_tolerance:
        problems.append(
            f"logits differ from the reference on the same routes by "
            f"{worst:.4f} (tolerance {logit_tolerance})"
        )
    if check["largest_slack"] > epsilon:
        problems.append(
            f"{check['routes_beyond_epsilon']} tokens were sent to an expert "
            f"more than {epsilon} below the reference's cut "
            f"(furthest {check['largest_slack']:.4f})"
        )
    if check["state_rel_err"] > state_tolerance:
        problems.append(
            f"the cache's SSM state differs from the reference's scan by "
            f"{check['state_rel_err']:.4f} of its norm "
            f"(tolerance {state_tolerance})"
        )
    return problems
