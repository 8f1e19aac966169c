"""Granite 4.0-H (``model_type: granitemoehybrid``) for
``runners/serve_family.py``: the program's config from the published
keys, the serving programs lowered at a configuration's sizes, the
comparison with the plain reference and its limits, and the bytes and
operations that the per-layer metrics divide by time. Everything the
runner, ``server_family``, ``aot_fit_serve_family`` and the reducers
need of a model family is one of the names below, so another family is
another module."""

from __future__ import annotations

import importlib

# Largest |logit| difference between the timed programs' logits (bf16
# weights and activations at use; chunked scan at chunk 256, grouped or
# every-row expert matmuls, the prefill and paged attention kernels;
# float32 state and router) and the float32 reference *on the same
# routes*, over the last prompt position and four decode steps of a
# 4,000-token prompt prefilled whole and a 9,000-token one prefilled in
# five chunks. The logits are divided by 16 (``logits_scaling``) and
# read 1.71-1.95 at their largest (a token's own row of the tied
# embedding), under half the sibling families' 4.0-5.1, so the limit is
# set against that magnitude and not copied: twenty sublayers of bf16
# matmuls land at 0.0042-0.0056 over 10 runs on the chip, a seed each
# (PR 39). A dropped multiplier, a head that is not the embedding, gates
# normalised over all 72 logits, a stale convolution tail or state, or
# an 8-bit matmul moves logits by hundredths and fails: the reference
# with its weights rounded to e4m3 reads 0.0685-0.0705 against the
# system (two seeds; PERF.md section 6).
LOGIT_TOLERANCE = 0.015
# Every route the system chose must lie within this of the reference's
# own cut: ``1 - p(lowest applied) / p(tenth chosen)`` of the router's
# probabilities, which is ``1 - exp(logit gap)``: a token may go to the
# reference's 11th expert for its 10th only where the gate it would
# carry is this close to the tenth's. The router runs in float32 on both
# sides, but its input is the residual stream, which the system carries
# in bf16. A softmax gate moves with the logit itself where a sigmoid
# score moves with a quarter of it, so the readings are wider than the
# sibling families' on the same rounding: over the same 10 runs the
# furthest swap lay 0.042-0.057 below the cut, 7.1-7.5% of (token,
# layer) pairs swapped. With e4m3 weights the reference's own routes lie
# 0.487-0.509 below, 70% swapped.
MARGIN_EPSILON = 0.15
# Each Mamba layer's state after the last decode step, as the cache
# holds it for the slot, against the reference's token-by-token
# recurrence: largest over the nine layers of |S - S_ref|_F /
# |S_ref|_F. The program's inputs to the recurrence are bf16
# activations, the state itself is float32 and is carried across the
# chunks of a prompt (four times in the 9,000-token one): 0.0125-0.0166
# over the 10 runs; with e4m3 weights 0.187-0.193. A state that is stale,
# not carried across chunks, or read past the true length is off by its
# whole norm.
STATE_TOLERANCE = 0.05

# What of the program this family needs beyond what every serving cell
# needs: the runner looks before it starts anything, so that a checkout
# that lacks them (this cell's parent commit) fails at once and not when
# a replica cannot be built.
PROGRAM_FILES = ("models/granite_hybrid.py", "llm/hybrid_kv.py")


def config(model: dict, **program):
    """``GraniteHybridConfig`` for the published keys in ``model``;
    ``program`` are fields of the program's own (``max_seq``, ``dtype``,
    ``dense_expert_rows``). A file that states a switch the program does
    not have is refused here, so that it cannot state a model the
    program does not run."""
    from ray_tpu.models.granite_hybrid import GraniteHybridConfig, sublayers

    if model["model_type"] != "granitemoehybrid":
        raise ValueError(f"not a Granite 4.0-H configuration: {model['model_type']}")
    if len(model["layer_types"]) != model["num_hidden_layers"]:
        raise ValueError("layer_types is not num_hidden_layers long")
    for key in ("attention_bias", "mamba_proj_bias", "rope_scaling"):
        if model[key]:
            raise ValueError(f"models/granite_hybrid.py has no {key}")
    for key, want in (
        ("mamba_conv_bias", True), ("tie_word_embeddings", True),
        ("hidden_act", "silu"), ("normalization_function", "rmsnorm"),
        ("position_embedding_type", "nope"), ("rms_norm_eps", 1e-5),
    ):
        if model[key] != want:
            raise ValueError(f"models/granite_hybrid.py runs {key} = {want!r}")
    d_inner = model["mamba_n_heads"] * model["mamba_d_head"]
    if d_inner != model["mamba_expand"] * model["hidden_size"]:
        raise ValueError("mamba_n_heads x mamba_d_head is not the expanded width")
    published = model.get("published", {})
    program.setdefault("max_seq", model["max_position_embeddings"])
    if "dense_expert_rows" in model.get("program", {}):
        program.setdefault(
            "dense_expert_rows", model["program"]["dense_expert_rows"]
        )
    return GraniteHybridConfig(
        vocab_size=model["vocab_size"],
        d_model=model["hidden_size"],
        pattern=sublayers(model["layer_types"]),
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        # The config gives no head_dim: hidden_size / num_attention_heads.
        head_dim=model["hidden_size"] // model["num_attention_heads"],
        mamba_heads=model["mamba_n_heads"],
        mamba_head_dim=model["mamba_d_head"],
        ssm_groups=model["mamba_n_groups"],
        ssm_state=model["mamba_d_state"],
        conv_kernel=model["mamba_d_conv"],
        chunk_size=model["mamba_chunk_size"],
        # The router is as wide as the model's experts; the file's own
        # count is how many of them are held here.
        num_experts=published.get("num_local_experts", model["num_local_experts"]),
        experts_held=(
            (model.get("first_expert_held", 0), model["num_local_experts"])
            if "num_local_experts" in published else None
        ),
        top_k=model["num_experts_per_tok"],
        d_ff=model["intermediate_size"],
        shared_d_ff=model["shared_intermediate_size"],
        embedding_multiplier=float(model["embedding_multiplier"]),
        residual_multiplier=float(model["residual_multiplier"]),
        attention_scale=float(model["attention_multiplier"]),
        logits_scaling=float(model["logits_scaling"]),
        **program,
    )


def lowered_programs(conf: dict, traffic: dict, device, use_kernel=True):
    """name -> the lowered program, as `LLMEngine` would call it for this
    configuration and mix: the chunk program of every bucket (a whole
    prompt's where the bucket is no longer than the chunk) and the decode
    program."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.llm import hybrid_kv
    from ray_tpu.models.granite_hybrid import init_params

    eng = conf["engine"]
    cfg = config(conf, max_seq=eng["max_seq"])
    one = SingleDeviceSharding(device)

    def on(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), tree
        )

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)

    params = on(jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0)))
    page, b = eng["page_size"], eng["max_batch"]
    cache = on(jax.eval_shape(
        lambda: hybrid_kv.init_hybrid_cache(cfg, eng["num_pages"] + 1, page, b)
    ))
    chunk = eng.get("prefill_chunk")
    out = {}
    for pad in traffic["fit_prefill_buckets"]:
        n_pages = pad // page
        whole = chunk is None or pad <= chunk
        name = f"prefill_{pad}" if whole else f"prefill_chunk_{chunk}_of_{pad}"
        size = pad if whole else chunk
        out[name] = hybrid_kv.prefill_program(
            cfg, n_pages, size // page, use_kernel
        ).lower(
            params, i32(1, size), cache, i32(n_pages), i32(), i32(), i32()
        )
    key = on(jax.eval_shape(lambda: jax.random.key(0)))
    out["decode"] = hybrid_kv.hybrid_decode.lower(
        params, i32(b, 1), cache, i32(b, -(-eng["max_seq"] // page)), i32(b),
        jax.ShapeDtypeStruct((b,), jnp.bool_, sharding=one),
        jax.ShapeDtypeStruct((b,), jnp.float32, sharding=one), key,
        cfg=cfg, use_kernel=use_kernel,
    )
    return out


# ------------------------------------------------------- bytes and operations
def _layers(model: dict, kind: str) -> int:
    return model["layer_types"].count(kind)


def _d_inner(model: dict) -> int:
    return model["mamba_n_heads"] * model["mamba_d_head"]


def _conv_dim(model: dict) -> int:
    return _d_inner(model) + 2 * model["mamba_n_groups"] * model["mamba_d_state"]


def held_parameters(model: dict) -> int:
    """Parameters of the tree as the configuration holds it."""
    d, di, conv = model["hidden_size"], _d_inner(model), _conv_dim(model)
    h = model["mamba_n_heads"]
    mamba = (d + d * (di + conv + h) + (model["mamba_d_conv"] + 1) * conv
             + 3 * h + di + di * d)
    hq = d  # num_attention_heads x head_dim
    hkv = model["num_key_value_heads"] * (d // model["num_attention_heads"])
    attn = d + 2 * d * hq + 2 * d * hkv
    routed_all = model.get("published", {}).get(
        "num_local_experts", model["num_local_experts"]
    )
    ffn = (d + d * routed_all
           + model["num_local_experts"] * 3 * d * model["intermediate_size"]
           + 3 * d * model["shared_intermediate_size"])
    return (_layers(model, "mamba") * mamba + _layers(model, "attention") * attn
            + model["num_hidden_layers"] * ffn + model["vocab_size"] * d + d)


def held_expert_slots(model: dict) -> int:
    """Held experts over all layers: what a decode step could touch at
    most."""
    return model["num_local_experts"] * model["num_hidden_layers"]


def _traced(engine: dict) -> dict:
    """The engine's counters over the traced steps, where the server
    took them (``server_family``); else over the replica's life."""
    return engine.get("traced") or engine


def ssm_state_bytes_per_decode_step(model: dict, engine: dict) -> float:
    """Bytes of recurrent state a decode step has to move: each decoding
    slot's SSM state (float32 ``[128, 64, 128]``) and convolution tail
    (bfloat16 ``[3, 8448]``), read once and written once in each Mamba
    layer. The program computes all ``max_batch`` slots; the slots that
    were not decoding are not counted."""
    engine = _traced(engine)
    if not engine.get("decode_steps"):
        return 0.0
    slots = engine["slot_steps"] / engine["decode_steps"]
    per_slot = (
        _d_inner(model) * model["mamba_d_state"] * 4
        + (model["mamba_d_conv"] - 1) * _conv_dim(model) * 2
    )
    return 2.0 * slots * _layers(model, "mamba") * per_slot


def _scan_tokens_per_program(engine: dict) -> float:
    """Live tokens one prefill program's chunked scans take, summed over
    its Mamba layers: the serving object's own count over the programs
    it ran."""
    engine = _traced(engine)
    if not engine.get("prefill_programs"):
        return 0.0
    return engine["ssm_scan_tokens"] / engine["prefill_programs"]


def scan_flops_per_token(model: dict) -> float:
    """Operations of the state-space dual form per token and Mamba
    layer at chunks of Q tokens, a multiply-add as two, whatever
    implements it: within its chunk a token meets (Q + 1) / 2 tokens at
    or before it, and each such pair costs a ``C.B`` score a group (2 N)
    and a weighted ``x`` a head (2 P); the chunk's contribution to the
    state and the read of the carried state are 2 P N a token and head
    each. (A form that computes whole Q x Q blocks and masks half does
    twice the first term: not counted, as the prefill attention's masked
    half is not.)"""
    q = model["mamba_chunk_size"]
    h, p = model["mamba_n_heads"], model["mamba_d_head"]
    g, n = model["mamba_n_groups"], model["mamba_d_state"]
    return (q + 1) / 2 * (2.0 * n * g + 2.0 * p * h) + 2 * (2.0 * p * n * h)


def scan_flops_per_program(model: dict, engine: dict) -> float:
    """Operations the chunked scans of one prefill program need."""
    return _scan_tokens_per_program(engine) * scan_flops_per_token(model)


def scan_bytes_per_program(model: dict, engine: dict) -> float:
    """Bytes the same have to move: per token and layer ``x`` in and
    ``y`` out (bf16, the inner width each), ``B`` and ``C`` (bf16) and
    ``dt`` (float32 a head); per program and layer the carried state
    read and written once (float32 ``[H, P, N]``). The within-chunk
    scores and decays need not leave the chip."""
    h, p = model["mamba_n_heads"], model["mamba_d_head"]
    g, n = model["mamba_n_groups"], model["mamba_d_state"]
    per_token = 2 * (2 * h * p) + 2 * (2 * g * n) + 4 * h
    state = 2 * _layers(model, "mamba") * h * p * n * 4
    tokens = _scan_tokens_per_program(engine)
    return tokens * per_token + (state if tokens else 0.0)


# ------------------------------------------------------ against the reference
def check(server, seed: int, whole_prompt_len: int = 4000,
          chunked_prompt_len: int = 9000, decode: int = 4,
          lower: str | None = None) -> dict:
    """``server_family.BenchFamilyServer.check`` for this family, inside
    the replica: a prompt that is prefilled whole and one that goes in
    chunks (state and convolution tail carried, later chunks attending
    earlier chunks' pages, a padded last chunk), then ``decode`` steps
    each through the pages and the slot's state, against the float32
    reference's one full pass over the same tokens, the recurrence a
    token a step, run sublayer by sublayer so that it fits beside the
    engine: with the system's routes forced on the reference, the
    largest absolute logit difference at the last prompt position and
    at each decoded one; each token's routes against the reference's own
    cut; and each Mamba layer's state as the slot holds it after the
    last step against the recurrence's. Runs alone, before any request.
    ``lower`` computes the reference in a lower precision, for the
    reading a limit must fail."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    reference = importlib.import_module("benchmarks.reference_granite_hybrid")
    eng = server.engine
    sizes = reference.for_model(server._conf) | {"lower": lower}
    rng = np.random.default_rng(seed + 11)
    out = {
        "logit_max_abs_err": [], "logit_scale": 0.0, "finite": True,
        "largest_slack": 0.0, "routes_beyond_epsilon": 0,
        "share_routed_otherwise": [], "state_rel_err": 0.0,
        "tokens": 0, "prefill_calls": [], "margin_epsilon": MARGIN_EPSILON,
        "paged_attn_kernel": bool(eng.paged_attn_kernel),
    }
    jitted = {}

    def block_fn(kind, fn):
        # One compiled program per kind of sublayer and sequence length.
        return jitted.setdefault(kind, jax.jit(fn))

    chunk = eng.prefill_chunk
    for n, whole in ((whole_prompt_len, True), (chunked_prompt_len, False)):
        jitted.clear()
        eng.prefill_chunk = None if whole else chunk
        try:
            got = server._run_tapped(
                rng.integers(1, eng.cfg.vocab_size, n).tolist(), decode
            )
        finally:
            eng.prefill_chunk = chunk
        states = np.asarray(eng.cache["ssm"][:, got["slot"]])
        want, record = reference.forward_with_record(
            eng.params, jnp.asarray(got["tokens"], jnp.int32),
            routes=jnp.asarray(got["routes"]),
            rows=list(range(n - 1, n + decode)), block_fn=block_fn, **sizes,
        )
        want = np.asarray(want)
        out["logit_max_abs_err"] += [
            float(v) for v in np.abs(got["logits"] - want).max(-1)
        ]
        out["logit_scale"] = max(out["logit_scale"], float(np.abs(want).max()))
        out["finite"] &= bool(np.isfinite(got["logits"]).all())
        same = (
            np.sort(got["routes"], -1)
            == np.sort(np.asarray(record["routes"]), -1)
        ).all(-1)
        slack = np.asarray(record["slack"])
        out["largest_slack"] = max(out["largest_slack"], float(slack.max()))
        out["routes_beyond_epsilon"] += int((slack > MARGIN_EPSILON).sum())
        out["share_routed_otherwise"].append(float(1.0 - same.mean()))
        ref_states = np.asarray(record["states"])
        diff = np.linalg.norm(
            (states - ref_states).reshape(len(ref_states), -1), axis=-1
        )
        norm = np.linalg.norm(ref_states.reshape(len(ref_states), -1), axis=-1)
        out["state_rel_err"] = max(out["state_rel_err"],
                                   float((diff / norm).max()))
        out["tokens"] += n + decode
        out["prefill_calls"].append(got["prefill_calls"])
    return out


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
            f"the cache's SSM state differs from the reference's recurrence "
            f"by {check['state_rel_err']:.4f} of its norm "
            f"(tolerance {state_tolerance})"
        )
    return problems
