"""Qwen3-Next (``model_type: qwen3_next``) for
``runners/serve_family.py``: the program's config from the published
keys, the serving programs lowered at a configuration's sizes, the
comparison with the plain reference and its limits, and the bytes and
operations that the per-layer metrics divide by time. The names are the
ones ``models/granite_hybrid.py`` has for its family."""

from __future__ import annotations

import importlib

# Largest |logit| difference between the timed programs' logits (bf16
# weights and activations at use; the chunked delta rule at chunk 32,
# grouped or every-row expert matmuls, the prefill and paged attention
# kernels at a head of 256; float32 state, gates, decays and router) and
# the float32 reference *on the same routes*, over the last prompt
# position and four decode steps of a 4,000-token prompt prefilled whole
# and a 9,000-token one prefilled in five chunks. The logits read
# 4.1-4.7 at their largest; eight sublayers of bf16 matmuls land at
# 0.059-0.076 over 17 runs on the chip, 15 seeds (PR 51). A dropped
# gate, norm or rotation, a stale state or tail, or an 8-bit matmul
# moves logits by tenths and fails: the reference with its weights
# rounded to e4m3 reads 1.32-1.41 against the system (two seeds;
# PERF.md section 6). The limit is 3.3 times the one and 5.3 times
# under the other.
LOGIT_TOLERANCE = 0.25
# Every route the system chose must lie within this of the reference's
# own cut: ``1 - p(lowest applied) / p(tenth chosen)`` of the router's
# probabilities, which is ``1 - exp(logit gap)``. The router runs in
# float32 on both sides, but its input is the residual stream, which the
# system carries in bf16: over the same runs the furthest swap lay
# 0.050-0.066 below the cut, 12.4-13.2% of (token, layer) pairs swapped
# (with 512 experts the tenth and eleventh probabilities of a token lie
# close). With e4m3 weights the reference's own routes lie 0.69-0.78
# below, 93% swapped. A bfloat16 ROUTER in the reference reads 0.053
# (its logits 0.074, its states as float32's) and passes every limit
# here: no limit on these measures can tell it from float32, as PR 31
# found; what holds the router to float32 is `moe_ffn`'s own casts,
# pinned by tests/test_nemotron_h.py.
MARGIN_EPSILON = 0.2
# Each Gated DeltaNet layer's state after the last decode step, as the
# cache holds it for the slot, against the reference's token-by-token
# recurrence: largest over the three layers of |S - S_ref|_F /
# |S_ref|_F. The program's inputs to the rule are bf16 activations, the
# state itself is float32 and is carried across the chunks of a prompt
# (four times in the 9,000-token one): 0.0143-0.0153 over the same runs;
# with e4m3 weights 0.31-0.32. A state that is stale, not
# carried across chunks, or read past the true length is off by its
# whole norm. The second and third layers' inputs already differ by the
# bf16 stream above them, so a state ROUNDED to bfloat16 every token
# reads only 0.023-0.025 here (two seeds) and passes: the next limit is
# the one that fails it.
STATE_TOLERANCE = 0.05
# The FIRST layer's state alone, by the same measure. Its input is the
# embedding's own rows, the same numbers on both sides, so what it reads
# is the rule's arithmetic and nothing upstream of it (the normed input
# rounded to bf16, the projections, the bf16 convolution tail across
# chunks and decode steps, the rule at full float32 precision):
# 0.00284-0.00298 over the same runs, where the reference with its state
# rounded to bfloat16 after every token reads 0.0081-0.0084 (two seeds)
# and fails, by this limit alone (e4m3 weights: 0.072). The limit is 1.7
# times the one and 1.6 times under the other: the two readings are 2.8
# times apart and each moves by under 4% between seeds. With the rule's
# products at one bf16 pass the system itself
# read 0.42% on 2,048 tokens alone and no limit could stand between
# (`models/qwen3_next.py gdn_chunked`).
FIRST_STATE_TOLERANCE = 0.005

# What of the program this family needs beyond what every serving cell
# needs: the runner looks before it starts anything, so that a checkout
# that lacks them (this cell's parent commit) fails at once and not when
# a replica cannot be built.
PROGRAM_FILES = ("models/qwen3_next.py", "llm/hybrid_kv.py")


def config(model: dict, **program):
    """``Qwen3NextConfig`` for the published keys in ``model``;
    ``program`` are fields of the program's own (``max_seq``, ``dtype``,
    ``dense_expert_rows``, ``gdn_chunk``). A file that states a switch
    the program does not have is refused here, so that it cannot state a
    model the program does not run."""
    from ray_tpu.models.qwen3_next import Qwen3NextConfig, sublayers

    if model["model_type"] != "qwen3_next":
        raise ValueError(f"not a Qwen3-Next configuration: {model['model_type']}")
    for key in ("rope_scaling", "use_sliding_window", "mlp_only_layers",
                "tie_word_embeddings"):
        if model[key]:
            raise ValueError(f"models/qwen3_next.py has no {key}")
    for key, want in (
        ("hidden_act", "silu"), ("decoder_sparse_step", 1),
        ("norm_topk_prob", True),
    ):
        if model[key] != want:
            raise ValueError(f"models/qwen3_next.py runs {key} = {want!r}")
    if model["linear_num_value_heads"] % model["linear_num_key_heads"]:
        raise ValueError("linear_num_key_heads does not divide the value heads")
    published = model.get("published", {})
    program.setdefault("max_seq", model["max_position_embeddings"])
    for key in ("dense_expert_rows", "gdn_chunk"):
        if key in model.get("program", {}):
            program.setdefault(key, model["program"][key])
    return Qwen3NextConfig(
        vocab_size=model["vocab_size"],
        d_model=model["hidden_size"],
        pattern=sublayers(
            model["num_hidden_layers"], model["full_attention_interval"]
        ),
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        head_dim=model["head_dim"],
        rotary_dim=int(model["head_dim"] * model["partial_rotary_factor"]),
        rope_theta=float(model["rope_theta"]),
        norm_eps=model["rms_norm_eps"],
        gdn_key_heads=model["linear_num_key_heads"],
        gdn_value_heads=model["linear_num_value_heads"],
        gdn_key_dim=model["linear_key_head_dim"],
        gdn_value_dim=model["linear_value_head_dim"],
        conv_kernel=model["linear_conv_kernel_dim"],
        # The router is as wide as the model's experts; the file's own
        # count is how many of them are held here.
        num_experts=published.get("num_experts", model["num_experts"]),
        experts_held=(
            (model.get("first_expert_held", 0), model["num_experts"])
            if "num_experts" in published else None
        ),
        top_k=model["num_experts_per_tok"],
        d_ff=model["moe_intermediate_size"],
        shared_d_ff=model["shared_expert_intermediate_size"],
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
    from ray_tpu.models.qwen3_next import init_params

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
def _linear_layers(model: dict) -> int:
    n, every = model["num_hidden_layers"], model["full_attention_interval"]
    return n - n // every


def _key_width(model: dict) -> int:
    return model["linear_num_key_heads"] * model["linear_key_head_dim"]


def _value_width(model: dict) -> int:
    return model["linear_num_value_heads"] * model["linear_value_head_dim"]


def _conv_dim(model: dict) -> int:
    return 2 * _key_width(model) + _value_width(model)


def _gdn_chunk(model: dict) -> int:
    """The chunk the prefill programs run the rule at (the program's own
    default where the file does not set it)."""
    return model.get("program", {}).get("gdn_chunk", 32)


def held_parameters(model: dict) -> int:
    """Parameters of the tree as the configuration holds it."""
    d, hv = model["hidden_size"], model["linear_num_value_heads"]
    conv, value = _conv_dim(model), _value_width(model)
    linear = (d + d * (conv + value) + d * 2 * hv
              + model["linear_conv_kernel_dim"] * conv + 2 * hv
              + model["linear_value_head_dim"] + value * d)
    dh = model["head_dim"]
    hq, hkv = model["num_attention_heads"] * dh, model["num_key_value_heads"] * dh
    full = d + d * 2 * hq + 2 * d * hkv + 2 * dh + hq * d
    routed_all = model.get("published", {}).get(
        "num_experts", model["num_experts"]
    )
    ffn = (d + d * routed_all
           + model["num_experts"] * 3 * d * model["moe_intermediate_size"]
           + 3 * d * model["shared_expert_intermediate_size"] + d)
    n_linear = _linear_layers(model)
    return (n_linear * linear + (model["num_hidden_layers"] - n_linear) * full
            + model["num_hidden_layers"] * ffn
            + 2 * model["vocab_size"] * d + d)


def held_expert_slots(model: dict) -> int:
    """Held experts over all layers: what a decode step could touch at
    most."""
    return model["num_experts"] * model["num_hidden_layers"]


def _traced(engine: dict) -> dict:
    """The engine's counters over the traced steps, where the server
    took them (``server_family``); else over the replica's life."""
    return engine.get("traced") or engine


def gdn_state_bytes_per_slot(model: dict) -> int:
    """Bytes of one slot's state in one Gated DeltaNet layer: a float32
    ``[dk, dv]`` matrix a value head (32 x 128 x 128 x 4 = 2,097,152)."""
    return (_value_width(model) * model["linear_key_head_dim"]) * 4


def gdn_state_bytes_per_decode_step(model: dict, engine: dict) -> float:
    """Bytes of recurrent state a decode step has to move: each decoding
    slot's matrix state and convolution tail (bfloat16 ``[3, 8192]``),
    read once and written once in each Gated DeltaNet layer. The program
    computes all ``max_batch`` slots; the slots that were not decoding
    are not counted."""
    engine = _traced(engine)
    if not engine.get("decode_steps"):
        return 0.0
    slots = engine["slot_steps"] / engine["decode_steps"]
    per_slot = (
        gdn_state_bytes_per_slot(model)
        + (model["linear_conv_kernel_dim"] - 1) * _conv_dim(model) * 2
    )
    return 2.0 * slots * _linear_layers(model) * per_slot


def _scan_tokens_per_program(engine: dict) -> float:
    """Live tokens one prefill program's chunked rules take, summed over
    its Gated DeltaNet layers: the serving object's own count over the
    programs it ran. A program without the counter (the parent of the PR
    that brought it) gives 0 and the metric is left out."""
    engine = _traced(engine)
    if not engine.get("prefill_programs"):
        return 0.0
    return engine.get("gdn_scan_tokens", 0) / engine["prefill_programs"]


def gdn_scan_flops_per_token(model: dict) -> float:
    """Operations of the chunked gated delta rule per token and layer at
    chunks of C tokens, a multiply-add as two, whatever implements it.
    Within its chunk a token meets (C - 1) / 2 tokens before it and
    (C + 1) / 2 at or before it. Per key head: a ``k.k`` score with each
    token before it and a ``q.k`` score with each at or before it (2 dk
    each). Per value head: its row of the triangular solve for ``U`` and
    ``W`` (each token before it, 2 (dv + dk)); ``W S``, ``Q S`` and the
    chunk's contribution ``K^T V'`` to the state (2 dk dv each); the
    scores times ``V'`` (2 dv each token at or before it). A form that
    builds the whole inverse, or computes C x C blocks and masks half,
    does more: not counted."""
    c = _gdn_chunk(model)
    hk, dk = model["linear_num_key_heads"], model["linear_key_head_dim"]
    hv, dv = model["linear_num_value_heads"], model["linear_value_head_dim"]
    before, upto = (c - 1) / 2, (c + 1) / 2
    return (
        hk * (before + upto) * 2.0 * dk
        + hv * (before * 2.0 * (dv + dk) + 3 * 2.0 * dk * dv + upto * 2.0 * dv)
    )


def gdn_scan_flops_per_program(model: dict, engine: dict) -> float:
    """Operations the chunked rules of one prefill program need."""
    return _scan_tokens_per_program(engine) * gdn_scan_flops_per_token(model)


def gdn_scan_bytes_per_program(model: dict, engine: dict) -> float:
    """Bytes the same have to move: per token and layer ``q`` and ``k``
    (bf16, the key width each), ``v`` in and ``o`` out (bf16, the value
    width each), ``beta`` and ``g`` (float32 a value head); per program
    and layer the carried state read and written once (float32 ``[Hv,
    dk, dv]``). The within-chunk scores, decays and the solve need not
    leave the chip."""
    hv = model["linear_num_value_heads"]
    per_token = 2 * (2 * _key_width(model)) + 2 * (2 * _value_width(model)) + 8 * hv
    state = 2 * _linear_layers(model) * gdn_state_bytes_per_slot(model)
    tokens = _scan_tokens_per_program(engine)
    return tokens * per_token + (state if tokens else 0.0)


# ------------------------------------------------------ against the reference
def check(server, seed: int, whole_prompt_len: int = 4000,
          chunked_prompt_len: int = 9000, decode: int = 4,
          lower: str | None = None) -> dict:
    """``server_family.BenchFamilyServer.check`` for this family, inside
    the replica: a prompt that is prefilled whole and one that goes in
    chunks (matrix state and convolution tail carried, later chunks
    attending earlier chunks' pages at their true positions, a padded
    last chunk), then ``decode`` steps each through the pages and the
    slot's state, against the float32 reference's one full pass over the
    same tokens, the recurrence a token a step, run sublayer by sublayer
    so that it fits beside the engine: with the system's routes forced
    on the reference, the largest absolute logit difference at the last
    prompt position and at each decoded one; each token's routes against
    the reference's own cut; and each Gated DeltaNet layer's state as
    the slot holds it after the last step against the recurrence's (the
    largest of the three, and the first layer's apart). Runs
    alone, before any request. ``lower`` computes the reference in a
    lower precision, for the reading a limit must fail."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    reference = importlib.import_module("benchmarks.reference_qwen3_next")
    eng = server.engine
    sizes = reference.for_model(server._conf) | {"lower": lower}
    rng = np.random.default_rng(seed + 11)
    out = {
        "logit_max_abs_err": [], "logit_scale": 0.0, "finite": True,
        "largest_slack": 0.0, "routes_beyond_epsilon": 0,
        "share_routed_otherwise": [], "state_rel_err": 0.0,
        "first_state_rel_err": 0.0, "tokens": 0, "prefill_calls": [], "margin_epsilon": MARGIN_EPSILON,
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
        states = np.asarray(eng.cache["gdn"][:, got["slot"]])
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
        out["first_state_rel_err"] = max(out["first_state_rel_err"],
                                         float(diff[0] / norm[0]))
        out["tokens"] += n + decode
        out["prefill_calls"].append(got["prefill_calls"])
    return out


def check_problems(check: dict, logit_tolerance: float = LOGIT_TOLERANCE,
                   epsilon: float = MARGIN_EPSILON,
                   state_tolerance: float = STATE_TOLERANCE,
                   first_state_tolerance: float = FIRST_STATE_TOLERANCE,
                   ) -> list[str]:
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
            f"the cache's delta-rule state differs from the reference's "
            f"recurrence by {check['state_rel_err']:.4f} of its norm "
            f"(tolerance {state_tolerance})"
        )
    if check["first_state_rel_err"] > first_state_tolerance:
        problems.append(
            f"the first layer's delta-rule state, whose input is the "
            f"embedding itself, differs from the reference's recurrence by "
            f"{check['first_state_rel_err']:.5f} of its norm "
            f"(tolerance {first_state_tolerance})"
        )
    return problems
