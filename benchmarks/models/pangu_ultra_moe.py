"""Pangu Ultra MoE (``model_type: pangu_ultra_moe``) for
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
# weights and activations at use; expanded prefill through the prefill
# kernel, absorbed decode through the latent pages, batched or grouped
# expert matmuls; float32 router and norms) and the float32 reference
# *on the same routes*, over the last prompt position and four decode
# steps of a 4,000-token prompt prefilled whole and a 9,000-token one
# prefilled in five chunks, on logits of magnitude 4.1-4.2. Five layers
# of bf16 matmuls land within a few hundredths: 0.0355-0.0399 over 15
# runs on the chip, a seed each (PR 33). A dropped pair, an unscaled
# gate, a missing sandwich norm or shared expert, a rope at the wrong
# position, a stale or misplaced cell, or an 8-bit matmul moves logits
# by tenths and fails: the reference with its weights rounded to e4m3
# reads 0.87-1.04 against the system (PERF.md section 6).
LOGIT_TOLERANCE = 0.12
# Every route the system chose must lie within this of the reference's
# own cut, as a share of the reference's 8th selection score: a token
# may go to the reference's 9th expert for its 8th only where the two
# scores are this close. The router runs in float32 on both sides, but
# its input is the residual stream, which the system carries in bf16:
# over the same 15 runs the furthest swap lay 0.0054-0.0085 below the
# cut, 7.5-8.3% of (token, layer) pairs swapped. With e4m3 weights the
# reference's own routes lie 0.177 below. (A router computed in bfloat16
# reads 0.0094 where the same seed reads 0.0058 without: under what the
# bf16 residual stream already costs other seeds; no limit on routes can
# tell it, so what holds the router to float32 is structural:
# tests/test_pangu_ultra_moe.py pins the dtypes.)
MARGIN_EPSILON = 0.03
# The slot's latent pages after the last decode step against the
# reference's ``[Nkv(c); rope(kpe)]``: largest over layers of |C -
# C_ref|_F / |C_ref|_F over every cached position (4,004 and 9,004
# cells a layer). The cells are bf16 roundings of projections of a bf16
# residual stream: 0.0093-0.0100 over the 15 runs; with e4m3 weights
# 0.232. A cell written to the wrong page or offset, a missing Nkv, a
# rope at the wrong position or a stale page is off by its whole norm.
LATENT_TOLERANCE = 0.03

# What of the program this family needs beyond what every serving cell
# needs: the runner looks before it starts anything, so that a checkout
# that lacks them (this cell's parent commit) fails at once and not when
# a replica cannot be built.
PROGRAM_FILES = (
    "models/pangu_ultra_moe.py", "llm/latent_kv.py",
    "ops/pallas/latent_attention.py",
)


def config(model: dict, **program):
    """``PanguUltraMoEConfig`` for the published keys in ``model``;
    ``program`` are fields of the program's own (``max_seq``, ``dtype``,
    ``dense_expert_rows``). A file that states a switch the program does
    not have is refused here, so that it cannot state a model the
    program does not run."""
    from ray_tpu.models.pangu_ultra_moe import PanguUltraMoEConfig

    if model["model_type"] != "pangu_ultra_moe":
        raise ValueError(f"not a Pangu Ultra MoE configuration: {model['model_type']}")
    for key in ("attention_bias", "tie_word_embeddings"):
        if model[key]:
            raise ValueError(f"models/pangu_ultra_moe.py has no {key}")
    if not model["sandwich_norm"]:
        raise ValueError("models/pangu_ultra_moe.py norms each sublayer's output")
    if model["hidden_act"] != "silu":
        raise ValueError("the dense layers and the experts are SwiGLU")
    if model["n_shared_experts"] != 1:
        raise ValueError("models/moe.py applies one shared expert")
    if model["rms_norm_eps"] != 1e-5:
        raise ValueError("ops/norms.py fixes rms_norm eps at 1e-5")
    if model["num_key_value_heads"] != model["num_attention_heads"]:
        raise ValueError("latent attention expands a key and a value per head")
    if model["num_nextn_predict_layers"]:
        raise ValueError("the multi-token-prediction module is not written")
    published = model.get("published", {})
    program.setdefault("max_seq", model["max_position_embeddings"])
    for key in ("dense_expert_rows", "prefill_key_block"):
        if key in model.get("program", {}):
            program.setdefault(key, model["program"][key])
    return PanguUltraMoEConfig(
        vocab_size=model["vocab_size"],
        d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        first_k_dense=model["first_k_dense_replace"],
        n_heads=model["num_attention_heads"],
        q_lora_rank=model["q_lora_rank"],
        kv_lora_rank=model["kv_lora_rank"],
        qk_nope_head_dim=model["qk_nope_head_dim"],
        qk_rope_head_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"],
        rope_theta=float(model["rope_theta"]),
        dense_d_ff=model["intermediate_size"],
        # The router is as wide as the model's experts; the file's own
        # count is how many of them are held here.
        num_experts=published.get("n_routed_experts", model["n_routed_experts"]),
        experts_held=(
            (model.get("first_expert_held", 0), model["n_routed_experts"])
            if "n_routed_experts" in published else None
        ),
        top_k=model["num_experts_per_tok"],
        d_ff=model["moe_intermediate_size"],
        shared_d_ff=model["moe_intermediate_size"] * model["n_shared_experts"],
        norm_topk_prob=bool(model["norm_topk_prob"]),
        routed_scaling_factor=float(model["routed_scaling_factor"]),
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

    from ray_tpu.llm import latent_kv
    from ray_tpu.models.pangu_ultra_moe import init_params

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
        lambda: latent_kv.init_latent_cache(cfg, eng["num_pages"] + 1, page)
    ))
    chunk = eng.get("prefill_chunk")
    out = {}
    for pad in traffic["fit_prefill_buckets"]:
        n_pages = pad // page
        whole = chunk is None or pad <= chunk
        name = f"prefill_{pad}" if whole else f"prefill_chunk_{chunk}_of_{pad}"
        size = pad if whole else chunk
        out[name] = latent_kv.prefill_program(
            cfg, n_pages, size // page, use_kernel
        ).lower(
            params, i32(1, size), cache, i32(n_pages), i32(), i32()
        )
    key = on(jax.eval_shape(lambda: jax.random.key(0)))
    out["decode"] = latent_kv.latent_decode.lower(
        params, i32(b, 1), cache, i32(b, -(-eng["max_seq"] // page)), i32(b),
        jax.ShapeDtypeStruct((b,), jnp.bool_, sharding=one),
        jax.ShapeDtypeStruct((b,), jnp.float32, sharding=one), key,
        cfg=cfg, use_kernel=use_kernel,
    )
    return out


# ------------------------------------------------------- bytes and operations
def _expert_layers(model: dict) -> int:
    return model["num_hidden_layers"] - model["first_k_dense_replace"]


def latent_dim(model: dict) -> int:
    return model["kv_lora_rank"] + model["qk_rope_head_dim"]


def held_parameters(model: dict) -> int:
    """Parameters of the tree as the configuration holds it."""
    d, h = model["hidden_size"], model["num_attention_heads"]
    rq, rkv = model["q_lora_rank"], model["kv_lora_rank"]
    qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    mla = (d * rq + rq * h * qk + d * latent_dim(model)
           + h * rkv * (model["qk_nope_head_dim"] + model["v_head_dim"])
           + h * model["v_head_dim"] * d + 4 * d + rq + rkv)
    dense = 3 * d * model["intermediate_size"]
    routed_all = model.get("published", {}).get(
        "n_routed_experts", model["n_routed_experts"]
    )
    f = model["moe_intermediate_size"]
    expert = (d * routed_all + routed_all
              + (model["n_routed_experts"] + model["n_shared_experts"]) * 3 * d * f)
    return (model["num_hidden_layers"] * mla
            + model["first_k_dense_replace"] * dense
            + _expert_layers(model) * expert + 2 * model["vocab_size"] * d + d)


def held_expert_slots(model: dict) -> int:
    """Held experts over all expert layers: what a decode step could
    touch at most."""
    return model["n_routed_experts"] * _expert_layers(model)


def _traced(engine: dict) -> dict:
    """The engine's counters over the traced steps, where the server
    took them (``server_family``); else over the replica's life."""
    return engine.get("traced") or engine


def _live_tokens_per_decode_step(model: dict, engine: dict) -> float:
    """Cached tokens a decode step attends, all decoding slots of one
    layer: the live pages of the engine's own count (pages up to each
    slot's position, free slots' one dump page not among them) times the
    page size. A page's dead tail (past the position) is fetched and
    multiplied like its live cells, so whole pages are what the kernel
    has to move."""
    engine = _traced(engine)
    if not engine.get("decode_steps"):
        return 0.0
    pages = engine["attn_pages_live"] / engine["decode_steps"]
    return pages * model["engine"]["page_size"]


def latent_attn_bytes_per_decode_step(model: dict, engine: dict) -> float:
    """Bytes the latent decode kernel has to read in one decode step:
    every live latent page once, in every layer (bf16 cells of
    ``kv_lora_rank + qk_rope_head_dim`` numbers: 1,152 B a token). The
    queries and the outputs (128 rows a slot) are not counted: under 2%
    of a 4k context's cells."""
    return (_live_tokens_per_decode_step(model, engine)
            * model["num_hidden_layers"] * latent_dim(model) * 2)


def latent_attn_flops_per_decode_step(model: dict, engine: dict) -> float:
    """Operations of the same: per live token and layer every head's
    score (W wide) and its weighted sum (``kv_lora_rank`` wide), a
    multiply-add as two: 2 x 128 x (576 + 512)."""
    per_token = 2.0 * model["num_attention_heads"] * (
        latent_dim(model) + model["kv_lora_rank"]
    )
    return (_live_tokens_per_decode_step(model, engine)
            * model["num_hidden_layers"] * per_token)


def _pair_heads_per_prefill_program(model: dict, engine: dict) -> float:
    """(query, key, head) triples one prefill program attends under the
    causal mask, all layers: the serving object's own count of pairs
    over the programs it ran."""
    engine = _traced(engine)
    if not engine.get("latent_prefill_programs"):
        return 0.0
    pairs = engine["latent_prefill_pairs"] / engine["latent_prefill_programs"]
    return pairs * model["num_attention_heads"]


def prefill_attn_flops_per_program(model: dict, engine: dict) -> float:
    """Operations of a prefill program's attention kernel as the
    arithmetic needs them: per (query, key) pair and head a score 192
    wide and a weighted value 128 wide, a multiply-add as two. (The
    kernel's rotary product is padded to 128: it executes 384 where this
    counts 320, so the share reads under what the matmul unit does.)"""
    per = 2.0 * (model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
                 + model["v_head_dim"])
    return _pair_heads_per_prefill_program(model, engine) * per


def prefill_attn_bytes_per_program(model: dict, engine: dict) -> float:
    """Bytes of the same: a head's keys and values (2 x 128 bf16 a token)
    are read once per block of 512 queries, 1 B a (query, key, head);
    operations outnumber them 640 to 1, so compute bounds this kernel."""
    return _pair_heads_per_prefill_program(model, engine) * (
        2.0 * (model["qk_nope_head_dim"] + model["v_head_dim"]) / 512
    )


# ------------------------------------------------------ against the reference
def check(server, seed: int, whole_prompt_len: int = 4000,
          chunked_prompt_len: int = 9000, decode: int = 4,
          lower: str | None = None) -> dict:
    """``server_family.BenchFamilyServer.check`` for this family, inside
    the replica: a prompt that is prefilled whole and one that goes in
    chunks (later chunks attend earlier chunks' latent pages), then
    ``decode`` steps each in the absorbed form, against the float32
    reference's one full pass over the same tokens in the NON-absorbed
    form, run sublayer by sublayer so that it fits beside the engine:
    with the system's routes forced on the reference, the largest
    absolute logit difference at the last prompt position and at each
    decoded one; each token's routes against the reference's own cut;
    and the slot's latent pages after the last step against the
    reference's ``[Nkv(c); rope(kpe)]`` of every position. Runs alone,
    before any request. ``lower`` computes the reference in a lower
    precision, for the reading a limit must fail."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    reference = importlib.import_module("benchmarks.reference_pangu_ultra_moe")
    eng = server.engine
    sizes = reference.for_model(server._conf) | {"lower": lower}
    rng = np.random.default_rng(seed + 11)
    out = {
        "logit_max_abs_err": [], "logit_scale": 0.0, "finite": True,
        "largest_slack": 0.0, "routes_beyond_epsilon": 0,
        "share_routed_otherwise": [], "latent_rel_err": 0.0,
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
        # The cache itself: the slot's pages, every cached position.
        held = n + decode
        pages = jnp.asarray(got["pages"], jnp.int32)
        cells = np.asarray(
            eng.cache["latent"][:, pages].astype(jnp.float32)
        ).reshape(eng.cfg.n_layers, -1, eng.cfg.cell_width)[
            :, :held, : eng.cfg.latent_dim
        ]
        ref_cells = np.asarray(record["latents"])
        diff = np.linalg.norm((cells - ref_cells).reshape(len(cells), -1), axis=-1)
        norm = np.linalg.norm(ref_cells.reshape(len(cells), -1), axis=-1)
        out["latent_rel_err"] = max(out["latent_rel_err"],
                                    float((diff / norm).max()))
        out["tokens"] += held
        out["prefill_calls"].append(got["prefill_calls"])
    return out


def check_problems(check: dict, logit_tolerance: float = LOGIT_TOLERANCE,
                   epsilon: float = MARGIN_EPSILON,
                   latent_tolerance: float = LATENT_TOLERANCE) -> list[str]:
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
    if check["latent_rel_err"] > latent_tolerance:
        problems.append(
            f"the latent pages differ from the reference's [c; kpe] by "
            f"{check['latent_rel_err']:.4f} of their norm "
            f"(tolerance {latent_tolerance})"
        )
    return problems
