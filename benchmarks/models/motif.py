"""Motif-3-Beta's language model (``model_type: Motif``) for
``runners/serve_family.py``: the program's config from the published
keys, the serving programs lowered at a configuration's sizes, the
comparison with the plain reference and its limits, and the bytes and
operations that per-layer metrics divide by time. The names are the ones
``benchmarks/models/glm5_next.py`` has for its family."""

from __future__ import annotations

import importlib

# The limits of the check, each between two readings with room on both
# sides (my chip runs, PR 65: the system's range over the runs of
# `motif3-longctx-16` on SEEDS_READ seeds, three tapped prompts each; the
# departures of the reference on seed 2147486911, the 9,000-token prompt,
# the table under `check_problems`; `weights_e4m3` is the nearest
# precision below the configuration's bfloat16).
#
# Largest |logit| difference between the timed programs' logits (bf16
# weights and activations at use, four bf16 residual streams; attention
# expanded by two kernels in the chunks and absorbed in the steps, the
# subtraction's result a bf16 head; grouped or every-row PolyNorm experts
# whose norms are float32; float32 router and residual mixing) and the
# float32 reference *on the same routes*, over the last prompt position
# and four decode steps of a 2,000-token prompt prefilled whole, a
# 9,000-token one prefilled in five chunks and a 33,001-token one in
# seventeen. The logits read 3.8-4.3 at their largest; ten sublayers of
# bf16 matmuls land at LOGIT_RANGE. The least departure is e4m3
# weights' 1.56 (`static_h` 3.13, `lambda_const` 2.31). The limit is
# twice the one and six times under the other.
LOGIT_TOLERANCE = 0.25
# Every route the system chose must lie within this of the reference's
# own cut, as a share of the reference's 8th selection score (sigmoid
# scores: `reference_pangu_ultra_moe`'s measure, whose router this is
# without its bias). The furthest swap lay SLACK_RANGE below the cut,
# 17-18% of (token, layer) pairs swapped (384 experts: the eighth and
# ninth scores lie close). e4m3 weights 0.32, every other departure but
# the router's over 0.6.
MARGIN_EPSILON = 0.05
# The cells the cache holds of the tapped request against the
# reference's, the larger of two |A - A_ref|_F / |A_ref|_F: the full
# layer's cells at every position, from the request's pages
# (CELL_RANGE: the third layer's, behind four sublayers of bf16
# streams); each window layer's last W cells, from the slot's ring
# (RING_RANGE: the fifth layer's the largest). e4m3 weights 0.24 and
# 0.32, `static_h` 0.23 and 0.34. The limit is 2.6 times the one and
# four times under the other. A cell at the wrong page, position or ring
# index, or a ring carried wrongly across chunks, is off by its norm.
CELL_TOLERANCE = 0.06

# What of the program this family needs beyond what every serving cell
# needs: the runner looks before it starts anything, so that a checkout
# that lacks them (this cell's parent commit) fails at once and not when
# a replica cannot be built.
PROGRAM_FILES = ("models/motif.py", "llm/hybrid_kv.py")


def _first_layer(model: dict) -> int:
    return model.get("first_layer", 0)


def config(model: dict, **program):
    """``MotifConfig`` for the published keys in ``model``; ``program``
    are fields of the program's own (``max_seq``, ``dtype``,
    ``dense_expert_rows``). A file that states a switch the program does
    not have is refused here, so that it cannot state a model the program
    does not run."""
    from ray_tpu.models.motif import MotifConfig, sublayers

    if model["model_type"] != "Motif":
        raise ValueError(f"not a Motif configuration: {model['model_type']}")
    for key in ("tie_word_embeddings", "num_nextn_predict_layers",
                "headwise_attn_output_gate", "score_before_experts",
                "mhc_identity_init"):
        if model[key]:
            raise ValueError(f"models/motif.py has no {key}")
    for key, want in (
        ("attention_cls", "gdla"), ("diff_v2", True),
        ("elementwise_attn_output_gate", True), ("hidden_act", "poly_norm"),
        ("mhc_enabled", True), ("route_norm", True), ("score_func", "sigmoid"),
        ("num_shared_experts", 1), ("interleave_moe_layer_step", 1),
        ("use_sliding_window", True), ("sliding_window_pattern", "interleave"),
        ("k_ratio", 1), ("mscale", 1), ("polynorm_output_scale_per_layer", {}),
    ):
        if model[key] != want:
            raise ValueError(f"models/motif.py runs {key} = {want!r}")
    if model["rope_scaling"]["apply_yarn_scaling"]:
        raise ValueError("models/motif.py rotates plainly: no YaRN scaling")
    if model["swa_rope_theta"] != model["rope_theta"]:
        raise ValueError("models/motif.py has one rotary base for both kinds")
    published = model.get("published", {})
    first = _first_layer(model)
    program.setdefault("max_seq", model["max_position_embeddings"])
    for key in ("dense_expert_rows",):
        if key in model.get("program", {}):
            program.setdefault(key, model["program"][key])
    return MotifConfig(
        vocab_size=model["vocab_size"],
        d_model=model["hidden_size"],
        pattern=sublayers(
            model["num_hidden_layers"], first + model["n_dense_first_layers"],
            model["sliding_window_period"], first=first,
        ),
        norm_eps=model["rms_norm_eps"],
        hc_mult=model["mhc_expansion_rate"],
        hc_sinkhorn_iters=model["mhc_sinkhorn_iters"],
        hc_eps=model["assumed_values"]["mhc_eps"],
        hidden_clamp=float(model["hidden_clamp"]),
        n_heads=model["num_attention_heads"],
        noise_heads=model["num_noise_heads"],
        n_kv_heads=model["num_key_value_heads"],
        q_lora_rank=model["q_lora_rank"],
        kv_lora_rank=model["kv_lora_rank"],
        qk_nope_head_dim=model["head_dim"] - model["qk_rope_head_dim"],
        qk_rope_head_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"],
        rope_theta=float(model["rope_theta"]),
        sliding_window=model["sliding_window"],
        dense_d_ff=model["intermediate_size"],
        # The router is as wide as the model's experts; the file's own
        # count is how many of them are held here.
        num_experts=published.get("num_experts", model["num_experts"]),
        experts_held=(
            (model.get("first_expert_held", 0), model["num_experts"])
            if "num_experts" in published else None
        ),
        top_k=model["experts_top_k"],
        d_ff=model["moe_intermediate_size"],
        shared_d_ff=model["moe_intermediate_size"] * model["num_shared_experts"],
        routed_scaling_factor=float(model["route_scale"]),
        polynorm_scale=float(model["polynorm_output_scale"]),
        polynorm_clamp=float(model["polynorm_bias_clamp"]),
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
    from ray_tpu.models.motif import init_params

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
def full_layers(model: dict) -> int:
    period, first = model["sliding_window_period"], _first_layer(model)
    return sum(
        (first + i) % period == period - 1
        for i in range(model["num_hidden_layers"])
    )


def window_layers(model: dict) -> int:
    return model["num_hidden_layers"] - full_layers(model)


def _expert_layers(model: dict) -> int:
    return model["num_hidden_layers"] - model["n_dense_first_layers"]


def latent_dim(model: dict) -> int:
    return model["kv_lora_rank"] + model["qk_rope_head_dim"]


def held_parameters(model: dict) -> int:
    """Parameters of the tree as the configuration holds it."""
    d, n = model["hidden_size"], model["mhc_expansion_rate"]
    hc = n * d * (2 * n + n * n) + 3 + 2 * n + n * n
    heads, groups = model["num_attention_heads"], model["num_key_value_heads"]
    signal = heads - model["num_noise_heads"]
    rq, rkv = model["q_lora_rank"], model["kv_lora_rank"]
    nope = model["head_dim"] - model["qk_rope_head_dim"]
    width = signal * model["v_head_dim"]
    mixer = (d + d * rq + rq + rq * heads * model["head_dim"]
             + d * latent_dim(model) + rkv
             + groups * rkv * (nope + model["v_head_dim"])
             + d * signal + 2 * d * width + hc)
    routed_all = model.get("published", {}).get(
        "num_experts", model["num_experts"]
    )
    f = model["moe_intermediate_size"]
    sparse = (d + d * routed_all + model["num_experts"] * (3 * d * f + 4)
              + (3 * d * f + 4) * model["num_shared_experts"] + hc)
    dense = d + 3 * d * model["intermediate_size"] + 4 + hc
    n_sparse = _expert_layers(model)
    return (model["num_hidden_layers"] * mixer + n_sparse * sparse
            + model["n_dense_first_layers"] * dense
            + 2 * model["vocab_size"] * d + d)


def held_expert_slots(model: dict) -> int:
    """Held experts over all sparse-FFN layers: what a decode step could
    touch at most."""
    return model["num_experts"] * _expert_layers(model)


def _traced(engine: dict) -> dict:
    """The engine's counters over the traced steps, where the server
    took them (``server_family``); else over the replica's life."""
    return engine.get("traced") or engine


def _per_program(engine: dict, key: str) -> float:
    """A counter of the serving object over the prefill programs it ran,
    a program's share; 0 without the counter."""
    engine = _traced(engine)
    if not engine.get("prefill_programs"):
        return 0.0
    return engine.get(key, 0) / engine["prefill_programs"]


def _per_decode_step(engine: dict, key: str) -> float:
    engine = _traced(engine)
    if not engine.get("decode_steps"):
        return 0.0
    return engine.get(key, 0) / engine["decode_steps"]


def _live_tokens_per_program(model: dict, engine: dict) -> float:
    """Live tokens of one prefill program: ``mhc_tokens`` counts them
    once a sublayer."""
    return _per_program(engine, "mhc_tokens") / (2 * model["num_hidden_layers"])


def _pair_flops(model: dict) -> float:
    """Per (query, key) pair and query head in the expanded form: a
    score ``head_dim`` (192) wide and a weighted value ``v_head_dim``
    (128) wide, a multiply-add as two."""
    return 2.0 * (model["head_dim"] + model["v_head_dim"])


def latent_prefill_flops_per_program(model: dict, engine: dict) -> float:
    """Operations the grouped latent prefill kernel
    (``latent_prefill_attention`` at 80 query heads over 16 expanded
    groups, under ``mla:attend`` in the chunk programs) needs in one
    program: per causal (query, key) pair of the full layers
    (``prefill_attn_pairs``) and query head `_pair_flops`. (The kernel's
    rotary product is padded to 128: it executes 384 where this counts
    320.)"""
    pairs = _per_program(engine, "prefill_attn_pairs")
    return pairs * model["num_attention_heads"] * _pair_flops(model)


def latent_prefill_bytes_per_program(model: dict, engine: dict) -> float:
    """Bytes the same has to move, all bf16: per query and head ``q`` in
    (192) and the result out (128); the context's expanded keys and
    values once a query block of 1,024 (16 groups x 256 a key, and the
    rotary key 64): the mean context of a query by its pairs."""
    tokens = _live_tokens_per_program(model, engine) * full_layers(model)
    if not tokens:
        return 0.0
    heads, groups = model["num_attention_heads"], model["num_key_value_heads"]
    context = _per_program(engine, "prefill_attn_pairs") / tokens
    nope = model["head_dim"] - model["qk_rope_head_dim"]
    key = groups * (nope + model["v_head_dim"]) + model["qk_rope_head_dim"]
    per_token = heads * (model["head_dim"] + model["v_head_dim"])
    return 2.0 * (tokens * per_token + tokens / 1024.0 * context * key)


def latent_decode_flops_per_step(model: dict, engine: dict) -> float:
    """Operations the latent decode kernel (``latent_paged_attention`` at
    80 rows a slot, under ``mla:attend`` in the decode program) needs in
    one step: per live cached token of the full layers every head's
    score (576 wide) and its weighted sum (512 wide), a multiply-add as
    two; live tokens by the engine's ``attn_pages_live``."""
    tokens = (_per_decode_step(engine, "attn_pages_live")
              * model["engine"]["page_size"])
    per_token = 2.0 * model["num_attention_heads"] * (
        latent_dim(model) + model["kv_lora_rank"]
    )
    return tokens * full_layers(model) * per_token


def latent_decode_bytes_per_step(model: dict, engine: dict) -> float:
    """Bytes of the same: every live page of the full layers once (bf16
    cells of 576 numbers: 1,152 B a token)."""
    tokens = (_per_decode_step(engine, "attn_pages_live")
              * model["engine"]["page_size"])
    return tokens * full_layers(model) * latent_dim(model) * 2.0


def window_bytes_per_slot(model: dict) -> int:
    """Bytes of one slot's ring in one window layer as the arithmetic
    needs them: W bf16 cells of 576 numbers."""
    return model["sliding_window"] * latent_dim(model) * 2


def window_attn_flops_per_program(model: dict, engine: dict) -> float:
    """Operations the window layers' attention of one prefill program
    needs (the band kernel at a score width of 192 and a value width of
    128, five query heads a group; under ``attn:window/``): per (query,
    key) pair inside the band (``prefill_window_pairs``: a query at
    position t needs ``min(t + 1, W)`` keys, summed over the window
    layers) and query head `_pair_flops`; and per cell of the band
    (``latent_cells_expanded`` less the full layers' tables) its
    expansion into 16 groups' keys and values (2 x 512 x 16 x 256),
    which runs under the same scope."""
    pairs = _per_program(engine, "prefill_window_pairs")
    groups = model["num_key_value_heads"]
    nope = model["head_dim"] - model["qk_rope_head_dim"]
    expand = 2.0 * model["kv_lora_rank"] * groups * (nope + model["v_head_dim"])
    return (pairs * model["num_attention_heads"] * _pair_flops(model)
            + _band_cells_per_program(model, engine) * expand)


def _band_cells_per_program(model: dict, engine: dict) -> float:
    """Cells a program's window layers expand: the ring's W and the
    chunk's own, a layer (the engine's chunk is the program's width)."""
    if not _per_program(engine, "window_tokens"):
        return 0.0
    chunk = model["engine"].get("prefill_chunk") or 0
    return window_layers(model) * (model["sliding_window"] + chunk)


def window_attn_bytes_per_program(model: dict, engine: dict) -> float:
    """Bytes the same have to move, all bf16: per live token and window
    layer ``q`` in (80 x 192), the result out (80 x 128) and the token's
    own cell (576), by the serving object's ``window_tokens``; per
    program and window layer the slot's ring read once and written once.
    The expanded keys and values and the scores need not leave the
    chip."""
    tokens = _per_program(engine, "window_tokens")
    if not tokens:
        return 0.0
    heads = model["num_attention_heads"]
    per_token = 2 * (heads * (model["head_dim"] + model["v_head_dim"])
                     + latent_dim(model))
    carried = 2 * window_layers(model) * window_bytes_per_slot(model)
    return tokens * per_token + carried


def _expert_matrix_bytes(model: dict) -> float:
    """One expert's three bf16 matrices: 31.5 MB at 4,096 x 1,280."""
    return 3.0 * model["hidden_size"] * model["moe_intermediate_size"] * 2


def expert_rows_bytes_per_step(model: dict, engine: dict) -> float:
    """Bytes the every-row expert kernel (``experts_on_rows`` at
    ``polynorm``, under ``moe:experts`` in the decode program) has to
    read in one decode step: the three matrices of every held expert
    that got a row (``experts_touched``, summed over the expert
    layers)."""
    return (_per_decode_step(engine, "experts_touched")
            * _expert_matrix_bytes(model))


def expert_rows_flops_per_step(model: dict, engine: dict) -> float:
    """Operations of the same as the kernel does them: every touched
    expert on every one of the step's ``max_batch`` rows, three products
    of 2 d f."""
    rows = model["engine"]["max_batch"]
    per = 3 * 2.0 * model["hidden_size"] * model["moe_intermediate_size"]
    return _per_decode_step(engine, "experts_touched") * rows * per


def grouped_rows_flops_per_program(model: dict, engine: dict) -> float:
    """Operations the grouped expert kernel's two calls a layer
    (``grouped_rows`` at ``polynorm`` and the down product, under
    ``moe:experts`` in the chunk programs) need in one program: per pair
    computed here (``moe_pairs_here`` over the prefill programs: the
    engine's counter holds the decode steps' pairs too, a few hundredths
    of it in this cell) three products of 2 d f."""
    per = 3 * 2.0 * model["hidden_size"] * model["moe_intermediate_size"]
    return _per_program(engine, "moe_pairs_here") * per


def grouped_rows_bytes_per_program(model: dict, engine: dict) -> float:
    """Bytes of the same at the least: every held expert's three
    matrices once a layer (a 2,048-token chunk gives each of 48 some 43
    rows: all are touched), and per pair its row in, its hidden row out
    and in and its result out (bf16)."""
    d, f = model["hidden_size"], model["moe_intermediate_size"]
    pairs = _per_program(engine, "moe_pairs_here")
    if not pairs:
        return 0.0
    return (held_expert_slots(model) * _expert_matrix_bytes(model)
            + pairs * 2.0 * (2 * d + 2 * f))


# ------------------------------------------------------ against the reference
def held_cells(cache, pages: list[int], slot: int, tokens: int, width: int):
    """A request's cells as the cache holds them after ``tokens``
    positions, float32: the full layers' at every position [Lf, tokens,
    width] from its pages, and the window layers' last W [Lw, W, width]
    from the slot's rings, oldest first (ring index ``r`` holds the
    position that is ``r mod W``)."""
    import numpy as np

    ids = np.asarray(pages, np.int32)
    full = np.asarray(cache["cells"][:, ids].astype("float32"))
    full = full.reshape(full.shape[0], -1, full.shape[-1])[:, :tokens, :width]
    rings = np.asarray(cache["win_cells"][:, slot].astype("float32"))
    w = rings.shape[1]
    order = (tokens - w + np.arange(w)) % w
    return full, rings[:, order, :width]


def _rel(got, want):
    """Largest over the leading axis of |got - want|_F / |want|_F."""
    import numpy as np

    flat = (len(want), -1)
    diff = np.linalg.norm((got - want).reshape(flat), axis=-1)
    return float((diff / np.linalg.norm(want.reshape(flat), axis=-1)).max())


# How the reference runs the long prompt so that it fits beside the
# engine (`reference_motif.forward_with_record`): 33,005 tokens' float32
# streams are 2.2 GB held as seven blocks of 4,715 tokens (every block
# one shape, so one compiled program a kind), a group's scores 5 heads x
# 1,024 queries x 33,005 keys (0.68 GB).
LONG_PASS = {"token_block": 4715, "query_block": 1024}


def check(server, seed: int, whole_prompt_len: int = 2000,
          chunked_prompt_len: int = 9000, long_prompt_len: int = 0,
          decode: int = 4, lower: str | None = None) -> dict:
    """``server_family.BenchFamilyServer.check`` for this family, inside
    the replica: a prompt the engine prefills whole (``whole_prompt_len``
    0: left out), one that goes in chunks (the rings carried from chunk
    to chunk, the full layer attending earlier chunks' cells at their
    true positions, a padded last chunk) and one past 32,768 tokens
    (``long_prompt_len`` 0: left out), whose chunks run the program of
    the widest table; then ``decode`` steps each through the pool and
    the rings in the absorbed form, against the float32 reference's one
    full pass over the same tokens (expanded, per-token keys and values,
    the window as a mask), run sublayer by sublayer so that it fits
    beside the engine: with the system's routes forced on the reference,
    the largest absolute logit difference at the last prompt position
    and at each decoded one; each token's routes against the reference's
    own cut; and the cells the cache holds (the full layer's at every
    position, each window layer's last W) against the reference's. Runs
    alone, before any request. ``lower`` computes the reference
    otherwise, for the reading a limit must fail."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    reference = importlib.import_module("benchmarks.reference_motif")
    eng = server.engine
    conf = server._conf
    sizes = reference.for_model(conf) | {"lower": lower}
    kinds = [full for full, _ in reference.layer_kinds(**sizes)]
    width, w = latent_dim(conf), conf["sliding_window"]
    rng = np.random.default_rng(seed + 11)
    out = {
        "logit_max_abs_err": [], "logit_scale": 0.0, "finite": True,
        "largest_slack": 0.0, "routes_beyond_epsilon": 0,
        "share_routed_otherwise": [], "cell_rel_err": 0.0,
        "ring_rel_err": 0.0, "tokens": 0, "prefill_calls": [],
        "margin_epsilon": MARGIN_EPSILON,
        "paged_attn_kernel": bool(eng.paged_attn_kernel),
    }
    jitted = {}

    def block_fn(kind, fn):
        # One compiled program per kind of sublayer and sequence length.
        return jitted.setdefault(kind, jax.jit(fn))

    for n in (whole_prompt_len, chunked_prompt_len, long_prompt_len):
        if not n:
            continue
        how = LONG_PASS if n == long_prompt_len else {}
        jitted.clear()
        got = server._run_tapped(
            rng.integers(1, eng.cfg.vocab_size, n).tolist(), decode
        )
        held = n + decode
        full, rings = held_cells(eng.cache, got["pages"], got["slot"], held,
                                 width)
        want, record = reference.forward_with_record(
            eng.params, jnp.asarray(got["tokens"], jnp.int32),
            routes=jnp.asarray(got["routes"]),
            rows=list(range(n - 1, held)), block_fn=block_fn, **sizes, **how,
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
        cells = np.asarray(record["cells"])  # [L, held, width]
        out["cell_rel_err"] = max(out["cell_rel_err"], _rel(
            full, cells[[i for i, f in enumerate(kinds) if f]]
        ))
        out["ring_rel_err"] = max(out["ring_rel_err"], _rel(
            rings, cells[[i for i, f in enumerate(kinds) if not f], held - w:]
        ))
        out["tokens"] += held
        out["prefill_calls"].append(got["prefill_calls"])
    return out


def check_problems(check: dict, logit_tolerance: float = LOGIT_TOLERANCE,
                   epsilon: float = MARGIN_EPSILON,
                   cell_tolerance: float = CELL_TOLERANCE) -> list[str]:
    """What of the server's ``check`` record makes a run not correct.

    Which departure of the reference (``reference_motif.py``, ``lower``)
    fails which limit, as read on the chip on the final tree's programs
    (my chip run, PR 65, seed 2147486911, the 9,000-token prompt; logits
    / furthest route slack / cells of the pages, of the rings; limits
    0.25 / 0.05 / 0.06; ``scripts/family_check_lowers.py --config
    motif3beta-serve1 --skip-whole --skip-long``):

        none (the check itself) 0.105 / 0.015 / 0.016, 0.022   passes
        weights_e4m3            1.555 / 0.321 / 0.241, 0.320   fails all
        no_noise                4.376 / 0.935 / 0.913, 1.032   fails all
        lambda_const            2.310 / 0.653 / 0.380, 0.496   fails all
        window_as_full          4.844 / 0.970 / 1.265, 1.338   fails all
        polynorm_as_silu        3.202 / 0.613 / 0.440, 0.581   fails all
        static_h                3.132 / 0.648 / 0.229, 0.343   fails all
        router_bf16             0.105 / 0.015 / 0.016, 0.022   PASSES

    A bfloat16 ROUTER passes as in every family (PRs 31, 51, 55, 59): the
    system's float32 router reads a bf16 stream whose rounding is as
    large (17.7% of pairs routed otherwise against 18.4%). What holds the
    router to float32 is `moe_ffn`'s own casts, pinned by
    tests/test_motif.py (`router_bf16` moves the routes there)."""
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
    worst_cell = max(check["cell_rel_err"], check["ring_rel_err"])
    if worst_cell > cell_tolerance:
        problems.append(
            f"the request's latent cells (pages {check['cell_rel_err']:.4f}, "
            f"rings {check['ring_rel_err']:.4f}) differ from the reference's "
            f"by {worst_cell:.4f} of their norm (tolerance {cell_tolerance})"
        )
    return problems
