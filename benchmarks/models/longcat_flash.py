"""LongCat-Flash's language model (``config.json`` of LongCat-Flash-Omni)
for ``runners/serve_family.py``: the program's config from the published
keys, the serving programs lowered at a configuration's sizes, the
comparison with the plain reference and its limits, and the bytes and
operations that the per-layer metrics divide by time, under the names
the ``.longdoc`` readers call (``benchmarks/models/pangu_ultra_moe.py``
has the same names for its own model). A layer here has TWO attention
sublayers, each with cells of its own: everything that counts cached
tokens or attended pairs counts ``2 x num_layers`` sublayers."""

from __future__ import annotations

import importlib

# The latent path is one path: what counts a prefill program's attended
# pairs, a decode step's live cells and a check's problems is Pangu's
# own, read from this configuration's keys.
from benchmarks.models.pangu_ultra_moe import (  # noqa: F401
    _live_tokens_per_decode_step,
    latent_dim,
    prefill_attn_bytes_per_program,
    prefill_attn_flops_per_program,
)
from benchmarks.models.pangu_ultra_moe import check_problems as _problems

# Largest |logit| difference between the timed programs' logits (bf16
# weights and activations at use; expanded prefill through the prefill
# kernel, absorbed decode through the latent pages, batched or grouped
# expert matmuls, the identity outputs' sum in float32; float32 router
# and norms) and the float32 reference *on the same routes*, over the
# last prompt position and four decode steps of a 2,000-token prompt
# prefilled whole and a 7,000-token one prefilled in four chunks, on
# logits of magnitude 3.7-4.9. Four double layers (eight attention
# sublayers, eight dense FFNs, four expert layers) of bf16 matmuls, no
# norm on a sublayer's output: 0.0430-0.0518 over 10 seeds on the chip
# (PR 61; Pangu's five layers with output norms read 0.036-0.040). What
# it must fail, same chip, seed 7 (PERF.md section 6): the reference
# with its weights rounded to e4m3 reads 1.07, with the identity
# outputs' sum dropped 2.40, with the shortcut added one sublayer early
# 2.57, with the latent unscaled 5.40. (With the matrices behind the
# scaled latents drawn at rank^-0.5 the SAME programs read 1.6-2.0: the
# scores' deviation is then 6.9 times a trained model's and bf16's
# half-percent decides which key a query attends; `init_mla`.)
LOGIT_TOLERANCE = 0.15
# Every route the system chose must lie within this of the reference's
# own cut, as a share of the reference's 12th selection score (identity
# outputs are routes like any other): the router runs in float32 on
# both sides, but its input is the residual stream, which the system
# carries in bf16, and a softmax over 768 outputs packs its 12th and
# 13th probabilities closer than a sigmoid's scores: over the 10 seeds
# the furthest swap lay 0.0350-0.0443 below the cut, 12.8-14.6% of
# (token, layer) pairs with a route swapped (Pangu: 0.005-0.009, 8%).
# With e4m3 weights the reference's own routes lie 0.62 below, with the
# identity sum dropped 0.89, with the shortcut early 0.92, with the
# latent unscaled 0.99. (A router computed in bfloat16 reads 0.0407
# where the same seed reads 0.0404 without: no limit on routes can tell
# it; tests/test_longcat_flash.py pins the dtypes.)
MARGIN_EPSILON = 0.12
# The slot's latent pages after the last decode step against the
# reference's ``[akv Nkv(c); rope(kpe)]``: largest over the 8 attention
# sublayers of |C - C_ref|_F / |C_ref|_F over every cached position
# (2,004 and 7,004 cells a sublayer). bf16 roundings of projections of
# a bf16 residual stream: 0.0108-0.0114 over the 10 seeds (one layer
# alone: 0.0032 the first sublayer's, 0.0088 the second's); with e4m3
# weights 0.27, with the identity sum dropped 0.47, with the shortcut
# early 0.56, and a latent without its factor (3.46) is off by 3.27. A
# cell at the wrong page, offset or sublayer row, a missing Nkv, a rope
# at the wrong position or a stale page is off by its whole norm.
LATENT_TOLERANCE = 0.03

# What of the program this family needs beyond what every serving cell
# needs: the runner looks before it starts anything, so that a checkout
# that lacks them (this cell's parent commit) fails at once and not when
# a replica cannot be built.
PROGRAM_FILES = (
    "models/longcat_flash.py", "llm/latent_kv.py",
    "ops/pallas/latent_attention.py",
)


def _latent_scale(model: dict, flag: str, rank: str) -> float:
    return (model["hidden_size"] / model[rank]) ** 0.5 if model[flag] else 1.0


def config(model: dict, **program):
    """``LongcatFlashConfig`` for the published keys in ``model``;
    ``program`` are fields of the program's own (``max_seq``, ``dtype``,
    ``dense_expert_rows``). A file that states a switch the program does
    not have is refused here, so that it cannot state a model the
    program does not run."""
    from ray_tpu.models.longcat_flash import LongcatFlashConfig

    if model["attention_method"] != "MLA":
        raise ValueError(f"not latent attention: {model['attention_method']}")
    if model["zero_expert_type"] != "identity":
        raise ValueError("models/moe.py's zero-compute experts are identities")
    if model["attention_bias"]:
        raise ValueError("models/longcat_flash.py has no attention_bias")
    if model["rms_norm_eps"] != 1e-5:
        raise ValueError("ops/norms.py fixes rms_norm eps at 1e-5")
    published = model.get("published", {})
    program.setdefault("max_seq", model["max_position_embeddings"])
    for key in ("dense_expert_rows", "prefill_key_block"):
        if key in model.get("program", {}):
            program.setdefault(key, model["program"][key])
    return LongcatFlashConfig(
        vocab_size=model["vocab_size"],
        d_model=model["hidden_size"],
        n_layers=model["num_layers"],
        n_heads=model["num_attention_heads"],
        q_lora_rank=model["q_lora_rank"],
        kv_lora_rank=model["kv_lora_rank"],
        qk_nope_head_dim=model["qk_nope_head_dim"],
        qk_rope_head_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"],
        rope_theta=float(model["rope_theta"]),
        q_latent_scale=_latent_scale(model, "mla_scale_q_lora", "q_lora_rank"),
        kv_latent_scale=_latent_scale(model, "mla_scale_kv_lora", "kv_lora_rank"),
        dense_d_ff=model["ffn_hidden_size"],
        # The router is as wide as the model's experts and identity
        # outputs; the file's own count is how many experts are held.
        num_experts=published.get("n_routed_experts", model["n_routed_experts"]),
        zero_experts=model["zero_expert_num"],
        experts_held=(
            (model.get("first_expert_held", 0), model["n_routed_experts"])
            if "n_routed_experts" in published else None
        ),
        top_k=model["moe_topk"],
        d_ff=model["expert_ffn_hidden_size"],
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
    from ray_tpu.models.longcat_flash import init_params

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
def attention_sublayers(model: dict) -> int:
    """Two a layer: each writes and reads cells of its own."""
    return 2 * model["num_layers"]


def held_parameters(model: dict) -> int:
    """Parameters of the tree as the configuration holds it."""
    d, h = model["hidden_size"], model["num_attention_heads"]
    rq, rkv = model["q_lora_rank"], model["kv_lora_rank"]
    qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    mla = (d * rq + rq * h * qk + d * latent_dim(model)
           + h * rkv * (model["qk_nope_head_dim"] + model["v_head_dim"])
           + h * model["v_head_dim"] * d + d + rq + rkv)
    dense = 3 * d * model["ffn_hidden_size"] + d
    outputs = model.get("published", {}).get(
        "n_routed_experts", model["n_routed_experts"]
    ) + model["zero_expert_num"]
    experts = (d * outputs + outputs
               + model["n_routed_experts"] * 3 * d * model["expert_ffn_hidden_size"])
    return (model["num_layers"] * (2 * mla + 2 * dense + experts)
            + 2 * model["vocab_size"] * d + d)


def held_expert_slots(model: dict) -> int:
    """Held experts over all expert layers: what a decode step could
    touch at most."""
    return model["n_routed_experts"] * model["num_layers"]


def latent_attn_bytes_per_decode_step(model: dict, engine: dict) -> float:
    """Bytes the latent decode kernel has to read in one decode step:
    every live latent page once, in every attention sublayer (bf16 cells
    of ``kv_lora_rank + qk_rope_head_dim`` numbers: 1,152 B a token and
    sublayer, 2,304 B a token and layer). The queries and the outputs
    (64 rows a slot) are not counted."""
    return (_live_tokens_per_decode_step(model, engine)
            * attention_sublayers(model) * latent_dim(model) * 2)


def latent_attn_flops_per_decode_step(model: dict, engine: dict) -> float:
    """Operations of the same: per live token and attention sublayer
    every head's score (W wide) and its weighted sum (``kv_lora_rank``
    wide), a multiply-add as two: 2 x 64 x (576 + 512). At 64 heads that
    is 121 operations a byte, under a v5e's ridge of 240: the bytes bound
    this kernel here, where Pangu's 128 heads sit at the ridge."""
    per_token = 2.0 * model["num_attention_heads"] * (
        latent_dim(model) + model["kv_lora_rank"]
    )
    return (_live_tokens_per_decode_step(model, engine)
            * attention_sublayers(model) * per_token)


# ------------------------------------------------------ against the reference
def check(server, seed: int, whole_prompt_len: int = 2000,
          chunked_prompt_len: int = 7000, decode: int = 4,
          lower: str | None = None) -> dict:
    """``server_family.BenchFamilyServer.check`` for this family, inside
    the replica: a prompt that is prefilled whole and one that goes in
    chunks (later chunks attend earlier chunks' latent pages, of both
    attention sublayers of every layer), then ``decode`` steps each in
    the absorbed form, against the float32 reference's one full pass
    over the same tokens in the NON-absorbed form, run sublayer by
    sublayer so that it fits beside the engine: with the system's routes
    forced on the reference, the largest absolute logit difference at
    the last prompt position and at each decoded one; each token's
    routes against the reference's own cut, identity outputs included;
    and the slot's latent pages of all ``2 x num_layers`` sublayers
    after the last step against the reference's ``[c; rope(kpe)]`` of
    every position. Runs alone, before any request. ``lower`` computes
    the reference otherwise (``reference_longcat_flash``), for the
    reading a limit must fail."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    reference = importlib.import_module("benchmarks.reference_longcat_flash")
    eng = server.engine
    cfg = eng.cfg
    sizes = reference.for_model(server._conf) | {"lower": lower}
    rng = np.random.default_rng(seed + 11)
    out = {
        "logit_max_abs_err": [], "logit_scale": 0.0, "finite": True,
        "largest_slack": 0.0, "routes_beyond_epsilon": 0,
        "share_routed_otherwise": [], "identity_route_share": [],
        "latent_rel_err": 0.0, "tokens": 0, "prefill_calls": [],
        "margin_epsilon": MARGIN_EPSILON,
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
                rng.integers(1, cfg.vocab_size, n).tolist(), decode
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
        out["identity_route_share"].append(
            float((got["routes"] >= cfg.num_experts).mean())
        )
        # The cache itself: the slot's pages in every attention
        # sublayer's row of the pool, every cached position.
        held = n + decode
        pages = jnp.asarray(got["pages"], jnp.int32)
        cells = np.asarray(
            eng.cache["latent"][:, pages].astype(jnp.float32)
        ).reshape(cfg.attn_sublayers, -1, cfg.cell_width)[
            :, :held, : cfg.latent_dim
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
    """What of the server's ``check`` record makes a run not correct:
    Pangu's three comparisons at this family's limits."""
    return _problems(check, logit_tolerance, epsilon, latent_tolerance)
