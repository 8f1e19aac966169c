"""Laguna (``model_type: laguna``) for ``runners/serve_family.py``: the
program's config from the published keys, the serving programs lowered
at a configuration's sizes, the comparison with the plain reference and
its limits, and the bytes and operations that the per-layer metrics
divide by time. The names are the ones ``models/qwen3_next.py`` has for
its family."""

from __future__ import annotations

import importlib

# Largest |logit| difference between the timed programs' logits (bf16
# weights and activations at use; the band kernel at 72 heads and the
# prefill and paged attention kernels at 48, grouped or every-row expert
# matmuls; float32 router, norms and soft-max statistics) and the float32
# reference *on the same routes*, over the last prompt position and four
# decode steps of a 4,000-token prompt (two chunks) and a 9,000-token one
# (five). The logits read 4.0-4.1 at their largest; ten sublayers of bf16
# matmuls land at 0.054-0.076 over 9 runs on the chip, 9 seeds (PR 55).
# The reference with its weights rounded to e4m3 reads 1.25 against the
# system, and every part of the architecture dropped from the reference
# reads 0.90 and more (the table under `check_problems`). The limit is
# 3.3 times the one and 3.6 times under the least of the others.
LOGIT_TOLERANCE = 0.25
# Every route the system chose must lie within this of the reference's
# own cut: ``1 - p(lowest applied) / p(tenth chosen)`` of the router's
# probabilities, which is ``1 - exp(logit gap)``. The router runs in
# float32 on both sides, but its input is the residual stream, which the
# system carries in bf16: over the same runs the furthest swap lay
# 0.054-0.073 below the cut, 13.2-14.5% of (token, layer) pairs swapped.
# With e4m3 weights the reference's own routes lie 0.71 below; with a
# part dropped 0.51 and more.
MARGIN_EPSILON = 0.2
# Each window layer's carried keys and values, as the slot's ring holds
# them after the last decode step (512 positions a layer, put back in the
# order of their positions), against the reference's rotated keys and
# values at the last 512 positions of its one pass: the largest over the
# three layers and over keys and values of |A - A_ref|_F / |A_ref|_F.
# 0.01316-0.01322 over the same runs (bf16 cells of a bf16 stream: it
# hardly moves); with e4m3 weights 0.239; 0.127 and more with a part
# dropped (the first window layer's keys do not feel a dropped window,
# the second and third do). A ring that is written at the wrong index,
# not carried across chunks, written past the true length of a padded
# last chunk or rotated by the full layers' scheme is off by its whole
# norm. The limit is 3.8 times the one and 2.5 times under the least of
# the others.
WINDOW_TOLERANCE = 0.05

# What of the program this family needs beyond what every serving cell
# needs: the runner looks before it starts anything, so that a checkout
# that lacks them (this cell's parent commit) fails at once and not when
# a replica cannot be built.
PROGRAM_FILES = ("models/laguna.py", "ops/pallas/window_attention.py")

_KINDS = {"full_attention": "*", "sliding_attention": "W"}
_FFNS = {"dense": "D", "sparse": "E"}


def _layers(model: dict):
    """(layer_types, mlp_layer_types, heads a layer) of the layers the
    file runs: the first ``num_hidden_layers`` entries of the published
    lists, which a file carries whole."""
    n = model["num_hidden_layers"]
    return (model["layer_types"][:n], model["mlp_layer_types"][:n],
            model["num_attention_heads_per_layer"][:n])


def _window_heads(model: dict) -> int:
    kinds, _, heads = _layers(model)
    found = {h for kind, h in zip(kinds, heads) if kind == "sliding_attention"}
    if len(found) > 1:
        raise ValueError(f"window layers of {sorted(found)} heads")
    return found.pop() if found else model["num_attention_heads"]


def config(model: dict, **program):
    """``LagunaConfig`` for the published keys in ``model``; ``program``
    are fields of the program's own (``max_seq``, ``dtype``,
    ``dense_expert_rows``). A file that states a switch the program does
    not have is refused here, so that it cannot state a model the
    program does not run."""
    from ray_tpu.models.laguna import LagunaConfig

    if model["model_type"] != "laguna":
        raise ValueError(f"not a Laguna configuration: {model['model_type']}")
    for key in ("attention_bias", "tie_word_embeddings",
                "moe_apply_router_weight_on_input",
                "moe_router_logit_softcapping"):
        if model[key]:
            raise ValueError(f"models/laguna.py has no {key}")
    for key, want in (
        ("decoder_sparse_step", 1), ("norm_topk_prob", True),
        ("gating", "per-head"),
    ):
        if model[key] != want:
            raise ValueError(f"models/laguna.py runs {key} = {want!r}")
    kinds, ffns, heads = _layers(model)
    n = model["num_hidden_layers"]
    if set(model["gating_types"][:n]) != {"per_head"}:
        raise ValueError("models/laguna.py gates every layer a head")
    if [i for i, f in enumerate(ffns) if f == "dense"] != [
        i for i in model["mlp_only_layers"] if i < n
    ]:
        raise ValueError("mlp_layer_types and mlp_only_layers disagree")
    for kind, h in zip(kinds, heads):
        if kind == "full_attention" and h != model["num_attention_heads"]:
            raise ValueError(f"a full layer of {h} heads")
    ropes = model["rope_parameters"]
    full, window = ropes["full_attention"], ropes["sliding_attention"]
    if full["rope_type"] != "yarn" or window["rope_type"] != "default":
        raise ValueError(
            "models/laguna.py rotates full layers by yarn and window "
            "layers by default"
        )
    dh = model["head_dim"]
    published = model.get("published", {})
    program.setdefault("max_seq", model["max_position_embeddings"])
    if "dense_expert_rows" in model.get("program", {}):
        program.setdefault(
            "dense_expert_rows", model["program"]["dense_expert_rows"]
        )
    return LagunaConfig(
        vocab_size=model["vocab_size"],
        d_model=model["hidden_size"],
        pattern="".join(
            _KINDS[kind] + _FFNS[ffn] for kind, ffn in zip(kinds, ffns)
        ),
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        head_dim=dh,
        norm_eps=model["rms_norm_eps"],
        rotary_dim=int(dh * full["partial_rotary_factor"]),
        rope_theta=float(full["rope_theta"]),
        rope_yarn=(
            float(full["factor"]), full["original_max_position_embeddings"],
            float(full["beta_fast"]), float(full["beta_slow"]),
            full["attention_factor"],
        ),
        window_heads=_window_heads(model),
        sliding_window=model["sliding_window"],
        window_rotary_dim=int(dh * window["partial_rotary_factor"]),
        window_rope_theta=float(window["rope_theta"]),
        dense_d_ff=model["intermediate_size"],
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
        routed_scaling_factor=float(model["moe_routed_scaling_factor"]),
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
    from ray_tpu.models.laguna import init_params

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
def _count(model: dict, of: list, what: str) -> int:
    return sum(1 for entry in of[: model["num_hidden_layers"]] if entry == what)


def window_layers(model: dict) -> int:
    return _count(model, model["layer_types"], "sliding_attention")


def _sparse_layers(model: dict) -> int:
    return _count(model, model["mlp_layer_types"], "sparse")


def held_parameters(model: dict) -> int:
    """Parameters of the tree as the configuration holds it."""
    d, dh = model["hidden_size"], model["head_dim"]
    kv = 2 * d * model["num_key_value_heads"] * dh
    _, _, heads = _layers(model)
    # Norm, W_q and W_o, W_k and W_v, the gate's [d, H].
    attention = sum(d + 2 * d * h * dh + kv + d * h for h in heads)
    routed_all = model.get("published", {}).get(
        "num_experts", model["num_experts"]
    )
    sparse = (d + d * routed_all
              + model["num_experts"] * 3 * d * model["moe_intermediate_size"]
              + 3 * d * model["shared_expert_intermediate_size"])
    dense = d + 3 * d * model["intermediate_size"]
    n_sparse = _sparse_layers(model)
    return (attention + n_sparse * sparse
            + (model["num_hidden_layers"] - n_sparse) * dense
            + 2 * model["vocab_size"] * d + d)


def held_expert_slots(model: dict) -> int:
    """Held experts over all sparse layers: what a decode step could
    touch at most."""
    return model["num_experts"] * _sparse_layers(model)


def _traced(engine: dict) -> dict:
    """The engine's counters over the traced steps, where the server
    took them (``server_family``); else over the replica's life."""
    return engine.get("traced") or engine


def window_bytes_per_slot(model: dict) -> int:
    """Bytes one slot keeps in one window layer: the last
    ``sliding_window`` keys and values in bf16 (512 x 8 x 128 x 2 x 2 =
    2,097,152), whatever ``max_seq``."""
    return (model["sliding_window"] * model["num_key_value_heads"]
            * model["head_dim"] * 2 * 2)


def window_attn_flops_per_program(model: dict, engine: dict) -> float:
    """Operations the window layers' attention of one prefill program
    needs, a multiply-add as two: per (query, key) pair inside the band
    and query head ``q.k`` and ``p v`` (2 Dh each). The pairs are the
    serving object's own count over the programs it ran
    (``prefill_window_pairs``: a query at position t needs ``min(t + 1,
    W)`` keys, summed over the window layers). What the kernel computes
    outside the band inside its blocks is not counted. A program without
    the counter (the parent of the PR that brought it) gives 0 and the
    metric is left out."""
    engine = _traced(engine)
    if not engine.get("prefill_programs"):
        return 0.0
    pairs = engine.get("prefill_window_pairs", 0) / engine["prefill_programs"]
    return pairs * _window_heads(model) * model["head_dim"] * 4.0


def window_attn_bytes_per_program(model: dict, engine: dict) -> float:
    """Bytes the same have to move, all bf16: per live token and window
    layer ``q`` in and the result out (``H Dh`` each) and the token's own
    key and value (``Hkv Dh`` each), by the serving object's
    ``window_tokens``; per program and window layer the slot's carried
    window read once. The scores need not leave the chip."""
    engine = _traced(engine)
    if not engine.get("prefill_programs"):
        return 0.0
    tokens = engine.get("window_tokens", 0) / engine["prefill_programs"]
    dh = model["head_dim"]
    per_token = 2 * (2 * _window_heads(model) * dh
                     + 2 * model["num_key_value_heads"] * dh)
    carried = window_layers(model) * window_bytes_per_slot(model)
    return tokens * per_token + (carried if tokens else 0.0)


# ------------------------------------------------------ against the reference
def carried_windows(cache, slot: int, end: int):
    """A slot's rings ``win_k`` / ``win_v`` ``[L, B, W, Hkv, Dh]`` as the
    reference records a window: ``[L, 2 (k, v), W, Hkv, Dh]`` float32,
    the positions ``end - W .. end - 1`` oldest first (ring index ``r``
    holds the position that is ``r mod W``)."""
    import numpy as np

    rings = np.stack([
        np.asarray(cache[leaf][:, slot].astype("float32"))
        for leaf in ("win_k", "win_v")
    ], axis=1)  # [L, 2, W, Hkv, Dh]
    w = rings.shape[2]
    return rings[:, :, (end - w + np.arange(w)) % w]


def check(server, seed: int, whole_prompt_len: int = 4000,
          chunked_prompt_len: int = 9000, decode: int = 4,
          lower: str | None = None) -> dict:
    """``server_family.BenchFamilyServer.check`` for this family, inside
    the replica: two prompts through the engine's own programs as the
    configuration runs them (with its chunk of 2,048 a 4,000-token prompt
    is two chunks and a 9,000-token one five: the windows carried from
    chunk to chunk, the full layers attending earlier chunks' pages at
    their true positions, a padded last chunk), then ``decode`` steps
    each through the pages and the slot's windows, against the float32
    reference's one full pass over the same tokens, run sublayer by
    sublayer so that it fits beside the engine: with the system's routes
    forced on the reference, the largest absolute logit difference at
    the last prompt position and at each decoded one; each token's
    routes against the reference's own cut; and each window layer's
    carried keys and values as the slot holds them after the last step
    against the reference's at the last 512 positions. Runs alone,
    before any request. ``lower`` computes the reference otherwise, for
    the reading a limit must fail."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    reference = importlib.import_module("benchmarks.reference_laguna")
    eng = server.engine
    sizes = reference.for_model(server._conf) | {"lower": lower}
    rng = np.random.default_rng(seed + 11)
    out = {
        "logit_max_abs_err": [], "logit_scale": 0.0, "finite": True,
        "largest_slack": 0.0, "routes_beyond_epsilon": 0,
        "share_routed_otherwise": [], "window_rel_err": 0.0,
        "tokens": 0, "prefill_calls": [], "margin_epsilon": MARGIN_EPSILON,
        "paged_attn_kernel": bool(eng.paged_attn_kernel),
    }
    jitted = {}

    def block_fn(kind, fn):
        # One compiled program per kind of sublayer and sequence length.
        return jitted.setdefault(kind, jax.jit(fn))

    for n in (whole_prompt_len, chunked_prompt_len):
        jitted.clear()
        got = server._run_tapped(
            rng.integers(1, eng.cfg.vocab_size, n).tolist(), decode
        )
        held = carried_windows(eng.cache, got["slot"], n + decode)
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
        windows = np.asarray(record["windows"])  # [Lw, 2, W, Hkv, Dh]
        flat = (len(windows) * 2, -1)
        diff = np.linalg.norm((held - windows).reshape(flat), axis=-1)
        norm = np.linalg.norm(windows.reshape(flat), axis=-1)
        out["window_rel_err"] = max(out["window_rel_err"],
                                    float((diff / norm).max()))
        out["tokens"] += n + decode
        out["prefill_calls"].append(got["prefill_calls"])
    return out


def check_problems(check: dict, logit_tolerance: float = LOGIT_TOLERANCE,
                   epsilon: float = MARGIN_EPSILON,
                   window_tolerance: float = WINDOW_TOLERANCE) -> list[str]:
    """What of the server's ``check`` record makes a run not correct.

    Which departure of the reference (``reference_laguna.py``, ``lower``)
    fails which limit, as read on the chip (my chip run, PR 55, seed
    2147484001; logits / furthest slack / windows; limits 0.25 / 0.2 /
    0.05; ``scripts/family_check_lowers.py``):

        none (the check itself)   0.065 / 0.063 / 0.0132   passes
        weights_e4m3              1.25  / 0.71  / 0.239    fails all three
        no_window                 0.96  / 0.51  / 0.162    fails all three
        no_gate                   4.23  / 0.99  / 0.859    fails all three
        no_yarn_factor            3.98  / 0.98  / 0.818    fails all three
        no_partial_rotary         5.33  / 0.99  / 1.30     fails all three
        full_heads_in_window      0.90  / 0.75  / 0.127    fails all three
        no_routed_scaling         2.21  / 0.81  / 0.320    fails all three
        router_bf16               0.069 / 0.061 / 0.0133   PASSES

    A bfloat16 ROUTER in the reference passes every limit, as PRs 31 and
    51 found for their families: the system's float32 router reads a
    bf16 residual stream, whose rounding is as large as a bf16 router's
    own, so no limit on these measures can stand between. (The share of
    (token, layer) pairs routed otherwise moves 14.2 / 13.3% -> 15.3 /
    14.2% on that seed, but reads 13.2-13.6% on the longer prompt over
    nine seeds of the system alone: a limit there would have 4% of
    room.) What holds the router to float32 is `moe_ffn`'s own casts,
    pinned by tests/test_laguna.py and tests/test_pangu_ultra_moe.py."""
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
    if check["window_rel_err"] > window_tolerance:
        problems.append(
            f"a window layer's carried keys or values differ from the "
            f"reference's at the window's positions by "
            f"{check['window_rel_err']:.4f} of their norm "
            f"(tolerance {window_tolerance})"
        )
    return problems
