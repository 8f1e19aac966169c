"""Phi-4-mini-flash-reasoning (``model_type: phi4flash``) for
``runners/serve_family.py``: the program's config from the published
keys, the serving programs lowered at a configuration's sizes, the
comparison with the plain reference and its limits, and the bytes and
operations that per-layer metrics divide by time. The names are the ones
``benchmarks/models/laguna.py`` and ``granite_hybrid.py`` have for their
families, so that the entries that read those read this."""

from __future__ import annotations

import importlib

# The limits of the check, each between two readings with room on both
# sides (my chip runs, PR 68: the system's range over twelve runs of
# `phi4flash-reasonctx-32` on SEEDS_READ, three tapped prompts each; the
# departures of the reference on seed 7, the 9,000-token prompt, the
# table under `check_problems`; `weights_e4m3` is the nearest precision
# below the configuration's bfloat16).
#
# Largest |logit| difference between the timed programs' logits (bf16
# weights and activations at use, one bf16 residual stream through 64
# sublayers; Mamba's state float32, its x, dt and y bf16 at the scan
# kernel's edge; the three attention kernels over bf16 pairs; the
# subtraction and its norm float32) and the float32 reference, over the
# last prompt position and four decode steps of a 2,000-token prompt
# prefilled whole, a 9,000-token one in five chunks and a 24,001-token
# one in twelve. The logits read 5.1-5.7 at their largest; the system
# reads 0.125-0.185 (the other families 0.10-0.15 behind ten sublayers:
# the stream's bf16 rounding, 64 times). The least departure is a window
# of 511 keys, 0.42 (one key of 512 in eight layers); e4m3 weights 2.09.
# The limit is 1.6 times the one and 1.4 under the other.
LOGIT_TOLERANCE = 0.3
# The keys and values the cache holds of the tapped request against the
# reference's, the larger of |A - A_ref|_F / |A_ref|_F: the pool layer's
# at every position, from the request's pages (0.023-0.027: layer 17's,
# behind 34 sublayers of a bf16 stream); each window layer's last 512,
# from the slot's ring pages (0.021-0.024). e4m3 weights 0.35 and 0.33,
# `lam` held constant 0.22 and 0.21, a cross layer given its own keys
# 0.68 of the pool. The limit is 2.3 times the one and 3.5 under the
# least of those; a window of 511 reads 0.056 and is the logits' to
# catch. A cell at the wrong page, position or ring index, or a ring
# carried wrongly across chunks, is off by its norm.
CELL_TOLERANCE = 0.06
# Each Mamba layer's state as the slot holds it after the last step
# against the scan's, the same measure: 0.014-0.021 (the ninth layer's:
# the state is float32 on both sides; what differs is its bf16 inputs).
# A bfloat16 STATE in the reference reads 0.29 and moves nothing else
# past its limit (logits 0.19): this limit is what holds the state to
# float32. e4m3 weights 0.36. The limit is 2.4 times the one and 5.9
# under the other.
STATE_TOLERANCE = 0.05
SEEDS_READ = (2147486911, 7, 2147488001, 31, 1234567, 2147483777,
              2147488002, 2147488003, 3000680021)

# What of the program this family needs beyond what every serving cell
# needs: the runner looks before it starts anything, so that a checkout
# that lacks them (this cell's parent commit) fails at once and not when
# a replica cannot be built.
PROGRAM_FILES = ("models/phi4_flash.py", "ops/pallas/selective_scan.py",
                 "llm/hybrid_kv.py")


def config(model: dict, **program):
    """``Phi4FlashConfig`` for the published keys in ``model``;
    ``program`` are fields of the program's own (``max_seq``, ``dtype``).
    A file that states a switch the program does not have is refused
    here, so that it cannot state a model the program does not run."""
    from ray_tpu.models.phi4_flash import Phi4FlashConfig, sublayers

    if model["model_type"] != "phi4flash":
        raise ValueError(f"not a phi4flash configuration: {model['model_type']}")
    for key in ("mlp_bias", "lm_head_bias", "embd_pdrop", "resid_pdrop"):
        if model[key]:
            raise ValueError(f"models/phi4_flash.py has no {key}")
    for key, want in (("hidden_act", "silu"), ("tie_word_embeddings", True)):
        if model[key] != want:
            raise ValueError(f"models/phi4_flash.py runs {key} = {want!r}")
    assumed = model["assumed_values"]
    heads, kv_heads = model["num_attention_heads"], model["num_key_value_heads"]
    width = model["hidden_size"] // heads
    if heads != 2 * kv_heads or kv_heads % 2:
        raise ValueError("heads pair up: two queries a key/value head, two "
                         "key/value heads a pair")
    if assumed["expand"] * model["hidden_size"] % 128:
        raise ValueError("d_inner is held in tiles of 128 channels")
    program.setdefault("max_seq", model["max_position_embeddings"])
    layers = model["num_hidden_layers"]
    return Phi4FlashConfig(
        vocab_size=model["vocab_size"],
        d_model=model["hidden_size"],
        pattern=sublayers(layers, layers // 2 + model["mb_per_layer"]),
        norm_eps=model["layer_norm_eps"],
        n_heads=heads,
        n_kv_heads=kv_heads // 2,  # pairs
        head_dim=2 * width,
        attention_scale=width**-0.5,
        sliding_window=model["sliding_window"],
        ssm_state=assumed["d_state"],
        conv_kernel=assumed["d_conv"],
        ssm_expand=assumed["expand"],
        dt_rank=assumed["dt_rank"],
        dense_d_ff=model["intermediate_size"],
        **program,
    )


def lowered_programs(conf: dict, traffic: dict, device, use_kernel=True):
    """name -> the lowered program, as `LLMEngine` would call it for this
    configuration and mix: BOTH chunk programs of every bucket (the one
    that stops before the cross-decoder, ``.._self``, and the one that
    runs it on the last row) and the decode program."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.llm import hybrid_kv
    from ray_tpu.models.phi4_flash import init_params

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
        for self_only in ((False,) if whole else (True, False)):
            out[name + ("_self" if self_only else "")] = hybrid_kv.prefill_program(
                cfg, n_pages, size // page, use_kernel, self_only
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
def _self_layers(model: dict) -> int:
    return model["num_hidden_layers"] // 2 + model["mb_per_layer"]


def mamba_layers(model: dict) -> int:
    return _self_layers(model) // 2


def window_layers(model: dict) -> int:
    return _self_layers(model) // 2 - 1


def cross_layers(model: dict) -> int:
    return (model["num_hidden_layers"] - _self_layers(model)) // 2


def d_inner(model: dict) -> int:
    return model["assumed_values"]["expand"] * model["hidden_size"]


def kv_token_bytes(model: dict) -> int:
    """One token's keys and values in one attention layer, bf16: 2 x 20
    heads x 64 x 2 B = 5,120 B."""
    width = model["hidden_size"] // model["num_attention_heads"]
    return 2 * model["num_key_value_heads"] * width * 2


def held_parameters(model: dict) -> int:
    """Parameters of the tree as the configuration holds it: the whole
    model, the tied embedding once."""
    return config(model).num_params()


def held_expert_slots(model: dict) -> int:
    return 0  # no experts


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


def scan_bytes_per_program(model: dict, engine: dict) -> float:
    """Bytes Mamba-1's chunked scan (``selective_scan_chunk``, under
    ``ssm:scan`` in both prefill programs) has to move in one program:
    per live token and Mamba layer x and dt in and y out (bf16, d_inner
    each) and B and C (float32, N each), tokens by the serving object's
    ``ssm_scan_tokens``; per layer the carried float32 state once each
    way and the convolution tail written. The gate by z is outside the
    scope."""
    tokens = _per_program(engine, "ssm_scan_tokens")
    if not tokens:
        return 0.0
    n, wide = model["assumed_values"]["d_state"], d_inner(model)
    per_token = 3 * 2 * wide + 2 * 4 * n
    state = 2 * 4 * n * wide + 2 * (model["assumed_values"]["d_conv"] - 1) * wide
    return tokens * per_token + mamba_layers(model) * state


def scan_flops_per_program(model: dict, engine: dict) -> float:
    """The scan's element operations in one program: per live token,
    channel and state index the decay's product and exponential, the
    state's multiply-add, the input's product and the read-out's
    multiply-add (7), and per token and channel softplus, ``dt x`` and
    the skip (8). They run on the vector units, which no peak of
    ``peaks.json`` describes: against the bf16 matmul peak this stays
    under the bytes' bound, so the share is read against HBM and says how
    far the vector units hold the kernel from it."""
    tokens = _per_program(engine, "ssm_scan_tokens")
    n, wide = model["assumed_values"]["d_state"], d_inner(model)
    return tokens * wide * (7.0 * n + 8.0)


def ssm_state_bytes_per_decode_step(model: dict, engine: dict) -> float:
    """Bytes of per-slot recurrent state one decode step has to move:
    each DECODING slot's float32 state (d_inner x N) and bf16 convolution
    tail read once and written once a Mamba layer; slots by the engine's
    ``slot_steps``. The program computes all ``max_batch`` slots; the
    slots that were not decoding are not counted."""
    n = model["assumed_values"]["d_state"]
    tail = model["assumed_values"]["d_conv"] - 1
    slot = 4 * n * d_inner(model) + 2 * tail * d_inner(model)
    return (_per_decode_step(engine, "slot_steps")
            * mamba_layers(model) * 2.0 * slot)


def _pair_flops(model: dict) -> float:
    """Per (query, key) pair and query head as the arithmetic needs it:
    a score 64 wide and a weighted value 128 wide, a multiply-add as
    two. (The kernels contract the score 128 deep, zeros in one half: on
    a matrix unit 128 deep the same passes.)"""
    width = model["hidden_size"] // model["num_attention_heads"]
    return 2.0 * (width + 2 * width)


def window_attn_flops_per_program(model: dict, engine: dict) -> float:
    """Operations the window layers' attention of one prefill program
    needs (the band kernel at 40 query heads over 10 pairs, under
    ``attn:window/``): per (query, key) pair inside the band
    (``prefill_window_pairs``: a query at position t needs ``min(t + 1,
    W)`` keys, summed over the window layers) and query head
    `_pair_flops`."""
    pairs = _per_program(engine, "prefill_window_pairs")
    return pairs * model["num_attention_heads"] * _pair_flops(model)


def window_bytes_per_slot(model: dict) -> int:
    """Bytes of one slot's ring in one window layer: W tokens' keys and
    values."""
    return model["sliding_window"] * kv_token_bytes(model)


def window_attn_bytes_per_program(model: dict, engine: dict) -> float:
    """Bytes the same have to move, all bf16: per live token and window
    layer ``q`` in (40 x 64), the result out (40 x 128: a head's weighted
    pair) and the token's own keys and values (2 x 20 x 64), by the
    serving object's ``window_tokens``; per program and window layer the
    slot's ring read once and written once."""
    tokens = _per_program(engine, "window_tokens")
    if not tokens:
        return 0.0
    width = model["hidden_size"] // model["num_attention_heads"]
    heads = model["num_attention_heads"]
    per_token = 2 * heads * (width + 2 * width) + kv_token_bytes(model)
    carried = 2 * window_layers(model) * window_bytes_per_slot(model)
    return tokens * per_token + carried


def shared_kv_bytes_per_decode_step(model: dict, engine: dict) -> float:
    """Bytes of the ONE pool layer a decode step has to read: every
    decoding slot's context once an attending block (the block that
    writes it and the seven cross blocks: the serving object's
    ``shared_kv_bytes``, which counts 8 x 5,120 B a token of context).
    For ``shared_kv_hbm_pct`` (``hbm_share`` over ``attn:full`` in
    ``hybrid_decode``), which waits for an entry (ROADMAP W13)."""
    return _per_decode_step(engine, "shared_kv_bytes")


def shared_kv_flops_per_decode_step(model: dict, engine: dict) -> float:
    """The paged differential attend's operations in one decode step: per
    token of context, attending block and query head `_pair_flops`."""
    tokens = shared_kv_bytes_per_decode_step(model, engine) / kv_token_bytes(model)
    return tokens * model["num_attention_heads"] * _pair_flops(model)


# ------------------------------------------------------ against the reference
def held_cells(cache, pages: list[int], slot: int, tokens: int):
    """A request's keys and values as the cache holds them after
    ``tokens`` positions, float32: the pool layer's at every position,
    (k, v) each [tokens, pairs, 128], from its pages; the window layers'
    last W, (k, v) each [Lw, W, pairs, 128], from the slot's rings,
    oldest first (ring index ``r`` holds the position that is ``r mod
    W``)."""
    import numpy as np

    ids = np.asarray(pages, np.int32)

    def paged(leaf):
        cells = np.asarray(leaf[0, ids].astype("float32"))  # [n, Hkv, P, Dh]
        cells = cells.transpose(0, 2, 1, 3)
        return cells.reshape(-1, *cells.shape[2:])[:tokens]

    # A slot's ring is W / P pages of a layer's (`llm/hybrid_kv.py`).
    page = cache["win_k"].shape[3]
    per = (cache["win_k"].shape[1] - 1) // (
        cache["ssm1"].shape[1]
    )
    w = per * page
    order = (tokens - w + np.arange(w)) % w

    def ring(leaf):
        cells = np.asarray(
            leaf[:, slot * per: (slot + 1) * per].astype("float32")
        )  # [Lw, per, Hkv, P, Dh]
        cells = cells.transpose(0, 1, 3, 2, 4)
        return cells.reshape(len(cells), w, *cells.shape[3:])[:, order]

    return ((paged(cache["k"]), paged(cache["v"])),
            (ring(cache["win_k"]), ring(cache["win_v"])))


def _rel(got, want):
    """|got - want|_F / |want|_F."""
    import numpy as np

    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def run_tapped(server, prompt: list[int], decode: int) -> dict:
    """One request alone through the engine's own programs: the last
    prompt position's logits, each decode step's for the request's slot,
    the tokens it generated, its slot and the pages it held (their
    contents outlive the request: a page is not cleared when it is
    freed). ``server_family``'s own reads a record of routes, which a
    model without experts does not leave."""
    import numpy as np

    from ray_tpu.llm.engine import SamplingParams

    eng = server.engine
    seen = []
    eng.on_logits = lambda phase, logits, record: seen.append((phase, logits))
    try:
        rid = eng.add_request(prompt, SamplingParams(max_tokens=decode + 1))
        req = eng._queue[-1]
        slot, generated, pages = None, None, []
        while generated is None:
            for fin in eng.step():
                if fin["request_id"] == rid:
                    generated = fin["tokens"]
            if slot is None:
                slot = eng.slot_of(rid)
            pages = req.pages or pages
    finally:
        eng.on_logits = None
    prefills = [s for s in seen if s[0].startswith("prefill")]
    decodes = [s for s in seen if s[0] == "decode"]
    if len(decodes) != decode or slot is None:
        raise RuntimeError(
            f"engine made {len(prefills)} prefill and {len(decodes)} "
            f"decode calls for {decode + 1} tokens (slot {slot})"
        )
    # A chunk that stopped before the cross-decoder returned no logits.
    if any(s[1] is not None for s in prefills[:-1]):
        raise RuntimeError("a chunk before a prompt's last returned logits")
    logits = [np.asarray(prefills[-1][1])[0, 0]] + [
        np.asarray(s[1])[slot] for s in decodes
    ]
    return {
        "tokens": prompt + generated[:-1], "logits": np.stack(logits),
        "slot": slot, "pages": list(pages), "prefill_calls": len(prefills),
    }


def check(server, seed: int, whole_prompt_len: int = 2000,
          chunked_prompt_len: int = 9000, long_prompt_len: int = 0,
          decode: int = 4, lower: str | None = None) -> dict:
    """``server_family.BenchFamilyServer.check`` for this family, inside
    the replica: a prompt the engine prefills whole (``whole_prompt_len``
    0: left out; one program, both halves), one that goes in chunks (the
    rings and Mamba's state carried from chunk to chunk, every chunk but
    the last stopping before the cross-decoder, the last a padded one
    whose cross blocks read earlier chunks' pages) and one at the widest
    table (``long_prompt_len`` 0: left out); then ``decode`` steps each
    through the pool, the rings and the state, against the float32
    reference's one full pass over the same tokens, which runs ALL layers
    at EVERY position: the largest absolute logit difference at the last
    prompt position and at each decoded one; the keys and values the
    cache holds (the pool layer's at every position, each ring's last W)
    and each Mamba layer's state after the last step against the
    reference's. Runs alone, before any request. ``lower`` computes the
    reference otherwise, for the reading a limit must fail."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    reference = importlib.import_module("benchmarks.reference_phi4flash")
    eng = server.engine
    conf = server._conf
    sizes = reference.for_model(conf) | {"lower": lower}
    w = conf["sliding_window"]
    rng = np.random.default_rng(seed + 11)
    out = {
        "logit_max_abs_err": [], "logit_scale": 0.0, "finite": True,
        "cell_rel_err": 0.0, "ring_rel_err": 0.0, "state_rel_err": 0.0,
        "tokens": 0, "prefill_calls": [],
        "paged_attn_kernel": bool(eng.paged_attn_kernel),
    }
    jitted = {}

    def block_fn(kind, fn):
        # One compiled program per kind of sublayer and sequence length.
        return jitted.setdefault(kind, jax.jit(fn))

    for n in (whole_prompt_len, chunked_prompt_len, long_prompt_len):
        if not n:
            continue
        jitted.clear()
        got = run_tapped(
            server, rng.integers(1, eng.cfg.vocab_size, n).tolist(), decode
        )
        held = n + decode
        (k, v), (win_k, win_v) = held_cells(
            eng.cache, got["pages"], got["slot"], held
        )
        states = np.asarray(eng.cache["ssm1"][:, got["slot"]])
        want, record = reference.forward_with_record(
            eng.params, jnp.asarray(got["tokens"], jnp.int32),
            rows=list(range(n - 1, held)), block_fn=block_fn, **sizes,
        )
        want = np.asarray(want)
        out["logit_max_abs_err"] += [
            float(e) for e in np.abs(got["logits"] - want).max(-1)
        ]
        out["logit_scale"] = max(out["logit_scale"], float(np.abs(want).max()))
        out["finite"] &= bool(np.isfinite(got["logits"]).all())

        def pairs(a):  # [.., H / 2, w] -> [.., H / 4, 2 w]
            a = np.asarray(a)
            return a.reshape(*a.shape[:-2], a.shape[-2] // 2, -1)

        out["cell_rel_err"] = max(
            out["cell_rel_err"], _rel(k, pairs(record["k"])),
            _rel(v, pairs(record["v"])),
        )
        for got_ring, name in ((win_k, "win_k"), (win_v, "win_v")):
            ref_ring = pairs(record[name])  # the last W positions
            out["ring_rel_err"] = max(out["ring_rel_err"], max(
                _rel(g[-len(r):], r) for g, r in zip(got_ring, ref_ring)
            ))
        # The slot's [N, R, 128] against the reference's [d_inner, N].
        ref_states = np.asarray(record["states"]).transpose(0, 2, 1)
        out["state_rel_err"] = max(out["state_rel_err"], max(
            _rel(g.reshape(r.shape), r) for g, r in zip(states, ref_states)
        ))
        out["tokens"] += held
        out["prefill_calls"].append(got["prefill_calls"])
    return out


def check_problems(check: dict, logit_tolerance: float = LOGIT_TOLERANCE,
                   cell_tolerance: float = CELL_TOLERANCE,
                   state_tolerance: float = STATE_TOLERANCE) -> list[str]:
    """What of the server's ``check`` record makes a run not correct.

    Which departure of the reference (``reference_phi4flash.py``,
    ``lower``) fails which limit, as read on the chip (my chip run, PR
    68, seed 7, the 9,000-token prompt; logits / keys and values of the
    pages, of the rings / states; limits 0.3 / 0.06 / 0.05;
    ``scripts/family_check_lowers.py --config phi4miniflash-serve1
    --skip-whole --skip-long``):

        none (the check itself) 0.160 / 0.026, 0.024 / 0.014   passes
        weights_e4m3            2.090 / 0.353, 0.334 / 0.361   fails all
        state_bf16              0.191 / 0.026, 0.026 / 0.293   fails: states
        lam_const               1.408 / 0.222, 0.208 / 0.189   fails all
        window_511              0.420 / 0.056, 0.054 / 0.034   fails: logits
        memory_after_gate       0.961 / 0.026, 0.024 / 0.014   fails: logits
        cross_own_keys          0.508 / 0.685, 0.024 / 0.014   fails: logits, pages

    (A cross layer's own keys are compared against the ONE pool layer
    the system holds, which is why the pages read it; the memory is no
    leaf of the cache, so only the logits read where it was taken.)"""
    problems = []
    worst = max(check["logit_max_abs_err"])
    if not check["finite"] or worst > logit_tolerance:
        problems.append(
            f"logits differ from the reference by {worst:.4f} "
            f"(tolerance {logit_tolerance})"
        )
    worst_cell = max(check["cell_rel_err"], check["ring_rel_err"])
    if worst_cell > cell_tolerance:
        problems.append(
            f"the request's keys and values (pages {check['cell_rel_err']:.4f}"
            f", rings {check['ring_rel_err']:.4f}) differ from the "
            f"reference's by {worst_cell:.4f} of their norm (tolerance "
            f"{cell_tolerance})"
        )
    if check["state_rel_err"] > state_tolerance:
        problems.append(
            f"a Mamba layer's state differs from the reference's by "
            f"{check['state_rel_err']:.4f} of its norm (tolerance "
            f"{state_tolerance})"
        )
    return problems
