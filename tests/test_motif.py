"""Motif-3-Beta's language model through `LLMEngine` on the CPU at a tiny
size (`motif_tiny`'s shape: the dense layer and one whole period, so
every letter, at a window of 8 positions) against the plain reference
(benchmarks/reference_motif.py) on seeded float32 weights: a prompt
prefilled whole, one in chunks across the window's edge and chunk
boundaries, decode through the pool and the rings past the window; each
switch of the reference moves what the comparison reads; the eight
shares' routed parts and the shared expert once add up to the uncut
layer; the PolyNorm expert kernels and the grouped prefill kernel and
the band kernel at two widths, interpreted, against their off-TPU forms.
"""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_motif as reference
from benchmarks.models import motif as bench_model
from ray_tpu.llm import hybrid_kv
from ray_tpu.llm.engine import LLMEngine, SamplingParams
from ray_tpu.models import moe, motif
from ray_tpu.models.moe import moe_ffn
from ray_tpu.models.motif import MOTIF_PRESETS, MotifConfig, init_params
from ray_tpu.ops.pallas import expert_rows, grouped_rows
from ray_tpu.ops.pallas.latent_attention import (
    keys_expanded,
    latent_expand,
    latent_prefill_attention,
)
from ray_tpu.ops.pallas.window_attention import (
    window_attention,
    window_attention_dense,
)

TOL = 2e-4
PAGE, CHUNK, WINDOW = 8, 16, 8

# The published keys (the catalog's) at a tiny size: what a
# configuration file carries, so that `config` and `for_model` are under
# test too.
TINY = {
    "model_type": "Motif", "attention_cls": "gdla", "diff_v2": True,
    "elementwise_attn_output_gate": True, "headwise_attn_output_gate": False,
    "experts_top_k": 3, "head_dim": 24, "hidden_act": "poly_norm",
    "hidden_size": 64, "interleave_moe_layer_step": 1,
    "intermediate_size": 96, "k_ratio": 1, "kv_lora_rank": 32,
    "max_position_embeddings": 256, "max_window_layers": 9,
    "mhc_enabled": True, "mhc_expansion_rate": 4, "mhc_identity_init": False,
    "mhc_sinkhorn_iters": 20, "moe_intermediate_size": 32, "mscale": 1,
    "n_dense_first_layers": 1, "num_attention_heads": 10, "num_experts": 8,
    "num_hidden_layers": 5, "num_key_value_heads": 2, "num_noise_heads": 2,
    "num_shared_experts": 1, "q_lora_rank": 24, "qk_rope_head_dim": 8,
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "route_norm": True,
    "route_scale": 2, "score_before_experts": False, "score_func": "sigmoid",
    "sliding_window": WINDOW, "sliding_window_pattern": "interleave",
    "sliding_window_period": 4, "swa_rope_theta": 10000,
    "tie_word_embeddings": False, "use_sliding_window": True,
    "v_head_dim": 16, "vocab_size": 256,
    "rope_scaling": {"apply_yarn_scaling": False},
    "polynorm_output_scale": 0.5, "polynorm_output_scale_per_layer": {},
    "polynorm_bias_clamp": 0.5, "hidden_clamp": 1000000,
    "num_nextn_predict_layers": 0, "first_layer": 1,
    "assumed_values": {"mhc_eps": 1e-6},
}
# Rows up to 8 take `moe_ffn`'s every-row form and more its sorted one,
# so that an engine's decode steps (2 slots) run the first and its
# prefills (16 rows and more) the second, as the two meet in a replica.
CFG = bench_model.config(
    TINY, dtype=jnp.float32, dense_expert_rows=8, cell_lanes=16
)
REF = reference.for_model(TINY)
LOWERS = ("no_noise", "lambda_const", "window_as_full", "polynorm_as_silu",
          "static_h", "router_bf16", "weights_e4m3")


def _hot(params):
    """The tree with PolyNorm numbers that are not the initial thirds and
    zero: weights that differ a power and an expert, and biases of which
    some pass the clamp."""
    def heat(block, at):
        out = dict(block)
        for name in ("poly_w", "shared_poly_w"):
            if name in block:
                w = block[name]
                out[name] = w * (1.0 + 0.5 * jnp.cos(
                    at + jnp.arange(w.size, dtype=jnp.float32).reshape(w.shape)
                ))
        for name in ("poly_b", "shared_poly_b"):
            if name in block:
                b = block[name]
                out[name] = 0.9 * jnp.sin(
                    1.0 + at + jnp.arange(b.size, dtype=jnp.float32)
                ).reshape(b.shape)
        return out

    return {**params, "blocks": tuple(
        heat(b, float(i)) for i, b in enumerate(params["blocks"])
    )}


@pytest.fixture(scope="module")
def params():
    return _hot(init_params(jax.random.key(3), CFG))


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(1, CFG.vocab_size, n).tolist()


def _engine(params, cfg=CFG, **kw):
    kw = {"max_batch": 2, "max_seq": 192, "page_size": PAGE, **kw}
    eng = LLMEngine(cfg, params=params, **kw)
    eng.pages_of_last, eng.slot_of_last = [], None
    return eng


def _run(eng, prompt, new):
    """One request to its end: (tokens the model saw, prefills, decodes).
    Its pages and its slot (whose contents outlive it) are left on the
    engine."""
    seen = []
    eng.on_logits = lambda phase, logits, record: seen.append(
        (phase, np.asarray(logits), jax.tree.map(np.asarray, record))
    )
    rid = eng.add_request(prompt, SamplingParams(max_tokens=new))
    req = eng._queue[-1]
    done = None
    while done is None:
        for fin in eng.step():
            done = fin
        eng.pages_of_last = list(req.pages or eng.pages_of_last)
        if eng.slot_of_last is None:
            eng.slot_of_last = eng.slot_of(rid)
    return (prompt + done["tokens"][:-1],
            [s for s in seen if s[0].startswith("prefill")],
            [s for s in seen if s[0] == "decode"])


def _routes(prefills, decodes, n, slot):
    first = np.concatenate([s[2]["routes"] for s in prefills], axis=1)[:, :n]
    return np.concatenate(
        [first] + [s[2]["routes"][:, slot: slot + 1] for s in decodes], axis=1
    )


def test_the_config_is_the_published_layer_pattern():
    """Layer l attends the whole context where l % 4 == 3, the first two
    FFNs are dense; the tiny config is published layers 1-5."""
    full = MotifConfig()
    assert len(full.pattern) == 106
    assert full.pattern[:8] == "RDRDREAE" and full.pattern[8:16] == "REREREAE"
    assert full.count("A") == 13 and full.count("R") == 40
    assert full.count("D") == 2 and full.count("E") == 51
    assert (full.signal_heads, full.group_heads, full.cell_width) == (64, 5, 640)
    assert CFG.pattern == "RDREAERERE" == MOTIF_PRESETS["motif_tiny"].pattern
    assert (CFG.group_heads, CFG.latent_dim, CFG.cell_width) == (5, 40, 48)
    with pytest.raises(ValueError, match="KV group"):
        dataclasses.replace(CFG, noise_heads=4)
    with pytest.raises(ValueError, match="k_ratio"):
        bench_model.config({**TINY, "k_ratio": 2})


def test_two_kinds_of_latent_state_in_one_tree(params):
    """The pool counts the full layer only; the four window layers keep
    W cells a slot; both in the cache's dtype, and the engine counts the
    first as pages and the second as per-slot state."""
    cache = jax.eval_shape(lambda: hybrid_kv.init_hybrid_cache(CFG, 5, PAGE, 3))
    assert cache["cells"].shape == (1, 5, PAGE, CFG.cell_width)
    assert cache["win_cells"].shape == (4, 3, WINDOW, CFG.cell_width)
    assert cache["k"].shape[0] == 0
    serving = CFG.serving()
    held = serving.init_cache(5, PAGE, 3)
    pool, state = serving.cache_bytes(held)
    assert pool == held["cells"].nbytes and state == held["win_cells"].nbytes
    assert serving.counters()["window_bytes"] == state
    assert serving.counters()["latent_bytes"] == pool


# ---------------------------------------------- prefill, decode: the reference
@pytest.mark.parametrize(
    "chunk,calls", [(None, 1), (CHUNK, 5)], ids=["whole", "five_chunks"]
)
def test_prefill_then_decode_equals_the_reference_pass(params, chunk, calls):
    """A 75-token prompt (nine windows; with a chunk of 16 every chunk
    but the first attends a ring the chunk before it left, and the last
    holds 11 real tokens and 5 of padding) and twelve decode steps (past
    a whole window, so that every ring index is one a step wrote)
    through the engine's own programs against the reference's one pass:
    logits at the last prompt position and at each step, every token's
    routes, and the cells as the pages and the rings hold them after the
    last step."""
    n, new = 75, 13
    eng = _engine(params, prefill_chunk=chunk)
    # A slot's last request's cells: what a ring holds that its request
    # did not write is masked, never cleared.
    eng.cache["win_cells"] = eng.cache["win_cells"] + 7.0
    tokens, prefills, decodes = _run(eng, _prompt(1, n), new)
    assert len(prefills) == calls and len(decodes) == new - 1
    want, record = reference.forward_with_record(
        params, jnp.asarray(tokens, jnp.int32), **REF
    )
    got = np.stack([prefills[-1][1][0, 0]] + [s[1][eng.slot_of_last]
                                              for s in decodes])
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want[n - 1:], atol=TOL, rtol=0)
    mine = np.sort(_routes(prefills, decodes, n, eng.slot_of_last), -1)
    assert (mine == np.sort(np.asarray(record["routes"]), -1)).all()
    held = n + new - 1
    full, rings = bench_model.held_cells(
        eng.cache, eng.pages_of_last, eng.slot_of_last, held, CFG.latent_dim
    )
    cells = np.asarray(record["cells"])
    np.testing.assert_allclose(full, cells[[2]], atol=TOL, rtol=0)
    np.testing.assert_allclose(
        rings, cells[[0, 1, 3, 4], held - WINDOW:], atol=TOL, rtol=0
    )
    stats = eng.stats()
    assert stats["window_tokens"] == 4 * n and stats["mhc_tokens"] == 10 * n
    assert stats["prefill_attn_pairs"] == n * (n + 1) // 2
    assert stats["prefill_window_pairs"] == 4 * (
        WINDOW * (WINDOW + 1) // 2 + (n - WINDOW) * WINDOW
    )
    # Off the kernels the full layer's dense scores read the whole table
    # in each of the programs (the bucket's 16 pages: 128 cells), and
    # each of the four window layers its ring and the program's rows.
    width = 128 if chunk is None else CHUNK
    assert stats["latent_cells_expanded"] == calls * (
        128 + 4 * (WINDOW + width)
    )


def test_kernel_programs_are_the_dense_programs(params):
    """The chunk program with both attention kernels (interpreted here)
    and the decode program with the paged latent kernel give the dense
    forms' logits and cells: a second chunk at a true position, a padded
    tail, the ring in front of it."""
    n_pages, chunk_pages = 6, 2
    pages = jnp.arange(1, 1 + n_pages, dtype=jnp.int32)
    tokens = np.asarray(_prompt(5, 3 * CHUNK), np.int32)
    outs = {}
    for use_kernel in (False, True):
        cache = hybrid_kv.init_hybrid_cache(CFG, 1 + n_pages, PAGE, 2)
        program = hybrid_kv.prefill_program(
            CFG, n_pages, chunk_pages, use_kernel
        )
        for start in (0, CHUNK, 2 * CHUNK):
            logits, cache, _ = program(
                params, jnp.asarray(tokens[start: start + CHUNK])[None],
                cache, pages, np.int32(start), np.int32(1), np.int32(43),
            )
        tables = jnp.stack([jnp.full((n_pages,), -1, jnp.int32), pages])
        _, step, cache, _ = hybrid_kv.hybrid_decode(
            params, jnp.asarray([[0], [9]], jnp.int32), cache, tables,
            jnp.asarray([0, 43], jnp.int32), jnp.asarray([False, True]),
            jnp.zeros((2,), jnp.float32), jax.random.key(0), cfg=CFG,
            use_kernel=use_kernel,
        )
        outs[use_kernel] = (logits, step[1], cache["cells"][:, 1:],
                            cache["win_cells"][:, 1])
    for dense, kernel in zip(outs[False], outs[True], strict=True):
        np.testing.assert_allclose(kernel, dense, atol=TOL, rtol=0)


# ---------------------------------------- the full layer's expansion, bounded
@pytest.mark.parametrize(
    "start, table", [(0, 64), (16, 64), (48, 64), (0, 16)],
    ids=["first_chunk", "middle", "last_chunk", "one_block"],
)
def test_bounded_expansion_is_a_groups_einsums_up_to_the_chunks_last_block(
    params, start, table
):
    """`latent_expand` over Motif's two KV groups under ten heads (G <
    H: a key and a value a GROUP, interpreted, key blocks of 16): the
    blocks up to the one that holds the chunk's last position are
    `_expand`'s, and no step writes a block past it (the interpreter
    hands out NaN for what nothing wrote)."""
    p = params["blocks"][4]  # the full layer
    cells = jnp.asarray(
        np.random.default_rng(start).normal(size=(table, CFG.cell_width)),
        jnp.float32,
    )
    got = latent_expand(
        cells, p["w_uk"], p["w_uv"], jnp.int32(start), n_queries=CHUNK,
        block_kv=16, block_groups=1, interpret=True,
    )
    live = keys_expanded(start, CHUNK, table, 16)
    assert live == start + CHUNK
    for mine, whole in zip(got, motif._expand(cells, p, CFG), strict=True):
        assert mine.shape == whole.shape and mine.shape[0] == 2
        np.testing.assert_allclose(
            mine[:, :live], whole[:, :live], atol=2e-5, rtol=0
        )
        assert np.isnan(np.asarray(mine[:, live:])).all()


def _full_layer_over_a_long_table(params, start, dead_page=None):
    """The full layer's mixer for a chunk of 1,024 rows at ``start`` over
    a table of 3,072 cells (three key blocks of the kernels' 1,024;
    pages of 8), by dense scores and by the two kernels; with
    ``dead_page`` the kernels' table points at that page wherever a page
    lies past the chunk's end. Returns (kernels' out, dense out, the two
    pools' live cells' largest difference)."""
    chunk, table = 1024, 3072
    p = params["blocks"][4]
    rng = np.random.default_rng(start)
    n = table // PAGE
    pages = np.asarray(2 + rng.permutation(n), np.int32)
    pool = jnp.asarray(
        rng.normal(size=(2 + n, PAGE, CFG.cell_width)), jnp.float32
    ).at[..., CFG.latent_dim:].set(0.0).at[1].set(jnp.nan)
    h = jnp.asarray(rng.normal(size=(chunk, CFG.d_model)), jnp.float32)
    own = jnp.asarray(pages[start // PAGE: (start + chunk) // PAGE])
    want, dense_pool = motif.gdla_prefill_full(
        h, p, CFG, pool, 0, jnp.asarray(pages), own, jnp.int32(start), False
    )
    if dead_page is not None:
        pages[(start + chunk) // PAGE:] = dead_page
    got, kernel_pool = motif.gdla_prefill_full(
        h, p, CFG, pool, 0, jnp.asarray(pages), own, jnp.int32(start), True
    )
    moved = float(np.abs(np.asarray(kernel_pool[2:] - dense_pool[2:])).max())
    return np.asarray(got), np.asarray(want), moved


@pytest.mark.parametrize("start", [0, 1024, 2048], ids=["first", "middle", "last"])
def test_a_full_layers_chunk_through_the_bounded_expansion_is_the_dense_one(
    params, start
):
    """`gdla_prefill_full` by `latent_expand` and the grouped prefill
    kernel (interpreted, at their own blocks of 1,024 keys) against the
    dense scores over the whole table's expansion: at `start` 0 two of
    the three key blocks are never expanded, and never read."""
    got, want, moved = _full_layer_over_a_long_table(params, start)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    assert moved == 0.0


@pytest.mark.parametrize("start", [0, 1024], ids=["first", "middle"])
def test_a_full_layers_pages_of_nan_past_the_chunk_change_nothing(
    params, start
):
    """The table's pages past the chunk's end pointed at a page of NaN:
    neither kernel touches a key block past the chunk's last, so the
    mixer's output is finite and is the one of the table as it was."""
    clean, _, _ = _full_layer_over_a_long_table(params, start)
    got, want, _ = _full_layer_over_a_long_table(params, start, dead_page=1)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, clean)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_the_kernels_count_a_full_layers_cells_up_to_the_chunks_end():
    """`HybridServing._count` by the kernels: chunks of 2,048 over a
    table of 8,192 expand 2,048, 4,096 .. cells in the one full layer
    (whole key blocks of 1,024 up to each chunk's end) where dense
    scores read the table each time; the window layers' part is the
    same by either."""
    rings = 4 * (WINDOW + 2048)
    for use_kernel, full in ((True, [2048, 4096, 6144, 8192]),
                             (False, [8192] * 4)):
        serving = CFG.serving()
        for i, start in enumerate(range(0, 8192, 2048)):
            serving._count(start, 2048, 8000, 8192, use_kernel)
            assert serving.counters()["latent_cells_expanded"] == (
                sum(full[: i + 1]) + (i + 1) * rings
            )


def test_the_reference_in_token_blocks_is_the_reference(params):
    """The pass the chip's check makes of its longest prompt
    (`bench_model.LONG_PASS`): blocks of tokens, a mixer's queries a
    block at a time against every position's cell; here 75 tokens in
    blocks of 32 (the last of 11) and 8 queries at a time, with forced
    routes as the check gives them: the same logits and the same
    record."""
    tokens = jnp.asarray(_prompt(7, 75), jnp.int32)
    want, record = reference.forward_with_record(params, tokens, **REF)
    got, again = reference.forward_with_record(
        params, tokens, routes=record["routes"], rows=[40, 74],
        token_block=32, query_block=8, block_fn=lambda kind, fn: jax.jit(fn),
        **REF,
    )
    np.testing.assert_allclose(got, want[jnp.asarray([40, 74])], atol=TOL)
    for key in ("routes", "cells"):
        np.testing.assert_allclose(again[key], record[key], atol=TOL)


@pytest.mark.parametrize("lower", LOWERS)
def test_each_switch_of_the_reference_fails_the_comparison(params, lower):
    """What the comparison reads (logits on the reference's own routes,
    the routes, the cells) moves by far more than its tolerance under
    each departure: the noise heads, lambda's input, the window, the
    PolyNorm, the input-dependent mixing, the router's precision and the
    weights' each count."""
    tokens = jnp.asarray(_prompt(11, 60), jnp.int32)
    want, record = reference.forward_with_record(params, tokens, **REF)
    got, other = reference.forward_with_record(
        params, tokens, **REF, lower=lower
    )
    logits = float(np.abs(np.asarray(got) - np.asarray(want))[-8:].max())
    routes = (np.sort(np.asarray(other["routes"]), -1)
              != np.sort(np.asarray(record["routes"]), -1)).any()
    cells = float(np.abs(
        np.asarray(other["cells"]) - np.asarray(record["cells"])
    ).max())
    assert logits > 50 * TOL or routes or cells > 50 * TOL
    if lower != "router_bf16":
        assert logits > 50 * TOL


def test_the_eight_shares_add_up_to_the_uncut_layer(params):
    """An expert layer whose tree holds one of eight shares computes
    that share's routed part and the shared expert: the eight routed
    parts plus the shared expert ONCE are the uncut reference's layer."""
    whole = dataclasses.replace(CFG, experts_held=None)
    p = _hot(init_params(jax.random.key(5), whole))["blocks"][3]
    x = jax.random.normal(jax.random.key(6), (1, 24, CFG.d_model))
    sizes = {**REF, "first_expert_held": 0}
    streams = jnp.broadcast_to(x[0][:, None], (24, 4, CFG.d_model))
    # The reference's layer on a single stream: spread's parts undone.
    h, res, post = reference.mix(p["hc"], streams, **sizes)
    after, _ = reference.expert_sublayer(p, streams, **sizes)
    want = (after - jnp.einsum("sij,sjd->sid", res, streams))[:, 0] / post[:, :1]
    u = np.asarray(reference._rms_norm(h, p["norm"], 1e-5))[None]
    total = None
    for share in range(8):
        cfg = dataclasses.replace(CFG, experts_held=(share, 1))
        part = {**p, **{
            k: p[k][share: share + 1]
            for k in ("w_gate", "w_up", "w_down", "poly_w", "poly_b")
        }}
        out, aux = moe_ffn(jnp.asarray(u), part, cfg)
        total = out if total is None else total + out
    shared = moe_ffn(
        jnp.asarray(u),
        {**{k: p[k][:1] for k in ("w_gate", "w_up", "w_down", "poly_w",
                                  "poly_b")},
         **{k: v for k, v in p.items() if k not in (
             "w_gate", "w_up", "w_down", "poly_w", "poly_b")}},
        dataclasses.replace(CFG, experts_held=(99, 1)),
    )[0]
    np.testing.assert_allclose(
        (total - 7 * shared)[0], want, atol=TOL, rtol=0
    )


# ------------------------------------------------------------ the kernels
def _expert_case(key, n, held, d, f, dtype):
    keys = jax.random.split(key, 6)
    x = jax.random.normal(keys[0], (n, d)).astype(dtype)
    w = lambda k, shape, fan: (  # noqa: E731
        jax.random.normal(k, shape) * fan**-0.5
    ).astype(dtype)
    tree = {
        "w_gate": w(keys[1], (held, d, f), d), "w_up": w(keys[2], (held, d, f), d),
        "w_down": w(keys[3], (held, f, d), f),
        "poly_w": 0.3 + jax.random.uniform(keys[4], (held, 3)),
        "poly_b": jax.random.normal(keys[5], (held, 1)),
    }
    return x, tree


@pytest.mark.parametrize(
    "n,held,d,f,dtype", [(5, 4, 64, 32, "float32"), (16, 6, 128, 256, "bfloat16")],
    ids=["tiny", "two_lane_tiles"],
)
def test_expert_rows_polynorm_is_the_einsum_form(n, held, d, f, dtype):
    """`experts_on_rows` with PolyNorm's numbers, interpreted, against
    `every_row_einsum`: the expert's whole width in one step (256 lanes
    are two of the tiles another kind would be free to take), untouched
    experts skipped."""
    cfg = dataclasses.replace(
        CFG, d_model=d, d_ff=f, experts_held=(0, held), dtype=jnp.dtype(dtype)
    )
    x, tree = _expert_case(jax.random.key(n), n, held, d, f, dtype)
    routes = jax.random.randint(jax.random.key(1), (n, 2), 0, held - 1)
    gates = jax.random.uniform(jax.random.key(2), (n, 2))
    weight, load = moe.every_row_gates(cfg, routes, gates, None)
    assert int(load[held - 1]) == 0  # one expert got no row
    want = moe.every_row_einsum(cfg, x, tree, weight)
    ids, count = moe.touched_first(load)
    got = expert_rows.experts_on_rows(
        x, tree["w_gate"], tree["w_up"], tree["w_down"], weight, ids, count,
        interpret=True, poly=moe._expert_poly(cfg, tree), eps=cfg.norm_eps,
    )
    tol = 2e-5 if dtype == "float32" else 5e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol
    )


@pytest.mark.parametrize(
    "sizes,d,f,dtype",
    [((3, 0, 9, 4), 64, 32, "float32"), ((40, 7, 0, 90, 23), 128, 256, "bfloat16")],
    ids=["tiny", "two_lane_tiles"],
)
def test_grouped_rows_polynorm_is_ragged_dot(sizes, d, f, dtype):
    """`grouped_rows` at ``act="polynorm"``, interpreted, against
    `poly_glu` over ``jax.lax.ragged_dot``: each group its own expert's
    numbers, groups of no rows, tiles that hold several groups."""
    held, total = len(sizes), sum(sizes)
    cfg = dataclasses.replace(
        CFG, d_model=d, d_ff=f, experts_held=(0, held), dtype=jnp.dtype(dtype)
    )
    x, tree = _expert_case(jax.random.key(total), total + 5, held, d, f, dtype)
    load = jnp.asarray(sizes, jnp.int32)
    poly = moe._expert_poly(cfg, tree)
    got = grouped_rows.grouped_rows(
        x, [tree["w_gate"], tree["w_up"]], load, "polynorm", 16,
        interpret=True, poly=poly, eps=cfg.norm_eps,
    )
    expert_of = jnp.repeat(jnp.arange(held), load, total_repeat_length=total + 5)
    want = moe.poly_glu(
        x, tree["w_gate"], tree["w_up"], poly[expert_of], cfg.norm_eps,
        lambda a, w: jax.lax.ragged_dot(a, w, load),
    )
    # (In bfloat16 the oracle rounds the gate product before the norms,
    # whose cube triples the rounding; the kernel norms it in float32.)
    tol = {"atol": 2e-5} if dtype == "float32" else {"atol": 5e-2, "rtol": 2e-2}
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[:total],
        np.asarray(want, np.float32)[:total], **tol,
    )


def test_grouped_rows_refuses_a_polynorm_it_would_split():
    """A PolyNorm expert too wide for one column pass is refused: its
    norms run over the whole width."""
    rows = jax.ShapeDtypeStruct((64, 8192), jnp.bfloat16)
    stack = jax.ShapeDtypeStruct((2, 8192, 4096), jnp.bfloat16)
    with pytest.raises(ValueError, match="whole width"):
        jax.eval_shape(
            lambda r, a, b: grouped_rows.grouped_rows(
                r, [a, b], jnp.asarray([32, 32]), "polynorm", 32,
                poly=jnp.ones((2, 4)), eps=1e-5,
            ), rows, stack, stack,
        )


@pytest.mark.parametrize("start", [0, 64, 192])
def test_grouped_prefill_kernel_is_the_dense_form(start):
    """`latent_prefill_attention` with 2 expanded groups under 10 query
    heads, interpreted, against `motif._attend_dense`: a head reads its
    group's keys and values and the one rotary key."""
    c, t, groups, rep, nope, rope, v_dim = 64, 256, 2, 5, 16, 8, 16
    keys = jax.random.split(jax.random.key(start), 5)
    q_nope = jax.random.normal(keys[0], (c, groups, rep, nope))
    q_pe = jax.random.normal(keys[1], (c, groups, rep, rope))
    k_nope = jax.random.normal(keys[2], (groups, t, nope))
    kpe = jax.random.normal(keys[3], (t, rope))
    v = jax.random.normal(keys[4], (groups, t, v_dim))
    cfg = dataclasses.replace(CFG, qk_nope_head_dim=nope, qk_rope_head_dim=rope)
    hidden = jnp.arange(t)[None, :] > (start + jnp.arange(c))[:, None]
    want = motif._attend_dense(q_nope, q_pe, k_nope, kpe, v, hidden, cfg)
    got = latent_prefill_attention(
        motif._heads_first(q_nope), motif._heads_first(q_pe), k_nope, kpe, v,
        jnp.int32(start), scale=cfg.softmax_scale, block_q=32, block_kv=64,
        interpret=True,
    )
    np.testing.assert_allclose(
        got.reshape(groups, rep, c, v_dim).transpose(2, 0, 1, 3), want,
        atol=2e-5,
    )


@pytest.mark.parametrize("start", [0, 16, 64])
def test_band_kernel_at_two_widths_is_the_dense_form(start):
    """`window_attention` with keys 32 wide and values 16 wide, five
    query heads a group, interpreted, against `window_attention_dense`."""
    c, w, groups, rep, dq, dv = 32, 16, 2, 5, 32, 16
    keys = jax.random.split(jax.random.key(start + 1), 3)
    q = jax.random.normal(keys[0], (c, groups * rep, dq))
    k = jax.random.normal(keys[1], (groups, w + c, dq))
    v = jax.random.normal(keys[2], (groups, w + c, dv))
    want = window_attention_dense(q, k, v, jnp.int32(start), window=w)
    got = window_attention(
        q, k, v, jnp.int32(start), window=w, block_q=16, block_kv=16,
        interpret=True,
    )
    assert got.shape == (c, groups * rep, dv)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_hidden_clamp_holds_a_sublayers_output(params):
    """`_residual` clips a sublayer's output where the config has a
    bound, and only there."""
    x = jnp.zeros((1, 2, 4))
    out = jnp.asarray([[[3.0, -3.0, 0.5, 9.0], [1.0, 1.0, 1.0, 1.0]]])
    cfg = types.SimpleNamespace(hidden_clamp=2.0, residual_multiplier=1.0)
    np.testing.assert_allclose(
        hybrid_kv._residual(x, out, cfg)[0, 0], [2.0, -2.0, 0.5, 2.0]
    )
    cfg.hidden_clamp = None
    np.testing.assert_allclose(hybrid_kv._residual(x, out, cfg), out)
