"""Phi-4-mini-flash-reasoning through `LLMEngine` on the CPU at a tiny
size (every kind of layer: two Mamba-1, a window, the full layer; two
gated memory units, two cross layers; a window of 8 positions) against
the plain reference (benchmarks/reference_phi4flash.py), which runs ALL
layers at EVERY position, on seeded float32 weights: a prompt prefilled
whole and in chunks, with the prompt's last token in the first, a middle
and the last chunk of its table; decode through the pool, the rings and
the state past the window; the halves of the prefill; what a decode step
leaves of a slot that does not decode; each switch of the reference moves
what the comparison reads.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_phi4flash as reference
from benchmarks.models import phi4flash as bench_model
from ray_tpu.llm import hybrid_kv
from ray_tpu.llm.engine import LLMEngine, SamplingParams
from ray_tpu.models import phi4_flash
from ray_tpu.models.phi4_flash import Phi4FlashConfig, init_params, sublayers

TOL = 2e-4
PAGE, CHUNK, WINDOW = 4, 8, 8

# The published keys (the catalog's) at a tiny size: what a
# configuration file carries, so that `config` and `for_model` are under
# test too.
TINY = {
    "model_type": "phi4flash", "embd_pdrop": 0, "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 96, "layer_norm_eps": 1e-5,
    "max_position_embeddings": 256, "mb_per_layer": 0,
    "num_attention_heads": 8, "num_hidden_layers": 8,
    "num_key_value_heads": 4, "resid_pdrop": 0, "sliding_window": WINDOW,
    "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False,
    "vocab_size": 256,
    "assumed_values": {"d_state": 4, "d_conv": 4, "expand": 2, "dt_rank": 4},
}
CFG = bench_model.config(TINY, dtype=jnp.float32)
REF = reference.for_model(TINY) | {"query_block": 16}


@pytest.fixture(scope="module")
def params():
    """Seeded weights whose norms have weights and biases that are not
    the initial ones and zero."""
    tree = init_params(jax.random.key(3), CFG)

    def heat(block, at):
        out = dict(block)
        for name, leaf in block.items():
            if name.endswith("norm"):
                out[name] = 0.3 * jnp.cos(at + jnp.arange(leaf.size) * 0.7)
            if name.endswith("norm_bias"):
                out[name] = 0.1 * jnp.sin(at + jnp.arange(leaf.size) * 0.3)
        return out

    tree = heat(tree, 9.0)
    return {**tree, "blocks": tuple(
        heat(b, float(i)) for i, b in enumerate(tree["blocks"])
    )}


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(1, CFG.vocab_size, n).tolist()


def _engine(params, cfg=CFG, **kw):
    kw = {"max_batch": 2, "max_seq": 128, "page_size": PAGE, **kw}
    eng = LLMEngine(cfg, params=params, **kw)
    eng.pages_of_last, eng.slot_of_last = [], None
    return eng


def _run(eng, prompt, new):
    """One request to its end: (tokens the model saw, prefills, decodes).
    Its pages and its slot (whose contents outlive it) are left on the
    engine."""
    seen = []
    eng.on_logits = lambda phase, logits, record: seen.append(
        (phase, None if logits is None else np.asarray(logits), record)
    )
    rid = eng.add_request(prompt, SamplingParams(max_tokens=new))
    req = eng._queue[-1]
    done = None
    eng.slot_of_last = None
    while done is None:
        for fin in eng.step():
            done = fin
        eng.pages_of_last = list(req.pages or eng.pages_of_last)
        if eng.slot_of_last is None:
            eng.slot_of_last = eng.slot_of(rid)
    return (prompt + done["tokens"][:-1],
            [s for s in seen if s[0].startswith("prefill")],
            [s for s in seen if s[0] == "decode"])


def _against_the_reference(eng, prompt, new, params, **ref):
    """The worst differences between what one request left and read and
    the reference's one pass: logits, the pool layer, the rings, the
    states."""
    tokens, prefills, decodes = _run(eng, prompt, new)
    want, record = reference.forward_with_record(
        params, jnp.asarray(tokens, jnp.int32), **{**REF, **ref}
    )
    n, slot = len(prompt), eng.slot_of_last
    got = np.stack(
        [prefills[-1][1][0, 0]] + [d[1][slot] for d in decodes]
    )
    held = len(tokens)
    (k, v), (win_k, win_v) = bench_model.held_cells(
        eng.cache, eng.pages_of_last, slot, held
    )

    def pairs(a):
        a = np.asarray(a)
        return a.reshape(*a.shape[:-2], a.shape[-2] // 2, -1)

    lo = max(held - WINDOW, 0)  # what the reference's record holds too
    states = np.asarray(eng.cache["ssm1"][:, slot])
    return {
        "logits": float(np.abs(got - np.asarray(want)[n - 1:]).max()),
        "pool": max(float(np.abs(k - pairs(record["k"])).max()),
                    float(np.abs(v - pairs(record["v"])).max())),
        "rings": max(
            float(np.abs(win_k[:, lo - held:] - pairs(record["win_k"])).max()),
            float(np.abs(win_v[:, lo - held:] - pairs(record["win_v"])).max()),
        ),
        "states": float(np.abs(
            states.reshape(len(states), CFG.ssm_state, -1)
            - np.asarray(record["states"]).transpose(0, 2, 1)
        ).max()),
        "prefills": prefills,
    }


def test_the_config_is_the_published_layer_pattern():
    cfg = Phi4FlashConfig()
    assert cfg.pattern == sublayers(32) and len(cfg.pattern) == 64
    assert [cfg.count(kind) for kind in "SW*UCD"] == [9, 8, 1, 7, 7, 32]
    assert cfg.pattern[:4] == "SDWD" and cfg.pattern[32:40] == "SD*DUDCD"
    assert cfg.cross_from == 36
    # ISSUE 68's count: 3.34 B in the layers and 512 M in the embedding.
    assert 3.84e9 < cfg.num_params() < 3.86e9
    assert CFG.pattern == "SDWDSD*DUDCDUDCD" and CFG.cross_from == 8
    tree = jax.eval_shape(lambda k: init_params(k, CFG), jax.random.key(0))
    held = sum(leaf.size for leaf in jax.tree.leaves(tree))
    assert held == CFG.num_params() + CFG.pattern.count("W") + 3  # lam_init
    with pytest.raises(ValueError, match="come before"):
        dataclasses.replace(CFG, pattern="UDSD*DCD")
    with pytest.raises(ValueError, match="ONE full layer"):
        dataclasses.replace(CFG, pattern="SD*DSD*DUDCD")


def test_three_kinds_of_lasting_state_and_one_pool_layer():
    cache = hybrid_kv.init_hybrid_cache(CFG, 10, PAGE, 3)
    shapes = {name: leaf.shape for name, leaf in cache.items()}
    assert shapes == {
        "k": (1, 10, 2, PAGE, 16), "v": (1, 10, 2, PAGE, 16),
        "ssm1": (2, 3, 4, 1, 128), "ssm1_conv": (2, 3, 3, 128),
        # A slot's ring: WINDOW / PAGE pages; a dump page behind them.
        "win_k": (1, 3 * 2 + 1, 2, PAGE, 16), "win_v": (1, 3 * 2 + 1, 2, PAGE, 16),
    }
    assert cache["ssm1"].dtype == jnp.float32


@pytest.mark.parametrize(
    "chunk, n, calls", [(None, 29, 1), (CHUNK, 29, 4), (CHUNK, 21, 3)],
    ids=["whole", "chunks-padded-last", "chunks-mid-page"],
)
def test_prefill_then_decode_equals_the_reference_pass(params, chunk, n, calls):
    """The rings wrap more than once (a window of 8, 40 positions), the
    decode steps run all sublayers on every slot."""
    eng = _engine(params, prefill_chunk=chunk)
    diff = _against_the_reference(eng, _prompt(n, n), 12, params)
    prefills = diff.pop("prefills")
    assert len(prefills) == calls
    assert all(value < TOL for value in diff.values()), diff
    # Only the chunk that holds the prompt's last token has logits.
    assert [p[1] is None for p in prefills] == [True] * (calls - 1) + [False]
    stats = eng.stats()
    assert stats["prefill_self_only_chunks"] == calls - 1
    # The full layer attends in the chunk that holds the last token
    # alone (elsewhere nothing reads its output): that chunk's pairs.
    start = (calls - 1) * CHUNK if chunk else 0
    live = n - start
    assert stats["prefill_attn_pairs"] == live * start + live * (live + 1) // 2
    assert stats["cross_decoder_rows"] == 1 + 11  # a prompt, 11 steps of one
    assert stats["shared_kv_reads"] == 3
    per_token = 2 * 2 * 16 * 4  # k and v, 2 pairs of 16, float32
    context = sum(range(n + 1, n + 12))
    assert stats["shared_kv_bytes"] == 3 * per_token * context
    assert stats["shared_kv_bytes_per_decode_step"] * 11 == stats["shared_kv_bytes"]


@pytest.mark.parametrize(
    "length", [5, 14, 29], ids=["first", "middle", "last"]
)
def test_the_last_token_in_any_chunk_of_the_table(params, length):
    """A table of four chunks: the chunk that holds position ``length -
    1`` runs the cross-decoder on that one row, and its logits are the
    reference's, which ran it at every position; the chunks before it
    stop before it and return none."""
    tokens = jnp.asarray(_prompt(7, 32), jnp.int32)
    want = reference.forward(params, tokens[:length], **REF)
    cache = hybrid_kv.init_hybrid_cache(CFG, 12, PAGE, 2)
    pages = jnp.arange(1, 9, dtype=jnp.int32)
    for start in range(0, 32, CHUNK):
        self_only = start + CHUNK < length
        logits, cache, record = hybrid_kv.prefill_program(
            CFG, 8, CHUNK // PAGE, False, self_only
        )(params, tokens[None, start: start + CHUNK], cache, pages,
          np.int32(start), np.int32(1), np.int32(length))
        assert record is None
        if self_only:
            assert logits is None
        else:
            assert float(jnp.abs(logits[0, 0] - want[-1]).max()) < TOL
            break


def test_program_names_say_which_half():
    names = [
        hybrid_kv.prefill_program(CFG, 8, 2, False, self_only).__name__
        for self_only in (True, False)
    ]
    assert names == ["hybrid_prefill_self_2_of_8", "hybrid_prefill_cross_2_of_8"]
    from ray_tpu.models.laguna import LAGUNA_PRESETS

    other = LAGUNA_PRESETS["laguna_tiny"]
    assert hybrid_kv.prefill_program(other, 8, 2, False).__name__ == (
        "hybrid_prefill_2_of_8"
    )
    with pytest.raises(ValueError, match="no cross-decoder"):
        hybrid_kv.prefill_program(other, 8, 2, False, True)


def test_the_cross_decoder_reads_one_pool_layer_and_writes_nothing(params):
    """From the same cache, the program that stops before the
    cross-decoder and the one that runs it leave the same cache, leaf by
    leaf and bit by bit: four blocks read the one pool layer (its owner,
    the two cross blocks twice over the halves) and none but its owner
    writes."""
    tokens = jnp.asarray(_prompt(5, 16), jnp.int32)
    pages = jnp.arange(1, 5, dtype=jnp.int32)
    left = []
    for self_only in (True, False):
        cache = hybrid_kv.init_hybrid_cache(CFG, 8, PAGE, 2)
        assert cache["k"].shape[0] == 1  # ONE layer of pages
        for start in (0, CHUNK):
            only = self_only or start == 0
            _, cache, _ = hybrid_kv.prefill_program(
                CFG, 4, CHUNK // PAGE, False, only
            )(params, tokens[None, start: start + CHUNK], cache, pages,
              np.int32(start), np.int32(0), np.int32(16))
        left.append(cache)
    for name in left[0]:
        np.testing.assert_array_equal(left[0][name], left[1][name], err_msg=name)


def test_a_slot_that_does_not_decode_keeps_its_three_kinds_of_state(params):
    """A decode step over two slots of which one decodes: the other's
    state, convolution tail and rings stay bit for bit (it may be free or
    mid-prefill), and its key and value go to the dump page."""
    eng = _engine(params)
    _run(eng, _prompt(11, 13), 3)
    cache = jax.tree.map(jnp.copy, eng.cache)
    idle = 1 - eng.slot_of_last
    # Give the idle slot a state to lose.
    for name in ("ssm1", "ssm1_conv", "win_k", "win_v"):
        at = slice(idle * 2, idle * 2 + 2) if name.startswith("win") else idle
        noise = jax.random.normal(jax.random.key(1), cache[name][:, at].shape)
        cache[name] = cache[name].at[:, at].set(noise.astype(cache[name].dtype))
    before = jax.tree.map(np.asarray, cache)

    def of_slot(name, leaf, slot):
        if name.startswith("win"):  # its WINDOW / PAGE pages
            per = WINDOW // PAGE
            return np.asarray(leaf[:, slot * per: (slot + 1) * per])
        return np.asarray(leaf[:, slot])

    tables = np.full((2, 32), -1, np.int32)
    tables[eng.slot_of_last, : len(eng.pages_of_last)] = eng.pages_of_last
    active = np.arange(2) == eng.slot_of_last
    _, _, after, _ = hybrid_kv.hybrid_decode(
        params, np.ones((2, 1), np.int32), cache, tables,
        np.asarray([15, 15], np.int32), active, np.zeros(2, np.float32),
        jax.random.key(0), cfg=CFG, use_kernel=False,
    )
    for name in ("ssm1", "ssm1_conv", "win_k", "win_v"):
        np.testing.assert_array_equal(
            of_slot(name, after[name], idle), of_slot(name, before[name], idle)
        )
        assert (of_slot(name, after[name], 1 - idle)
                != of_slot(name, before[name], 1 - idle)).any()
    changed = np.unique(np.nonzero(np.asarray(after["k"]) != before["k"])[1])
    assert set(changed) <= {0, eng.pages_of_last[15 // PAGE]}


@pytest.mark.parametrize("n", [21, 5], ids=["past-the-window", "inside-it"])
def test_kernel_programs_are_the_dense_programs(params, n):
    """The decode program with the pool's two kernels (interpreted here)
    over the pool layer (its owner's write and attend, the two cross
    blocks' attends) AND over the rings as a second pool gives the gather
    path's logits and leaves, beside two slots that do not decode."""
    eng = _engine(params, max_batch=3, prefill_chunk=CHUNK)
    _run(eng, _prompt(50 + n, n), 3)
    slot = eng.slot_of_last
    tables = np.full((3, 32), -1, np.int32)
    tables[slot, : len(eng.pages_of_last)] = eng.pages_of_last
    outs = {}
    for use_kernel in (False, True):
        cache = jax.tree.map(jnp.copy, eng.cache)
        _, logits, cache, _ = hybrid_kv.hybrid_decode(
            params, np.full((3, 1), 7, np.int32), cache, tables,
            np.full(3, n + 2, np.int32), np.arange(3) == slot,
            np.zeros(3, np.float32), jax.random.key(0), cfg=CFG,
            use_kernel=use_kernel,
        )
        dump = cache["win_k"].shape[1] - 1
        outs[use_kernel] = (
            logits[slot], cache["k"][:, 1:], cache["v"][:, 1:],
            cache["win_k"][:, :dump], cache["win_v"][:, :dump], cache["ssm1"],
        )
    for dense, kernel in zip(outs[False], outs[True], strict=True):
        np.testing.assert_allclose(kernel, dense, atol=TOL, rtol=0)


def test_a_reused_slot_starts_from_zero(params):
    """The second request in a slot reads nothing of the first: its
    state starts from zero at position 0 and its rings are masked by
    position."""
    eng = _engine(params, max_batch=1, prefill_chunk=CHUNK)
    _run(eng, _prompt(21, 27), 6)
    diff = _against_the_reference(eng, _prompt(22, 19), 6, params)
    diff.pop("prefills")
    assert all(value < TOL for value in diff.values()), diff


def test_the_memory_is_the_last_mamba_layers_y_before_the_gate(params):
    """`mamba1_chunked`'s second result is what the reference hands its
    gated memory units; gated, it is not."""
    tokens = jnp.asarray(_prompt(3, 16), jnp.int32)
    _, record = reference.forward_with_record(params, tokens, **REF)
    _, gated = reference.forward_with_record(
        params, tokens, **REF, lower="memory_after_gate"
    )
    # The second Mamba layer's input is the stream after two layers.
    x = reference._f32(params["tok_emb"][tokens])
    zero = (jnp.zeros((128, 4)), jnp.zeros((3, 128)))
    p, p_attn = params["blocks"][0], params["blocks"][2]
    x = x + reference.mamba(p, x, *zero, 16, **REF)[0]
    x = x + reference.mlp(params["blocks"][1], x, **REF)
    q, k, v = reference.qkv(p_attn, x, **REF)
    x = x + reference.attend(p_attn, q, k, v, 0, WINDOW, **REF)
    x = x + reference.mlp(params["blocks"][3], x, **REF)
    p = params["blocks"][4]
    u = phi4_flash._norm(x, p, "norm", CFG)
    (_, y), _, _ = phi4_flash.mamba1_chunked(
        u, p, CFG, jnp.zeros((4, 1, 128)), jnp.zeros((3, 128)), jnp.int32(16)
    )
    assert float(jnp.abs(y - record["memory"]).max()) < TOL
    assert float(jnp.abs(y - gated["memory"]).max()) > 100 * TOL


def test_the_reference_in_token_blocks_is_the_reference(params):
    """Walked 8 rows at a time (the last block padded), the state and
    the convolution's tail handed from block to block, as one block."""
    tokens = jnp.asarray(_prompt(9, 29), jnp.int32)
    whole, record = reference.forward_with_record(params, tokens, **REF)
    for how in ({"token_block": 8}, {"token_block": 16, "scan_block": 4}):
        blocks, walked = reference.forward_with_record(
            params, tokens, **REF, **how
        )
        assert float(jnp.abs(whole - blocks).max()) < 1e-5
        for name in record:
            assert record[name].shape == walked[name].shape, name
            assert float(jnp.abs(record[name] - walked[name]).max()) < 1e-5, name


@pytest.mark.parametrize("lower", reference.LOWERS)
def test_each_switch_of_the_reference_fails_the_comparison(params, lower):
    """Each departure moves a reading the check makes far past float32's
    agreement (which precision's rounding it is to exceed is the chip's
    to say): logits for all; the state for a bfloat16 one; the rings' or
    the pool's keys where the weights are rounded."""
    eng = _engine(params, prefill_chunk=CHUNK)
    diff = _against_the_reference(eng, _prompt(31, 27), 6, params, lower=lower)
    diff.pop("prefills")
    if lower == "state_bf16":
        # A state of ~0.1 rounded to 8 bits: 3e-4, where float32's
        # agreement is 1e-7; the logits hardly read it at this size.
        assert diff["states"] > TOL, diff
        return
    assert diff["logits"] > 20 * TOL, diff
    if lower == "weights_e4m3":
        assert min(diff["pool"], diff["rings"]) > 20 * TOL, diff


def test_the_benchmarks_functions_read_the_counters(params):
    """What the per-layer entries call of benchmarks/models/phi4flash.py
    returns numbers from an engine's own counters."""
    eng = _engine(params, prefill_chunk=CHUNK)
    _run(eng, _prompt(41, 27), 6)
    stats = eng.stats()
    conf = TINY | {"engine": {"page_size": PAGE, "max_batch": 2}}
    assert stats["ssm_scan_tokens"] == 2 * 27
    assert stats["window_tokens"] == 27
    for fn in ("scan_bytes_per_program", "scan_flops_per_program",
               "window_attn_bytes_per_program", "window_attn_flops_per_program",
               "ssm_state_bytes_per_decode_step",
               "shared_kv_bytes_per_decode_step",
               "shared_kv_flops_per_decode_step"):
        assert getattr(bench_model, fn)(conf, stats) > 0, fn
    assert bench_model.kv_token_bytes(conf) == 2 * 4 * 8 * 2
    assert bench_model.held_parameters(conf) == CFG.num_params()
    assert bench_model.check_problems({
        "logit_max_abs_err": [0.01], "finite": True, "cell_rel_err": 0.0,
        "ring_rel_err": 0.0, "state_rel_err": 0.0,
    }) == []
    assert len(bench_model.check_problems({
        "logit_max_abs_err": [9.0], "finite": True, "cell_rel_err": 1.0,
        "ring_rel_err": 0.0, "state_rel_err": 1.0,
    })) == 3
