"""Pallas paged-attention kernel vs the XLA gather path.

The kernel (ops/pallas/paged_attention.py) must match the gather+dense
reference numerically on every shape class the engine dispatches —
GQA and MHA, decode (K=1) and speculative verify (K>1), page-boundary
positions, and slots clamped to the dump page — and the engine's greedy
token streams must be identical with the kernel on and off.

(reference capability: vLLM's paged_attention kernel, which ray.llm
inherits — python/ray/llm/_internal/serve/deployments/llm/vllm/.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.pallas.paged_attention import (
    _pages_per_block,
    paged_attention,
)


def _reference(q, kp, vp, tables, positions, scale=None):
    """The gather+repeat+dense-softmax math from paged_kv.paged_verify
    (pools are head-major: [pages, Hkv, P, Dh])."""
    b, k, h, dh = q.shape
    _, hkv, p, _ = kp.shape
    maxp = tables.shape[1]
    window = maxp * p
    t = jnp.maximum(tables, 0)
    kk = jnp.take(kp, t, axis=0).transpose(0, 1, 3, 2, 4).reshape(
        b, window, hkv, dh
    )
    vv = jnp.take(vp, t, axis=0).transpose(0, 1, 3, 2, 4).reshape(
        b, window, hkv, dh
    )
    kk = jnp.repeat(kk, h // hkv, axis=2)
    vv = jnp.repeat(vv, h // hkv, axis=2)
    pos2d = positions[:, None] + jnp.arange(k)[None, :]
    mask = jnp.arange(window)[None, None, :] > pos2d[:, :, None]
    s = (
        jnp.einsum(
            "bqhd,bkhd->bhqk", q, kk, preferred_element_type=jnp.float32
        )
        * (dh**-0.5 if scale is None else scale)
    )
    s = jnp.where(mask[:, None, :, :], -2.0e38, s)
    probs = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum(
        "bhqk,bkhd->bqhd", probs, vv, preferred_element_type=jnp.float32
    )


def _case(seed, b, k, h, hkv, dh, p, maxp, positions):
    """Pools holding the dump page and each slot's own pages. A
    position of None is a dead slot: table -1, position 0."""
    rng = np.random.default_rng(seed)
    needs = [
        0 if pos is None else min((pos + k + p - 1) // p, maxp)
        for pos in positions
    ]
    npages = sum(needs) + 1
    q = jnp.asarray(rng.normal(size=(b, k, h, dh)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(npages, hkv, p, dh)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(npages, hkv, p, dh)), jnp.float32)
    tables = np.full((b, maxp), -1, np.int32)
    nxt = 1
    for i, need in enumerate(needs):
        tables[i, :need] = np.arange(nxt, nxt + need)
        nxt += need
    pos = [0 if pos is None else pos for pos in positions]
    return q, kp, vp, jnp.asarray(tables), jnp.asarray(pos, jnp.int32)


# mistral7b-serve1's table (benchmarks/configs): 32 slots of 132 pages
# of 64 tokens, 32 query and 8 KV heads of 128.
SERVE = dict(b=32, h=32, hkv=8, dh=128, p=64, maxp=132)


def _serve_case(k, lengths):
    """A 32-slot batch at the serving shapes: ``lengths`` (tokens a
    slot holds once this step's K are written; 0 = dead) spread over
    the slots with dead ones before, between and after them."""
    positions = [None] * SERVE["b"]
    for i, n in enumerate(lengths):
        positions[1 + 3 * i] = n - k
    return pytest.param(
        SERVE["b"], k, SERVE["h"], SERVE["hkv"], SERVE["dh"], SERVE["p"],
        SERVE["maxp"], positions, id=f"serve-k{k}",
    )


def _serve_block(k):
    """Tokens in one block of the kernel's loop at the serving shapes
    (float32 pools here: half the pages a block of bf16 ones has)."""
    r = SERVE["h"] // SERVE["hkv"] * k
    return SERVE["p"] * _pages_per_block(
        SERVE["hkv"], SERVE["p"], SERVE["dh"], r, 4, SERVE["maxp"]
    )


@pytest.mark.parametrize(
    "b,k,h,hkv,dh,p,maxp,positions,scale",
    [
        # A model's own score scale (models/granite_hybrid.py: 1/128).
        (3, 1, 8, 2, 64, 16, 4, [17, 50, 3], 1 / 128),
        (3, 3, 8, 2, 64, 8, 32, [6, 190, 253], 1 / 128),
    ] + [
        pytest.param(*getattr(case, "values", case), None,
                     id=getattr(case, "id", None))
        for case in [
        (3, 1, 8, 2, 64, 16, 4, [17, 50, 3]),          # GQA decode
        (2, 1, 4, 4, 32, 8, 3, [0, 20]),               # MHA, pos 0
        (3, 4, 8, 2, 64, 16, 4, [15, 47, 60]),         # verify K=4,
        #   incl. pos 15: the K window crosses a page boundary
        (2, 2, 16, 1, 64, 8, 8, [31, 62]),             # 1 kv head (MQA)
        (4, 1, 8, 2, 64, 16, 4, [None, 33, None, 5]),  # dead slots
        (3, 3, 8, 2, 64, 8, 32, [6, 190, 253]),        # a long table;
        #   pos 253 + 3 = its last cell
        # One token, exactly one page, exactly one block, one past a
        # block, the full 8,448.
        _serve_case(1, [
            1, 64, _serve_block(1), _serve_block(1) + 1, 132 * 64,
        ]),
        # K = 5: drafts across a page boundary (cells 62..66), across a
        # block boundary, up to the table's last cell, and past it
        # (positions 8,446..8,450 of 8,448: near max_seq).
        _serve_case(5, [
            67, _serve_block(5) + 2, 132 * 64, 132 * 64 + 3,
        ]),
        ]
    ],
)
def test_kernel_matches_gather_reference(
    b, k, h, hkv, dh, p, maxp, positions, scale
):
    q, kp, vp, tables, pos = _case(7, b, k, h, hkv, dh, p, maxp, positions)
    out = paged_attention(
        q, kp, vp, tables, pos, n_kv_heads=hkv, interpret=True, scale=scale
    )
    # Slot by slot: the reference repeats a slot's whole window to all
    # query heads. A dead slot's output is nobody's.
    for i in [i for i, at in enumerate(positions) if at is not None]:
        one = slice(i, i + 1)
        ref = _reference(q[one], kp, vp, tables[one], pos[one], scale)
        np.testing.assert_allclose(
            np.asarray(out[one]), np.asarray(ref), atol=2e-5, rtol=2e-5
        )


@pytest.mark.parametrize(
    "hkv,p,dh,r,itemsize,maxp,n",
    [
        (8, 64, 128, 4, 2, 132, 4),    # mistral7b-serve1, K = 1
        (8, 64, 128, 20, 2, 132, 4),   # ... K = 5
        (8, 64, 128, 4, 4, 132, 2),    # float32 pools: half the pages
        (2, 16, 64, 4, 4, 3, 2),       # no more than the table holds
        (8, 1024, 128, 4, 2, 8, 1),    # a page over the budget: one
    ],
)
def test_block_size_follows_from_the_shapes(hkv, p, dh, r, itemsize, maxp, n):
    assert _pages_per_block(hkv, p, dh, r, itemsize, maxp) == n


def test_inactive_slot_is_harmless():
    """A slot with an all -1 table (clamped to the dump page) must not
    poison other slots' outputs."""
    q, kp, vp, tables, pos = _case(3, 3, 1, 8, 2, 64, 16, 4, [9, 25, 40])
    t = np.asarray(tables).copy()
    t[1, :] = -1
    p0 = np.asarray(pos).copy()
    p0[1] = 0
    out = paged_attention(
        q, kp, vp, jnp.asarray(t), jnp.asarray(p0),
        n_kv_heads=2, interpret=True,
    )
    ref = _reference(q, kp, vp, tables, pos)
    np.testing.assert_allclose(
        np.asarray(out)[[0, 2]], np.asarray(ref)[[0, 2]],
        atol=2e-5, rtol=2e-5,
    )


def test_stale_cells_beyond_frontier_are_masked():
    """Garbage in allocated-but-not-yet-written cells (past positions+K)
    must not affect the output — the per-slot length mask covers it."""
    q, kp, vp, tables, pos = _case(5, 2, 1, 4, 2, 32, 8, 4, [5, 12])
    ref = paged_attention(
        q, kp, vp, tables, pos, n_kv_heads=2, interpret=True
    )
    # Poison every cell beyond each slot's frontier in its own pages.
    kp2, vp2 = np.asarray(kp).copy(), np.asarray(vp).copy()
    t = np.asarray(tables)
    for b in range(2):
        frontier = int(pos[b]) + 1
        for pi, pg in enumerate(t[b]):
            if pg < 0:
                continue
            lo = max(0, frontier - pi * 8)
            kp2[pg, :, lo:] = 999.0  # head-major: positions at dim 2
            vp2[pg, :, lo:] = -999.0
    out = paged_attention(
        jnp.asarray(q), jnp.asarray(kp2), jnp.asarray(vp2),
        tables, pos, n_kv_heads=2, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


# ------------------------------------------------ engine token parity
def test_engine_greedy_parity_kernel_vs_gather(monkeypatch):
    """The paged engine must emit IDENTICAL greedy token streams with
    the kernel on and off (argmax is robust to the fp reduction-order
    differences between online and dense softmax)."""
    from ray_tpu.llm.engine import LLMEngine, SamplingParams
    from ray_tpu.models.llama import PRESETS, init_params

    cfg = PRESETS["tiny"]
    params = init_params(jax.random.key(0), cfg)
    prompts = [[1, 2, 3, 4, 5], [7, 8], [9, 10, 11, 12]]
    sp = SamplingParams(max_tokens=6)

    monkeypatch.setenv("RAY_TPU_PAGED_ATTN", "0")
    gather = LLMEngine(
        cfg, max_batch=2, max_seq=64, params=params,
        kv="paged", page_size=16,
    )
    assert not gather.paged_attn_kernel
    monkeypatch.setenv("RAY_TPU_PAGED_ATTN", "1")
    kernel = LLMEngine(
        cfg, max_batch=2, max_seq=64, params=params,
        kv="paged", page_size=16,
    )
    assert kernel.paged_attn_kernel
    assert gather.generate(prompts, sp) == kernel.generate(prompts, sp)


def test_engine_speculative_parity_with_kernel(monkeypatch):
    """Speculative decoding through the kernel verify path stays
    bit-identical to plain decode (the speculative CI gate, now with
    the kernel underneath)."""
    from ray_tpu.llm.engine import LLMEngine, SamplingParams
    from ray_tpu.models.llama import PRESETS, init_params

    cfg = PRESETS["tiny"]
    params = init_params(jax.random.key(0), cfg)
    # Repetitive prompt so prompt-lookup actually drafts.
    prompt = [5, 6, 7, 5, 6, 7, 5, 6, 7, 5, 6]
    sp = SamplingParams(max_tokens=8)

    monkeypatch.setenv("RAY_TPU_PAGED_ATTN", "1")
    plain = LLMEngine(
        cfg, max_batch=1, max_seq=64, params=params,
        kv="paged", page_size=16,
    )
    spec = LLMEngine(
        cfg, max_batch=1, max_seq=64, params=params,
        kv="paged", page_size=16, speculate=3,
    )
    assert plain.generate([prompt], sp) == spec.generate([prompt], sp)
