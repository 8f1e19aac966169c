"""The Pallas K/V cell write (ops/pallas/kv_cell_write.py), interpreted,
against XLA's scatter on the same inputs.

The kernel exists for the pool's layout (its module docstring); what it
writes must be what ``pool.at[pages, :, offs, :].set(new)`` writes, bit
for bit, on every pattern ``paged_verify`` produces: one cell a slot, K
drafts of a slot in one page (the block stays resident between their
grid steps), a draft that crosses a page boundary, and the two routes to
the dump page 0 — inactive slots (table -1) and positions past the
table's window — which may hold anything but must be the ONLY page that
does.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.pallas.kv_cell_write import write_kv_cells

HKV, P, DH, MAXP = 2, 8, 16, 3
WINDOW = MAXP * P


def _write_targets(tables, positions, k):
    """paged_verify's own arithmetic: physical page and row of each of
    the K cells a slot writes, slot-major."""
    pos2d = positions[:, None] + np.arange(k)[None, :]
    page_of = np.minimum(pos2d // P, MAXP - 1)
    pages = np.maximum(np.take_along_axis(tables, page_of, axis=1), 0)
    pages = np.where(pos2d < WINDOW, pages, 0)
    return pages.reshape(-1).astype(np.int32), (pos2d % P).reshape(-1).astype(
        np.int32
    )


# name -> (K, block tables, first write position of each slot)
CASES = {
    # Plain decode: one cell a slot, every slot its own page.
    "k1": (1, [[1, 2, -1], [3, -1, -1], [4, 5, 6]], [9, 0, 23]),
    # Four drafts inside one page: the page is written on four
    # consecutive grid steps and fetched once.
    "k4_drafts_in_one_page": (4, [[1, 2, -1], [3, 4, -1]], [2, 11]),
    # Drafts that cross a page boundary: two cells in page 1, two in 2.
    "k4_draft_crosses_a_page": (4, [[1, 2, -1], [3, 4, 5]], [6, 15]),
    # Inactive slots between live ones: their cells go to page 0, which
    # comes back after other pages were written.
    "k1_inactive_slots": (
        1, [[-1, -1, -1], [1, 2, -1], [-1, -1, -1], [3, -1, -1]],
        [0, 12, 0, 5],
    ),
    # Near max_seq a K-wide step runs past the window: those cells go to
    # page 0 and the slot's last page keeps its cells.
    "k4_past_the_window": (4, [[1, 2, 3], [-1, -1, -1]], [WINDOW - 2, 0]),
}


@pytest.mark.parametrize("base", [0, 7], ids=["layer0", "layer1"])
@pytest.mark.parametrize("case", list(CASES))
def test_cell_write_equals_xla_scatter(case, base):
    """``base``: the layer's first page in the flat pool the layer loop
    carries (llm/paged_kv.py _scan_layers); page 0 of a layer is its
    dump page."""
    k, tables, positions = CASES[case]
    tables = np.asarray(tables, np.int32)
    positions = np.asarray(positions, np.int32)
    pages, offs = _write_targets(tables, positions, k)
    dump = base
    pages = pages + base
    n = len(pages)

    rng = np.random.default_rng(len(case) * 10 + base)
    num_pages = 2 * 7
    k_pool = rng.normal(size=(num_pages, HKV, P, DH)).astype(np.float32)
    v_pool = rng.normal(size=(num_pages, HKV, P, DH)).astype(np.float32)
    k_new = rng.normal(size=(n, HKV, DH)).astype(np.float32)
    v_new = rng.normal(size=(n, HKV, DH)).astype(np.float32)

    got_k, got_v = write_kv_cells(
        jnp.asarray(k_pool), jnp.asarray(v_pool), jnp.asarray(k_new),
        jnp.asarray(v_new), jnp.asarray(pages), jnp.asarray(offs),
        interpret=True,
    )
    want_k = jnp.asarray(k_pool).at[pages, :, offs, :].set(jnp.asarray(k_new))
    want_v = jnp.asarray(v_pool).at[pages, :, offs, :].set(jnp.asarray(v_new))

    live = np.arange(num_pages) != dump
    for got, want, before, new in (
        (got_k, want_k, k_pool, k_new),
        (got_v, want_v, v_pool, v_new),
    ):
        got, want = np.asarray(got), np.asarray(want)
        # Every page but the dump page: XLA's scatter, bit for bit.
        assert np.array_equal(got[live], want[live])
        # Outside the written cells nothing moved, and each live cell
        # holds its own new row.
        untouched = np.ones((num_pages, P), bool)
        untouched[pages, offs] = False
        untouched[dump] = False
        assert np.array_equal(
            got.transpose(0, 2, 1, 3)[untouched],
            before.transpose(0, 2, 1, 3)[untouched],
        )
        for i in np.nonzero(pages != dump)[0]:
            assert np.array_equal(got[pages[i], :, offs[i], :], new[i])
    if not (pages == dump).any():
        assert np.array_equal(np.asarray(got_k), np.asarray(want_k))
        assert np.array_equal(np.asarray(got_v), np.asarray(want_v))


@pytest.mark.parametrize("k", [1, 4])
def test_verify_writes_each_layers_pages_on_both_paths(k):
    """Through ``paged_verify`` with the pool carried: kernel path
    (interpreted) and XLA path leave the same pool. Layer 0's cells are
    functions of the embeddings alone, so they are equal bit for bit;
    deeper layers see the two attentions' rounding. Pages no slot wrote
    are the same bytes as before, in every layer."""
    from ray_tpu.llm.paged_kv import paged_verify
    from ray_tpu.models.llama import PRESETS, init_params

    cfg = PRESETS["tiny"]
    params = init_params(jax.random.key(0), cfg)
    page, num_pages = 8, 9
    rng = np.random.default_rng(k)
    shape = (cfg.n_layers, num_pages, cfg.n_kv_heads, page, cfg.head_dim)
    start = {
        "k": rng.normal(size=shape).astype(np.float32),
        "v": rng.normal(size=shape).astype(np.float32),
    }
    tables = np.asarray([[1, 2], [-1, -1], [3, 4]], np.int32)
    positions = np.asarray([6, 0, 9], np.int32)  # slot 0 crosses a page
    tokens = rng.integers(1, cfg.vocab_size, (3, k)).astype(np.int32)

    pools = {}
    for use_kernel in (False, True):
        _, _, pools[use_kernel], _, _ = paged_verify(
            params, jnp.asarray(tokens),
            {n: jnp.asarray(a, cfg.dtype) for n, a in start.items()},
            jnp.asarray(tables), jnp.asarray(positions),
            jnp.zeros((3,), jnp.float32), jax.random.key(1),
            cfg=cfg, use_kernel=use_kernel, stochastic=False,
        )
    assert pools[True]["k"].shape == shape
    written = sorted({1, 2, 3, 4} if k > 1 else {1, 4})
    idle = [p for p in range(1, num_pages) if p not in written]
    for name in ("k", "v"):
        xla = np.asarray(pools[False][name], np.float32)
        kernel = np.asarray(pools[True][name], np.float32)
        before = np.asarray(jnp.asarray(start[name], cfg.dtype), np.float32)
        assert np.array_equal(kernel[0, 1:], xla[0, 1:])
        np.testing.assert_allclose(
            kernel[:, 1:], xla[:, 1:], atol=0.05, rtol=0.05
        )
        assert np.array_equal(kernel[:, idle], before[:, idle])
        assert np.array_equal(xla[:, idle], before[:, idle])
        # Each layer's cells landed in that layer's pages.
        for layer in range(cfg.n_layers):
            assert not np.array_equal(
                kernel[layer, written], before[layer, written]
            )


@pytest.mark.parametrize("flag", ["0", "1"])
def test_stats_name_the_cell_write_the_engine_compiled(flag, monkeypatch):
    """The write follows the attention's path, and ``stats()`` says
    which: no option of its own."""
    from ray_tpu.llm.engine import LLMEngine, SamplingParams
    from ray_tpu.models.llama import PRESETS, init_params

    cfg = PRESETS["tiny"]
    monkeypatch.setenv("RAY_TPU_PAGED_ATTN", flag)
    eng = LLMEngine(
        cfg, max_batch=2, max_seq=64, kv="paged", page_size=16,
        params=init_params(jax.random.key(0), cfg),
    )
    eng.generate([[1, 2, 3]], SamplingParams(max_tokens=3))
    stats = eng.stats()
    assert stats["kv_write_kernel"] is (flag == "1")
    assert stats["kv_write_kernel"] == stats["paged_attn_kernel"]
