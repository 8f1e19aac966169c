"""Paged decode attention as a Pallas TPU kernel.

The XLA fallback in ``llm/paged_kv.py`` gathers every slot's full page
window out of the pool (``jnp.take``): per step it moves B x window x
Hkv x Dh bytes of HBM whatever each request's true length. This
kernel's work list is the live pages:

- **A loop over each slot's own pages.** The grid runs over the slots
  alone. Inside, a ``fori_loop`` whose trip count is the slot's number
  of live blocks, ``ceil((positions[b] + K) / (N * P))``, read from
  scalar prefetch: the table's width is in no grid dimension, so a
  100-token request in an 8,448-token-wide table costs one block and
  an empty slot (position 0) one block of one page.
- **Pages are read in place, N at a time.** The pool stays in HBM
  (``pl.ANY``); a block's live pages are fetched through the block
  table by one async copy each into a double buffer, head-major
  (``[Hkv, N*P, Dh]``), and the next block's copies (the next slot's
  first block, after a slot's last) start before this block's
  arithmetic. No gathered copy of the window ever exists in HBM.
- **One soft-max update a block, heads batched.** A block is ``N*P``
  keys: one ``[Hkv] x [R, Dh] x [Dh, N*P]`` and one ``[Hkv] x [R, N*P]
  x [N*P, Dh]`` product for all KV heads, queries laid out ``[B, Hkv,
  n_rep*K, Dh]`` so that KV is never repeated to n_heads, and the
  running max and sum carried at their own width.
- **N follows from the shapes** (``_pages_per_block``): what the K/V
  double buffers and a block's scores take of ``_BLOCK_VMEM_BYTES``.

Numerics follow the flash kernel (online softmax with finite mask
values, operands in the pool's dtype, fp32 scores and accumulation);
outputs match the XLA gather path to fp tolerance, and greedy token
streams are identical (gated by tests).

The pool layout is HEAD-major ([pages, Hkv, P, Dh]) and, as for every
Mosaic operand, row-major in memory: a page is one contiguous 131 KB
copy at Mistral-7B's shapes. THIS KERNEL FIXES THE POOL'S LAYOUT for
the program it is in: whatever else touches the pool there must leave
it row-major, or XLA re-lays the whole pool out before every call. So
the decode program writes its cells with
``kv_cell_write.write_kv_cells`` (a Mosaic call, the pool aliased to
its result) and not with an XLA scatter, and carries the pool through
its layer loop (``llm/paged_kv.py _scan_layers``): the ``k_pool`` /
``v_pool`` here are that loop's carry, all layers' pages in one flat
view, with the layer's page base already in ``block_tables``.

The reference has no paged attention of its own: ray.llm buys it from
vLLM (reference: python/ray/llm/_internal/serve/deployments/llm/vllm/
vllm_models.py:234, engine_kwargs pass-through); this is the TPU-native
equivalent of vLLM's paged_attention kernel. Models read, not imported:
jax/experimental/pallas/ops/tpu/paged_attention (its pool is
``[Hkv, pages, P, Dh]``, ours is not).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Finite mask/init values (see flash_attention.py): exp(x - m) underflows
# to exactly 0 without the -inf NaN guards.
_MASK = -1e9
_M_INIT = -1e30
# What one block may take of VMEM: the K and V double buffers and the
# block's fp32 scores and probabilities. A v5e core has 16 MiB. At 3 MiB
# a block is 4 pages of Mistral-7B's (256 keys, 1 MB of K and V): on the
# chip blocks of 4, 8 and 16 pages read 8,448-token contexts equally
# fast (91% of the HBM peak) and 4 is cheapest where a slot has a page
# or two, since a block's arithmetic is paid on its masked keys too
# (PERF.md section 6, PR 30). The compile for a described v5e
# (tests/test_tpu_aot_compile.py) is the proof that the serving shapes
# fit.
_BLOCK_VMEM_BYTES = 3 * 1024 * 1024
_SUBLANES = 8


def _pages_per_block(
    n_kv_heads: int, page_size: int, head_dim: int, r: int,
    itemsize: int, max_pages: int,
) -> int:
    """N: the largest power of two of pages whose block fits
    ``_BLOCK_VMEM_BYTES``, and no more than the table holds."""
    kv = 4 * n_kv_heads * page_size * head_dim * itemsize  # K, V, x 2
    rows = -(-r // _SUBLANES) * _SUBLANES
    scores = 2 * n_kv_heads * rows * page_size * 4  # s and p, fp32
    fit = max(_BLOCK_VMEM_BYTES // (kv + scores), 1)
    n = 1 << (fit.bit_length() - 1)
    while n > max_pages:
        n //= 2
    return n


def _make_kernel(
    block_pages: int, page_size: int, n_queries: int, max_pages: int,
    scale: float,
):
    """Kernel of one slot a grid step. Refs: scalar prefetch (tables,
    pos), q, the K and V pools in HBM, out, then the K and V double
    buffers ``[2, Hkv, N*P, Dh]``, their DMA semaphores ``[2 (K, V), 2
    (buffer)]`` and, in SMEM, the buffer that holds this step's first
    block (started by the step before)."""
    block_keys = block_pages * page_size

    def _kernel(
        tables_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref,
        k_buf, v_buf, sems, first_buf_ref,
    ):
        b = pl.program_id(0)

        def live_pages(slot):
            # Drafts may reach past the table near max_seq: their cells
            # went to the dump page (paged_verify), nobody attends them.
            return jnp.minimum(
                pl.cdiv(pos_ref[slot] + n_queries, page_size), max_pages
            )

        def for_block_copies(slot, blk, buf, do):
            """``do`` (start or wait) each page copy of block ``blk`` of
            ``slot`` into buffer ``buf``: the block's live pages only, so
            the table's dead width costs no HBM traffic."""
            first = blk * block_pages
            count = jnp.minimum(live_pages(slot) - first, block_pages)

            def one_page(j, carry):
                page = tables_ref[slot, first + j]
                keys = pl.ds(
                    pl.multiple_of(j * page_size, page_size), page_size
                )
                for hbm, vmem, sem in (
                    (k_hbm, k_buf, sems.at[0, buf]),
                    (v_hbm, v_buf, sems.at[1, buf]),
                ):
                    do(pltpu.make_async_copy(
                        hbm.at[page], vmem.at[buf, :, keys, :], sem
                    ))
                return carry

            jax.lax.fori_loop(0, count, one_page, None)

        def start(copy):
            copy.start()

        def wait(copy):
            copy.wait()

        @pl.when(b == 0)
        def _first_step():
            # A masked key's probability is exactly 0, and 0 x what the
            # buffer held before any copy (NaN bits, perhaps) is not: V
            # starts finite. K needs none: its scores are overwritten.
            v_buf[...] = jnp.zeros_like(v_buf)
            first_buf_ref[0] = 0
            for_block_copies(0, 0, 0, start)

        n_blocks = pl.cdiv(live_pages(b), block_pages)
        q = q_ref[0]  # [Hkv, R, Dh]
        n_kv, r, head_dim = q.shape

        def block(i, carry):
            m_prev, l_prev, acc, buf = carry
            # The next block's copies, before this block's arithmetic:
            # this slot's next block or, after its last, the next
            # slot's first (every slot has one: positions >= 0).
            last = i + 1 == n_blocks
            next_slot = jnp.where(last, b + 1, b)

            @pl.when(next_slot < pl.num_programs(0))
            def _prefetch():
                for_block_copies(
                    next_slot, jnp.where(last, 0, i + 1), 1 - buf, start
                )

            for_block_copies(b, i, buf, wait)
            s = jax.lax.dot_general(
                q, k_buf[buf],
                dimension_numbers=(((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            ) * scale  # [Hkv, R, N*P]
            # Causal / length mask: key cell c of the block lives at
            # global position i*N*P + c; query row r is query token
            # r % K writing at pos + r % K. (Stale cells beyond the
            # frontier, and pages of the block that were not fetched,
            # are masked; cells behind it are valid by the
            # scatter-before-gather invariant shared with the XLA path.)
            key_pos = i * block_keys + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 2
            )
            q_pos = pos_ref[b] + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1
            ) % n_queries
            s = jnp.where(key_pos > q_pos, _MASK, s)

            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)  # masked -> 0
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + p.sum(axis=-1, keepdims=True)
            acc = acc * alpha + jax.lax.dot_general(
                p.astype(v_buf.dtype), v_buf[buf],
                dimension_numbers=(((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )  # [Hkv, R, Dh]
            return m_new, l_new, acc, 1 - buf

        _, l, acc, buf = jax.lax.fori_loop(
            0, n_blocks, block,
            (
                jnp.full((n_kv, r, 1), _M_INIT, jnp.float32),
                jnp.zeros((n_kv, r, 1), jnp.float32),
                jnp.zeros((n_kv, r, head_dim), jnp.float32),
                first_buf_ref[0],
            ),
        )
        first_buf_ref[0] = buf
        o_ref[0] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)

    return _kernel


@functools.partial(
    jax.jit, static_argnames=("n_kv_heads", "interpret", "scale")
)
def paged_attention(
    q: jnp.ndarray,  # [B, K, H, Dh] (rope applied)
    k_pool: jnp.ndarray,  # [num_pages, Hkv, P, Dh] (head-major)
    v_pool: jnp.ndarray,  # [num_pages, Hkv, P, Dh]
    block_tables: jnp.ndarray,  # [B, max_pages] int32 (-1 = unused)
    positions: jnp.ndarray,  # [B] int32: write position of q[:, 0]
    *,
    n_kv_heads: int,
    interpret: bool = False,
    scale: float | None = None,
) -> jnp.ndarray:
    """Decode/verify attention over the page pool; returns [B, K, H, Dh].

    Query token k of slot b attends to key positions <= positions[b]+k
    within the slot's block table (the K=1 case is plain decode). The
    pool is read in place, each slot's live pages only: see the module
    docstring. ``scale`` multiplies the scores (``Dh**-0.5`` where None).
    """
    b, kk, n_heads, head_dim = q.shape
    num_pages, hkv, page_size, _ = k_pool.shape
    assert hkv == n_kv_heads
    n_rep = n_heads // n_kv_heads
    r = n_rep * kk
    max_pages = block_tables.shape[1]
    block_pages = _pages_per_block(
        n_kv_heads, page_size, head_dim, r, k_pool.dtype.itemsize, max_pages
    )

    # [B, K, H, Dh] -> [B, Hkv, n_rep*K, Dh]: head h = g*n_rep + h_rep
    # lands in group g, row h_rep*K + k — so row % K is the query index.
    qg = (
        q.transpose(0, 2, 1, 3)
        .reshape(b, n_kv_heads, n_rep, kk, head_dim)
        .reshape(b, n_kv_heads, r, head_dim)
    )
    q_spec = pl.BlockSpec(
        (1, n_kv_heads, r, head_dim), lambda bi, tab, pos: (bi, 0, 0, 0)
    )
    kv_buf = pltpu.VMEM(
        (2, n_kv_heads, block_pages * page_size, head_dim), k_pool.dtype
    )
    out = pl.pallas_call(
        _make_kernel(
            block_pages=block_pages,
            page_size=page_size,
            n_queries=kk,
            max_pages=max_pages,
            scale=head_dim**-0.5 if scale is None else scale,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[
                q_spec,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=q_spec,
            scratch_shapes=[
                kv_buf,
                kv_buf,
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(
            (b, n_kv_heads, r, head_dim), q.dtype
        ),
        # A step starts the next slot's first copies: the slots in order.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
    )(
        jnp.maximum(block_tables, 0).astype(jnp.int32),
        positions.astype(jnp.int32), qg, k_pool, v_pool,
    )
    # [B, Hkv, n_rep*K, Dh] -> [B, K, H, Dh]
    return (
        out.reshape(b, n_kv_heads, n_rep, kk, head_dim)
        .transpose(0, 3, 1, 2, 4)
        .reshape(b, kk, n_heads, head_dim)
    )
