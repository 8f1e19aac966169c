"""Latent attention's three kernels: paged decode attention over a
LATENT page pool, a chunk's causal attention over expanded keys and
values, and the expansion in front of it.

**Decode** (``latent_paged_attention``). The sibling of
``paged_attention.py`` for multi-head latent attention in its absorbed
form (``llm/latent_kv.py``): a token's cache cell is one row ``[c; kpe;
zeros]`` of width W (576 numbers held 640 wide at the published widths)
that serves EVERY query head, and the values are the keys' first
``v_width`` columns. So there is one pool, not a K and a V pool, and a slot's H
heads x K queries are the R rows of one matrix against one "KV head":

    s = q [R, W] . cell^T * scale;  o = soft-max(s) . cell[:, :v_width]

Passing the pool to ``paged_attention`` as both K and V would fetch every
page twice and multiply the probabilities by W - v_width dead columns.
Here a page is fetched once into one buffer that both products read.

The rest is ``paged_attention``'s: the grid runs over the slots, a
``fori_loop`` over the slot's own live blocks of N pages (trip count
from scalar prefetch), pages fetched through the block table by one
async copy each into a double buffer, the next block's copies (the next
slot's first, after a slot's last) started before this block's
arithmetic, one soft-max update a block with finite mask values. The
pool is ``[pages, P, W]``, the layer loop's carry in a flat view, with
the layer's page base already in ``block_tables``.

By the numbers (v5e, 128 heads, 576 numbers a cell, v_width 512, bf16):
a cached token costs 2 x 128 x (576 + 512) operations and 1,152 bytes,
242 operations a byte, which is the chip's ridge (197 TFLOP/s over 819
GB/s): the kernel is bound by the matmul unit and by HBM at once. On the
chip it reaches 47% of that in the serving cell's decode steps
(``latent_attn_roofline_pct.longdoc``, traced; alone, 32 slots with 16 of
them at 4-16k, a call takes 1.13-1.26 ms on the host's clock for 165,312
live tokens with blocks of 4 to 16 pages: my chip runs, PR 33): a
block's two products have 128 rows against a 512-key tile, and the
running sum is a loop-carried value (ROADMAP S15).

**Prefill** (``latent_prefill_attention``): a chunk of C queries at
positions ``start .. start + C - 1`` over T expanded keys, flash style
(``flash_attention.py``'s forward: a running max, sum and accumulator in
VMEM over the key blocks, no scores in HBM), with what that kernel does
not take: fewer queries than keys under a causal mask that starts at a
traced ``start`` (scalar prefetch: key blocks past a query block's last
position are neither computed nor fetched, so a chunk's work is the
context so far, not the bucket's width); a head's scores in two
products, ``q_nope . k_nope`` (128 wide, per head) and ``q_pe . kpe``
with ONE rotary key block for all heads, so the per-head keys are never
concatenated with a broadcast ``kpe``; values narrower than the scores'
contraction. The rotary parts come 128 wide, zeros behind the 64 (a
cache cell's ``[kpe; zeros]`` as it lies): the scores' contraction is
256 where the arithmetic needs 192, a third more of the score product
and a fifth more of the kernel's operations, all tile-aligned. Keys and
values come a KV GROUP, ``[G, T, .]``: where the model shares an expanded
group between ``H / G`` query heads (``models/motif.py``: 16 groups under
80 heads, so a fifth of the per-head expansion) the heads of a group are
neighbours and a head's blocks are its group's; with a key and value a
head (``G == H``) the index maps are the plain ones.

**The expansion** (``latent_expand``, PR 66): the keys and values that
kernel reads, ``c W_uk`` and ``c W_uv`` a group, made from a table's
cells as far as the chunk attends and no further. The prefill kernel's
index maps stop at the key block that holds the chunk's last position
(`_last_key_block`); the expansion's stop at the same block by the same
rule: a grid over (blocks of groups, key blocks) whose steps past that
block do nothing and repeat its index, so that nothing is fetched for
them and nothing written: the output arrays are as wide as the table and
their blocks past the chunk's end hold whatever the buffers held. No
fill passes over them either (1.07 GB of zeros a layer at 16,384 cells
and 128 heads would cost half of what the bound saves). A step takes a
block of 1,024 cells' latents once for both products of
``block_groups`` groups, whose two matrices stay in VMEM while the key
blocks pass. `keys_expanded` is the same count on the host, for the
serving objects' counters. Until PR 66 two einsums of XLA's turned the
WHOLE table into keys and values in every chunk, the part of the bucket
no token had reached with it (half of the cells expanded in
``pangu-longdoc-16``'s traffic).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas.flash_attention import _LANES, _fit_block
from ray_tpu.ops.pallas.paged_attention import _M_INIT, _MASK

# Pages a block holds, at most: 8 pages of 64 cells are 512 keys, 0.66 MB
# a buffer at 640 lanes, 0.5 MB of float32 scores and probabilities for
# 128 rows. On the chip blocks of 4, 8 and 16 pages read 1.26, 1.24 and
# 1.13 ms, 2 pages 1.76 (module docstring).
_BLOCK_PAGES = 8


def _make_kernel(
    block_pages: int, page_size: int, n_queries: int, max_pages: int,
    v_width: int, scale: float,
):
    """Kernel of one slot a grid step. Refs: scalar prefetch (tables,
    pos), q ``[1, R, W]``, the pool in HBM, out ``[1, R, v_width]``, then
    the double buffer ``[2, N*P, W]``, its DMA semaphores ``[2]`` and, in
    SMEM, the buffer that holds this step's first block."""
    block_keys = block_pages * page_size

    def _kernel(tables_ref, pos_ref, q_ref, pool_hbm, o_ref, buf_ref, sems,
                first_buf_ref):
        b = pl.program_id(0)

        def live_pages(slot):
            return jnp.minimum(
                pl.cdiv(pos_ref[slot] + n_queries, page_size), max_pages
            )

        def for_block_copies(slot, blk, buf, do):
            first = blk * block_pages
            count = jnp.minimum(live_pages(slot) - first, block_pages)

            def one_page(j, carry):
                page = tables_ref[slot, first + j]
                keys = pl.ds(
                    pl.multiple_of(j * page_size, page_size), page_size
                )
                do(pltpu.make_async_copy(
                    pool_hbm.at[page], buf_ref.at[buf, keys, :], sems.at[buf]
                ))
                return carry

            jax.lax.fori_loop(0, count, one_page, None)

        @pl.when(b == 0)
        def _first_step():
            # The buffer is both keys and values: a masked key's
            # probability is exactly 0, and 0 x what the buffer held
            # before any copy (NaN bits, perhaps) is not.
            buf_ref[...] = jnp.zeros_like(buf_ref)
            first_buf_ref[0] = 0
            for_block_copies(0, 0, 0, lambda copy: copy.start())

        n_blocks = pl.cdiv(live_pages(b), block_pages)
        q = q_ref[0]  # [R, W]
        r = q.shape[0]

        def block(i, carry):
            m_prev, l_prev, acc, buf = carry
            last = i + 1 == n_blocks
            next_slot = jnp.where(last, b + 1, b)

            @pl.when(next_slot < pl.num_programs(0))
            def _prefetch():
                for_block_copies(
                    next_slot, jnp.where(last, 0, i + 1), 1 - buf,
                    lambda copy: copy.start(),
                )

            for_block_copies(b, i, buf, lambda copy: copy.wait())
            cells = buf_ref[buf]  # [N*P, W]
            s = jax.lax.dot_general(
                q, cells, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [R, N*P]
            # Row r is query token r % K of head r // K, writing at
            # pos + r % K; key cell c of the block is position i*N*P + c.
            key_pos = i * block_keys + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1
            )
            q_pos = pos_ref[b] + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0
            ) % n_queries
            s = jnp.where(key_pos > q_pos, _MASK, s)
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + p.sum(axis=-1, keepdims=True)
            acc = acc * alpha + jax.lax.dot_general(
                p.astype(cells.dtype), cells[:, :v_width],
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [R, v_width]
            return m_new, l_new, acc, 1 - buf

        _, l, acc, buf = jax.lax.fori_loop(
            0, n_blocks, block,
            (
                jnp.full((r, 1), _M_INIT, jnp.float32),
                jnp.zeros((r, 1), jnp.float32),
                jnp.zeros((r, v_width), jnp.float32),
                first_buf_ref[0],
            ),
        )
        first_buf_ref[0] = buf
        o_ref[0] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)

    return _kernel


@functools.partial(
    jax.jit, static_argnames=("v_width", "scale", "block_pages", "interpret")
)
def latent_paged_attention(
    q: jnp.ndarray,  # [B, K, H, W]: absorbed queries [qa; q_pe], rope applied
    pool: jnp.ndarray,  # [num_pages, P, W]: cells [c; kpe]
    block_tables: jnp.ndarray,  # [B, max_pages] int32 (-1 = unused)
    positions: jnp.ndarray,  # [B] int32: write position of q[:, 0]
    *,
    v_width: int,
    scale: float,
    block_pages: int = _BLOCK_PAGES,
    interpret: bool = False,
) -> jnp.ndarray:
    """Absorbed latent attention over the page pool; returns
    ``[B, K, H, v_width]``: each head's soft-max-weighted sum of the
    cells' first ``v_width`` columns. Query token k of slot b attends
    key positions <= positions[b] + k within the slot's block table; the
    pool is read in place, each slot's live pages once."""
    b, kk, n_heads, width = q.shape
    page_size = pool.shape[1]
    max_pages = block_tables.shape[1]
    while block_pages > max_pages:
        block_pages //= 2
    r = n_heads * kk
    # [B, K, H, W] -> [B, H*K, W]: row h*K + k, so row % K is the query.
    rows = q.transpose(0, 2, 1, 3).reshape(b, r, width)
    out = pl.pallas_call(
        _make_kernel(
            block_pages=block_pages, page_size=page_size, n_queries=kk,
            max_pages=max_pages, v_width=v_width, scale=scale,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, r, width), lambda bi, tab, pos: (bi, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(
                (1, r, v_width), lambda bi, tab, pos: (bi, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((2, block_pages * page_size, width), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, r, v_width), q.dtype),
        # A step starts the next slot's first copies: the slots in order.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
    )(
        jnp.maximum(block_tables, 0).astype(jnp.int32),
        positions.astype(jnp.int32), rows.astype(pool.dtype), pool,
    )
    return out.reshape(b, n_heads, kk, v_width).transpose(0, 2, 1, 3)


# ------------------------------------------------------------------ prefill
# Keys a block of the prefill kernel, and so of the expansion in front
# of it: the two count a chunk's key blocks by one rule.
_PREFILL_BLOCK_KV = 1024


def _last_key_block(start, n_queries, block_kv):
    """The key block that holds position ``start + n_queries - 1``: the
    last one that queries ``start ..``, that many, read under the causal
    mask. Python's integers or traced ones."""
    return (start + n_queries - 1) // block_kv


def keys_expanded(start: int, n_queries: int, n_keys: int,
                  block_kv: int = _PREFILL_BLOCK_KV) -> int:
    """The keys `latent_expand` writes for a chunk of ``n_queries`` at
    ``start`` over a table of ``n_keys``: whole key blocks up to the
    chunk's end, the table at most. On the host, for the counters."""
    block_kv = _fit_block(block_kv, n_keys)
    return min(
        (_last_key_block(start, n_queries, block_kv) + 1) * block_kv, n_keys
    )


def _expand_kernel(
    start_ref, cells_ref, wk_ref, wv_ref, k_ref, v_ref, *, n_queries: int,
    block_kv: int,
):
    """One (group block, key block) step: the block's latents times each
    group's two matrices. Key blocks past the chunk's last do nothing,
    and their index maps repeat that last block's index: nothing is
    fetched for them and the output block, which stays where it is, is
    written back once, when the group block changes."""
    ki = pl.program_id(1)

    @pl.when(ki <= _last_key_block(start_ref[0], n_queries, block_kv))
    def _expand():
        cells = cells_ref[...]  # [block_kv, rank]
        for g in range(wk_ref.shape[0]):
            for w_ref, out_ref in ((wk_ref, k_ref), (wv_ref, v_ref)):
                out_ref[g] = jax.lax.dot(
                    cells, w_ref[g], preferred_element_type=jnp.float32
                ).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("n_queries", "block_kv", "block_groups", "interpret"),
)
def latent_expand(
    cells: jnp.ndarray,  # [T, W]: a table's cells, the latent in front
    w_uk: jnp.ndarray,  # [G, rank, nope]
    w_uv: jnp.ndarray,  # [G, rank, v]
    start: jnp.ndarray,  # [] int32: position of the chunk's first query
    *,
    n_queries: int,
    block_kv: int = _PREFILL_BLOCK_KV,
    # Groups a step: their two matrices stay in VMEM while the key blocks
    # pass, and a step's two outputs are block_groups x 0.25 MB each at
    # 1,024 keys of 128 bf16 numbers.
    block_groups: int = 8,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The latents of a table turned back into keys (without their rotary
    part) and values, ``c W_uk`` and ``c W_uv`` a group, as far as a
    chunk of ``n_queries`` at ``start`` attends: returns ``k_nope [G, T,
    nope]`` and ``v [G, T, v]`` whose key blocks up to the chunk's last
    (`_last_key_block`, the rule of `latent_prefill_attention`'s index
    maps at the same ``block_kv``) hold the two products and whose
    blocks past it are NEVER WRITTEN: what they hold is whatever the
    buffers held, and the kernel behind reads none of it. Nothing passes
    over the dead part, to fill it either: the work is the context so
    far, not the bucket's width."""
    t = cells.shape[0]
    groups, rank, nope = w_uk.shape
    v_dim = w_uv.shape[-1]
    block_kv = _fit_block(block_kv, t)
    block_groups = _fit_block(block_groups, groups)
    dt = w_uk.dtype
    # A step's blocks, each in two buffers: the latents, the groups' two
    # matrices, the two outputs.
    moved = jnp.dtype(dt).itemsize * (
        block_kv * rank + block_groups * (rank + block_kv) * (nope + v_dim)
    )

    def live(ki, start):
        return jnp.minimum(ki, _last_key_block(start[0], n_queries, block_kv))

    return pl.pallas_call(
        functools.partial(
            _expand_kernel, n_queries=n_queries, block_kv=block_kv
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(groups // block_groups, t // block_kv),
            in_specs=[
                pl.BlockSpec((block_kv, rank), lambda gi, ki, s: (live(ki, s), 0)),
                pl.BlockSpec((block_groups, rank, nope), lambda gi, ki, s: (gi, 0, 0)),
                pl.BlockSpec((block_groups, rank, v_dim), lambda gi, ki, s: (gi, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec(
                    (block_groups, block_kv, nope),
                    lambda gi, ki, s: (gi, live(ki, s), 0),
                ),
                pl.BlockSpec(
                    (block_groups, block_kv, v_dim),
                    lambda gi, ki, s: (gi, live(ki, s), 0),
                ),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((groups, t, nope), dt),
            jax.ShapeDtypeStruct((groups, t, v_dim), dt),
        ],
        # An output block is revisited by the steps past the chunk's end:
        # the key blocks in order.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=2 * moved + 8 * 1024 * 1024,
        ),
        interpret=interpret,
    )(jnp.reshape(start, (1,)).astype(jnp.int32), cells.astype(dt), w_uk, w_uv)


def _prefill_kernel(
    start_ref, qn_ref, qp_ref, kn_ref, kp_ref, v_ref, o_ref, m_ref, l_ref,
    acc_ref, *, block_q: int, block_kv: int, num_kv: int,
):
    """One (head, query block, key block) step; the queries are
    pre-scaled. Key blocks wholly past the query block's last position
    are skipped (their index maps repeat the last block needed, so they
    are not fetched either), blocks the diagonal crosses are masked, the
    rest are not."""
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _M_INIT)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lo = start_ref[0] + qi * block_q
    k_lo = ki * block_kv

    def _accumulate(masked: bool):
        contract_last = (((1,), (1,)), ((), ()))
        s = jax.lax.dot_general(
            qn_ref[0], kn_ref[0], contract_last,
            preferred_element_type=jnp.float32,
        ) + jax.lax.dot_general(
            qp_ref[0], kp_ref[...], contract_last,
            preferred_element_type=jnp.float32,
        )  # [block_q, block_kv]
        if masked:
            q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(k_pos > q_pos, _MASK, s)
        m_prev, l_prev = m_ref[:, 0], l_ref[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot(
            p.astype(v_ref.dtype), v_ref[0],
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    k_hi = k_lo + block_kv - 1
    crossed = jnp.logical_and(k_lo <= q_lo + block_q - 1, k_hi > q_lo)

    @pl.when(crossed)
    def _masked():
        _accumulate(True)

    @pl.when(k_hi <= q_lo)
    def _unmasked():
        _accumulate(False)

    @pl.when(ki == num_kv - 1)
    def _finalize():
        # Every query sees key 0: the sum is never zero.
        o_ref[0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "block_q", "block_kv", "interpret")
)
def latent_prefill_attention(
    q_nope: jnp.ndarray,  # [H, C, nope]
    q_pe: jnp.ndarray,  # [H, C, R]: rope applied, zeros behind the rotary part
    k_nope: jnp.ndarray,  # [G, T, nope]: head h reads group h // (H / G)
    kpe: jnp.ndarray,  # [T, R]: one rotary key a token, for all heads
    v: jnp.ndarray,  # [G, T, v]
    start: jnp.ndarray,  # [] int32: position of query 0; keys start at 0
    *,
    scale: float,
    # On a v5e, 2,048 queries at 6,144 over 8,192 keys, 128 heads (my
    # chip run, PR 33): blocks of 512 x 512 take 20.5 ms, 1,024 x 512
    # 20.5, 512 x 1,024 13.3, 1,024 x 1,024 11.8 (52% of the bf16 peak by
    # the operations the arithmetic needs); the XLA loop took 84.9.
    block_q: int = 1024,
    block_kv: int = _PREFILL_BLOCK_KV,
    interpret: bool = False,
) -> jnp.ndarray:
    """Causal attention of C queries at ``start ..`` over the keys at
    ``0 .. T - 1`` (query i sees keys <= start + i); returns [H, C, v]."""
    h, c, nope = q_nope.shape
    t, r = kpe.shape
    n_rep = h // k_nope.shape[0]
    # A key and a value a head: the index maps as they always were.
    group = (lambda hi: hi) if n_rep == 1 else (lambda hi: hi // n_rep)
    v_dim = v.shape[-1]
    block_q, block_kv = _fit_block(block_q, c), _fit_block(block_kv, t)
    num_q, num_kv = c // block_q, t // block_kv
    dt = k_nope.dtype
    q_nope = (q_nope.astype(jnp.float32) * scale).astype(dt)
    q_pe = (q_pe.astype(jnp.float32) * scale).astype(dt)

    def last_needed(qi, ki, start):
        # The last key block a query block reads: steps past it repeat
        # its index, and a block whose index repeats is not fetched.
        return jnp.minimum(
            ki, _last_key_block(start[0], (qi + 1) * block_q, block_kv)
        )

    out = pl.pallas_call(
        functools.partial(
            _prefill_kernel, block_q=block_q, block_kv=block_kv,
            num_kv=num_kv,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(h, num_q, num_kv),
            in_specs=[
                pl.BlockSpec((1, block_q, nope), lambda hi, qi, ki, s: (hi, qi, 0)),
                pl.BlockSpec((1, block_q, r), lambda hi, qi, ki, s: (hi, qi, 0)),
                pl.BlockSpec(
                    (1, block_kv, nope),
                    lambda hi, qi, ki, s: (group(hi), last_needed(qi, ki, s), 0),
                ),
                pl.BlockSpec(
                    (block_kv, r),
                    lambda hi, qi, ki, s: (last_needed(qi, ki, s), 0),
                ),
                pl.BlockSpec(
                    (1, block_kv, v_dim),
                    lambda hi, qi, ki, s: (group(hi), last_needed(qi, ki, s), 0),
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, block_q, v_dim), lambda hi, qi, ki, s: (hi, qi, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((block_q, _LANES), jnp.float32),  # running max
                pltpu.VMEM((block_q, _LANES), jnp.float32),  # running sum
                pltpu.VMEM((block_q, v_dim), jnp.float32),  # accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((h, c, v_dim), dt),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(
        jnp.reshape(start, (1,)).astype(jnp.int32), q_nope, q_pe,
        k_nope, kpe.astype(dt), v,
    )
    return out
