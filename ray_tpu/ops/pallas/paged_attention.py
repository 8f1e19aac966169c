"""Paged decode attention as a Pallas TPU kernel.

The XLA fallback in ``llm/paged_kv.py`` gathers every slot's full page
window out of the pool (``jnp.take``) and then repeats KV to all query
heads — per step it moves B x window x n_heads x Dh bytes of HBM
regardless of each request's true length. This kernel removes both
factors:

- **Pages are read in place.** The grid is (B, max_pages) and the K/V
  BlockSpec index maps use the scalar-prefetched block table to point
  each grid step at the physical page — no gathered copy of the window
  ever exists in HBM.
- **GQA-aware blocking.** Queries are laid out [B, Hkv, n_rep*K, Dh] so
  each page's K/V block ([P, Hkv, Dh]) is multiplied once per KV head
  against its whole query group — KV is never repeated to n_heads.
  Traffic scales with n_kv_heads (8 for llama-8B), not n_heads (32).
- **Per-slot length early-exit.** Pages past a slot's true length are
  clamped by the index map to the slot's LAST page: Pallas skips the
  DMA when consecutive grid steps map to the same block, and pl.when
  skips the compute, so a 100-token request in a 4096-token-wide table
  pays for one page, not 32.

Numerics follow the flash kernel (online softmax with finite mask
values, fp32 accumulation); outputs match the XLA gather path to fp
tolerance, and greedy token streams are identical (gated by tests).

The pool layout is HEAD-major ([pages, Hkv, P, Dh]) and, as for every
Mosaic operand, row-major in memory: each KV head's page tile is a
contiguous slice, measured ~40% faster than page-major for the kernel.
THIS KERNEL FIXES THE POOL'S LAYOUT for the program it is in: whatever
else touches the pool there must leave it row-major, or XLA re-lays the
whole pool out before every call. So the decode program writes its
cells with ``kv_cell_write.write_kv_cells`` (a Mosaic call, the pool
aliased to its result) and not with an XLA scatter, and carries the
pool through its layer loop (``llm/paged_kv.py _scan_layers``): the
``k_pool`` / ``v_pool`` here are that loop's carry, all layers' pages in
one flat view, with the layer's page base already in ``block_tables``.
NOTE the honest caveat: the same round also rewrote
the XLA gather fallback (einsum-folded, GQA-grouped, no repeat) which
brought IT from 17.4 ms to ~4.6 ms at 32/8 heads — at this window
size the kernel's remaining edge is 1.1-1.3x, and its structural
advantage (no materialized gathered window) grows with table width.
Grouping multiple pages per grid step measured SLOWER (see
pages_per_step below).

The reference has no paged attention of its own — ray.llm buys it from
vLLM (reference: python/ray/llm/_internal/serve/deployments/llm/vllm/
vllm_models.py:234, engine_kwargs pass-through); this is the TPU-native
equivalent of vLLM's paged_attention kernel.
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
_LANES = 128


def _make_kernel(
    group: int, page_size: int, n_queries: int, scale: float
):
    """Kernel over GROUPS of ``group`` pages per grid step: fewer,
    fatter steps amortize per-step overhead and let Pallas issue the
    group's page DMAs together. Refs: scalar prefetch (tables, lastp,
    pos), q, group x k pages, group x v pages, out, then m/l/acc
    scratch."""

    def _kernel(tables_ref, lastp_ref, pos_ref, q_ref, *rest):
        k_refs = rest[:group]  # each [1, Hkv, P, Dh]
        v_refs = rest[group: 2 * group]
        o_ref = rest[2 * group]  # [1, Hkv, R, Dh]
        m_ref, l_ref, acc_ref = rest[2 * group + 1:]
        b = pl.program_id(0)
        i = pl.program_id(1)

        @pl.when(i == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, _M_INIT)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        n_kv = q_ref.shape[1]
        for j in range(group):
            # Global page index of this group member; members past the
            # slot's last page skip compute (their block index was
            # clamped, so no DMA happened either).
            ip = i * group + j

            @pl.when(ip <= lastp_ref[b])
            def _accumulate(j=j, ip=ip):
                k_ref, v_ref = k_refs[j], v_refs[j]
                # Static unrolled loop over KV heads: Mosaic wants
                # plain 2D MXU matmuls, and the head-major layout makes
                # each head's [P, Dh] tile a contiguous slice. Each
                # group's K/V tile is touched once for all n_rep * K
                # query rows — KV is never repeated across the group.
                for g in range(n_kv):
                    s = jax.lax.dot_general(
                        q_ref[0, g], k_ref[0, g],
                        dimension_numbers=(((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    ) * scale  # [R, P]
                    # Causal / length mask: key cell c lives at global
                    # position ip*P + c; query row r is query token
                    # r % K writing at pos + r % K. (Stale cells beyond
                    # the frontier are masked; cells behind it are
                    # valid by the scatter-before-gather invariant
                    # shared with the XLA path.)
                    key_pos = ip * page_size + jax.lax.broadcasted_iota(
                        jnp.int32, s.shape, 1
                    )
                    q_pos = pos_ref[b] + jax.lax.broadcasted_iota(
                        jnp.int32, s.shape, 0
                    ) % n_queries
                    s = jnp.where(key_pos > q_pos, _MASK, s)

                    m_prev = m_ref[g, :, 0]  # [R]
                    l_prev = l_ref[g, :, 0]
                    m_new = jnp.maximum(m_prev, s.max(axis=-1))
                    p = jnp.exp(s - m_new[:, None])  # masked -> 0
                    alpha = jnp.exp(m_prev - m_new)
                    l_ref[g] = jnp.broadcast_to(
                        (alpha * l_prev + p.sum(axis=-1))[:, None],
                        l_ref.shape[1:],
                    )
                    m_ref[g] = jnp.broadcast_to(
                        m_new[:, None], m_ref.shape[1:]
                    )
                    acc_ref[g] = acc_ref[g] * alpha[:, None] + (
                        jax.lax.dot_general(
                            p.astype(v_ref.dtype), v_ref[0, g],
                            dimension_numbers=(((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32,
                        )
                    )

        @pl.when(i == pl.num_programs(1) - 1)
        def _finalize():
            l = l_ref[:, :, 0]
            denom = jnp.where(l == 0.0, 1.0, l)
            o_ref[0] = (
                acc_ref[...] / denom[:, :, None]
            ).astype(o_ref.dtype)

    return _kernel


@functools.partial(
    jax.jit, static_argnames=("n_kv_heads", "interpret", "pages_per_step")
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
    pages_per_step: int = 1,
) -> jnp.ndarray:
    """Decode/verify attention over the page pool; returns [B, K, H, Dh].

    Query token k of slot b attends to key positions <= positions[b]+k
    within the slot's block table (the K=1 case is plain decode). The
    pool is read page-by-page in place — see module docstring.
    """
    b, kk, n_heads, head_dim = q.shape
    num_pages, hkv, page_size, _ = k_pool.shape
    assert hkv == n_kv_heads
    n_rep = n_heads // n_kv_heads
    r = n_rep * kk
    max_pages = block_tables.shape[1]

    # [B, K, H, Dh] -> [B, Hkv, n_rep*K, Dh]: head h = g*n_rep + h_rep
    # lands in group g, row h_rep*K + k — so row % K is the query index.
    qg = (
        q.transpose(0, 2, 1, 3)
        .reshape(b, n_kv_heads, n_rep, kk, head_dim)
        .reshape(b, n_kv_heads, r, head_dim)
    )
    tables = jnp.maximum(block_tables, 0).astype(jnp.int32)
    lastp = jnp.clip(
        (positions + kk - 1) // page_size, 0, max_pages - 1
    ).astype(jnp.int32)
    # pages_per_step > 1 loads a GROUP of pages per grid step. Measured
    # on v5e at batch 64: G=1 3.4 ms, G=4 5.0 ms, G=8 3.5 ms — the
    # extra per-spec double buffers cost more VMEM/pipelining than the
    # step amortization saves, so 1 is the default; the knob stays for
    # other table-width/page-size regimes.
    group = pages_per_step
    while max_pages % group:
        group //= 2  # table widths are powers of two in practice
    group = max(group, 1)

    def page_spec(j):
        # Group member j of grid step i holds page i*group + j, clamped
        # to the slot's last live page: steps past it re-map to the
        # same block index and Pallas elides the repeated DMA, so the
        # table's dead width costs no HBM traffic.
        return pl.BlockSpec(
            (1, n_kv_heads, page_size, head_dim),
            lambda bi, i, tab, lp, pos, j=j: (
                tab[bi, jnp.minimum(i * group + j, lp[bi])], 0, 0, 0,
            ),
        )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, max_pages // group),
        in_specs=[
            pl.BlockSpec(
                (1, n_kv_heads, r, head_dim),
                lambda bi, i, tab, lp, pos: (bi, 0, 0, 0),
            ),
            *[page_spec(j) for j in range(group)],  # K pages
            *[page_spec(j) for j in range(group)],  # V pages
        ],
        out_specs=pl.BlockSpec(
            (1, n_kv_heads, r, head_dim),
            lambda bi, i, tab, lp, pos: (bi, 0, 0, 0),
        ),
        scratch_shapes=[
            pltpu.VMEM((n_kv_heads, r, _LANES), jnp.float32),
            pltpu.VMEM((n_kv_heads, r, _LANES), jnp.float32),
            pltpu.VMEM((n_kv_heads, r, head_dim), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        _make_kernel(
            group=group,
            page_size=page_size,
            n_queries=kk,
            scale=head_dim**-0.5,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (b, n_kv_heads, r, head_dim), q.dtype
        ),
        interpret=interpret,
    )(
        tables, lastp, positions.astype(jnp.int32), qg,
        *([k_pool] * group), *([v_pool] * group),
    )
    # [B, Hkv, n_rep*K, Dh] -> [B, K, H, Dh]
    return (
        out.reshape(b, n_kv_heads, n_rep, kk, head_dim)
        .transpose(0, 3, 1, 2, 4)
        .reshape(b, kk, n_heads, head_dim)
    )
