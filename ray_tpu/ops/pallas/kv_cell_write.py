"""The decode/verify K/V cell write as a Pallas TPU kernel: the page pool
is aliased to the result and only the written pages move.

Why a kernel for 32 cells of 2 KB: the layout. ``paged_attention`` takes
the pool row-major (``[page, head, cell, dim]``), as every Mosaic operand
is. XLA's own write of one cell per page, ``pool.at[pages, :, offs,
:].set(new)``, is a scatter for which XLA picks ``[page, cell, head,
dim]`` (the written window contiguous), so a program that scatters with
XLA and attends with the kernel re-lays the whole pool out twice per
layer, for K and for V (with the layer loop's restacking, 58% of
``chat-open``'s device time: PERF.md §6, PR 28). This write keeps the
kernel's layout, so the pool is one buffer in one layout from the
program's argument to its result.

One grid step per written cell. The cell's page is the block (index from
the scalar-prefetched page ids), fetched, patched at the cell's row and
written back: two page moves per cell and pool, nothing else.

**What a caller must hold to.** Cells are written in order, and a page
may be written more than once in one call only on CONSECUTIVE steps: the
block then stays resident (Pallas neither fetches it again nor writes it
back between the steps), and the kernel patches the resident block
instead of the fetched one, which lacks the step before's cell. That is
the case of a slot's K drafts in one page. A page that comes back after
another page was written races its own write-back: only the dump page
(page 0 of a layer: inactive slots and positions past the window, never
attended, any content is right) may do that. ``paged_verify`` holds to
it: slots own their pages, a slot's cells are consecutive, and a shared
prefix page is full, so no decode cell lands in one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(
    pages_ref, offs_ref, k_new_ref, v_new_ref, k_in_ref, v_in_ref,
    k_out_ref, v_out_ref,
):
    i = pl.program_id(0)
    resident = jnp.logical_and(
        i > 0, pages_ref[i] == pages_ref[jnp.maximum(i - 1, 0)]
    )
    # [Hkv, P, Dh]: True on the written cell's row of every head.
    hit = (
        jax.lax.broadcasted_iota(jnp.int32, k_out_ref.shape[1:], 1)
        == offs_ref[i]
    )
    for new_ref, in_ref, out_ref in (
        (k_new_ref, k_in_ref, k_out_ref),
        (v_new_ref, v_in_ref, v_out_ref),
    ):
        new = new_ref[0][:, None, :]  # [Hkv, 1, Dh]

        @pl.when(jnp.logical_not(resident))
        def _fetched(new=new, in_ref=in_ref, out_ref=out_ref):
            out_ref[0] = jnp.where(hit, new, in_ref[0])

        @pl.when(resident)
        def _resident(new=new, out_ref=out_ref):
            out_ref[0] = jnp.where(hit, new, out_ref[0])


@functools.partial(jax.jit, static_argnames=("interpret",))
def write_kv_cells(
    k_pool: jnp.ndarray,  # [num_pages, Hkv, P, Dh] (head-major)
    v_pool: jnp.ndarray,  # [num_pages, Hkv, P, Dh]
    k_new: jnp.ndarray,  # [N, Hkv, Dh]
    v_new: jnp.ndarray,  # [N, Hkv, Dh]
    pages: jnp.ndarray,  # [N] int32: physical page of each cell
    offsets: jnp.ndarray,  # [N] int32: row of each cell in its page
    *,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``pool[pages[i], :, offsets[i], :] = new[i]`` for K and V, in
    place; returns the two pools. See the module docstring for which
    pages may repeat."""
    n, hkv, head_dim = k_new.shape
    page_size = k_pool.shape[2]
    cell_spec = pl.BlockSpec(
        (1, hkv, head_dim), lambda i, pages, offs: (i, 0, 0)
    )
    page_spec = pl.BlockSpec(
        (1, hkv, page_size, head_dim),
        lambda i, pages, offs: (pages[i], 0, 0, 0),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n,),
            in_specs=[cell_spec, cell_spec, page_spec, page_spec],
            out_specs=[page_spec, page_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
            jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
        ],
        # Operands count the two scalar-prefetch arrays: the pools are
        # inputs 4 and 5, aliased to outputs 0 and 1.
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
    )(
        pages.astype(jnp.int32), offsets.astype(jnp.int32),
        k_new.astype(k_pool.dtype), v_new.astype(v_pool.dtype),
        k_pool, v_pool,
    )
