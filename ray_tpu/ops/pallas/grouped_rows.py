"""The sorted expert form's grouped matmuls as a Pallas TPU kernel: each
group of rows times its own expert's matrix, an expert's weights read
from HBM once however its rows fall into tiles.

``models/moe.py _experts_on_pairs_here`` leaves the rows of the pairs
computed here in sorted (expert) order: expert ``g``'s rows are the
``sizes[g]`` after those of the experts before it. The compiler's
grouped matmul (``jax.lax.ragged_dot``) visits every (group, row tile)
pair that holds a row, pays a whole tile of arithmetic a visit and reads
the expert's matrix again a visit, so its row tile is tied to its weight
read: a tile small enough for a 40-row group reads a 284-row group's
6 MB several times, and one large enough for that group multiplies
mostly masked rows for the other (PERF.md section 6, PRs 41, 51, 56).
This kernel unties them:

- **The weights are a stream of their own.** The stacks stay in HBM
  (``pl.ANY``); the groups that hold a row, in order, are the work list
  (scalar prefetch), and each one's matrices are copied whole into one
  of `_BUFFERS` VMEM buffers while the group before it is multiplied:
  the copy of group ``l + 1`` starts when group ``l``'s arithmetic
  does. A group is copied once, whatever the number of its row tiles
  and whichever row blocks it spans: the buffer outlives the grid step,
  so the part of a group in the next block finds its weights where they
  are.
- **The rows are a grid over blocks of `_ROW_BLOCK`**, fetched and
  written back by Pallas's own pipeline under the arithmetic. Inside a
  block a loop walks the groups that have rows in it and, in a group,
  tiles of ``tile`` rows that start at the group's first row rounded
  down to the dtype's sublane packing: a group smaller than a tile is
  one tile, never two. A tile's rows outside the group keep what the
  output held (a select at the store), so a tile that holds three
  groups is written by each in turn.
- **Rows at and past the groups' sum are not visited**: the blocks past
  the last live one name the block that is already there, so nothing is
  copied for them, and the loops have trip counts, not masks. Whatever
  those rows hold, NaN included, changes nothing; what the output holds
  there is not defined.
- **bf16 (the operands' dtype) in, float32 sums over the whole
  contraction, one rounding at the store**, as ``ragged_dot``; with two
  matrices the step is a gated expert's ``silu(x Wg) * (x Wu)`` on the
  two float32 products (``act="swiglu"``), with one and ``act="relu2"``
  ``relu(x W)^2``; ``act="polynorm"`` is ``PolyNorm(x Wg) * (x Wu)``
  (``models/moe.py poly_norm``), whose three norms run over the
  expert's WHOLE width: a tile's gate product is held whole in float32
  before them, so the stacks go in ONE column pass (Motif-3-Beta's two
  4,096 x 1,280 matrices are 21 MB a group, 42 MB in two buffers: inside
  the budget; a wider expert would be refused, not split), and the
  group's four numbers are scalars in SMEM.
- **The tiles follow from the shapes.** The row tile (`_tile_rows`):
  the groups' mean size rounded up to the packing, at most
  `_MAX_TILE_ROWS`. The column tile (`_column_tile`): the widest
  multiple of 128 lanes that divides the output's width and whose
  buffers fit `_WEIGHT_VMEM_BYTES`; a stack too wide for that
  (openPangu's 7,680 x 2,048 twice) is taken in column passes,
  outermost, each a stream of its own.

**No backward pass.** The form this serves is a serving program's,
forward only; the train step's form keeps ``jax.lax.ragged_dot``
(``_experts_on_sorted_pairs``). Differentiating through this raises.

Models read, not imported: jax/experimental/pallas/ops/tpu/megablox
(``gmm``: the groups' ends masked at the store, a work list of (group,
tile) visits whose weight block follows the grid),
``ops/pallas/paged_attention.py`` (a double buffer filled across grid
steps) and ``ops/pallas/expert_rows.py`` (a tile sized to a budget).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas.expert_rows import POLY_SPEC, poly_norm_rows

# What the buffers of a group's matrices may take of VMEM (a v5e
# core has 128 MiB). Granite's and Laguna's gate and up matrices (2 x
# 6.3 MB) fit whole twice; openPangu's (2 x 31.5 MB) go in four column
# passes of 512. The compile for a described v5e
# (tests/test_tpu_aot_compile.py) is the proof that the served shapes fit.
_WEIGHT_VMEM_BYTES = 48 * 1024 * 1024
# Buffers of a group's matrices: one being multiplied, the others on
# their way. A third (under a budget of 72 MiB) read 2 / 3 / 8 / 1% less
# alone at granite's / Laguna's / Qwen3-Next's / openPangu's widths and
# was not taken to the cells (PERF.md section 6, PR 56).
_BUFFERS = 2
# Rows of one streamed block.
_ROW_BLOCK = 512
# Rows of a tile at most. A group's masked rows are half a tile on
# average, and under some 32 rows a tile costs the matmul unit a pass of
# the weights whatever its rows: on a v5e one layer-chunk's three
# matmuls at granite's widths (284-row groups, the one served family
# the arithmetic bounds) read 2.46 / 1.42 / 1.35 / 1.39 / 1.41 / 1.44 /
# 1.56 / 1.99 ms at 16 / 32 / 48 / 64 / 80 / 96 / 128 / 256 rows; the
# other three families, bound by their weights' bytes, read the same
# from 32 to 128 rows (PERF.md section 6, PR 56).
_MAX_TILE_ROWS = 64
_LANES = 128


def _packing(dtype) -> int:
    """Rows of one packed tile of ``dtype``: 8 sublanes of 32 bits."""
    return 32 // jnp.dtype(dtype).itemsize


def _tile_rows(group_rows: int, pack: int, block: int) -> int:
    """Rows of a tile: the groups' mean size rounded up to the packing,
    at most `_MAX_TILE_ROWS` and the block."""
    want = -(-max(group_rows, 1) // pack) * pack
    return min(want, _MAX_TILE_ROWS, block)


def _column_tile(k: int, n: int, n_matrices: int, itemsize: int) -> int:
    """Columns of the output a pass takes: the widest multiple of 128
    lanes that divides ``n`` and whose `_BUFFERS` buffers of
    ``n_matrices`` ``[k, tile]`` matrices fit `_WEIGHT_VMEM_BYTES`; all
    of ``n`` where it is no multiple of 128."""
    if n % _LANES:
        return n
    units = n // _LANES
    fit = _WEIGHT_VMEM_BYTES // (
        _BUFFERS * n_matrices * k * _LANES * itemsize
    )
    return _LANES * max(
        u for u in range(1, units + 1) if units % u == 0 and u <= max(fit, 1)
    )


def _make_kernel(
    act: str | None, n_w: int, tile: int, pack: int, passes: int, depth: int,
    limit: float | None = None, eps: float | None = None,
):
    """Kernel of one (column pass, row block) a grid step. Refs: scalar
    prefetch (each block's first live group and how many it holds; the
    live groups' ids, first rows and ends; their count and the last live
    block), the rows, the groups' PolyNorm numbers (``act="polynorm"``
    alone), the stacks in HBM, out, the stacks' buffers
    ``[depth, k, width]`` and their DMA semaphores ``[matrix, buffer]``."""

    def _kernel(first_ref, held_ref, ids_ref, starts_ref, ends_ref, meta_ref,
                rows_ref, *refs):
        if act == "polynorm":
            poly_ref, *refs = refs
        stacks, o_ref = refs[:n_w], refs[n_w]
        buffers, sems = refs[n_w + 1: 2 * n_w + 1], refs[2 * n_w + 1]
        c, i = pl.program_id(0), pl.program_id(1)
        block, width = o_ref.shape
        n_live = meta_ref[0]
        if passes == 1:
            columns = slice(None)
        else:
            columns = pl.ds(pl.multiple_of(c * width, _LANES), width)

        def for_copies(l, do):
            """``do`` (start or wait) the copy of each matrix of live
            group ``l`` into its buffer."""
            for k in range(n_w):
                do(pltpu.make_async_copy(
                    stacks[k].at[ids_ref[l], :, columns],
                    buffers[k].at[l % depth], sems.at[k, l % depth],
                ))

        for ahead in range(depth - 1):

            @pl.when((i == 0) & (ahead < n_live))
            def _first_block():
                for_copies(ahead, lambda copy: copy.start())

        base = i * block

        def group(l, carry):
            # A group is met first in the block its first row lies in:
            # its weights are on their way; the next group's start now,
            # under this group's arithmetic. In a later block they are
            # where that block left them.
            @pl.when(starts_ref[l] >= base)
            def _first_met():
                for_copies(l, lambda copy: copy.wait())

                @pl.when(l + depth - 1 < n_live)
                def _group_ahead():
                    for_copies(l + depth - 1, lambda copy: copy.start())

            lo = jnp.maximum(starts_ref[l] - base, 0)
            hi = jnp.minimum(ends_ref[l] - base, block)
            first = lo // pack * pack

            def one_tile(t, carry):
                # The block's last tile may reach back into rows the
                # tile before it took: the same values again.
                at = pl.multiple_of(
                    jnp.minimum(first + t * tile, block - tile), pack
                )
                here = pl.ds(at, tile)
                x = rows_ref[here, :]
                y = jnp.dot(
                    x, buffers[-1][l % depth],
                    preferred_element_type=jnp.float32,
                )
                if act == "swiglu":
                    gate = jnp.dot(
                        x, buffers[0][l % depth],
                        preferred_element_type=jnp.float32,
                    )
                    if limit is not None:  # a clamped SwiGLU
                        gate = jnp.minimum(gate, limit)
                        y = jnp.clip(y, -limit, limit)
                    y = jax.nn.silu(gate) * y
                elif act == "relu2":
                    y = jnp.square(jnp.maximum(y, 0.0))
                elif act == "polynorm":
                    gate = jnp.dot(
                        x, buffers[0][l % depth],
                        preferred_element_type=jnp.float32,
                    )
                    y = poly_norm_rows(gate, poly_ref, ids_ref[l], eps) * y
                row = at + jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
                o_ref[here, :] = jnp.where(
                    (row >= lo) & (row < hi), y.astype(o_ref.dtype),
                    o_ref[here, :],
                )
                return carry

            jax.lax.fori_loop(0, pl.cdiv(hi - first, tile), one_tile, 0)
            return carry

        jax.lax.fori_loop(
            first_ref[i], first_ref[i] + held_ref[i], group, 0
        )

    return _kernel


def _work_list(sizes, blocks: int, block: int):
    """The kernel's scalar operands from the groups' sizes: the groups
    that hold a row packed to the front in order (ids, first rows, ends),
    each row block's first such group and how many have rows in it, and
    (count of live groups, last live block)."""
    sizes = sizes.astype(jnp.int32)
    every_end = jnp.cumsum(sizes)
    live = sizes > 0
    n_live = live.sum().astype(jnp.int32)
    ids = jnp.argsort(~live, stable=True).astype(jnp.int32)
    dead = jnp.arange(sizes.shape[0]) >= n_live
    never = jnp.iinfo(jnp.int32).max
    starts = jnp.where(dead, never, (every_end - sizes)[ids])
    ends = jnp.where(dead, never, every_end[ids])
    lows = jnp.arange(blocks, dtype=jnp.int32) * block
    # Live groups' ends and first rows both rise strictly.
    first = jnp.searchsorted(ends, lows, side="right").astype(jnp.int32)
    held = jnp.searchsorted(starts, lows + block, side="left") - first
    meta = jnp.stack([n_live, jnp.maximum((every_end[-1] - 1) // block, 0)])
    return first, held.astype(jnp.int32), ids, starts, ends, meta


@functools.partial(
    jax.jit,
    static_argnames=("act", "group_rows", "interpret", "limit", "eps"),
)
def _grouped_rows(rows, weights, sizes, act, group_rows, interpret,
                  limit=None, poly=None, eps=None):
    total, k = rows.shape
    n = weights[0].shape[2]
    n_w = len(weights)
    pack = _packing(rows.dtype)
    # Whole streamed blocks (the served shapes are: thousands of rows);
    # fewer rows are one block, padded to the packing.
    block = min(_ROW_BLOCK, -(-total // pack) * pack)
    pad = -total % block
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    blocks = (total + pad) // block
    tile = _tile_rows(group_rows, pack, block)
    width = _column_tile(k, n, n_w, rows.dtype.itemsize)
    if act == "polynorm" and width != n:
        raise ValueError(
            f"a PolyNorm expert's {n} columns do not fit one pass of "
            f"{width}: its norms run over the whole width"
        )
    numbers = [poly.astype(jnp.float32)] if act == "polynorm" else []
    scalars = _work_list(sizes, blocks, block)

    def row_block(c, i, first, held, ids, starts, ends, meta):
        # Past the live rows: the last live block again, so no copy.
        return jnp.minimum(i, meta[1])

    item = rows.dtype.itemsize
    vmem = (
        _BUFFERS * n_w * k * width * item  # the groups' matrices
        + 2 * block * (k + width) * item  # the streamed rows, out
        + tile * (k * item + (n_w + 2) * max(width, _LANES) * 4)  # a tile's
    )
    out = pl.pallas_call(
        _make_kernel(act, n_w, tile, pack, n // width, _BUFFERS, limit, eps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(n // width, blocks),
            in_specs=[pl.BlockSpec(
                (block, k), lambda c, i, *s: (row_block(c, i, *s), 0)
            )] + [POLY_SPEC] * len(numbers)
            + [pl.BlockSpec(memory_space=pl.ANY)] * n_w,
            out_specs=pl.BlockSpec(
                (block, width), lambda c, i, *s: (row_block(c, i, *s), c)
            ),
            scratch_shapes=[
                pltpu.VMEM((_BUFFERS, k, width), rows.dtype)
                for _ in range(n_w)
            ] + [pltpu.SemaphoreType.DMA((n_w, _BUFFERS))],
        ),
        out_shape=jax.ShapeDtypeStruct((total + pad, n), rows.dtype),
        # One stream of weights through every step: the steps in order.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem + 8 * 1024 * 1024,
        ),
        interpret=interpret,
    )(*scalars, rows, *numbers, *weights)
    return out[:total] if pad else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 8))
def grouped_rows(
    rows: jnp.ndarray,  # [total, k]: the rows in group order
    weights: list,  # one or two stacks [groups, k, n] in `rows`' dtype
    sizes: jnp.ndarray,  # [groups] int32: each group's rows
    act: str | None = None,  # None, "swiglu" / "polynorm" (two stacks) or "relu2"
    group_rows: int = _MAX_TILE_ROWS,  # the groups' mean size, for the tile
    interpret: bool = False,
    limit: float | None = None,  # "swiglu"'s clamp (`moe.clamped_swiglu`)
    poly: jnp.ndarray | None = None,  # "polynorm": [groups, 4], `moe.poly_terms`
    eps: float | None = None,  # of "polynorm"'s three norms
) -> jnp.ndarray:
    """Rows ``[sum(sizes[:g]), sum(sizes[:g + 1]))`` times
    ``weights[-1][g]`` for every group ``g``, [total, n] in ``rows``'
    dtype, summed in float32; with ``act`` the expert's activation of
    that product (and of the rows times ``weights[0][g]``: see the module
    docstring). Rows at and past ``sum(sizes)`` are never read and what
    comes back in their place is not defined. Forward only."""
    return _grouped_rows(
        rows, weights, sizes, act, group_rows, interpret, limit, poly, eps
    )


def _forward(rows, weights, sizes, act, group_rows, interpret, limit, poly,
             eps):
    return _grouped_rows(
        rows, weights, sizes, act, group_rows, interpret, limit, poly, eps
    ), None


def _backward(act, group_rows, interpret, limit, eps, residuals, g):
    raise NotImplementedError(
        "ops/pallas/grouped_rows.py has no backward pass: the sorted "
        "expert form over the pairs computed here is a serving program's; "
        "a train step computes every pair by jax.lax.ragged_dot "
        "(models/moe.py _experts_on_sorted_pairs)"
    )


grouped_rows.defvjp(_forward, _backward)
