"""The held experts on a few rows, as a Pallas TPU kernel that reads
only the experts that got a row.

``models/moe.py``'s every-row form applies every held expert to every
row and weights the results by a ``[n, held]`` matrix of gates that is
zero where a row did not choose the expert. As two batched einsums that
reads every held expert's weights whatever the routes: at a decode
step's 32 rows a third to a half of them met no row, and their 20 MB
each (Nemotron-3-Nano's widths; 94 MB at openPangu's) are read,
multiplied and weighted by zero. This kernel's work list is the touched
experts:

- **A grid over (touched experts, tiles of the expert width).** The ids
  of the experts that got a row come packed to the front of ``ids``
  with their ``count``, through scalar prefetch. The weight operands'
  index maps read ``ids``; past ``count`` they name the block the last
  live step named, so Pallas issues no copy, and ``pl.when`` skips the
  arithmetic. An untouched expert's weights are never fetched (but for
  one tile of expert 0 where NO expert got a row: a pipeline has a
  first block).
- **Up, activation, down and combine are one step.** For expert ``e``
  and tile ``t`` of its width: ``act = f(x @ w_up[e][:, t])`` (relu^2,
  or ``silu(x @ w_gate[e][:, t]) *`` that), ``act @ w_down[e][t, :]``,
  times the expert's column of the gate matrix, added to a float32
  ``[n, d]`` accumulator in VMEM that is written out once. The
  ``[held, n, f]`` and ``[held, n, d]`` intermediates of the einsum
  form do not exist. Operands stay in their dtype (bf16), products
  accumulate in float32, and an expert's output is not rounded to bf16
  before the weighted sum, so results differ from the einsum form in
  the last bits.
- **The rows stay in VMEM** (``[n, d]``, the gate matrix, the
  accumulator and the result), so every weight is read once whatever
  ``n``; inside a step the rows go through the products
  ``_ROW_BLOCK`` at a time, which bounds the step's temporaries.
- **The tile follows from the shapes** (``_width_tile``): the widest
  multiple of 128 lanes that divides the expert width and whose double
  buffers (two or three matrices) fit ``_WEIGHT_VMEM_BYTES``.
- **A PolyNorm expert takes its whole width in one step** (``poly``,
  ``models/moe.py poly_norm``): its three norms run over the expert's
  width, so the gate product of a row is held whole, in float32, before
  the norms and the down product. At Motif-3-Beta's 4,096 x 1,280 three
  matrices are 31.5 MB an expert, 63 MB twice buffered: over the budget
  the other kinds tile under (they would take 640 lanes there), inside
  the core's 128 MiB. The four numbers an expert are scalars, a row of a
  ``[held, 4]`` float32 array in SMEM.

The stacks are read where they lie, row-major as every Mosaic operand:
``[held, d, f]`` and ``[held, f, d]`` with ``f`` a multiple of 128, the
layout ``ops/pallas/grouped_rows.py`` takes them in too (the sorted
form of larger calls), so neither form copies a stack
(tests/test_tpu_aot_compile.py).

**No backward pass.** Nothing differentiates the every-row form: it
serves decode steps and short prefill chunks, and training takes the
sorted form. Differentiating through this raises.

Models read, not imported: jax/experimental/pallas/ops/tpu/megablox
(``gmm``: groups of sorted rows, where this has every row and a gate
matrix) and ``ops/pallas/paged_attention.py`` (a work list from scalar
prefetch).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# What the weight tiles' double buffers may take of VMEM (a v5e core has
# 128 MiB; a step's copies are (2 or 3) x d x tile x 2 B). At
# Nemotron-3-Nano's widths (d 2688, f 1920, two matrices) a tile is then
# the whole width, 20.6 MB a step and each copy contiguous; at
# openPangu's (d 7680, f 2048, three) 512 lanes, 23.6 MB. Measured on a
# v5e at 32 rows with three quarters of the held experts touched
# (PERF.md section 6, PR 34): at 12 / 24 / 48 MiB Nemotron's tiles of
# 384 / 640 / 1920 lanes read 675 / 703 / 734 GB/s (the einsum form
# reads every expert at 685), openPangu's of 128 / 256 / 512 lanes 731 /
# 689 / 701 in one call and 731 / 706 / 736 in another (the einsum form:
# 735); copies split in two to five made no difference. The compile
# for a described v5e (tests/test_tpu_aot_compile.py) is the proof that
# the serving shapes fit.
_WEIGHT_VMEM_BYTES = 48 * 1024 * 1024
# Rows that go through a step's products at once.
_ROW_BLOCK = 256
_LANES = 128
# bf16 rows come in tiles of 16 sublanes: the rows are padded to that.
_ROW_TILE = 16


def _width_tile(d: int, f: int, n_matrices: int, itemsize: int) -> int:
    """Lanes of the expert width a grid step takes: the widest multiple
    of 128 that divides ``f`` and whose double-buffered tiles fit
    ``_WEIGHT_VMEM_BYTES``; all of ``f`` where it is no multiple of 128
    (a block that is the whole dimension needs no alignment)."""
    if f % _LANES:
        return f
    units = f // _LANES
    fit = _WEIGHT_VMEM_BYTES // (2 * n_matrices * d * _LANES * itemsize)
    return _LANES * max(
        u for u in range(1, units + 1) if units % u == 0 and u <= max(fit, 1)
    )


def _make_kernel(gated: bool, n_rows: int, limit: float | None,
                 poly_eps: float | None = None):
    """Kernel of one (touched expert, width tile) a grid step. Refs:
    scalar prefetch (ids, count), the rows, the gate matrix, PolyNorm's
    numbers (where ``poly_eps`` is not None: the gate is then a
    PolyNorm's and the tile the whole width), the weight tiles (gate's
    first where ``gated``), out, the accumulator."""

    def _kernel(ids_ref, count_ref, x_ref, weight_ref, *refs):
        if poly_eps is not None:
            poly_ref, *refs = refs
        *w_gate_ref, w_up_ref, w_down_ref, o_ref, acc_ref = refs
        i, t = pl.program_id(0), pl.program_id(1)

        @pl.when((i == 0) & (t == 0))
        def _first_step():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(i < count_ref[0])
        def _touched():
            expert = ids_ref[i]
            for start in range(0, n_rows, _ROW_BLOCK):
                rows = slice(start, min(start + _ROW_BLOCK, n_rows))
                x = x_ref[rows, :]
                up = jnp.dot(
                    x, w_up_ref[0], preferred_element_type=jnp.float32
                )
                if gated:
                    gate = jnp.dot(
                        x, w_gate_ref[0][0],
                        preferred_element_type=jnp.float32,
                    )
                    if limit is not None:  # a clamped SwiGLU
                        gate = jnp.minimum(gate, limit)
                        up = jnp.clip(up, -limit, limit)
                    if poly_eps is None:
                        act = jax.nn.silu(gate) * up
                    else:
                        act = poly_norm_rows(
                            gate, poly_ref, expert, poly_eps
                        ) * up
                else:
                    act = jnp.square(jnp.maximum(up, 0.0))
                out = jnp.dot(
                    act.astype(x.dtype), w_down_ref[0],
                    preferred_element_type=jnp.float32,
                )  # [rows, d]
                # The expert's column of the gate matrix: zero for a
                # row that did not choose it.
                weight = weight_ref[rows, :]
                lane = jax.lax.broadcasted_iota(jnp.int32, weight.shape, 1)
                gate = jnp.sum(
                    jnp.where(lane == expert, weight, 0.0),
                    axis=1, keepdims=True,
                )
                acc_ref[rows, :] += gate * out

        @pl.when(
            (i == pl.num_programs(0) - 1) & (t == pl.num_programs(1) - 1)
        )
        def _last_step():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)

    return _kernel


def poly_norm_of(z, cube, square, first, bias, eps: float):
    """``cube N(z^3) + square N(z^2) + first N(z) + bias`` with ``N(a) =
    a / sqrt(mean(a^2) + eps)`` over z's WHOLE last axis, z [.., f]
    float32: PolyNorm's arithmetic (arXiv:2411.03884), for the kernels
    here and in ``grouped_rows.py`` (the four numbers scalars) and for
    ``models/moe.py poly_norm`` (arrays that broadcast against z)."""
    def unit(a):
        return a * jax.lax.rsqrt(jnp.mean(a * a, axis=-1, keepdims=True) + eps)

    z2 = z * z
    return cube * unit(z2 * z) + square * unit(z2) + first * unit(z) + bias


def poly_norm_rows(z, poly_ref, expert, eps: float):
    """`poly_norm_of` inside a kernel, with row ``expert`` of ``poly_ref``
    ([held, 4] float32 in SMEM: scalars)."""
    return poly_norm_of(z, *(poly_ref[expert, i] for i in range(4)), eps)


# PolyNorm's numbers as a kernel takes them: whole, in SMEM.
POLY_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


@functools.partial(jax.jit, static_argnames=("interpret", "limit", "eps"))
def _experts_on_rows(x, w_gate, w_up, w_down, weight, ids, count, interpret,
                     limit=None, poly=None, eps=None):
    n, d = x.shape
    held, _, f = w_up.shape
    gated = w_gate is not None
    stacks = ([w_gate] if gated else []) + [w_up, w_down]
    # A PolyNorm's norms need a row's whole width of the gate product.
    tile = f if poly is not None else _width_tile(
        d, f, len(stacks), w_up.dtype.itemsize
    )
    n_tiles = f // tile
    n_rows = -(-n // _ROW_TILE) * _ROW_TILE
    x = jnp.pad(x, ((0, n_rows - n), (0, 0)))
    weight = jnp.pad(weight.astype(jnp.float32), ((0, n_rows - n), (0, 0)))

    def width_tile(i, t, count):
        # Past the touched experts: the last live step's tile again.
        return jnp.where(i < count[0], t, n_tiles - 1)

    resident = lambda shape: pl.BlockSpec(  # noqa: E731
        shape, lambda i, t, ids, count: (0, 0)
    )
    up_spec = pl.BlockSpec(
        (1, d, tile),
        lambda i, t, ids, count: (ids[i], 0, width_tile(i, t, count)),
    )
    down_spec = pl.BlockSpec(
        (1, tile, d),
        lambda i, t, ids, count: (ids[i], width_tile(i, t, count), 0),
    )
    item = x.dtype.itemsize
    row_block = min(n_rows, _ROW_BLOCK)
    vmem = (
        2 * len(stacks) * d * tile * w_up.dtype.itemsize  # weight tiles
        + 2 * n_rows * (2 * d * item + max(held, _LANES) * 4)  # x, out, gates
        + n_rows * d * 4  # accumulator
        # a row block's up / gate / act and its two [rows, d] results
        + row_block * (4 * max(tile, _LANES) + 2 * d) * 4
    )
    numbers = [] if poly is None else [poly.astype(jnp.float32)]
    out = pl.pallas_call(
        _make_kernel(gated, n_rows, limit, None if poly is None else eps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(held, n_tiles),
            in_specs=[resident((n_rows, d)), resident((n_rows, held))]
            + [POLY_SPEC] * len(numbers)
            + [up_spec] * (len(stacks) - 1) + [down_spec],
            out_specs=resident((n_rows, d)),
            scratch_shapes=[pltpu.VMEM((n_rows, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n_rows, d), x.dtype),
        # One accumulator over every step: the steps in order.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem + 8 * 1024 * 1024,
        ),
        interpret=interpret,
    )(ids.astype(jnp.int32), count.astype(jnp.int32).reshape(1), x, weight,
      *numbers, *stacks)
    return out[:n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 10))
def experts_on_rows(
    x: jnp.ndarray,  # [n, d]
    w_gate: jnp.ndarray | None,  # [held, d, f]; None: relu(x w_up)^2
    w_up: jnp.ndarray,  # [held, d, f]
    w_down: jnp.ndarray,  # [held, f, d]
    weight: jnp.ndarray,  # [n, held] float32: a row's gate for an expert
    ids: jnp.ndarray,  # [held] int32: the touched experts, packed first
    count: jnp.ndarray,  # [] int32: how many of `ids` are touched
    interpret: bool = False,
    limit: float | None = None,  # a gated expert's clamp (`clamped_swiglu`)
    poly: jnp.ndarray | None = None,  # [held, 4]: `moe.poly_terms`; the
    # gate is then PolyNorm's and not silu's
    eps: float | None = None,  # of PolyNorm's three norms
) -> jnp.ndarray:
    """``sum_e weight[:, e] * expert_e(x)`` over the experts ``ids[:count]``,
    [n, d] in ``x``'s dtype. Every expert with a nonzero column of
    ``weight`` must be among them; ``ids`` past ``count`` must repeat
    ``ids[count - 1]`` (0 where ``count`` is 0), so that those steps
    fetch nothing: see the module docstring. Forward only."""
    return _experts_on_rows(
        x, w_gate, w_up, w_down, weight, ids, count, interpret, limit, poly,
        eps,
    )


def _forward(x, w_gate, w_up, w_down, weight, ids, count, interpret, limit,
             poly, eps):
    return _experts_on_rows(
        x, w_gate, w_up, w_down, weight, ids, count, interpret, limit, poly,
        eps,
    ), None


def _backward(interpret, limit, eps, residuals, g):
    raise NotImplementedError(
        "ops/pallas/expert_rows.py has no backward pass: the every-row "
        "expert form serves decode steps and short prefill chunks; a "
        "train step takes the sorted form (dense_expert_rows = 0)"
    )


experts_on_rows.defvjp(_forward, _backward)
