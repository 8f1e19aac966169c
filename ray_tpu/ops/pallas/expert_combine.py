"""The sorted expert form's combine as a Pallas TPU kernel: each row of
the experts' output, times its float32 gate, added onto its token's
float32 sum.

``models/moe.py _experts_on_pairs_here`` leaves the rows of the pairs
computed here in sorted (expert) order, ``m`` of them, each with the
token it belongs to and its gate. XLA sums them back with a row
scatter-add, which costs 0.27 us a row onto up to 4,096 float32 columns
on a v5e and 5 to 20 times that onto wider or odd widths, whatever the
row holds (PERF.md section 6, PR 41): some hundreds of cycles for the 16
to 60 vectors of a row. This kernel does the same sum where the data
lies:

- **A grid over (column tiles of the width, blocks of rows).** Inside a
  column tile the tokens' sums ``[n, tile]`` are a float32 accumulator
  resident in VMEM; the rows stream through it a block at a time and
  the result is written once, in the rows' dtype, straight from the
  accumulator: the float32 sums never reach HBM.
- **One dynamic-sublane read-add-write a row.** Token and gate of every
  row come through scalar prefetch (SMEM). Rows are loaded a packed
  tile (`_GROUP` = 16 sublanes) at a time and taken out of it
  statically; only the accumulator is indexed at run time:
  ``acc[token] += gate * row``. A row is its dtype's value times a
  float32 gate added in float32, in sorted order, every time: nothing
  is rounded on the way, and a token's rows are added in the order XLA's
  scatter-add took them.
- **Rows at and past ``m`` are not visited.** The loop over a block's
  groups has a trip count, not a mask (the one group that ``m`` cuts
  selects its live rows); the blocks past the last live one name the
  block that is already there, so Pallas issues no copy for them.
  Whatever those rows hold, NaN included, adds nothing.
- **The column tile follows from the shapes** (`_column_tile`): the
  widest multiple of 128 lanes that divides the width and whose
  accumulator fits `_ACC_VMEM_BYTES`. 4,096, 2,048 and 7,680 columns are
  taken as they are: no padding to a power of two, no pieces.

**No backward pass.** The form this serves is a serving program's,
forward only; the train step's form sums by a gather
(``_experts_on_sorted_pairs``). Differentiating through this raises.

Model read, not imported: ``ops/pallas/expert_rows.py`` (a float32
accumulator in VMEM written out once, a tile sized to a budget, a work
list from scalar prefetch).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# What the tokens' float32 sums of one column tile may take of VMEM (a
# v5e core has 128 MiB): at a 2,048-token chunk 2,048 lanes, so granite's
# 4,096 columns are two tiles, Qwen3-Next's 2,048 one and openPangu's
# 7,680 four of 1,920. A row costs its chain (read, add, write at an
# address the row before may have written) more than its vectors, so a
# wide tile is cheaper a row than a narrow one: PERF.md section 6, PR 52,
# has the sweep. The compile for a described v5e
# (tests/test_tpu_aot_compile.py) is the proof that the served shapes fit.
_ACC_VMEM_BYTES = 16 * 1024 * 1024
# Rows of one streamed block.
_ROW_BLOCK = 512
_LANES = 128
# Rows loaded at once: a packed bfloat16 tile's sublanes.
_GROUP = 16


def _column_tile(n: int, d: int) -> int:
    """Columns of the width a grid step takes: the widest multiple of
    128 lanes that divides ``d`` and whose ``[n, tile]`` float32
    accumulator fits `_ACC_VMEM_BYTES`; all of ``d`` where it is no
    multiple of 128 (a block that is the whole dimension needs no
    alignment)."""
    if d % _LANES:
        return d
    units = d // _LANES
    fit = _ACC_VMEM_BYTES // (n * _LANES * 4)
    return _LANES * max(
        u for u in range(1, units + 1) if units % u == 0 and u <= max(fit, 1)
    )


def _kernel(token_ref, gate_ref, m_ref, rows_ref, out_ref, acc_ref):
    """One (column tile, row block) a grid step. Refs: scalar prefetch
    (token, gate, m), the rows, out, the accumulator."""
    row_block = rows_ref.shape[0]
    b = pl.program_id(1)

    @pl.when(b == 0)
    def _first_block():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    base = b * row_block
    live = jnp.clip(m_ref[0] - base, 0, row_block)  # rows of this block

    def add_group(g, cut):
        start = pl.multiple_of(g * _GROUP, _GROUP)
        x = rows_ref[pl.ds(start, _GROUP), :].astype(jnp.float32)
        if cut:  # the group that holds row m: its rows behind add 0
            row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
            x = jnp.where(row < live - start, x, 0.0)
        for r in range(_GROUP):
            at = base + start + r
            here = pl.ds(token_ref[at], 1)
            acc_ref[here, :] = acc_ref[here, :] + gate_ref[at] * x[r: r + 1, :]

    whole = live // _GROUP

    def whole_group(g, carry):
        add_group(g, cut=False)
        return carry

    jax.lax.fori_loop(0, whole, whole_group, 0)

    @pl.when(live % _GROUP != 0)
    def _cut_group():
        add_group(whole, cut=True)

    @pl.when(b == pl.num_programs(1) - 1)
    def _last_block():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def _combine_rows(rows, token, gate, m, n, interpret):
    blocks, block, d = rows.shape
    tile = _column_tile(n, d)
    # Whole streamed blocks a block of the caller's (the served shapes
    # are: 1,024 rows): a block that is not is padded with dead rows.
    row_block = min(_ROW_BLOCK, -(-block // _GROUP) * _GROUP)
    pad = -block % row_block
    if pad:
        rows = jnp.pad(rows, ((0, 0), (0, pad), (0, 0)))
        lay = lambda v: jnp.pad(  # noqa: E731
            v.reshape(blocks, block), ((0, 0), (0, pad))
        ).reshape(-1)
        token, gate = lay(token), lay(gate)
    per_block = (block + pad) // row_block
    m = jnp.minimum(jnp.asarray(m, jnp.int32), blocks * block)
    # Where row `m` lies among the padded blocks.
    m = (m // block * (block + pad) + m % block).reshape(1)

    def rows_block(j, b, token, gate, m):
        # Past the live rows: the last live block again, so no copy.
        b = jnp.minimum(b, jnp.maximum((m[0] - 1) // row_block, 0))
        return b // per_block, b % per_block, j

    vmem = (
        2 * row_block * tile * rows.dtype.itemsize  # the streamed rows
        + 2 * n * tile * rows.dtype.itemsize  # out
        + n * tile * 4  # accumulator
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(d // tile, blocks * per_block),
            in_specs=[pl.BlockSpec((None, row_block, tile), rows_block)],
            out_specs=pl.BlockSpec((n, tile), lambda j, b, *_: (0, j)),
            scratch_shapes=[pltpu.VMEM((n, tile), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n, d), rows.dtype),
        # One accumulator over a tile's blocks: the steps in order.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem + 8 * 1024 * 1024,
        ),
        interpret=interpret,
    )(token.astype(jnp.int32), gate.astype(jnp.float32), m, rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def combine_rows(
    rows: jnp.ndarray,  # [blocks, block, d]: the experts' output, sorted
    token: jnp.ndarray,  # [blocks * block] int32: each row's token
    gate: jnp.ndarray,  # [blocks * block] float32: each row's gate
    m: jnp.ndarray,  # [] int32: the rows before this one are live
    n: int,  # tokens
    interpret: bool = False,
) -> jnp.ndarray:
    """``sum over i < m with token[i] == t of gate[i] * rows[i]`` for
    every token ``t``, [n, d] in ``rows``' dtype, summed in float32 in
    row order. Rows at and past ``m`` are never read into the sum;
    their tokens must still lie in ``[0, n)``. Forward only."""
    return _combine_rows(rows, token, gate, m, n, interpret)


def _forward(rows, token, gate, m, n, interpret):
    return _combine_rows(rows, token, gate, m, n, interpret), None


def _backward(n, interpret, residuals, g):
    raise NotImplementedError(
        "ops/pallas/expert_combine.py has no backward pass: the sorted "
        "expert form over the pairs computed here is a serving program's; "
        "a train step computes every pair and sums by a gather "
        "(models/moe.py _experts_on_sorted_pairs)"
    )


combine_rows.defvjp(_forward, _backward)
