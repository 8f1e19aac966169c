"""Mamba-2's chunked scan as a Pallas TPU kernel: what
``models/nemotron_h.py mamba_chunked`` does between the convolution's
output and ``_project_out``, one call a Mamba layer and prefill program.

XLA's program of the state-space dual form (`nemotron_h._dual_form`)
writes and reads back every intermediate: the differences, the decays
and the decayed scores as float32 ``[c, g, r, Q, Q]`` arrays (268 MB
each at Granite's served shape), ``x dt`` transposed into and ``y`` out
of a head-major layout, then carries the state through a ``lax.scan``
over the chunks. Here a head's tokens pass through VMEM once:

- **Grid (head blocks, chunks), the chunk axis sequential.** A head
  block's float32 ``[P, N]`` states are the resident block of the second
  result: read from ``state0`` at the block's first chunk, updated in
  VMEM across all of them, written to HBM once
  (`ops/pallas/gdn_chunk.py`'s pattern; its helpers are imported).
- **Operands where they lie**: ``x`` ``[T, H x P]`` and ``B``, ``C``
  ``[T, G x N]`` are three block views of the convolution's one result
  ``[T, x | B | C]`` (a custom call's operand is a whole array: handed
  ``x`` alone, XLA copies its 67 MB out of that result first), ``y``
  leaves as ``[T, H x P]``, the layout ``_project_out`` reads. ``dt``
  comes head-major ``[H, T]``; the running sum ``cum`` of ``dt A``
  within the chunk is one product with a matrix of ones (exact: the
  summand split in three bfloat16 terms, `gdn_chunk._split`), and ONE
  transpose a grid step turns ``dt`` and ``cum`` into the columns that
  multiply rows.
- **A group's ``C B^T`` once a chunk** serves every head of the group in
  the block. A chunk is ``Q / 128`` sub-blocks of 128 tokens and only
  the sub-blocks on and under the diagonal exist: the decay
  ``exp(cum_l - cum_s)`` of a head is made a sub-block at a time, under
  the causal mask on the diagonal and bare under it, times ``C B^T``,
  rounded, and met with ``x dt`` in one product a row of sub-blocks.
- **Two heads of 64 are one tile of 128 lanes.** ``x``, ``y`` and the
  state are taken ``128 / P`` heads at a time: ``C S^T`` and the state's
  update ``(x dt exp(cum_Q - cum))^T B`` are one product for both, each
  head's decayed scores meet the pair's ``x dt`` (the matrix unit's
  columns are 128 whatever is asked of them) and a select over lanes
  keeps each head's own.
- **A number a token and head costs a broadcast along the lanes**, which
  is the permute units' work (three of them, one vector every ~7 cycles
  each) and was the first form's bound (below). So: the pair's ``dt`` is
  ONE gather along the lanes (`take_along_axis`: head ``lane // P``'s
  column), and a head's ``cum`` is broadcast once a sub-block, from
  which the decays against the columns, ``exp(cum)`` and ``exp(cum_Q -
  cum)`` all come by arithmetic on whole vectors.
- **The same arithmetic** as `_dual_form` on a TPU: every product takes
  its operands rounded to bfloat16 in one pass and accumulates in
  float32 (XLA's default precision there; `_operands`), and the same
  arrays are rounded: ``C``, ``B``, ``C B^T * decay``, ``x dt``, ``x dt
  exp(cum_Q - cum)`` and the state where ``C`` reads it. Decays,
  running sums, the state and every sum stay float32; every decay is
  the exponential of a difference <= 0.
- **A chunk that lies wholly at or past ``length``** takes no step and
  its outputs mean nothing: it is not computed (zeros leave, so that
  they stay finite), and names the last live chunk's blocks, so nothing
  is fetched for it. A chunk that straddles ``length`` is computed
  under the caller's ``dt = 0``.
- **Shapes decide.** Heads a grid step: the largest divisor of a group's
  heads up to ``_HEADS_A_STEP`` (Granite: 16 of its one group's 128;
  Nemotron-3-Nano: a group's 8). Sub-blocks: 128 tokens where the chunk
  is a multiple of that, else the chunk (a program of 64 tokens is one
  chunk of one sub-block of 64).

One mixer alone (v5e, `scripts/ssd_chunk_layer.py`, my chip runs, PR
67; a timed call is a chain of nine): ms of the rule alone (x, B, C, dt
to y and the state) / of the whole mixer (`mamba_chunked`) / the state
after the live tokens off the token-a-step recurrence's, as a share of
its norm (the same to four digits with either form: the same arrays are
rounded):

    granite4hsmall-serve1, 2,048 tokens (128 heads, one group, Q 256)
      every token live        XLA's form   2.07  / 4.76  / 0.1986%
                              this kernel  0.275 / 3.10  / 0.1986%
      the last tenth padding  XLA's form   2.08  / 4.79  / 0.1739%
                              this kernel  0.275 / 3.10  / 0.1739%
    nemotron3nano-serve1, 512 tokens (64 heads, 8 groups, Q 128)
      every token live        XLA's form   0.142 / 0.367 / 0.1949%
                              this kernel  0.114 / 0.380 / 0.1949%
      the last tenth padding  XLA's form   0.143 / 0.367 / 0.1933%
                              this kernel  0.106 / 0.380 / 0.1933%
    nemotron3nano-serve1, 64 tokens (one chunk of 64)
      every token live        XLA's form   0.108 / 0.078 / 0.2054%
                              this kernel  0.101 / 0.076 / 0.2054%

(At 64 tokens a link of the rule's chain is the dispatch's, not the
device's.) Short programs gain nothing as a mixer: XLA fuses the
convolution into its form's first passes, and the call has it written
whole; `nemotron_h._SCAN_KERNEL_TOKENS` keeps XLA's form under 1,024
tokens. What the time is at Granite's shape: the call moves 142 MB (x in
and y out in float32, as the fusions around it hold them: 0.173 ms at
the HBM peak) and reads 0.275 ms, 516 GB/s; the compiler's schedule of a
grid step (16 heads, one chunk) is 4,074 bundles, 0.174 ms for the 64
steps at 1.5 GHz, of which 3,450 hold a store (mostly spills of the
unrolled pairs' vectors), 770 a permute and a third a matrix push: the
vector units and the stores, not the products. The first form, which
broadcast each head's ``dt``, ``exp(cum)`` and ``exp(cum_Q - cum)``
columns along the lanes, was 6,185 bundles a step, bound by the permute
units; the gather a pair took it to 4,606, arithmetic on the one
broadcast of ``cum`` to 4,141, a sub-block's rows at a time to 4,074.

Forward only, as the prefill programs are. Off the TPU ``mamba_chunked``
keeps XLA's form, which is tier 1's path and this kernel's oracle
(tests/test_ssd_chunk_kernel.py, interpreted).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas.gdn_chunk import _live_block, _split

_HIGHEST = jax.lax.Precision.HIGHEST
# Tokens of a sub-block and lanes of a tile: the matrix unit's own size.
_TILE = 128
# Heads a grid step, at most: the loop over them is unrolled, and what a
# program's tracing and lowering take of it is set-up. The rule alone at
# Granite's shape (v5e, my chip runs, PR 67), ms by heads a step: 8
# 0.330, 16 0.275, 32 0.244, 64 0.240; `trace_lower_s` of
# `granite-longdoc-16` 11.9 s with XLA's form, 15.7 at 16, 20.1 at 32,
# for the same 21,600 tokens/s end to end at 16 and at 32: 16 stay. (A
# `fori_loop` over the pairs is 815 bundles a pair in the compiler's
# schedule where the unrolled loop is 509: nothing of the next pair
# starts under this one's products.)
_HEADS_A_STEP = 16


def _operands():
    """The dtype every product of the form takes its operands in, which
    is XLA's own rule for `_dual_form`'s einsums on a TPU: bfloat16, one
    pass, unless `jax.default_matmul_precision` asks for more (float32
    then: a test holds the kernel to a CPU's einsums that way). Read
    while tracing; the setting is part of a jitted call's cache key."""
    asked = jax.config.jax_default_matmul_precision
    fast = asked in (None, "default", "fastest", "bfloat16")
    return jnp.bfloat16 if fast else jnp.float32


def _dot(a, b, contract=(1, 0)):
    """a b (or, by ``contract``, a b^T / a^T b) summed in float32:
    bfloat16 operands in one pass, float32 ones at full precision."""
    return jax.lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((), ())),
        precision=_HIGHEST if a.dtype == jnp.float32 else None,
        preferred_element_type=jnp.float32,
    )


def _running_sum(rows):
    """The running sum of rows [h, Q] float32 along Q, exact to
    float32's own sums: three bfloat16 terms of each summand against a
    matrix of ones, summed in float32."""
    q = rows.shape[1]
    before = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    upto = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    ones = (before <= upto).astype(jnp.bfloat16)
    h = rows.shape[0]
    sums = _dot(jnp.concatenate(_split(rows), axis=0), ones)  # [3 h, Q]
    return sums[2 * h:] + sums[h:2 * h] + sums[:h]


def _kernel(chunk, sub, heads, pair, p, operands, length_ref, x_ref, b_ref,
            c_ref, dt_ref, a_ref, d_ref, s0_ref, y_ref, s_ref):
    """One (head block, chunk) a grid step. Refs: ``length`` (scalar
    prefetch); the chunk's x [Q, heads x P], B and C [Q, N] of the
    block's group, dt [heads, Q]; the heads' A [heads, 1] and D spread
    over their lanes [1, heads x P]; their ``state0`` [heads, P, N]; y
    [Q, heads x P]; the heads' state, resident over the chunks."""
    step = pl.program_id(1)

    @pl.when(step == 0)
    def _first():
        s_ref[...] = s0_ref[...]

    width = pair * p  # lanes of `pair` heads
    blocks = chunk // sub

    def rows(i):
        return slice(i * sub, (i + 1) * sub)

    @pl.when(step * chunk >= length_ref[0])
    def _dead():
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    @pl.when(step * chunk < length_ref[0])
    def _live():
        b = b_ref[...].astype(operands)
        c = c_ref[...].astype(operands)
        # C B^T, the sub-blocks on and under the diagonal.
        scores = {
            (i, j): _dot(c[rows(i)], b[rows(j)], (1, 1))
            for i in range(blocks) for j in range(i + 1)
        }
        dt_rows = dt_ref[...]  # [heads, Q]
        cum_rows = _running_sum(dt_rows * a_ref[...])
        # The sum at the chunk's end: the last column, taken out by a
        # masked sum over lanes (what leaves is on every lane).
        at_end = jax.lax.broadcasted_iota(jnp.int32, cum_rows.shape, 1)
        end = jnp.sum(
            jnp.where(at_end == chunk - 1, cum_rows, 0.0), axis=1,
            keepdims=True,
        )  # [heads, 1]
        whole = jnp.exp(end)  # the chunk's decay of each head's state
        stacked = [dt_rows, cum_rows]
        fill = -2 * heads % _TILE
        if fill:
            stacked.append(jnp.zeros((fill, chunk), jnp.float32))
        cols = jnp.concatenate(stacked, axis=0).T  # [Q, 2 heads + fill]
        lane = jax.lax.broadcasted_iota(jnp.int32, (sub, width), 1)

        def by_head(parts):
            """[sub, width] whose lanes of head k are ``parts[k]``'s."""
            out = parts[-1]
            for k in reversed(range(pair - 1)):
                out = jnp.where(lane < (k + 1) * p, parts[k], out)
            return out

        def steps_of(first, at):
            """[sub, width] whose lanes of head ``first + k`` hold its
            ``dt`` at rows ``at``: one gather along the lanes."""
            return jnp.take_along_axis(cols[at], first + lane // p, axis=1)

        row = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 0)
        causal = row >= jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 1)

        for first in range(0, heads, pair):
            mine = range(first, first + pair)
            at = slice(first * p, first * p + width)
            xdt = [
                x_ref[rows(i), at] * steps_of(first, rows(i))
                for i in range(blocks)
            ]
            xdt_in = jnp.concatenate(xdt, axis=0).astype(operands)
            state = s_ref[first:first + pair]  # [pair, P, N]
            # What the chunk reads of the state it starts from.
            read = _dot(c, state.reshape(width, -1).astype(operands), (1, 1))
            ends = []
            # A sub-block's rows at a time, so that little is held.
            for i in range(blocks):
                # A head's running sum down the rows, on every lane: the
                # one broadcast along lanes a head (the permute units'
                # work), from which come the decays against the columns,
                # exp(cum) and exp(cum_Q - cum).
                down = [
                    jnp.broadcast_to(
                        cols[rows(i), heads + h:heads + h + 1], (sub, width)
                    )
                    for h in mine
                ]
                # Within the chunk: y_l += sum_{s <= l} (C_l . B_s)
                # decay(s -> l) dt_s x_s, a head's decays against the
                # pair's x dt.
                within = []
                for h, cum in zip(mine, down):
                    if width != sub:
                        cum = jnp.broadcast_to(cum[:, :1], (sub, sub))
                    decayed = []
                    for j in range(i + 1):
                        diff = cum - cum_rows[h:h + 1, rows(j)]
                        if i == j:
                            diff = jnp.where(causal, diff, -jnp.inf)
                        decayed.append(
                            (scores[i, j] * jnp.exp(diff)).astype(operands)
                        )
                    within.append(_dot(
                        jnp.concatenate(decayed, axis=1),
                        xdt_in[:(i + 1) * sub],
                    ))
                y_ref[rows(i), at] = (
                    by_head(within)
                    + read[rows(i)] * by_head([jnp.exp(cum) for cum in down])
                    + d_ref[:, at] * x_ref[rows(i), at]
                )
                to_end = by_head([
                    jnp.exp(end[h:h + 1] - cum) for h, cum in zip(mine, down)
                ])
                ends.append((xdt[i] * to_end).astype(operands))
            # What the chunk adds to the state by its end.
            added = _dot(jnp.concatenate(ends, axis=0), b, (0, 0))
            for k, h in enumerate(mine):
                s_ref[h] = state[k] * whole[h:h + 1] + added[k * p:(k + 1) * p]


@functools.partial(
    jax.jit, static_argnames=("groups", "chunk", "interpret")
)
def ssd_chunk_rule(
    xbc: jnp.ndarray,  # [T, H x P + 2 G x N] float32: x | B | C
    dt: jnp.ndarray,  # [T, H] float32, 0 from `length` on
    a: jnp.ndarray,  # [H] float32, < 0
    d: jnp.ndarray,  # [H] float32
    state0: jnp.ndarray,  # [H, P, N] float32
    length: jnp.ndarray,  # [] int32: how many of the T tokens are real
    *,
    groups: int,
    chunk: int,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The state-space dual form over T tokens of one sequence in chunks
    of ``chunk`` (which divides T), head ``h`` of group ``h // (H / G)``;
    ``xbc`` is the convolution's output as it lies, of which the call
    reads x, B and C as three views (so N divides H x P). Returns (y [T,
    H x P] float32 with the skip ``D x`` in it, whose rows from
    ``length`` on mean nothing, and the state after token ``length -
    1``)."""
    t = xbc.shape[0]
    h, p, n = state0.shape
    if t % chunk:
        raise ValueError(f"{t} tokens do not divide into chunks of {chunk}")
    if xbc.shape[1] != h * p + 2 * groups * n or h * p % n:
        raise ValueError(
            f"{xbc.shape[1]} channels are not {h} heads of {p} and twice "
            f"{groups} groups of {n}, or {n} does not divide {h * p}"
        )
    sub = _TILE if chunk % _TILE == 0 else chunk
    rep = h // groups
    heads = math.gcd(rep, _HEADS_A_STEP)
    pair = math.gcd(heads, max(1, _TILE // p))

    def live(s, length):
        return _live_block(s, length, chunk)

    def group_block(first):
        """B or C of head block i's group: the view whose first group
        starts ``first`` blocks of N into the channels."""
        return pl.BlockSpec(
            (chunk, n),
            lambda i, s, length: (live(s, length), first + i * heads // rep),
        )

    state_block = pl.BlockSpec((heads, p, n), lambda i, s, length: (i, 0, 0))
    # A step's blocks in and out, twice (the pipeline's two buffers), and
    # some dozens of [Q, 128] float32 arrays between products.
    moved = 4 * (
        2 * chunk * heads * p + 2 * chunk * n + heads * (chunk + 1 + p)
        + 2 * heads * p * n
    )
    held = 4 * 48 * chunk * max(_TILE, pair * p)
    y, end = pl.pallas_call(
        functools.partial(_kernel, chunk, sub, heads, pair, p, _operands()),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(h // heads, t // chunk),
            in_specs=[
                pl.BlockSpec(
                    (chunk, heads * p),
                    lambda i, s, length: (live(s, length), i),
                ),
                group_block(h * p // n),
                group_block(h * p // n + groups),
                pl.BlockSpec(
                    (heads, chunk), lambda i, s, length: (i, live(s, length))
                ),
                pl.BlockSpec((heads, 1), lambda i, s, length: (i, 0)),
                pl.BlockSpec((1, heads * p), lambda i, s, length: (0, i)),
                state_block,
            ],
            out_specs=[
                pl.BlockSpec(
                    (chunk, heads * p), lambda i, s, length: (s, i)
                ),
                state_block,
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((t, h * p), jnp.float32),
            jax.ShapeDtypeStruct((h, p, n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(2 * moved + held + (16 << 20), 96 << 20),
        ),
        interpret=interpret,
    )(
        jnp.asarray(length, jnp.int32).reshape(1),
        xbc, xbc, xbc, dt.T,
        a.reshape(h, 1), jnp.repeat(d, p).reshape(1, h * p), state0,
    )
    return y, end
