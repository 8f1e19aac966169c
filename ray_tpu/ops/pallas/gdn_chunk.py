"""The chunked gated delta rule as a Pallas TPU kernel: what
``models/qwen3_next.py gdn_chunked`` does between the gates and ``o``,
one call a Gated DeltaNet layer and prefill program.

XLA's program of the chunked form writes and reads back every
intermediate of the rule: the ``[.., C, C]`` decays, ``L`` and its
inverse, ``U``, ``W``, ``Q exp(gamma)`` and ``K exp(gamma_C - gamma)``
as float32 ``[n, Hk, r, C, 128]`` arrays of 33.5 MB each at the served
shape, then carries the state through a ``lax.scan`` of 64 trips
(PERF.md section 6, PR 58). Here a key head's tokens pass through VMEM
once:

- **Grid (key heads, token blocks), the token axis sequential.** The
  value heads' float32 ``[dk, dv]`` states of the step's key heads are
  the resident block of the second result: read from ``state0`` at a
  head's first token block, updated in VMEM across all of them, written
  to HBM once.
- **``q``, ``k``, ``v`` are read where they lie**, blocks of the ``[T,
  heads x dim]`` arrays as the convolution leaves them; ``o`` leaves as
  ``[T, value width]``. The per-token numbers of a value head (``beta``,
  the running sum ``gamma`` of ``g`` inside its rule chunk and that sum
  at the chunk's end) come as two small arrays, token-major for what
  multiplies rows (a column taken out by a masked sum over lanes) and
  head-major for the one row the decays need.
- **A group of rule chunks is one matrix.** ``128 / C`` consecutive
  chunks of a key head make one ``[G, G]`` problem whose scores ``K K^T``
  and ``Q K^T`` are ONE product of 2G rows, and whose decays, ``L`` and
  ``(I + L)^-1`` are block-diagonal under a mask (``i // C == j // C``):
  the blocks are the chunks' own, every entry outside them is an exact
  zero, and the matrix unit sees operands of its own size. The inverse
  is `models/qwen3_next.py _unit_lower_inverse`'s, by halves, written
  with masks in place of slices (`_unit_lower_inverses`): with ``X``
  the inverse of the diagonal blocks of size s and ``M`` the part of
  ``L`` in the lower-left corner of each block of size 2s, ``X <- X - X
  M X`` is the inverse of the blocks of size 2s (``-B^-1 M A^-1`` lands
  in the corner). No power of ``L`` is formed.
- **Then chunk by chunk**, C rows at a time out of the group's ``U``,
  ``W``, ``Q exp(gamma)``, ``K exp(gamma_C - gamma)``: ``[W; Q
  exp(gamma)]`` meet the state as one product of 2C rows, ``V' = U - W
  S``, ``S <- exp(gamma_C) S + (K exp(gamma_C - gamma))^T V'``; the
  group's ``lower(Q K^T * D) V'`` is one product after its last chunk.
- **Every step is taken for all the grid step's value heads before the
  next one** (`one_group`): a head's rounds of the inverse and its
  chunks' state updates each wait for the one before, and the matrix
  unit is busy with another head's meanwhile (the table below).
- **The same arithmetic**: float32 throughout, every product at full
  float32 precision (``precision=HIGHEST``: Mosaic's
  ``contract_precision<fp32>``, which is six bfloat16 products of the
  operands split in three, summed in float32; the state's update
  writes the same six out as one product, `_dot_over_rows`), every
  decay the exponential of a difference <= 0, the chunk
  ``cfg.gdn_chunk``. What the time is: the matrix unit's, which takes a
  pass of 128 rows in 32 cycles whatever of its 128 x 128 the operands
  fill, so the rows passed are the cost (PERF.md section 6, PR 58).
- **A group that lies wholly at or past ``length``** takes no step and
  its outputs mean nothing: it is not computed (zeros leave, so that
  they stay finite), and a token block past the last live one names the
  block before it, so nothing is fetched for it. A group that straddles
  ``length`` is computed under the caller's mask (``beta`` 0, ``g`` 0).

One layer alone at the served shape (2,048 tokens, 16 key and 32 value
heads of 128 x 128, chunks of 32; v5e, `scripts/gdn_chunk_layer.py`, my
chip runs, PR 58): ms of the rule alone (q, k, v, beta, g to o and the
state) / of the whole mixer (`gdn_chunked`) / the state after the live
tokens off the token-a-step recurrence's, as a share of its norm:

    every token live         XLA's form   2.97 / 4.49 / 0.0098%
                             this kernel  1.07 / 2.35 / 0.0098%
    the last tenth padding   XLA's form   2.97 / 4.49 / 0.0095%
                             this kernel  1.02 / 2.31 / 0.0095%

and on the way there, the rule alone with every token live: 2.17 ms a
head at a time in program order (heads or groups a step moved nothing:
2.14-2.32), of which the inverse 0.80, the chunks' state steps 0.85 and
everything that is no product 0.45 (each left out in turn); with one
bf16 pass a product 1.28, so the six passes were 0.9 ms and ran beside
nothing; 1.35 with the steps taken for all heads of a grid step and the
lower halves' rows alone in the inverse's last two rounds; 1.07-1.10
with blocks of four in closed form and the state's update as one
product. Six products written out in the kernel for every product
(2.00-2.08) moved little: the time is the matrix unit's passes, not the
split of the operands.

Forward only, as the prefill programs are. Off the TPU
``gdn_chunked`` keeps XLA's form, which is tier 1's path and this
kernel's oracle (tests/test_gdn_chunk_kernel.py, interpreted).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HIGHEST = jax.lax.Precision.HIGHEST
# Tokens of a group: the matrix unit's own size.
_GROUP_TOKENS = 128
# Groups a grid step and key heads a grid step: the loops over both are
# unrolled. Heads are what the steps of `one_group` alternate between;
# a second group in a step only saves the step's ~0.35 us. One layer at
# the served shape (2,048 tokens, 16 key and 32 value heads of 128 x
# 128, chunks of 32; v5e, my chip run, PR 58), ms of the rule alone
# (XLA's form 2.97) by (groups, heads) a step: (1, 1) 1.36, (1, 2)
# 1.09, (2, 2) 1.07, (4, 2) 1.07, (1, 4) 1.07; the kernel compiles in
# 1.5 s at (1, 1), 3.3 s at (1, 2), 3.8 s at (1, 4) (here, for a
# described v5e).
_GROUPS_A_STEP = 1
_HEADS_A_STEP = 4


def _dot(a, b, contract=(1, 0)):
    """a b (or, by ``contract``, a b^T / a^T b) at full float32 precision."""
    return jax.lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((), ())),
        precision=_HIGHEST, preferred_element_type=jnp.float32,
    )


def _split(x):
    """x as three bfloat16 terms whose sum is x (float32 has 24 bits of
    mantissa, bfloat16 8)."""
    high = x.astype(jnp.bfloat16)
    rest = x - high.astype(jnp.float32)
    middle = rest.astype(jnp.bfloat16)
    return high, middle, (rest - middle.astype(jnp.float32)).astype(jnp.bfloat16)


def _dot_over_rows(a, b):
    """a^T b for a [C, m], b [C, n] at full float32 precision, as
    `precision=HIGHEST` computes it (each operand split in three
    bfloat16 terms, six products summed in float32), written out: the
    six products are ONE product over a contraction of 6 C, smallest
    first, so that the matrix unit sums them and its rows are passed
    twice (6 C = 192 of its 128) and not six times. Worth it where the
    contraction is short, which is the state's update alone."""
    a1, a2, a3 = _split(a)
    b1, b2, b3 = _split(b)
    return jax.lax.dot_general(
        jnp.concatenate([a3, a2, a1, a2, a1, a1], axis=0),
        jnp.concatenate([b1, b2, b3, b1, b2, b1], axis=0),
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )


def _column(block, lane):
    """Column ``lane`` (traced) of block [G, W] as [G, 1]."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
    return jnp.sum(jnp.where(lanes == lane, block, 0.0), axis=1, keepdims=True)


def _row(ref, index, at):
    """Row ``index`` (traced) of ref [8 n, B] at lanes ``at`` as [1, G]:
    the eight aligned rows that hold it, and a masked sum over them."""
    base = pl.multiple_of(index // 8 * 8, 8)
    block = ref[pl.ds(base, 8), at]
    rows = jax.lax.broadcasted_iota(jnp.int32, block.shape, 0)
    return jnp.sum(
        jnp.where(rows == index - base, block, 0.0), axis=0, keepdims=True
    )


def _unit_lower_inverses(lowers, row, col, chunk):
    """``(I + L)^-1`` for each ``L`` [G, G] of ``lowers``, strictly
    lower-triangular inside diagonal blocks of ``chunk`` (a power of
    two) and zero outside them: by halves, as the module's docstring
    says, the matrices taken through each round side by side (they do
    not depend on one another, and the unit is kept busy by the ones
    that do not wait).

    Blocks of two rows are ``[[1, 0], [-l, 1]]``: ``X = I - M`` with
    ``M`` the entries right under the diagonal. Blocks of four need no
    product either: ``M`` holds one number a row and a column, so ``M A``
    is ``A`` a row down times a column of numbers and ``A M`` is ``A`` a
    lane on times a row of them. From there ``X <- X - X M X``, which is
    zero outside the lower half's rows of each block of twice the size:
    from halves of eight rows on, a whole tile's, only those rows are
    multiplied."""
    group = row.shape[0]
    eye = (row == col).astype(jnp.float32)
    if chunk == 1:
        return [eye] * len(lowers)

    def corners(size):
        """L in the lower-left corner of each block of twice the size."""
        mask = jnp.logical_and(
            (row ^ col) < 2 * size,
            jnp.logical_and((row & size) != 0, (col & size) == 0),
        )
        return [jnp.where(mask, lower, 0.0) for lower in lowers]

    under = corners(1)
    invs = [eye - m for m in under]
    if chunk > 2:
        down = [jnp.sum(m, axis=1, keepdims=True) for m in under]  # [G, 1]
        along = [jnp.sum(m, axis=0, keepdims=True) for m in under]  # [1, G]
        # X M X = (M - M1 M)(I - M1), M1 the blocks of two's entries.
        left = [
            m - d * pltpu.roll(m, 1, 0) for m, d in zip(corners(2), down)
        ]
        invs = [
            inv - t + pltpu.roll(t, group - 1, 1) * e
            for inv, t, e in zip(invs, left, along)
        ]
    size = 4
    while size < chunk:
        # The rows of the lower halves, where they are whole tiles.
        rows = None if size % 8 else [
            slice(at, at + size) for at in range(size, group, 2 * size)
        ]
        halves = invs if rows is None else [
            jnp.concatenate([inv[r] for r in rows], axis=0) for inv in invs
        ]
        steps = [_dot(half, m) for half, m in zip(halves, corners(size))]
        halves = [
            half - _dot(step, inv)
            for half, step, inv in zip(halves, steps, invs)
        ]
        invs = halves if rows is None else [
            jnp.concatenate([
                piece
                for i, r in enumerate(rows)
                for piece in (
                    inv[r.start - size:r.start], half[i * size:(i + 1) * size]
                )
            ], axis=0)
            for inv, half in zip(invs, halves)
        ]
        size *= 2
    return invs


def _kernel(chunk, group, groups, heads, rep, dk, dv, length_ref, q_ref,
            k_ref, v_ref, cols_ref, rows_ref, s0_ref, o_ref, s_ref):
    """One (key heads, token block) a grid step. Refs: ``length`` (scalar
    prefetch); the block's q and k [B, heads x dk] and v [B, heads x rep
    x dv]; its tokens' ``[beta | gamma | gamma_end]`` [B, 3 Hv] and
    gamma again [Hv up to eight rows, B]; the heads' ``state0`` [heads x
    rep, dk, dv]; o [B, heads x rep x dv]; the heads' state, resident
    over the token blocks."""
    h, s = pl.program_id(0), pl.program_id(1)
    length = length_ref[0]
    value_heads = cols_ref.shape[1] // 3
    mine = range(heads * rep)  # the step's value heads

    @pl.when(s == 0)
    def _first():
        s_ref[...] = s0_ref[...]

    row = jax.lax.broadcasted_iota(jnp.int32, (group, group), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (group, group), 1)
    together = (row ^ col) < chunk  # of one rule chunk (a power of two)
    causal = jnp.logical_and(together, row >= col)
    strict = jnp.logical_and(together, row > col)

    def one_group(at):
        """Every step below is taken for all the step's value heads
        before the next one: what one head waits for, another
        computes."""
        cols = cols_ref[at, :]
        q = [q_ref[at, hh * dk:(hh + 1) * dk] for hh in range(heads)]
        k = [k_ref[at, hh * dk:(hh + 1) * dk] for hh in range(heads)]
        scores = [
            _dot(jnp.concatenate([q[hh], k[hh]], axis=0), k[hh], (1, 1))
            for hh in range(heads)
        ]  # [2G, G]: Q K^T over K K^T
        first = h * heads * rep  # the step's first value head among all
        beta = [_column(cols, first + i) for i in mine]
        gamma = [_column(cols, value_heads + first + i) for i in mine]
        gamma_end = [_column(cols, 2 * value_heads + first + i) for i in mine]
        # exp(gamma_i - gamma_j) inside a chunk, on and under the
        # diagonal; an exact zero elsewhere.
        decay = [
            jnp.exp(jnp.where(
                causal, gamma[i] - _row(rows_ref, first + i, at), -jnp.inf
            ))
            for i in mine
        ]
        solve = _unit_lower_inverses(
            [
                jnp.where(strict, beta[i] * scores[i // rep][group:] * decay[i],
                          0.0)
                for i in mine
            ],
            row, col, chunk,
        )
        grow = [jnp.exp(gamma[i]) for i in mine]
        wu = [
            _dot(solve[i], jnp.concatenate([
                (beta[i] * grow[i]) * k[i // rep],
                beta[i] * v_ref[at, i * dv:(i + 1) * dv],
            ], axis=1))
            for i in mine
        ]  # [G, dk + dv]: W beside U
        q_grown = [q[i // rep] * grow[i] for i in mine]
        k_end = [k[i // rep] * jnp.exp(gamma_end[i] - gamma[i]) for i in mine]
        state = [s_ref[i] for i in mine]
        reads, news = [[] for _ in mine], [[] for _ in mine]
        for c in range(group // chunk):
            rows = slice(c * chunk, (c + 1) * chunk)
            both = [
                _dot(jnp.concatenate(
                    [wu[i][rows, :dk], q_grown[i][rows]], axis=0
                ), state[i])
                for i in mine
            ]  # [2 C, dv]: W S over (Q exp(gamma)) S
            for i in mine:
                news[i].append(wu[i][rows, dk:] - both[i][:chunk])
                reads[i].append(both[i][chunk:])
            state = [
                state[i] * jnp.exp(gamma_end[i][c * chunk:c * chunk + 1])
                + _dot_over_rows(k_end[i][rows], news[i][c])
                for i in mine
            ]
        for i in mine:
            s_ref[i] = state[i]
            o_ref[at, i * dv:(i + 1) * dv] = jnp.concatenate(
                reads[i], axis=0
            ) + _dot(
                scores[i // rep][:group] * decay[i],
                jnp.concatenate(news[i], axis=0),
            )

    _live_groups(s, groups, group, length, o_ref, one_group)


def _live_groups(s, groups, group, length, o_ref, one_group):
    """``one_group(rows)`` for each of token block ``s``'s groups that
    holds a token before ``length``; zeros leave for the others' rows of
    ``o_ref``, so that they stay finite."""
    for gi in range(groups):
        at = pl.ds(gi * group, group)
        first = (s * groups + gi) * group  # the group's first token

        @pl.when(first < length)
        def _live(at=at):
            one_group(at)

        @pl.when(first >= length)
        def _dead(at=at):
            o_ref[at, :] = jnp.zeros((group, o_ref.shape[1]), o_ref.dtype)


def _live_block(s, length, block):
    """Token block ``s`` for an index map, or, past the last block that
    holds a token before ``length`` (the scalar-prefetch ref), that block
    again: nothing is copied for a block named twice in a row."""
    return jnp.minimum(s, jnp.maximum(length[0] - 1, 0) // block)


def _blocking(t: int, chunk: int) -> tuple[int, int, int]:
    """(tokens a group, groups a grid step, grid steps) for t tokens."""
    n_chunks = -(-t // chunk)
    per_group = max(1, min(_GROUP_TOKENS // chunk, n_chunks))
    n_groups = -(-n_chunks // per_group)
    groups = min(_GROUPS_A_STEP, n_groups)
    return per_group * chunk, groups, -(-n_groups // groups)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def gdn_chunk_rule(
    q: jnp.ndarray,  # [T, Hk, dk] float32, unit length times dk^-0.5
    k: jnp.ndarray,  # [T, Hk, dk] float32, unit length
    v: jnp.ndarray,  # [T, Hk, r, dv] float32
    beta: jnp.ndarray,  # [T, Hk, r] float32, 0 from `length` on
    g: jnp.ndarray,  # [T, Hk, r] float32 (log of the decay), 0 from `length` on
    state0: jnp.ndarray,  # [Hk, r, dk, dv] float32
    length: jnp.ndarray,  # [] int32: how many of the T tokens are real
    *,
    chunk: int,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The gated delta rule over T tokens of one sequence in chunks of
    ``chunk`` (a power of two). Returns (o [T, Hk x r x dv] float32,
    whose rows from ``length`` on mean nothing, and the state after token
    ``length - 1``)."""
    t, hk, dk = q.shape
    rep, dv = v.shape[2:]
    hv = hk * rep
    group, groups, steps = _blocking(t, chunk)
    block = group * groups
    padded = steps * block
    heads = math.gcd(hk, _HEADS_A_STEP)

    def flat(a):
        a = a.reshape(t, -1)
        return a if padded == t else jnp.pad(a, ((0, padded - t), (0, 0)))

    beta, g = flat(beta), flat(g)
    gamma = jnp.cumsum(g.reshape(-1, chunk, hv), axis=1)
    gamma_end = jnp.broadcast_to(gamma[:, -1:], gamma.shape)
    gamma = gamma.reshape(padded, hv)
    cols = jnp.concatenate(
        [beta, gamma, gamma_end.reshape(padded, hv)], axis=1
    )

    def tokens(s, length):
        return _live_block(s, length, block)

    def token_block(width):
        return pl.BlockSpec(
            (block, width), lambda h, s, length: (tokens(s, length), h)
        )

    state_block = pl.BlockSpec(
        (heads * rep, dk, dv), lambda h, s, length: (h, 0, 0)
    )
    # A step's blocks in and out, twice (the pipeline's two buffers), and
    # a dozen [G, dk + dv] float32 arrays a value head between products.
    moved = 4 * (
        block * heads * (2 * dk + 2 * rep * dv)
        + block * (3 * hv + hv + -hv % 8) + 2 * heads * rep * dk * dv
    )
    held = 4 * 12 * heads * rep * group * max(group, dk + dv)
    o, end = pl.pallas_call(
        functools.partial(_kernel, chunk, group, groups, heads, rep, dk, dv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(hk // heads, steps),
            in_specs=[
                token_block(heads * dk),
                token_block(heads * dk),
                token_block(heads * rep * dv),
                pl.BlockSpec(
                    (block, 3 * hv),
                    lambda h, s, length: (tokens(s, length), 0),
                ),
                pl.BlockSpec(
                    (hv + -hv % 8, block),
                    lambda h, s, length: (0, tokens(s, length)),
                ),
                state_block,
            ],
            out_specs=[
                pl.BlockSpec(
                    (block, heads * rep * dv), lambda h, s, length: (s, h)
                ),
                state_block,
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((padded, hv * dv), jnp.float32),
            jax.ShapeDtypeStruct((hv, dk, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(2 * moved + held + (16 << 20), 96 << 20),
        ),
        interpret=interpret,
    )(
        jnp.asarray(length, jnp.int32).reshape(1),
        flat(q), flat(k), flat(v), cols,
        jnp.pad(gamma.T, ((0, -hv % 8), (0, 0))),
        state0.reshape(hv, dk, dv),
    )
    return o[:t], end.reshape(state0.shape)
