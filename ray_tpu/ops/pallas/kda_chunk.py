"""The chunked per-channel delta rule (KDA) as a Pallas TPU kernel: what
``models/glm5_next.py kda_chunked`` does between the gates and ``o``, one
call a KDA layer and prefill program.

XLA's program of the chunked form (`glm5_next._kda_rule`) writes and
reads back every intermediate of the rule: the running sum ``gamma`` of
the log-decay, the rows' and the columns' decay factors, the keys times
each, two ``[.., C, C]`` score arrays, the inverse, ``U``, ``W``, ``Q
exp(gamma)`` and ``K exp(gamma_C - gamma)`` as float32 arrays of 67 MB
each at the served shape (the columns' twice that), then carries the
state through a ``lax.scan`` of 64 trips (PERF.md section 6, PR 60). It
is `ops/pallas/gdn_chunk.py`'s problem with a decay a key CHANNEL, so
the decays do not factor out of the scores and go inside the products,
and this is that kernel's form (read its docstring first; its helpers
are imported, not copied) with three differences:

- **``g`` is a full operand**, ``[T, H x dk]`` float32 read where it
  lies beside ``q``, ``k``, ``v``; ``beta`` stays the small token-major
  array. Inside a group of 128 tokens the running sum ``gamma`` of ``g``
  within each rule chunk is made in VMEM (log2 C rounds of a roll down
  the rows and a masked add), and with it the reference points ``R_a``
  (``gamma`` at the MIDDLE row of each sub-chunk of ``sub`` rows, spread
  over the sub-chunk's rows), ``exp(gamma - R_a)`` for rows and ``exp(R_a
  - gamma)`` for columns, which multiply ``q`` and ``k`` before the
  products. `_kda_rule`'s bound holds: no exponent passes ``sub x
  |lower| / 2`` (40) either way, and a column of an earlier sub-chunk
  carries one <= 0.
- **The scores are one product a sub-chunk distance.** Rows of sub-chunk
  ``a`` meet columns of sub-chunk ``b <= a`` of their chunk against
  ``R_a``, so the columns are needed in ``C / sub`` variants: each
  against its own sub-chunk's reference (distance 0: all rows ``[K; Q]``
  times ``K exp(R - gamma)``, kept where row and column share a
  sub-chunk) and against the reference ``d`` sub-chunks on (only the
  rows from sub-chunk ``d`` of each chunk on are multiplied, and kept
  where the distance is ``d``). At chunk 32 that is two products, of
  256 and of 128 rows.
- **The state is held transposed**, ``[dv, dk]`` a head in a scratch
  buffer: its decay a chunk, ``exp(gamma_C)`` over the key channels, is
  then a ROW that multiplies every sublane, where the state as it is
  stored would need that row turned into a column four times a group.
  ``state0`` is transposed in at a head's first token block and the
  result out at its last, two transposes a head and call.

The rest is `gdn_chunk.py`'s: grid (heads, token blocks), the token axis
sequential; ``(I + L)^-1`` block-diagonal by halves under masks
(`_unit_lower_inverses`); ``[W | U]`` one product; then chunk by chunk
``[W; Q exp(gamma)] S`` as one product, ``V' = U - W S``, ``S <-
exp(gamma_C) S + (K exp(gamma_C - gamma))^T V'`` with the six bfloat16
products of the update written out as one (`_dot_over_rows`); the
group's ``lower(scores) V'`` after its last chunk; every step taken for
all of a grid step's heads before the next; a group wholly at or past
``length`` not computed, a token block past the last live one not
fetched. **The same arithmetic**: float32 throughout, every product at
``precision=HIGHEST``, every exponential exact, the chunk and the
sub-chunk the caller's (``cfg.kda_chunk``, ``_KDA_SUBCHUNK``).

One layer alone at the served shape (2,048 tokens, 64 heads of 128 x
128, chunks of 32 in sub-chunks of 16; v5e, `scripts/glm5_next_layer.py
kda`, my chip run, PR 60): ms of the rule alone (q, k, v, beta, g to o
and the state) / of the whole mixer (`kda_chunked`) / the state after
the live tokens off the token-a-step recurrence's, as a share of its
norm:

    every token live         XLA's form   10.20 / 17.31 / 0.0132%
                             this kernel   2.25 /  9.51 / 0.0132%
    the last tenth padding   XLA's form   10.22 / 17.29 / 0.0130%
                             this kernel   2.15 /  9.40 / 0.0130%

(XLA's form at chunk 64: 10.71 / 18.33, at 128: 14.45 / 21.69.) What
the 2.23 ms are (4 heads a step; each replaced in turn in a throwaway
copy, same run): with ONE bf16 pass a product 1.46, so the six passes
cost 0.78; without the inverse (``I - L`` in its place) 1.59, so the
inverse 0.64; the running sum and the spread rows nothing (2.23, 2.27:
the vector unit's work hides under the matrix unit's), the running sum
as a product of a triangle of ones 2.37 (slower: the matrix unit is
what is full); all four at once 0.78, which is the loads, the seven
exponentials, the masks and the grid. A head and group is 80 passes of
128 rows through the matrix unit (scores 12 + 6, the inverse 24, ``[W |
U]`` 12, the chunks' state products 12 and updates 8, ``within V'`` 6):
1.75 ms at the unit's peak for 64 heads x 16 groups.

Forward only, as the prefill programs are. Off the TPU ``kda_chunked``
keeps XLA's form, which is tier 1's path and this kernel's oracle
(tests/test_kda_chunk_kernel.py, interpreted).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas.gdn_chunk import (
    _blocking,
    _column,
    _dot,
    _dot_over_rows,
    _live_block,
    _live_groups,
    _unit_lower_inverses,
)

# Heads a grid step: what the steps of `one_group` alternate between
# (the loop over them is unrolled). One layer at the served shape, every
# token live (v5e, my chip runs, PR 60), ms of the rule alone (XLA's
# form 10.20) by heads a step: 1 4.42-4.45, 2 2.75, 4 2.23-2.27, 8
# 2.04-2.05; two groups a step at 2 heads 2.68-2.69 for 2.75. Eight heads
# compile in 4.5 s a call for 2.4 at four (first call on the chip, the
# run included) and a prefill program holds four calls: not worth 0.2 ms.
_HEADS_A_STEP = 4


def _running_sum(x, rows_in, chunk):
    """The running sum of x [G, W] down the rows of each chunk of
    ``chunk`` rows (a power of two; ``rows_in`` is each row's index in
    its chunk): log2 chunk rounds, each adding the partial sums that end
    ``step`` rows up."""
    step = 1
    while step < chunk:
        x = x + jnp.where(rows_in >= step, pltpu.roll(x, step, 0), 0.0)
        step *= 2
    return x


def _spread_row(x, size, at):
    """Row ``at`` of each ``size`` rows of x [G, W], over those rows."""
    group, width = x.shape
    if size == 1:
        return x
    picked = x.reshape(group // size, size, width)[:, at:at + 1]
    return jnp.broadcast_to(picked, (group // size, size, width)).reshape(
        group, width
    )


def _from_row(x, chunk, skip):
    """The rows of x [G, W] from row ``skip`` of each chunk on."""
    if not skip:
        return x
    return jnp.concatenate(
        [x[c + skip:c + chunk] for c in range(0, x.shape[0], chunk)], axis=0
    )


def _to_rows(part, chunk, skip):
    """`_from_row`'s rows back where they were, zeros above them."""
    if not skip:
        return part
    kept = chunk - skip
    zeros = jnp.zeros((skip, part.shape[1]), part.dtype)
    return jnp.concatenate([
        piece
        for c in range(0, part.shape[0], kept)
        for piece in (zeros, part[c:c + kept])
    ], axis=0)


def _kernel(chunk, sub, group, groups, heads, dk, dv, length_ref, q_ref,
            k_ref, v_ref, g_ref, beta_ref, s0_ref, o_ref, s_ref, st_ref):
    """One (heads, token block) a grid step. Refs: ``length`` (scalar
    prefetch); the block's q, k, g [B, heads x dk] and v [B, heads x
    dv]; its tokens' beta [B, H]; the heads' ``state0`` [heads, dk, dv];
    o [B, heads x dv]; the heads' state out; and the state as the steps
    hold it, transposed [heads, dv, dk], resident over the token
    blocks."""
    h, s = pl.program_id(0), pl.program_id(1)
    length = length_ref[0]
    mine = range(heads)
    subs = chunk // sub

    @pl.when(s == 0)
    def _first():
        for i in mine:
            st_ref[i] = s0_ref[i].T

    row = jax.lax.broadcasted_iota(jnp.int32, (group, group), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (group, group), 1)
    together = (row ^ col) < chunk  # of one rule chunk (a power of two)
    # How many sub-chunks of its chunk a row lies past a column.
    shift = sub.bit_length() - 1
    ahead = ((row & (chunk - 1)) >> shift) - ((col & (chunk - 1)) >> shift)
    rows_in = jax.lax.broadcasted_iota(jnp.int32, (group, dk), 0) & (chunk - 1)

    def one_group(at):
        """Every step below is taken for all the step's heads before the
        next one: what one head waits for, another computes."""
        lanes = [slice(i * dk, (i + 1) * dk) for i in mine]
        betas = beta_ref[at, :]
        beta = [_column(betas, h * heads + i) for i in mine]
        q = [q_ref[at, lanes[i]] for i in mine]
        k = [k_ref[at, lanes[i]] for i in mine]
        gamma = [_running_sum(g_ref[at, lanes[i]], rows_in, chunk) for i in mine]
        # R_a over the rows of sub-chunk a: gamma at its middle row.
        ref = [_spread_row(gamma[i], sub, max(sub // 2 - 1, 0)) for i in mine]
        rows_decay = [jnp.exp(gamma[i] - ref[i]) for i in mine]
        # [K; Q] with the rows' factor: [2G, dk].
        kq = [
            jnp.concatenate([k[i] * rows_decay[i], q[i] * rows_decay[i]], axis=0)
            for i in mine
        ]
        scores = [jnp.zeros((2 * group, group), jnp.float32) for _ in mine]
        for d in range(subs):
            # Columns against the reference d sub-chunks on: R_(b + d) -
            # gamma_j <= 0 for d > 0; no such sub-chunk in the chunk, no
            # column.
            reach = rows_in < chunk - d * sub
            k_cols = [
                k[i] * jnp.exp(jnp.where(
                    reach,
                    (pltpu.roll(ref[i], group - d * sub, 0) if d else ref[i])
                    - gamma[i],
                    -jnp.inf,
                ))
                for i in mine
            ]
            # Rows from sub-chunk d of each chunk on, of K and of Q.
            part = [
                _dot(jnp.concatenate([
                    _from_row(kq[i][:group], chunk, d * sub),
                    _from_row(kq[i][group:], chunk, d * sub),
                ], axis=0), k_cols[i], (1, 1))
                for i in mine
            ]
            keep = jnp.logical_and(together, ahead == d)
            keep = jnp.concatenate([keep, keep], axis=0)
            for i in mine:
                half = part[i].shape[0] // 2
                scores[i] = jnp.where(keep, jnp.concatenate([
                    _to_rows(part[i][:half], chunk, d * sub),
                    _to_rows(part[i][half:], chunk, d * sub),
                ], axis=0), scores[i])
        solve = _unit_lower_inverses(
            [
                jnp.where(row > col, beta[i] * scores[i][:group], 0.0)
                for i in mine
            ],
            row, col, chunk,
        )
        grow = [jnp.exp(gamma[i]) for i in mine]
        wu = [
            _dot(solve[i], jnp.concatenate([
                (beta[i] * grow[i]) * k[i],
                beta[i] * v_ref[at, i * dv:(i + 1) * dv],
            ], axis=1))
            for i in mine
        ]  # [G, dk + dv]: W beside U
        within = [jnp.where(row >= col, scores[i][group:], 0.0) for i in mine]
        q_grown = [q[i] * grow[i] for i in mine]
        gamma_end = [_spread_row(gamma[i], chunk, chunk - 1) for i in mine]
        k_end = [k[i] * jnp.exp(gamma_end[i] - gamma[i]) for i in mine]
        state = [st_ref[i] for i in mine]  # [dv, dk] each
        reads, news = [[] for _ in mine], [[] for _ in mine]
        for c in range(group // chunk):
            rows = slice(c * chunk, (c + 1) * chunk)
            both = [
                _dot(jnp.concatenate(
                    [wu[i][rows, :dk], q_grown[i][rows]], axis=0
                ), state[i], (1, 1))
                for i in mine
            ]  # [2 C, dv]: W S over (Q exp(gamma)) S
            for i in mine:
                news[i].append(wu[i][rows, dk:] - both[i][:chunk])
                reads[i].append(both[i][chunk:])
            last = (c + 1) * chunk - 1
            state = [
                state[i] * jnp.exp(gamma[i][last:last + 1])
                + _dot_over_rows(news[i][c], k_end[i][rows])
                for i in mine
            ]
        for i in mine:
            st_ref[i] = state[i]
            o_ref[at, i * dv:(i + 1) * dv] = jnp.concatenate(
                reads[i], axis=0
            ) + _dot(within[i], jnp.concatenate(news[i], axis=0))

    _live_groups(s, groups, group, length, o_ref, one_group)

    @pl.when(s == pl.num_programs(1) - 1)
    def _last():
        for i in mine:
            s_ref[i] = st_ref[i].T


@functools.partial(jax.jit, static_argnames=("chunk", "sub", "interpret"))
def kda_chunk_rule(
    q: jnp.ndarray,  # [T, H, dk] float32, unit length times dk^-0.5
    k: jnp.ndarray,  # [T, H, dk] float32, unit length
    v: jnp.ndarray,  # [T, H, dv] float32
    beta: jnp.ndarray,  # [T, H] float32, 0 from `length` on
    g: jnp.ndarray,  # [T, H, dk] float32 (log of the decay), 0 from `length` on
    state0: jnp.ndarray,  # [H, dk, dv] float32
    length: jnp.ndarray,  # [] int32: how many of the T tokens are real
    *,
    chunk: int,
    sub: int,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The per-channel delta rule over T tokens of one sequence in chunks
    of ``chunk`` and sub-chunks of ``sub`` (powers of two, ``sub`` at most
    ``chunk``). Returns (o [T, H x dv] float32, whose rows from
    ``length`` on mean nothing, and the state after token ``length -
    1``)."""
    t, h, dk = q.shape
    dv = v.shape[2]
    group, groups, steps = _blocking(t, chunk)
    block = group * groups
    padded = steps * block
    heads = math.gcd(h, _HEADS_A_STEP)

    def flat(a):
        a = a.reshape(t, -1)
        return a if padded == t else jnp.pad(a, ((0, padded - t), (0, 0)))

    def token_block(width):
        return pl.BlockSpec(
            (block, width),
            lambda hh, s, length: (_live_block(s, length, block), hh),
        )

    state_block = pl.BlockSpec((heads, dk, dv), lambda hh, s, length: (hh, 0, 0))
    # A step's blocks in and out, twice (the pipeline's two buffers), the
    # state three times, and two dozen [G, dk + dv] float32 arrays a head
    # between products.
    moved = 4 * (
        block * heads * (3 * dk + 2 * dv) + block * h + 2 * heads * dk * dv
    )
    held = 4 * (24 * heads * group * max(group, dk + dv) + heads * dk * dv)
    o, end = pl.pallas_call(
        functools.partial(_kernel, chunk, sub, group, groups, heads, dk, dv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(h // heads, steps),
            in_specs=[
                token_block(heads * dk),
                token_block(heads * dk),
                token_block(heads * dv),
                token_block(heads * dk),
                pl.BlockSpec(
                    (block, h),
                    lambda hh, s, length: (_live_block(s, length, block), 0),
                ),
                state_block,
            ],
            out_specs=[
                pl.BlockSpec(
                    (block, heads * dv), lambda hh, s, length: (s, hh)
                ),
                state_block,
            ],
            scratch_shapes=[pltpu.VMEM((heads, dv, dk), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((padded, h * dv), jnp.float32),
            jax.ShapeDtypeStruct((h, dk, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(2 * moved + held + (16 << 20), 96 << 20),
        ),
        interpret=interpret,
    )(
        jnp.asarray(length, jnp.int32).reshape(1),
        flat(q), flat(k), flat(v), flat(g), flat(beta), state0,
    )
    return o[:t], end
