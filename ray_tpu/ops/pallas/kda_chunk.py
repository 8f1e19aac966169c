"""The KDA mixer between its matmuls as a Pallas TPU kernel: everything
``models/glm5_next.py kda_chunked`` does between the in-projections'
float32 results and the out-projection's operand, one call a KDA layer
and prefill program. Three parts on the same tiles: a PROLOGUE (the
causal convolution, ``silu``, the unit lengths of ``q`` and ``k``, the
decay's sigmoid, the mask past ``length``; PR 62), the chunked
per-channel delta rule (PR 60), an EPILOGUE (the head norm, the output
gate, the cast to the model's dtype; PR 62).

**The rule.** XLA's program of the chunked form (`glm5_next._kda_rule`)
writes and reads back every intermediate of the rule: the running sum
``gamma`` of the log-decay, the rows' and the columns' decay factors,
the keys times each, two ``[.., C, C]`` score arrays, the inverse,
``U``, ``W``, ``Q exp(gamma)`` and ``K exp(gamma_C - gamma)`` as float32
arrays of 67 MB each at the served shape (the columns' twice that), then
carries the state through a ``lax.scan`` of 64 trips (PERF.md section 6,
PR 60). It is `ops/pallas/gdn_chunk.py`'s problem with a decay a key
CHANNEL, so the decays do not factor out of the scores and go inside the
products, and this is that kernel's form (read its docstring first; its
helpers are imported, not copied) with three differences:

- **``g`` is a full ``[G, dk]`` array a head** (made in the prologue;
  ``beta`` stays the small token-major array). Inside a group of 128
  tokens the running sum ``gamma`` of ``g`` within each rule chunk is
  made in VMEM (log2 C rounds of a roll down the rows and a masked
  add), and with it the reference points ``R_a``
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

**The passes around it** (PR 62). Between the matmuls and the rule
XLA made float32 passes over ``[T, 3 H dk]`` and ``[T, H dk]`` arrays
(201 and 67 MB at the served shape): the tail's rows concatenated before
the in-projection's result, the convolution's four shifted reads with
``silu``, the split and the unit lengths, the decay's sigmoid and its
mask, and behind the rule the head norm and the gate: 1.4-2.4 GB a
mixer, 7 of its 9.5 ms with the matmuls (PERF.md section 6, PR 62).
Here the kernel's operands are the matmuls' results where they lie:

- **``q``, ``k``, ``v`` are three block views of the in-projection's one
  result** (column blocks ``h``, ``H / heads + h``, ``2 H / heads +
  h``), raw. The convolution over a token block's rows is `_taps`: a row
  ``back`` up is a roll down the sublanes whose first ``back`` rows come
  from the eight rows before the block, which a scratch buffer keeps
  (the rows before the sequence, ``conv0``, at a head's first block;
  after a group its own last eight raw rows: the token axis is
  sequential). Then ``silu``, `_unit` over a head's channels, ``q``
  times ``dk^-0.5``.
- **``g = lower * sigmoid(exp(A_log) (low + dt_bias))``** of the decay's
  pre-activation ``low`` (still made outside at ``precision=HIGHEST``),
  and ``g`` and ``beta`` zero on rows at and past ``length`` (the
  prefetched scalar): nothing of the mask is an array in HBM.
- **The output leaves gated and normed in the model's dtype**: ``o *
  rsqrt(mean(o^2) + eps) * w * sigmoid(gate)`` a head, cast where
  `glm5_next._kda_gated` casts, ``[T, H x dv]``; ``W_o`` stays XLA's.

The same arithmetic as XLA's passes: float32 from the matmuls'
accumulators to the cast, the taps summed oldest first, exact ``exp``
and division, the same three epsilons.

One layer alone at the served shape (2,048 tokens, 64 heads of 128 x
128, chunks of 32 in sub-chunks of 16; v5e, `scripts/glm5_next_layer.py
kda`, my chip runs, PRs 60 and 62), ms of this call alone / of the whole
mixer (`kda_chunked`); the state after the live tokens is 0.0132% of its
norm off the token-a-step recurrence's in every row (0.0130% with the
last tenth padding), the mixer's bfloat16 output 0.017% off XLA's
form's:

                                   every token live   the last tenth padding
    XLA's form (the rule 10.20)          - / 17.56          - / 17.56
    the rule alone in here (PR 60)    2.26 /  9.54       2.15 /  9.41
    with the epilogue                    - /  9.70          - /  9.61
    with the prologue                    - /  6.42          - /  6.28
    with both (PR 62)                 2.67 /  5.79       2.57 /  5.67

(The two middle rows are this kernel with a stage passed through and
XLA's passes in its place, which as a whole reads 10.32 where the real
parent reads 9.54: their differences count, not their levels.) By heads
a grid step, both stages in: 1 5.01 / 8.06, 2 3.31 / 6.39, 4 2.67 /
5.79, 8 2.45 / 5.59; two groups a step at two heads 3.21 / 6.32. Eight
heads compile in 4.5 s a call for 2.4 at four and a prefill program
holds four calls: not worth 0.2 ms (PR 60's reading, and still so). What
the stages cost in the call, each left out in turn (a throwaway script,
same run): the taps 0.09, ``silu`` 0.10, the unit lengths 0.06, the
decay's sigmoid 0.04, the head norm 0.03, the gate's sigmoid and the
cast nothing; approximate reciprocals in the five sigmoids would save
0.11 and are not taken (the arithmetic is XLA's). All of both stages is
0.37 ms beside a rule of 2.25: most of the vector unit's work hides
under the matrix unit's, as PR 60 found for the running sum.

What the rule's 2.23 ms are (PR 60; 4 heads a step; each replaced in
turn in a throwaway copy, same run): with ONE bf16 pass a product 1.46,
so the six passes cost 0.78; without the inverse (``I - L`` in its
place) 1.59, so the inverse 0.64; the running sum and the spread rows
nothing (2.23, 2.27); the running sum as a product of a triangle of
ones 2.37 (slower: the matrix unit is what is full); all four at once
0.78, which is the loads, the seven exponentials, the masks and the
grid. A head and group is 80 passes of 128 rows through the matrix unit
(scores 12 + 6, the inverse 24, ``[W | U]`` 12, the chunks' state
products 12 and updates 8, ``within V'`` 6): 1.75 ms at the unit's peak
for 64 heads x 16 groups.

Forward only, as the prefill programs are. Off the TPU ``kda_chunked``
keeps XLA's passes and `_kda_rule`, which are tier 1's path and this
kernel's oracle (tests/test_kda_chunk_kernel.py, interpreted).
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
# (the loop over them is unrolled). The table by heads a step is in the
# module's docstring; eight compile in 4.5 s a call for 2.4 at four and a
# prefill program holds four calls: not worth 0.2 ms.
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


def _taps(x, before, w):
    """The causal depthwise convolution of a token block's rows x [G, W]
    with the rows before them, the LAST of ``before`` [8, W], under the
    taps w [K, W] (w[K - 1] meets the row itself): row i is ``sum_j w[j]
    seq[i + j]`` with ``seq`` the K - 1 rows before the block and then
    the block, summed oldest tap first as `glm5_next.kda_chunked` sums
    it. A row ``back`` up is a roll down the sublanes; its first
    ``back`` rows come from ``before`` rolled the same way."""
    taps = w.shape[0]
    first = jax.lax.broadcasted_iota(jnp.int32, before.shape, 0)
    conv = None
    for j in range(taps):
        back = taps - 1 - j
        shifted = x
        if back:
            rolled = pltpu.roll(x, back, 0)
            head = jnp.where(
                first < back, pltpu.roll(before, back, 0), rolled[:8]
            )
            shifted = head if x.shape[0] == 8 else jnp.concatenate(
                [head, rolled[8:]], axis=0
            )
        term = shifted * w[j:j + 1]
        conv = term if conv is None else conv + term
    return conv


def _unit(x, eps):
    """`qwen3_next._unit`: x [G, dk] over each row's length."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True) + eps)


def _prologue(raw, before, w, low, bias, rate, live, lower, eps):
    """What the rule takes of one head's rows of a group, from what the
    in-projections' matmuls left: ``raw`` the three [G, dk] blocks of
    ``[q | k | v]`` before the convolution, ``before`` the eight rows
    before each, ``w`` their taps; ``low`` [G, dk] the decay's
    pre-activation, ``bias`` and ``rate`` [1, dk] its ``dt_bias`` and
    ``exp(A_log)``; ``live`` [G, 1] the rows before ``length``. Returns
    q (unit length times dk^-0.5), k (unit length), v and g (the log of
    the decay, in ``[lower, 0]``, 0 on a row that is not live), as
    `glm5_next._kda_in`, the convolution, ``silu`` and `_kda_split` make
    them."""
    q, k, v = (
        jax.nn.silu(_taps(x, rows, taps))
        for x, rows, taps in zip(raw, before, w)
    )
    g = lower * jax.nn.sigmoid(rate * (low + bias))
    return (_unit(q, eps) * q.shape[1] ** -0.5, _unit(k, eps), v,
            jnp.where(live, g, 0.0))


def _epilogue(o, gate, weight, eps):
    """`glm5_next._kda_out` before its matmul, of one head's o [G, dv]:
    ``RMSNorm(o) * weight * sigmoid(gate)``."""
    var = jnp.mean(o * o, axis=1, keepdims=True)
    return o * jax.lax.rsqrt(var + eps) * weight * jax.nn.sigmoid(gate)


def _kernel(chunk, sub, group, groups, heads, dk, dv, lower, l2_eps,
            norm_eps, length_ref, *refs):
    """One (heads, token block) a grid step. Refs: ``length`` (scalar
    prefetch); the block's rows of the in-projection's q, k and v [B,
    heads x dk], three views of one array (dv = dk); the eight rows
    before the sequence [8, .] and the taps [K, .] of each; the decay's
    pre-activation [B, heads x dk] and its ``dt_bias`` over
    ``exp(A_log)`` [2, heads x dk]; the block's beta [B, H]; the output
    gate's pre-activation [B, heads x dv] and the head norm's weight [1,
    dv]; the heads' ``state0`` [heads, dk, dv]; out [B, heads x dv] in
    the model's dtype; the heads' state out; the state as the steps hold
    it, transposed [heads, dv, dk], and the last eight rows of q, k, v
    before the next group [3, 8, .], both resident over the token
    blocks."""
    raw_refs, before_refs, tap_refs = refs[0:3], refs[3:6], refs[6:9]
    (low_ref, decay_ref, beta_ref, gate_ref, norm_ref, s0_ref, o_ref, s_ref,
     st_ref, tail_ref) = refs[9:]
    h, s = pl.program_id(0), pl.program_id(1)
    length = length_ref[0]
    mine = range(heads)
    subs = chunk // sub

    @pl.when(s == 0)
    def _first():
        for i in mine:
            st_ref[i] = s0_ref[i].T
        for part, ref in enumerate(before_refs):
            tail_ref[part] = ref[...]

    row = jax.lax.broadcasted_iota(jnp.int32, (group, group), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (group, group), 1)
    together = (row ^ col) < chunk  # of one rule chunk (a power of two)
    # How many sub-chunks of its chunk a row lies past a column.
    shift = sub.bit_length() - 1
    ahead = ((row & (chunk - 1)) >> shift) - ((col & (chunk - 1)) >> shift)
    rows_in = jax.lax.broadcasted_iota(jnp.int32, (group, dk), 0) & (chunk - 1)
    in_group = jax.lax.broadcasted_iota(jnp.int32, (group, 1), 0)

    def one_group(at):
        """Every step below is taken for all the step's heads before the
        next one: what one head waits for, another computes."""
        lanes = [slice(i * dk, (i + 1) * dk) for i in mine]
        live = (s * groups * group + at.start) + in_group < length
        betas = beta_ref[at, :]
        beta = [
            jnp.where(live, _column(betas, h * heads + i), 0.0) for i in mine
        ]
        q, k, v, g = zip(*(
            _prologue(
                [ref[at, lanes[i]] for ref in raw_refs],
                [tail_ref[part, :, lanes[i]] for part in range(3)],
                [ref[:, lanes[i]] for ref in tap_refs],
                low_ref[at, lanes[i]], decay_ref[0:1, lanes[i]],
                decay_ref[1:2, lanes[i]], live, lower, l2_eps,
            )
            for i in mine
        ))
        # The next group's rows before it: this one's last eight, raw.
        for part, ref in enumerate(raw_refs):
            tail_ref[part] = ref[pl.ds(at.start + group - 8, 8), :]
        gamma = [_running_sum(g[i], rows_in, chunk) for i in mine]
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
            _dot(solve[i], jnp.concatenate(
                [(beta[i] * grow[i]) * k[i], beta[i] * v[i]], axis=1
            ))
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
            o = jnp.concatenate(reads[i], axis=0) + _dot(
                within[i], jnp.concatenate(news[i], axis=0)
            )
            o_ref[at, lanes[i]] = _epilogue(
                o, gate_ref[at, lanes[i]], norm_ref[...], norm_eps
            ).astype(o_ref.dtype)

    _live_groups(s, groups, group, length, o_ref, one_group)

    @pl.when(s == pl.num_programs(1) - 1)
    def _last():
        for i in mine:
            s_ref[i] = st_ref[i].T


@functools.partial(
    jax.jit,
    static_argnames=(
        "chunk", "sub", "lower", "l2_eps", "norm_eps", "dtype", "interpret"
    ),
)
def kda_chunk_rule(
    qkv: jnp.ndarray,  # [T, 3 H dk] float32: [q | k | v] before the convolution
    conv0: jnp.ndarray,  # [K - 1, 3 H dk]: the rows before qkv[0]
    conv_w: jnp.ndarray,  # [K, 3 H dk] float32
    low: jnp.ndarray,  # [T, H dk] float32: the decay's pre-activation
    dt_bias: jnp.ndarray,  # [H, dk] float32
    a_log: jnp.ndarray,  # [H] float32
    beta: jnp.ndarray,  # [T, H] float32
    gate: jnp.ndarray,  # [T, H dk] float32: the output gate's pre-activation
    gate_norm: jnp.ndarray,  # [dk] float32: the head norm's weight
    state0: jnp.ndarray,  # [H, dk, dv] float32, dv = dk
    length: jnp.ndarray,  # [] int32: how many of the T tokens are real
    *,
    chunk: int,
    sub: int,
    lower: float,
    l2_eps: float,
    norm_eps: float,
    dtype,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The KDA mixer between its in-projections and its out-projection
    over T tokens of one sequence, the rule in chunks of ``chunk`` and
    sub-chunks of ``sub`` (powers of two, ``sub`` at most ``chunk``).
    Returns (the gated, normed output [T, H x dv] in ``dtype``, whose
    rows from ``length`` on mean nothing, and the state after token
    ``length - 1``)."""
    t = qkv.shape[0]
    h, dk, dv = state0.shape
    if dk != dv:
        raise ValueError("q, k and v are three blocks of one width")
    taps = conv_w.shape[0]
    # A group holds the eight rows the next one's convolution reads.
    group, groups, steps = _blocking(max(t, 8), chunk)
    block = group * groups
    padded = steps * block
    heads = math.gcd(h, _HEADS_A_STEP)
    wide = h // heads  # blocks of `heads` heads across q, across k, across v
    width = heads * dk

    def rows(a):
        return a if padded == t else jnp.pad(a, ((0, padded - t), (0, 0)))

    def token_block(part=0):
        return pl.BlockSpec(
            (block, width),
            lambda hh, s, length: (_live_block(s, length, block), part * wide + hh),
        )

    def channels(n_rows, part=0):
        return pl.BlockSpec(
            (n_rows, width), lambda hh, s, length: (0, part * wide + hh)
        )

    state_block = pl.BlockSpec((heads, dk, dv), lambda hh, s, length: (hh, 0, 0))
    # A step's blocks in and out, twice (the pipeline's two buffers), the
    # state three times, and two dozen [G, dk + dv] float32 arrays a head
    # between products.
    moved = 4 * (
        block * heads * (4 * dk + 3 * dv) + block * h + 2 * heads * dk * dv
        + (8 + taps + 2) * heads * (2 * dk + dv)
    )
    held = 4 * (
        24 * heads * group * max(group, dk + dv) + heads * dk * dv
        + 8 * heads * (2 * dk + dv)
    )
    before = jnp.pad(conv0.astype(jnp.float32), ((8 - (taps - 1), 0), (0, 0)))
    decay = jnp.stack([dt_bias.reshape(-1), jnp.repeat(jnp.exp(a_log), dk)])
    qkv = rows(qkv)
    out, end = pl.pallas_call(
        functools.partial(
            _kernel, chunk, sub, group, groups, heads, dk, dv, lower, l2_eps,
            norm_eps,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(wide, steps),
            in_specs=[
                *(token_block(part) for part in range(3)),
                *(channels(8, part) for part in range(3)),
                *(channels(taps, part) for part in range(3)),
                token_block(),
                channels(2),
                pl.BlockSpec(
                    (block, h),
                    lambda hh, s, length: (_live_block(s, length, block), 0),
                ),
                token_block(),
                pl.BlockSpec((1, dv), lambda hh, s, length: (0, 0)),
                state_block,
            ],
            out_specs=[
                pl.BlockSpec((block, width), lambda hh, s, length: (s, hh)),
                state_block,
            ],
            scratch_shapes=[
                pltpu.VMEM((heads, dv, dk), jnp.float32),
                pltpu.VMEM((3, 8, width), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((padded, h * dv), dtype),
            jax.ShapeDtypeStruct((h, dk, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(2 * moved + held + (16 << 20), 96 << 20),
        ),
        interpret=interpret,
    )(
        jnp.asarray(length, jnp.int32).reshape(1),
        qkv, qkv, qkv, before, before, before, conv_w, conv_w, conv_w,
        rows(low), decay, rows(beta), rows(gate), gate_norm.reshape(1, dv),
        state0,
    )
    return out[:t], end
