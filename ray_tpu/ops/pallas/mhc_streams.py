"""The residual path of a model with several residual streams
(``models/glm5_next.py mhc_mix`` / ``mhc_spread``, manifold-constrained
hyper-connections) as two Pallas TPU kernels over tiles of tokens: a
sublayer reads the streams twice (for the mix, and after ``F`` for the
spread) and writes them once, and nothing of the path's float32
arithmetic is an array in HBM.

XLA's program of the two functions converts all ``[T, n, d]`` of the
bf16 streams to float32 and writes that down at every step: the square
mean, the unit row, a ``precision=HIGHEST`` product of ``[T, n d]``
float32 with ``P`` ``[n d, 2n + n n]`` (six bf16 passes for 24 columns),
Sinkhorn's rounds as a loop of small fusions, ``Hpre X``, then ``Hres
X``, ``Hpost^T y``, the sum and the cast: about ten passes over 67-134
MB at the served shape (2,048 tokens, four streams of 4,096), 1.4-1.8
ms a sublayer where its bytes take 0.2 (PERF.md section 6, PR 64).

Both calls see the streams as ``[T, n d]`` (a free reshape), a tile of
``_TILE`` tokens a grid step, and walk a tile's rows ``_ROWS`` at a
time and its lanes ``_LANES`` at a time, so that what lives between a
load and a store fits the vector registers.

**`mhc_mix`** reads ``x`` and the sublayer's ``P`` (transposed: a
bitcast of how the chip holds a ``[n d, 24]`` array), ``a`` and ``b``
(scalars in SMEM); writes ``h = Hpre X`` ``[T, d]`` in the streams'
dtype, ``Hres`` ``[T, n n]`` and ``Hpost`` ``[T, n]`` float32. Nothing
is made before the call: its operands are the parameter leaves.

- The row's mean square over all ``n d`` numbers, float32 on the vector
  unit: squares summed lane by lane over the row's chunks, then one sum
  across the lanes.
- ``x~ P`` is linear in the row's scale, so the product is taken of the
  raw streams and multiplied by ``rsqrt(var + eps)`` afterwards. The
  streams are bf16 VALUES, so only ``P`` needs splitting to keep
  ``HIGHEST``'s accuracy: the call's FIRST grid step cuts the float32
  ``P^T`` into three bf16 parts (`_nearest_bf16`, on the bits) and lays
  them into one ``[128, n d]`` scratch operand, each part's 24 rows
  from a row offset of its own (``_PART``), which the later steps keep
  (the grid runs in order); the three passes are then ONE pass of the
  matrix unit against 128 rows, whose result carries the parts' sums
  at three lane offsets, added in float32, smallest first (two lane
  rolls). Streams of any other dtype are multiplied as float32 at
  ``precision=HIGHEST`` with ``P^T`` as it is.
- The two sigmoids and the ``exp`` on the ``[tile, 128]`` result, token
  major, lanes as ``P``'s columns (``Hpre``'s n, ``Hpost``'s n,
  ``Hres``' n n); then the 16 numbers a token are TRANSPOSED, tokens
  along the lanes (``[128, tile]``, of which rows 8-23 are ``Hres``' ``4
  i + j``): a token's 4 x 4 is then 16 sublanes of one lane, a tile's
  Sinkhorn works on two vector registers a 128 tokens, and the sums are
  sublane rolls: over ``j`` (four consecutive rows) two rounds of a
  roll either way under a mask, over ``i`` (rows 4 apart, cyclic in 16)
  two rolls. ``iters`` rounds of exact divisions, then the transpose
  back.
- ``h = sum_s Hpre[s] x_s``, float32 from the load of a stream to the
  cast.

**`mhc_spread`** reads ``x``, ``out`` ``[T, d]``, ``Hres``, ``Hpost``;
writes ``Hres X + Hpost^T out`` in the streams' dtype over ``x``'s
buffer (``input_output_aliases``; XLA copies where the caller still
needs ``x``), float32 between.

At the served shape the two move 67 + 17 and 67 + 17 + 67 MB a
sublayer: 0.29 ms at the HBM peak. The benchmark's yardstick
(``benchmarks/models/glm5_next.py mhc_bytes_per_program``) counts ONE
read and one write of the streams a sublayer, 0.20 ms: a spread fused
with the next sublayer's mix would reach it, and would change
``hybrid_kv._read`` / ``_residual``'s contract across sublayers (ROADMAP
S20 (c)).

Ten sublayers a call, the streams handed from a spread to the next mix,
on a v5e (`scripts/glm5_next_layer.py mhc`, my chip run, PR 64), ms a
sublayer: at 2,048 tokens **0.288** for XLA's form's 1.82 (the two
calls' bytes take 0.29 at the HBM peak; in `glm53flash-longctx-16`'s
chunk programs a `mhc_mix` call reads 0.13 ms in the trace, a
`mhc_spread` 0.10 and the whole residual path 0.28 ms a sublayer), the three ``H`` within 1.3e-6 of
XLA's form's and ``h`` and the streams within a bf16 unit; at a decode
step's 32 / 16 rows 0.076 / 0.065 for XLA's 0.065 / 0.064, which is why
`mhc._MHC_KERNEL_ROWS` leaves those shapes to XLA. By (tokens a
step, rows, lanes between a load and a store): (128, 16, 512) 0.292,
(128, 32, 512) 0.297, (128, 16, 256) 0.299, (128, 16, 1024) 0.311,
(256, 32, 1024) 0.364, (256, 16, 512) 0.373, (512, 16, 512) 0.399. One
call alone, timed from the host, reads the host's dispatch (~0.6 ms on
the one-chip machine), not the device: chain the calls.

A token count that is no multiple of the tile leaves a last block that
hangs over the arrays' end, fewer tokens than a tile one such block:
what a row past the end holds stays in that row (the product's rows,
the transposes' columns and Sinkhorn's lanes are a token each) and is
not written back.

The same arithmetic as XLA's form: float32 from the streams' load to
the result's cast, exact ``exp``, sigmoids and divisions, ``eps`` and
``iters`` the caller's. Forward only. Off the TPU ``mhc.mhc_mix``
/ ``mhc_spread`` keep XLA's form, which is tier 1's path and these
kernels' oracle (tests/test_mhc_streams_kernel.py, interpreted).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Tokens a grid step: 4 MB of bf16 streams a buffer at the served width.
# (This and the next two by the chip: the table in the module's docstring.)
_TILE = 128
# Rows and lanes between a load and a store: [16, 512] float32 is eight
# vector registers an array (16 rows: one packed bf16 register's).
_ROWS = 16
_LANES = 512
# The matrix unit's width, and the lane offset of each bf16 part of P in
# the one operand: 2n + n n columns a part (24 at four streams).
_WIDTH = 128
_PART = 32


def _lane_chunks(width: int):
    """Static (start, size) pieces of ``width`` lanes, `_LANES` at most."""
    return [(lo, min(_LANES, width - lo)) for lo in range(0, width, _LANES)]


def _row_groups(tile: int, body) -> None:
    """``body(rows)`` for each group of `_ROWS` rows of a tile."""
    def step(g, carry):
        body(pl.ds(pl.multiple_of(g * _ROWS, _ROWS), _ROWS))
        return carry

    jax.lax.fori_loop(0, tile // _ROWS, step, 0)


def _sinkhorn(m, n: int, iters: int, eps: float):
    """`mhc.sinkhorn` on m [n n, tokens]: a token a lane, row ``n
    i + j`` its matrix's entry (i, j); ``n`` a power of two."""
    size = n * n
    row = jax.lax.broadcasted_iota(jnp.int32, m.shape, 0)

    def over_j(m):
        # Rows n i .. n i + n - 1: a butterfly, the partner r ^ step.
        step = 1
        while step < n:
            m = m + jnp.where(
                row & step == 0,
                pltpu.roll(m, size - step, 0), pltpu.roll(m, step, 0),
            )
            step *= 2
        return m

    def over_i(m):
        # Rows j, n + j, ..: cyclic in the n n rows.
        step = n
        while step < size:
            m = m + pltpu.roll(m, step, 0)
            step *= 2
        return m

    def one(_, m):
        m = m / (over_j(m) + eps)
        return m / (over_i(m) + eps)

    return jax.lax.fori_loop(0, iters, one, m)


def _nearest_bf16(a):
    """float32 ``a`` rounded to the nearest bf16 value (ties to even),
    still float32: on the bits, where no compiler folds it away as it
    may a pair of converts."""
    bits = jax.lax.bitcast_convert_type(a, jnp.int32)
    bits = bits + 0x7FFF + ((bits >> 16) & 1)
    return jax.lax.bitcast_convert_type(bits & -0x10000, jnp.float32)


def _mix_kernel(n, d, iters, eps, split, x_ref, p_ref, a_ref, b_pre_ref,
                b_post_ref, b_res_ref, h_ref, res_ref, post_ref, w_ref,
                sq_ref, pre_ref):
    tile = x_ref.shape[0]
    size, used = n * n, 2 * n + n * n
    f32 = jnp.float32

    @pl.when(pl.program_id(0) == 0)
    def _operand():
        # P^T's rows, each bf16 part from a row (the product's lane)
        # offset of its own; the steps run in order and keep it.
        p = p_ref[...]
        gap = jnp.zeros((_PART - used, n * d), f32)
        if split:
            hi = _nearest_bf16(p)
            mid = _nearest_bf16(p - hi)
            parts = [hi, mid, p - hi - mid]
        else:
            parts = [p]
        for k, part in enumerate(parts):
            w_ref[k * _PART:(k + 1) * _PART, :] = jnp.concatenate(
                [part, gap], axis=0
            ).astype(w_ref.dtype)
        rest = len(parts) * _PART
        w_ref[rest:, :] = jnp.zeros((_WIDTH - rest, n * d), w_ref.dtype)

    def squares(rows):
        # Lane by lane over the chunks, then ONE sum across the lanes.
        by_lane = {}
        for lo, width in _lane_chunks(n * d):
            part = x_ref[rows, lo:lo + width].astype(f32)
            by_lane[width] = by_lane.get(width, 0.0) + part * part
        sq_ref[rows, :] = sum(
            jnp.sum(lanes, axis=1, keepdims=True) for lanes in by_lane.values()
        )

    _row_groups(tile, squares)
    wide = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
        precision=None if split else jax.lax.Precision.HIGHEST,
        preferred_element_type=f32,
    )  # [tile, 128]
    if split:
        # The three parts' sums, smallest first.
        wide = (
            pltpu.roll(wide, _WIDTH - 2 * _PART, 1)
            + pltpu.roll(wide, _WIDTH - _PART, 1)
        ) + wide
    # Lanes as P's columns: Hpre's n, Hpost's n, Hres' n n.
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _WIDTH), 1)
    a = jnp.where(
        lane < n, a_ref[0],
        jnp.where(lane < 2 * n, a_ref[1],
                  jnp.where(lane < used, a_ref[2], 0.0)),
    )
    b = jnp.zeros((1, _WIDTH), f32)
    for k in range(n):
        b = jnp.where(lane == k, b_pre_ref[k], b)
        b = jnp.where(lane == n + k, b_post_ref[k], b)
        for j in range(n):
            b = jnp.where(lane == 2 * n + n * k + j, b_res_ref[k, j], b)
    var = sq_ref[...] / (n * d)
    z = a * (wide * jax.lax.rsqrt(var + eps)) + b
    gates = jnp.where(lane < n, 1.0, 2.0) * jax.nn.sigmoid(z)
    h_res = _sinkhorn(jnp.exp(z).T[2 * n:used], n, iters, eps)  # [n n, tile]
    back = jnp.concatenate(
        [h_res, jnp.zeros((_WIDTH - size, tile), f32)], axis=0
    ).T
    res_ref[...] = back[:, :size]
    post_ref[...] = pltpu.roll(gates, _WIDTH - n, 1)[:, :n]
    pre_ref[...] = gates

    def read(rows):
        pre = pre_ref[rows, :]
        for lo, width in _lane_chunks(d):
            h = pre[:, 0:1] * x_ref[rows, lo:lo + width].astype(f32)
            for s in range(1, n):
                at = s * d + lo
                h += pre[:, s:s + 1] * x_ref[rows, at:at + width].astype(f32)
            h_ref[rows, lo:lo + width] = h.astype(h_ref.dtype)

    _row_groups(tile, read)


def _spread_kernel(n, d, x_ref, y_ref, res_ref, post_ref, o_ref):
    f32 = jnp.float32

    def write(rows):
        res = res_ref[rows, :]
        post = post_ref[rows, :]
        for lo, width in _lane_chunks(d):
            y = y_ref[rows, lo:lo + width].astype(f32)
            streams = [
                x_ref[rows, j * d + lo:j * d + lo + width].astype(f32)
                for j in range(n)
            ]
            for i in range(n):
                mixed = res[:, n * i:n * i + 1] * streams[0]
                for j in range(1, n):
                    mixed += res[:, n * i + j:n * i + j + 1] * streams[j]
                at = i * d + lo
                o_ref[rows, at:at + width] = (
                    mixed + post[:, i:i + 1] * y
                ).astype(o_ref.dtype)

    _row_groups(x_ref.shape[0], write)


def _tokens(width: int) -> pl.BlockSpec:
    """A tile's rows of an array [tokens, width]."""
    return pl.BlockSpec((_TILE, width), lambda i: (i, 0))


def _limit(held: int) -> int:
    """A call's VMEM: its blocks twice (the pipeline's two buffers) and
    room for what the compiler keeps between them."""
    return min(2 * held + (24 << 20), 100 << 20)


@functools.partial(jax.jit, static_argnames=("iters", "eps", "interpret"))
def mhc_mix(
    x: jnp.ndarray,  # [.., n, d]: the streams
    proj: jnp.ndarray,  # [n d, 2n + n n] float32: P_pre | P_post | P_res
    scale: jnp.ndarray,  # [3] float32: a_pre, a_post, a_res
    b_pre: jnp.ndarray,  # [n] float32
    b_post: jnp.ndarray,  # [n] float32
    b_res: jnp.ndarray,  # [n, n] float32
    *,
    iters: int,
    eps: float,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """`mhc.mhc_mix`'s three results: ``h = Hpre X`` [.., d] in
    ``x``'s dtype, ``Hres`` [.., n, n] and ``Hpost`` [.., n] float32."""
    *lead, n, d = x.shape
    size, used = n * n, 2 * n + n * n
    if n & (n - 1) or used > _PART:
        raise ValueError("the streams' count is a power of two, four at most")
    flat = x.reshape(-1, n * d)
    t = flat.shape[0]
    split = x.dtype == jnp.bfloat16
    operand = jnp.bfloat16 if split else jnp.float32
    held = (
        _TILE * (n + 1) * d * x.dtype.itemsize + used * n * d * 4
        + 4 * _TILE * _WIDTH * 4
    )
    small = pl.BlockSpec(memory_space=pltpu.SMEM)
    h, h_res, h_post = pl.pallas_call(
        functools.partial(_mix_kernel, n, d, iters, eps, split),
        grid=(pl.cdiv(t, _TILE),),
        in_specs=[
            _tokens(n * d), pl.BlockSpec((used, n * d), lambda i: (0, 0)),
            small, small, small, small,
        ],
        out_specs=[_tokens(d), _tokens(size), _tokens(n)],
        out_shape=[
            jax.ShapeDtypeStruct((t, d), x.dtype),
            jax.ShapeDtypeStruct((t, size), jnp.float32),
            jax.ShapeDtypeStruct((t, n), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((_WIDTH, n * d), operand),
            pltpu.VMEM((_TILE, 1), jnp.float32),
            pltpu.VMEM((_TILE, _WIDTH), jnp.float32),
        ],
        # The first step makes the product's operand for the rest.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_limit(
                held + _WIDTH * n * d * jnp.dtype(operand).itemsize // 2
            ),
        ),
        interpret=interpret,
    )(
        flat, proj.astype(jnp.float32).T, scale.astype(jnp.float32),
        b_pre.astype(jnp.float32), b_post.astype(jnp.float32),
        b_res.astype(jnp.float32),
    )
    return (
        h.reshape(*lead, d), h_res.reshape(*lead, n, n),
        h_post.reshape(*lead, n),
    )


@functools.partial(jax.jit, static_argnames="interpret")
def mhc_spread(
    x: jnp.ndarray,  # [.., n, d]: the streams before the sublayer
    out: jnp.ndarray,  # [.., d]: the sublayer's output
    h_res: jnp.ndarray,  # [.., n, n] float32
    h_post: jnp.ndarray,  # [.., n] float32
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """`mhc.mhc_spread`: ``Hres X + Hpost^T out`` [.., n, d] in
    ``x``'s dtype, written over ``x`` where the program lets it."""
    *_, n, d = x.shape
    flat = x.reshape(-1, n * d)
    t = flat.shape[0]
    held = _TILE * ((2 * n + 1) * d * x.dtype.itemsize + 2 * _WIDTH * 4)
    wrote = pl.pallas_call(
        functools.partial(_spread_kernel, n, d),
        grid=(pl.cdiv(t, _TILE),),
        in_specs=[_tokens(n * d), _tokens(d), _tokens(n * n), _tokens(n)],
        out_specs=_tokens(n * d),
        out_shape=jax.ShapeDtypeStruct(flat.shape, x.dtype),
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_limit(held),
        ),
        interpret=interpret,
    )(
        flat, out.reshape(t, d),
        h_res.reshape(t, n * n).astype(jnp.float32),
        h_post.reshape(t, n).astype(jnp.float32),
    )
    return wrote.reshape(x.shape)
