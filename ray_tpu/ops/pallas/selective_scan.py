"""Mamba-1's selective scan as two Pallas TPU kernels: a prefill chunk's
walk over its tokens, and a decode step's one-token update of the slots
that decode, in place.

The recurrence (Gu & Dao 2023, arXiv:2312.00752; ``models/phi4_flash.py``
has the mixer around it), a channel ``c`` of ``d_inner`` and a state
index ``n`` of ``N``:

    h_t[n, c] = exp(dt_t[c] A[n, c]) h_{t-1}[n, c] + dt_t[c] B_t[n] x_t[c]
    y_t[c]    = sum_n C_t[n] h_t[n, c] + D[c] x_t[c]

A decay a CHANNEL AND STATE INDEX and one ``B``, ``C`` for all channels:
there is no head whose scalar decay would turn a chunk into matrix
products (``ops/pallas/ssd_chunk.py``'s dual form does not compute it),
so the chunk is walked a token at a time on the vector units. Written
with ``lax.scan`` or an associative scan XLA materialises ``[T, N,
d_inner]`` float32 (671 MB a layer at 2,048 tokens and 5,120 channels);
here the state never leaves VMEM inside a chunk.

**The layout.** The state is held ``[N, d_inner / 128, 128]``: a state
index is a whole ``[R, 128]`` tile of channels, so that ``B_t[n]`` and
``C_t[n]`` are SCALARS of the update (read from SMEM and splat), the sum
over ``n`` is sixteen multiply-adds of tiles and nothing is reduced
across lanes or sublanes. The cache's leaf has that shape (``llm/
hybrid_kv.py``: ``[layers, slots, N, R, 128]`` float32), so neither
kernel's operand is a copy. ``x``, ``dt`` and ``y`` are ``[T, R, 128]``
views of ``[T, d_inner]``.

**`selective_scan_chunk`**: a grid over blocks of ``_BLOCK_T`` tokens, in
order; the state in a VMEM scratch across them, from ``h0`` before the
first and to the result after the last. ``dt`` comes as the projection
left it: its bias and softplus are taken here, as the published kernel
does (``delta_softplus``), so the float32 ``dt`` is never written. A
position from ``length`` on takes no step (``dt`` 0: the decay is 1 and
nothing is added) and a block that lies past it whole is skipped, its
``y`` zeros. Per layer at 2,048 tokens: x and dt in, y out (63 MB in
bfloat16), B and C once (0.26 MB), the state once each way (0.66 MB).

**`selective_state_step`**: ``ops/pallas/state_step.py``'s skeleton (the
stack aliased in and out, the slots in `live_order`'s visiting order and
their count through scalar prefetch, a step past the count naming the
block that is already there) with this recurrence's body. A slot's
``B_t`` and ``C_t`` come broadcast along lanes (``[2N, 128]`` a slot: a
row of it is a tile's multiplier), since a float is no scalar-prefetch
operand. Skipped slots' rows of ``y`` are zeros.

Forward only, as the serving programs are. Float32 inside; what differs
from ``lax.scan`` in float32 is the order of the sum over ``n`` and the
exponentials' last bits.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Tokens a grid step. Three blocks of [_BLOCK_T, R, 128] (x, dt, y), each
# double-buffered: 7.5 MB in bfloat16 at R = 40 (held 48 rows a token).
_BLOCK_T = 128


def _softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log(1.0 + jnp.exp(-jnp.abs(x)))


def _token(h_in, h_out, a_ref, x, dt, b_of, c_of, n_state: int):
    """One token's update of a state [N, R, 128] (read from ``h_in`` and
    written to ``h_out`` a state index at a time; they may be one ref)
    and its read-out: x, dt [R, 128] float32; ``b_of(n)`` / ``c_of(n)``
    what multiplies a tile at state index n. Returns ``sum_n C[n]
    h[n]``."""
    dtx = dt * x
    y = jnp.zeros_like(x)
    for n in range(n_state):
        h = h_in[n] * jnp.exp(dt * a_ref[n]) + dtx * b_of(n)
        h_out[n] = h
        y = y + h * c_of(n)
    return y


def _chunk_kernel(length_ref, x_ref, dt_ref, bc_ref, a_ref, d_ref, bias_ref,
                  h0_ref, y_ref, end_ref, h_ref, *, block_t: int,
                  n_state: int):
    i = pl.program_id(0)
    base = i * block_t
    length = length_ref[0]

    @pl.when(i == 0)
    def _first():
        h_ref[...] = h0_ref[...]

    @pl.when(base < length)
    def _live():
        def token(t, carry):
            x = x_ref[t].astype(jnp.float32)
            dt = _softplus(dt_ref[t].astype(jnp.float32) + bias_ref[...])
            dt = jnp.where(base + t < length, dt, 0.0)
            y = _token(
                h_ref, h_ref, a_ref, x, dt, lambda n: bc_ref[t, n],
                lambda n: bc_ref[t, n_state + n], n_state,
            )
            y_ref[t] = (y + d_ref[...] * x).astype(y_ref.dtype)
            return carry

        jax.lax.fori_loop(0, block_t, token, 0)

    @pl.when(base >= length)
    def _padding():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(i == pl.num_programs(0) - 1)
    def _last():
        end_ref[...] = h_ref[...]


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def selective_scan_chunk(
    x: jnp.ndarray,  # [T, d_inner]: the convolution's output, silu'd
    dt: jnp.ndarray,  # [T, d_inner]: W_dt d, before the bias and softplus
    b: jnp.ndarray,  # [T, N]
    c: jnp.ndarray,  # [T, N]
    a: jnp.ndarray,  # [N, R, 128] float32: -exp(A_log)
    d: jnp.ndarray,  # [d_inner] float32
    dt_bias: jnp.ndarray,  # [d_inner] float32
    h0: jnp.ndarray,  # [N, R, 128] float32: the state before x[0]
    length: jnp.ndarray,  # [] int32: how many of the T tokens are real
    *,
    block_t: int | None = None,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The scan over one chunk of one sequence. Returns (y [T, d_inner]
    in x's dtype, with the skip ``D x`` in it and before any gate; the
    state after token ``length - 1``, float32 [N, R, 128])."""
    t, width = x.shape
    n_state, rows, lanes = h0.shape
    if rows * lanes != width:
        raise ValueError(f"a state of {rows} x {lanes} for {width} channels")
    block_t = min(block_t or _BLOCK_T, t)
    if t % block_t:
        raise ValueError(f"{t} tokens do not divide into blocks of {block_t}")
    tile = (rows, lanes)
    bc = jnp.concatenate([b, c], axis=-1).astype(jnp.float32)  # [T, 2N]

    def tokens(i, length):
        return i, 0, 0

    def whole(i, length):
        return 0, 0, 0

    per_token = pl.BlockSpec((block_t, *tile), tokens)
    state = pl.BlockSpec((n_state, *tile), whole)
    channel = pl.BlockSpec(tile, lambda i, length: (0, 0))
    held = -(-rows // 16) * 16 * lanes  # a token's tile as VMEM holds it
    vmem = 6 * block_t * held * 4 + 8 * n_state * held * 4
    y, end = pl.pallas_call(
        functools.partial(_chunk_kernel, block_t=block_t, n_state=n_state),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(t // block_t,),
            in_specs=[
                per_token, per_token,
                pl.BlockSpec(
                    (block_t, 2 * n_state), lambda i, length: (i, 0),
                    memory_space=pltpu.SMEM,
                ),
                state, channel, channel, state,
            ],
            out_specs=[per_token, state],
            scratch_shapes=[pltpu.VMEM((n_state, *tile), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((t, *tile), x.dtype),
            jax.ShapeDtypeStruct(h0.shape, jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem + 16 * 1024 * 1024,
        ),
        interpret=interpret,
    )(
        jnp.asarray(length, jnp.int32).reshape(1),
        x.reshape(t, *tile), dt.reshape(t, *tile), bc,
        a.astype(jnp.float32), d.astype(jnp.float32).reshape(tile),
        dt_bias.astype(jnp.float32).reshape(tile), h0.astype(jnp.float32),
    )
    return y.reshape(t, width), end


def _step_kernel(layer_ref, order_ref, count_ref, x_ref, dt_ref, bc_ref,
                 a_ref, s_in, s_out, y_ref, *, n_state: int):
    """One slot in visiting order a grid step. Refs: scalar prefetch
    (layer, order, count), the slot's x and dt [R, 128] and its B and C
    along lanes [2N, 128], A, the slot's state in and out, its y."""
    i = pl.program_id(0)
    count = count_ref[0]

    @pl.when(i < count)
    def _live():
        y_ref[...] = _token(
            s_in, s_out, a_ref, x_ref[...], dt_ref[...],
            lambda n: bc_ref[n: n + 1, :],
            lambda n: bc_ref[n_state + n: n_state + n + 1, :], n_state,
        )

    @pl.when(i >= count)
    def _skipped():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(jnp.logical_and(count == 0, i == 0))
    def _none_live():
        # The one block the index map names all along: back as it came.
        s_out[...] = s_in[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_state_step(
    stack: jnp.ndarray,  # [L, B, N, R, 128] float32: every layer's state
    layer: jnp.ndarray,  # [] int32: the layer stepped
    order: jnp.ndarray,  # [B] int32: `state_step.live_order`
    count: jnp.ndarray,  # [1] int32
    x: jnp.ndarray,  # [B, d_inner] float32
    dt: jnp.ndarray,  # [B, d_inner] float32: after the bias and softplus
    b: jnp.ndarray,  # [B, N] float32
    c: jnp.ndarray,  # [B, N] float32
    a: jnp.ndarray,  # [N, R, 128] float32: -exp(A_log)
    *,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One token's update for the first ``count`` slots of ``order`` in
    ``stack[layer]``, in place. Returns (the stack, ``sum_n C[n] h[n]``
    [B, d_inner] float32 without the skip: zeros for the other slots)."""
    _, slots, n_state, rows, lanes = stack.shape
    tile = (rows, lanes)
    bc = jnp.concatenate([b, c], axis=-1).astype(jnp.float32)
    bc = jnp.broadcast_to(bc[:, :, None], (*bc.shape, lanes))  # [B, 2N, 128]

    def slot_of(i, order, count):
        return order[jnp.clip(i, 0, jnp.maximum(count[0] - 1, 0))]

    def state_block(i, layer, order, count):
        # Past the live slots: the last live block again, so no copy.
        return layer[0], slot_of(i, order, count), 0, 0, 0

    def slot_block(i, layer, order, count):
        return slot_of(i, order, count), 0, 0

    state_spec = pl.BlockSpec((None, None, n_state, *tile), state_block)
    row_spec = pl.BlockSpec((None, *tile), slot_block)
    stack, y = pl.pallas_call(
        functools.partial(_step_kernel, n_state=n_state),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(slots,),
            in_specs=[
                row_spec, row_spec,
                pl.BlockSpec((None, 2 * n_state, lanes), slot_block),
                pl.BlockSpec(
                    (n_state, *tile), lambda i, layer, order, count: (0, 0, 0)
                ),
                state_spec,
            ],
            out_specs=[
                state_spec,
                # Every slot's rows, the skipped ones' too (zeros).
                pl.BlockSpec(
                    (None, *tile),
                    lambda i, layer, order, count: (order[i], 0, 0),
                ),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(stack.shape, stack.dtype),
            jax.ShapeDtypeStruct((slots, *tile), jnp.float32),
        ],
        # Operands count the scalar-prefetch arrays: the stack is the
        # last input, aliased to the first result.
        input_output_aliases={7: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), order, count,
        x.astype(jnp.float32).reshape(slots, *tile),
        dt.astype(jnp.float32).reshape(slots, *tile), bc,
        a.astype(jnp.float32), stack,
    )
    return stack, y.reshape(slots, rows * lanes)


def selective_scan_reference(x, dt, b, c, a, d, dt_bias, h0, length):
    """XLA's form, a token a step of ``lax.scan``: what runs off the TPU
    and what the kernels are tested against. Arguments and results as
    `selective_scan_chunk`'s."""
    t, width = x.shape
    a2 = a.reshape(a.shape[0], width)
    step = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
    step = jnp.where(jnp.arange(t)[:, None] < length, step, 0.0)

    def token(h, inputs):
        x_t, dt_t, b_t, c_t = inputs
        h = h * jnp.exp(dt_t[None, :] * a2) + (dt_t * x_t)[None, :] * b_t[:, None]
        return h, (h * c_t[:, None]).sum(0)

    f32 = lambda v: v.astype(jnp.float32)  # noqa: E731
    end, y = jax.lax.scan(
        token, f32(h0).reshape(a2.shape), (f32(x), step, f32(b), f32(c))
    )
    return (y + d * f32(x)).astype(x.dtype), end.reshape(h0.shape)
