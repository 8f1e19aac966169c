"""A decode step's recurrent-state update as a Pallas TPU kernel: one
pass over the state of the slots that decode, in place.

``llm/hybrid_kv.py hybrid_decode`` keeps each decode slot's recurrent
state in one stack a kind of block (``[layers, slots, H, P, N]`` float32
for Mamba-2, ``[layers, slots, Hv, dk, dv]`` for the gated delta rule).
XLA's own form of a layer's step reads every slot's state for the
read-out, reads it again for the update and writes every slot back under
a mask, dead slots' included: three passes over ``slots`` states where
the arithmetic needs one read and one write of each slot that decodes
(PERF.md section 6, PR 53). This kernel makes that one pass:

- **The stack is aliased in and out** (``input_output_aliases``, as
  ``kv_cell_write`` aliases the page pool): whatever the kernel does not
  name stays where it lies, other layers' state and dead slots' among it.
- **A grid over (live slot, head tile).** The slots in visiting order
  (the decoding ones first), their count and the layer come through
  scalar prefetch. A step past the count names the state block that is
  already there and does nothing to it, so Pallas issues no copy: a slot
  that does not decode is neither read nor written. (With no slot
  decoding at all one block is fetched and written back as it was.)
- **One read, one write a tile.** A head's state is loaded into VMEM
  once, updated there in float32 elementwise arithmetic exactly as
  ``models/nemotron_h.py mamba_step`` / ``models/qwen3_next.py gdn_step``
  define the step (no product rounds the state; the delta rule's decay,
  read, correction and read-out all on the tile while it is resident),
  stored once, and the head's ``y`` / ``o`` row leaves with it.
- **Skipped slots' rows of ``y`` / ``o`` are zeros**: they go on through
  the layer like the others' and must stay finite.

Two bodies, for the two recurrences' algebra; one skeleton (`_step`):
grid, visiting order, aliasing, a head tile taken from the shapes
(`_head_tile`) and, inside a tile, `_HEADS_AT_ONCE` heads a step of the
loop as one vector operation (a head alone leaves the vector units
waiting on each row-to-column turn and each sum over lanes: 325 GB/s of
the live slots' bytes at granite's shape, 590 with eight; PERF.md
section 6, PR 53). What differs a head are rows of the slot's small
operands (VMEM, fetched once a slot): a number a head comes broadcast
along the state's minor dimension; a vector that multiplies along the
second-minor one (Mamba-2's ``x dt``, the delta rule's ``k`` and ``q``)
is turned from a row into a column in the kernel.

Forward only, as the decode program is. The sum over the minor
dimension (128 lanes) may be taken in another order than XLA takes it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# What one state block may take: the heads of a slot are tiled so that a
# block is at most this (in and out, double-buffered: four of them in
# VMEM). A step of the grid costs ~0.35 us whatever it moves; at
# granite's shape blocks of 4 / 2 / 1 / 0.5 MiB read alike down to 1 MiB
# and 6% slower at 0.5 (PERF.md section 6, PR 53).
_BLOCK_BYTES = 2 * 1024 * 1024
# Heads updated as one vector operation, and how many such operations a
# trip of the loop over a tile holds (the compiler overlaps them).
_HEADS_AT_ONCE = 8
_UNROLL = 2


def _head_tile(heads: int, head_bytes: int, block_bytes: int) -> int:
    """Heads a block: the most that divide ``heads`` and fit the budget."""
    return max(
        t for t in range(1, heads + 1)
        if heads % t == 0 and (t * head_bytes <= block_bytes or t == 1)
    )


def live_order(active: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The slots in the kernel's visiting order (the active ones first,
    each part in slot order) int32 [B], and how many are active [1]."""
    order = jnp.argsort(jnp.logical_not(active), stable=True)
    return order.astype(jnp.int32), active.sum(dtype=jnp.int32).reshape(1)


def _kernel(body, tile, at_once, layer_ref, order_ref, count_ref, *refs):
    """One (slot in visiting order, head tile) a grid step. Refs: scalar
    prefetch (layer, order, count), the slot's small operands, the state
    tile in, the state tile out, the slot's ``y`` rows."""
    *rows, s_in, s_out, y_out = refs
    i, j = pl.program_id(0), pl.program_id(1)
    count = count_ref[0]
    unroll = _UNROLL if tile % (at_once * _UNROLL) == 0 else 1

    @pl.when(i < count)
    def _live():
        def some_heads(trip, carry):
            for u in range(unroll):
                at = pl.multiple_of((trip * unroll + u) * at_once, at_once)
                h = pl.multiple_of(j * tile + at, at_once)  # in the slot
                new, y = body(s_in[pl.ds(at, at_once)], h, rows)
                s_out[pl.ds(at, at_once)] = new
                y_out[pl.ds(h, at_once), :] = y
            return carry

        jax.lax.fori_loop(0, tile // (at_once * unroll), some_heads, 0)

    @pl.when(i >= count)
    def _skipped():
        y_out[...] = jnp.zeros_like(y_out)

    @pl.when(jnp.logical_and(count == 0, jnp.logical_and(i == 0, j == 0)))
    def _none_live():
        # The one block the index map names all along: back as it came.
        s_out[...] = s_in[...]


def _mamba_body(per_group: int, at_once: int):
    def body(s, h, rows):
        """s [n, P, N] of heads h.. (all of one group)."""
        keep, xdt, b, c = rows
        heads, group = pl.ds(h, at_once), pl.ds(h // per_group, 1)
        s = (
            s * keep[heads, :][:, None, :]
            + xdt[heads, :][:, :, None] * b[group, :][None]
        )
        return s, jnp.sum(s * c[group, :][None], axis=-1)

    return body


def _gdn_body(at_once: int):
    def body(s, h, rows):
        """s [n, dk, dv] of value heads h.. ."""
        decay, beta, q, k, v = (row[pl.ds(h, at_once), :] for row in rows)
        k_col = k[:, :, None]
        s = s * decay[:, None, :]
        read = jnp.sum(s * k_col, axis=1)  # S^T k: [n, dv]
        s = s + k_col * (beta * (v - read))[:, None, :]
        return s, jnp.sum(s * q[:, :, None], axis=1)

    return body


def _kda_body(at_once: int):
    def body(s, h, rows):
        """s [n, dk, dv] of heads h..; `_gdn_body` with a decay a key
        channel (``decay`` [n, dk]) in place of one a head."""
        decay, beta, q, k, v = (row[pl.ds(h, at_once), :] for row in rows)
        k_col = k[:, :, None]
        s = s * decay[:, :, None]
        read = jnp.sum(s * k_col, axis=1)  # S^T k: [n, dv]
        s = s + k_col * (beta * (v - read))[:, None, :]
        return s, jnp.sum(s * q[:, :, None], axis=1)

    return body


def _step(body, stack, layer, order, count, rows, width, share,
          block_bytes, interpret):
    """The skeleton: ``stack`` [L, B, H, a, b] float32 aliased to the
    first result; ``rows`` [B, r, n] float32 each (a slot's are one
    block); ``body(at_once)`` steps that many heads, which must divide
    ``share``; the second result [B, H, width] float32, a row a head."""
    _, slots, heads, a, b = stack.shape
    tile = _head_tile(heads, a * b * 4, block_bytes or _BLOCK_BYTES)
    tiles = heads // tile
    at_once = math.gcd(_HEADS_AT_ONCE, tile, share)

    def slot_of(i, order, count):
        return order[jnp.clip(i, 0, jnp.maximum(count[0] - 1, 0))]

    def state_block(i, j, layer, order, count):
        # Past the live slots: the last live block again, so no copy.
        return (
            layer[0], slot_of(i, order, count),
            jnp.where(i < count[0], j, tiles - 1), 0, 0,
        )

    def slot_block(i, j, layer, order, count):
        return slot_of(i, order, count), 0, 0

    state_spec = pl.BlockSpec((None, None, tile, a, b), state_block)
    vmem = 4 * tile * a * b * 4 + 4 * sum(
        r.shape[1] * max(r.shape[2], 128) * 4 for r in rows
    ) + 2 * heads * max(width, 128) * 4
    return pl.pallas_call(
        functools.partial(_kernel, body(at_once), tile, at_once),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(slots, tiles),
            in_specs=[
                *(
                    pl.BlockSpec((None, *r.shape[1:]), slot_block)
                    for r in rows
                ),
                state_spec,
            ],
            out_specs=[
                state_spec,
                # Every slot's rows, the skipped ones' too (zeros).
                pl.BlockSpec(
                    (None, heads, width),
                    lambda i, j, layer, order, count: (order[i], 0, 0),
                ),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(stack.shape, stack.dtype),
            jax.ShapeDtypeStruct((slots, heads, width), jnp.float32),
        ],
        # Operands count the scalar-prefetch arrays: the stack is the
        # last input, aliased to the first result.
        input_output_aliases={3 + len(rows): 0},
        # A slot's rows of ``y`` stay resident over its head tiles and
        # the blocks past the count rest on the one before: in order.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem + 16 * 1024 * 1024,
        ),
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), order, count,
        *(r.astype(jnp.float32) for r in rows), stack,
    )


def _along(x, n: int):
    """x [B, H] -> [B, H, n]: a number a head, along the minor
    dimension of its state."""
    return jnp.broadcast_to(x[:, :, None], (*x.shape, n))


@functools.partial(jax.jit, static_argnames=("block_bytes", "interpret"))
def mamba_state_step(
    stack: jnp.ndarray,  # [L, B, H, P, N] float32: every layer's state
    layer: jnp.ndarray,  # [] int32: the layer stepped
    order: jnp.ndarray,  # [B] int32: `live_order`
    count: jnp.ndarray,  # [1] int32
    keep: jnp.ndarray,  # [B, H] float32: exp(dt A)
    xdt: jnp.ndarray,  # [B, H, P] float32: x dt
    b: jnp.ndarray,  # [B, G, N] float32
    c: jnp.ndarray,  # [B, G, N] float32
    *,
    block_bytes: int | None = None,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``S = S keep + (x dt) B^T`` and ``y = S C`` for the first
    ``count`` slots of ``order`` in ``stack[layer]``, in place. Returns
    (the stack, y [B, H, P] float32: zeros for the other slots)."""
    heads, groups = stack.shape[2], b.shape[1]
    per_group = heads // groups
    return _step(
        functools.partial(_mamba_body, per_group), stack, layer, order,
        count, [_along(keep, stack.shape[4]), xdt, b, c], stack.shape[3],
        per_group, block_bytes, interpret,
    )


@functools.partial(jax.jit, static_argnames=("block_bytes", "interpret"))
def gdn_state_step(
    stack: jnp.ndarray,  # [L, B, Hv, dk, dv] float32: every layer's state
    layer: jnp.ndarray,  # [] int32: the layer stepped
    order: jnp.ndarray,  # [B] int32: `live_order`
    count: jnp.ndarray,  # [1] int32
    decay: jnp.ndarray,  # [B, Hv] float32: exp(g)
    beta: jnp.ndarray,  # [B, Hv] float32
    q: jnp.ndarray,  # [B, Hk, dk] float32
    k: jnp.ndarray,  # [B, Hk, dk] float32
    v: jnp.ndarray,  # [B, Hv, dv] float32
    *,
    block_bytes: int | None = None,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The gated delta rule's step, ``S = S decay``, ``S += k (beta (v -
    S^T k))^T``, ``o = S^T q``, for the first ``count`` slots of
    ``order`` in ``stack[layer]``, in place. Returns (the stack, o [B,
    Hv, dv] float32: zeros for the other slots)."""
    heads, dv = stack.shape[2], stack.shape[4]
    # A key head's query and key, once a value head of its.
    q, k = (jnp.repeat(x, heads // x.shape[1], axis=1) for x in (q, k))
    return _step(
        _gdn_body, stack, layer, order, count,
        [_along(decay, dv), _along(beta, dv), q, k, v], dv, heads,
        block_bytes, interpret,
    )


@functools.partial(jax.jit, static_argnames=("block_bytes", "interpret"))
def kda_state_step(
    stack: jnp.ndarray,  # [L, B, H, dk, dv] float32: every layer's state
    layer: jnp.ndarray,  # [] int32: the layer stepped
    order: jnp.ndarray,  # [B] int32: `live_order`
    count: jnp.ndarray,  # [1] int32
    decay: jnp.ndarray,  # [B, H, dk] float32: exp(g), a key channel each
    beta: jnp.ndarray,  # [B, H] float32
    q: jnp.ndarray,  # [B, H, dk] float32
    k: jnp.ndarray,  # [B, H, dk] float32
    v: jnp.ndarray,  # [B, H, dv] float32
    *,
    block_bytes: int | None = None,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Kimi Delta Attention's step (`models/glm5_next.py kda_step`), ``S
    = diag(decay) S``, ``S += k (beta (v - S^T k))^T``, ``o = S^T q``,
    for the first ``count`` slots of ``order`` in ``stack[layer]``, in
    place. Returns (the stack, o [B, H, dv] float32: zeros for the other
    slots)."""
    heads, dv = stack.shape[2], stack.shape[4]
    return _step(
        _kda_body, stack, layer, order, count,
        [decay, _along(beta, dv), q, k, v], dv, heads, block_bytes, interpret,
    )
