"""Serving prefill's attention in a sliding-window layer as a Pallas TPU
kernel: a chunk's queries over the band of keys they may see.

A window layer's query at position ``t`` sees the keys at ``t - W + 1 ..
t``. For a chunk of C tokens that is C x W (query, key) pairs wherever in
the context the chunk starts, where ``prefill_attention.py`` (every key
up to the diagonal, from pages) would fetch and multiply ``C x (start +
C / 2)``. A sibling of that kernel and not an option of it: its keys are
the context's pages and its key block is sized to a long context (2,048
keys, four windows wide); this one's keys are ONE contiguous run, the W
keys the slot carries in front of the chunk's own C, its key tiles are
fractions of the window, and it never meets a key outside the band, so
neither kernel carries a branch for the other's shape.

The keys come as ``[Hkv, W + C, Dh]``, head-major: index ``j`` is the key
at position ``start - W + j``. Query row ``i`` (position ``start + i``)
sees index ``j`` iff ``i < j <= i + W`` and the position is not negative
(``j >= W - start``: in a prompt's first chunk the carried keys are
whatever the slot's last request left).

The grid runs over (KV head, query block) and a step takes the block's
WHOLE band: query block ``qi`` needs the indices ``qi bq + 1 .. qi bq +
bq - 1 + W``, which with ``bk | bq`` and ``bk | W`` are exactly the ``(bq +
W) / bk`` key tiles from ``qi bq / bk`` on. Each tile is an operand of its
own (the same array under ``(bq + W) / bk`` block specs whose index maps
add the tile's number to the query block's offset), so **a key outside
the band is neither fetched nor multiplied**, and a head's soft-max is
ONE pass: scores of every tile, one row maximum, one exponential, one
sum, the tiles' ``p v`` added up. No running maximum, no rescaled
accumulator, no scratch: with at most a few tiles a band there is
nothing to carry. (Written first flash style, a third grid dimension
over the tiles with the statistics in VMEM, the same blocks took 1.30 ms
where this takes 0.59: per step an accumulator rescale and two
statistics' stores for every 256 keys, and a mask computed in two of
three steps.) Tile ``t`` starts ``t bk`` keys ahead of the block's first
query whatever the block, so which of its pairs the band holds is known
at trace time: tiles whose offset lies in ``[bq, W - bk + 1]`` hold no
masked pair and are not masked; the band's two ends get a mask that is
the same for every step and head. Every query sees its own key, so a
row's maximum is a real score and a masked pair's ``exp(-1e9 - max)`` is
exactly 0. Keys of negative positions exist only in the first query
blocks of a prompt's first chunks: those steps take a second branch
that also masks by position.

Grouped queries as in ``prefill_attention.py``: the ``n_rep`` query heads
of a KV head side by side in the lanes of one block, each a lane-aligned
slice attended against the step's one set of key and value tiles;
operands in the cache's dtype, float32 scores. The values' width is
their own (``models/motif.py``: scores 192 wide, held 256 with the
cell's zeros behind the rotary part, over values of 128, five query
heads a group of expanded latent cells); where it is the keys' the
blocks are the ones they were.

Block sizes, found on the chip (v5e, 72 query / 8 KV heads of 128, bf16,
2,048 queries at ``start`` 8,192 over a window of 512; my chip run, PR 55,
``scripts/window_attention_layer.py``; ms a call, and the share of the
bf16 peak by the pairs the band NEEDS, 2,048 x 512 x 72 x 512
operations; work computed over work needed is ``(bq + W) / W``):

    block_q x block_kv    ms     peak    computed / needed
    128 x 128            0.69    28.6%        1.25
    256 x 128            0.73    27.0%        1.5
    256 x 256            0.59    33.2%        1.5
    512 x 128            0.82    23.8%        2.0
    512 x 256            0.70    27.9%        2.0
    512 x 512            0.68    28.8%        2.0
    1,024 x 256          1.18    16.6%        3.0
    1,024 x 512          1.19    16.5%        3.0

The same chunk by ``prefill_attention`` (every key up to the diagonal)
takes 1.52 / 4.95 / 9.42 ms at ``start`` 0 / 6,144 / 14,336, and by dense
float32 scores over the band ``[8, 9, 2048, 2560]`` 11.5 ms. The vector
units bound it, not the matmul unit: 256 x 256 is 50% of the peak by the
pairs it computes, and each score costs an exponential, a maximum, a sum
and a cast beside its 512 multiply-adds.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas.paged_attention import _MASK
from ray_tpu.ops.pallas.prefill_attention import (
    _SUBLANES_BF16,
    _VMEM_LIMIT_BYTES,
    _fit_rows,
)

# Query block and key tile rows asked for: the module docstring has the
# sweep.
_BLOCK_Q = 256
_BLOCK_KV = 256


def band_blocks(c: int, window: int, block_q: int = _BLOCK_Q,
                block_kv: int = _BLOCK_KV) -> tuple[int, int] | None:
    """(query block, key block) rows for C queries over a window of W:
    the query block divides C, the key block divides both it and W, all
    multiples of a bf16 tile's 16 rows. None where no such pair exists
    (the caller then attends by dense scores under the same mask)."""
    bq = _fit_rows(block_q, c, _SUBLANES_BF16)
    if c % bq or bq % _SUBLANES_BF16:
        return None
    for bk in range(min(block_kv, bq), 0, -_SUBLANES_BF16):
        if bk % _SUBLANES_BF16 == 0 and bq % bk == 0 and window % bk == 0:
            return bq, bk
    return None


def _kernel(start_ref, q_ref, *refs, block_q: int, block_kv: int, tiles: int,
            n_rep: int, head_dim: int, v_dim: int, window: int):
    """One (KV head, query block) step: the block's whole band at once.
    ``q_ref`` ``[block_q, n_rep * Dh]`` (pre-scaled); then ``tiles`` key
    refs ``[block_kv, Dh]`` and ``tiles`` value refs ``[block_kv, Dv]``,
    consecutive key blocks from the query block's own offset on; then
    ``o_ref`` ``[block_q, n_rep * Dv]``. Tile ``t``'s first key is ``t *
    block_kv`` ahead of the block's first query, whatever the block:
    which pairs of it the band holds is known when the kernel is
    traced."""
    k_refs, v_refs, o_ref = refs[:tiles], refs[tiles: 2 * tiles], refs[-1]
    qi = pl.program_id(1)
    k_lo = qi * block_q  # the key index of the band's first key
    first_real = window - start_ref[0]  # the key index of position 0
    shape = (block_q, block_kv)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    ahead = col - jax.lax.broadcasted_iota(jnp.int32, shape, 0)

    def _attend(first_chunk: bool):
        hidden = []
        for t in range(tiles):
            offset = t * block_kv
            whole = block_q <= offset and offset + block_kv - 1 <= window
            out = None if whole else (
                (ahead + offset < 1) | (ahead + offset > window)
            )
            if first_chunk:
                before = k_lo + offset + col < first_real
                out = before if out is None else out | before
            hidden.append(out)
        keys = [ref[...] for ref in k_refs]
        values = [ref[...] for ref in v_refs]
        for r in range(n_rep):
            lanes = slice(r * head_dim, (r + 1) * head_dim)
            q = q_ref[:, lanes]
            scores = []
            for k, out in zip(keys, hidden):
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # [block_q, block_kv]
                scores.append(s if out is None else jnp.where(out, _MASK, s))
            # Every query sees its own key, so the row's max is a real
            # score and a masked pair's exp(-1e9 - max) is exactly 0.
            top = functools.reduce(
                jnp.maximum, [s.max(axis=-1, keepdims=True) for s in scores]
            )
            probs = [jnp.exp(s - top) for s in scores]
            total = sum(p.sum(axis=-1, keepdims=True) for p in probs)
            acc = sum(
                jax.lax.dot(p.astype(v.dtype), v,
                            preferred_element_type=jnp.float32)
                for p, v in zip(probs, values)
            )
            o_ref[:, r * v_dim: (r + 1) * v_dim] = (
                acc / total
            ).astype(o_ref.dtype)

    # Keys before position 0 exist only in a prompt's first chunks, and
    # there only in the query blocks whose band reaches back that far.
    @pl.when(k_lo >= first_real)
    def _inside():
        _attend(False)

    @pl.when(k_lo < first_real)
    def _first_chunk():
        _attend(True)


@functools.partial(
    jax.jit,
    static_argnames=("window", "block_q", "block_kv", "interpret", "scale"),
)
def window_attention(
    q: jnp.ndarray,  # [C, H, Dh], rope applied
    k: jnp.ndarray,  # [Hkv, W + C, Dh]: the carried keys, then the chunk's
    v: jnp.ndarray,  # [Hkv, W + C, Dv]: Dv need not be Dh
    start: jnp.ndarray,  # [] int32: position of query 0
    *,
    window: int,
    block_q: int = _BLOCK_Q,
    block_kv: int = _BLOCK_KV,
    interpret: bool = False,
    scale: float | None = None,
) -> jnp.ndarray:
    """Attention of C queries at ``start .. start + C - 1`` over keys at
    ``start - W .. start + C - 1`` given in that order, query ``t`` seeing
    the keys at ``max(t - W + 1, 0) .. t``; returns ``[C, H, Dv]``. What
    ``k`` holds for a negative position does not reach the result; ``v``
    has to be finite there (a probability of 0 times it). ``scale``
    multiplies the scores (``Dh**-0.5`` where None)."""
    c, n_heads, head_dim = q.shape
    n_kv, total, _ = k.shape
    v_dim = v.shape[-1]
    if total != window + c:
        raise ValueError(f"{total} keys for {c} queries and a window of {window}")
    blocks = band_blocks(c, window, block_q, block_kv)
    if blocks is None:
        raise ValueError(f"no blocks for {c} queries and a window of {window}")
    block_q, block_kv = blocks
    n_rep = n_heads // n_kv
    dt = k.dtype
    tiles = (block_q + window) // block_kv
    group = n_rep * head_dim
    rows = (
        q.astype(jnp.float32) * (head_dim**-0.5 if scale is None else scale)
    ).astype(dt).reshape(c, n_heads * head_dim)
    q_spec = pl.BlockSpec((block_q, group), lambda g, qi, s: (qi, g))

    def tile_specs(width):
        return [
            pl.BlockSpec(
                (None, block_kv, width),
                lambda g, qi, s, t=t: (g, qi * (block_q // block_kv) + t, 0),
            )
            for t in range(tiles)
        ]

    o_spec = pl.BlockSpec((block_q, n_rep * v_dim), lambda g, qi, s: (qi, g))
    out = pl.pallas_call(
        functools.partial(
            _kernel, block_q=block_q, block_kv=block_kv, tiles=tiles,
            n_rep=n_rep, head_dim=head_dim, v_dim=v_dim, window=window,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_kv, c // block_q),
            in_specs=[q_spec, *tile_specs(head_dim), *tile_specs(v_dim)],
            out_specs=o_spec,
        ),
        out_shape=jax.ShapeDtypeStruct((c, n_heads * v_dim), dt),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(jnp.reshape(start, (1,)).astype(jnp.int32), rows, *[k] * tiles,
      *[v] * tiles)
    return out.reshape(c, n_heads, v_dim)


def window_attention_dense(q, k, v, start, *, window: int,
                           scale: float | None = None):
    """`window_attention`'s definition by dense float32 scores ``[Hkv,
    n_rep, C, W + C]`` under the mask: what a program without the kernel
    runs (a CPU, a chunk no block divides) and what the kernel is tested
    against."""
    c, n_heads, head_dim = q.shape
    n_kv = k.shape[0]
    qg = q.reshape(c, n_kv, n_heads // n_kv, head_dim)
    scores = jnp.einsum(
        "cgrd,gjd->grcj", qg, k, preferred_element_type=jnp.float32
    ) * (head_dim**-0.5 if scale is None else scale)
    ahead = jnp.arange(window + c)[None, :] - jnp.arange(c)[:, None]
    hidden = (ahead < 1) | (ahead > window) | (
        jnp.arange(window + c)[None, :] < window - start
    )
    probs = jax.nn.softmax(jnp.where(hidden, -jnp.inf, scores), axis=-1)
    out = jnp.einsum("grcj,gjd->cgrd", probs.astype(v.dtype), v)
    return out.reshape(c, n_heads, v.shape[-1])
