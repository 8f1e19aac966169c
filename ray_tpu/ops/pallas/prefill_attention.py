"""Serving prefill's attention as a Pallas TPU kernel: a chunk's queries
at a traced ``start`` over the context so far.

What the serving prefill programs need (``llm/paged_kv.py``
``paged_prefill`` and ``paged_prefill_chunk``) and the training kernel
does not offer: fewer queries than keys (a chunk of C tokens against a
context of up to ``start + C``), a causal diagonal that starts at a
TRACED position (one compiled program a bucket, whatever the chunk),
keys and values that lie as PAGES (``[n_pages, Hkv, P, Dh]``: the pool's
own cell layout, so the gathered context and a prompt's fresh cells both
go in as they are), and no gradient, so no residuals. It shares no line
with the training kernel: that one is square, starts at zero, carries a
backward pass and runs under a mesh, and a change made for serving must
not recompile a train step.

Flash style: the grid runs over (KV head, query block, key block); a
running max, sum and accumulator for each of the group's query heads
stay in VMEM over the key blocks, scores exist only as one
``[block_q, block_kv]`` float32 tile at a time, never in HBM.

- **Key blocks wholly past a query block's last position are neither
  computed nor fetched**: their grid steps do nothing and their index
  maps repeat the last block needed, which Pallas does not fetch again.
  So a chunk's work is the context so far, not the table's width: the
  first 2,048-token chunk of an 8,192-token bucket reads 2,048 keys.
- **Masking only on blocks the diagonal crosses**, with finite mask
  values. In such a block the value rows past the block's last query
  are zeroed as well: a probability of exactly 0 times whatever a page
  nobody wrote holds (NaN bits, perhaps) is not 0.
- **Grouped queries without a repeated key**: the queries come as
  ``[C, H * Dh]``, which is how the projection leaves them, and a block
  is ``[block_q, n_rep * Dh]``: the ``n_rep`` query heads of one KV head
  side by side in the lanes. Each is a lane-aligned slice, attended
  against the one key and value tile of the step; the result leaves in
  the same layout, which is what the output projection takes. Neither
  queries nor results are transposed in HBM.

Operands in the pool's dtype (bf16), float32 scores, statistics and
accumulator, queries pre-scaled once: the numerics of the training and
the decode kernels.

Block sizes, found on the chip (v5e, 32 query / 8 KV heads of 128, bf16;
my chip runs, PR 37; milliseconds a call; the four chunks of an
8,192-token prompt, 2,048 queries at ``start`` 0 / 2,048 / 4,096 / 6,144
over a table of 8,192 keys, and their sum; the gather path's dense
scores take 13.1 ms a chunk there, 52 the prompt):

    block_q x block_kv   start 0   2,048   4,096   6,144     sum
    256 x 256              1.31    3.03    4.75    6.48    15.6
    512 x 512              0.84    1.84    2.84    3.85     9.4
    256 x 1,024            0.67    1.28    1.90    2.51     6.4
    512 x 1,024            0.59    1.11    1.63    2.15     5.5
    1,024 x 1,024          0.56    1.06    1.56    2.07     5.3
    256 x 2,048            0.66    1.12    1.57    2.03     5.4
    512 x 2,048            0.64    1.05    1.47    1.89     5.05
    1,024 x 2,048          2.17    2.49    2.83    3.17    10.7
    256 x 4,096            1.08    1.08    1.91    1.92     6.0
    512 x 4,096            2.37    2.38    3.05    3.05    10.9

512 x 2,048 reads 65% of the bf16 peak at ``start`` 6,144 (by the pairs
the arithmetic needs: 512 operations a query, key and head) and 27% on a
first chunk, where half of every crossed block is masked work; a wide
key block pays because the accumulator is rescaled once a key block.
The larger tiles spill (1,024 x 2,048 and 512 x 4,096 are two to four
times slower). In the serving cell 512 x 2,048 and 1,024 x 1,024 read
the same ``serve_tokens_per_s`` (33,962-33,984 and 33,935-34,024 over
three seeds). A whole prompt of 64 to 1,024 tokens (``paged_prefill``)
takes 0.22-0.26 ms with any of them, the launch's own time, where dense
attention takes 0.21-0.24 up to 512 tokens, 1.05 at 1,024 and 4.06 at
2,048 (the kernel: 0.55-0.61).

At a head of 256 (v5e, 16 query / 2 KV heads of 256, bf16: Qwen3-Next's;
my chip run, PR 51; milliseconds a call, 2,048 queries at ``start`` 0 /
the middle / the last chunk over a table of 8,192 and of 16,384 keys).
The group of 8 heads already halves the query block to 256 (below); the
key block has to halve with the head, since a tile's bytes are what
spills:

    block_q x block_kv   8,192: 0   2,048   6,144   16,384: 0   6,144   14,336
    128 x 1,024            0.56    1.08    2.09        0.61    2.14    4.17
    128 x 2,048            0.61    1.06    1.93        0.66    1.97    3.72
    256 x 512              0.56    1.14    2.32        0.59    2.38    4.73
    256 x 1,024            0.50    0.94    1.80        0.54    1.83    3.56
    256 x 2,048            2.01    2.33    3.03        3.64    4.64    6.03
    256 x 4,096            1.82    1.81    2.70        2.91    3.55    4.78
    512 x 1,024            2.03    2.37    3.05        3.71    4.72    6.09
    512 x 2,048            1.55    1.85    2.48        2.66    3.57    4.82

256 x 1,024 reads 73% of the bf16 peak on the last chunk of a
16,384-token table (1,024 operations a query, key and head). So the key
block is ``_BLOCK_KV`` keys of 128 lanes and as many bytes of a wider
head: 2,048 at 128 (the cells that were there keep their blocks), 1,024
at 256.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas.flash_attention import _LANES
from ray_tpu.ops.pallas.paged_attention import _M_INIT, _MASK

_BLOCK_Q = 512
_BLOCK_KV = 2048  # 32 pages of 64: the module docstring has the sweep
_SUBLANES_BF16 = 16  # a bf16 tile's rows: query blocks are multiples of it
# What the call may take of VMEM (a v5e core has 128 MiB; the compiler's
# own limit is 16): the q, k, v and result tiles twice, the statistics
# and a head's float32 scores and probabilities, in both branches.
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _fit_rows(requested: int, rows: int, multiple: int) -> int:
    """Largest block <= requested that divides ``rows`` and is a multiple
    of ``multiple`` (``rows`` itself where it fits)."""
    if rows <= requested:
        return rows
    for d in range(requested - requested % multiple, 0, -multiple):
        if rows % d == 0:
            return d
    return multiple


def _kernel(
    start_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, block_q: int, block_kv: int, num_kv: int, n_rep: int, head_dim: int,
):
    """One (KV head, query block, key block) step. ``q_ref``
    ``[block_q, n_rep * Dh]`` (pre-scaled), ``k_ref`` / ``v_ref``
    ``[block_kv / P, P, Dh]``, ``o_ref`` as ``q_ref``; ``m_ref`` /
    ``l_ref`` ``[n_rep, block_q, 128]`` (lane-replicated) and
    ``acc_ref`` ``[n_rep, block_q, Dh]``."""
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _M_INIT)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lo = start_ref[0] + qi * block_q
    q_hi = q_lo + block_q - 1
    k_lo = ki * block_kv
    k_hi = k_lo + block_kv - 1

    def _accumulate(masked: bool):
        k = k_ref[...].reshape(block_kv, head_dim)
        v = v_ref[...].reshape(block_kv, head_dim)
        if masked:
            shape = (block_q, block_kv)
            hidden = k_lo + jax.lax.broadcasted_iota(jnp.int32, shape, 1) > (
                q_lo + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            )
            v_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
            v = jnp.where(v_pos > q_hi, jnp.zeros_like(v), v)
        for r in range(n_rep):
            lanes = slice(r * head_dim, (r + 1) * head_dim)
            s = jax.lax.dot_general(
                q_ref[:, lanes], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [block_q, block_kv]
            if masked:
                s = jnp.where(hidden, _MASK, s)
            m_prev, l_prev = m_ref[r][:, :1], l_ref[r][:, :1]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)  # masked -> 0
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + p.sum(axis=-1, keepdims=True)
            acc_ref[r] = acc_ref[r] * alpha + jax.lax.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32
            )
            m_ref[r] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[r] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    @pl.when(jnp.logical_and(k_lo <= q_hi, k_hi > q_lo))
    def _crossed():
        _accumulate(True)

    @pl.when(k_hi <= q_lo)
    def _below():
        _accumulate(False)

    @pl.when(ki == num_kv - 1)
    def _finalize():
        # Every query sees key 0: the sum is never zero.
        for r in range(n_rep):
            lanes = slice(r * head_dim, (r + 1) * head_dim)
            o_ref[:, lanes] = (acc_ref[r] / l_ref[r][:, :1]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_kv", "interpret", "scale")
)
def prefill_attention(
    q: jnp.ndarray,  # [C, H, Dh], rope applied
    k_pages: jnp.ndarray,  # [n_pages, Hkv, P, Dh]: the context's pages in order
    v_pages: jnp.ndarray,  # [n_pages, Hkv, P, Dh]
    start: jnp.ndarray,  # [] int32: position of query 0; key 0 is position 0
    *,
    block_q: int = _BLOCK_Q,
    block_kv: int | None = None,
    interpret: bool = False,
    scale: float | None = None,
) -> jnp.ndarray:
    """Causal attention of C queries at ``start .. start + C - 1`` over
    the keys at ``0 .. n_pages * P - 1`` (query i sees keys <= start +
    i); returns ``[C, H, Dh]``. ``start + C`` may not pass the pages
    given; what the pages hold past it does not reach the result.
    ``scale`` multiplies the scores (``Dh**-0.5`` where None; a model
    states its own: ``models/granite_hybrid.py``)."""
    c, n_heads, head_dim = q.shape
    n_pages, n_kv, page_size, _ = k_pages.shape
    n_rep = n_heads // n_kv
    dt = k_pages.dtype
    # The statistics, the accumulator and the unrolled heads' scores are
    # kept for each of the group's n_rep query heads, so the query block
    # shrinks as the group grows: n_rep x block_q rows at most what the
    # sweep above ran (4 x 512). At 16 heads a KV head (Nemotron-3-Nano)
    # a 512-row block asks 66 MB of VMEM and the compiler refuses it.
    block_q = min(block_q, max(4 * _BLOCK_Q // n_rep, _SUBLANES_BF16))
    block_q = _fit_rows(block_q, c, _SUBLANES_BF16)
    if block_kv is None:
        # A key or value tile keeps its bytes as the head widens: 2,048
        # keys of 128, 1,024 of 256 (the sweep at a head of 256 above).
        block_kv = max(_BLOCK_KV * _LANES // max(head_dim, _LANES), page_size)
    block_pages = _fit_rows(max(block_kv // page_size, 1), n_pages, 1)
    block_kv = block_pages * page_size
    num_q, num_kv = c // block_q, n_pages // block_pages
    group = n_rep * head_dim
    rows = (
        q.astype(jnp.float32) * (head_dim**-0.5 if scale is None else scale)
    ).astype(dt).reshape(c, n_heads * head_dim)

    def last_needed(qi, ki, start):
        # The last key block a query block reads: steps past it repeat
        # its index, and a block whose index repeats is not fetched.
        return jnp.minimum(ki, (start[0] + (qi + 1) * block_q - 1) // block_kv)

    q_spec = pl.BlockSpec((block_q, group), lambda g, qi, ki, s: (qi, g))
    kv_spec = pl.BlockSpec(
        (block_pages, None, page_size, head_dim),
        lambda g, qi, ki, s: (last_needed(qi, ki, s), g, 0, 0),
    )
    out = pl.pallas_call(
        functools.partial(
            _kernel, block_q=block_q, block_kv=block_kv, num_kv=num_kv,
            n_rep=n_rep, head_dim=head_dim,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_kv, num_q, num_kv),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((n_rep, block_q, _LANES), jnp.float32),  # max
                pltpu.VMEM((n_rep, block_q, _LANES), jnp.float32),  # sum
                pltpu.VMEM((n_rep, block_q, head_dim), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((c, n_heads * head_dim), dt),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(jnp.reshape(start, (1,)).astype(jnp.int32), rows, k_pages, v_pages)
    return out.reshape(c, n_heads, head_dim)
