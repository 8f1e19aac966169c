"""Ring attention: causal attention with the sequence sharded over `sp`.

The reference has no sequence/context parallelism (SURVEY.md section 5:
"Long-context / sequence parallelism: Not present"); this module fills
that gap TPU-natively. Each sp shard holds one sequence block of Q/K/V.
K/V blocks rotate around the ring via `ppermute` (nearest-neighbor ICI
hops) while each shard accumulates its queries' attention over every
block with streaming flash-style (max, denom) statistics — memory stays
O(block²) and the rotation overlaps with compute (the python loop is
unrolled, letting XLA schedule the next permute during the current
block's matmuls; cf. PAPERS.md ring/overlap literature).

Differentiable (pure jnp + ppermute, which has a transpose rule), so it
drops into the training step as the model's attention function.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


from ray_tpu.ops.attention import _repeat_kv

_NEG_BIG = -1.0e30


def _block_stats(q, k, v, q_off, kv_off):
    """One Q-block × KV-block partial attention.

    Returns (o, m, l): unnormalized output [B,Sq,H,D] = exp(S - m) @ V,
    rowmax m and rowsum l, both [B,H,Sq], fp32. Fully-masked rows give
    m=_NEG_BIG, l=0, o=0 so they vanish in the streaming combine.
    """
    n_rep = q.shape[2] // k.shape[2]
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    scale = q.shape[-1] ** -0.5
    logits = (
        jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
        * scale
    )
    q_pos = jnp.arange(q.shape[1]) + q_off
    k_pos = jnp.arange(k.shape[1]) + kv_off
    mask = (q_pos[:, None] >= k_pos[None, :])[None, None]
    logits = jnp.where(mask, logits, _NEG_BIG)
    m = jnp.max(logits, axis=-1)  # [B,H,Sq]
    p = jnp.exp(logits - m[..., None]) * mask  # masked rows → 0
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v).astype(
        jnp.float32
    )
    return o, m, l


def ring_attention_kernel(q, k, v, *, axis_name: str):
    """Per-shard body; call under shard_map with seq sharded on
    ``axis_name``. q/k/v: [B, S_local, H(or Hkv), D]."""
    n = jax.lax.psum(1, axis_name)
    r = jax.lax.axis_index(axis_name)
    s_local = q.shape[1]
    q_off = r * s_local

    b, _, h, d = q.shape
    o = jnp.zeros((b, s_local, h, d), jnp.float32)
    m = jnp.full((b, h, s_local), _NEG_BIG, jnp.float32)
    l = jnp.zeros((b, h, s_local), jnp.float32)

    fwd = [(i, (i + 1) % n) for i in range(n)]
    for step in range(n):
        # This iteration's KV block came from rank (r - step) mod n.
        kv_rank = (r - step) % n
        kv_off = kv_rank * s_local
        o_b, m_b, l_b = _block_stats(q, k, v, q_off, kv_off)
        # Streaming (flash) combine in fp32.
        m_new = jnp.maximum(m, m_b)
        alpha = jnp.exp(m - m_new)
        beta = jnp.exp(m_b - m_new)
        o = o * alpha.transpose(0, 2, 1)[..., None] + o_b * beta.transpose(
            0, 2, 1
        )[..., None]
        l = l * alpha + l_b * beta
        m = m_new
        if step != n - 1:
            k = jax.lax.ppermute(k, axis_name, perm=fwd)
            v = jax.lax.ppermute(v, axis_name, perm=fwd)

    denom = jnp.where(l == 0.0, 1.0, l).transpose(0, 2, 1)[..., None]
    return (o / denom).astype(q.dtype)


def make_ring_attention(mesh, batch_axes=("dp", "fsdp"), seq_axis="sp",
                        head_axis="tp"):
    """Build an attention fn (q,k,v → o, all [B,S,H,D] global) running the
    ring kernel under shard_map on ``mesh``. Drop-in for
    ray_tpu.models.llama.forward(attn_fn=...)."""
    spec = P(batch_axes, seq_axis, head_axis, None)
    kernel = partial(ring_attention_kernel, axis_name=seq_axis)
    return shard_map(
        kernel,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
