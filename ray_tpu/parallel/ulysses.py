"""Ulysses-style sequence parallelism: all-to-all head↔sequence re-shard.

The second SP strategy the reference lacks (SURVEY.md section 5). Where
ring attention rotates KV blocks, Ulysses transposes the sharding: each sp
shard holds all positions for a subset of heads during attention, so the
attention itself is entirely local — two all-to-alls (over ICI) bracket
it. Best when n_heads % sp == 0 and the sequence is long relative to the
ring's per-hop latency.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
from jax import shard_map
from jax.sharding import PartitionSpec as P


from ray_tpu.ops.attention import causal_attention


def _a2a(x, axis_name, split_axis, concat_axis):
    return jax.lax.all_to_all(
        x, axis_name, split_axis=split_axis, concat_axis=concat_axis,
        tiled=True,
    )


def ulysses_attention_kernel(
    q, k, v, *, axis_name: str, inner: Callable = causal_attention
):
    """Per-shard body under shard_map; q/k/v: [B, S_local, H, D].

    all_to_all: [B, S/n, H, D] → [B, S, H/n, D]; run full-sequence
    attention on the local head group; transpose back.
    """
    n = jax.lax.psum(1, axis_name)
    if q.shape[2] % n:
        raise ValueError(
            f"ulysses needs heads ({q.shape[2]}) divisible by sp ({n})"
        )
    qh = _a2a(q, axis_name, split_axis=2, concat_axis=1)
    kh = _a2a(k, axis_name, split_axis=2, concat_axis=1)
    vh = _a2a(v, axis_name, split_axis=2, concat_axis=1)
    oh = inner(qh, kh, vh)
    return _a2a(oh, axis_name, split_axis=1, concat_axis=2)


def make_ulysses_attention(mesh, batch_axes=("dp", "fsdp"), seq_axis="sp",
                           head_axis="tp"):
    spec = P(batch_axes, seq_axis, head_axis, None)
    kernel = partial(ulysses_attention_kernel, axis_name=seq_axis)
    return shard_map(
        kernel, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )
