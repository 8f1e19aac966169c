"""Pipeline parallelism: GPipe microbatch schedule over the `pp` mesh axis.

TPU-native PP is one SPMD program, not a runtime of stage processes: each
device along `pp` holds ONE stage's parameters; a `lax.scan` runs the
circulating schedule (stage s works on microbatch t-s at step t) and
`lax.ppermute` hands activations to the next stage over ICI. Because the
whole schedule lives inside jit, `jax.grad` through it yields the 1F1B-ish
backward for free — XLA pipelines the reverse ppermutes the same way.

The reference has no native PP (SURVEY.md §2.3: delegated to vLLM and to
compiled-graph NCCL P2P channels); this module is the substrate that
fills it, alongside dag/ for cross-process pipelines.

Bubble fraction is the GPipe (P-1)/(M+P-1); pick num_microbatches >= 4*P
to amortize.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


StageFn = Callable[[Any, jnp.ndarray], jnp.ndarray]


def pipeline_apply(
    stage_params: Any,
    x: jnp.ndarray,  # [batch, ...] global inputs
    stage_fn: StageFn,  # (one stage's params, microbatch) -> microbatch
    *,
    mesh,
    num_microbatches: int,
    axis: str = "pp",
    param_specs: Any = None,
) -> jnp.ndarray:
    """Run x through P chained stages, microbatched and pipelined.

    ``stage_params`` leaves have a leading stage dim P (sharded over
    ``axis``); every stage must map [mb, ...] → [mb, ...] of the same
    shape (the circulating buffer is homogeneous). Returns the last
    stage's outputs for the full batch, replicated over ``axis``.

    ``param_specs`` (optional tree of PartitionSpecs, leading dim =
    ``axis``) shards stage-param dims over FURTHER mesh axes — e.g.
    ``P("pp", "ep")`` for expert-stacked MoE weights or
    ``P("pp", None, "fsdp")`` for ZeRO-3 stage weights — and
    ``stage_fn`` then uses those axes collectively (psum over "ep",
    all_gather over "fsdp"): pipeline, expert, and data/ZeRO
    parallelism compose inside ONE shard_map program.
    """
    n_stages = mesh.shape[axis]
    for leaf in jax.tree.leaves(stage_params):
        if leaf.shape[0] != n_stages:
            raise ValueError(
                f"stage dim {leaf.shape[0]} != mesh {axis}={n_stages}; a "
                "mismatch would silently drop stages"
            )
    if param_specs is not None:
        for spec in jax.tree.leaves(
            param_specs, is_leaf=lambda s: isinstance(s, P)
        ):
            first = spec[0] if len(spec) else None
            names = first if isinstance(first, tuple) else (first,)
            if axis not in names:
                raise ValueError(
                    f"param_specs leaf {spec} must shard its LEADING "
                    f"dim over {axis!r}; otherwise every device would "
                    "silently run stage 0's weights"
                )
    # Batch shards over the data axes (pipeline composes with DP); each
    # dp shard runs its own GPipe schedule on its slice.
    dp_axes = tuple(
        a for a in ("dp", "fsdp") if a in mesh.shape and mesh.shape[a] > 1
    )
    dp_total = 1
    for a in dp_axes:
        dp_total *= mesh.shape[a]
    batch = x.shape[0]
    if batch % (num_microbatches * dp_total):
        raise ValueError(
            f"batch {batch} not divisible by microbatches "
            f"{num_microbatches} x data shards {dp_total}"
        )
    mb = batch // dp_total // num_microbatches

    def per_device(params_local, x_full):
        # params_local leaves: [1, ...] (this device's stage); x_full is
        # this data shard's slice of the batch (replicated over pp).
        params_one = jax.tree.map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index(axis)
        micro = x_full.reshape(num_microbatches, mb, *x_full.shape[1:])

        num_steps = num_microbatches + n_stages - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def step(carry, t):
            recv, outputs = carry
            # Stage 0 ingests microbatch t (clamped; masked later).
            feed = jax.lax.dynamic_index_in_dim(
                micro, jnp.clip(t, 0, num_microbatches - 1), 0,
                keepdims=False,
            )
            x_in = jnp.where(stage == 0, feed, recv)
            y = stage_fn(params_one, x_in)
            # The last stage completes microbatch t - (P-1) at step t.
            out_idx = t - (n_stages - 1)
            valid = jnp.logical_and(
                stage == n_stages - 1, out_idx >= 0
            )
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs,
                jnp.where(
                    valid,
                    y,
                    jax.lax.dynamic_index_in_dim(
                        outputs, jnp.clip(out_idx, 0, num_microbatches - 1),
                        0, keepdims=False,
                    ),
                ),
                jnp.clip(out_idx, 0, num_microbatches - 1),
                0,
            )
            # Rotate activations one stage forward over ICI.
            recv_next = jax.lax.ppermute(y, axis, perm)
            return (recv_next, outputs), None

        outputs0 = jnp.zeros_like(micro)
        recv0 = jnp.zeros((mb, *x_full.shape[1:]), x_full.dtype)
        (recv, outputs), _ = jax.lax.scan(
            step, (recv0, outputs0), jnp.arange(num_steps)
        )
        # Only the last stage holds real outputs; replicate via psum.
        outputs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outputs, 0.0), axis
        )
        return outputs.reshape(-1, *x_full.shape[1:])

    spec_params = (
        param_specs
        if param_specs is not None
        else jax.tree.map(lambda _: P(axis), stage_params)
    )
    batch_spec = P(dp_axes if dp_axes else None)
    return shard_map(
        per_device,
        mesh=mesh,
        in_specs=(spec_params, batch_spec),
        out_specs=batch_spec,
        check_vma=False,
    )(stage_params, x)


def pipeline_loss_fn(
    stage_params: Any,
    batch: dict,
    stage_fn: StageFn,
    loss_head: Callable[[jnp.ndarray, dict], jnp.ndarray],
    *,
    mesh,
    num_microbatches: int,
    param_specs: Any = None,
) -> jnp.ndarray:
    """Differentiable pipelined loss: forward through the stages, then a
    replicated loss head (logits → scalar). Use under jax.grad/jit."""
    y = pipeline_apply(
        stage_params,
        batch["inputs"],
        stage_fn,
        mesh=mesh,
        num_microbatches=num_microbatches,
        param_specs=param_specs,
    )
    return loss_head(y, batch)
