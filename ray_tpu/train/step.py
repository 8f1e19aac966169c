"""Sharded next-token-prediction train step for the flagship model.

One pjit'd program: forward (scan+remat) → cross-entropy → backward → adamw
update. Under a mesh with fsdp>1 the optimizer state and params are sharded
(ZeRO-3); XLA inserts the param all-gathers and gradient reduce-scatters.
The reference reaches the same endpoint via torch DDP/FSDP process groups
(reference: python/ray/train/torch/config.py:73); here it is one compiled
XLA program per (mesh, shapes).
"""

from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import optax

from ray_tpu.models.llama import (
    LlamaConfig,
    init_params,
    param_logical_axes,
)
from ray_tpu.parallel.sharding import is_axes_leaf, tree_shardings, use_mesh


def _model_fns(cfg: LlamaConfig):
    """(init, logical_axes) for the config's model family — dense Llama
    or MoE (ray_tpu.models.moe adds expert-parallel params)."""
    from ray_tpu.models.moe import (
        MoEConfig,
        init_moe_params,
        moe_param_logical_axes,
    )

    if isinstance(cfg, MoEConfig):
        return init_moe_params, moe_param_logical_axes
    return init_params, param_logical_axes


class TrainState(NamedTuple):
    step: jnp.ndarray  # scalar int32
    params: Any
    opt_state: Any


def make_optimizer(
    lr: float = 3e-4,
    warmup: int = 100,
    total_steps: int = 10000,
    weight_decay: float = 0.1,
    grad_clip: float = 1.0,
    mu_dtype=None,
) -> optax.GradientTransformation:
    """``mu_dtype=jnp.bfloat16`` halves the first-moment memory (the
    8-bit-optimizer-style tradeoff; the variance stays fp32) — measured
    loss-neutral on the bench model and frees HBM for batch at 8B."""
    sched = optax.warmup_cosine_decay_schedule(
        0.0, lr, warmup, max(total_steps, warmup + 1), lr * 0.1
    )
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(
            sched, b1=0.9, b2=0.95, weight_decay=weight_decay,
            mu_dtype=mu_dtype,
        ),
    )


def init_train_state(
    key: jax.Array, cfg: LlamaConfig, optimizer: optax.GradientTransformation
) -> TrainState:
    init, _ = _model_fns(cfg)
    params = init(key, cfg)
    state = TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        opt_state=optimizer.init(params),
    )
    _register_state_memory(state)
    return state


def init_zero_train_state(
    key: jax.Array,
    cfg: LlamaConfig,
    optimizer: optax.GradientTransformation,
    rank: int,
    world: int,
):
    """ZeRO-sharded counterpart of :func:`init_train_state`: full
    params plus a :class:`~ray_tpu.train.zero.ZeroOptimizer` holding
    optimizer state for this rank's ~1/world of the leaves only
    (arXiv:2004.13336). Both tenants are claimed in the device-memory
    ledger — params at full size, the optimizer at SHARD size — so the
    HBM ledger and OOM forensics price the ZeRO win honestly instead
    of assuming replicated adamw. Returns ``(params, zero_optimizer)``;
    the step loop syncs grads with
    ``GradBucketer.sync_sharded_async`` and applies
    ``zero_optimizer.apply`` between the two hops."""
    from ray_tpu.train.zero import ZeroOptimizer

    init, _ = _model_fns(cfg)
    params = init(key, cfg)
    _register_tagged(
        "train.state.params", "params", params
    )
    zo = ZeroOptimizer(optimizer, params, rank, world)
    return params, zo


def jit_grad_step(cfg: LlamaConfig, attn_fn=None):
    """jit the forward+backward half of the train step:
    ``(params, batch) -> (metrics, grads)``. For dataplanes that sync
    and update OUTSIDE the compiled program — the ZeRO-sharded path
    reduce-scatters these grads, updates shard-locally, and allgathers
    weights — so the optimizer math never has to live inside the fused
    step."""

    def grad_step(params, batch):
        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
        (_, metrics), grads = grad_fn(params, batch, cfg, attn_fn)
        return metrics, grads

    return jax.jit(grad_step)


# Live memory-ledger claims for the resident train state, keyed by
# tag. Retained so re-initialization (elastic resize, new attempt)
# explicitly retires the previous claim instead of leaning on
# tag-replacement (TPU404), and so teardown CAN close them.
_STATE_REGS: dict[str, object] = {}


def _tree_bytes(tree) -> int:
    return int(
        sum(
            leaf.nbytes
            for leaf in jax.tree_util.tree_leaves(tree)
            if hasattr(leaf, "nbytes")
        )
    )


def _register_tagged(tag: str, kind: str, tree) -> None:
    """One resident-state ledger claim with the _STATE_REGS discipline:
    the previous claim under the tag is explicitly retired (elastic
    resize / new attempt re-inits must not leak a Registration), and
    the arrays are tagged for OOM forensics."""
    from ray_tpu.runtime import memory as rmem

    if not rmem.enabled():
        return
    old = _STATE_REGS.get(tag)
    if old is not None:
        old.close()
    _STATE_REGS[tag] = rmem.track(tag, kind=kind, nbytes=_tree_bytes(tree))
    rmem.tag_arrays(tag, kind, tree)


def _register_state_memory(state: TrainState) -> None:
    """Claim the resident train state in the device-memory ledger
    (runtime/memory.py): params and optimizer moments are the two
    biggest fixed tenants of HBM (BENCH_8B: ~9.4 GB of a 16 GB v5e at
    4 full llama3-8b layers), so they register at creation — and their
    arrays are tagged so an OOM forensics report names them."""
    from ray_tpu.runtime import memory as rmem

    if not rmem.enabled():
        return
    _register_tagged("train.state.params", "params", state.params)
    _register_tagged("train.state.optimizer", "optimizer", state.opt_state)


class _Box:
    """Opaque wrapper so an axes tuple traverses pytree maps as one leaf."""

    __slots__ = ("axes",)

    def __init__(self, axes):
        self.axes = axes


def state_logical_axes(
    cfg: LlamaConfig, optimizer: optax.GradientTransformation
) -> TrainState:
    """Logical axes for every leaf of TrainState (opt state mirrors params).

    `optax.tree_map_params` pairs each param-shaped leaf of the optimizer
    state with its parameter by *position in the tree*, so adam moments get
    exactly their parameter's axes (shape coincidences like wq [L,d,hq] vs
    wo [L,hq,d] with hq==d cannot cross-contaminate); non-param leaves
    (e.g. adam's count) get ()."""
    init, logical_axes = _model_fns(cfg)
    p_axes = logical_axes(cfg)
    p_shapes = jax.eval_shape(partial(init, cfg=cfg), jax.random.key(0))
    opt_shapes = jax.eval_shape(optimizer.init, p_shapes)

    boxed = jax.tree.map(_Box, p_axes, is_leaf=is_axes_leaf)
    axes_state = optax.tree_map_params(
        optimizer,
        lambda _, box: box.axes,
        opt_shapes,
        boxed,
        transform_non_params=lambda _: (),
    )

    return TrainState(step=(), params=p_axes, opt_state=axes_state)


def chunked_cross_entropy(
    hidden: jnp.ndarray,  # [B, S, d] final-norm hidden states
    lm_head: jnp.ndarray,  # [d, V]
    targets: jnp.ndarray,  # [B, S] int32
    dtype,
    # 1024 measured fastest on v5e at B8/S2048/V32k (+0.9% step over
    # 512: fewer scan trips at the same peak-logits memory order).
    chunk: int = 1024,
) -> jnp.ndarray:
    """Mean next-token CE without materializing [B, S, V] logits.

    A rematerialized scan projects one sequence-chunk of hidden states at
    a time, so peak memory is O(B·chunk·V) instead of O(B·S·V) — at
    32k vocab this is what bounds the trainable batch size on a chip.
    """
    b, s, d = hidden.shape
    if s % chunk:
        # Largest divisor <= chunk: falling back to chunk=s would
        # materialize the full [B, S, V] logits for any length the
        # default doesn't divide (e.g. seq 2560) — a multi-GB memory
        # cliff. Divisor-poor lengths (primes) floor at 128: below
        # that the scan degrades to matvecs, and a single full-logits
        # pass is the lesser evil for such (rare, short-eval) shapes.
        chunk = next(c for c in range(min(chunk, s), 0, -1) if s % c == 0)
        if chunk < 128:
            chunk = s
    n = s // chunk
    xc = hidden.reshape(b, n, chunk, d).swapaxes(0, 1)  # [n, B, chunk, d]
    tc = targets.reshape(b, n, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def body(acc, xt):
        xcb, tcb = xt
        logits = (xcb @ lm_head.astype(dtype)).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, tcb[..., None], axis=-1)[..., 0]
        return acc + (logz - tgt).sum(), None

    total, _ = jax.lax.scan(body, jnp.float32(0.0), (xc, tc))
    return total / (b * s)


def loss_fn(
    params: Any,
    batch: dict[str, jnp.ndarray],
    cfg: LlamaConfig,
    attn_fn=None,
) -> tuple[jnp.ndarray, dict[str, jnp.ndarray]]:
    """Next-token cross entropy. batch["tokens"]: [B, S+1] int32."""
    from ray_tpu.models.llama import forward_with_aux
    from ray_tpu.models.moe import MoEConfig, moe_forward, router_losses

    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    if isinstance(cfg, MoEConfig):
        hidden, aux = moe_forward(
            params, inputs, cfg, attn_fn=attn_fn, return_hidden=True
        )
        router = router_losses(aux, cfg)
    else:
        hidden, _ = forward_with_aux(
            params, inputs, cfg, attn_fn=attn_fn, return_hidden=True
        )
        router = None
    ce = chunked_cross_entropy(
        hidden, params["lm_head"], targets, cfg.dtype
    )
    metrics = {"loss": ce, "perplexity": jnp.exp(ce)}
    if router is None:
        return ce, metrics
    metrics.update(router)
    return ce + router["aux_loss"] + router["router_z_loss"], metrics


def make_train_step(
    cfg: LlamaConfig,
    optimizer: optax.GradientTransformation,
    attn_fn=None,
):
    """Returns train_step(state, batch) -> (state, metrics), ready to jit."""

    def train_step(state: TrainState, batch: dict[str, jnp.ndarray]):
        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
        (_, metrics), grads = grad_fn(state.params, batch, cfg, attn_fn)
        updates, opt_state = optimizer.update(
            grads, state.opt_state, state.params
        )
        params = optax.apply_updates(state.params, updates)
        metrics["grad_norm"] = optax.global_norm(grads)
        return TrainState(state.step + 1, params, opt_state), metrics

    return train_step


def jit_train_step(
    cfg: LlamaConfig,
    optimizer: optax.GradientTransformation,
    mesh,
    batch_axes: tuple = ("batch", None),
):
    """jit the train step with sharded state in/out and donated state.

    ``batch_axes`` shards the raw token batch [B, S+1]; the sequence dim is
    left unsharded by default (S+1 rarely divides sp) — activations get
    their seq sharding from the `constrain` calls inside the model.
    """
    attn_fn = None
    if cfg.attn_impl == "ring":
        from ray_tpu.parallel.ring_attention import make_ring_attention

        attn_fn = make_ring_attention(mesh)
    elif cfg.attn_impl == "ulysses":
        from ray_tpu.parallel.ulysses import make_ulysses_attention

        attn_fn = make_ulysses_attention(mesh)
    elif cfg.attn_impl == "flash":
        from ray_tpu.ops.pallas.flash_attention import make_flash_attention

        attn_fn = make_flash_attention(mesh)
    elif cfg.attn_impl != "dense":
        raise ValueError(f"unknown attn_impl {cfg.attn_impl!r}")
    step = make_train_step(cfg, optimizer, attn_fn=attn_fn)

    if mesh is None or mesh.size == 1:
        # Single chip: sharding annotations + the mesh context are pure
        # overhead — the constraint ops inhibit fusion (measured ~1% on
        # the v5e bench) — and computing the shardings at all would
        # crash for mesh=None. Plain donated jit.
        return jax.jit(step, donate_argnums=(0,))

    axes = state_logical_axes(cfg, optimizer)
    state_sh = tree_shardings(mesh, axes)
    batch_sh = {"tokens": tree_shardings(mesh, batch_axes)}

    def step_in_mesh(state, batch):
        with use_mesh(mesh):
            return step(state, batch)

    return jax.jit(
        step_in_mesh,
        in_shardings=(state_sh, batch_sh),
        out_shardings=(state_sh, None),
        donate_argnums=(0,),
    )
