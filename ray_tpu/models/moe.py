"""Mixture-of-Experts decoder: dropless top-k routing with a grouped
expert matmul.

Every (token, expert) pair the router picks is computed: the pairs are
sorted by expert (one stable argsort), the rows gathered in that order,
the three expert matmuls run as grouped matmuls whose ``group_sizes``
are the tokens each expert received (``jax.lax.ragged_dot``), the
results weighted by the gates and added back per token. There is no
per-expert slot limit and no one-hot dispatch tensor, so the work is
tokens x top_k whatever the skew. Experts carry the "expert" logical
axis, sharded over the mesh's ep axis.

The attention sublayer, scan scaffolding, and non-expert parameters are
the flagship Llama's (ray_tpu.models.llama — this module only swaps the
FFN hook). The shape fields are those of OLMoE-1B-7B-0125-Instruct
(Muennighoff et al. 2024, arXiv:2409.02060: 64 experts of width 1024,
8 per token, gates not renormalised, QK-norm), the public model the
benchmark trains through this file.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu.models.llama import (
    LlamaConfig,
    Params,
    forward_with_aux,
    init_params,
    param_logical_axes,
)


@dataclasses.dataclass(frozen=True)
class MoEConfig(LlamaConfig):
    """``d_ff`` is the width of one expert."""

    num_experts: int = 8
    top_k: int = 2
    # Whether the top-k gates are renormalised to sum to 1: a field of
    # the model's shape, read from its published config
    # (``norm_topk_prob``), like n_kv_heads.
    norm_topk_prob: bool = False
    # Weights of the router's two auxiliary losses (OLMoE section 3):
    # load balance (Switch section 2.2) and the z-loss on its logits.
    aux_loss_weight: float = 0.01
    z_loss_weight: float = 0.001

    def _matmul_params(self, experts: int) -> int:
        """Parameters of the matrices a token meets when each layer
        applies ``experts`` experts: projections, router, experts, head."""
        d, hd = self.d_model, self.head_dim
        attn = 2 * d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd
        per_layer = attn + d * self.num_experts + experts * 3 * d * self.d_ff
        return self.n_layers * per_layer + d * self.vocab_size

    def num_params(self) -> int:
        """All parameters held: what memory and the optimizer pay for."""
        d = self.d_model
        norms = 2 * d
        if self.qk_norm:
            norms += (self.n_heads + self.n_kv_heads) * self.head_dim
        return (
            self._matmul_params(self.num_experts)
            + self.n_layers * norms + d + self.vocab_size * d
        )

    def flops_per_token(self, seq: int) -> float:
        """Training (fwd+bwd) FLOPs per token: 6 x the matmul parameters
        a token passes through (``top_k`` experts a layer, not all) plus
        causal attention (a query at position t reads t + 1 keys)."""
        attention = 12 * self.n_layers * self.n_heads * self.head_dim * (
            seq + 1
        ) / 2
        return 6.0 * self._matmul_params(self.top_k) + attention


MOE_PRESETS: dict[str, MoEConfig] = {
    # CPU-test scale, with OLMoE's switches on (as many key as query
    # heads, QK-norm, gates as they are).
    "moe_tiny": MoEConfig(
        vocab_size=512, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4,
        d_ff=128, max_seq=256, dtype=jnp.float32, remat="none",
        num_experts=4, top_k=2, qk_norm=True,
    ),
}


def moe_param_logical_axes(cfg: MoEConfig) -> Params:
    axes = param_logical_axes(cfg)
    axes["blocks"].update(
        router=("layers", "embed", "expert"),
        w_gate=("layers", "expert", "embed", "mlp"),
        w_up=("layers", "expert", "embed", "mlp"),
        w_down=("layers", "expert", "mlp", "embed"),
    )
    return axes


def init_moe_params(key: jax.Array, cfg: MoEConfig) -> Params:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    L = cfg.n_layers
    base_key, *keys = jax.random.split(key, 5)

    def w(k, shape, fan_in):
        return (
            jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32)
            * fan_in**-0.5
        )

    params = init_params(base_key, cfg)
    params["blocks"].update(
        router=w(keys[0], (L, d, e), d),
        w_gate=w(keys[1], (L, e, d, f), d),
        w_up=w(keys[2], (L, e, d, f), d),
        w_down=w(keys[3], (L, e, f, d), f),
    )
    return params


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _take_rows(x, index, inverse, fan):
    """``x[index // fan]`` where ``index`` is a permutation of
    ``range(fan * len(x))`` and ``inverse`` its inverse: each row is read
    ``fan`` times. The cotangent is a gather by ``inverse`` and a sum of
    each row's ``fan`` copies, where the gather's own transpose would be
    a scatter-add of the same rows."""
    return x[index // fan]


def _take_rows_fwd(x, index, inverse, fan):
    return x[index // fan], inverse


def _take_rows_bwd(fan, inverse, g):
    rows = g[inverse]
    return rows.reshape(-1, fan, g.shape[-1]).sum(1), None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def moe_ffn(x: jnp.ndarray, p: Params, cfg: MoEConfig):
    """FFN hook for llama._block: x [B, S, d] -> (out, aux).

    ``aux`` is the layer's router record: ``balance_loss`` and
    ``z_loss`` (unweighted), ``expert_load`` (pairs each expert
    computed, int32[e]: their sum is tokens x top_k, nothing is
    dropped) and ``routes`` (the experts of each token, int32[T, k])."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.top_k
    n = b * s
    dt = cfg.dtype
    tokens = x.reshape(n, d)

    with jax.named_scope("moe:route"):
        # Matmul, softmax and top-k in float32: the 8th and 9th
        # probabilities of a token are often closer than bf16 rounding.
        logits = jnp.dot(
            tokens.astype(jnp.float32), p["router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        probs = jax.nn.softmax(logits, axis=-1)  # [n, e]
        gates, routes = jax.lax.top_k(probs, k)  # [n, k]
        if cfg.norm_topk_prob:
            gates = gates / gates.sum(-1, keepdims=True)

    with jax.named_scope("moe:dispatch"):
        # Pairs in expert order; a stable sort keeps each expert's rows
        # in token order.
        pair_expert = routes.reshape(n * k)
        order = jnp.argsort(pair_expert, stable=True)
        inverse = jnp.argsort(order)
        load = jnp.bincount(pair_expert, length=e).astype(jnp.int32)
        rows = _take_rows(tokens, order, inverse, k)  # [n * k, d]

    with jax.named_scope("moe:experts"):
        gate = jax.lax.ragged_dot(rows, p["w_gate"].astype(dt), load)
        up = jax.lax.ragged_dot(rows, p["w_up"].astype(dt), load)
        rows_out = jax.lax.ragged_dot(
            jax.nn.silu(gate) * up, p["w_down"].astype(dt), load
        )

    with jax.named_scope("moe:combine"):
        # Back to token order: pair j of token t sits at row t * k + j.
        pairs = _take_rows(rows_out, inverse, order, 1).reshape(n, k, d)
        out = (pairs.astype(jnp.float32) * gates[..., None]).sum(1)
        out = out.astype(dt)

    with jax.named_scope("moe:route"):
        # Load balance: e * sum_e (share of pairs routed to e) * (mean
        # probability of e) (Switch section 2.2); z-loss: the mean
        # squared logsumexp of the router's logits.
        balance = e * (probs.mean(0) * (load / n)).sum()
        z = jnp.square(jax.nn.logsumexp(logits, axis=-1)).mean()
    aux = {"balance_loss": balance, "z_loss": z, "expert_load": load,
           "routes": routes}
    return out.reshape(b, s, d), aux


def moe_forward(
    params: Params,
    tokens: jnp.ndarray,
    cfg: MoEConfig,
    attn_fn=None,
    return_hidden: bool = False,
) -> tuple[jnp.ndarray, dict]:
    """tokens [B, S] -> (logits [B, S, V] fp32 — or final hidden states
    with ``return_hidden`` — and every layer's router record, stacked
    on a leading layer dimension: see :func:`moe_ffn`)."""
    # The experts are cast to the compute dtype as whole stacks, before
    # the scan over layers, so that the scan's backward pass stacks their
    # gradients in that dtype too (they are the grouped matmul's outputs,
    # upcast) and the float32 gradient exists only inside the fusions
    # that consume it: at OLMoE's widths 1.6 GB less at the step's peak.
    blocks = dict(params["blocks"])
    for name in ("w_gate", "w_up", "w_down"):
        blocks[name] = blocks[name].astype(cfg.dtype)
    return forward_with_aux(
        {**params, "blocks": blocks}, tokens, cfg, attn_fn=attn_fn,
        ffn_fn=moe_ffn, return_hidden=return_hidden,
    )


def router_losses(aux: dict, cfg: MoEConfig) -> dict[str, jnp.ndarray]:
    """The weighted auxiliary losses and the step's router counters,
    from ``moe_forward``'s record."""
    load = aux["expert_load"].astype(jnp.float32)  # [L, e]
    return {
        "aux_loss": cfg.aux_loss_weight * aux["balance_loss"].mean(),
        "router_z_loss": cfg.z_loss_weight * aux["z_loss"].mean(),
        # Largest expert's pairs over the mean, in the worst layer.
        "expert_load_max_over_mean": (
            load.max(-1) / load.mean(-1)
        ).max(),
        # Pairs computed this step: tokens x top_k x layers, always.
        "moe_pairs": aux["expert_load"].sum(),
    }
