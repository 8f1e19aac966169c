"""OLMoE-1B-7B's forward pass and loss, plainly: float32 ``jax.numpy``,
no kernel, no sort, no grouped matmul, no cache, matmuls at ``highest``
precision (on a TPU a float32 matmul otherwise runs in bf16 passes).

Follows the published architecture (Muennighoff et al. 2024,
arXiv:2409.02060; ``modeling_olmoe.py``): pre-norm decoder blocks;
RMSNorm with a learned weight over the whole query and key projections
before the split into heads; rotary embeddings in the split-halves
("rotate_half") convention; causal attention with as many key as query
heads; a router that takes the softmax over all experts in float32 and
keeps the ``top_k`` largest probabilities as gates **without
renormalising them** (``norm_topk_prob`` false); every chosen
(token, expert) pair computed, each expert a SwiGLU of its own width;
no shared expert; RMSNorm eps 1e-5; an untied output head. The loss is
the next-token cross entropy plus 0.01 x the load-balancing loss plus
0.001 x the router z-loss (the paper's section 3).

Each expert is applied, in a Python loop over all of them, to every row
and kept for the rows that chose it, by a mask.

Departures, noted:

- the repo stores a norm's weight as ``scale`` with the layer computing
  ``x * (1 + scale)`` (``ops/norms.py``; initialised 0 where OLMoE
  initialises its weight to 1), so this file reads the weight as
  ``1 + scale``;
- the load-balancing loss is taken per layer over that layer's tokens
  and averaged over layers, as the paper writes it;
  ``modeling_olmoe.py`` pools the tokens of all layers before the
  product of routed share and mean probability.

``forward`` takes optional ``routes`` (``[layers, T, top_k]`` expert
ids, T = batch x sequence in row-major order): the experts each token is
sent to, in place of the reference's own top-k. Routing is discrete, and
with random weights a token's 8th and 9th probabilities are often closer
than bf16 rounding, so a comparison of logits forces the system's routes
on the reference, and compares the routes themselves apart
(``forward_with_router`` hands out, beside the logits, the reference's
own choice, its margin, and how far below the reference's cut the forced
routes lie).

Takes the program's parameter tree (``tok_emb``, ``blocks`` stacked on a
leading layer dimension, ``final_norm``, ``lm_head``) and nothing else
of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-5


def _rms_norm(x, scale):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + EPS) * (1.0 + scale)


def _rope(x, theta):
    """x: [B, S, H, D]; split-halves rotation by absolute position."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward_with_router(params, tokens, *, n_heads, rope_theta, top_k,
                        norm_topk_prob, routes=None):
    """logits [B, S, V] and the router's record, stacked over layers:
    ``routes`` [layers, T, top_k], the reference's own choice whether or
    not another was forced; ``margin`` [layers, T], how far each token
    is from going to another expert: ``1 - p(top_k + 1) / p(top_k)`` of
    its sorted probabilities; ``slack`` [layers, T], how far below the
    reference's own cut the lowest of the *applied* routes lies,
    ``1 - min_j p(applied_j) / p(top_k)``: 0 where the applied routes
    are the reference's own, at most ``margin`` where one expert was
    swapped for the next; ``balance`` and ``z`` [layers], the two
    router losses, unweighted, of the routes that were applied."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
        x = f32(params["tok_emb"])[tokens]
        b, s, d = x.shape
        blocks = params["blocks"]
        dh = d // n_heads
        causal = jnp.tril(jnp.ones((s, s), bool))
        record = {"routes": [], "margin": [], "slack": [], "balance": [],
                  "z": []}
        for i in range(blocks["wq"].shape[0]):
            p = {k: f32(v[i]) for k, v in blocks.items()}
            h = _rms_norm(x, p["attn_norm"])
            q = _rms_norm(h @ p["wq"], p["q_norm"]).reshape(b, s, n_heads, dh)
            k = _rms_norm(h @ p["wk"], p["k_norm"]).reshape(b, s, n_heads, dh)
            v = (h @ p["wv"]).reshape(b, s, n_heads, dh)
            q, k = _rope(q, rope_theta), _rope(k, rope_theta)
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
                jnp.float32(dh)
            )
            scores = jnp.where(causal[None, None], scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1)
            attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, -1)
            x = x + attn @ p["wo"]

            h = _rms_norm(x, p["mlp_norm"]).reshape(b * s, d)
            router_logits = h @ p["router"]
            probs = jax.nn.softmax(router_logits, axis=-1)  # [T, E]
            n_experts = probs.shape[-1]
            top, own = jax.lax.top_k(probs, top_k + 1)
            chosen = own[:, :top_k] if routes is None else routes[i]
            gates = jnp.take_along_axis(probs, chosen, axis=-1)  # [T, k]
            gates_as_given = gates
            if norm_topk_prob:
                gates = gates / gates.sum(-1, keepdims=True)
            y = jnp.zeros_like(h)
            for e in range(n_experts):
                # The gate of expert e for each row: 0 where the row did
                # not choose it.
                weight = jnp.where(chosen == e, gates, 0.0).sum(-1)
                out = (
                    jax.nn.silu(h @ p["w_gate"][e]) * (h @ p["w_up"][e])
                ) @ p["w_down"][e]
                y = y + weight[:, None] * out
            x = x + y.reshape(b, s, d)

            share = jnp.zeros(n_experts).at[chosen.reshape(-1)].add(1.0) / (
                b * s
            )
            record["routes"].append(own[:, :top_k])
            record["margin"].append(1.0 - top[:, top_k] / top[:, top_k - 1])
            record["slack"].append(
                jnp.maximum(1.0 - gates_as_given.min(-1) / top[:, top_k - 1], 0.0)
            )
            record["balance"].append(
                n_experts * (share * probs.mean(0)).sum()
            )
            record["z"].append(
                jnp.square(jax.nn.logsumexp(router_logits, axis=-1)).mean()
            )
        x = _rms_norm(x, f32(params["final_norm"]))
        logits = x @ f32(params["lm_head"])
        return logits, {k: jnp.stack(v) for k, v in record.items()}


def forward(params, tokens, **kw):
    """tokens [B, S] int32 -> logits [B, S, V] float32."""
    return forward_with_router(params, tokens, **kw)[0]


def loss(params, tokens, *, aux_loss_weight=0.01, z_loss_weight=0.001,
         **kw):
    """Mean next-token cross entropy of ``tokens`` [B, S + 1], plus the
    two weighted router losses, each the mean over layers."""
    logits, record = forward_with_router(params, tokens[:, :-1], **kw)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return (
        -picked.mean()
        + aux_loss_weight * record["balance"].mean()
        + z_loss_weight * record["z"].mean()
    )


def for_model(model: dict) -> dict:
    """``forward``'s keyword arguments, from a configuration file's keys."""
    return {
        "n_heads": model["num_attention_heads"],
        "rope_theta": float(model["rope_theta"]),
        "top_k": model["num_experts_per_tok"],
        "norm_topk_prob": bool(model["norm_topk_prob"]),
    }
