"""Mistral-7B's forward pass, plainly: float32 ``jax.numpy``, no kernel,
no cache, no batching tricks, matmuls at ``highest`` precision (on a TPU
a float32 matmul otherwise runs in bf16 passes).

Follows the published architecture (Jiang et al. 2023, arXiv:2310.06825;
``modeling_mistral.py``): pre-norm decoder blocks of grouped-query
attention with rotary embeddings in the split-halves ("rotate_half")
convention, SwiGLU feed-forward, RMSNorm with eps 1e-5, an untied output
head; v0.3 has no sliding window. One departure, noted: the repo stores a
norm's weight as ``scale`` with the layer computing ``x * (1 + scale)``
(``ops/norms.py``; initialised 0 where Mistral initialises its weight to
1), so this file reads the weight as ``1 + scale``.

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


def forward(params, tokens, *, n_heads: int, n_kv_heads: int,
            rope_theta: float):
    """tokens [B, S] int32 -> logits [B, S, V] float32."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
        x = f32(params["tok_emb"])[tokens]
        b, s, d = x.shape
        blocks = params["blocks"]
        dh = blocks["wq"].shape[-1] // n_heads
        causal = jnp.tril(jnp.ones((s, s), bool))
        for i in range(blocks["wq"].shape[0]):
            p = {k: f32(v[i]) for k, v in blocks.items()}
            h = _rms_norm(x, p["attn_norm"])
            q = (h @ p["wq"]).reshape(b, s, n_heads, dh)
            k = (h @ p["wk"]).reshape(b, s, n_kv_heads, dh)
            v = (h @ p["wv"]).reshape(b, s, n_kv_heads, dh)
            q, k = _rope(q, rope_theta), _rope(k, rope_theta)
            # Each KV head serves n_heads / n_kv_heads consecutive
            # query heads.
            k = jnp.repeat(k, n_heads // n_kv_heads, axis=2)
            v = jnp.repeat(v, n_heads // n_kv_heads, axis=2)
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
                jnp.float32(dh)
            )
            scores = jnp.where(causal[None, None], scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1)
            attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, -1)
            x = x + attn @ p["wo"]
            h = _rms_norm(x, p["mlp_norm"])
            x = x + (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p[
                "w_down"
            ]
        x = _rms_norm(x, f32(params["final_norm"]))
        return x @ f32(params["lm_head"])


def loss(params, tokens, **kw):
    """Mean next-token cross entropy of ``tokens`` [B, S + 1]."""
    logits = forward(params, tokens[:, :-1], **kw)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -picked.mean()


def for_model(model: dict) -> dict:
    """The keyword arguments above, from a configuration file's keys."""
    return {
        "n_heads": model["num_attention_heads"],
        "n_kv_heads": model["num_key_value_heads"],
        "rope_theta": float(model["rope_theta"]),
    }
