"""Pangu Ultra MoE's forward pass, plainly: float32 ``jax.numpy``, no
kernel, no cache, no absorbed matrices, no sort, no grouped matmul,
matmuls at ``highest`` precision (on a TPU a float32 matmul otherwise
runs in bf16 passes). One full pass over one sequence.

Follows the published architecture (``config.json`` of
openPangu-Ultra-MoE-718B, ``model_type: pangu_ultra_moe``; latent
attention as DeepSeek-V2, arXiv:2405.04434, section 2.1, in its
NON-absorbed form, so that the serving programs' absorbed decode is
checked against an independent one). One layer, x ``[T, d]``:

    h = x + N2(MLA(N1(x)));  y = h + N4(F(N3(h)))

``N*`` RMSNorm with gain, eps 1e-5 (``sandwich_norm``: a norm before AND
after each sublayer); ``F`` the dense SwiGLU in the first
``first_k_dense_replace`` layers and the expert layer in the rest.

- MLA: ``cq = Nq(x Wqa)`` ``[T, q_lora_rank]``; ``q = cq Wqb`` -> H heads
  of ``[q_nope (nope); q_pe (rope)]``; ``[c; kpe] = x Wkva`` with ``c =
  Nkv(c)`` ``[T, kv_lora_rank]`` and ``kpe`` ``[T, rope]`` one for all
  heads; rope on ``q_pe`` and ``kpe``; ``[k_nope_h; v_h] = c Wkvb_h``;
  ``k_h = [k_nope_h; kpe]``; ``o_h = softmax(q_h k_h^T (nope +
  rope)^-0.5, causal) v_h``; out ``= concat(o_h) Wo``. What a cache
  would hold per token and layer is ``[c; rope(kpe)]``: the record's
  ``latents``.
- Expert layer: ``s = sigmoid(x Wr)`` over all experts; the ``top_k``
  largest (of ``s + bias``; the published model has no bias, the tree's
  is zero); ``g = routed_scaling_factor * s_top / sum(s_top)``;
  ``sum_i g_i E_i(x) + E_shared(x)``, ``E(x) = (silu(x Wg) * (x Wu)) Wd``.
  Each held expert is applied, in a plain loop over the experts, to
  every row and kept for the rows that chose it, by a mask.
- Rope: split halves, dimension ``i`` of the first half pairs with ``i``
  of the second at angle ``pos * theta^(-2i / rope)`` (the repo's
  convention; with random weights a pairing is a permutation of
  ``Wqb``'s and ``Wkva``'s columns).

The share: where the tree holds ``held`` of the model's experts (the
experts ``first .. first + held - 1``), a pair whose expert is not held
adds nothing, here as in the program; the vocabulary is whatever the
tree's embedding and head hold.

At the published widths the pass has to fit beside a serving engine, so
each layer's function works in pieces INSIDE one program: attention a
group of heads and a block of queries at a time (9,000 tokens x 128
heads of float32 scores at once would be 41 GB), the dense SwiGLU a
block of rows at a time, one expert at a time. The pieces change the
order of nothing but sums over head groups.

``forward_with_record`` takes optional ``routes`` (``[expert layers, T,
top_k]``) in place of the reference's own choice, and records ``routes``,
``margin`` and ``slack`` as ``reference_nemotron_h`` does. ``lower`` names
one thing to compute in the precision below the one the configuration
states, for the reading that a limit has to fail: ``"weights_e4m3"``
(every matmul weight rounded to float8 e4m3), ``"router_bf16"``
(router input, weights and scores in bfloat16).

Takes the program's parameter tree (``tok_emb``, ``blocks``: one tree
per layer, ``final_norm``, ``lm_head``; ``Wkvb``'s halves as the tree
holds them, ``w_uk`` and ``w_uv`` ``[H, rank, 128]``) and nothing else of
the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference_nemotron_h import (
    _f32,
    _rms_norm,
    _to_bf16,
    _weight,
    embed,
    head,
)

HEAD_GROUP = 8  # heads attended at a time
QUERY_BLOCK = 512  # queries attended at a time
ROW_BLOCK = 1024  # rows of the dense SwiGLU at a time


def _rope(x, theta):
    """x [T, ..., D] rotated by position (row index), split halves."""
    t, d = x.shape[0], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq  # [T, D/2]
    angle = angle.reshape((t,) + (1,) * (x.ndim - 2) + (d // 2,))
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _in_blocks(fn, rows, block):
    """``fn`` on ``rows`` (an array ``[T, ...]`` or a tuple of them) a
    block of rows at a time; zero rows pad the last block and are
    dropped."""
    t = jax.tree.leaves(rows)[0].shape[0]
    block = min(block, t)
    n = -(-t // block)

    def blocks(a):
        padded = jnp.pad(a, ((0, n * block - t),) + ((0, 0),) * (a.ndim - 1))
        return padded.reshape((n, block) + a.shape[1:])

    out = jax.lax.map(fn, jax.tree.map(blocks, rows))
    return out.reshape((n * block,) + out.shape[2:])[:t]


def attention(p, x, *, num_attention_heads, qk_nope_head_dim,
              qk_rope_head_dim, kv_lora_rank, rope_theta, lower=None, **_):
    """x [T, d] -> (x + N2(MLA(N1(x))), ``[c; rope(kpe)]`` [T, W])."""
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        n_heads, nope, rope = num_attention_heads, qk_nope_head_dim, qk_rope_head_dim
        h = _rms_norm(x, _f32(p["norm1"]))
        cq = _rms_norm(h @ _weight(p["wq_a"], lower), _f32(p["q_norm"]))
        ckv = h @ _weight(p["wkv_a"], lower)
        c = _rms_norm(ckv[:, :kv_lora_rank], _f32(p["kv_norm"]))
        kpe = _rope(ckv[:, kv_lora_rank:], rope_theta)  # [T, rope]
        scale = (nope + rope) ** -0.5
        groups = n_heads // min(HEAD_GROUP, n_heads)
        per = n_heads // groups
        wq_b = p["wq_b"].reshape(-1, groups, per, nope + rope).transpose(1, 0, 2, 3)
        w_uk = p["w_uk"].reshape((groups, per) + p["w_uk"].shape[1:])
        w_uv = p["w_uv"].reshape((groups, per) + p["w_uv"].shape[1:])
        v_dim = p["w_uv"].shape[-1]
        wo = p["wo"].reshape(groups, per * v_dim, -1)
        key_pos = jnp.arange(t)

        def one_group(out, group):
            g_wq_b, g_w_uk, g_w_uv, g_wo = group
            q = jnp.einsum("tr,rhd->thd", cq, _weight(g_wq_b, lower))
            q = jnp.concatenate(
                [q[..., :nope], _rope(q[..., nope:], rope_theta)], -1
            )
            # [k_nope_h; v_h] = c Wkvb_h, the tree's two halves of it.
            w_kvb = jnp.concatenate(
                [_weight(g_w_uk, lower), _weight(g_w_uv, lower)], -1
            )
            kv = jnp.einsum("tc,hcd->thd", c, w_kvb)
            k = jnp.concatenate(
                [kv[..., :nope],
                 jnp.broadcast_to(kpe[:, None, :], (t, per, rope))], -1
            )
            v = kv[..., nope:]

            def one_query_block(rows):
                q_rows, q_pos = rows
                scores = jnp.einsum("qhd,khd->hqk", q_rows, k) * scale
                seen = key_pos[None, :] <= q_pos[:, None]
                probs = jax.nn.softmax(
                    jnp.where(seen[None], scores, -jnp.inf), axis=-1
                )
                return jnp.einsum("hqk,khd->qhd", probs, v)

            # A padded query row sits at position 0 and sees key 0.
            heads = _in_blocks(one_query_block, (q, key_pos), QUERY_BLOCK)
            return out + heads.reshape(t, -1) @ _weight(g_wo, lower), None

        out, _ = jax.lax.scan(
            one_group, jnp.zeros_like(x), (wq_b, w_uk, w_uv, wo)
        )
        latent = jnp.concatenate([c, kpe], -1)
        return x + _rms_norm(out, _f32(p["norm2"])), latent


def _swiglu(h, w_gate, w_up, w_down, lower):
    return (
        jax.nn.silu(h @ _weight(w_gate, lower)) * (h @ _weight(w_up, lower))
    ) @ _weight(w_down, lower)


def dense_ffn(p, x, *, lower=None, **_):
    """x [T, d] -> x + N4(SwiGLU(N3(x)))."""
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, _f32(p["norm3"]))
        out = _in_blocks(
            lambda rows: _swiglu(rows, p["w_gate"], p["w_up"], p["w_down"], lower),
            h, ROW_BLOCK,
        )
        return x + _rms_norm(out, _f32(p["norm4"]))


def expert_ffn(p, x, routes=None, *, num_experts_per_tok, norm_topk_prob,
               routed_scaling_factor, first_expert_held=0, lower=None, **_):
    """x [T, d] -> (x + N4(experts(N3(x))), the router's record of this
    layer): ``routes`` [T, k], the reference's own choice whether or not
    another was forced; ``margin`` [T], ``1 - sel(k + 1) / sel(k)`` of
    the sorted selection scores; ``slack`` [T], how far below the
    reference's own cut the lowest *applied* route lies."""
    with jax.default_matmul_precision("highest"):
        k = num_experts_per_tok
        h = _rms_norm(x, _f32(p["norm3"]))
        if lower == "router_bf16":
            scores = _to_bf16(jax.nn.sigmoid(
                _to_bf16(_to_bf16(h) @ _to_bf16(_f32(p["router"])))
            ))
        else:
            scores = jax.nn.sigmoid(h @ _f32(p["router"]))  # [T, E]
        select = scores + _f32(p["router_bias"])
        top, own = jax.lax.top_k(select, k + 1)
        chosen = own[:, :k] if routes is None else routes
        gates = jnp.take_along_axis(scores, chosen, axis=-1)
        if norm_topk_prob:
            gates = gates / gates.sum(-1, keepdims=True)
        gates = gates * routed_scaling_factor

        def one_expert(y, expert):
            e, w_gate, w_up, w_down = expert
            weight = jnp.where(chosen == first_expert_held + e, gates, 0.0)
            return y + weight.sum(-1)[:, None] * _swiglu(
                h, w_gate, w_up, w_down, lower
            ), None

        held = p["w_up"].shape[0]
        y, _ = jax.lax.scan(
            one_expert, jnp.zeros_like(h),
            (jnp.arange(held), p["w_gate"], p["w_up"], p["w_down"]),
        )
        y = y + _swiglu(
            h, p["shared_gate"], p["shared_up"], p["shared_down"], lower
        )
        applied = jnp.take_along_axis(select, chosen, axis=-1)
        record = {
            "routes": own[:, :k],
            "margin": 1.0 - top[:, k] / top[:, k - 1],
            "slack": jnp.maximum(1.0 - applied.min(-1) / top[:, k - 1], 0.0),
        }
        return x + _rms_norm(y, _f32(p["norm4"])), record


def forward_with_record(params, tokens, *, first_k_dense_replace, routes=None,
                        rows=None, block_fn=lambda kind, fn: fn, **sizes):
    """tokens [T] int32 -> (logits [T, V] float32, or of ``rows`` only;
    the record). The record holds ``latents`` [L, T, W], what a cache
    would hold of each token in each layer, and, stacked over the expert
    layers, ``routes`` [Le, T, k], ``margin`` and ``slack`` [Le, T].

    ``block_fn(kind, fn)`` wraps each sublayer's function; the chip
    check passes ``jax.jit`` so that the pass runs sublayer by sublayer,
    one compiled program per kind, and fits beside the engine."""
    fns = {
        "A": block_fn("A", lambda p, x: attention(p, x, **sizes)),
        "D": block_fn("D", lambda p, x: dense_ffn(p, x, **sizes)),
        "E": block_fn(
            "E", lambda p, x, forced: expert_ffn(p, x, forced, **sizes)
        ),
    }
    x = embed(params, tokens)
    record = {"latents": [], "routes": [], "margin": [], "slack": []}
    n_expert = 0
    for i, p in enumerate(params["blocks"]):
        x, latent = fns["A"](p, x)
        record["latents"].append(latent)
        if i < first_k_dense_replace:
            x = fns["D"](p, x)
        else:
            forced = None if routes is None else routes[n_expert]
            x, rec = fns["E"](p, x, forced)
            n_expert += 1
            for key, value in rec.items():
                record[key].append(value)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    logits = head(params, x, sizes.get("lower"))
    return logits, {k: jnp.stack(v) for k, v in record.items() if v}


def forward(params, tokens, **kw):
    """tokens [T] int32 -> logits [T, V] float32."""
    return forward_with_record(params, tokens, **kw)[0]


def for_model(model: dict) -> dict:
    """The keyword arguments above, from a configuration file's keys."""
    keys = (
        "first_k_dense_replace", "num_attention_heads", "qk_nope_head_dim",
        "qk_rope_head_dim", "kv_lora_rank", "num_experts_per_tok",
        "norm_topk_prob", "routed_scaling_factor",
    )
    return {k: model[k] for k in keys} | {
        "rope_theta": float(model["rope_theta"]),
        "first_expert_held": model.get("first_expert_held", 0),
    }
