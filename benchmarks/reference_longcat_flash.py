"""LongCat-Flash's language model, plainly: float32 ``jax.numpy``, no
kernel, no cache, no absorbed matrices, no sort, no grouped matmul,
matmuls at ``highest`` precision (on a TPU a float32 matmul otherwise
runs in bf16 passes). One full pass over one sequence.

Follows the published architecture (``config.json`` of
LongCat-Flash-Omni: ``attention_method: MLA``, ``mla_scale_q_lora``,
``mla_scale_kv_lora``, ``zero_expert_num`` 256, ``zero_expert_type:
identity``, ``moe_topk`` 12; the family's report, arXiv:2509.01322:
the double layer with its shortcut-connected experts, and
zero-computation experts). One layer, x ``[T, d]``, N an RMSNorm with
gain, eps 1e-5:

    a1 = x  + MLA_0(Na1(x))
    u  = Nf1(a1);  s = MoE(u)
    h1 = a1 + FFN_1(u)
    a2 = h1 + MLA_1(Na2(h1))
    y  = a2 + FFN_2(Nf2(a2)) + s

- MLA in its NON-absorbed form (DeepSeek-V2, arXiv:2405.04434, section
  2.1), so that the serving programs' absorbed decode is checked against
  an independent one: ``cq = Nq(h Wqa)``; per head ``[q_nope; q_pe] = aq
  (cq Wqb)``, ``aq = sqrt(hidden / q_lora_rank)``; ``[c; kpe] = h Wkva``,
  ``c = akv Nkv(c)``, ``akv = sqrt(hidden / kv_lora_rank)``, ``kpe`` not
  scaled and one for all heads; rope on ``q_pe`` and ``kpe``;
  ``[k_nope_h; v_h] = c Wkvb_h``; ``o_h = softmax(q_h k_h^T (nope +
  rope)^-0.5, causal) v_h``; out ``= concat(o_h) Wo``. What a cache
  would hold per token and attention sublayer is ``[c; rope(kpe)]``: the
  record's ``latents``, two a layer.
- MoE: ``p = softmax(u Wr)`` over the router's ``E + Z`` outputs (``E``
  real experts, ``Z`` identity outputs behind them); the ``k`` largest
  of ``p + bias`` chosen; ``g_e = routed_scaling_factor * p_e``, not
  renormalised; ``sum_{e < E} g_e SwiGLU_e(u) + (sum_{e >= E} g_e) u``.
  Each held expert is applied, in a plain loop over the experts, to
  every row and kept for the rows that chose it, by a mask.
- Rope: split halves, as ``reference_pangu_ultra_moe`` (imported).

The share: where the tree holds ``held`` of the model's experts (the
experts ``first .. first + held - 1``), a pair whose expert is not held
adds nothing, here as in the program; the identity part is what every
chip computes for its own rows and is whole; the vocabulary is whatever
the tree's embedding and head hold.

At the published widths the pass has to fit beside a serving engine, so
each sublayer's function works in pieces inside one program, as
``reference_pangu_ultra_moe``'s: attention a group of heads and a block
of queries at a time, a dense SwiGLU a block of rows at a time, one
expert at a time.

``forward_with_record`` takes optional ``routes`` (``[layers, T, k]``) in
place of the reference's own choice, and records ``routes``, ``margin``
and ``slack`` as ``reference_nemotron_h`` does (identity outputs are
routes like any other). ``lower`` names one thing to compute otherwise,
for the reading that a limit has to fail: ``"weights_e4m3"`` (every
matmul weight rounded to float8 e4m3), ``"router_bf16"`` (router input,
weights and scores in bfloat16), ``"no_identity"`` (the identity
outputs' sum dropped), ``"latent_unscaled"`` (``akv`` = 1),
``"shortcut_early"`` (``s`` added to ``h1``, one attention sublayer
before its place).

Takes the program's parameter tree (``tok_emb``, ``blocks``: per layer
``attn`` (two trees), ``ffn`` (two trees), ``moe``; ``final_norm``,
``lm_head``) and nothing else of the program.
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
from benchmarks.reference_pangu_ultra_moe import (
    HEAD_GROUP,
    QUERY_BLOCK,
    ROW_BLOCK,
    _in_blocks,
    _rope,
    _swiglu,
)


def attention(p, x, *, num_attention_heads, qk_nope_head_dim,
              qk_rope_head_dim, kv_lora_rank, rope_theta, q_latent_scale,
              kv_latent_scale, lower=None, **_):
    """x [T, d] -> (x + MLA(Na(x)), ``[c; rope(kpe)]`` [T, W])."""
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        n_heads, nope, rope = num_attention_heads, qk_nope_head_dim, qk_rope_head_dim
        if lower == "latent_unscaled":
            kv_latent_scale = 1.0
        h = _rms_norm(x, _f32(p["norm1"]))
        cq = _rms_norm(h @ _weight(p["wq_a"], lower), _f32(p["q_norm"]))
        ckv = h @ _weight(p["wkv_a"], lower)
        c = kv_latent_scale * _rms_norm(
            ckv[:, :kv_lora_rank], _f32(p["kv_norm"])
        )
        kpe = _rope(ckv[:, kv_lora_rank:], rope_theta)  # [T, rope]
        scale = (nope + rope) ** -0.5
        groups = n_heads // min(HEAD_GROUP, n_heads)
        per = n_heads // groups
        wq_b = p["wq_b"].reshape(-1, groups, per, nope + rope).transpose(1, 0, 2, 3)
        w_uk = p["w_uk"].reshape((groups, per) + p["w_uk"].shape[1:])
        w_uv = p["w_uv"].reshape((groups, per) + p["w_uv"].shape[1:])
        wo = p["wo"].reshape(groups, per * p["w_uv"].shape[-1], -1)
        key_pos = jnp.arange(t)

        def one_group(out, group):
            g_wq_b, g_w_uk, g_w_uv, g_wo = group
            q = q_latent_scale * jnp.einsum(
                "tr,rhd->thd", cq, _weight(g_wq_b, lower)
            )
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
        return x + out, jnp.concatenate([c, kpe], -1)


def dense_ffn(p, x, *, lower=None, **_):
    """x [T, d] -> x + SwiGLU(Nf(x))."""
    with jax.default_matmul_precision("highest"):
        out = _in_blocks(
            lambda rows: _swiglu(rows, p["w_gate"], p["w_up"], p["w_down"], lower),
            _rms_norm(x, _f32(p["norm"])), ROW_BLOCK,
        )
        return x + out


def expert_branch(norm, p, x, routes=None, *, moe_topk, n_routed_experts,
                  routed_scaling_factor, first_expert_held=0, lower=None, **_):
    """x [T, d] (``a1``) -> (``MoE(Nf1(x))``, the router's record of this
    layer): ``routes`` [T, k], the reference's own choice whether or not
    another was forced; ``margin`` [T], ``1 - sel(k + 1) / sel(k)`` of
    the sorted selection scores; ``slack`` [T], how far below the
    reference's own cut the lowest *applied* route lies. ``norm`` is the
    first dense FFN's input norm; ``n_routed_experts`` the model's real
    experts, behind which the router's outputs are identity experts."""
    with jax.default_matmul_precision("highest"):
        k = moe_topk
        u = _rms_norm(x, _f32(norm))
        if lower == "router_bf16":
            probs = _to_bf16(jax.nn.softmax(
                _to_bf16(_to_bf16(u) @ _to_bf16(_f32(p["router"]))), axis=-1
            ))
        else:
            probs = jax.nn.softmax(u @ _f32(p["router"]), axis=-1)  # [T, E + Z]
        select = probs + _f32(p["router_bias"])
        top, own = jax.lax.top_k(select, k + 1)
        chosen = own[:, :k] if routes is None else routes
        gates = routed_scaling_factor * jnp.take_along_axis(
            probs, chosen, axis=-1
        )

        def one_expert(y, expert):
            e, w_gate, w_up, w_down = expert
            weight = jnp.where(chosen == first_expert_held + e, gates, 0.0)
            return y + weight.sum(-1)[:, None] * _swiglu(
                u, w_gate, w_up, w_down, lower
            ), None

        held = p["w_up"].shape[0]
        y, _ = jax.lax.scan(
            one_expert, jnp.zeros_like(u),
            (jnp.arange(held), p["w_gate"], p["w_up"], p["w_down"]),
        )
        if lower != "no_identity":
            identity = jnp.where(chosen >= n_routed_experts, gates, 0.0)
            y = y + identity.sum(-1)[:, None] * u
        applied = jnp.take_along_axis(select, chosen, axis=-1)
        record = {
            "routes": own[:, :k],
            "margin": 1.0 - top[:, k] / top[:, k - 1],
            "slack": jnp.maximum(1.0 - applied.min(-1) / top[:, k - 1], 0.0),
        }
        return y, record


def forward_with_record(params, tokens, *, routes=None, rows=None,
                        block_fn=lambda kind, fn: fn, **sizes):
    """tokens [T] int32 -> (logits [T, V] float32, or of ``rows`` only;
    the record). The record holds ``latents`` [2 L, T, W], what a cache
    would hold of each token in each attention sublayer (row ``2 i + j``),
    and, stacked over the layers, ``routes`` [L, T, k], ``margin`` and
    ``slack`` [L, T].

    ``block_fn(kind, fn)`` wraps each sublayer's function; the chip
    check passes ``jax.jit`` so that the pass runs sublayer by sublayer,
    one compiled program per kind, and fits beside the engine."""
    attend = block_fn("A", lambda p, x: attention(p, x, **sizes))
    dense = block_fn("D", lambda p, x: dense_ffn(p, x, **sizes))
    experts = block_fn(
        "E", lambda norm, p, x, forced: expert_branch(norm, p, x, forced, **sizes)
    )
    early = sizes.get("lower") == "shortcut_early"
    x = embed(params, tokens)
    record = {"latents": [], "routes": [], "margin": [], "slack": []}
    for i, p in enumerate(params["blocks"]):
        first, second = p["ffn"]
        x, latent = attend(p["attn"][0], x)
        record["latents"].append(latent)
        shortcut, rec = experts(
            first["norm"], p["moe"], x, None if routes is None else routes[i]
        )
        for key, value in rec.items():
            record[key].append(value)
        x = dense(first, x)
        if early:
            x = x + shortcut
        x, latent = attend(p["attn"][1], x)
        record["latents"].append(latent)
        x = dense(second, x)
        if not early:
            x = x + shortcut
    if rows is not None:
        x = x[jnp.asarray(rows)]
    logits = head(params, x, sizes.get("lower"))
    return logits, {k: jnp.stack(v) for k, v in record.items()}


def forward(params, tokens, **kw):
    """tokens [T] int32 -> logits [T, V] float32."""
    return forward_with_record(params, tokens, **kw)[0]


def for_model(model: dict) -> dict:
    """The keyword arguments above, from a configuration file's keys."""
    keys = (
        "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
        "kv_lora_rank", "moe_topk",
    )
    hidden = model["hidden_size"]
    return {k: model[k] for k in keys} | {
        "rope_theta": float(model["rope_theta"]),
        "routed_scaling_factor": float(model["routed_scaling_factor"]),
        # The real experts of the model, held or not: the router's
        # outputs from there on are identity experts.
        "n_routed_experts": model.get("published", {}).get(
            "n_routed_experts", model["n_routed_experts"]
        ),
        "first_expert_held": model.get("first_expert_held", 0),
        "q_latent_scale": (
            (hidden / model["q_lora_rank"]) ** 0.5
            if model["mla_scale_q_lora"] else 1.0
        ),
        "kv_latent_scale": (
            (hidden / model["kv_lora_rank"]) ** 0.5
            if model["mla_scale_kv_lora"] else 1.0
        ),
    }
