"""Motif-3-Beta's language model, plainly: float32 ``jax.numpy``, no
kernel, no cache, no absorbed attention, no ring, no sort, no grouped
matmul, matmuls at ``highest`` precision. One full pass over one
sequence.

Follows ISSUE 65's equations (``config.json`` of
Motif-Technologies/Motif-3-Beta, ``model_type: Motif``; grouped
differential attention, arXiv:2510.06949, in Differential Transformer
V2's form; multi-head latent attention, arXiv:2405.04434; mHC,
arXiv:2512.24880; PolyNorm, arXiv:2411.03884). With ``d`` the hidden
size, every ``Linear`` without a bias, ``norm(x) = x / sqrt(mean(x^2) +
eps) * (1 + w)``, ``eps = rms_norm_eps``:

- ``X_0 = E[token]`` copied to ``n = mhc_expansion_rate`` streams, ``[S,
  n, d]``. Every sublayer ``F`` (a layer is its mixer, then its FFN):
  ``x~ = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)``; ``[pre | post | res]
  = x~ P``; ``Hpre = sigmoid(a_0 pre + b_pre)``, ``Hpost = 2 sigmoid(a_1
  post + b_post)``, ``Hres = Sinkhorn(exp(a_2 mat(res) + b_res))``,
  ``mhc_sinkhorn_iters`` rounds of rows / (row sums + hc_eps), then
  columns; ``h = Hpre X``; ``y = clip(F(norm(h)), +-hidden_clamp)``; ``X
  <- Hres X + Hpost^T y``. The final norm and the head read the sum of
  the streams.
- The mixer: ``cq = norm(u W_qa)``, ``q = cq W_qb`` ``[80, 192]`` =
  ``[q_nope 128 | q_pe 64]``; ``[c | kpe] = u W_kva``, ``c <- norm(c)``;
  ``q_pe``, ``kpe`` rotated (split halves, base ``rope_theta``, at the
  token's own position; one ``kpe`` for all heads). KV group ``g`` of
  16: ``k_g = [c W_uk,g; kpe]``, ``v_g = c W_uv,g``, MADE FOR EVERY
  TOKEN. Heads 0-63 are signal heads, 64-79 noise heads; signal head
  ``j`` and noise head ``64 + j // 4`` read group ``j // 4``. ``A_h =
  softmax_t(q_h . k_g(h),t * 192^-0.5) v_g(h),t`` under a ``[queries,
  S]`` mask: a full layer ``t <= i``, a window layer ``i - W < t <= i``.
  ``lambda = sigmoid(u W_lambda)`` ``[64]``; ``o_j = A_j - lambda_j
  A_{64 + j // 4}``; ``y = (o * sigmoid(u W_g)) W_o``. Published layer
  ``l`` is full where ``l % sliding_window_period ==
  sliding_window_period - 1``. The scores are made a block of queries
  and a group's five heads at a time, so that a long sequence fits;
  every query sees all its keys at once.
- FFN: ``W_down (PolyNorm(u W_gate) * (u W_up))``, ``PolyNorm(z) = s
  (w_0 N(z^3) + w_1 N(z^2) + w_2 N(z)) + clip(b, -c, c)``, ``N(a) = a /
  sqrt(mean(a^2) + eps)`` over the FFN's width. Dense, or ``p =
  sigmoid(u W_r)`` over all experts, the ``experts_top_k`` largest,
  gates ``p_i / sum(chosen p) * route_scale`` on the expert's OUTPUT,
  plus the shared expert ungated; the experts as a loop.

Not here, as not in the program: the multi-token-prediction module.

The share: where the tree holds ``held`` of the model's experts, a pair
whose expert is not held adds nothing, here as in the program; the
vocabulary is whatever rows the tree's embedding and head hold.

``forward_with_record`` takes optional ``routes`` (``[expert layers, S,
top_k]``): the system's discrete choices in place of the reference's
own, so that a comparison of logits is on the same routes, and the
choices themselves are compared apart (``slack``, as
``reference_glm5_next`` has it).

``lower`` names one thing to compute otherwise, for the reading that a
limit has to fail: ``"weights_e4m3"``, ``"router_bf16"``, ``"no_noise"``
(lambda = 0: the noise heads do not count), ``"lambda_const"`` (lambda =
1/2 for every token and head: its input does not count),
``"window_as_full"`` (a window layer sees every key up to the query),
``"polynorm_as_silu"`` (``silu`` in PolyNorm's place), ``"static_h"``
(``a = 0``: the residual mixing does not read the streams).

Takes the program's parameter tree (``tok_emb``, ``blocks``: one tree
per SUBLAYER, ``final_norm``, ``lm_head``) and nothing else of the
program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _weight(a, lower):
    """A matmul weight as float32, through e4m3 where asked."""
    if lower == "weights_e4m3":
        a = jnp.asarray(a, jnp.float32).astype(jnp.float8_e4m3fn)
    return _f32(a)


def _to_bf16(a):
    """float32 values rounded to bfloat16's 8 bits (not a pair of
    ``astype``s, which XLA may drop)."""
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + scale)


# ------------------------------------------------------- the residual path
def mix(p, x, *, mhc_sinkhorn_iters, hc_eps, lower=None, **_):
    """x [S, n, d] -> (h [S, d], Hres [S, n, n], Hpost [S, n])."""
    with jax.default_matmul_precision("highest"):
        s, n, _ = x.shape
        flat = x.reshape(s, -1)
        unit = flat / jnp.sqrt(
            jnp.mean(flat * flat, axis=-1, keepdims=True) + hc_eps
        )
        raw = unit @ _f32(p["proj"])
        a = jnp.zeros((3,)) if lower == "static_h" else _f32(p["scale"])
        pre = jax.nn.sigmoid(a[0] * raw[:, :n] + _f32(p["b_pre"]))
        post = 2.0 * jax.nn.sigmoid(a[1] * raw[:, n: 2 * n] + _f32(p["b_post"]))
        res = jnp.exp(a[2] * raw[:, 2 * n:].reshape(s, n, n) + _f32(p["b_res"]))
        for _ in range(mhc_sinkhorn_iters):
            res = res / (res.sum(-1, keepdims=True) + hc_eps)
            res = res / (res.sum(-2, keepdims=True) + hc_eps)
        return jnp.einsum("sn,snd->sd", pre, x), res, post


def spread(x, y, res, post, *, hidden_clamp, lower=None, **_):
    """``Hres X + Hpost^T clip(y)``: [S, n, d]."""
    y = jnp.clip(y, -hidden_clamp, hidden_clamp)
    return jnp.einsum("sij,sjd->sid", res, x) + post[:, :, None] * y[:, None, :]


# --------------------------------------------------------------- the mixer
def _rope(x, positions, theta):
    """x [S, .., D] rotated at ``positions`` [S], split halves."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq  # [S, half]
    angles = angles.reshape(x.shape[0], *([1] * (x.ndim - 2)), half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def cells(p, x, first, *, kv_lora_rank, rope_theta, rms_norm_eps, lower=None,
          **sizes):
    """The cells ``[c; kpe]`` [S, rank + rope] of the tokens of x [S, n,
    d] at positions ``first ..``: what a cache would hold of them."""
    with jax.default_matmul_precision("highest"):
        h, _, _ = mix(p["hc"], x, lower=lower, **sizes)
        u = _rms_norm(h, _f32(p["attn_norm"]), rms_norm_eps)
        ckv = u @ _weight(p["wkv_a"], lower)
        c = _rms_norm(ckv[:, :kv_lora_rank], _f32(p["kv_norm"]), rms_norm_eps)
        positions = first + jnp.arange(x.shape[0])
        kpe = _rope(ckv[:, kv_lora_rank:], positions, rope_theta)
        return jnp.concatenate([c, kpe], axis=-1)


def gdla_sublayer(p, x, keys, first, full: bool, *, num_attention_heads,
                  num_noise_heads, num_key_value_heads, head_dim,
                  qk_rope_head_dim, kv_lora_rank, sliding_window, rope_theta,
                  rms_norm_eps, query_block=None, lower=None, **sizes):
    """x [Q, n, d], the tokens at positions ``first ..`` of a sequence
    whose every cell is ``keys`` [S, rank + rope] -> the streams after
    the mixer."""
    with jax.default_matmul_precision("highest"):
        n_q, s = x.shape[0], keys.shape[0]
        heads, groups = num_attention_heads, num_key_value_heads
        signal = heads - num_noise_heads
        per = signal // groups
        nope = head_dim - qk_rope_head_dim
        h_in, res, post = mix(p["hc"], x, lower=lower, **sizes)
        u = _rms_norm(h_in, _f32(p["attn_norm"]), rms_norm_eps)
        cq = _rms_norm(u @ _weight(p["wq_a"], lower), _f32(p["q_norm"]),
                       rms_norm_eps)
        q = (cq @ _weight(p["wq_b"], lower)).reshape(n_q, heads, head_dim)
        t = first + jnp.arange(n_q)
        q = jnp.concatenate(
            [q[..., :nope], _rope(q[..., nope:], t, rope_theta)], axis=-1
        )
        if lower == "no_noise":
            lam = jnp.zeros((n_q, signal))
        elif lower == "lambda_const":
            lam = jnp.full((n_q, signal), 0.5)
        else:
            lam = jax.nn.sigmoid(u @ _weight(p["w_lambda"], lower))
        c, kpe = keys[:, :kv_lora_rank], keys[:, kv_lora_rank:]
        pos = jnp.arange(s)
        block = query_block or n_q
        n_blocks = -(-n_q // block)
        pad = n_blocks * block - n_q
        # Group g's heads: signal 4g .. 4g + 3, then noise 64 + g.
        of_group = jnp.concatenate([
            jnp.arange(signal).reshape(groups, per),
            signal + jnp.arange(groups)[:, None],
        ], axis=1)  # [G, per + 1]
        q_g = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))[:, of_group]
        q_g = q_g.reshape(n_blocks, block, groups, per + 1, head_dim)
        t_b = jnp.pad(t, (0, pad)).reshape(n_blocks, block)
        w_uk, w_uv = _weight(p["w_uk"], lower), _weight(p["w_uv"], lower)

        def one_block(args):
            q_b, t_q = args  # [Q, G, r, D], [Q]
            seen = pos[None, :] <= t_q[:, None]
            if not full and lower != "window_as_full":
                seen &= pos[None, :] > t_q[:, None] - sliding_window

            def one_group(args):
                q_r, uk, uv = args  # [Q, r, D], [rank, nope], [rank, v]
                k = jnp.concatenate([c @ uk, kpe], axis=-1)  # [S, D]
                v = c @ uv
                scores = jnp.einsum("qrd,kd->rqk", q_r, k) * head_dim**-0.5
                probs = jax.nn.softmax(
                    jnp.where(seen[None], scores, -jnp.inf), axis=-1
                )
                return jnp.einsum("rqk,kd->qrd", probs, v)

            return jax.lax.map(
                one_group, (jnp.moveaxis(q_b, 1, 0), w_uk, w_uv)
            )  # [G, Q, r, v]

        attn = jax.lax.map(one_block, (q_g, t_b))  # [NB, G, Q, r, v]
        attn = jnp.moveaxis(attn, 1, 2).reshape(
            n_blocks * block, groups, per + 1, -1
        )[:n_q]
        o = attn[:, :, :per] - lam.reshape(n_q, groups, per, 1) * attn[:, :, per:]
        gate = jax.nn.sigmoid(u @ _weight(p["wg"], lower))
        y = (o.reshape(n_q, -1) * gate) @ _weight(p["wo"], lower)
        return spread(x, y, res, post, lower=lower, **sizes)


# --------------------------------------------------------------- the FFNs
def _poly_norm(z, w, b, scale, clamp, eps):
    def unit(a):
        return a / jnp.sqrt(jnp.mean(a * a, axis=-1, keepdims=True) + eps)

    return scale * (
        w[0] * unit(z**3) + w[1] * unit(z**2) + w[2] * unit(z)
    ) + jnp.clip(b[0], -clamp, clamp)


def _gated(h, w_gate, w_up, w_down, poly_w, poly_b, sizes, lower):
    z, up = h @ _weight(w_gate, lower), h @ _weight(w_up, lower)
    if lower == "polynorm_as_silu":
        gate = jax.nn.silu(z)
    else:
        gate = _poly_norm(
            z, _f32(poly_w), _f32(poly_b), sizes["polynorm_output_scale"],
            sizes["polynorm_bias_clamp"], sizes["rms_norm_eps"],
        )
    return gate * up @ _weight(w_down, lower)


def dense_sublayer(p, x, *, lower=None, **sizes):
    with jax.default_matmul_precision("highest"):
        h_in, res, post = mix(p["hc"], x, lower=lower, **sizes)
        h = _rms_norm(h_in, _f32(p["norm"]), sizes["rms_norm_eps"])
        y = _gated(h, p["w_gate"], p["w_up"], p["w_down"], p["poly_w"],
                   p["poly_b"], sizes, lower)
        return spread(x, y, res, post, lower=lower, **sizes)


def expert_sublayer(p, x, routes=None, *, experts_top_k, route_scale,
                    first_expert_held=0, lower=None, **sizes):
    """x [S, n, d] -> (the streams after the FFN, the router's record of
    this layer: ``routes`` [S, k] the reference's own, ``margin`` and
    ``slack`` [S] as ``1 - p_low / p_cut``)."""
    with jax.default_matmul_precision("highest"):
        h_in, res, post = mix(p["hc"], x, lower=lower, **sizes)
        k = experts_top_k
        h = _rms_norm(h_in, _f32(p["norm"]), sizes["rms_norm_eps"])
        if lower == "router_bf16":
            logits = _to_bf16(_to_bf16(h) @ _to_bf16(_f32(p["router"])))
        else:
            logits = h @ _f32(p["router"])  # [S, E]
        h = h
        probs = jax.nn.sigmoid(logits)
        top, own = jax.lax.top_k(probs, k + 1)
        chosen = own[:, :k] if routes is None else routes
        applied = jnp.take_along_axis(probs, chosen, axis=-1)
        gates = applied / applied.sum(-1, keepdims=True) * route_scale

        def one_expert(y, expert):
            e, w_gate, w_up, w_down, poly_w, poly_b = expert
            weight = jnp.where(chosen == first_expert_held + e, gates, 0.0)
            return y + weight.sum(-1)[:, None] * _gated(
                h, w_gate, w_up, w_down, poly_w, poly_b, sizes, lower
            ), None

        held = p["w_up"].shape[0]
        y, _ = jax.lax.scan(
            one_expert, jnp.zeros_like(h),
            (jnp.arange(held), p["w_gate"], p["w_up"], p["w_down"],
             p["poly_w"], p["poly_b"]),
        )
        y = y + _gated(h, p["shared_gate"], p["shared_up"], p["shared_down"],
                       p["shared_poly_w"], p["shared_poly_b"], sizes, lower)
        cut = top[:, k - 1]
        lowest = applied.min(-1)
        record = {
            "routes": own[:, :k],
            "margin": 1.0 - top[:, k] / cut,
            "slack": jnp.maximum(1.0 - lowest / cut, 0.0),
        }
        return spread(x, y, res, post, lower=lower, **sizes), record


def embed(params, tokens, *, mhc_expansion_rate, lower=None, **_):
    x = _weight(params["tok_emb"][tokens], lower)
    n = mhc_expansion_rate
    return jnp.broadcast_to(x[:, None, :], (x.shape[0], n, x.shape[1]))


def head(params, x, *, rms_norm_eps, lower=None, **_):
    """Final norm and the head on the rows given: x [R, n, d] -> logits
    [R, V]."""
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x.sum(1), _f32(params["final_norm"]), rms_norm_eps)
        return x @ _weight(params["lm_head"], lower)


def layer_kinds(*, num_hidden_layers, n_dense_first_layers,
                sliding_window_period, first_layer=0, **_):
    """(full?, dense?) of each layer kept: published layer ``l`` attends
    the whole context where ``l % period == period - 1``; the first
    ``n_dense_first_layers`` kept have a dense FFN."""
    period = sliding_window_period
    return [
        ((first_layer + i) % period == period - 1, i < n_dense_first_layers)
        for i in range(num_hidden_layers)
    ]


def forward_with_record(params, tokens, *, routes=None, rows=None,
                        token_block=None, block_fn=lambda kind, fn: fn,
                        **sizes):
    """tokens [S] int32 -> (logits [S, V] float32, or of ``rows`` only;
    the record). The record holds, stacked over the layers of their
    kind, ``routes`` [Le, S, k], ``margin`` and ``slack`` [Le, S], and
    every mixer's cells in layer order, ``cells`` [L, S, rank + rope].

    ``block_fn(kind, fn)`` wraps each kind's sublayer function; the chip
    check passes ``jax.jit`` so that the pass runs sublayer by sublayer,
    one compiled program per kind, and fits beside the engine.
    ``token_block`` runs each sublayer over that many tokens at a time: a
    mixer first makes every position's cell, then runs each block's
    queries against all of them. The same numbers as the pass over the
    whole sequence at once."""
    fns = {
        "cells": block_fn(
            "cells", lambda p, x, first: cells(p, x, first, **sizes)
        ),
        True: block_fn("A", lambda p, x, keys, first: gdla_sublayer(
            p, x, keys, first, True, **sizes
        )),
        False: block_fn("R", lambda p, x, keys, first: gdla_sublayer(
            p, x, keys, first, False, **sizes
        )),
        "dense": block_fn("D", lambda p, x: dense_sublayer(p, x, **sizes)),
        "sparse": block_fn(
            "E", lambda p, x, forced: expert_sublayer(p, x, forced, **sizes)
        ),
    }
    blocks = iter(params["blocks"])
    n = tokens.shape[0]
    size = token_block or n
    starts = list(range(0, n, size))
    xs = [embed(params, tokens[a: a + size], **sizes) for a in starts]
    record = {}

    def note(rec):
        for key, value in rec.items():
            record.setdefault(key, []).append(value)

    n_routed = 0
    for full, dense in layer_kinds(**sizes):
        p = next(blocks)
        keys = jnp.concatenate([
            fns["cells"](p, x, jnp.int32(a))
            for a, x in zip(starts, xs, strict=True)
        ])
        for i, a in enumerate(starts):
            xs[i] = fns[full](p, xs[i], keys, jnp.int32(a))
        note({"cells": keys})
        p = next(blocks)
        if dense:
            for i, x in enumerate(xs):
                xs[i] = fns["dense"](p, x)
        else:
            forced = None if routes is None else routes[n_routed]
            recs = []
            for i, a in enumerate(starts):
                xs[i], rec = fns["sparse"](
                    p, xs[i], None if forced is None else forced[a: a + size]
                )
                recs.append(rec)
            note({key: jnp.concatenate([r[key] for r in recs])
                  for key in recs[0]})
            n_routed += 1
    if rows is None:
        x = jnp.concatenate(xs)
    else:
        x = jnp.stack([xs[r // size][r % size] for r in rows])
    logits = head(params, x, **sizes)
    return logits, {k: jnp.stack(v) for k, v in record.items()}


def forward(params, tokens, **kw):
    """tokens [S] int32 -> logits [S, V] float32."""
    return forward_with_record(params, tokens, **kw)[0]


def for_model(model: dict) -> dict:
    """The keyword arguments above, from a configuration file's keys
    (the assumed readings under ``assumed_values``)."""
    keys = (
        "num_hidden_layers", "n_dense_first_layers", "sliding_window_period",
        "sliding_window", "mhc_expansion_rate", "mhc_sinkhorn_iters",
        "rms_norm_eps", "num_attention_heads", "num_noise_heads",
        "num_key_value_heads", "head_dim", "qk_rope_head_dim", "kv_lora_rank",
        "experts_top_k", "route_scale", "polynorm_output_scale",
        "polynorm_bias_clamp", "hidden_clamp",
    )
    return {k: model[k] for k in keys} | {
        "rope_theta": float(model["rope_theta"]),
        "hc_eps": model["assumed_values"]["mhc_eps"],
        "first_layer": model.get("first_layer", 0),
        "first_expert_held": model.get("first_expert_held", 0),
    }
