"""GLM-5.3-Flash's language model, plainly: float32 ``jax.numpy``, no
kernel, no cache, no chunked form of the recurrence, no absorbed
attention, no gather of selected cells, no sort, no grouped matmul,
matmuls at ``highest`` precision. One full pass over one sequence.

Follows ISSUE 59's equations (``config.json`` of zai-org/GLM-5.3-Flash,
``model_type: glm5_next_text``; Kimi Linear, arXiv:2510.26692;
DeepSeek-V3.2-Exp's sparse attention; mHC, arXiv:2512.24880;
DeepSeek-V3's ``noaux_tc`` router). With ``d`` the hidden size, every
``Linear`` without a bias, ``norm(x) = x / sqrt(mean(x^2) + eps) * (1 +
w)``, ``eps = rms_norm_eps``:

- ``X_0 = E[token]`` copied to ``n = hc_mult`` streams, ``[S, n, d]``.
  Every sublayer ``F`` (a layer is its mixer, then its FFN): ``x~ =
  vec(X) / sqrt(mean(vec(X)^2) + hc_eps)``; ``[pre | post | res] = x~
  P``; ``Hpre = sigmoid(a_0 pre + b_pre)``, ``Hpost = 2 sigmoid(a_1 post
  + b_post)``, ``Hres = Sinkhorn(exp(a_2 mat(res) + b_res))``,
  ``hc_sinkhorn_iters`` rounds of rows / (row sums + hc_eps), then
  columns; ``h = Hpre X``; ``X <- Hres X + Hpost^T F(h)``. The final
  norm and the head read the sum of the streams.
- ``linear_attention`` (KDA): ``[q | k | v] = silu(causal depthwise
  conv1d_K(norm(h) W_in))``, no bias; per head ``q <- q / sqrt(|q|^2 +
  1e-6) * dk^-0.5``, ``k <- k / sqrt(|k|^2 + 1e-6)``; ``g = lower *
  sigmoid(exp(A_log) (W_fb (W_fa u) + dt_bias))`` a key channel,
  ``beta = sigmoid(W_b u)`` a head; per head, ``S`` ``[dk, dv]`` zero
  before the first token, **as a scan over time, a token a step**: ``S
  <- diag(exp(g)) S``; ``r = S^T k``; ``d = beta (v - r)``; ``S <- S + k
  d^T``; ``o = S^T q``. Then ``o / sqrt(mean(o^2) + eps) * w *
  sigmoid(W_gb (W_ga u))`` over each head (``w`` plain) and ``W_o``.
- ``deepseek_sparse_attention``: ``cq = norm(u W_qa)``, ``q_j = cq
  W_qb``; ``c = norm(u W_kva)``; ``k_j = c W_uk,j``, ``v_j = c W_uv,j``
  (made for every token: the expanded form); scores ``q_j . k_j *
  qk_head_dim^-0.5`` under a ``[queries, S]`` mask, soft-max, ``W_o``.
  The mask: query ``t`` sees position ``s`` iff ``s <= t`` and ``s``
  lies in ``t``'s own block of ``index_kpool`` positions, or in one of
  the ``index_topk / index_kpool`` blocks of largest ``I_{t,b} = sum_j
  w_{t,j} relu(qI_{t,j} . kbar_b) (Hi Di)^-0.5`` among the blocks that
  lie wholly before ``t``'s own (all of them where there are fewer);
  ``qI = rope(cq W_Iq)``, ``kI = rope(LayerNorm(u W_Ik))`` (the first
  ``index_rotary_dim`` dimensions, consecutive pairs, at the token's own
  position), ``kbar_b`` the mean of block ``b``'s ``kI``, ``w = u W_Iw``.
  The scores are made a block of queries at a time, and where asked a
  few heads at a time, so that a long sequence fits; every query sees
  all its keys at once.
- FFN: dense, or ``p = sigmoid(u W_r)`` over all experts, the ``top_k``
  largest of ``p + router_bias``, gates ``p_i / sum(chosen p) *
  routed_scaling_factor``, plus the shared expert ungated; each
  ``W_down (silu(min(u W_gate, l)) * clip(u W_up, -l, l))``.

Not here, as not in the program: the multi-token-prediction module, the
vision tower.

The share: where the tree holds ``held`` of the model's experts, a pair
whose expert is not held adds nothing, here as in the program; the
vocabulary is whatever rows the tree's embedding and head hold.

``forward_with_record`` takes optional ``routes`` (``[expert layers, S,
top_k]``) and ``selected`` (``[sparse layers, S, index_topk /
index_kpool]`` block ids, -1 for none): the system's discrete choices in
place of the reference's own, so that a comparison of logits is on the
same routes and the same keys, and the choices themselves are compared
apart: for routes as ``reference_qwen3_next`` has it; for blocks,
``select_slack`` [S] is how far below the reference's own cut (its
``index_topk / index_kpool``-th largest candidate score) the lowest
applied block's reference score lies, in units of the standard deviation
of that query's candidate scores (0 where a query has no more candidates
than it may pick), and ``select_same`` [S] the share of the reference's
own blocks that the applied choice holds too.

``lower`` names one thing to compute otherwise, for the reading that a
limit has to fail: ``"weights_e4m3"``, ``"state_bf16"`` (the rule's state
rounded to bfloat16 at every step), ``"router_bf16"``,
``"one_decay_a_head"`` (the mean of ``g`` over the channels),
``"unbounded_gate"`` (``g = -exp(A_log) softplus(.)``: Kimi Linear's own
gate, without ``gate_lower_bound``), ``"attend_all"``, ``"recent_keys"``
(the most recent ``index_topk`` positions' blocks in place of the
chosen), ``"no_pooling"`` (a block's score is its best single key's),
``"no_tail"`` (the query's own block is not attended but for itself),
``"static_h"`` (``a = 0``), ``"no_sinkhorn"`` (a row soft-max),
``"one_stream"`` (``X <- X + F(mean of the streams)`` on every stream),
``"no_clamp"``, ``"no_routed_scaling"``. A lowered selection is applied
in place of the forced one.

Takes the program's parameter tree (``tok_emb``, ``blocks``: one tree
per SUBLAYER, ``final_norm``, ``lm_head``) and nothing else of the
program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

L2_EPS = 1e-6
_SELECTION_LOWERS = ("attend_all", "recent_keys", "no_pooling")


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


def _unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


# ------------------------------------------------------- the residual path
def mix(p, x, *, hc_sinkhorn_iters, hc_eps, lower=None, **_):
    """x [S, n, d] -> (h [S, d], Hres [S, n, n], Hpost [S, n])."""
    with jax.default_matmul_precision("highest"):
        s, n, _ = x.shape
        if lower == "one_stream":
            eye = jnp.broadcast_to(jnp.eye(n), (s, n, n))
            return x.mean(1), eye, jnp.ones((s, n))
        flat = x.reshape(s, -1)
        unit = flat / jnp.sqrt(
            jnp.mean(flat * flat, axis=-1, keepdims=True) + hc_eps
        )
        raw = unit @ _f32(p["proj"])
        a = jnp.zeros((3,)) if lower == "static_h" else _f32(p["scale"])
        pre = jax.nn.sigmoid(a[0] * raw[:, :n] + _f32(p["b_pre"]))
        post = 2.0 * jax.nn.sigmoid(a[1] * raw[:, n: 2 * n] + _f32(p["b_post"]))
        logits = a[2] * raw[:, 2 * n:].reshape(s, n, n) + _f32(p["b_res"])
        if lower == "no_sinkhorn":
            res = jax.nn.softmax(logits, axis=-1)
        else:
            res = jnp.exp(logits)
            for _ in range(hc_sinkhorn_iters):
                res = res / (res.sum(-1, keepdims=True) + hc_eps)
                res = res / (res.sum(-2, keepdims=True) + hc_eps)
        return jnp.einsum("sn,snd->sd", pre, x), res, post


def spread(x, y, res, post):
    return jnp.einsum("sij,sjd->sid", res, x) + post[:, :, None] * y[:, None, :]


# ------------------------------------------------------------- the mixers
def kda_start(*, kda_heads, kda_head_dim, conv_kernel, **_):
    """What a KDA layer carries before the first token: a zero state [H,
    dk, dv] and a zero tail of the convolution's input [K - 1, 3 H dk]."""
    return (jnp.zeros((kda_heads, kda_head_dim, kda_head_dim)),
            jnp.zeros((conv_kernel - 1, 3 * kda_heads * kda_head_dim)))


def kda_sublayer(p, x, carry, *, kda_heads, kda_head_dim, conv_kernel,
                 gate_lower_bound, rms_norm_eps, lower=None, **sizes):
    """x [S, n, d], ``carry`` the state and the convolution's last K - 1
    inputs before x[0] (`kda_start` at the sequence's first token) ->
    (the streams after the mixer, the carry after the last token)."""
    with jax.default_matmul_precision("highest"):
        state0, tail = carry
        h_in, res, post = mix(p["hc"], x, lower=lower, **sizes)
        s = x.shape[0]
        h, dk = kda_heads, kda_head_dim
        u = _rms_norm(h_in, _f32(p["norm"]), rms_norm_eps)
        qkv = u @ _weight(p["in_proj"], lower)
        padded = jnp.concatenate([tail, qkv])
        qkv = jax.nn.silu(sum(
            padded[j: j + s] * _f32(p["conv_w"])[j] for j in range(conv_kernel)
        ))
        q, k, v = (a.reshape(s, h, dk) for a in jnp.split(qkv, 3, axis=-1))
        q, k = _unit(q) * dk**-0.5, _unit(k)
        raw = (
            (u @ _weight(p["f_a"], lower)) @ _weight(p["f_b"], lower)
        ).reshape(s, h, dk) + _f32(p["dt_bias"])
        rate = jnp.exp(_f32(p["A_log"]))[:, None]
        if lower == "unbounded_gate":
            g = -rate * jax.nn.softplus(raw)
        else:
            g = gate_lower_bound * jax.nn.sigmoid(rate * raw)
        if lower == "one_decay_a_head":
            g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
        beta = jax.nn.sigmoid(u @ _weight(p["b_proj"], lower))  # [S, H]
        gate = (u @ _weight(p["g_a"], lower)) @ _weight(p["g_b"], lower)

        def step(state, now):
            q_t, k_t, v_t, beta_t, g_t = now
            state = jnp.exp(g_t)[:, :, None] * state  # [H, dk, dv]
            read = jnp.einsum("hkv,hk->hv", state, k_t)
            delta = beta_t[:, None] * (v_t - read)
            state = state + k_t[:, :, None] * delta[:, None, :]
            if lower == "state_bf16":
                state = _to_bf16(state)
            return state, jnp.einsum("hkv,hk->hv", state, q_t)

        state, o = jax.lax.scan(step, state0, (q, k, v, beta, g))
        var = jnp.mean(o * o, axis=-1, keepdims=True)
        normed = o / jnp.sqrt(var + rms_norm_eps) * _f32(p["gate_norm"])
        gated = normed.reshape(s, h * dk) * jax.nn.sigmoid(gate)
        y = gated @ _weight(p["out_proj"], lower)
        return spread(x, y, res, post), (state, padded[s:])


def _rope_interleaved(x, positions, rotary_dim, theta):
    """x [S, .., D] at positions [S]: consecutive pairs of the first
    ``rotary_dim`` dimensions rotated."""
    half = rotary_dim // 2
    inv_freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq  # [S, half]
    angles = angles.reshape(angles.shape[0], *(1,) * (x.ndim - 2), half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    even, odd = x[..., 0:rotary_dim:2], x[..., 1:rotary_dim:2]
    turned = jnp.stack(
        [even * cos - odd * sin, odd * cos + even * sin], axis=-1
    ).reshape(*x.shape[:-1], rotary_dim)
    return jnp.concatenate([turned, x[..., rotary_dim:]], axis=-1)


def _dsa_inputs(p, x, first, *, index_n_heads, index_head_dim,
                index_rotary_dim, index_rope_theta, rms_norm_eps,
                lower=None, **sizes):
    """What a sparse latent mixer makes of each token of x [S, n, d], the
    tokens at positions ``first ..``: the mix, the query's latent ``cq``,
    the cell ``c`` [S, rank], the indexer's queries [S, Hi, Di], key [S,
    Di] and head weights [S, Hi]."""
    s = x.shape[0]
    h_in, res, post = mix(p["hc"], x, lower=lower, **sizes)
    u = _rms_norm(h_in, _f32(p["attn_norm"]), rms_norm_eps)
    cq = _rms_norm(u @ _weight(p["wq_a"], lower), _f32(p["q_norm"]),
                   rms_norm_eps)
    c = _rms_norm(u @ _weight(p["wkv_a"], lower), _f32(p["kv_norm"]),
                  rms_norm_eps)
    pos = first + jnp.arange(s)
    q_i = _rope_interleaved(
        (cq @ _weight(p["index_q"], lower)).reshape(
            s, index_n_heads, index_head_dim
        ), pos, index_rotary_dim, index_rope_theta,
    )
    raw = u @ _weight(p["index_k"], lower)
    mean = raw.mean(-1, keepdims=True)
    var = jnp.mean(jnp.square(raw - mean), axis=-1, keepdims=True)
    k_i = _rope_interleaved(
        (raw - mean) / jnp.sqrt(var + rms_norm_eps)
        * _f32(p["index_k_norm"]) + _f32(p["index_k_bias"]),
        pos, index_rotary_dim, index_rope_theta,
    )
    return (res, post), cq, c, q_i, k_i, u @ _weight(p["index_w"], lower)


def dsa_keys(p, x, first, **sizes):
    """x [S, n, d], the tokens at positions ``first ..`` -> what later
    queries read of them: their cells [S, rank] and indexer keys [S,
    Di]."""
    with jax.default_matmul_precision("highest"):
        _, _, c, _, k_i, _ = _dsa_inputs(p, x, first, **sizes)
        return c, k_i


def dsa_sublayer(p, x, forced, keys, first, *, num_attention_heads,
                 qk_head_dim, v_head_dim, index_n_heads, index_head_dim,
                 index_topk, index_kpool, query_block=256, head_block=None,
                 lower=None, **sizes):
    """x [Q, n, d], the queries at positions ``first ..`` of a sequence
    whose every position's cell and indexer key ``keys`` holds (``c``
    [S, rank], ``k_i`` [S, Di]: `dsa_keys`) -> (the streams after the
    mixer, the record of these queries: ``selected`` [Q, K] the
    reference's own blocks (-1: none), ``select_slack`` and
    ``select_same`` [Q] (the module docstring)). ``forced`` [Q, K]: the
    blocks to attend in place of the reference's own, or None. The keys
    and values are expanded from the cells ``head_block`` heads at a
    time (all at once where None)."""
    with jax.default_matmul_precision("highest"):
        c, k_i = keys
        (res, post), cq, _, q_i, _, w_i = _dsa_inputs(
            p, x, first, index_n_heads=index_n_heads,
            index_head_dim=index_head_dim, lower=lower, **sizes
        )
        n_queries, s = x.shape[0], c.shape[0]
        heads, hi, di, pool = (num_attention_heads, index_n_heads,
                               index_head_dim, index_kpool)
        top = index_topk // pool
        q = (cq @ _weight(p["wq_b"], lower)).reshape(
            n_queries, heads, qk_head_dim
        )
        per = head_block or heads
        w_uk = _weight(p["w_uk"], lower).reshape(heads // per, per, -1, qk_head_dim)
        w_uv = _weight(p["w_uv"], lower).reshape(heads // per, per, -1, v_head_dim)
        pos = jnp.arange(s)
        n_blocks = -(-s // pool)
        k_pad = jnp.pad(k_i, ((0, n_blocks * pool - s), (0, 0)))
        by_block = k_pad.reshape(n_blocks, pool, di)
        pooled = by_block.mean(1)  # [NB, Di]; the last may be incomplete
        scale = (hi * di) ** -0.5
        block = min(query_block, n_queries)
        n_q = -(-n_queries // block)
        pad_q = n_q * block - n_queries

        def rows(a):
            a = jnp.pad(a, ((0, pad_q),) + ((0, 0),) * (a.ndim - 1))
            return a.reshape(n_q, block, *a.shape[1:])

        forced_rows = rows(
            jnp.full((n_queries, top), -1, jnp.int32) if forced is None
            else forced.astype(jnp.int32)
        )
        key_block = jnp.arange(s) // pool  # of each position

        def one_block(args):
            q_b, qi_b, wi_b, forced_b, first = args
            t = first + jnp.arange(block)  # [Q]
            own = t // pool
            if lower == "no_pooling":
                # A block's score: its best single key's.
                single = jnp.einsum("qhd,npd->qhnp", qi_b, by_block)
                score = jnp.einsum(
                    "qhnp,qh->qnp", jax.nn.relu(single), wi_b
                ).max(-1) * scale
            else:
                dots = jnp.einsum("qhd,nd->qhn", qi_b, pooled)
                score = jnp.einsum(
                    "qhn,qh->qn", jax.nn.relu(dots), wi_b
                ) * scale  # [Q, NB]
            if lower == "recent_keys":
                score = jnp.broadcast_to(
                    jnp.arange(n_blocks, dtype=jnp.float32), score.shape
                )
            candidate = jnp.arange(n_blocks)[None, :] < own[:, None]
            masked = jnp.where(candidate, score, -jnp.inf)
            k_top = min(top, n_blocks)
            best, ids = jax.lax.top_k(masked, k_top)
            ids = jnp.where(jnp.isfinite(best), ids, -1)
            ids = jnp.pad(ids, ((0, 0), (0, top - k_top)), constant_values=-1)
            n_cand = candidate.sum(-1)
            cut = jnp.where(n_cand > top, best[:, k_top - 1], -jnp.inf)
            # The applied choice against the reference's own.
            applied = (
                ids if forced is None or lower in _SELECTION_LOWERS
                else forced_b
            )

            def blocks_of(chosen):
                """[Q, NB] bool: the blocks ``chosen`` [Q, K] names."""
                return jnp.zeros((block, n_blocks + 1), bool).at[
                    jnp.arange(block)[:, None],
                    jnp.where(chosen >= 0, chosen, n_blocks),
                ].set(True)[:, :n_blocks]

            took, own_took = blocks_of(applied), blocks_of(ids)
            if lower == "attend_all":  # its own choice: every candidate
                own_took, cut = candidate, jnp.full_like(cut, -jnp.inf)
            checked = took if forced is None else blocks_of(forced_b)
            cand_mean = jnp.where(candidate, score, 0.0).sum(-1) / jnp.maximum(
                n_cand, 1
            )
            cand_var = jnp.where(
                candidate, jnp.square(score - cand_mean[:, None]), 0.0
            ).sum(-1) / jnp.maximum(n_cand, 1)
            lowest = jnp.where(checked, score, jnp.inf).min(-1)
            slack = jnp.where(
                jnp.isfinite(cut) & jnp.isfinite(lowest),
                jnp.maximum(cut - lowest, 0.0) / jnp.sqrt(cand_var + 1e-12),
                0.0,
            )
            n_own = own_took.sum(-1)
            same = jnp.where(
                n_own > 0, (own_took & checked).sum(-1) / jnp.maximum(n_own, 1),
                1.0,
            )
            if lower == "attend_all":
                seen = pos[None, :] <= t[:, None]
            else:
                in_own = key_block[None, :] == own[:, None]
                if lower == "no_tail":
                    in_own = pos[None, :] == t[:, None]
                seen = (
                    jnp.take_along_axis(
                        took, jnp.broadcast_to(key_block, (block, s)), axis=1
                    ) | in_own
                ) & (pos[None, :] <= t[:, None])

            def some_heads(args):
                q_g, uk, uv = args  # [Q, per, d], [per, rank, d] twice
                k = jnp.einsum("sr,hrd->shd", c, uk)
                v = jnp.einsum("sr,hrd->shd", c, uv)
                scores = jnp.einsum("qhd,khd->hqk", q_g, k) * qk_head_dim**-0.5
                probs = jax.nn.softmax(
                    jnp.where(seen[None], scores, -jnp.inf), axis=-1
                )
                return jnp.einsum("hqk,khd->qhd", probs, v)

            attn = jax.lax.map(some_heads, (
                jnp.moveaxis(q_b.reshape(block, -1, per, qk_head_dim), 1, 0),
                w_uk, w_uv,
            ))  # [groups, Q, per, dv]
            attn = jnp.moveaxis(attn, 0, 1).reshape(block, heads, v_head_dim)
            return attn, ids, slack, same

        attn, ids, slack, same = jax.lax.map(one_block, (
            rows(q), rows(q_i), rows(w_i), forced_rows,
            first + jnp.arange(n_q) * block,
        ))
        flat = lambda a: a.reshape(n_q * block, *a.shape[2:])[:n_queries]  # noqa: E731
        y = flat(attn).reshape(n_queries, heads * v_head_dim) @ _weight(
            p["wo"], lower
        )
        record = {
            "selected": flat(ids), "select_slack": flat(slack),
            "select_same": flat(same),
        }
        return spread(x, y, res, post), record


# --------------------------------------------------------------- the FFNs
def _gated(h, w_gate, w_up, w_down, limit, lower):
    gate, up = h @ _weight(w_gate, lower), h @ _weight(w_up, lower)
    if limit is not None and lower != "no_clamp":
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    return (jax.nn.silu(gate) * up) @ _weight(w_down, lower)


def dense_sublayer(p, x, *, swiglu_limit, rms_norm_eps, lower=None, **sizes):
    with jax.default_matmul_precision("highest"):
        h_in, res, post = mix(p["hc"], x, lower=lower, **sizes)
        h = _rms_norm(h_in, _f32(p["norm"]), rms_norm_eps)
        y = _gated(h, p["w_gate"], p["w_up"], p["w_down"], swiglu_limit, lower)
        return spread(x, y, res, post)


def expert_sublayer(p, x, routes=None, *, num_experts_per_tok,
                    routed_scaling_factor, swiglu_limit, rms_norm_eps,
                    first_expert_held=0, lower=None, **sizes):
    """x [S, n, d] -> (the streams after the FFN, the router's record of
    this layer: ``routes`` [S, k] the reference's own, ``margin`` and
    ``slack`` [S] as ``1 - p_low / p_cut`` of the biased scores)."""
    with jax.default_matmul_precision("highest"):
        h_in, res, post = mix(p["hc"], x, lower=lower, **sizes)
        k = num_experts_per_tok
        h = _rms_norm(h_in, _f32(p["norm"]), rms_norm_eps)
        if lower == "router_bf16":
            logits = _to_bf16(_to_bf16(h) @ _to_bf16(_f32(p["router"])))
        else:
            logits = h @ _f32(p["router"])  # [S, E]
        probs = jax.nn.sigmoid(logits)
        biased = probs + _f32(p["router_bias"])
        top, own = jax.lax.top_k(biased, k + 1)
        chosen = own[:, :k] if routes is None else routes
        applied = jnp.take_along_axis(probs, chosen, axis=-1)
        gates = applied / applied.sum(-1, keepdims=True)
        if lower != "no_routed_scaling":
            gates = gates * routed_scaling_factor

        def one_expert(y, expert):
            e, w_gate, w_up, w_down = expert
            weight = jnp.where(chosen == first_expert_held + e, gates, 0.0)
            return y + weight.sum(-1)[:, None] * _gated(
                h, w_gate, w_up, w_down, swiglu_limit, lower
            ), None

        held = p["w_up"].shape[0]
        y, _ = jax.lax.scan(
            one_expert, jnp.zeros_like(h),
            (jnp.arange(held), p["w_gate"], p["w_up"], p["w_down"]),
        )
        y = y + _gated(h, p["shared_gate"], p["shared_up"], p["shared_down"],
                       swiglu_limit, lower)
        cut = top[:, k - 1]
        lowest = jnp.take_along_axis(biased, chosen, axis=-1).min(-1)
        record = {
            "routes": own[:, :k],
            "margin": 1.0 - top[:, k] / cut,
            "slack": jnp.maximum(1.0 - lowest / cut, 0.0),
        }
        return spread(x, y, res, post), record


def embed(params, tokens, *, hc_mult, lower=None, **_):
    x = _weight(params["tok_emb"][tokens], lower)
    return jnp.broadcast_to(x[:, None, :], (x.shape[0], hc_mult, x.shape[1]))


def head(params, x, *, rms_norm_eps, lower=None, **_):
    """Final norm and the head on the rows given: x [R, n, d] -> logits
    [R, V]."""
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x.sum(1), _f32(params["final_norm"]), rms_norm_eps)
        return x @ _weight(params["lm_head"], lower)


def forward_with_record(params, tokens, *, layer_types, mlp_layer_types,
                        routes=None, selected=None, rows=None,
                        token_block=None, block_fn=lambda kind, fn: fn,
                        **sizes):
    """tokens [S] int32 -> (logits [S, V] float32, or of ``rows`` only;
    the record). The record holds, stacked over the layers of their kind,
    ``routes`` [Le, S, k], ``margin`` and ``slack`` [Le, S], ``states``
    [Lk, H, dk, dv] (each KDA layer's state after the last token) and
    the sparse layers' ``selected`` [Ls, S, K], ``select_slack`` and
    ``select_same`` [Ls, S], ``cells`` [Ls, S, rank] and ``pooled`` [Ls,
    S // pool, Di].

    ``block_fn(kind, fn)`` wraps each kind's sublayer function; the chip
    check passes ``jax.jit`` so that the pass runs sublayer by sublayer,
    one compiled program per kind, and fits beside the engine.
    ``token_block`` runs each sublayer over that many tokens at a time
    (the streams of a 33k-token sequence are 2 GB, a KDA sublayer's
    operands over all of it 3 GB more): the recurrence carries its state
    and the convolution's tail from one block to the next, and a sparse
    layer first makes every position's cell and indexer key, then runs
    each block's queries against all of them. The same numbers as the
    pass over the whole sequence at once."""
    fns = {
        "linear_attention": block_fn(
            "K", lambda p, x, carry: kda_sublayer(p, x, carry, **sizes)
        ),
        "keys": block_fn(
            "keys", lambda p, x, first: dsa_keys(p, x, first, **sizes)
        ),
        "deepseek_sparse_attention": block_fn(
            "L", lambda p, x, forced, keys, first: dsa_sublayer(
                p, x, forced, keys, first, **sizes
            )
        ),
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

    def part(forced, a):
        return None if forced is None else forced[a: a + size]

    def note_joined(recs):
        note({key: jnp.concatenate([r[key] for r in recs])
              for key in recs[0]})

    pool = sizes["index_kpool"]
    n_sparse = n_routed = 0
    for kind, ffn in zip(layer_types, mlp_layer_types, strict=True):
        p = next(blocks)
        if kind == "linear_attention":
            carry = kda_start(**sizes)
            for i, x in enumerate(xs):
                xs[i], carry = fns[kind](p, x, carry)
            note({"states": carry[0]})
        else:
            forced = None if selected is None else selected[n_sparse]
            c, k_i = (jnp.concatenate(a) for a in zip(*(
                fns["keys"](p, x, jnp.int32(a))
                for a, x in zip(starts, xs, strict=True)
            ), strict=True))
            recs = []
            for i, a in enumerate(starts):
                xs[i], rec = fns[kind](
                    p, xs[i], part(forced, a), (c, k_i), jnp.int32(a)
                )
                recs.append(rec)
            note_joined(recs)
            note({"cells": c, "pooled": k_i[: n // pool * pool].reshape(
                n // pool, pool, -1
            ).mean(1)})
            n_sparse += 1
        p = next(blocks)
        if ffn == "dense":
            for i, x in enumerate(xs):
                xs[i] = fns[ffn](p, x)
        else:
            forced = None if routes is None else routes[n_routed]
            recs = []
            for i, a in enumerate(starts):
                xs[i], rec = fns[ffn](p, xs[i], part(forced, a))
                recs.append(rec)
            note_joined(recs)
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
        "layer_types", "mlp_layer_types", "hc_mult", "hc_sinkhorn_iters",
        "hc_eps", "rms_norm_eps", "num_attention_heads", "qk_head_dim",
        "v_head_dim", "index_n_heads", "index_head_dim", "index_topk",
        "index_kpool", "num_experts_per_tok", "routed_scaling_factor",
        "swiglu_limit",
    )
    linear = model["linear_attn_config"]
    assumed = model["assumed_values"]
    return {k: model[k] for k in keys} | {
        "kda_heads": linear["num_heads"],
        "kda_head_dim": linear["head_dim"],
        "conv_kernel": linear["short_conv_kernel_size"],
        "gate_lower_bound": float(linear["gate_lower_bound"]),
        "index_rotary_dim": assumed["index_rotary_dim"],
        "index_rope_theta": float(assumed["index_rope_theta"]),
        "first_expert_held": model.get("first_expert_held", 0),
    }
