"""Qwen3-Next's forward pass, plainly: float32 ``jax.numpy``, no kernel,
no cache, no chunked form of the recurrence, no sort, no grouped matmul,
matmuls at ``highest`` precision (on a TPU a float32 matmul otherwise
runs in bf16 passes). One full pass over one sequence.

Follows the published architecture (``config.json`` of
Qwen/Qwen3-Next-80B-A3B-Instruct, ``model_type: qwen3_next``; Gated
DeltaNet: Yang, Kautz & Hatamizadeh 2024, arXiv:2412.06464). With ``d``
the hidden size, every ``Linear`` without a bias, and ``norm(x) = x /
sqrt(mean(x^2) + 1e-6) * (1 + w)``:

- ``x_0 = E[token]``. Layer *l* is two sublayers: ``x <- x +
  mixer_l(norm(x))``, then ``x <- x + (routed(u) + g_s(u) shared(u))``
  with ``u = norm(x)``. Layer *l* is ``full_attention`` where ``(l + 1) %
  full_attention_interval == 0``, else ``linear_attention``.
- ``linear_attention`` mixer (Gated DeltaNet): ``[q | k | v | z] = u
  W_qkvz`` (``Hk dk | Hk dk | Hv dv | Hv dv``), ``[b | a] = u W_ba``
  (``Hv | Hv``); ``[q | k | v] <- silu(causal depthwise conv1d_K([q | k |
  v]))``, no bias; per head ``q <- q / sqrt(|q|^2 + 1e-6) * dk^-0.5``,
  ``k <- k / sqrt(|k|^2 + 1e-6)``; key head *j* serves value heads ``2j``
  and ``2j + 1``; ``beta = sigmoid(b)``, ``alpha = exp(-exp(A_log) *
  softplus(a + dt_bias))``; per value head, with ``S`` ``[dk, dv]`` zero
  before the first token, **as a scan over time, a token a step**:
  ``S <- alpha S``; ``r = S^T k``; ``d = beta (v - r)``; ``S <- S + k
  d^T``; ``o = S^T q``. Then ``o / sqrt(mean(o^2) + 1e-6) * w * silu(z)``
  over each value head's ``dv`` (``w`` plain: NOT ``1 + w``), and
  ``W_out``.
- ``full_attention`` mixer: ``[q | g] = u W_q`` head by head (``H x (Dh +
  Dh)``), ``k``, ``v`` grouped-query; ``q <- norm(q)``, ``k <- norm(k)``
  over each head's ``Dh``; a rotary embedding (theta ``rope_theta``,
  split halves) on the first ``partial_rotary_factor * Dh`` dimensions
  of each head of ``q`` and ``k``, the rest passing through; causal
  soft-max of ``q.k * Dh^-0.5``; ``(attn * sigmoid(g)) W_o``. The scores
  are made a block of queries at a time so that a long sequence fits;
  every query sees all its keys at once (no running soft-max).
- ``routed(u)``: ``p = softmax(u W_r)`` over all experts; the ``top_k``
  largest; gates ``p_i / sum(chosen p)`` (which is the soft-max over the
  chosen logits); expert *e* is ``W_down,e (silu(u W_gate,e) * (u
  W_up,e))``. ``shared(u)`` has the same gated form, sees every token
  and is multiplied by ``g_s(u) = sigmoid(u w_s)``, a scalar a token.
  Each held expert is applied, in a plain loop over the experts (a
  ``lax.scan``, so that 256 of them compile as one), to every row and
  kept for the rows that chose it.
- ``logits = norm(x_L) W_head``: the head is its own matrix.

Not here, as not in the program: the multi-token-prediction module.

The share: where the tree holds ``held`` of the model's experts
(``w_up [held, d, f]``, the experts ``first .. first + held - 1``), a
pair whose expert is not held adds nothing, here as in the program (its
gate still takes its part of the soft-max); the vocabulary is whatever
rows the tree's embedding and head hold.

Departures, noted: ``W_qkvz`` is read as four plain blocks and ``W_ba``
as two (the checkpoint interleaves them by key head: with random
weights a permutation of columns).

``forward_with_record`` takes optional ``routes`` (``[layers, S,
top_k]``): the experts each token is sent to, in place of the
reference's own choice. Routing is discrete, and a flipped tenth expert
carries a gate of a few hundredths, so a comparison of logits forces the
system's routes on the reference and compares the routes themselves
apart: ``margin`` [S] is ``1 - p(k + 1) / p(k)`` of the sorted router
probabilities, ``slack`` [S] how far below the reference's own cut the
lowest *applied* route lies, ``max(1 - min_j p(applied_j) / p(k), 0)``.

``lower`` names one thing to compute in the precision below the one
the configuration states, for the reading that a limit has to fail:
``"state_bf16"`` (the delta rule's state rounded to bfloat16 at every
step), ``"router_bf16"`` (router input, weights and logits in
bfloat16), ``"weights_e4m3"`` (every matmul weight, the embedding among
them, rounded to float8 e4m3).

Takes the program's parameter tree (``tok_emb``, ``blocks``: one tree
per SUBLAYER, ``final_norm``, ``lm_head``) and nothing else of the
program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-6


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _weight(a, lower):
    """A matmul weight as float32, through e4m3 where asked."""
    if lower == "weights_e4m3":
        a = jnp.asarray(a, jnp.float32).astype(jnp.float8_e4m3fn)
    return _f32(a)


def _to_bf16(a):
    """float32 values rounded to bfloat16's 8 bits. Not a pair of
    ``astype``s: XLA may drop such a round trip (it allows itself excess
    precision), and the reading would then be of float32."""
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _rms_norm(x, scale):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + EPS) * (1.0 + scale)


def _unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + EPS)


def layer_kinds(num_hidden_layers: int, full_attention_interval: int):
    """Each layer's mixer: ``"full"`` or ``"linear"``."""
    return [
        "full" if (layer + 1) % full_attention_interval == 0 else "linear"
        for layer in range(num_hidden_layers)
    ]


def linear_attention_sublayer(p, x, *, linear_num_key_heads,
                              linear_key_head_dim, linear_num_value_heads,
                              linear_value_head_dim, linear_conv_kernel_dim,
                              lower=None, **_):
    """x [S, d] -> (x + mixer(norm(x)), the state after the last token
    [Hv, dk, dv])."""
    with jax.default_matmul_precision("highest"):
        s = x.shape[0]
        hk, dk = linear_num_key_heads, linear_key_head_dim
        hv, dv = linear_num_value_heads, linear_value_head_dim
        u = _rms_norm(x, _f32(p["norm"]))
        qkvz = u @ _weight(p["in_proj"], lower)
        ba = u @ _weight(p["ba_proj"], lower)
        conv_dim = 2 * hk * dk + hv * dv
        qkv, z = qkvz[:, :conv_dim], qkvz[:, conv_dim:]
        # Causal depthwise convolution: tap K - 1 is the current token.
        taps = linear_conv_kernel_dim
        padded = jnp.concatenate([jnp.zeros((taps - 1, conv_dim)), qkv])
        qkv = jax.nn.silu(sum(
            padded[j: j + s] * _f32(p["conv_w"])[j] for j in range(taps)
        ))
        q = _unit(qkv[:, : hk * dk].reshape(s, hk, dk)) * dk**-0.5
        k = _unit(qkv[:, hk * dk: 2 * hk * dk].reshape(s, hk, dk))
        v = qkv[:, 2 * hk * dk:].reshape(s, hv, dv)
        # Value head h reads key head h // (Hv / Hk).
        q = jnp.repeat(q, hv // hk, axis=1)  # [S, Hv, dk]
        k = jnp.repeat(k, hv // hk, axis=1)
        beta = jax.nn.sigmoid(ba[:, :hv])  # [S, Hv]
        alpha = jnp.exp(
            -jnp.exp(_f32(p["A_log"]))
            * jax.nn.softplus(ba[:, hv:] + _f32(p["dt_bias"]))
        )

        def step(state, now):
            q_t, k_t, v_t, beta_t, alpha_t = now
            state = alpha_t[:, None, None] * state  # [Hv, dk, dv]
            read = jnp.einsum("hkv,hk->hv", state, k_t)
            delta = beta_t[:, None] * (v_t - read)
            state = state + k_t[:, :, None] * delta[:, None, :]
            if lower == "state_bf16":
                state = _to_bf16(state)
            return state, jnp.einsum("hkv,hk->hv", state, q_t)

        state, o = jax.lax.scan(
            step, jnp.zeros((hv, dk, dv)), (q, k, v, beta, alpha)
        )
        var = jnp.mean(o * o, axis=-1, keepdims=True)
        normed = o / jnp.sqrt(var + EPS) * _f32(p["gate_norm"])
        gated = normed.reshape(s, hv * dv) * jax.nn.silu(z)
        return x + gated @ _weight(p["out_proj"], lower), state


def _gated(h, w_gate, w_up, w_down, lower):
    return (
        jax.nn.silu(h @ _weight(w_gate, lower)) * (h @ _weight(w_up, lower))
    ) @ _weight(w_down, lower)


def shared_expert(p, h, lower=None):
    """``sigmoid(h w_s) * shared(h)``: h [S, d] (normed) -> [S, d]."""
    with jax.default_matmul_precision("highest"):
        scalar = jax.nn.sigmoid(h @ _weight(p["shared_expert_gate"], lower))
        return scalar * _gated(
            h, p["shared_gate"], p["shared_up"], p["shared_down"], lower
        )


def expert_sublayer(p, x, routes=None, *, num_experts_per_tok,
                    first_expert_held=0, lower=None, **_):
    """x [S, d] -> (x + (routed + gated shared)(norm(x)), the router's
    record of this layer): ``routes`` [S, k], the reference's own choice
    whether or not another was forced; ``margin`` and ``slack`` [S] (the
    module docstring)."""
    with jax.default_matmul_precision("highest"):
        k = num_experts_per_tok
        h = _rms_norm(x, _f32(p["norm"]))
        if lower == "router_bf16":
            logits = _to_bf16(_to_bf16(h) @ _to_bf16(_f32(p["router"])))
        else:
            logits = h @ _f32(p["router"])  # [S, E]
        top, own = jax.lax.top_k(logits, k + 1)
        chosen = own[:, :k] if routes is None else routes
        applied = jnp.take_along_axis(logits, chosen, axis=-1)
        gates = jax.nn.softmax(applied, axis=-1)  # p_i / sum of the chosen p

        def one_expert(y, expert):
            # The gate of held expert e for each row: 0 where the row
            # did not choose it.
            e, w_gate, w_up, w_down = expert
            weight = jnp.where(chosen == first_expert_held + e, gates, 0.0)
            return y + weight.sum(-1)[:, None] * _gated(
                h, w_gate, w_up, w_down, lower
            ), None

        held = p["w_up"].shape[0]
        y, _ = jax.lax.scan(
            one_expert, jnp.zeros_like(h),
            (jnp.arange(held), p["w_gate"], p["w_up"], p["w_down"]),
        )
        y = y + shared_expert(p, h, lower)
        cut = top[:, k - 1]
        record = {
            "routes": own[:, :k],
            "margin": 1.0 - jnp.exp(top[:, k] - cut),
            "slack": jnp.maximum(1.0 - jnp.exp(applied.min(-1) - cut), 0.0),
        }
        return x + y, record


def _rotate(x, rotary_dim, rope_theta):
    """x [S, H, Dh]: the first ``rotary_dim`` dimensions of each head
    rotated by position (split halves within them)."""
    half = rotary_dim // 2
    inv_freq = 1.0 / rope_theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1
    )


def full_attention_sublayer(p, x, *, num_attention_heads,
                            num_key_value_heads, head_dim,
                            partial_rotary_factor, rope_theta,
                            query_block=512, lower=None, **_):
    """x [S, d] -> x + gated attention(norm(x))."""
    with jax.default_matmul_precision("highest"):
        s = x.shape[0]
        hq, hkv = num_attention_heads, num_key_value_heads
        h = _rms_norm(x, _f32(p["attn_norm"]))
        qg = (h @ _weight(p["wq"], lower)).reshape(s, hq, 2 * head_dim)
        q, gate = qg[..., :head_dim], qg[..., head_dim:]
        k = (h @ _weight(p["wk"], lower)).reshape(s, hkv, head_dim)
        v = (h @ _weight(p["wv"], lower)).reshape(s, hkv, head_dim)
        rotary_dim = int(head_dim * partial_rotary_factor)
        q = _rotate(_rms_norm(q, _f32(p["q_norm"])), rotary_dim, rope_theta)
        k = _rotate(_rms_norm(k, _f32(p["k_norm"])), rotary_dim, rope_theta)
        # Each KV head serves hq / hkv consecutive query heads.
        k = jnp.repeat(k, hq // hkv, axis=1)
        v = jnp.repeat(v, hq // hkv, axis=1)
        block = min(query_block, s)
        n_blocks = -(-s // block)
        q = jnp.pad(q, ((0, n_blocks * block - s), (0, 0), (0, 0)))
        key_pos = jnp.arange(s)

        def one_block(args):
            q_b, first = args  # [block, hq, Dh], the block's first position
            scores = jnp.einsum("qhd,khd->hqk", q_b, k) * head_dim**-0.5
            seen = key_pos[None, :] <= first + jnp.arange(block)[:, None]
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
            return jnp.einsum("hqk,khd->qhd", probs, v)

        attn = jax.lax.map(
            one_block,
            (q.reshape(n_blocks, block, hq, head_dim),
             jnp.arange(n_blocks) * block),
        ).reshape(n_blocks * block, hq, head_dim)[:s]
        gated = (attn * jax.nn.sigmoid(gate)).reshape(s, -1)
        return x + gated @ _weight(p["wo"], lower)


def embed(params, tokens, *, lower=None, **_):
    return _weight(params["tok_emb"][tokens], lower)


def head(params, x, *, lower=None, **_):
    """Final norm and the head on the rows given: x [R, d] -> logits
    [R, V]."""
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, _f32(params["final_norm"]))
        return x @ _weight(params["lm_head"], lower)


def forward_with_record(params, tokens, *, num_hidden_layers,
                        full_attention_interval, routes=None, rows=None,
                        block_fn=lambda kind, fn: fn, **sizes):
    """tokens [S] int32 -> (logits [S, V] float32, or of ``rows`` only;
    the record). The record holds, stacked over the layers, ``routes``
    [L, S, k], ``margin`` and ``slack`` [L, S], and ``states`` [Lg, Hv,
    dk, dv]: each linear-attention layer's state after the last token.

    ``block_fn(kind, fn)`` wraps each kind's sublayer function; the chip
    check passes ``jax.jit`` so that the pass runs sublayer by sublayer,
    one compiled program per kind, and fits beside the engine."""
    fns = {
        "linear": block_fn(
            "linear", lambda p, x: linear_attention_sublayer(p, x, **sizes)
        ),
        "full": block_fn(
            "full", lambda p, x: (full_attention_sublayer(p, x, **sizes), None)
        ),
        "E": block_fn(
            "E", lambda p, x, forced: expert_sublayer(p, x, forced, **sizes)
        ),
    }
    blocks = iter(params["blocks"])
    x = embed(params, tokens, **sizes)
    record = {"routes": [], "margin": [], "slack": [], "states": []}
    kinds = layer_kinds(num_hidden_layers, full_attention_interval)
    for layer, kind in enumerate(kinds):
        x, state = fns[kind](next(blocks), x)
        if state is not None:
            record["states"].append(state)
        forced = None if routes is None else routes[layer]
        x, rec = fns["E"](next(blocks), x, forced)
        for key, value in rec.items():
            record[key].append(value)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    logits = head(params, x, **sizes)
    return logits, {k: jnp.stack(v) for k, v in record.items() if v}


def forward(params, tokens, **kw):
    """tokens [S] int32 -> logits [S, V] float32."""
    return forward_with_record(params, tokens, **kw)[0]


def for_model(model: dict) -> dict:
    """The keyword arguments above, from a configuration file's keys."""
    keys = (
        "num_hidden_layers", "full_attention_interval",
        "linear_num_key_heads", "linear_key_head_dim",
        "linear_num_value_heads", "linear_value_head_dim",
        "linear_conv_kernel_dim", "num_experts_per_tok",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "partial_rotary_factor", "rope_theta",
    )
    return {k: model[k] for k in keys} | {
        "first_expert_held": model.get("first_expert_held", 0),
    }
