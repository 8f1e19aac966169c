"""Granite 4.0-H's forward pass, plainly: float32 ``jax.numpy``, no
kernel, no cache, no chunked scan, no sort, no grouped matmul, matmuls
at ``highest`` precision (on a TPU a float32 matmul otherwise runs in
bf16 passes). One full pass over one sequence.

Follows the published architecture (``config.json`` of
ibm-granite/granite-4.0-h-small, ``model_type: granitemoehybrid``;
Mamba-2: Dao & Gu 2024, arXiv:2405.21060). With ``d`` the hidden size,
RMSNorm at eps 1e-5 and every ``Linear`` without a bias:

- ``x_0 = embedding_multiplier * E[token]``.
- Layer *l* is two sublayers, ``r = residual_multiplier``:
  ``x <- x + r * mixer_l(RMSNorm(x))``, then
  ``x <- x + r * (routed(u) + shared(u))`` with ``u = RMSNorm(x)``.
- ``mamba`` mixer: ``[z | xBC | dt] = u W_in``; ``xBC <- silu(causal
  depthwise conv1d_K(xBC) + b)``, split into ``x [H, P]``, ``B [G, N]``,
  ``C [G, N]`` (H / G heads share a group's B and C; G is 1 in the
  published model); ``dt <- softplus(dt + dt_bias)`` with no clamp,
  ``A = -exp(A_log)``; per head ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
  B_t^T``, ``y_t = S_t C_t + D x_t``, **as a scan over time, a token a
  step**; then ``RMSNorm(y * silu(z))`` over each group's share of the
  inner width (all of it at G = 1), times its weight, and ``W_out``.
- ``attention`` mixer: ``q, k, v`` grouped-query, **no positional
  embedding** (``position_embedding_type: nope``; ``rope_theta`` is not
  read), causal soft-max of ``q.k * attention_multiplier`` (NOT
  ``head_dim^-0.5``), ``o``. The scores are made a block of queries at
  a time so that a long sequence fits; every query sees all its keys at
  once (no running soft-max).
- ``routed(u)``: ``logits = u W_r`` over all experts; the ``top_k``
  largest logits are chosen; the gates are the soft-max over THOSE
  logits; expert *e* is ``W_down,e (silu(u W_gate,e) * (u W_up,e))``
  (``[a | b] = W_in u`` held as its halves). ``shared(u)`` has the same
  gated form and sees every token. Each held expert is applied, in a
  plain loop over the experts (a ``lax.scan``, so that 36 of them
  compile as one), to every row and kept for the rows that chose it.
- ``logits = (RMSNorm(x_L) E^T) / logits_scaling``: the head is the
  embedding.

The share: where the tree holds ``held`` of the model's experts
(``w_up [held, d, f]``, the experts ``first .. first + held - 1``), a
pair whose expert is not held adds nothing, here as in the program (its
gate still takes its part of the soft-max); the vocabulary is whatever
rows the tree's embedding holds.

Departures, noted: a norm's weight is stored as ``scale`` and read as
``1 + scale`` (``ops/norms.py``).

``forward_with_record`` takes optional ``routes`` (``[layers, S,
top_k]``): the experts each token is sent to, in place of the
reference's own choice. Routing is discrete, and a flipped tenth expert
carries a gate of a few hundredths, so a comparison of logits forces the
system's routes on the reference and compares the routes themselves
apart: ``margin`` [S] is ``1 - p(k + 1) / p(k)`` of the sorted router
probabilities (``p(j) / p(k) = exp(logit_j - logit_k)``: the gate the
next expert would have had, as a share of the tenth's), ``slack`` [S]
how far below the reference's own cut the lowest *applied* route lies,
``max(1 - min_j p(applied_j) / p(k), 0)``.

``lower`` names one thing to compute in the precision below the one
the configuration states, for the reading that a limit has to fail:
``"state_bf16"`` (the SSM state rounded to bfloat16 at every step),
``"router_bf16"`` (router input, weights and logits in bfloat16),
``"weights_e4m3"`` (every matmul weight, the embedding among them,
rounded to float8 e4m3).

Takes the program's parameter tree (``tok_emb``, ``blocks``: one tree
per SUBLAYER, ``final_norm``) and nothing else of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-5
_MIXER = {"mamba": "M", "attention": "*"}


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


def mamba_sublayer(p, x, *, mamba_n_heads, mamba_d_head, mamba_n_groups,
                   mamba_d_state, mamba_d_conv, residual_multiplier,
                   lower=None, **_):
    """x [S, d] -> (x + r * mixer(norm(x)), the SSM state after the last
    token [H, P, N])."""
    with jax.default_matmul_precision("highest"):
        s = x.shape[0]
        h, pd, g, n = mamba_n_heads, mamba_d_head, mamba_n_groups, mamba_d_state
        d_inner = h * pd
        u = _rms_norm(x, _f32(p["norm"]))
        zxbcdt = u @ _weight(p["in_proj"], lower)
        z = zxbcdt[:, :d_inner]
        xbc = zxbcdt[:, d_inner: d_inner + d_inner + 2 * g * n]
        dt = zxbcdt[:, -h:]
        # Causal depthwise convolution: tap K - 1 is the current token.
        padded = jnp.concatenate(
            [jnp.zeros((mamba_d_conv - 1, xbc.shape[1])), xbc]
        )
        conv = _f32(p["conv_b"]) + sum(
            padded[j: j + s] * _f32(p["conv_w"])[j] for j in range(mamba_d_conv)
        )
        xbc = jax.nn.silu(conv)
        xs = xbc[:, :d_inner].reshape(s, h, pd)
        b = xbc[:, d_inner: d_inner + g * n].reshape(s, g, n)
        c = xbc[:, d_inner + g * n:].reshape(s, g, n)
        dt = jax.nn.softplus(dt + _f32(p["dt_bias"]))  # [S, H]
        a = -jnp.exp(_f32(p["A_log"]))  # [H]

        def step(state, now):
            x_t, b_t, c_t, dt_t = now
            # Head h reads group h // (H / G).
            b_t = jnp.repeat(b_t, h // g, axis=0)  # [H, N]
            c_t = jnp.repeat(c_t, h // g, axis=0)
            state = (
                jnp.exp(dt_t * a)[:, None, None] * state
                + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
            )
            if lower == "state_bf16":
                state = _to_bf16(state)
            return state, jnp.einsum("hpn,hn->hp", state, c_t)

        state, y = jax.lax.scan(step, jnp.zeros((h, pd, n)), (xs, b, c, dt))
        y = y + _f32(p["D"])[:, None] * xs
        gated = y.reshape(s, d_inner) * jax.nn.silu(z)
        grouped = gated.reshape(s, g, -1)
        var = jnp.mean(grouped * grouped, axis=-1, keepdims=True)
        normed = (grouped / jnp.sqrt(var + EPS)).reshape(s, d_inner)
        normed = normed * (1.0 + _f32(p["gate_norm"]))
        out = normed @ _weight(p["out_proj"], lower)
        return x + residual_multiplier * out, state


def _gated(h, w_gate, w_up, w_down, lower):
    return (
        jax.nn.silu(h @ _weight(w_gate, lower)) * (h @ _weight(w_up, lower))
    ) @ _weight(w_down, lower)


def expert_sublayer(p, x, routes=None, *, num_experts_per_tok,
                    residual_multiplier, first_expert_held=0, lower=None,
                    **_):
    """x [S, d] -> (x + r * (routed + shared)(norm(x)), the router's
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
        gates = jax.nn.softmax(applied, axis=-1)  # over the chosen alone

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
        y = y + _gated(
            h, p["shared_gate"], p["shared_up"], p["shared_down"], lower
        )
        cut = top[:, k - 1]
        record = {
            "routes": own[:, :k],
            "margin": 1.0 - jnp.exp(top[:, k] - cut),
            "slack": jnp.maximum(1.0 - jnp.exp(applied.min(-1) - cut), 0.0),
        }
        return x + residual_multiplier * y, record


def attention_sublayer(p, x, *, num_attention_heads, num_key_value_heads,
                       head_dim, attention_multiplier, residual_multiplier,
                       query_block=512, lower=None, **_):
    """x [S, d] -> x + r * attention(norm(x)); no positional embedding,
    scores times ``attention_multiplier``."""
    with jax.default_matmul_precision("highest"):
        s = x.shape[0]
        hq, hkv = num_attention_heads, num_key_value_heads
        h = _rms_norm(x, _f32(p["attn_norm"]))
        q = (h @ _weight(p["wq"], lower)).reshape(s, hq, head_dim)
        k = (h @ _weight(p["wk"], lower)).reshape(s, hkv, head_dim)
        v = (h @ _weight(p["wv"], lower)).reshape(s, hkv, head_dim)
        # Each KV head serves hq / hkv consecutive query heads.
        k = jnp.repeat(k, hq // hkv, axis=1)
        v = jnp.repeat(v, hq // hkv, axis=1)
        block = min(query_block, s)
        n_blocks = -(-s // block)
        q = jnp.pad(q, ((0, n_blocks * block - s), (0, 0), (0, 0)))
        key_pos = jnp.arange(s)

        def one_block(args):
            q_b, first = args  # [block, hq, Dh], the block's first position
            scores = jnp.einsum("qhd,khd->hqk", q_b, k) * attention_multiplier
            seen = key_pos[None, :] <= first + jnp.arange(block)[:, None]
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
            return jnp.einsum("hqk,khd->qhd", probs, v)

        attn = jax.lax.map(
            one_block,
            (q.reshape(n_blocks, block, hq, head_dim),
             jnp.arange(n_blocks) * block),
        ).reshape(n_blocks * block, -1)[:s]
        return x + residual_multiplier * (attn @ _weight(p["wo"], lower))


def embed(params, tokens, *, embedding_multiplier, lower=None, **_):
    return embedding_multiplier * _weight(params["tok_emb"][tokens], lower)


def head(params, x, *, logits_scaling, lower=None, **_):
    """Final norm and the tied head on the rows given: x [R, d] ->
    logits [R, V]."""
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, _f32(params["final_norm"]))
        return x @ _weight(params["tok_emb"], lower).T / logits_scaling


def forward_with_record(params, tokens, *, layer_types, routes=None,
                        rows=None, block_fn=lambda kind, fn: fn, **sizes):
    """tokens [S] int32 -> (logits [S, V] float32, or of ``rows`` only;
    the record). The record holds, stacked over the layers, ``routes``
    [L, S, k], ``margin`` and ``slack`` [L, S], and ``states`` [Lm, H,
    P, N]: each Mamba layer's state after the last token.

    ``block_fn(kind, fn)`` wraps each kind's sublayer function; the chip
    check passes ``jax.jit`` so that the pass runs sublayer by sublayer,
    one compiled program per kind, and fits beside the engine."""
    fns = {
        "M": block_fn("M", lambda p, x: mamba_sublayer(p, x, **sizes)),
        "*": block_fn(
            "*", lambda p, x: (attention_sublayer(p, x, **sizes), None)
        ),
        "E": block_fn(
            "E", lambda p, x, forced: expert_sublayer(p, x, forced, **sizes)
        ),
    }
    blocks = iter(params["blocks"])
    x = embed(params, tokens, **sizes)
    record = {"routes": [], "margin": [], "slack": [], "states": []}
    for layer, kind in enumerate(layer_types):
        x, state = fns[_MIXER[kind]](next(blocks), x)
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
        "layer_types", "mamba_n_heads", "mamba_d_head", "mamba_n_groups",
        "mamba_d_state", "mamba_d_conv", "num_experts_per_tok",
        "num_attention_heads", "num_key_value_heads", "embedding_multiplier",
        "attention_multiplier", "residual_multiplier", "logits_scaling",
    )
    return {k: model[k] for k in keys} | {
        # The config gives no head_dim: hidden_size / num_attention_heads.
        "head_dim": model["hidden_size"] // model["num_attention_heads"],
        "first_expert_held": model.get("first_expert_held", 0),
    }
