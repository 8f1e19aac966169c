"""Nemotron-H's forward pass, plainly: float32 ``jax.numpy``, no kernel,
no cache, no chunked scan, no sort, no grouped matmul, matmuls at
``highest`` precision (on a TPU a float32 matmul otherwise runs in bf16
passes). One full pass over one sequence.

Follows the published architecture (``config.json`` of
NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, ``model_type: nemotron_h``;
Mamba-2: Dao & Gu 2024, arXiv:2405.21060). Block *i* is of the kind
``pattern[i]`` and computes ``x <- x + mixer_i(RMSNorm(x; eps 1e-5))``;
after the last block one more RMSNorm and the untied head.

- ``M``, Mamba-2: ``[z | xBC | dt] = u W_in``; ``xBC <- silu(causal
  depthwise conv1d_K(xBC) + b)``, split into ``x [H, P]``, ``B [G, N]``,
  ``C [G, N]`` (H / G heads share a group's B and C); ``dt <-
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head the recurrence
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D
  x_t``, **as a scan over time**; then ``RMSNorm_groups(y * silu(z))``
  over G groups, times its weight, and ``W_out``.
- ``E``, experts: ``s = sigmoid(h W_r)`` over all experts; the ``top_k``
  largest of ``s + bias`` are chosen (``n_group`` 1, ``topk_group`` 1:
  group-limited selection is the identity); gates are the chosen ``s``
  over their sum, times ``routed_scaling_factor``; expert *e* is
  ``relu(h W_up,e)^2 W_down,e``; one shared expert of the same form
  sees every token. Each held expert is applied, in a plain loop over
  the experts (a ``lax.scan``, so that 64 of them compile as one), to
  every row and kept for the rows that chose it, by a mask.
- ``*``, attention: ``q, k, v`` without bias, grouped-query, causal
  soft-max at ``head_dim^-0.5``, ``o``. **No rotary embedding** (the
  family's attention, descended from Jamba's, applies none;
  ``rope_theta`` in the config is not read).

The share: where the tree holds ``held`` of the model's experts
(``w_up [held, d, f]``, the experts ``first .. first + held - 1``), a
pair whose expert is not held adds nothing, here as in the program; the
vocabulary is whatever the tree's embedding and head hold.

Departures, noted: a norm's weight is stored as ``scale`` and read as
``1 + scale`` (``ops/norms.py``).

``forward_with_record`` takes optional ``routes`` (``[expert blocks, S,
top_k]``): the experts each token is sent to, in place of the
reference's own choice. Routing is discrete, and a flipped sixth expert
carries a gate near 0.4, so a comparison of logits forces the system's
routes on the reference and compares the routes themselves apart, by
``margin`` and ``slack`` (``reference_olmoe.py`` has the same pair).

``lower`` names one thing to compute in the precision below the one
the configuration states, for the reading that a limit has to fail:
``"state_bf16"`` (the SSM state rounded to bfloat16 at every step),
``"router_bf16"`` (router input, weights and scores in bfloat16),
``"weights_e4m3"`` (every matmul weight rounded to float8 e4m3).

Takes the program's parameter tree (``tok_emb``, ``blocks``: one tree
per block, ``final_norm``, ``lm_head``) and nothing else of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-5


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


def mamba_block(p, x, *, mamba_heads, mamba_head_dim, n_groups,
                ssm_state_size, conv_kernel, lower=None, **_):
    """x [S, d] -> (x + mixer(norm(x)), the SSM state after the last
    token [H, P, N])."""
    with jax.default_matmul_precision("highest"):
        s = x.shape[0]
        h, pd, g, n = mamba_heads, mamba_head_dim, n_groups, ssm_state_size
        d_inner = h * pd
        u = _rms_norm(x, _f32(p["norm"]))
        zxbcdt = u @ _weight(p["in_proj"], lower)
        z = zxbcdt[:, :d_inner]
        xbc = zxbcdt[:, d_inner: d_inner + d_inner + 2 * g * n]
        dt = zxbcdt[:, -h:]
        # Causal depthwise convolution: tap K - 1 is the current token.
        padded = jnp.concatenate(
            [jnp.zeros((conv_kernel - 1, xbc.shape[1])), xbc]
        )
        conv = _f32(p["conv_b"]) + sum(
            padded[j: j + s] * _f32(p["conv_w"])[j] for j in range(conv_kernel)
        )
        xbc = jax.nn.silu(conv)
        xs = xbc[:, :d_inner].reshape(s, h, pd)
        b = xbc[:, d_inner: d_inner + g * n].reshape(s, g, n)
        c = xbc[:, d_inner + g * n:].reshape(s, g, n)
        # Head h reads group h // (H / G).
        b = jnp.repeat(b, h // g, axis=1)  # [S, H, N]
        c = jnp.repeat(c, h // g, axis=1)
        dt = jax.nn.softplus(dt + _f32(p["dt_bias"]))  # [S, H]
        a = -jnp.exp(_f32(p["A_log"]))  # [H]

        def step(state, now):
            x_t, b_t, c_t, dt_t = now
            state = (
                jnp.exp(dt_t * a)[:, None, None] * state
                + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
            )
            if lower == "state_bf16":
                state = _to_bf16(state)
            return state, jnp.einsum("hpn,hn->hp", state, c_t)

        state, y = jax.lax.scan(
            step, jnp.zeros((h, pd, n)), (xs, b, c, dt)
        )
        y = y + _f32(p["D"])[:, None] * xs
        gated = y.reshape(s, d_inner) * jax.nn.silu(z)
        grouped = gated.reshape(s, g, -1)
        var = jnp.mean(grouped * grouped, axis=-1, keepdims=True)
        normed = (grouped / jnp.sqrt(var + EPS)).reshape(s, d_inner)
        normed = normed * (1.0 + _f32(p["gate_norm"]))
        return x + normed @ _weight(p["out_proj"], lower), state


def expert_block(p, x, routes=None, *, num_experts_per_tok, norm_topk_prob,
                 routed_scaling_factor, first_expert_held=0, lower=None, **_):
    """x [S, d] -> (x + experts(norm(x)), the router's record of this
    block): ``routes`` [S, k], the reference's own choice whether or not
    another was forced; ``margin`` [S], ``1 - sel(k + 1) / sel(k)`` of
    the sorted selection scores ``s + bias``; ``slack`` [S], how far
    below the reference's own cut the lowest *applied* route lies,
    ``max(1 - min_j sel(applied_j) / sel(k), 0)``."""
    with jax.default_matmul_precision("highest"):
        k = num_experts_per_tok
        h = _rms_norm(x, _f32(p["norm"]))
        if lower == "router_bf16":
            scores = _to_bf16(jax.nn.sigmoid(
                _to_bf16(_to_bf16(h) @ _to_bf16(_f32(p["router"])))
            ))
        else:
            scores = jax.nn.sigmoid(h @ _f32(p["router"]))  # [S, E]
        select = scores + _f32(p["router_bias"])
        top, own = jax.lax.top_k(select, k + 1)
        chosen = own[:, :k] if routes is None else routes
        gates = jnp.take_along_axis(scores, chosen, axis=-1)
        if norm_topk_prob:
            gates = gates / gates.sum(-1, keepdims=True)
        gates = gates * routed_scaling_factor
        def one_expert(y, expert):
            # The gate of held expert e for each row: 0 where the row
            # did not choose it.
            e, w_up, w_down = expert
            weight = jnp.where(chosen == first_expert_held + e, gates, 0.0)
            hidden = jnp.square(jax.nn.relu(h @ _weight(w_up, lower)))
            return y + weight.sum(-1)[:, None] * (
                hidden @ _weight(w_down, lower)
            ), None

        held = p["w_up"].shape[0]
        y, _ = jax.lax.scan(
            one_expert, jnp.zeros_like(h),
            (jnp.arange(held), p["w_up"], p["w_down"]),
        )
        shared = jnp.square(jax.nn.relu(h @ _weight(p["shared_up"], lower)))
        y = y + shared @ _weight(p["shared_down"], lower)
        applied = jnp.take_along_axis(select, chosen, axis=-1)
        record = {
            "routes": own[:, :k],
            "margin": 1.0 - top[:, k] / top[:, k - 1],
            "slack": jnp.maximum(1.0 - applied.min(-1) / top[:, k - 1], 0.0),
        }
        return x + y, record


def attention_block(p, x, *, num_attention_heads, num_key_value_heads,
                    head_dim, lower=None, **_):
    """x [S, d] -> x + attention(norm(x)); no rotary embedding."""
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
        scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(
            jnp.float32(head_dim)
        )
        causal = jnp.tril(jnp.ones((s, s), bool))
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        attn = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, -1)
        return x + attn @ _weight(p["wo"], lower)


def embed(params, tokens):
    return _f32(params["tok_emb"])[tokens]


def head(params, x, lower=None):
    """Final norm and the output head on the rows given: x [R, d] ->
    logits [R, V]."""
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, _f32(params["final_norm"]))
        return x @ _weight(params["lm_head"], lower)


def forward_with_record(params, tokens, *, pattern, routes=None, rows=None,
                        block_fn=lambda kind, fn: fn, **sizes):
    """tokens [S] int32 -> (logits [S, V] float32, or of ``rows`` only;
    the record). The record holds, stacked over the expert blocks,
    ``routes`` [Le, S, k], ``margin`` and ``slack`` [Le, S], and
    ``states`` [Lm, H, P, N]: each Mamba block's state after the last
    token.

    ``block_fn(kind, fn)`` wraps each kind's block function; the chip
    check passes ``jax.jit`` so that the pass runs block by block, one
    compiled program per kind, and fits beside the engine."""
    blocks = {
        "M": block_fn("M", lambda p, x: mamba_block(p, x, **sizes)),
        "E": block_fn(
            "E", lambda p, x, forced: expert_block(p, x, forced, **sizes)
        ),
        "*": block_fn("*", lambda p, x: attention_block(p, x, **sizes)),
    }
    x = embed(params, tokens)
    record = {"routes": [], "margin": [], "slack": [], "states": []}
    n_expert = 0
    for kind, p in zip(pattern, params["blocks"], strict=True):
        if kind == "M":
            x, state = blocks["M"](p, x)
            record["states"].append(state)
        elif kind == "E":
            forced = None if routes is None else routes[n_expert]
            x, rec = blocks["E"](p, x, forced)
            n_expert += 1
            for key, value in rec.items():
                record[key].append(value)
        else:
            x = blocks["*"](p, x)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    logits = head(params, x, sizes.get("lower"))
    return logits, {k: jnp.stack(v) for k, v in record.items() if v}


def forward(params, tokens, **kw):
    """tokens [S] int32 -> logits [S, V] float32."""
    return forward_with_record(params, tokens, **kw)[0]


def for_model(model: dict) -> dict:
    """The keyword arguments above, from a configuration file's keys."""
    keys = (
        "mamba_head_dim", "n_groups", "ssm_state_size", "conv_kernel",
        "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
        "num_attention_heads", "num_key_value_heads", "head_dim",
    )
    return {k: model[k] for k in keys} | {
        "mamba_heads": model["mamba_num_heads"],
        "pattern": model["hybrid_override_pattern"],
        "first_expert_held": model.get("first_expert_held", 0),
    }
