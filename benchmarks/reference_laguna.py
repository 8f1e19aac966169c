"""Laguna's forward pass, plainly: float32 ``jax.numpy``, no kernel, no
cache, no window buffer, no sort, no grouped matmul, matmuls at
``highest`` precision (on a TPU a float32 matmul otherwise runs in bf16
passes). One full pass over one sequence; attention is a ``[T, T]`` mask
a layer kind.

Follows the published architecture (``config.json`` of
poolside/Laguna-S-2.1, ``model_type: laguna``). With ``d`` the hidden
size, every ``Linear`` without a bias, and ``norm(x) = x / sqrt(mean(x^2)
+ eps) * (1 + w)``:

- ``x_0 = E[token]``. Layer *l* is two sublayers: ``x <- x +
  attention_l(norm(x))``, then ``x <- x + ffn_l(norm(x))``.
  ``layer_types[l]`` says which attention, ``mlp_layer_types[l]`` which
  FFN, ``num_attention_heads_per_layer[l]`` how many query heads ``H_l``.
- attention of ``n = norm(x)``: ``q = n W_q`` ``[T, H_l, Dh]``; ``k, v = n
  W_k, n W_v`` ``[T, Hkv, Dh]``; ``g = sigmoid(n W_g)`` ``[T, H_l]``, one
  number a head. ``q`` and ``k`` are rotated at positions ``0 .. T - 1`` by
  the layer kind's entry of ``rope_parameters`` (split halves within the
  rotated dimensions, the rest of the head passing through):
  ``full_attention``: the first ``partial_rotary_factor * Dh``
  dimensions, by YaRN's frequencies: with ``D`` that many dimensions and
  ``i = 0 .. D/2 - 1``, ``f_i = theta^(-2i/D)``; ``low`` / ``high`` the
  floor / ceiling of ``D ln(L0 / (2 pi b)) / (2 ln theta)`` for ``b =
  beta_fast`` / ``beta_slow`` (``L0`` ``original_max_position_embeddings``)
  held inside ``[0, D - 1]``; ``r_i = clip((i - low) / (high - low), 0,
  1)``; the frequency is ``f_i (1 - r_i) + f_i / factor * r_i``, and cos
  and sin are multiplied by ``attention_factor``;
  ``sliding_attention``: all ``Dh`` dimensions, ``theta^(-2i/Dh)``, no
  factor. Scores ``q k^T / sqrt(Dh)``; query ``t`` sees key ``j`` iff ``j
  <= t`` and, in a ``sliding_attention`` layer, ``j > t - sliding_window``;
  each KV head serves ``H_l / Hkv`` consecutive query heads; ``(g_h *
  softmax(.) v)_h W_o``. The scores are made a block of queries at a
  time so that a long sequence fits; every query sees all its keys at
  once (no running soft-max).
- ``dense`` FFN of ``m = norm(x)``: ``W_down(silu(m W_gate) * (m W_up))``.
- ``sparse`` FFN: ``p = softmax(m W_r)`` over all experts, float32; the
  ``top_k`` largest; gates ``moe_routed_scaling_factor * p_i /
  sum(chosen p)``; expert *e* is ``W_down,e (silu(m W_gate,e) * (m
  W_up,e))``; plus the shared expert of the same form, on every token,
  as it is. Each held expert is applied, in a plain loop over the
  experts (a ``lax.scan``, so that 128 of them compile as one), to every
  row and kept for the rows that chose it.
- ``logits = norm(x_L) W_head``: the head is its own matrix.

ASSUMED, as the program assumes and for the same reasons (the
configuration file lists them): the router's soft-max scoring, no norm
on ``q`` and ``k``, the gate read from the normed input through one ``[d,
H_l]`` matrix, the shared expert without a gate, ``sliding_window``
counting the query's own position. None of these is published.

The share: where the tree holds ``held`` of the model's experts
(``w_up [held, d, f]``, the experts ``first .. first + held - 1``), a
pair whose expert is not held adds nothing, here as in the program (its
gate still takes its part of the renormalisation); the vocabulary is
whatever rows the tree's embedding and head hold.

``forward_with_record`` takes optional ``routes`` (``[sparse layers, S,
top_k]``): the experts each token is sent to, in place of the
reference's own choice. Routing is discrete, so a comparison of logits
forces the system's routes on the reference and compares the routes
themselves apart: ``margin`` [S] is ``1 - p(k + 1) / p(k)`` of the sorted
router probabilities, ``slack`` [S] how far below the reference's own
cut the lowest *applied* route lies, ``max(1 - min_j p(applied_j) / p(k),
0)``. The record also holds ``windows``: each ``sliding_attention``
layer's keys and values (rotated, as a cache would keep them) at the
last ``sliding_window`` positions, oldest first.

``lower`` names one thing to compute otherwise, for the reading that a
limit has to fail. In the precision below the one the configuration
states: ``"weights_e4m3"`` (every matmul weight, the embedding among
them, rounded to float8 e4m3), ``"router_bf16"`` (router input, weights
and logits in bfloat16). A part of the architecture dropped:
``"no_window"`` (a ``sliding_attention`` layer sees every key before
it), ``"no_gate"`` (``g = 1``), ``"no_yarn_factor"`` (cos and sin as they
are), ``"no_partial_rotary"`` (a ``full_attention`` layer rotates the
whole head, by YaRN's frequencies for all ``Dh``), ``"no_routed_scaling"``
(gates times 1), ``"full_heads_in_window"`` (a ``sliding_attention`` layer
uses only as many of its heads as a ``full_attention`` layer has, the
first of each KV head's group).

Takes the program's parameter tree (``tok_emb``, ``blocks``: one tree
per SUBLAYER, ``final_norm``, ``lm_head``) and nothing else of the
program.
"""

from __future__ import annotations

import math

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
    """float32 values rounded to bfloat16's 8 bits. Not a pair of
    ``astype``s: XLA may drop such a round trip (it allows itself excess
    precision), and the reading would then be of float32."""
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + scale)


def _frequencies(rope: dict, dims: int):
    """(the ``dims / 2`` frequencies of a layer kind's rotary scheme, the
    factor on cos and sin)."""
    pairs = jnp.arange(dims // 2, dtype=jnp.float32)
    theta = float(rope["rope_theta"])
    kept = theta ** (-2.0 * pairs / dims)
    if rope["rope_type"] == "default":
        return kept, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")

    def pair_of(turns):
        return (
            dims * math.log(rope["original_max_position_embeddings"]
                            / (turns * 2.0 * math.pi))
            / (2.0 * math.log(theta))
        )

    low = max(math.floor(pair_of(rope["beta_fast"])), 0)
    high = min(math.ceil(pair_of(rope["beta_slow"])), dims - 1)
    ramp = jnp.clip((pairs - low) / max(high - low, 1e-3), 0.0, 1.0)
    blended = kept * (1.0 - ramp) + kept / rope["factor"] * ramp
    return blended, rope["attention_factor"]


def _rotate(x, rope: dict, lower):
    """x [S, H, Dh] at positions ``0 .. S - 1``."""
    head_dim = x.shape[-1]
    dims = int(head_dim * rope["partial_rotary_factor"])
    if lower == "no_partial_rotary":
        dims = head_dim
    inv_freq, factor = _frequencies(rope, dims)
    if lower == "no_yarn_factor":
        factor = 1.0
    half = dims // 2
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    cos = (jnp.cos(angles) * factor)[:, None, :]
    sin = (jnp.sin(angles) * factor)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:dims], x[..., dims:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1
    )


def attention_sublayer(p, x, kind: str, heads: int, *, num_key_value_heads,
                       head_dim, sliding_window, rope_parameters,
                       rms_norm_eps, full_heads, query_block=256, lower=None,
                       **_):
    """x [S, d] -> (x + gated attention(norm(x)), the layer's rotated
    keys and values at the last ``sliding_window`` positions, [W, Hkv,
    Dh] each, oldest first). ``kind``: the layer's ``layer_types`` entry;
    ``heads``: its query heads."""
    with jax.default_matmul_precision("highest"):
        s = x.shape[0]
        hkv = num_key_value_heads
        n = _rms_norm(x, _f32(p["attn_norm"]), rms_norm_eps)
        q = (n @ _weight(p["wq"], lower)).reshape(s, heads, head_dim)
        k = (n @ _weight(p["wk"], lower)).reshape(s, hkv, head_dim)
        v = (n @ _weight(p["wv"], lower)).reshape(s, hkv, head_dim)
        gate = jax.nn.sigmoid(n @ _weight(p["wg"], lower))  # [S, H]
        if lower == "no_gate":
            gate = jnp.ones_like(gate)
        rope = rope_parameters[kind]
        q, k = _rotate(q, rope, lower), _rotate(k, rope, lower)
        windowed = kind == "sliding_attention" and lower != "no_window"
        # Each KV head serves heads / hkv consecutive query heads.
        qg = q.reshape(s, hkv, heads // hkv, head_dim)
        block = min(query_block, s)
        n_blocks = -(-s // block)
        qg = jnp.pad(qg, ((0, n_blocks * block - s),) + ((0, 0),) * 3)
        key_pos = jnp.arange(s)

        def one_block(args):
            q_b, first = args  # [block, hkv, r, Dh], the first position
            scores = jnp.einsum("qgrd,kgd->grqk", q_b, k) * head_dim**-0.5
            pos = first + jnp.arange(block)[:, None]
            seen = key_pos[None, :] <= pos
            if windowed:
                seen &= key_pos[None, :] > pos - sliding_window
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
            return jnp.einsum("grqk,kgd->qgrd", probs, v)

        attn = jax.lax.map(
            one_block,
            (qg.reshape(n_blocks, block, *qg.shape[1:]),
             jnp.arange(n_blocks) * block),
        ).reshape(n_blocks * block, heads, head_dim)[:s]
        gated = attn * gate[:, :, None]
        if lower == "full_heads_in_window" and kind == "sliding_attention":
            # Of each KV head's group, the first full_heads / hkv heads.
            keep = jnp.arange(heads) % (heads // hkv) < full_heads // hkv
            gated = jnp.where(keep[None, :, None], gated, 0.0)
        out = x + gated.reshape(s, -1) @ _weight(p["wo"], lower)
        pad = max(sliding_window - s, 0)
        last = [
            jnp.pad(a, ((pad, 0), (0, 0), (0, 0)))[-sliding_window:]
            for a in (k, v)
        ]
        return out, jnp.stack(last)


def _gated(h, w_gate, w_up, w_down, lower):
    return (
        jax.nn.silu(h @ _weight(w_gate, lower)) * (h @ _weight(w_up, lower))
    ) @ _weight(w_down, lower)


def dense_sublayer(p, x, *, rms_norm_eps, lower=None, **_):
    """x [S, d] -> x + dense FFN(norm(x))."""
    with jax.default_matmul_precision("highest"):
        m = _rms_norm(x, _f32(p["norm"]), rms_norm_eps)
        return x + _gated(m, p["w_gate"], p["w_up"], p["w_down"], lower)


def expert_sublayer(p, x, routes=None, *, num_experts_per_tok,
                    moe_routed_scaling_factor, rms_norm_eps,
                    first_expert_held=0, lower=None, **_):
    """x [S, d] -> (x + (routed + shared)(norm(x)), the router's record
    of this layer): ``routes`` [S, k], the reference's own choice whether
    or not another was forced; ``margin`` and ``slack`` [S] (the module
    docstring)."""
    with jax.default_matmul_precision("highest"):
        k = num_experts_per_tok
        h = _rms_norm(x, _f32(p["norm"]), rms_norm_eps)
        if lower == "router_bf16":
            logits = _to_bf16(_to_bf16(h) @ _to_bf16(_f32(p["router"])))
        else:
            logits = h @ _f32(p["router"])  # [S, E]
        top, own = jax.lax.top_k(logits, k + 1)
        chosen = own[:, :k] if routes is None else routes
        applied = jnp.take_along_axis(logits, chosen, axis=-1)
        # p_i / sum of the chosen p, times the model's factor.
        gates = jax.nn.softmax(applied, axis=-1)
        if lower != "no_routed_scaling":
            gates = gates * moe_routed_scaling_factor

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
        y = y + _gated(h, p["shared_gate"], p["shared_up"], p["shared_down"],
                       lower)
        cut = top[:, k - 1]
        record = {
            "routes": own[:, :k],
            "margin": 1.0 - jnp.exp(top[:, k] - cut),
            "slack": jnp.maximum(1.0 - jnp.exp(applied.min(-1) - cut), 0.0),
        }
        return x + y, record


def embed(params, tokens, *, lower=None, **_):
    return _weight(params["tok_emb"][tokens], lower)


def head(params, x, *, rms_norm_eps, lower=None, **_):
    """Final norm and the head on the rows given: x [R, d] -> logits
    [R, V]."""
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, _f32(params["final_norm"]), rms_norm_eps)
        return x @ _weight(params["lm_head"], lower)


def forward_with_record(params, tokens, *, layer_types, mlp_layer_types,
                        num_attention_heads_per_layer, routes=None, rows=None,
                        block_fn=lambda kind, fn: fn, **sizes):
    """tokens [S] int32 -> (logits [S, V] float32, or of ``rows`` only;
    the record). The record holds, stacked over the sparse layers,
    ``routes`` [Ls, S, k], ``margin`` and ``slack`` [Ls, S], and, stacked
    over the ``sliding_attention`` layers, ``windows`` [Lw, 2 (k, v), W,
    Hkv, Dh].

    ``block_fn(kind, fn)`` wraps each kind's sublayer function; the chip
    check passes ``jax.jit`` so that the pass runs sublayer by sublayer,
    one compiled program per kind, and fits beside the engine."""
    full_heads = min(num_attention_heads_per_layer)
    fns = {
        "E": block_fn(
            "sparse", lambda p, x, forced: expert_sublayer(p, x, forced, **sizes)
        ),
        "D": block_fn("dense", lambda p, x: dense_sublayer(p, x, **sizes)),
    }
    for kind, heads in set(zip(layer_types, num_attention_heads_per_layer)):
        fns[kind, heads] = block_fn(
            kind,
            lambda p, x, kind=kind, heads=heads: attention_sublayer(
                p, x, kind, heads, full_heads=full_heads, **sizes
            ),
        )
    blocks = iter(params["blocks"])
    x = embed(params, tokens, **sizes)
    record = {"routes": [], "margin": [], "slack": [], "windows": []}
    sparse = 0
    for kind, heads, ffn in zip(
        layer_types, num_attention_heads_per_layer, mlp_layer_types,
        strict=True,
    ):
        x, last = fns[kind, heads](next(blocks), x)
        if kind == "sliding_attention":
            record["windows"].append(last)
        if ffn == "dense":
            x = fns["D"](next(blocks), x)
            continue
        forced = None if routes is None else routes[sparse]
        sparse += 1
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
    n = model["num_hidden_layers"]
    keys = (
        "num_key_value_heads", "head_dim", "sliding_window",
        "rope_parameters", "rms_norm_eps", "num_experts_per_tok",
        "moe_routed_scaling_factor",
    )
    return {k: model[k] for k in keys} | {
        "layer_types": list(model["layer_types"][:n]),
        "mlp_layer_types": list(model["mlp_layer_types"][:n]),
        "num_attention_heads_per_layer": list(
            model["num_attention_heads_per_layer"][:n]
        ),
        "first_expert_held": model.get("first_expert_held", 0),
    }
