"""Phi-4-mini-flash-reasoning, plainly: float32 ``jax.numpy``, no kernel,
no cache, no ring, no pages, no halves, matmuls at ``highest`` precision.
One full pass over one sequence, ALL layers at EVERY position.

Follows ISSUE 68's equations (``config.json`` of
microsoft/Phi-4-mini-flash-reasoning, ``model_type: phi4flash``; the
SambaY decoder, arXiv:2507.06607; Mamba, arXiv:2312.00752; Differential
Transformer, arXiv:2410.05258). With ``d`` the hidden size, ``LN(x) = (x
- mean) / sqrt(var + eps) * (1 + w) + b``, ``eps = layer_norm_eps``;
``x_0 = E[token]``; layer ``l`` of ``num_hidden_layers``: ``x += mixer_l
(LN(x))``, ``x += W_down (silu(g) * u)``, ``g = LN(x) W_gate``, ``u =
LN(x) W_up``; logits ``= LN(x) E^T``. No positional encoding. The first
``num_hidden_layers / 2 + mb_per_layer`` layers are the self-decoder:

- even ``l``, **Mamba-1**: ``[x; z] = u W_in``; ``x = silu(conv(x) +
  b)``, causal and depthwise over ``d_conv`` taps, zeros before the
  sequence; ``[dl; B; C] = x W_x``; ``dt = softplus(dl W_dt + b_dt)``;
  ``A = -exp(A_log)`` ``[d_inner, N]``; a token at a time by ``lax.scan``:
  ``h_t[c, n] = exp(dt_t[c] A[c, n]) h_{t-1}[c, n] + dt_t[c] B_t[n]
  x_t[c]``, ``y_t[c] = sum_n C_t[n] h_t[c, n] + D[c] x_t[c]``; ``out = (y
  * silu(z)) W_out``. The last such layer's ``y`` is the memory ``m``.
- odd ``l``, **differential attention**: ``[q; k; v] = u W_qkv + b``,
  ``H`` query heads and ``H / 2`` key/value heads of width ``w = d / H``.
  Pair ``j`` of ``H / 2``: ``q1 = q[2j]``, ``q2 = q[2j+1]``; with ``g = j
  // 2``: ``k1 = k[2g]``, ``k2 = k[2g+1]``, ``V = [v[2g]; v[2g+1]]``;
  ``o_j = (softmax(q1 k1^T / sqrt(w)) - lam softmax(q2 k2^T / sqrt(w)))
  V`` under a ``[queries, S]`` mask, ``t <= i`` and, but for the LAST
  self-decoder layer, ``i - sliding_window < t``; ``lam = exp(lq1 . lk1)
  - exp(lq2 . lk2) + lam_init``, ``lam_init = 0.8 - 0.6 exp(-0.3 l)``;
  ``o_j <- o_j / sqrt(mean(o_j^2) + eps) * (1 + w_sub) * (1 -
  lam_init)``; ``out = [o_0; ..] W_o + b_o``.

and the rest the cross-decoder:

- even ``l``, **gated memory unit**: ``out = (m * silu(u W_1)) W_2``.
- odd ``l``, **cross attention**: ``q = u W_q + b`` only; ``k``, ``v``
  are the last self-decoder layer's, every position's; the same
  differential form, causal, with the layer's own ``lam`` vectors, norm
  and ``lam_init``.

The stream is walked in blocks of ``token_block`` rows, each sublayer
over every block before the next (a recurrence hands its state and its
convolution's last inputs from block to block), and the scores are made
``query_block`` queries and a key/value pair's four heads at a time, so
that a 24k sequence fits beside a replica; every query still sees all
its keys at once, and every layer runs at every position.

DEPARTURES from the published description, all of storage and none of
arithmetic: ``W_gate_up`` is held as two matrices; ``A_log`` is held
``[N, d_inner / 128, 128]`` and read here as ``[d_inner, N]``; a norm's
weight is held as ``w - 1``; ``lam_init`` is a leaf of the layer's tree.

``lower`` names one thing to compute otherwise, for the reading that a
limit has to fail: ``"weights_e4m3"`` (matmul weights through e4m3, the
nearest precision below the configuration's bfloat16), ``"state_bf16"``
(Mamba's state rounded to bfloat16 after every token), ``"lam_const"``
(``lam = lam_init``: the four vectors do not count), ``"window_511"``
(one key fewer in a window), ``"memory_after_gate"`` (``m = y *
silu(z)``), ``"cross_own_keys"`` (a cross layer reads keys and values
made from ITS OWN input by the last self-decoder layer's ``W_kv``, where
the model shares that layer's).

Takes the program's parameter tree (``tok_emb``, ``blocks``: one tree
per SUBLAYER, ``final_norm`` and its bias) and nothing else of the
program.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

LOWERS = ("weights_e4m3", "state_bf16", "lam_const", "window_511",
          "memory_after_gate", "cross_own_keys")


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


def _layer_norm(x, p, name, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * (1.0 + _f32(p[name])) + _f32(
        p[f"{name}_bias"]
    )


def for_model(model: dict) -> dict:
    """The sizes the functions here take, from a configuration's
    published keys and its ``assumed`` ones."""
    assumed = model["assumed_values"]
    return {
        "num_hidden_layers": model["num_hidden_layers"],
        "self_layers": model["num_hidden_layers"] // 2 + model["mb_per_layer"],
        "num_attention_heads": model["num_attention_heads"],
        "num_key_value_heads": model["num_key_value_heads"],
        "sliding_window": model["sliding_window"],
        "layer_norm_eps": model["layer_norm_eps"],
        "d_state": assumed["d_state"],
        "d_conv": assumed["d_conv"],
        "dt_rank": assumed["dt_rank"],
    }


def layer_kinds(*, num_hidden_layers, self_layers, **_) -> list[str]:
    """Each layer's mixer: ``mamba``, ``window``, ``full``, ``gmu`` or
    ``cross``."""
    def kind(layer):
        if layer >= self_layers:
            return "cross" if layer % 2 else "gmu"
        if layer % 2 == 0:
            return "mamba"
        return "full" if layer == self_layers - 1 else "window"

    return [kind(layer) for layer in range(num_hidden_layers)]


# ---------------------------------------------------------------- Mamba-1
def mamba(p, x, h0, tail0, n_live, *, d_state, d_conv, dt_rank,
          layer_norm_eps, lower=None, **_):
    """A block of tokens: x [T, d] of which the first ``n_live`` are
    real, from the state h0 [d_inner, N] and the convolution's last
    ``d_conv - 1`` inputs tail0 before them (zeros before the sequence)
    -> (out [T, d], the memory candidate [T, d_inner], the state and the
    tail after token ``n_live - 1``)."""
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        u = _layer_norm(x, p, "norm", layer_norm_eps)
        xz = u @ _weight(p["in_proj"], lower)
        xi, z = jnp.split(xz, 2, axis=-1)
        padded = jnp.concatenate([tail0, xi], axis=0)
        conv = _f32(p["conv_b"]) + sum(
            padded[j: j + t] * _f32(p["conv_w"])[j] for j in range(d_conv)
        )
        xc = jax.nn.silu(conv)
        low, b_in, c_in = jnp.split(
            xc @ _weight(p["x_proj"], lower), [dt_rank, dt_rank + d_state],
            axis=-1,
        )
        dt = jax.nn.softplus(
            low @ _weight(p["dt_proj"], lower) + _f32(p["dt_bias"])
        )
        a = -jnp.exp(_f32(p["A_log"])).reshape(d_state, -1).T  # [d_inner, N]

        def token(h, inputs):
            x_t, dt_t, b_t, c_t, live = inputs
            new = jnp.exp(dt_t[:, None] * a) * h + (dt_t * x_t)[:, None] * b_t
            if lower == "state_bf16":
                new = _to_bf16(new)
            h = jnp.where(live, new, h)
            return h, (h * c_t).sum(-1)

        end, y = jax.lax.scan(
            token, h0, (xc, dt, b_in, c_in, jnp.arange(t) < n_live)
        )
        y = y + _f32(p["D"]) * xc
        gated = y * jax.nn.silu(z)
        memory = gated if lower == "memory_after_gate" else y
        out = gated @ _weight(p["out_proj"], lower)
        tail = jax.lax.dynamic_slice_in_dim(padded, n_live, d_conv - 1, axis=0)
        return out, memory, end, tail


# ----------------------------------------------- differential attention
def _lam(p, lower):
    init = _f32(p["lam_init"])
    if lower == "lam_const":
        return init, init
    return (
        jnp.exp(jnp.dot(_f32(p["lam_q1"]), _f32(p["lam_k1"])))
        - jnp.exp(jnp.dot(_f32(p["lam_q2"]), _f32(p["lam_k2"]))) + init
    ), init


def _differential(p, q, k, v, first, window, *, layer_norm_eps, query_block,
                  lower=None):
    """q [T, H, w] at positions ``first ..``, k and v [S, H / 2, w]
    (every position's) -> the pairs' outputs after the subtraction and
    the norm, [T, H / 2 x 2 w]. ``window``: how many keys a query sees,
    its own among them (None: all up to its own)."""
    t_q, heads, width = q.shape
    groups = heads // 4
    lam, init = _lam(p, lower)
    query_block = min(query_block, t_q)
    blocks = -(-t_q // query_block)
    pad = blocks * query_block - t_q
    # Queries in blocks (zeros behind the last: read by nobody); keys
    # whole. A pair of key/value heads is read by four query heads:
    # [g, (pair j of 2), (map of 2)].
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        blocks, query_block, groups, 2, 2, width
    )
    kg = k.reshape(-1, groups, 2, width)
    vg = v.reshape(-1, groups, 2 * width)
    t = jnp.arange(k.shape[0])

    def one_block(args):
        q_blk, at = args  # [qb, g, 2, 2, w]
        i = first + at + jnp.arange(query_block)
        hidden = t[None, :] > i[:, None]
        if window is not None:
            hidden |= t[None, :] <= i[:, None] - window

        def one_group(g_args):
            q_g, k_g, v_g = g_args  # [qb, 2, 2, w], [S, 2, w], [S, 2w]
            scores = jnp.einsum("qjmd,tmd->jmqt", q_g, k_g) / math.sqrt(width)
            probs = jax.nn.softmax(
                jnp.where(hidden[None, None], -jnp.inf, scores), axis=-1
            )
            maps = jnp.einsum("jmqt,td->qjmd", probs, v_g)
            return maps[:, :, 0] - lam * maps[:, :, 1]  # [qb, 2, 2w]

        out = jax.lax.map(
            one_group,
            (q_blk.transpose(1, 0, 2, 3, 4), kg.transpose(1, 0, 2, 3),
             vg.transpose(1, 0, 2)),
        )  # [g, qb, 2, 2w]
        return out.transpose(1, 0, 2, 3)

    out = jax.lax.map(
        one_block, (qb, jnp.arange(blocks) * query_block)
    )  # [blocks, qb, g, 2, 2w]
    out = out.reshape(blocks * query_block, heads // 2, 2 * width)[:t_q]
    var = jnp.mean(out * out, axis=-1, keepdims=True)
    out = out / jnp.sqrt(var + layer_norm_eps) * (1.0 + _f32(p["sub_norm"]))
    return (out * (1.0 - init)).reshape(t_q, -1)


def qkv(p, x, *, num_attention_heads, layer_norm_eps, lower=None, cross=False,
        **_):
    """A block's x [T, d] -> q [T, H, w] and, but for a cross layer, k
    and v [T, H / 2, w]: what a cache would hold of the layer."""
    with jax.default_matmul_precision("highest"):
        heads = num_attention_heads
        u = _layer_norm(x, p, "attn_norm", layer_norm_eps)
        width = u.shape[1] // heads
        out = u @ _weight(p["wqkv"], lower) + _f32(p["bqkv"])
        q = out[:, : heads * width].reshape(-1, heads, width)
        if cross:
            return q
        k, v = jnp.split(out[:, heads * width:], 2, axis=-1)
        return (q, k.reshape(-1, heads // 2, width),
                v.reshape(-1, heads // 2, width))


def attend(p, q, k, v, first, window, *, layer_norm_eps, query_block=512,
           lower=None, **_):
    """A block's queries q [T, H, w] at positions ``first ..`` over every
    position's keys and values k, v [S, H / 2, w] -> the layer's output
    [T, d]."""
    with jax.default_matmul_precision("highest"):
        o = _differential(
            p, q, k, v, first, window, layer_norm_eps=layer_norm_eps,
            query_block=query_block, lower=lower,
        )
        return o @ _weight(p["wo"], lower) + _f32(p["bo"])


def gmu(p, x, memory, *, layer_norm_eps, lower=None, **_):
    with jax.default_matmul_precision("highest"):
        u = _layer_norm(x, p, "norm", layer_norm_eps)
        gate = jax.nn.silu(u @ _weight(p["w_in"], lower))
        return (memory * gate) @ _weight(p["w_out"], lower)


def mlp(p, x, *, layer_norm_eps, lower=None, **_):
    with jax.default_matmul_precision("highest"):
        u = _layer_norm(x, p, "norm", layer_norm_eps)
        act = jax.nn.silu(u @ _weight(p["w_gate"], lower)) * (
            u @ _weight(p["w_up"], lower)
        )
        return act @ _weight(p["w_down"], lower)


def head(params, x, *, layer_norm_eps, lower=None, vocab_block=32768, **_):
    """x [R, d] -> logits [R, V], the embedding read a block of rows at
    a time (200,064 x 2,560 in float32 is 2 GB)."""
    with jax.default_matmul_precision("highest"):
        u = _layer_norm(x, params, "final_norm", layer_norm_eps)
        emb = params["tok_emb"]
        return jnp.concatenate([
            u @ _weight(emb[at: at + vocab_block], lower).T
            for at in range(0, emb.shape[0], vocab_block)
        ], axis=-1)


def forward_with_record(params, tokens, *, rows=None, block_fn=None,
                        lower=None, token_block=2048, scan_block=256,
                        **sizes):
    """tokens [S] int32 -> (logits [S or len(rows), V] float32, record).

    ``record``: ``k``, ``v`` [S, H / 2, w]: the last self-decoder layer's
    keys and values (what the pages hold); ``win_k``, ``win_v`` [window
    layers, min(S, W), H / 2, w]: the window layers' at the last
    ``sliding_window`` positions (what a ring holds); ``states`` [Mamba
    layers, d_inner, N] after the last token and ``tails`` [Mamba layers,
    d_conv - 1, d_inner]; ``memory`` [S, d_inner].

    The stream is walked ``token_block`` rows at a time (zeros behind the
    sequence fill the last block: they take no step of a recurrence, and
    no real query sees their keys), each sublayer over every block before
    the next, the stream and the memory held on the host between them: a
    24k-token pass then needs a block's activations, one layer's keys and
    values and one block's scores on the device, whatever else it holds
    (a replica's 13 GB). ``block_fn(kind, fn)`` may wrap a sublayer's
    function (``jax.jit``, cached a kind): every block has one shape."""
    import numpy as np

    if lower is not None and lower not in LOWERS:
        raise ValueError(f"lower={lower!r}: one of {LOWERS}")
    sizes = dict(sizes, lower=lower)
    wrap = block_fn or (lambda kind, fn: fn)
    kinds = layer_kinds(**sizes)
    window = sizes["sliding_window"] - (lower == "window_511")
    s = len(tokens)
    size = min(token_block, s)
    firsts = range(0, s, size)
    padded = len(firsts) * size

    def run(kind, fn, *args, **kw):
        return wrap(kind, lambda *a: fn(*a, **sizes, **kw))(*args)

    def rowwise(kind, fn, p, *streams):
        """``fn`` over every block of the host's ``streams``."""
        return np.concatenate([
            np.asarray(run(kind, fn, p, *(a[at: at + size] for a in streams)))
            for at in firsts
        ])

    tokens = np.pad(np.asarray(tokens), (0, padded - s))
    x = np.asarray(_f32(params["tok_emb"][tokens]))
    width = x.shape[1] // sizes["num_attention_heads"]
    d_inner = params["blocks"][0]["in_proj"].shape[1] // 2
    record = {"win_k": [], "win_v": [], "states": [], "tails": []}
    memory = k = v = shared = None
    for layer, kind in enumerate(kinds):
        p, p_mlp = params["blocks"][2 * layer], params["blocks"][2 * layer + 1]
        if kind == "mamba":
            h = jnp.zeros((d_inner, sizes["d_state"]))
            tail = jnp.zeros((sizes["d_conv"] - 1, d_inner))
            outs, mems = [], []
            # In blocks of at most `scan_block` rows: a compiler that
            # turns the token loop's per-token [d_inner, N] products
            # into a block's at once (one did, on the chip: 2.3 GB at
            # 2,004 rows) then makes 80 MB of them.
            step = scan_block if size % scan_block == 0 else size
            for at in range(0, padded, step):
                out, mem, h, tail = run(
                    kind, mamba, p, x[at: at + step], h, tail,
                    np.int32(max(min(step, s - at), 0)),
                )
                outs.append(np.asarray(out))
                mems.append(np.asarray(mem))
            out, memory = np.concatenate(outs), np.concatenate(mems)
            record["states"].append(h)
            record["tails"].append(tail)
        elif kind == "gmu":
            out = rowwise(kind, gmu, p, x, memory)
        else:
            if kind == "cross":
                q = rowwise("q", partial(qkv, cross=True), p, x)
                if lower == "cross_own_keys":
                    _, k, v = (np.concatenate(part) for part in zip(*(
                        run("qkv", qkv, shared, x[at: at + size])
                        for at in firsts
                    )))
            else:
                q, k, v = (np.concatenate(part) for part in zip(*(
                    run("qkv", qkv, p, x[at: at + size]) for at in firsts
                )))
                if kind == "window":
                    lo = max(s - sizes["sliding_window"], 0)
                    record["win_k"].append(k[lo:s])
                    record["win_v"].append(v[lo:s])
                else:
                    shared = p
            seen = window if kind == "window" else None
            k_all, v_all = jnp.asarray(k), jnp.asarray(v)
            out = np.concatenate([
                np.asarray(run(
                    kind, partial(attend, window=seen), p, q[at: at + size],
                    k_all, v_all, np.int32(at),
                )) for at in firsts
            ])
        x = x + out
        x = x + rowwise("mlp", mlp, p_mlp, x)
    x = x[:s] if rows is None else x[np.asarray(rows)]
    logits = run("head", head, params, jnp.asarray(x))
    record = {name: jnp.stack([jnp.asarray(leaf) for leaf in leaves])
              for name, leaves in record.items()}
    return logits, record | {
        "k": jnp.asarray(k[:s]), "v": jnp.asarray(v[:s]),
        "memory": jnp.asarray(memory[:s]),
    }


def forward(params, tokens, **sizes):
    """tokens [S] int32 -> logits [S, V] float32."""
    return forward_with_record(params, tokens, **sizes)[0]
