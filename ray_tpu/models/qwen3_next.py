"""Qwen3-Next (``model_type: qwen3_next``): three Gated DeltaNet layers
to one gated attention layer, a sparse-expert FFN in every layer.

Every layer is TWO sublayers, each behind its own RMSNorm (eps 1e-6):
``x <- x + mixer(norm(x))``, then ``x <- x + ffn(norm(x))``. Layer ``l``
attends where ``(l + 1) % full_attention_interval == 0``:

- ``G``, a Gated DeltaNet mixer (Yang, Kautz & Hatamizadeh 2024,
  arXiv:2412.06464), here in its two forms, `gdn_chunked` and
  `gdn_step`. ``[q | k | v | z] = u W_qkvz``, ``[b | a] = u W_ba``; a
  causal depthwise convolution of width 4 without a bias over ``[q | k |
  v]``, then SiLU; ``q`` and ``k`` scaled to unit length per head, ``q``
  then by ``d_k^-0.5``; key head ``j`` serves value heads ``2j`` and
  ``2j + 1``. Per value head and token, float32: ``beta = sigmoid(b)``,
  ``alpha = exp(-exp(A_log) softplus(a + dt_bias))`` and, with ``S``
  ``[d_k, d_v]``,

      S <- alpha S;  d = beta (v - S^T k);  S <- S + k d^T;  o = S^T q.

  The state is a MATRIX per head that each token first decays and then
  corrects by a rank-one term that depends on the state itself: no
  diagonal recurrence (``nemotron_h.mamba_chunked``'s dual form does not
  compute it). Then ``RMSNorm(o) * w * silu(z)`` over each head's 128
  (``w`` plain, initialised 1) and ``W_out``.
- ``*``, grouped-query attention with what ``NemotronHConfig`` holds off
  for the other families: ``W_q`` twice as wide, each head's second half
  a gate (``out = (attn * sigmoid(g)) W_o``), an RMSNorm over each head
  of ``q`` and ``k``, a rotary embedding on the first quarter of each
  head. It is ``llm/hybrid_kv.py``'s attention block, not this file's.
- ``E``, ``models/moe.py``'s ``moe_ffn``: softmax over all experts, the
  ten largest renormalised, gated-SiLU experts, and a shared expert
  times ``sigmoid(u w_s)``, a scalar a token.

The sizes are those of Qwen/Qwen3-Next-80B-A3B-Instruct, the public
model the benchmark serves through this file. The config subclasses
``NemotronHConfig`` for the reason ``models/granite_hybrid.py`` gives:
the serving programs stay one loop over sublayers, and every field they,
``moe_ffn`` and the attention block read is one of that class's. NOT
HERE: the multi-token-prediction module (no speculation is served over
recurrent state), a backward pass.

A norm's weight is stored as ``scale`` and applied as ``1 + scale``
(``ops/norms.py``), which is this model's own convention; the gated
norm inside the mixer holds its weight plain.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import ClassVar

import jax
import jax.numpy as jnp

from ray_tpu._private import chip
from ray_tpu.models import granite_hybrid
from ray_tpu.models.nemotron_h import (
    NemotronHConfig,
    Params,
    _init_block,
    _init_ends,
    _normal,
)
from ray_tpu.ops.pallas.gdn_chunk import gdn_chunk_rule
from ray_tpu.ops.pallas.state_step import gdn_state_step

_HIGHEST = jax.lax.Precision.HIGHEST
_L2_EPS = 1e-6


def sublayers(n_layers: int, full_attention_interval: int) -> str:
    """``pattern`` for ``n_layers`` layers: each layer's mixer (``*`` where
    ``(l + 1) % full_attention_interval == 0``, else ``G``), then its
    expert FFN."""
    return "".join(
        ("*" if (layer + 1) % full_attention_interval == 0 else "G") + "E"
        for layer in range(n_layers)
    )


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig(NemotronHConfig):
    vocab_size: int = 151936  # rows held, where the vocabulary is sliced
    d_model: int = 2048
    pattern: str = sublayers(48, 4)
    n_heads: int = 16
    n_kv_heads: int = 2
    head_dim: int = 256
    qk_norm: bool = True
    rotary_dim: int = 64  # partial_rotary_factor 0.25 of 256
    rope_theta: float = 1e7
    attn_output_gate: bool = True
    norm_eps: float = 1e-6
    # Gated DeltaNet blocks
    gdn_key_heads: int = 16
    gdn_value_heads: int = 32
    gdn_key_dim: int = 128
    gdn_value_dim: int = 128
    # Tokens the chunked form takes at once: within a chunk the rule is
    # matrix products and one triangular inverse of this size, between
    # chunks the state. The mixer alone over 2,048 tokens at these
    # widths, on a v5e, XLA's form (my chip run, PR 51): 4.94 / 4.68 /
    # 5.49 ms at chunks of 16 / 32 / 64 (at 128, with one-pass products,
    # 8.15 for 4.47 at 64): longer chunks pay the inverse and the C x C
    # scores, shorter ones more steps of the scan. On a TPU the rule is
    # `ops/pallas/gdn_chunk.py` since PR 58 (the mixer 4.49 -> 2.35 ms
    # at 32: my chip run, PR 58; not timed at the other chunks).
    gdn_chunk: int = 32
    num_experts: int = 512
    top_k: int = 10
    d_ff: int = 512
    shared_d_ff: int = 512
    routed_scaling_factor: float = 1.0
    router_kind: str = "softmax"
    expert_kind: str = "swiglu"
    # Up to this many rows every held expert that got a row is applied to
    # every row (a decode step's 32), above it pairs are sorted into
    # grouped matmuls (a 2,048-token chunk's 20,480 pairs). Measured on a
    # v5e at these widths, 256 of 512 experts held, one expert layer (my
    # chip run, PR 51), every row (`ops/pallas/expert_rows.py`) / sorted
    # (`_experts_on_pairs_here`): 1.20 ms / not run at 32 rows (320 pair
    # rows: the grouped matmul's 256-row tile does not divide them),
    # 1.55 at 64, 2.10 / 3.40 at 128, 2.31 / 3.78 at 256, 4.43 / 4.09 at
    # 512, 20.07 / 6.80 at 2,048. The forms cross near 450 rows; the
    # engine's calls have 32 or 2,048 rows and more, so any boundary
    # between gives the same programs, and 256 is the other families'.
    # (Those sorted readings summed their rows by XLA's scatter-add, ten
    # blocks of it at 2,048 rows; since PR 52 the sum is one kernel call,
    # `ops/pallas/expert_combine.py`, and 2,048 rows read 6.74 -> 5.12 ms
    # on a layer of these widths with random routes: my chip run, PR 52.
    # The shorter calls were not timed again; they can only cross lower.
    # Since PR 56 the sorted form's matmuls are `ops/pallas/grouped_rows.py`
    # on a TPU, whose tile divides nothing: 320 pair rows run.)
    dense_expert_rows: int = 256
    max_seq: int = 262144

    block_kinds: ClassVar[str] = "GE*"

    def __post_init__(self):
        super().__post_init__()
        if set(self.pattern[1::2]) != {"E"} or "E" in self.pattern[::2]:
            raise ValueError(
                f"pattern {self.pattern!r}: a layer is a mixer (G or *) "
                "and then its expert FFN (E)"
            )
        if self.gdn_value_heads % self.gdn_key_heads:
            raise ValueError("gdn_key_heads does not divide gdn_value_heads")
        if self.gdn_chunk & (self.gdn_chunk - 1):
            raise ValueError("gdn_chunk is not a power of two")

    @property
    def d_ff_held(self) -> int:
        return self.d_ff

    @property
    def gdn_conv_dim(self) -> int:
        """Channels the convolution runs over: ``[q | k | v]``."""
        return (2 * self.gdn_key_heads * self.gdn_key_dim
                + self.gdn_value_heads * self.gdn_value_dim)

    def serving(self):
        from ray_tpu.llm.hybrid_kv import HybridServing

        return HybridServing(self, init_params)


QWEN3_NEXT_PRESETS: dict[str, Qwen3NextConfig] = {
    # CPU-test scale: one whole period, the published switches.
    "qwen3_next_tiny": Qwen3NextConfig(
        vocab_size=256, d_model=64, pattern=sublayers(4, 4), n_heads=4,
        n_kv_heads=2, head_dim=16, rotary_dim=4, gdn_key_heads=2,
        gdn_value_heads=4, gdn_key_dim=8, gdn_value_dim=16, gdn_chunk=8,
        num_experts=8, top_k=3, d_ff=32, shared_d_ff=48,
        dense_expert_rows=8, max_seq=256, dtype=jnp.float32,
    ),
}


# ------------------------------------------------------------ parameters
@partial(jax.jit, static_argnames="cfg")
def _init_gdn(key, cfg: Qwen3NextConfig) -> Params:
    """A Gated DeltaNet mixer's tree. ``W_qkvz`` is held as four plain
    blocks ``[q | k | v | z]`` and ``W_ba`` as two (assumed: the
    checkpoint's own interleaving is a permutation of random columns).
    ``A_log`` and ``dt_bias`` by the convention of the family's
    recurrences here (`nemotron_h._init_block`): A in [1, 16], the step
    log-uniform in [time_step_min, time_step_max] through softplus's
    inverse."""
    d, dt, k = cfg.d_model, cfg.dtype, cfg.conv_kernel
    hv = cfg.gdn_value_heads
    d_value = hv * cfg.gdn_value_dim
    keys = jax.random.split(key, 6)
    step = jnp.exp(
        jax.random.uniform(keys[2], (hv,))
        * (math.log(cfg.time_step_max) - math.log(cfg.time_step_min))
        + math.log(cfg.time_step_min)
    )
    step = jnp.maximum(step, cfg.time_step_floor)
    bound = k**-0.5
    return {
        "norm": jnp.zeros((d,), jnp.float32),
        "in_proj": _normal(keys[0], (d, cfg.gdn_conv_dim + d_value), d, dt),
        "ba_proj": _normal(keys[1], (d, 2 * hv), d, dt),
        "conv_w": jax.random.uniform(
            keys[3], (k, cfg.gdn_conv_dim), jnp.float32, -bound, bound
        ),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "A_log": jnp.log(
            jax.random.uniform(keys[4], (hv,), minval=1.0, maxval=16.0)
        ),
        "gate_norm": jnp.ones((cfg.gdn_value_dim,), jnp.float32),
        "out_proj": _normal(keys[5], (d_value, d), d_value, dt),
    }


@partial(jax.jit, static_argnames="cfg")
def _init_attention(key, cfg: Qwen3NextConfig) -> Params:
    """`nemotron_h._init_block`'s attention tree with ``wq`` as wide as
    each head's query and gate, and the two per-head norms."""
    dh = cfg.head_dim
    p = _init_block(key, kind="*", cfg=cfg)
    p["wq"] = _normal(
        jax.random.fold_in(key, 1),
        (cfg.d_model, cfg.n_heads * 2 * dh), cfg.d_model, cfg.dtype,
    )
    p["q_norm"] = jnp.zeros((dh,), jnp.float32)
    p["k_norm"] = jnp.zeros((dh,), jnp.float32)
    return p


@partial(jax.jit, static_argnames="cfg")
def _init_experts(key, cfg: Qwen3NextConfig) -> Params:
    """`granite_hybrid._init_experts` and the shared expert's gate
    ``w_s: d -> 1``."""
    p = granite_hybrid._init_experts(key, cfg=cfg)
    p["shared_expert_gate"] = _normal(
        jax.random.fold_in(key, 1), (cfg.d_model, 1), cfg.d_model, cfg.dtype
    )
    return p


_INIT = {"G": _init_gdn, "*": _init_attention, "E": _init_experts}


def init_params(key: jax.Array, cfg: Qwen3NextConfig) -> Params:
    """The tree as it is held, one tree a SUBLAYER in ``cfg.pattern``'s
    order (matmul weights in ``cfg.dtype``; router, norms, convolution
    and the per-head ``A_log`` and ``dt_bias`` in float32), a program a
    sublayer as ``nemotron_h.init_params``. The head is its own matrix."""
    if cfg.tie_word_embeddings:
        raise ValueError("models/qwen3_next.py holds an untied head")
    params = _init_ends(jax.random.fold_in(key, len(cfg.pattern)), cfg=cfg)
    params["blocks"] = tuple(
        _INIT[kind](jax.random.fold_in(key, i), cfg=cfg)
        for i, kind in enumerate(cfg.pattern)
    )
    return params


# -------------------------------------------------------- Gated DeltaNet
def _project_in(u, p, cfg):
    """``[q k v | z] = u W_qkvz`` (widths conv_dim | value width) and
    ``[b | a] = u W_ba`` (a head each), both left in float32 as the
    matmul unit accumulates them: what feeds the rule is not rounded to
    bfloat16 on the way."""
    with jax.named_scope("gdn:in_proj"):
        qkvz = jnp.dot(u, p["in_proj"], preferred_element_type=jnp.float32)
        qkv, z = jnp.split(qkvz, [cfg.gdn_conv_dim], axis=-1)
        ba = jnp.dot(u, p["ba_proj"], preferred_element_type=jnp.float32)
        return qkv, z, ba


def _unit(x):
    """x over its length (float32; eps inside the root)."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + _L2_EPS)


def _split_qkv(act, cfg):
    """silu'd convolution output [.., conv_dim] -> q, k [.., Hk, dk]
    (unit length, q times dk^-0.5) and v [.., Hk, Hv / Hk, dv]: value
    heads as (their key head, which of its heads)."""
    hk, dk = cfg.gdn_key_heads, cfg.gdn_key_dim
    lead = act.shape[:-1]
    q, k, v = jnp.split(act, [hk * dk, 2 * hk * dk], axis=-1)
    q = _unit(q.reshape(*lead, hk, dk)) * dk**-0.5
    k = _unit(k.reshape(*lead, hk, dk))
    return q, k, v.reshape(*lead, hk, -1, cfg.gdn_value_dim)


def _gates(ba, p, cfg):
    """``beta = sigmoid(b)`` and ``g = -exp(A_log) softplus(a + dt_bias)``
    (the log of the decay), float32 [.., Hk, Hv / Hk]."""
    lead = ba.shape[:-1]
    b_raw, a_raw = jnp.split(ba, 2, axis=-1)
    beta = jax.nn.sigmoid(b_raw)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a_raw + p["dt_bias"])
    shape = (*lead, cfg.gdn_key_heads, -1)
    return beta.reshape(shape), g.reshape(shape)


def _project_out(o, z, p, cfg):
    """``RMSNorm(o) * w * silu(z)`` over each value head, then ``W_out``.
    o, z: [.., value width]."""
    with jax.named_scope("gdn:out"):
        lead = o.shape[:-1]
        heads = o.reshape(*lead, cfg.gdn_value_heads, cfg.gdn_value_dim)
        var = jnp.mean(heads * heads, axis=-1, keepdims=True)
        normed = heads * jax.lax.rsqrt(var + cfg.norm_eps) * p["gate_norm"]
        gated = normed.reshape(*lead, -1) * jax.nn.silu(z.astype(jnp.float32))
        return gated.astype(cfg.dtype) @ p["out_proj"]


def _unit_lower_inverse(lower):
    """``(I + L)^-1`` for strictly lower-triangular ``L`` [.., C, C], C a
    power of two, float32 at full matmul precision. By halves: the
    inverse of ``[[A, 0], [M, B]]`` is ``[[A^-1, 0], [-B^-1 M A^-1,
    B^-1]]``, from blocks of one row (whose inverse is 1) up, log2(C)
    rounds of batched products and no loop over rows. (The series ``I - L
    + L^2 - ...`` is products too, but its terms grow to 1e9 where the
    keys of a chunk are alike and cancel; this does not form them.)"""
    c = lower.shape[-1]
    lead = lower.shape[:-2]
    inv = jnp.ones((*lead, c, 1, 1), lower.dtype)  # blocks of one row
    size = 1
    while size < c:
        pairs = c // (2 * size)
        # The diagonal blocks of twice the size, [.., pairs, 2s, 2s].
        tiles = lower.reshape(*lead, pairs, 2 * size, pairs, 2 * size)
        tiles = jnp.moveaxis(
            jnp.diagonal(tiles, axis1=-4, axis2=-2), -1, -3
        )
        m = tiles[..., size:, :size]
        halves = inv.reshape(*lead, pairs, 2, size, size)
        a_inv, b_inv = halves[..., 0, :, :], halves[..., 1, :, :]
        corner = -jnp.matmul(
            jnp.matmul(b_inv, m, precision=_HIGHEST), a_inv,
            precision=_HIGHEST,
        )
        inv = jnp.concatenate([
            jnp.concatenate([a_inv, jnp.zeros_like(a_inv)], axis=-1),
            jnp.concatenate([corner, b_inv], axis=-1),
        ], axis=-2)
        size *= 2
    return inv.reshape(*lead, c, c)


def _chunked_rule(q, k, v, beta, g, state0, size):
    """`gdn_chunked`'s rule in XLA's own operations, between the gates
    and ``o``: q, k [T, Hk, dk], v [T, Hk, r, dv], beta and g [T, Hk, r]
    (0 where a token takes no step), state0 [Hk, r, dk, dv], chunks of
    ``size``. Returns (o [T, value width], the state after the last
    token). The path off the TPU and the oracle of
    ``ops/pallas/gdn_chunk.py``, which is the same rule on one."""
    t = q.shape[0]
    n = -(-t // size)
    padded = n * size

    def chunks(a):
        """[T, Hk, ..] -> [n, Hk, .., C, last]: chunked, head-major,
        time and the head's own dimension minor."""
        a = jnp.pad(a, ((0, padded - t),) + ((0, 0),) * (a.ndim - 1))
        a = a.reshape(n, size, *a.shape[1:])
        return jnp.moveaxis(a, 1, -2)

    q_c, k_c = chunks(q), chunks(k)  # [n, Hk, C, dk]
    v_c = chunks(v)  # [n, Hk, r, C, dv]
    beta_c = chunks(beta[..., None])  # [n, Hk, r, C, 1]
    gamma = jnp.cumsum(chunks(g[..., None])[..., 0], axis=-1)  # [n,Hk,r,C]
    diff = gamma[..., :, None] - gamma[..., None, :]  # [n, Hk, r, C, C]
    causal = jnp.tril(jnp.ones((size, size), bool))
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    grow = jnp.exp(gamma)[..., None]  # [n, Hk, r, C, 1]
    to_end = jnp.exp(gamma[..., -1:] - gamma)[..., None]
    whole = jnp.exp(gamma[..., -1])  # [n, Hk, r]

    kk = jnp.einsum("nhik,nhjk->nhij", k_c, k_c, precision=_HIGHEST)
    strict = jnp.tril(jnp.ones((size, size), bool), -1)
    lower = jnp.where(strict, beta_c * kk[:, :, None] * decay, 0.0)
    solve = _unit_lower_inverse(lower)  # [n, Hk, r, C, C]
    k_r = k_c[:, :, None]  # [n, Hk, 1, C, dk]: a key head's value heads
    # [n, Hk, r, C, dv] and [n, Hk, r, C, dk]
    u_c = jnp.matmul(solve, beta_c * v_c, precision=_HIGHEST)
    w_c = jnp.matmul(solve, beta_c * grow * k_r, precision=_HIGHEST)
    qk = jnp.einsum("nhik,nhjk->nhij", q_c, k_c, precision=_HIGHEST)
    within = qk[:, :, None] * decay  # [n, Hk, r, C, C]
    q_grown = q_c[:, :, None] * grow
    k_end = k_r * to_end

    def carry(state, chunk):
        u_i, w_i, within_i, q_i, k_i, whole_i = chunk
        v_new = u_i - jnp.matmul(w_i, state, precision=_HIGHEST)
        out = jnp.matmul(q_i, state, precision=_HIGHEST) + jnp.matmul(
            within_i, v_new, precision=_HIGHEST
        )  # [Hk, r, C, dv]
        state = state * whole_i[..., None, None] + jnp.einsum(
            "hrck,hrcv->hrkv", k_i, v_new, precision=_HIGHEST
        )
        return state, out

    end, o = jax.lax.scan(
        carry, state0, (u_c, w_c, within, q_grown, k_end, whole)
    )
    # [n, Hk, r, C, dv] -> [T, value width]
    o = jnp.moveaxis(o, -2, 1).reshape(padded, -1)[:t]
    return o, end


def gdn_chunked(u, p, cfg: Qwen3NextConfig, state0, conv0, length):
    """The Gated DeltaNet mixer over many tokens of one sequence, with
    `nemotron_h.mamba_chunked`'s contract.

    u [T, d] (normed input); state0 [Hv, dk, dv] float32 and conv0
    [K - 1, conv_dim] the state before u[0]; ``length`` (traced) how many
    of the T tokens are real. Returns (out [T, d], the state and the
    convolution tail after token ``length - 1``). Positions from
    ``length`` on take no step (``beta`` 0 and no decay there), so they
    leave the state as it is; their own outputs mean nothing.

    The chunked form (the WY representation of arXiv:2406.06484 with
    the decay of arXiv:2412.06464 folded in). Within a chunk of C
    tokens, with ``gamma_i`` the running sum of ``g`` and ``D_ij =
    exp(gamma_i - gamma_j)`` for ``i >= j``: every token's correction
    ``d_i`` depends on the ones before it through ``(I + L) d = beta (V
    - exp(gamma) K S_0)``, ``L = strict_lower(beta K K^T * D)``, which
    one triangular inverse ``T`` solves for any start state: ``d = U - W
    S_0`` with ``U = T beta V`` and ``W = T (beta exp(gamma) K)``. Then
    chunk by chunk, the state ``S`` carried (a scan over T / C chunks,
    not over tokens): ``V' = U - W S``, ``O = (Q exp(gamma)) S +
    lower(Q K^T * D) V'``, ``S <- exp(gamma_C) S + (K exp(gamma_C -
    gamma))^T V'``. Every decay is an exponential of a difference that
    is <= 0. Everything from the projection's output on is float32,
    and every product of the rule runs at full float32 precision (on a
    TPU six bf16 passes): with operands as the matmul unit takes them by
    default (one bf16 pass) the state after 2,048 tokens lies 0.42% of
    its norm from the recurrence's, at full precision 0.09%, where a
    state ROUNDED to bfloat16 every token lies 0.97% off, so only the
    second can tell a float32 state from a bfloat16 one; the mixer
    alone takes 4.68 ms for 3.39 at 2,048 tokens, a tenth of a chunk
    program (v5e, my chip run, PR 51; `benchmarks/models/qwen3_next.py`
    FIRST_STATE_TOLERANCE is the limit that rests on it).

    On a TPU the rule, between the gates and ``o``, is one call of
    ``ops/pallas/gdn_chunk.py`` (the same arithmetic with nothing of it
    in HBM: 2.35 ms the mixer, PR 58); elsewhere `_chunked_rule`, XLA's
    form. The platform decides, as it does for ``moe_ffn``'s kernels.
    """
    t = u.shape[0]
    hk, dk, dv = cfg.gdn_key_heads, cfg.gdn_key_dim, cfg.gdn_value_dim
    size = min(cfg.gdn_chunk, 1 << (t - 1).bit_length())
    qkv, z, ba = _project_in(u, p, cfg)

    with jax.named_scope("gdn:conv"):
        kernel = cfg.conv_kernel
        seq = jnp.concatenate([conv0.astype(qkv.dtype), qkv], axis=0)
        conv = sum(
            seq[j: j + t].astype(jnp.float32) * p["conv_w"][j]
            for j in range(kernel)
        )
        # Row i of `seq` is the input at position i - (K - 1).
        conv_end = jax.lax.dynamic_slice_in_dim(seq, length, kernel - 1, axis=0)
        q, k, v = _split_qkv(jax.nn.silu(conv), cfg)

    with jax.named_scope("gdn:scan"):
        beta, g = _gates(ba, p, cfg)  # [T, Hk, r]
        live = (jnp.arange(t) < length)[:, None, None]
        beta = jnp.where(live, beta, 0.0)
        g = jnp.where(live, g, 0.0)
        start = state0.reshape(hk, -1, dk, dv)
        # Chosen by the platform alone, as `moe_ffn` chooses its kernels.
        if chip.platform() == "tpu":
            o, end = gdn_chunk_rule(
                q, k, v, beta, g, start, length, chunk=size
            )
        else:
            o, end = _chunked_rule(q, k, v, beta, g, start, size)
    out = _project_out(o, z, p, cfg)
    return out, end.reshape(state0.shape), conv_end.astype(conv0.dtype)


def _step_operands(u, p, cfg, conv):
    """What one token's state update takes, of u [B, d] and the tail
    conv [B, K - 1, conv_dim]: z, ``[b | a]``, q and k [B, Hk, dk], v
    [B, Hk, r, dv], the window [B, K, conv_dim] whose last K - 1 rows
    are the next tail."""
    qkv, z, ba = _project_in(u, p, cfg)
    with jax.named_scope("gdn:conv"):
        window = jnp.concatenate(
            [conv, qkv[:, None].astype(conv.dtype)], axis=1
        )  # [B, K, conv_dim]
        out = (window.astype(jnp.float32) * p["conv_w"][None]).sum(1)
        q, k, v = _split_qkv(jax.nn.silu(out), cfg)
    return z, ba, q, k, v, window


def gdn_step(u, p, cfg: Qwen3NextConfig, state, conv):
    """The mixer for ONE token of each of B sequences: u [B, d], state
    [B, Hv, dk, dv] float32, conv [B, K - 1, conv_dim]. Returns (out
    [B, d], state, conv) after the token. The rule as it is defined, in
    float32 elementwise arithmetic: no product rounds the state."""
    bsz = u.shape[0]
    hk, dk, dv = cfg.gdn_key_heads, cfg.gdn_key_dim, cfg.gdn_value_dim
    z, ba, q, k, v, window = _step_operands(u, p, cfg, conv)

    with jax.named_scope("gdn:update"):
        beta, g = _gates(ba, p, cfg)  # [B, Hk, r]
        s = state.reshape(bsz, hk, -1, dk, dv)
        s = s * jnp.exp(g)[..., None, None]
        k_col = k[:, :, None, :, None]  # [B, Hk, 1, dk, 1]
        read = (s * k_col).sum(-2)  # S^T k: [B, Hk, r, dv]
        delta = beta[..., None] * (v - read)
        s = s + k_col * delta[..., None, :]
        o = (s * q[:, :, None, :, None]).sum(-2)  # [B, Hk, r, dv]
    out = _project_out(o.reshape(bsz, -1), z, p, cfg)
    return out, s.reshape(state.shape), window[:, 1:]


def gdn_step_live(u, p, cfg: Qwen3NextConfig, stack, layer, conv, order,
                  count):
    """`gdn_step` on a TPU, for the slots that decode: ``stack`` [L, B,
    Hv, dk, dv] is every layer's state, of which ``stack[layer]`` is
    stepped IN PLACE for the first ``count`` slots of ``order``
    (``ops/pallas/state_step.py live_order``) and no other slot's state
    is read or written: the masked write-back is the kernel's. Returns
    (out [B, d], the stack, conv after the token); a slot that does not
    decode gets the ``out`` of ``o = 0`` (finite, and dropped)."""
    bsz = u.shape[0]
    z, ba, q, k, v, window = _step_operands(u, p, cfg, conv)
    with jax.named_scope("gdn:update"):
        beta, g = _gates(ba, p, cfg)  # [B, Hk, r]
        stack, o = gdn_state_step(
            stack, layer, order, count, jnp.exp(g).reshape(bsz, -1),
            beta.reshape(bsz, -1), q, k, v.reshape(bsz, -1, v.shape[-1]),
        )
    out = _project_out(o.reshape(bsz, -1), z, p, cfg)
    return out, stack, window[:, 1:]
