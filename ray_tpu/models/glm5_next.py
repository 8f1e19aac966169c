"""GLM-5.3-Flash's language model (``model_type: glm5_next_text``): three
Kimi-Delta-Attention layers to one sparse latent-attention layer, four
residual streams mixed by manifold-constrained hyper-connections, a
dense FFN in the leading layers and sparse experts behind a clamped
SwiGLU in the rest.

Every layer is TWO sublayers ``F`` (a mixer, then an FFN). The carried
activation is ``X`` ``[n, d]`` a token, ``n = hc_mult`` streams:

- **The residual path (mHC, arXiv:2512.24880)**, every sublayer
  (``models/mhc.py`` `mhc_mix`, `mhc_spread`, which another family
  imports too): ``x~ = RMSNorm(vec(X))`` over all ``n d``
  numbers (eps ``hc_eps``, no weight); ``Hpre = sigmoid(a_pre (x~ P_pre)
  + b_pre)`` ``[n]``, ``Hpost = 2 sigmoid(a_post (x~ P_post) + b_post)``
  ``[n]``, ``Hres = Sinkhorn(exp(a_res mat(x~ P_res) + b_res))`` ``[n,
  n]``: ``hc_sinkhorn_iters`` rounds of dividing rows, then columns, by
  their sums (+ ``hc_eps``). ``h = Hpre X``, ``y = F(RMSNorm_l(h))``,
  ``X <- Hres X + Hpost^T y``. The embedding is copied to the n streams;
  the final norm and the head read their sum. ``x~``, the three ``H``
  and the mixes are float32; the streams are carried in ``cfg.dtype``.
- ``K``, **a KDA mixer** (Kimi Linear, arXiv:2510.26692; `kda_chunked`,
  `kda_step`): ``q, k, v = silu(conv(h W_q | W_k | W_v))`` (causal
  depthwise, ``conv_kernel`` taps, no bias); ``q``, ``k`` unit length a
  head, ``q`` times ``dk^-0.5``. A decay a key CHANNEL: ``g = lower *
  sigmoid(exp(A_log) (W_fb (W_fa h) + dt_bias))`` in ``[lower, 0]^dk`` a
  head; ``beta = sigmoid(W_b h)`` a head. With ``S`` ``[dk, dv]`` float32
  a head:

      S <- diag(exp(g)) S;  d = beta (v - S^T k);  S <- S + k d^T;  o = S^T q.

  Output ``W_o (RMSNorm_head(o) * sigmoid(W_gb (W_ga h)))``. One decay a
  head (``models/qwen3_next.py``'s rule) factors out of a chunk's ``Q
  K^T``; 128 a head do not, so the decays go INSIDE the products
  (`_kda_rule`; on a TPU ``ops/pallas/kda_chunk.py``, the same
  arithmetic with nothing of it in HBM).
- ``L``, **a sparse latent mixer** (DeepSeek Sparse Attention over
  multi-head latent attention without a rotary part; `dsa_prefill`,
  `dsa_decode`): ``cq = RMSNorm(h W_qa)``, ``q_j = cq W_qb``; ``c =
  RMSNorm(h W_kva)`` is the cached cell; ``k_j = c W_uk,j``, ``v_j = c
  W_uv,j``; scores ``q_j . k_j * qk_head_dim^-0.5``, soft-max over the
  SELECTED keys, ``W_o``. Here in the absorbed form (``qa_j = q_j
  W_uk,j^T`` against the cells as they lie, ``W_uv,j`` after the sum): no
  key or value is made for a cached token. The indexer: ``qI_j = rope(cq
  W_Iq)`` ``[Hi, Di]``, ``kI_s = rope(LayerNorm(h_s W_Ik))``, ``w_t = h_t
  W_Iw`` ``[Hi]``; block ``b`` holds positions ``pool b .. pool b + pool
  - 1`` (``pool = index_kpool``) and its pooled key is the mean of its
  ``kI``; ``I_{t,b} = sum_j w_{t,j} relu(qI_{t,j} . kbar_b) (Hi
  Di)^-0.5`` over the COMPLETE blocks before the one that holds ``t``;
  the query attends every position ``<= t`` of its own block and every
  position of the ``index_topk / pool`` blocks of largest ``I`` (all of
  them where there are fewer).
- ``D`` / ``E``: a dense FFN, or ``models/moe.py``'s ``moe_ffn`` (sigmoid
  scores, ``router_bias`` for the choice only, gates renormalised times
  ``routed_scaling_factor``, one shared expert ungated), each ``W_down
  (silu(min(h W_gate, l)) * clip(h W_up, -l, l))``, ``l = swiglu_limit``.

The sizes are those of zai-org/GLM-5.3-Flash, the public model the
benchmark serves through this file. The config subclasses
``NemotronHConfig`` for the reason ``models/granite_hybrid.py`` gives
(ROADMAP D9's rename of that class would say so in its name; it is not
needed for this and is left to its own PR). NOT HERE: the
multi-token-prediction module, the vision tower, a backward pass.

ASSUMED (the config names these and no paper spells them out;
``benchmarks/configs/glm53flash-serve1.json`` lists each with its
reason): the gate's bounded form and the ranks of ``W_fa``, ``W_ga``;
mean pooling, top-k counted in tokens, the indexer's rotary width, base
and interleaved pairs, ``cq`` shared with the main query; the clamp's
form; the residual path's entry copy and exit sum and its initial
``a``, ``b``.

A norm's weight is stored as ``scale`` and applied as ``1 + scale``
(``ops/norms.py``), as everywhere in the repo; the norm of a KDA head
holds its weight plain, and the indexer's LayerNorm a plain weight and
bias.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import ClassVar

import jax
import jax.numpy as jnp

from ray_tpu._private import chip
from ray_tpu.models import granite_hybrid, laguna
from ray_tpu.models.mhc import init_hc
from ray_tpu.models.nemotron_h import (
    NemotronHConfig,
    Params,
    _init_ends,
    _normal,
)
from ray_tpu.models.qwen3_next import _L2_EPS, _unit, _unit_lower_inverse
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.pallas.kda_chunk import kda_chunk_rule
from ray_tpu.ops.pallas.state_step import kda_state_step

_HIGHEST = jax.lax.Precision.HIGHEST
_NEG_INF = -1e30
# Sub-chunks of the chunked rule: the decays inside its products are
# referred to the middle of a sub-chunk (`_kda_rule`), so an exponent
# is at most 16 * |kda_lower| / 2 = 40 either way and float32's exp
# holds 88. 16 and 32 measured the same (17.23 against 17.14 ms a mixer
# at chunk 64: my chip run, PR 59).
_KDA_SUBCHUNK = 16
# Queries of a prefill chunk whose selected cells are gathered and
# attended at once (`dsa_prefill`): the gather is this x index_topk x
# kv_lora_rank cells (134 MB in bf16) and the float32 scores n_heads x
# this x index_topk (34 MB). The mixer alone as the last chunk of a 16k
# / 64k context, on a v5e (my chip run, PR 59): 33.8 / 65.0 ms at 64,
# 36.3 / 65.6 at 128, 41.0 / 69.2 at 256.
_DSA_QUERY_BLOCK = 64
# Pooled keys a prefill chunk's indexer scores at once: the float32
# products are chunk x index_heads x this (2,048 x 32 x 1,024: 268 MB).
_DSA_INDEX_BLOCK = 1024

# `layer_types` and `mlp_layer_types` of the published model: layer l is
# sparse latent attention where (l + 1) % 4 == 0, else KDA; the first
# three layers' FFN is dense.
_KINDS = {"linear_attention": "K", "deepseek_sparse_attention": "L"}
_FFNS = {"dense": "D", "sparse": "E"}


def sublayers(layer_types, mlp_layer_types) -> str:
    """``pattern`` for the layers listed: each layer's mixer (``K`` or
    ``L``), then its FFN (``D`` or ``E``)."""
    return "".join(
        _KINDS[kind] + _FFNS[ffn]
        for kind, ffn in zip(layer_types, mlp_layer_types, strict=True)
    )


def published_layers(n_layers: int = 45, every: int = 4, dense: int = 3):
    kinds = [
        "deepseek_sparse_attention" if (layer + 1) % every == 0
        else "linear_attention" for layer in range(n_layers)
    ]
    return kinds, ["dense"] * dense + ["sparse"] * (n_layers - dense)


@dataclasses.dataclass(frozen=True)
class Glm5NextConfig(NemotronHConfig):
    vocab_size: int = 154880  # rows held, where the vocabulary is sliced
    d_model: int = 4096
    pattern: str = sublayers(*published_layers())
    norm_eps: float = 1e-5
    # The residual path: `hc_mult` streams (`NemotronHConfig`'s field,
    # on here), Sinkhorn's rounds and the eps of both.
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    # KDA blocks (`linear_attn_config`)
    kda_heads: int = 64
    kda_head_dim: int = 128  # dk = dv
    kda_gate_rank: int = 128  # of W_fa and W_ga: assumed, Kimi Linear's
    kda_lower: float = -5.0  # `gate_lower_bound`
    # Tokens the chunked rule takes at once (`_kda_rule`, in sub-chunks
    # of `_KDA_SUBCHUNK`). The mixer alone over 2,048 tokens at these
    # widths, on a v5e (my chip run, PR 59; the rule 9.5-10.0 ms of it):
    # 16.34 ms at 32, 17.23 at 64, 20.48 at 128.
    kda_chunk: int = 32
    # Sparse latent blocks: the heads are n_heads of qk_head_dim (no
    # rotary part) and v_head_dim.
    n_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_head_dim: int = 256
    v_head_dim: int = 256
    index_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048  # in tokens: index_topk // index_kpool blocks
    index_kpool: int = 4
    index_rotary_dim: int = 64  # assumed: the family's 64
    index_rope_theta: float = 10000.0  # assumed
    dense_d_ff: int = 12288
    num_experts: int = 288
    top_k: int = 8
    d_ff: int = 2048
    shared_d_ff: int = 2048
    routed_scaling_factor: float = 2.5
    router_kind: str = "sigmoid"
    expert_kind: str = "swiglu"
    swiglu_limit: float | None = 10.0
    # Up to this many rows every held expert that got a row is applied to
    # every row (a decode step's 16), above it pairs are sorted into
    # grouped matmuls (a 2,048-token chunk's 16,384 pairs): the other
    # sparse families' boundary, whose calls have the same two sizes.
    dense_expert_rows: int = 256
    max_seq: int = 1048576

    block_kinds: ClassVar[str] = "KLDE"

    def __post_init__(self):
        super().__post_init__()
        if set(self.pattern[::2]) - set("KL") or set(self.pattern[1::2]) - set("DE"):
            raise ValueError(
                f"pattern {self.pattern!r}: a layer is its mixer (K or L) "
                "and then its FFN (D or E)"
            )
        if self.kda_chunk & (self.kda_chunk - 1):
            raise ValueError("kda_chunk is a power of two")
        if _KDA_SUBCHUNK * -self.kda_lower / 2 > 85.0:
            raise ValueError(
                f"{_KDA_SUBCHUNK} * |kda_lower| / 2 passes what float32's "
                "exp holds"
            )
        if self.index_topk % self.index_kpool:
            raise ValueError("index_kpool does not divide index_topk")

    @property
    def d_ff_held(self) -> int:
        return self.d_ff

    @property
    def kda_conv_dim(self) -> int:
        """Channels the convolution runs over: ``[q | k | v]``."""
        return 3 * self.kda_heads * self.kda_head_dim

    @property
    def index_blocks(self) -> int:
        """Blocks a query selects: ``index_topk`` counted in tokens."""
        return self.index_topk // self.index_kpool

    def serving(self):
        from ray_tpu.llm.hybrid_kv import HybridServing

        return HybridServing(self, init_params)

    def _sublayer_params(self) -> dict:
        d, n = self.d_model, self.hc_mult
        hc = n * d * (2 * n + n * n) + 3 + 2 * n + n * n
        hk = self.kda_heads * self.kda_head_dim
        rank = self.kda_gate_rank
        heads = self.n_heads
        return {
            "K": (d + d * self.kda_conv_dim + self.conv_kernel * self.kda_conv_dim
                  + 2 * (d * rank + rank * hk) + d * self.kda_heads
                  + self.kda_heads + hk + self.kda_head_dim + hk * d + hc),
            "L": (d + d * self.q_lora_rank + self.q_lora_rank
                  + self.q_lora_rank * heads * self.qk_head_dim
                  + d * self.kv_lora_rank + self.kv_lora_rank
                  + heads * self.kv_lora_rank
                  * (self.qk_head_dim + self.v_head_dim)
                  + heads * self.v_head_dim * d
                  + self.q_lora_rank * self.index_heads * self.index_head_dim
                  + d * self.index_head_dim + 2 * self.index_head_dim
                  + d * self.index_heads + hc),
            "D": d + 3 * d * self.dense_d_ff + hc,
            "E": (d + d * self.num_experts + self.num_experts
                  + self.n_experts_held * 3 * d * self.d_ff
                  + 3 * d * self.shared_d_ff + hc),
        }

    def num_params(self) -> int:
        """Parameters of the tree as `init_params` makes it for this
        config (held experts and held vocabulary rows). The published
        model whole: 313.2B, and its unserved multi-token-prediction
        module beside them."""
        per = self._sublayer_params()
        return (
            sum(self.count(kind) * n for kind, n in per.items())
            + 2 * self.vocab_size * self.d_model + self.d_model
        )


GLM5_NEXT_PRESETS: dict[str, Glm5NextConfig] = {
    # CPU-test scale: one leading dense layer and two whole periods, the
    # published switches; a query picks 4 blocks of 2 positions.
    "glm5_next_tiny": Glm5NextConfig(
        vocab_size=256, d_model=64,
        pattern="KD" + "LE" + "KEKEKE" + "LE" + "KE",
        kda_heads=4, kda_head_dim=16, kda_gate_rank=8, n_heads=4, q_lora_rank=32, kv_lora_rank=16,
        qk_head_dim=16, v_head_dim=16, index_heads=2, index_head_dim=16,
        index_topk=8, index_kpool=2, index_rotary_dim=8,
        dense_d_ff=96, num_experts=8,
        top_k=3, d_ff=32, shared_d_ff=48, dense_expert_rows=8, max_seq=256,
        dtype=jnp.float32,
    ),
}


# ------------------------------------------------------------ parameters
@partial(jax.jit, static_argnames="cfg")
def _init_kda(key, cfg: Glm5NextConfig) -> Params:
    """A KDA mixer's tree. ``W_q | W_k | W_v`` is held as one matrix of
    three plain blocks. ``A_log`` a head and ``dt_bias`` a channel by the
    convention of the family's recurrences here
    (`qwen3_next._init_gdn`): A in [1, 16], the step log-uniform in
    [time_step_min, time_step_max] through softplus's inverse."""
    d, dt, k = cfg.d_model, cfg.dtype, cfg.conv_kernel
    h, dk, rank = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_gate_rank
    keys = jax.random.split(key, 11)
    step = jnp.exp(
        jax.random.uniform(keys[0], (h, dk))
        * (math.log(cfg.time_step_max) - math.log(cfg.time_step_min))
        + math.log(cfg.time_step_min)
    )
    step = jnp.maximum(step, cfg.time_step_floor)
    bound = k**-0.5
    return {
        "norm": jnp.zeros((d,), jnp.float32),
        "in_proj": _normal(keys[1], (d, cfg.kda_conv_dim), d, dt),
        "conv_w": jax.random.uniform(
            keys[2], (k, cfg.kda_conv_dim), jnp.float32, -bound, bound
        ),
        "f_a": _normal(keys[3], (d, rank), d, dt),
        "f_b": _normal(keys[4], (rank, h * dk), rank, dt),
        "g_a": _normal(keys[5], (d, rank), d, dt),
        "g_b": _normal(keys[6], (rank, h * dk), rank, dt),
        "b_proj": _normal(keys[7], (d, h), d, dt),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "A_log": jnp.log(
            jax.random.uniform(keys[8], (h,), minval=1.0, maxval=16.0)
        ),
        "gate_norm": jnp.ones((dk,), jnp.float32),
        "out_proj": _normal(keys[9], (h * dk, d), h * dk, dt),
        "hc": init_hc(keys[10], cfg),
    }


@partial(jax.jit, static_argnames="cfg")
def _init_dsa(key, cfg: Glm5NextConfig) -> Params:
    """A sparse latent mixer's tree: the two low-rank paths with their
    norms, the heads' up-projections ``w_uk`` / ``w_uv`` ``[H, rank,
    .]``, ``W_o``, and the indexer's three matrices and LayerNorm."""
    d, dt = cfg.d_model, cfg.dtype
    h, rq, rkv = cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank
    hi, di = cfg.index_heads, cfg.index_head_dim
    keys = jax.random.split(key, 10)
    return {
        "attn_norm": jnp.zeros((d,), jnp.float32),
        "wq_a": _normal(keys[0], (d, rq), d, dt),
        "q_norm": jnp.zeros((rq,), jnp.float32),
        "wq_b": _normal(keys[1], (rq, h * cfg.qk_head_dim), rq, dt),
        "wkv_a": _normal(keys[2], (d, rkv), d, dt),
        "kv_norm": jnp.zeros((rkv,), jnp.float32),
        "w_uk": _normal(keys[3], (h, rkv, cfg.qk_head_dim), rkv, dt),
        "w_uv": _normal(keys[4], (h, rkv, cfg.v_head_dim), rkv, dt),
        "wo": _normal(keys[5], (h * cfg.v_head_dim, d), h * cfg.v_head_dim, dt),
        "index_q": _normal(keys[6], (rq, hi * di), rq, dt),
        "index_k": _normal(keys[7], (d, di), d, dt),
        "index_k_norm": jnp.ones((di,), jnp.float32),
        "index_k_bias": jnp.zeros((di,), jnp.float32),
        "index_w": _normal(keys[8], (d, hi), d, dt),
        "hc": init_hc(keys[9], cfg),
    }


@partial(jax.jit, static_argnames="cfg")
def _init_dense(key, cfg: Glm5NextConfig) -> Params:
    p = laguna._init_dense(key, cfg=cfg)
    p["hc"] = init_hc(jax.random.fold_in(key, 1), cfg)
    return p


@partial(jax.jit, static_argnames="cfg")
def _init_experts(key, cfg: Glm5NextConfig) -> Params:
    """`granite_hybrid._init_experts` and the choice's ``router_bias``
    (``e_score_correction_bias``: zero, as a fresh model's)."""
    p = granite_hybrid._init_experts(key, cfg=cfg)
    p["router_bias"] = jnp.zeros((cfg.num_experts,), jnp.float32)
    p["hc"] = init_hc(jax.random.fold_in(key, 1), cfg)
    return p


_INIT = {"K": _init_kda, "L": _init_dsa, "D": _init_dense, "E": _init_experts}


def init_params(key: jax.Array, cfg: Glm5NextConfig) -> Params:
    """The tree as it is held, one tree a SUBLAYER in ``cfg.pattern``'s
    order (matmul weights in ``cfg.dtype``; router, norms, convolution,
    the residual mixing and the per-head ``A_log`` and ``dt_bias`` in
    float32), a program a sublayer as ``nemotron_h.init_params``. The
    head is its own matrix."""
    if cfg.tie_word_embeddings:
        raise ValueError("models/glm5_next.py holds an untied head")
    params = _init_ends(jax.random.fold_in(key, len(cfg.pattern)), cfg=cfg)
    params["blocks"] = tuple(
        _INIT[kind](jax.random.fold_in(key, i), cfg=cfg)
        for i, kind in enumerate(cfg.pattern)
    )
    return params


# -------------------------------------------------- Kimi Delta Attention
def _kda_projections(u, p, cfg):
    """The mixer's matmuls of u [T, d] before the rule: ``[q | k | v]``
    [T, conv_dim] float32 as the matmul unit accumulates them, before
    the convolution; the decay's pre-activation ``low`` [T, H dk]
    (``W_fb (W_fa u)``, without ``dt_bias``); ``beta`` [T, H]; and the
    output gate's pre-activation [T, H dk], float32."""
    with jax.named_scope("kda:in"):
        f32 = partial(jnp.dot, preferred_element_type=jnp.float32)
        qkv = f32(u, p["in_proj"])
        # The decay's own path stays float32 behind its first product: an
        # error of the log-decay is one of the state's whole memory.
        low = jnp.dot(
            f32(u, p["f_a"]), p["f_b"].astype(jnp.float32), precision=_HIGHEST
        )
        beta = jax.nn.sigmoid(f32(u, p["b_proj"]))
        gate = f32(f32(u, p["g_a"]).astype(u.dtype), p["g_b"])
    return qkv, low, beta, gate


def _kda_in(u, p, cfg):
    """What the rule takes of u [T, d], before the convolution:
    `_kda_projections`'s with ``g`` [T, H, dk] (the log of the decay, in
    ``[lower, 0]``) in the place of its pre-activation."""
    qkv, low, beta, gate = _kda_projections(u, p, cfg)
    with jax.named_scope("kda:in"):
        g = _kda_decay(low, p, cfg)
    return qkv, g, beta, gate


def _kda_decay(low, p, cfg):
    """The log of the decay [T, H, dk], in ``[lower, 0]``, of its
    pre-activation low [T, H dk]."""
    raw = low.reshape(-1, cfg.kda_heads, cfg.kda_head_dim) + p["dt_bias"]
    return cfg.kda_lower * jax.nn.sigmoid(jnp.exp(p["A_log"])[:, None] * raw)


def _kda_split(act, cfg):
    """silu'd convolution output [.., conv_dim] -> q, k (unit length, q
    times dk^-0.5) and v, [.., H, dk] each."""
    h, dk = cfg.kda_heads, cfg.kda_head_dim
    q, k, v = jnp.split(act, 3, axis=-1)
    shape = (*act.shape[:-1], h, dk)
    return (_unit(q.reshape(shape)) * dk**-0.5, _unit(k.reshape(shape)),
            v.reshape(shape))


def _kda_gated(o, gate, p, cfg):
    """``RMSNorm_head(o) * w * sigmoid(gate)`` over each head: o [.., H,
    dk], gate [.., H dk] float32 -> [.., H dk] in ``cfg.dtype``."""
    var = jnp.mean(o * o, axis=-1, keepdims=True)
    normed = o * jax.lax.rsqrt(var + cfg.norm_eps) * p["gate_norm"]
    gated = normed.reshape(gate.shape) * jax.nn.sigmoid(gate)
    return gated.astype(cfg.dtype)


def _kda_out(o, gate, p, cfg):
    """`_kda_gated`, then ``W_o``."""
    with jax.named_scope("kda:out"):
        return _kda_gated(o, gate, p, cfg) @ p["out_proj"]


def _kda_rule(q, k, v, beta, g, state0, size: int, sub: int):
    """The chunked rule between the gates and ``o``: q, k, v [T, H, dk],
    beta [T, H], g [T, H, dk] (0 where a token takes no step, with beta
    0), state0 [H, dk, dv], chunks of ``size`` in sub-chunks of ``sub``.
    Returns (o [T, H, dv], the state after the last token).

    `qwen3_next.gdn_chunked`'s WY form with the decays inside the
    products. With ``G_i`` the running sum of ``g`` within a chunk (a
    vector over the key channels, <= 0): ``(I + L) d = beta (V - (K
    exp(G)) S_0)`` with ``L_ij = beta_i (k_i exp(G_i)) . (k_j exp(-G_j))``
    for ``j < i``, and ``o_i = (q_i exp(G_i)) S_0 + sum_{j <= i} ((q_i
    exp(G_i)) . (k_j exp(-G_j))) d_j``. ``exp(-G_j)`` alone overflows
    (64 tokens at -5 each: e^320), so each product is taken a sub-chunk
    of rows at a time against a reference point ``R_a``, the running sum
    at the MIDDLE of sub-chunk ``a``: rows ``i`` of ``a`` carry ``exp(G_i
    - R_a)`` and columns ``j`` carry ``exp(R_a - G_j)``, exponents of at
    most ``sub |lower| / 2`` (40) either way within the sub-chunk, so
    that neither factor leaves float32's normal range (a reference at
    the sub-chunk's start puts e^-80 on its last rows, and what of ``q``
    is a hundredth of its size is then flushed to zero); columns of the
    sub-chunks before ``a`` carry an exponent <= 0, columns of later ones
    (all masked) carry 0. Every product runs at full float32 precision,
    as the rule it extends."""
    t, heads, dk = q.shape
    n = -(-t // size)
    padded = n * size
    subs = size // sub

    def chunks(a):
        """[T, H, x] -> [n, H, C, x]."""
        a = jnp.pad(a, ((0, padded - t),) + ((0, 0),) * (a.ndim - 1))
        return jnp.moveaxis(a.reshape(n, size, *a.shape[1:]), 1, 2)

    q_c, k_c, v_c, g_c = chunks(q), chunks(k), chunks(v), chunks(g)
    beta_c = chunks(beta[..., None])  # [n, H, C, 1]
    gamma = jnp.cumsum(g_c, axis=2)  # [n, H, C, dk]
    # R_a: the running sum at the middle of sub-chunk a, [n, H, subs, 1, dk].
    before = gamma[:, :, max(sub // 2 - 1, 0)::sub][:, :, :, None]
    by_sub = lambda a: a.reshape(n, heads, subs, sub, dk)  # noqa: E731
    rows_decay = jnp.exp(by_sub(gamma) - before)  # [n, H, subs, sub, dk]
    # Columns for rows of sub-chunk a: [n, H, subs (a), C, dk].
    col_sub = jnp.arange(size) // sub
    reach = col_sub[None, :] <= jnp.arange(subs)[:, None]  # [subs, C]
    cols_decay = jnp.exp(jnp.where(
        reach[:, :, None], before - gamma[:, :, None], -jnp.inf
    ))
    k_cols = k_c[:, :, None] * cols_decay

    def scores(rows):
        """``(rows_i exp(G_i)) . (k_j exp(-G_j))`` [n, H, C, C]."""
        return jnp.einsum(
            "nhaik,nhajk->nhaij", by_sub(rows) * rows_decay, k_cols,
            precision=_HIGHEST,
        ).reshape(n, heads, size, size)

    grow = jnp.exp(gamma)  # [n, H, C, dk]
    whole = grow[:, :, -1]  # [n, H, dk]
    strict = jnp.tril(jnp.ones((size, size), bool), -1)
    lower = jnp.where(strict, beta_c * scores(k_c), 0.0)
    solve = _unit_lower_inverse(lower)  # [n, H, C, C]
    u_c = jnp.matmul(solve, beta_c * v_c, precision=_HIGHEST)
    w_c = jnp.matmul(solve, beta_c * grow * k_c, precision=_HIGHEST)
    causal = jnp.tril(jnp.ones((size, size), bool))
    within = jnp.where(causal, scores(q_c), 0.0)
    q_grown = q_c * grow
    k_end = k_c * jnp.exp(gamma[:, :, -1:] - gamma)

    def carry(state, chunk):
        u_i, w_i, within_i, q_i, k_i, whole_i = chunk
        v_new = u_i - jnp.matmul(w_i, state, precision=_HIGHEST)
        out = jnp.matmul(q_i, state, precision=_HIGHEST) + jnp.matmul(
            within_i, v_new, precision=_HIGHEST
        )  # [H, C, dv]
        state = state * whole_i[..., None] + jnp.einsum(
            "hck,hcv->hkv", k_i, v_new, precision=_HIGHEST
        )
        return state, out

    end, o = jax.lax.scan(
        carry, state0, (u_c, w_c, within, q_grown, k_end, whole)
    )
    # [n, H, C, dv] -> [T, H, dv]
    return jnp.moveaxis(o, 2, 1).reshape(padded, heads, -1)[:t], end


def _kda_conv(qkv, conv0, p, cfg, length):
    """The causal depthwise convolution of qkv [T, conv_dim] behind the
    K - 1 rows conv0 before it, ``silu`` and `_kda_split`: (q, k, v [T,
    H, dk], the K - 1 rows of input before position ``length``)."""
    t, kernel = qkv.shape[0], cfg.conv_kernel
    seq = jnp.concatenate([conv0.astype(qkv.dtype), qkv], axis=0)
    conv = sum(
        seq[j: j + t].astype(jnp.float32) * p["conv_w"][j]
        for j in range(kernel)
    )
    # Row i of `seq` is the input at position i - (K - 1).
    conv_end = jax.lax.dynamic_slice_in_dim(seq, length, kernel - 1, axis=0)
    return _kda_split(jax.nn.silu(conv), cfg), conv_end


def _conv_tail(qkv, conv0, length):
    """`_kda_conv`'s tail without its ``[T + K - 1, conv_dim]`` array:
    the K - 1 rows from row ``length`` on of ``[conv0; qkv]`` are among
    conv0 and the rows of qkv right before ``length`` (all of them under
    K - 1 tokens, conv0's last ones beside them)."""
    tail = conv0.shape[0]
    reach = min(tail, qkv.shape[0])
    window = jax.lax.dynamic_slice_in_dim(
        qkv, jnp.maximum(length - reach, 0), reach, axis=0
    )
    return jax.lax.dynamic_slice_in_dim(
        jnp.concatenate([conv0.astype(qkv.dtype), window], axis=0),
        jnp.minimum(length, reach), tail, axis=0,
    )


def _kda_chunked_fused(u, p, cfg, state0, conv0, length, size, sub):
    """`kda_chunked` on a TPU: everything between the in-projections'
    matmuls and the out-projection's is ONE call of
    ``ops/pallas/kda_chunk.py``, which reads the matmuls' float32
    results where they lie (the convolution, ``silu``, the unit lengths
    and the decay's sigmoid in its prologue, on the tiles the rule loads
    anyway) and writes the normed, gated output in ``cfg.dtype``."""
    qkv, low, beta, gate = _kda_projections(u, p, cfg)
    with jax.named_scope("kda:conv"):
        conv_end = _conv_tail(qkv, conv0, length)
    with jax.named_scope("kda:scan"):
        gated, end = kda_chunk_rule(
            qkv, conv0, p["conv_w"], low, p["dt_bias"], p["A_log"], beta,
            gate, p["gate_norm"], state0, length, chunk=size, sub=sub,
            lower=cfg.kda_lower, l2_eps=_L2_EPS, norm_eps=cfg.norm_eps,
            dtype=cfg.dtype,
        )
    with jax.named_scope("kda:out"):
        out = gated @ p["out_proj"]
    return out, end, conv_end.astype(conv0.dtype)


def kda_chunked(u, p, cfg: Glm5NextConfig, state0, conv0, length):
    """The KDA mixer over many tokens of one sequence, with
    `nemotron_h.mamba_chunked`'s contract: u [T, d] (normed input);
    state0 [H, dk, dv] float32 and conv0 [K - 1, conv_dim] the state
    before u[0]; ``length`` (traced) how many of the T tokens are real.
    Returns (out [T, d], the state and the convolution tail after token
    ``length - 1``). Positions from ``length`` on take no step.

    On a TPU everything between the matmuls is one call of
    ``ops/pallas/kda_chunk.py`` (the rule PR 60, the float32 passes
    around it PR 62: `_kda_chunked_fused`); elsewhere the passes are
    XLA's and the rule `_kda_rule`, which is tier 1's path and the
    kernel's oracle. The platform decides, as it does for
    `qwen3_next.gdn_chunked` and as `moe_ffn` chooses its kernels."""
    t = u.shape[0]
    size = min(cfg.kda_chunk, 1 << (t - 1).bit_length())
    sub = min(_KDA_SUBCHUNK, size)
    if chip.platform() == "tpu":
        return _kda_chunked_fused(u, p, cfg, state0, conv0, length, size, sub)
    qkv, g, beta, gate = _kda_in(u, p, cfg)
    with jax.named_scope("kda:conv"):
        (q, k, v), conv_end = _kda_conv(qkv, conv0, p, cfg, length)
    with jax.named_scope("kda:scan"):
        live = jnp.arange(t) < length
        beta = jnp.where(live[:, None], beta, 0.0)
        g = jnp.where(live[:, None, None], g, 0.0)
        o, end = _kda_rule(q, k, v, beta, g, state0, size, sub)
    out = _kda_out(o, gate, p, cfg)
    return out, end, conv_end.astype(conv0.dtype)


def _kda_step_operands(u, p, cfg, conv):
    """What one token's state update takes, of u [B, d] and the tail
    conv [B, K - 1, conv_dim]: q, k, v [B, H, dk], g, beta, the output
    gate, and the window whose last K - 1 rows are the next tail."""
    qkv, g, beta, gate = _kda_in(u, p, cfg)
    with jax.named_scope("kda:conv"):
        window = jnp.concatenate(
            [conv, qkv[:, None].astype(conv.dtype)], axis=1
        )  # [B, K, conv_dim]
        out = (window.astype(jnp.float32) * p["conv_w"][None]).sum(1)
        q, k, v = _kda_split(jax.nn.silu(out), cfg)
    return q, k, v, g, beta, gate, window


def kda_step(u, p, cfg: Glm5NextConfig, state, conv):
    """The mixer for ONE token of each of B sequences: u [B, d], state
    [B, H, dk, dv] float32, conv [B, K - 1, conv_dim]. Returns (out
    [B, d], state, conv) after the token. The rule as it is defined, in
    float32 elementwise arithmetic: no product rounds the state."""
    q, k, v, g, beta, gate, window = _kda_step_operands(u, p, cfg, conv)
    with jax.named_scope("kda:step"):
        s = state * jnp.exp(g)[..., None]  # a decay a key channel
        k_col = k[..., None]  # [B, H, dk, 1]
        read = (s * k_col).sum(-2)  # S^T k: [B, H, dv]
        delta = beta[..., None] * (v - read)
        s = s + k_col * delta[..., None, :]
        o = (s * q[..., None]).sum(-2)  # [B, H, dv]
    return _kda_out(o, gate, p, cfg), s, window[:, 1:]


def kda_step_live(u, p, cfg: Glm5NextConfig, stack, layer, conv, order,
                  count):
    """`kda_step` on a TPU, for the slots that decode: ``stack`` [L, B,
    H, dk, dv] is every layer's state, of which ``stack[layer]`` is
    stepped IN PLACE for the first ``count`` slots of ``order`` by
    ``ops/pallas/state_step.py``, as `qwen3_next.gdn_step_live`."""
    q, k, v, g, beta, gate, window = _kda_step_operands(u, p, cfg, conv)
    with jax.named_scope("kda:step"):
        stack, o = kda_state_step(
            stack, layer, order, count, jnp.exp(g), beta, q, k, v
        )
    return _kda_out(o, gate, p, cfg), stack, window[:, 1:]


# ------------------------------------------------- sparse latent attention
def _rope_interleaved(x, positions, rotary_dim: int, theta: float):
    """The first ``rotary_dim`` dimensions of x [.., D] rotated at
    ``positions`` (x's leading shape but for a heads axis, which
    broadcasts), consecutive pairs ``(2i, 2i + 1)``; float32."""
    half = rotary_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x = x.astype(jnp.float32)
    pairs = x[..., :rotary_dim].reshape(*x.shape[:-1], half, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    turned = jnp.stack(
        [even * cos - odd * sin, odd * cos + even * sin], axis=-1
    ).reshape(*x.shape[:-1], rotary_dim)
    return jnp.concatenate([turned, x[..., rotary_dim:]], axis=-1)


def _dsa_inputs(h, p, cfg, positions):
    """A sparse latent block's inputs of h [T, d] (not normed) at
    ``positions`` [T]: the absorbed queries ``qa`` [T, H, rank] in
    ``cfg.dtype``, the latent cells ``c`` [T, rank], and the indexer's
    queries [T, Hi, Di], keys [T, Di] (both rotated, float32) and head
    weights [T, Hi] float32."""
    heads, hi, di = cfg.n_heads, cfg.index_heads, cfg.index_head_dim
    f32 = partial(jnp.dot, preferred_element_type=jnp.float32)
    with jax.named_scope("dsa:in"):
        n = rms_norm(h, p["attn_norm"], cfg.norm_eps)
        cq = rms_norm(n @ p["wq_a"], p["q_norm"], cfg.norm_eps)
        q = (cq @ p["wq_b"]).reshape(-1, heads, cfg.qk_head_dim)
        c = rms_norm(n @ p["wkv_a"], p["kv_norm"], cfg.norm_eps)
        # Absorbed: each head's query against the cells as they lie.
        qa = jnp.einsum("thd,hrd->thr", q, p["w_uk"])
    with jax.named_scope("dsa:index"):
        q_i = _rope_interleaved(
            f32(cq, p["index_q"]).reshape(-1, hi, di), positions[:, None],
            cfg.index_rotary_dim, cfg.index_rope_theta,
        )
        raw = f32(n, p["index_k"])
        mean = raw.mean(-1, keepdims=True)
        var = jnp.mean(jnp.square(raw - mean), axis=-1, keepdims=True)
        k_i = _rope_interleaved(
            (raw - mean) * jax.lax.rsqrt(var + cfg.norm_eps)
            * p["index_k_norm"] + p["index_k_bias"],
            positions, cfg.index_rotary_dim, cfg.index_rope_theta,
        )
        w_i = f32(n, p["index_w"])
    return qa, c, q_i, k_i, w_i


def _index_scores(q_i, w_i, pooled, cfg):
    """``I`` [.., T, N] float32 of queries q_i [.., T, Hi, Di] with head
    weights w_i [.., T, Hi] against pooled keys [.., N, Di]: the products
    by the matmul unit in ``cfg.dtype`` summed in float32, the rest
    float32."""
    dt = cfg.dtype
    dots = jnp.einsum(
        "...thd,...nd->...thn", q_i.astype(dt), pooled.astype(dt),
        preferred_element_type=jnp.float32,
    )
    scale = (cfg.index_heads * cfg.index_head_dim) ** -0.5
    return jnp.einsum("...thn,...th->...tn", jax.nn.relu(dots), w_i) * scale


def _select(scores, positions, cfg):
    """The blocks each query attends beside its own: scores [.., T, N]
    over a context's blocks in order, positions [.., T]. A block is a
    candidate where it lies wholly before the block that holds the
    query. Returns block ids int32 [.., T, index_blocks], -1 where a
    query has fewer candidates. Exact (`jax.lax.top_k`)."""
    n = scores.shape[-1]
    k = min(cfg.index_blocks, n)
    own = positions // cfg.index_kpool
    candidate = jnp.arange(n) < own[..., None]
    top, ids = jax.lax.top_k(jnp.where(candidate, scores, _NEG_INF), k)
    ids = jnp.where(top > _NEG_INF, ids, -1).astype(jnp.int32)
    if k < cfg.index_blocks:
        pad = [(0, 0)] * (ids.ndim - 1) + [(0, cfg.index_blocks - k)]
        ids = jnp.pad(ids, pad, constant_values=-1)
    return ids


def _attend_cells(qa, cells, hidden, p, cfg):
    """The absorbed attention of qa [Q, H, rank] over each query's own
    cells [Q, N, rank] (``hidden`` [Q, N]: True where a cell is not
    attended), then ``W_uv``: [Q, H, v]."""
    scale = cfg.qk_head_dim**-0.5
    scores = jnp.einsum(
        "qhr,qnr->qhn", qa, cells, preferred_element_type=jnp.float32
    ) * scale
    scores = jnp.where(hidden[:, None, :], _NEG_INF, scores)
    probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
    mixed = jnp.einsum("qhn,qnr->qhr", probs, cells)
    return jnp.einsum("qhr,hrv->qhv", mixed, p["w_uv"])


def _block_rows(ids, pool: int):
    """The positions of context blocks ``ids`` [.., K] (-1: none): [..,
    K * pool] int32 (a missing block's clamped to 0) and which of them
    are hidden [.., K * pool]."""
    rows = jnp.maximum(ids, 0)[..., None] * pool + jnp.arange(pool)
    hidden = jnp.repeat(ids < 0, pool, axis=-1)
    return rows.reshape(*ids.shape[:-1], -1), hidden


def dsa_prefill(h, p, cfg: Glm5NextConfig, leaves, at, base, pages,
                chunk_pages, start, n_live):
    """A sparse latent block for one chunk of one slot. h [C, d] at
    positions ``start ..`` (page-aligned), of which the first ``n_live``
    are real; ``leaves`` the cache's ``latent`` [pages, P, rank] and
    ``index`` [pages, P / pool, Di] (flat over the layers; ``base`` this
    layer's first page) and ``index_tail`` [L, B, pool - 1, Di]; ``at``
    (layer, slot); ``pages`` the context's table, ``chunk_pages`` the
    chunk's own pages. Writes the chunk's cells, its blocks' pooled keys
    (a block that padding completes holds what is never a candidate: a
    later query's candidates lie before ITS block, and the block the
    prompt ends in is written again when a decode step completes it) and
    the slot's tail of the last ``pool - 1`` keys; scores every query
    against the context's pooled keys, a block of ``_DSA_INDEX_BLOCK`` at
    a time as far as the chunk's end; selects; attends the selected
    blocks' cells, gathered a block of ``_DSA_QUERY_BLOCK`` queries at a
    time, and the query's own block from the chunk itself. Returns (out
    [C, d], the leaves, the selection [C, index_blocks])."""
    c_len = h.shape[0]
    pool, rank = cfg.index_kpool, cfg.kv_lora_rank
    latent, index, tail = leaves
    page = latent.shape[1]
    positions = start + jnp.arange(c_len, dtype=jnp.int32)
    qa, c, q_i, k_i, w_i = _dsa_inputs(h, p, cfg, positions)
    with jax.named_scope("dsa:write"):
        latent = latent.at[base + chunk_pages].set(
            c.reshape(-1, page, rank).astype(latent.dtype)
        )
    with jax.named_scope("dsa:index_write"):
        pooled = k_i.reshape(-1, pool, k_i.shape[-1]).mean(1)
        index = index.at[base + chunk_pages].set(
            pooled.reshape(-1, page // pool, pooled.shape[-1]).astype(
                index.dtype
            )
        )
        # The last pool - 1 real keys, an earlier chunk's among them.
        # (From position 0 there are none, whatever the slot's last
        # request left.)
        seq = jnp.concatenate(
            [jnp.where(start == 0, 0.0, tail[at]), k_i.astype(tail.dtype)],
            axis=0,
        )
        tail = tail.at[at].set(
            jax.lax.dynamic_slice_in_dim(seq, n_live, pool - 1, axis=0)
        )
    with jax.named_scope("dsa:index"):
        n_blocks = pages.shape[0] * (page // pool)
        width = math.gcd(_DSA_INDEX_BLOCK, n_blocks)
        context = jnp.take(index, base + pages, axis=0, mode="clip").reshape(
            n_blocks // width, width, -1
        )

        def some_keys(j, scores):
            part = _index_scores(q_i, w_i, context[j], cfg)
            return jax.lax.dynamic_update_slice_in_dim(
                scores, part, j * width, axis=1
            )

        # Blocks at and past the chunk's end are no query's candidates.
        reach = (start + c_len) // pool
        scores = jax.lax.fori_loop(
            0, (reach + width - 1) // width, some_keys,
            jnp.full((c_len, n_blocks), _NEG_INF, jnp.float32),
        )
    with jax.named_scope("dsa:select"):
        # The top-k sorts a row: over the context as far as the chunk's
        # end, in the narrowest of a few widths that holds it, not over
        # the table (a 40k prompt's table is 64k wide).
        widths = sorted({
            max(n_blocks >> shift, min(n_blocks, cfg.index_blocks))
            for shift in range(4)
        })
        def within(width):
            return lambda s: _select(s[:, :width], positions, cfg)

        picked = jax.lax.switch(
            sum(reach > w for w in widths[:-1]), [within(w) for w in widths],
            scores,
        )
    with jax.named_scope("dsa:attend"):
        q_block = math.gcd(_DSA_QUERY_BLOCK, c_len)
        # The context's cells in the order of their positions: whole
        # pages copied once a chunk (64 KB each: at a 64k context 67 MB),
        # so that a selected position is a row of one array and no block
        # id goes through the table. Rows of 512, the pool's own layout:
        # a view of it by blocks ([.., 4, 512]) is a copy of the pool on
        # a TPU, whose tiles hold 16 rows.
        context = jnp.take(latent, base + pages, axis=0, mode="clip")
        context = context.reshape(-1, rank)  # [table positions, rank]
        # A query's own block: the chunk's own cells, up to itself.
        own = c.astype(cfg.dtype).reshape(-1, pool, rank)  # [C / pool, ..]
        later = jnp.arange(pool)[None, :] > (positions % pool)[:, None]

        def some_queries(args):
            qa_b, ids, first = args
            rows, hidden = _block_rows(ids, pool)
            cells = jnp.take(context, rows, axis=0, mode="clip")
            mine = first + jnp.arange(q_block)
            return _attend_cells(
                qa_b,
                jnp.concatenate(
                    [cells, jnp.take(own, mine // pool, axis=0)], axis=1
                ),
                jnp.concatenate(
                    [hidden, jnp.take(later, mine, axis=0)], axis=1
                ),
                p, cfg,
            )

        n_q = c_len // q_block
        heads = jax.lax.map(some_queries, (
            qa.reshape(n_q, q_block, *qa.shape[1:]),
            picked.reshape(n_q, q_block, -1),
            jnp.arange(n_q, dtype=jnp.int32) * q_block,
        ))
        out = heads.reshape(c_len, -1).astype(cfg.dtype) @ p["wo"]
    return out, (latent, index, tail), picked


def dsa_decode(h, p, cfg: Glm5NextConfig, leaves, layer, base, block_tables,
               positions, active):
    """A sparse latent block for one token of every slot. h [B, d] at
    ``positions`` [B]; ``block_tables`` [B, max_pages] (-1: unused);
    ``active`` the slots that decode (another slot writes nothing that
    lasts: its cell goes to the dump page, its tail stays). A block
    that this token completes gets its pooled key, the mean of the
    slot's tail and this key, as a prefill chunk would have written it.
    Returns (out [B, d], the leaves, the selection [B, index_blocks])."""
    pool = cfg.index_kpool
    latent, index, tail = leaves
    page = latent.shape[1]
    qa, c, q_i, k_i, w_i = _dsa_inputs(h, p, cfg, positions)
    table = jnp.where(block_tables >= 0, base + block_tables, 0)
    here = jnp.take_along_axis(
        table, (positions // page)[:, None], axis=1
    )[:, 0]
    here = jnp.where(active, here, 0)  # the dump page
    with jax.named_scope("dsa:write"):
        latent = latent.at[here, positions % page].set(
            c.astype(latent.dtype), mode="drop"
        )
    with jax.named_scope("dsa:index_write"):
        old = tail[layer]  # [B, pool - 1, Di]
        pooled = (old.sum(1) + k_i) / pool
        closes = active & (positions % pool == pool - 1)
        index = index.at[
            jnp.where(closes, here, index.shape[0]),
            (positions % page) // pool,
        ].set(pooled.astype(index.dtype), mode="drop")
        new = jnp.concatenate([old[:, 1:], k_i[:, None].astype(old.dtype)], 1)
        tail = tail.at[layer].set(
            jnp.where(active[:, None, None], new, old)
        )
    with jax.named_scope("dsa:index"):
        context = jnp.take(index, table, axis=0, mode="clip")
        context = context.reshape(h.shape[0], -1, context.shape[-1])
        scores = _index_scores(
            q_i[:, None], w_i[:, None], context, cfg
        )[:, 0]  # [B, blocks]
    with jax.named_scope("dsa:select"):
        picked = _select(scores, positions, cfg)
        # The query's own block, whose cells up to this one are written.
        ids = jnp.concatenate(
            [picked, (positions // pool)[:, None].astype(jnp.int32)], axis=1
        )
    with jax.named_scope("dsa:attend"):
        # A selected position's row of the pool, through the slot's table.
        rows, hidden = _block_rows(ids, pool)  # [B, (K + 1) * pool]
        where = jnp.take_along_axis(table, rows // page, axis=1) * page \
            + rows % page
        cells = jnp.take(
            latent.reshape(-1, latent.shape[-1]), where, axis=0, mode="clip"
        )  # [B, (K + 1) * pool, rank]
        hidden = hidden.at[:, -pool:].set(
            jnp.arange(pool)[None, :] > (positions % pool)[:, None]
        )
        heads = _attend_cells(qa, cells, hidden, p, cfg)
        out = heads.reshape(h.shape[0], -1).astype(cfg.dtype) @ p["wo"]
    return out, (latent, index, tail), picked
