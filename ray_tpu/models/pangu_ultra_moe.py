"""Pangu Ultra MoE (``model_type: pangu_ultra_moe``): latent attention in
every layer, a few leading dense layers, then sparse-expert layers.

Every layer is two sublayers, each behind an RMSNorm and, with
``sandwich_norm``, another on its output before the residual add:
``h = x + N2(MLA(N1(x)))``, ``y = h + N4(F(N3(h)))``. ``F`` is a dense
SwiGLU in the first ``first_k_dense`` layers (kind ``D``) and the expert
layer in the rest (kind ``E``: ``models/moe.py``'s ``moe_ffn`` with
sigmoid scores, gates renormalised and scaled, SwiGLU experts and one
shared expert). The sizes are those of openPangu-Ultra-MoE-718B, the
public model the benchmark serves through this file.

MLA (multi-head latent attention, DeepSeek-V2, arXiv:2405.04434): the
queries come through a ``q_lora_rank`` latent, ``cq = Nq(x Wqa)``, ``q =
cq Wqb`` -> per head ``[q_nope; q_pe]``; keys and values through ONE
``kv_lora_rank`` latent a token, ``[c; kpe] = x Wkva`` with ``c =
Nkv(c)``, and a rotary ``kpe`` that all heads share: ``k_h = [c Wuk_h;
rope(kpe)]``, ``v_h = c Wuv_h``. What a cache holds per token and layer
is ``[c; rope(kpe)]`` (``latent_dim`` numbers), whatever the number of
heads. ``Wkvb``'s two halves are held apart and head-major (``w_uk``,
``w_uv``: ``[H, kv_lora_rank, 128]``), so that the absorbed decode
(``q_nope Wuk^T``, then ``ol Wuv``) and the expansion in a prefill are
both batched matmuls over heads of the arrays as they lie.

Here: the configuration, the initialiser that makes the tree in the
dtypes it is held in, and what the serving programs and nothing else
share (the projections, rope, the dense sublayer). The programs
themselves are ``llm/latent_kv.py``. Blocks are a tuple of per-block
trees, run by a Python loop over the pattern, as ``models/nemotron_h.py``.

A norm's weight is stored as ``scale`` and applied as ``1 + scale``
(``ops/norms.py``), as everywhere in the repo. The multi-token-prediction
module is not here: it is a draft head beside the language model.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, ClassVar

import jax
import jax.numpy as jnp

from ray_tpu.models.nemotron_h import _normal
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import apply_rope

Params = dict[str, Any]


class LatentShape:
    """What the latent programs and kernels read of a config beside its
    fields: a cache cell's sizes, the pool's rows, the experts held.
    (`models/longcat_flash.py`'s config is one too.)"""

    @property
    def latent_dim(self) -> int:
        """What the cache holds of a token in one attention sublayer:
        ``[c; kpe]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cell_width(self) -> int:
        """The width a cache cell is held at: ``latent_dim`` and zeros."""
        return -(-self.latent_dim // self.cell_lanes) * self.cell_lanes

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        return self.qk_head_dim**-0.5

    @property
    def n_experts_held(self) -> int:
        return self.experts_held[1] if self.experts_held else self.num_experts

    @property
    def attn_sublayers(self) -> int:
        """Rows of the latent pool: one an attention sublayer."""
        return self.n_layers * self.attn_per_layer

    def count(self, kind: str) -> int:
        return self.pattern.count(kind)


@dataclasses.dataclass(frozen=True)
class PanguUltraMoEConfig(LatentShape):
    vocab_size: int = 153600  # rows held, where the vocabulary is sliced
    d_model: int = 7680
    n_layers: int = 61
    first_k_dense: int = 3
    # latent attention
    n_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 25.6e6
    # Factors on the heads' queries and on the normed key-value latent
    # (a family that scales them by sqrt(hidden / rank):
    # `models/longcat_flash.py`). 1.0: nothing of them in a program.
    q_latent_scale: float = 1.0
    kv_latent_scale: float = 1.0
    dense_d_ff: int = 18432
    # expert layers (the names `moe_ffn` reads)
    num_experts: int = 256
    top_k: int = 8
    d_ff: int = 2048
    shared_d_ff: int = 2048
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    experts_held: tuple | None = None  # (first, count); None = all
    router_kind: str = "sigmoid"
    expert_kind: str = "swiglu"
    swiglu_limit: float | None = None  # no clamp (`moe.clamped_swiglu`)
    zero_experts: int = 0  # no identity outputs behind the router's experts
    # Up to this many rows `moe_ffn` applies every held expert to every
    # row, above it it sorts pairs into grouped matmuls. One expert layer
    # at these widths with 16 of 256 experts held, on a v5e (my chip run,
    # PR 33), sorted / every row: 3.34 / 3.06 ms at 32 rows (a decode
    # step), 6.08 / 3.38 at 256, 9.96 / 21.55 at 2,048 (a prefill chunk,
    # where every row does 32 times the arithmetic its routes need).
    # Since PR 34 the every-row form reads only the experts that got a
    # row (`ops/pallas/expert_rows.py`): 12 / 16 of 16 touched take
    # 1.55-1.62 / 2.05-2.13 ms at 32 rows and 1.62-1.66 / 2.12-2.17 at
    # 256 (my chip run, PR 34), and a decode step here touches 4 to 5.
    dense_expert_rows: int = 256
    # A cache cell is held this many lanes wide, a multiple of:
    # `[c; kpe]` (576) padded with zeros to 640. A TPU lays a row of 576
    # bf16 out in 640 lanes anyway (4.5 tiles of 128), and the decode
    # kernel's page copies must be whole tiles: the compiler for a
    # described v5e refuses a slice 576 wide of the 640 it finds.
    cell_lanes: int = 128
    # Keys a prefill attends at a time (`latent_kv._attend_expanded`):
    # the float32 scores of one block are n_heads x chunk x this.
    prefill_key_block: int = 256
    max_seq: int = 131072
    dtype: Any = jnp.bfloat16

    # Attention sublayers in a layer: each has a row of its own in the
    # latent pool (`llm/latent_kv.py`).
    attn_per_layer: ClassVar[int] = 1

    def __post_init__(self):
        if not 0 <= self.first_k_dense <= self.n_layers:
            raise ValueError("first_k_dense is not within n_layers")
        if self.qk_rope_head_dim % 2:
            raise ValueError("the rotary part of a head pairs its dimensions")

    @property
    def pattern(self) -> str:
        """Each layer's kind: ``D`` dense SwiGLU, ``E`` experts."""
        return "D" * self.first_k_dense + "E" * (
            self.n_layers - self.first_k_dense
        )

    def serving(self):
        """What `LLMEngine` serves this model through: its cache and its
        three programs."""
        from ray_tpu.llm.latent_kv import LatentServing

        return LatentServing(self, init_params)


PANGU_PRESETS: dict[str, PanguUltraMoEConfig] = {
    # CPU-test scale: a dense and three expert layers, sandwich norms,
    # the shared expert, the published switches.
    "pangu_tiny": PanguUltraMoEConfig(
        vocab_size=256, d_model=64, n_layers=4, first_k_dense=1, n_heads=4,
        q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, rope_theta=10000.0,
        dense_d_ff=96, num_experts=8, top_k=3, d_ff=32, shared_d_ff=32,
        dense_expert_rows=8, cell_lanes=16, prefill_key_block=16, max_seq=256,
        dtype=jnp.float32,
    ),
}


# ------------------------------------------------------------ parameters
def _zeros(n):
    return jnp.zeros((n,), jnp.float32)


def init_mla(keys, cfg) -> Params:
    """One latent-attention sublayer's leaves from six keys: its input
    norm, the two latent norms and the six matrices. A matrix behind a
    latent that the model scales is drawn that much smaller, so that
    queries, keys and values have unit variance whatever the ranks:
    the factors ``sqrt(hidden / rank)`` exist to align the variance of
    what comes through a latent with what comes from the hidden state
    (the rotary key), under weights of ONE deviation, ``hidden^-0.5``;
    over weights drawn at ``rank^-0.5`` they would make the scores'
    deviation ``q_latent_scale x kv_latent_scale`` times (6.9 times)
    a trained model's and the soft-max nearly an arg-max."""
    d, dt, h = cfg.d_model, cfg.dtype, cfg.n_heads
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    fan_q, fan_kv = rq * cfg.q_latent_scale**2, rkv * cfg.kv_latent_scale**2
    return {
        "norm1": _zeros(d), "q_norm": _zeros(rq), "kv_norm": _zeros(rkv),
        "wq_a": _normal(keys[0], (d, rq), d, dt),
        "wq_b": _normal(keys[1], (rq, h * cfg.qk_head_dim), fan_q, dt),
        "wkv_a": _normal(keys[2], (d, cfg.latent_dim), d, dt),
        "w_uk": _normal(keys[3], (h, rkv, cfg.qk_nope_head_dim), fan_kv, dt),
        "w_uv": _normal(keys[4], (h, rkv, cfg.v_head_dim), fan_kv, dt),
        "wo": _normal(keys[5], (h * cfg.v_head_dim, d), h * cfg.v_head_dim, dt),
    }


@partial(jax.jit, static_argnames=("kind", "cfg"))
def _init_block(key, kind: str, cfg: PanguUltraMoEConfig) -> Params:
    """One layer's tree, each leaf made and rounded inside this program:
    the float32 draw of an expert stack never outlives it."""
    d, dt = cfg.d_model, cfg.dtype
    keys = jax.random.split(key, 14)
    block = {
        **init_mla(keys, cfg),
        "norm2": _zeros(d), "norm3": _zeros(d), "norm4": _zeros(d),
    }
    if kind == "D":
        f = cfg.dense_d_ff
        block.update(
            w_gate=_normal(keys[6], (d, f), d, dt),
            w_up=_normal(keys[7], (d, f), d, dt),
            w_down=_normal(keys[8], (f, d), f, dt),
        )
        return block
    held, f, fs = cfg.n_experts_held, cfg.d_ff, cfg.shared_d_ff
    block.update(
        # The router stays as wide as the model's experts, in float32.
        router=_normal(keys[6], (d, cfg.num_experts), d, jnp.float32),
        # No selection bias is published; `moe_ffn` adds this to the
        # scores for the choice (tests use a non-zero one).
        router_bias=_zeros(cfg.num_experts),
        w_gate=_normal(keys[7], (held, d, f), d, dt),
        w_up=_normal(keys[8], (held, d, f), d, dt),
        w_down=_normal(keys[9], (held, f, d), f, dt),
        shared_gate=_normal(keys[10], (d, fs), d, dt),
        shared_up=_normal(keys[11], (d, fs), d, dt),
        shared_down=_normal(keys[12], (fs, d), fs, dt),
    )
    return block


@partial(jax.jit, static_argnames="cfg")
def init_ends(key, cfg) -> Params:
    """The embedding, the final norm and the untied head."""
    k_emb, k_head = jax.random.split(key)
    v, d = cfg.vocab_size, cfg.d_model
    return {
        "tok_emb": (
            jax.random.normal(k_emb, (v, d), jnp.float32) * 0.02
        ).astype(cfg.dtype),
        "final_norm": jnp.zeros((d,), jnp.float32),
        "lm_head": _normal(k_head, (d, v), d, cfg.dtype),
    }


def init_params(key: jax.Array, cfg: PanguUltraMoEConfig) -> Params:
    """The tree as it is held: matmul weights in ``cfg.dtype``, the
    router and the norms in float32. One program per layer, so that no
    more than one layer's float32 draws exist at a time."""
    params = init_ends(jax.random.fold_in(key, cfg.n_layers), cfg=cfg)
    params["blocks"] = tuple(
        _init_block(jax.random.fold_in(key, i), kind=kind, cfg=cfg)
        for i, kind in enumerate(cfg.pattern)
    )
    return params


# ------------------------------------------------- what the programs share
def project_q(h, p, cfg, cos, sin, positions):
    """Normed input h [B, S, d] -> the heads' queries, rope applied to
    their rotary part: (q_nope [B, S, H, nope], q_pe [B, S, H, rope])."""
    with jax.named_scope("mla:q"):
        b, s, _ = h.shape
        cq = rms_norm(h @ p["wq_a"], p["q_norm"])
        q = (cq @ p["wq_b"]).reshape(b, s, cfg.n_heads, cfg.qk_head_dim)
        if cfg.q_latent_scale != 1.0:
            q = q * cfg.q_latent_scale
        q_nope, q_pe = jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)
        return q_nope, apply_rope(q_pe, cos, sin, positions=positions)


def pad_to_cell(x, cfg):
    """x [..., n] with ``cell_width - latent_dim`` columns of zeros behind
    it: a cell's ``[c; kpe]`` widened to the width the cache holds, or a
    query's rotary part widened like the cell's ``[kpe; zeros]``."""
    pad = jnp.zeros(x.shape[:-1] + (cfg.cell_width - cfg.latent_dim,), x.dtype)
    return jnp.concatenate([x, pad], axis=-1)


def project_latent(h, p, cfg, cos, sin, positions):
    """Normed input h [B, S, d] -> each token's cache cell,
    ``[kv_latent_scale * Nkv(c); rope(kpe); zeros]`` [B, S, cell_width]
    in ``cfg.dtype``."""
    ckv = h @ p["wkv_a"]
    c, kpe = jnp.split(ckv, [cfg.kv_lora_rank], axis=-1)
    c = rms_norm(c, p["kv_norm"])
    if cfg.kv_latent_scale != 1.0:
        c = c * cfg.kv_latent_scale  # the rotary key is not scaled
    # One rotary key for all heads: a head dimension of one.
    kpe = apply_rope(kpe[:, :, None, :], cos, sin, positions=positions)
    cell = jnp.concatenate([c, kpe[:, :, 0, :]], axis=-1)
    return pad_to_cell(cell, cfg).astype(cfg.dtype)


def dense_mlp(h, p):
    with jax.named_scope("dense:mlp"):
        return (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]
