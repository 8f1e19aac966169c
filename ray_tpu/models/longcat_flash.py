"""LongCat-Flash's language model (``config.json`` of LongCat-Flash-Omni,
``attention_method: MLA``, ``zero_expert_type: identity``): a double
layer of two latent-attention sublayers and two dense FFNs, with the
expert layer on a shortcut branch.

One layer, N an RMSNorm with gain:

    a1 = x  + MLA_0(Na1(x))
    u  = Nf1(a1)
    s  = MoE(u)                      # read here, added at the layer's end
    h1 = a1 + FFN_1(u)
    a2 = h1 + MLA_1(Na2(h1))
    y  = a2 + FFN_2(Nf2(a2)) + s

so that, across chips, the experts' exchange runs under the first dense
FFN, the second attention and the second dense FFN. Both attention
sublayers write and read cache cells of their own: two cells a token a
layer (``attn_per_layer``; the pool's row is ``2 i + j``).

- MLA is ``models/pangu_ultra_moe.py``'s, its projections imported,
  with two factors: the heads' queries times ``q_latent_scale`` and the
  normed key-value latent times ``kv_latent_scale`` (``sqrt(hidden /
  rank)``: ``mla_scale_q_lora`` / ``mla_scale_kv_lora``); the rotary key
  is not scaled. A cell holds ``[kv_latent_scale * Nkv(c); rope(kpe)]``.
  No norm on a sublayer's output. (`init_mla` draws the matrices behind
  a scaled latent that much smaller: the factors align variances under
  weights of one deviation, ``hidden^-0.5``.)
- MoE is ``models/moe.py``'s ``moe_ffn``: a softmax over ``num_experts +
  zero_experts`` outputs in float32, the ``top_k`` largest of ``p +
  router_bias`` chosen (the bias for the choice alone), gates
  ``routed_scaling_factor * p``, not renormalised; a route to one of the
  ``zero_experts`` identity outputs adds ``gate * u`` and costs no
  matmul; no shared expert.
- Embedding and head are untied.

Here: the configuration, the initialiser that makes the tree on the
device a sublayer at a time in the dtypes it is held in, and a tiny
preset. The programs are ``llm/latent_kv.py``'s, kind ``S``. A block's
tree: ``attn`` (two MLA trees), ``ffn`` (two dense trees, each with its
input ``norm``), ``moe``. The multimodal encoders and the codec decoder
of the Omni model sit outside the language model and are not here.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, ClassVar

import jax
import jax.numpy as jnp

from ray_tpu.models.nemotron_h import _normal
from ray_tpu.models.pangu_ultra_moe import (
    LatentShape,
    Params,
    _zeros,
    init_ends,
    init_mla,
)


@dataclasses.dataclass(frozen=True)
class LongcatFlashConfig(LatentShape):
    vocab_size: int = 131072  # rows held, where the vocabulary is sliced
    d_model: int = 6144
    n_layers: int = 28  # double layers
    # latent attention, twice a layer
    n_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 1e7
    q_latent_scale: float = (6144 / 1536) ** 0.5
    kv_latent_scale: float = (6144 / 512) ** 0.5
    dense_d_ff: int = 12288  # each of a layer's two dense FFNs
    # the expert layer on the shortcut (the names `moe_ffn` reads)
    num_experts: int = 512
    zero_experts: int = 256  # identity outputs behind the 512
    top_k: int = 12
    d_ff: int = 2048
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 6.0
    experts_held: tuple | None = None  # (first, count); None = all
    router_kind: str = "softmax"
    expert_kind: str = "swiglu"
    swiglu_limit: float | None = None
    # As `PanguUltraMoEConfig`'s: rows up to which every held expert is
    # applied to every row, a cell's lanes, keys a prefill's XLA path
    # attends at a time.
    dense_expert_rows: int = 256
    cell_lanes: int = 128
    prefill_key_block: int = 256
    max_seq: int = 131072
    dtype: Any = jnp.bfloat16

    attn_per_layer: ClassVar[int] = 2

    def __post_init__(self):
        if self.qk_rope_head_dim % 2:
            raise ValueError("the rotary part of a head pairs its dimensions")

    @property
    def pattern(self) -> str:
        """Each layer's kind: ``S``, the double layer with its shortcut."""
        return "S" * self.n_layers

    def serving(self):
        """What `LLMEngine` serves this model through: the latent cache
        and its three programs."""
        from ray_tpu.llm.latent_kv import LatentServing

        return LatentServing(self, init_params)


LONGCAT_PRESETS: dict[str, LongcatFlashConfig] = {
    # CPU-test scale: two double layers, 8 experts and 4 identity
    # outputs, 3 a token; 8 rows a call take the every-row expert form
    # and more the sorted one.
    "longcat_tiny": LongcatFlashConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, q_lora_rank=24,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, rope_theta=10000.0, q_latent_scale=(64 / 24) ** 0.5,
        kv_latent_scale=2.0**0.5, dense_d_ff=96, num_experts=8,
        zero_experts=4, top_k=3, d_ff=32, dense_expert_rows=8, cell_lanes=16,
        prefill_key_block=16, max_seq=256, dtype=jnp.float32,
    ),
}


# ------------------------------------------------------------ parameters
@partial(jax.jit, static_argnames="cfg")
def _init_attn(key, cfg: LongcatFlashConfig) -> Params:
    return init_mla(jax.random.split(key, 6), cfg)


@partial(jax.jit, static_argnames="cfg")
def _init_ffn(key, cfg: LongcatFlashConfig) -> Params:
    d, f, dt = cfg.d_model, cfg.dense_d_ff, cfg.dtype
    keys = jax.random.split(key, 3)
    return {
        "norm": _zeros(d),
        "w_gate": _normal(keys[0], (d, f), d, dt),
        "w_up": _normal(keys[1], (d, f), d, dt),
        "w_down": _normal(keys[2], (f, d), f, dt),
    }


@partial(jax.jit, static_argnames="cfg")
def _init_moe(key, cfg: LongcatFlashConfig) -> Params:
    d, f, dt, held = cfg.d_model, cfg.d_ff, cfg.dtype, cfg.n_experts_held
    outputs = cfg.num_experts + cfg.zero_experts
    keys = jax.random.split(key, 4)
    return {
        # The router stays as wide as the model's, identity outputs and
        # all, in float32.
        "router": _normal(keys[0], (d, outputs), d, jnp.float32),
        # ``e_score_correction_bias``: zero as made (tests use another).
        "router_bias": _zeros(outputs),
        "w_gate": _normal(keys[1], (held, d, f), d, dt),
        "w_up": _normal(keys[2], (held, d, f), d, dt),
        "w_down": _normal(keys[3], (held, f, d), f, dt),
    }


def init_params(key: jax.Array, cfg: LongcatFlashConfig) -> Params:
    """The tree as it is held: matmul weights in ``cfg.dtype``, the
    router and the norms in float32. One program a sublayer, so that no
    more than one sublayer's float32 draws exist at a time."""
    params = init_ends(jax.random.fold_in(key, cfg.n_layers), cfg=cfg)
    blocks = []
    for i in range(cfg.n_layers):
        keys = jax.random.split(jax.random.fold_in(key, i), 5)
        blocks.append({
            "attn": tuple(_init_attn(k, cfg=cfg) for k in keys[:2]),
            "ffn": tuple(_init_ffn(k, cfg=cfg) for k in keys[2:4]),
            "moe": _init_moe(keys[4], cfg=cfg),
        })
    params["blocks"] = tuple(blocks)
    return params
