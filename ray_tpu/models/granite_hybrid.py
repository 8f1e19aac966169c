"""Granite 4.0-H (``model_type: granitemoehybrid``): nine Mamba-2 layers
to one attention layer, a sparse-expert FFN in every layer, muP
multipliers and a tied head.

Every layer is TWO sublayers, each behind its own RMSNorm:
``x <- x + r * mixer(norm(x))``, then ``x <- x + r * ffn(norm(x))`` with
``r = residual_multiplier``. The mixer is a Mamba-2 state-space mixer
(``layer_types[l] == "mamba"``: ``models/nemotron_h.py``'s
``mamba_chunked`` and ``mamba_step``, here with one group shared by all
heads; on a TPU a prefill chunk's scan is ``ops/pallas/ssd_chunk.py``'s
one call a layer, 16 of the 128 heads a grid step, and a decode step's
state update ``ops/pallas/state_step.py``'s; off it XLA's forms, which
are the kernels' oracles: ``nemotron_h._dual_form``, ``mamba_step``) or grouped-query attention with no positional embedding and the
score scale ``attention_multiplier`` (not ``head_dim**-0.5``). The FFN
is ``models/moe.py``'s ``moe_ffn``: a softmax router over all experts
whose chosen gates are renormalised (which is the softmax over the
chosen logits), gated-SiLU experts and one gated shared expert. The
embedding is multiplied by ``embedding_multiplier``, the head is the
embedding's transpose and the logits are divided by ``logits_scaling``.
The sizes are those of ibm-granite/granite-4.0-h-small, the public
model the benchmark serves through this file.

To the serving programs (``llm/hybrid_kv.py``) a layer is two letters of
``pattern``, the mixer's (``M`` or ``*``) and ``E``: the programs loop
over sublayers, so Nemotron-H's one-mixer blocks and these layers run
through the same loop, cache and mixers, and the four multipliers are
fields that Nemotron-H holds at 1. That is why the config is a subclass:
every field the mixers, ``moe_ffn`` and the programs read is one of
``NemotronHConfig``'s, at this family's values.

A norm's weight is stored as ``scale`` and applied as ``1 + scale``
(``ops/norms.py``), as everywhere in the repo.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu.models.nemotron_h import (
    NemotronHConfig,
    Params,
    _init_block,
    _normal,
)

# granite-4.0-h-small's 40 layers: an attention layer at 5, 15, 25, 35.
LAYER_TYPES_SMALL = tuple(
    "attention" if i % 10 == 5 else "mamba" for i in range(40)
)
_MIXER = {"mamba": "M", "attention": "*"}


def sublayers(layer_types) -> str:
    """``pattern`` for ``layer_types``: each layer's mixer, then its
    expert FFN."""
    return "".join(_MIXER[kind] + "E" for kind in layer_types)


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig(NemotronHConfig):
    vocab_size: int = 100352  # rows held, where the vocabulary is sliced
    d_model: int = 4096
    pattern: str = sublayers(LAYER_TYPES_SMALL)
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 128
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    ssm_groups: int = 1
    ssm_state: int = 128
    chunk_size: int = 256
    num_experts: int = 72
    top_k: int = 10
    d_ff: int = 768
    shared_d_ff: int = 1536
    routed_scaling_factor: float = 1.0
    router_kind: str = "softmax"
    expert_kind: str = "swiglu"
    # Up to this many rows every held expert is applied to every row (a
    # decode step's 32), above it pairs are sorted into grouped matmuls
    # (a 2,048-token chunk's 20,480 pairs).
    dense_expert_rows: int = 256
    max_seq: int = 131072
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_scale: float | None = 0.0078125  # `attention_multiplier`
    logits_scaling: float = 16.0
    tie_word_embeddings: bool = True

    def __post_init__(self):
        super().__post_init__()
        if set(self.pattern[1::2]) != {"E"} or "E" in self.pattern[::2]:
            raise ValueError(
                f"pattern {self.pattern!r}: a layer is a mixer (M or *) "
                "and then its expert FFN (E)"
            )

    @property
    def d_ff_held(self) -> int:
        """Experts are held at their own width (768 is six tiles of 128
        lanes: the layout the grouped matmul takes is the array's own)."""
        return self.d_ff

    @property
    def layers(self) -> str:
        """Each layer's mixer: ``M`` or ``*``."""
        return self.pattern[::2]

    def serving(self):
        from ray_tpu.llm.hybrid_kv import HybridServing

        return HybridServing(self, init_params)


# ------------------------------------------------------------ parameters
@partial(jax.jit, static_argnames="cfg")
def _init_experts(key, cfg: GraniteHybridConfig) -> Params:
    """An expert FFN's tree: the router as wide as the model's experts,
    in float32; the held experts' and the shared expert's three
    matrices (``[a | b] = W_in u`` is held as its halves, ``w_gate`` and
    ``w_up``)."""
    d, dt = cfg.d_model, cfg.dtype
    held, f, fs = cfg.n_experts_held, cfg.d_ff, cfg.shared_d_ff
    keys = jax.random.split(key, 7)
    return {
        "norm": jnp.zeros((d,), jnp.float32),
        "router": _normal(keys[0], (d, cfg.num_experts), d, jnp.float32),
        "w_gate": _normal(keys[1], (held, d, f), d, dt),
        "w_up": _normal(keys[2], (held, d, f), d, dt),
        "w_down": _normal(keys[3], (held, f, d), f, dt),
        "shared_gate": _normal(keys[4], (d, fs), d, dt),
        "shared_up": _normal(keys[5], (d, fs), d, dt),
        "shared_down": _normal(keys[6], (fs, d), fs, dt),
    }


@partial(jax.jit, static_argnames="cfg")
def _init_ends(key, cfg: GraniteHybridConfig) -> Params:
    return {
        "tok_emb": (
            jax.random.normal(key, (cfg.vocab_size, cfg.d_model), jnp.float32)
            * 0.02
        ).astype(cfg.dtype),
        "final_norm": jnp.zeros((cfg.d_model,), jnp.float32),
    }


def init_params(key: jax.Array, cfg: GraniteHybridConfig) -> Params:
    """The tree as it is held, one tree a SUBLAYER in ``cfg.pattern``'s
    order (matmul weights in ``cfg.dtype``; router, norms, convolution
    and per-head SSM parameters in float32), a program a sublayer as
    ``nemotron_h.init_params``. The mixers' trees are that family's; the
    head is the embedding, so the tree has no ``lm_head``."""
    if not cfg.tie_word_embeddings:
        raise ValueError("models/granite_hybrid.py ties the head to the embedding")
    params = _init_ends(jax.random.fold_in(key, len(cfg.pattern)), cfg=cfg)
    params["blocks"] = tuple(
        _init_experts(jax.random.fold_in(key, i), cfg=cfg) if kind == "E"
        else _init_block(jax.random.fold_in(key, i), kind=kind, cfg=cfg)
        for i, kind in enumerate(cfg.pattern)
    )
    return params
