"""Laguna (``model_type: laguna``): three sliding-window attention layers
to one full attention layer, a gate a head on every one of them, a
sparse-expert FFN in every layer but the first.

Every layer is TWO sublayers, each behind its own RMSNorm (eps 1e-6):
``x <- x + attention(norm(x))``, then ``x <- x + ffn(norm(x))``. With
``n = norm(x)``, ``[T, d]``:

- ``*``, full attention: ``n_heads`` query heads over ``n_kv_heads`` KV
  heads of ``head_dim``; ``g = sigmoid(n W_g)``, ONE number a head and
  token; ``q`` and ``k`` rotated on the first ``rotary_dim`` dimensions
  of a head by YaRN's blended frequencies (``ops/rope.py
  yarn_inv_freq``), cos and sin times ``attention_factor``, the rest of
  the head passing through; causal over the whole context;
  ``(g_h softmax(q k^T / sqrt(Dh)) v)_h W_o``.
- ``W``, window attention: the same with ``window_heads`` query heads
  (more than a full layer's: the KV heads are the same 8), the whole
  head rotated at ``window_rope_theta`` without scaling, and query ``i``
  seeing key ``j`` iff ``i - sliding_window < j <= i``. What a sequence
  has to keep for such a layer is its last ``sliding_window`` keys and
  values, whatever its length: ``llm/hybrid_kv.py`` holds them per slot
  and not in pages.
- ``D``, the first layer's FFN: ``W_down(silu(m W_gate) * m W_up)`` of
  ``dense_d_ff``.
- ``E``, every other layer's: ``models/moe.py``'s ``moe_ffn`` as the
  other families call it (softmax over all experts, the ten largest
  renormalised and times ``routed_scaling_factor``, gated-SiLU experts,
  a shared expert added as it is).

The sizes are those of poolside/Laguna-S-2.1, the public model the
benchmark serves through this file. The config subclasses
``NemotronHConfig`` for the reason ``models/granite_hybrid.py`` gives:
the serving programs stay one loop over sublayers, and every field
they, ``moe_ffn`` and the attention blocks read is one of that class's
or, for the letters only this family's pattern holds (``W``, ``D``), one
of this one's. The attention blocks themselves are
``llm/hybrid_kv.py``'s. NOT HERE: a backward pass.

ASSUMED (the config does not say; ``benchmarks/configs/
laguna-s21-serve1.json`` lists each with its reason): a softmax router,
no norm on ``q`` and ``k``, the gate as one ``[d, H]`` matrix read from
the normed input, the shared expert ungated, the window counting the
query's own position.

A norm's weight is stored as ``scale`` and applied as ``1 + scale``
(``ops/norms.py``), as everywhere in the repo.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import ClassVar

import jax
import jax.numpy as jnp

from ray_tpu.models import granite_hybrid
from ray_tpu.models.nemotron_h import (
    NemotronHConfig,
    Params,
    _init_ends,
    _normal,
)

# `layer_types` of the published model: layer l attends everything where
# l % 4 == 0, else a window.
FULL_EVERY = 4


def sublayers(n_layers: int, full_every: int = FULL_EVERY,
              dense_layers: tuple = (0,)) -> str:
    """``pattern`` for the first ``n_layers`` layers: each layer's
    attention (``*`` where ``l % full_every == 0``, else ``W``), then its
    FFN (``D`` for the layers of ``dense_layers``, else ``E``)."""
    return "".join(
        ("W" if layer % full_every else "*")
        + ("D" if layer in dense_layers else "E")
        for layer in range(n_layers)
    )


@dataclasses.dataclass(frozen=True)
class LagunaConfig(NemotronHConfig):
    vocab_size: int = 100352  # rows held, where the vocabulary is sliced
    d_model: int = 3072
    pattern: str = sublayers(48)
    n_heads: int = 48  # a full layer's
    n_kv_heads: int = 8
    head_dim: int = 128
    head_gate: bool = True
    norm_eps: float = 1e-6
    # Full layers: YaRN on half of each head.
    rotary_dim: int = 64  # partial_rotary_factor 0.5 of 128
    rope_theta: float = 500000.0
    # (factor, original_max_position_embeddings, beta_fast, beta_slow,
    # attention_factor)
    rope_yarn: tuple | None = (128.0, 8192, 32.0, 1.0, 1.4852030263919618)
    # Window layers: their own head count, the whole head rotated.
    window_heads: int = 72
    sliding_window: int = 512
    window_rotary_dim: int = 128
    window_rope_theta: float = 10000.0
    dense_d_ff: int = 12288
    num_experts: int = 256
    top_k: int = 10
    d_ff: int = 1024
    shared_d_ff: int = 1024
    routed_scaling_factor: float = 2.5
    router_kind: str = "softmax"
    expert_kind: str = "swiglu"
    # Up to this many rows every held expert that got a row is applied to
    # every row (a decode step's 16), above it pairs are sorted into
    # grouped matmuls (a 2,048-token chunk's 20,480 pairs): the other
    # sparse families' boundary, whose calls have the same two sizes.
    dense_expert_rows: int = 256
    max_seq: int = 1048576

    block_kinds: ClassVar[str] = "*WDE"

    def __post_init__(self):
        super().__post_init__()
        if set(self.pattern[::2]) - set("*W") or set(self.pattern[1::2]) - set("DE"):
            raise ValueError(
                f"pattern {self.pattern!r}: a layer is its attention (* or "
                "W) and then its FFN (D or E)"
            )
        for heads in (self.n_heads, self.window_heads):
            if heads % self.n_kv_heads:
                raise ValueError("n_kv_heads does not divide a layer's heads")

    @property
    def d_ff_held(self) -> int:
        return self.d_ff

    def serving(self):
        from ray_tpu.llm.hybrid_kv import HybridServing

        return HybridServing(self, init_params)

    def num_params(self) -> int:
        """Parameters of the tree as `init_params` makes it for this
        config (held experts and held vocabulary rows)."""
        d, dh = self.d_model, self.head_dim
        kv = 2 * d * self.n_kv_heads * dh

        def attention(heads):
            return d + 2 * d * heads * dh + kv + d * heads

        ffn = {
            "D": d + 3 * d * self.dense_d_ff,
            "E": (d + d * self.num_experts
                  + self.n_experts_held * 3 * d * self.d_ff
                  + 3 * d * self.shared_d_ff),
        }
        return (
            self.count("*") * attention(self.n_heads)
            + self.count("W") * attention(self.window_heads)
            + sum(self.count(kind) * n for kind, n in ffn.items())
            + 2 * self.vocab_size * d + d
        )


LAGUNA_PRESETS: dict[str, LagunaConfig] = {
    # CPU-test scale: the dense layer and two whole periods, the
    # published switches; query groups of 3 and 2 heads.
    "laguna_tiny": LagunaConfig(
        vocab_size=256, d_model=64, pattern=sublayers(9), n_heads=4,
        n_kv_heads=2, head_dim=16, rotary_dim=8, rope_theta=100.0,
        rope_yarn=(8.0, 32, 4.0, 1.0, 1.2079441541679836),
        window_heads=6, sliding_window=8, window_rotary_dim=16,
        dense_d_ff=96, num_experts=8, top_k=3, d_ff=32, shared_d_ff=48,
        dense_expert_rows=8, max_seq=256, dtype=jnp.float32,
    ),
}


# ------------------------------------------------------------ parameters
@partial(jax.jit, static_argnames=("heads", "cfg"))
def _init_attention(key, heads: int, cfg: LagunaConfig) -> Params:
    """An attention block's tree at ``heads`` query heads: the three
    projections, the gate's ``[d, heads]`` and ``W_o``."""
    d, dt = cfg.d_model, cfg.dtype
    hq, hkv = heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    keys = jax.random.split(key, 5)
    return {
        "attn_norm": jnp.zeros((d,), jnp.float32),
        "wq": _normal(keys[0], (d, hq), d, dt),
        "wk": _normal(keys[1], (d, hkv), d, dt),
        "wv": _normal(keys[2], (d, hkv), d, dt),
        "wg": _normal(keys[3], (d, heads), d, dt),
        "wo": _normal(keys[4], (hq, d), hq, dt),
    }


@partial(jax.jit, static_argnames="cfg")
def _init_dense(key, cfg: LagunaConfig) -> Params:
    d, f, dt = cfg.d_model, cfg.dense_d_ff, cfg.dtype
    keys = jax.random.split(key, 3)
    return {
        "norm": jnp.zeros((d,), jnp.float32),
        "w_gate": _normal(keys[0], (d, f), d, dt),
        "w_up": _normal(keys[1], (d, f), d, dt),
        "w_down": _normal(keys[2], (f, d), f, dt),
    }


def init_params(key: jax.Array, cfg: LagunaConfig) -> Params:
    """The tree as it is held, one tree a SUBLAYER in ``cfg.pattern``'s
    order (matmul weights in ``cfg.dtype``; router and norms in
    float32), a program a sublayer as ``nemotron_h.init_params``. The
    expert FFN's tree is ``granite_hybrid``'s (a shared expert without a
    gate of its own). The head is its own matrix."""
    if cfg.tie_word_embeddings:
        raise ValueError("models/laguna.py holds an untied head")
    init = {
        "*": partial(_init_attention, heads=cfg.n_heads),
        "W": partial(_init_attention, heads=cfg.window_heads),
        "D": _init_dense,
        "E": granite_hybrid._init_experts,
    }
    params = _init_ends(jax.random.fold_in(key, len(cfg.pattern)), cfg=cfg)
    params["blocks"] = tuple(
        init[kind](jax.random.fold_in(key, i), cfg=cfg)
        for i, kind in enumerate(cfg.pattern)
    )
    return params
