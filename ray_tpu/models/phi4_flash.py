"""Phi-4-mini-flash-reasoning (``model_type: phi4flash``; the SambaY
decoder, arXiv:2507.06607): a decoder whose second half keeps no cache.

Every layer is TWO sublayers, each behind its own LayerNorm (weight and
bias, eps 1e-5 — not RMSNorm) and a residual add: ``x <- x +
mixer(LN(x))``, then ``x <- x + W_down(silu(g) * u)``, ``[g; u] =
W_gate_up LN(x)`` (``D``, ``llm/hybrid_kv.py _dense_ffn``). No rotary or
other positional encoding anywhere; the head is the embedding's
transpose behind a final LayerNorm. The mixers, by published layer ``l``
of 32:

- *The self-decoder*, ``l < 18``. Even ``l`` (``S``, nine layers):
  **Mamba-1.** ``[x; z] = W_in u``; ``x = silu(conv4(x) + b)`` (causal,
  depthwise); ``[d; B; C] = W_x x`` (``dt_rank + 2 N``); ``dt =
  softplus(W_dt d + b_dt)``; ``A = -exp(A_log)``, a number a channel and
  state index; ``h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t``, ``y_t = C_t
  h_t + D x_t``; ``out = W_out (y * silu(z))``. The LAST such layer's
  ``y`` (before the gate) is the **memory** ``m_t`` that the
  cross-decoder's gated memory units read. On a TPU the recurrence is
  ``ops/pallas/selective_scan.py``'s two kernels (a chunk walked in
  VMEM; a decode step's update in place in the cache's stack), elsewhere
  ``lax.scan`` a token a step, which is also their oracle.
  Odd ``l < 17`` (``W``, eight layers): **differential attention over
  the last ``sliding_window`` keys**; ``l = 17`` (``*``): the same over
  the whole context, and the ONLY layer whose keys and values are kept
  for good. ``[q; k; v] = W_qkv u + b``: 40 query heads, 20 key/value
  heads, width 64. Heads pair up in order: pair ``j`` of 20 has queries
  ``q1 = q[2j]``, ``q2 = q[2j+1]`` and reads key/value pair ``g = j //
  2`` of 10: ``k1 = k[2g]``, ``k2 = k[2g+1]``, ``V = [v[2g]; v[2g+1]]``
  (128 wide). ``o_j = (softmax(q1 k1^T / 8) - lam softmax(q2 k2^T / 8))
  V``; ``o_j <- RMSNorm_128(o_j) (1 - lam_init)``; ``lam = exp(lq1 . lk1)
  - exp(lq2 . lk2) + lam_init``, ``lam_init = 0.8 - 0.6 exp(-0.3 l)``;
  the 20 x 128 outputs go through ``W_o`` (+ b).
- *The cross-decoder*, ``l >= 18``, which writes NOTHING to any cache.
  Even ``l`` (``U``, seven layers): the **gated memory unit**, ``out =
  W_2 (m_t * silu(W_1 u))``. Odd ``l`` (``C``, seven layers): **cross
  attention**: ``q = W_q u + b`` only; keys and values are layer 17's,
  read from its pages; the same differential form with the layer's own
  ``lam`` vectors, norm and ``lam_init``.

**How a pair meets the kernels.** The cache's cell is a key/value PAIR:
``cfg.n_kv_heads`` = 10 pairs of ``cfg.head_dim`` = 128, ``[k[2g];
k[2g+1]]`` as the projection leaves them (the same bytes as 20 heads of
64). A query head of 64 is held 128 wide with zeros in the half that is
not its key's (`_pad_queries`): its score against the pair is then ``q .
k[2g + s]`` exactly, its weighted values are the pair's 128, and the four
query heads of a pair are a plain grouped-query group of 4 over head
width 128. So the ring, the pool and the three attention kernels
(``window_attention``, ``prefill_attention``, ``paged_attention``) run as
for any family, each reading a pair's keys and values once; the zeros
cost nothing the chip could have used (a v5e's matrix unit contracts 128
deep: a product 64 deep fills half of it for the same passes).

The sizes are those of microsoft/Phi-4-mini-flash-reasoning, the public
model the benchmark serves through this file, whole. The config
subclasses ``NemotronHConfig`` for the reason ``models/granite_hybrid.py``
gives. NOT HERE: a backward pass, dropout.

ASSUMED (the config has no key for them; the model's own code and paper
state them; ``benchmarks/configs/phi4miniflash-serve1.json`` lists
each): Mamba's ``d_state`` 16, ``d_conv`` 4, ``expand`` 2, ``dt_rank`` =
``d_model / 16``; the pairing of heads and the ``lam`` form; biases on
the attention's projections; which layer makes the memory and which the
shared keys.

A norm's weight is stored as ``scale`` and applied as ``1 + scale``
(``ops/norms.py``), as everywhere in the repo; its bias as it is.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import ClassVar

import jax
import jax.numpy as jnp

from ray_tpu._private import chip
from ray_tpu.models.nemotron_h import NemotronHConfig, Params, _normal
from ray_tpu.ops.norms import layer_norm, rms_norm
from ray_tpu.ops.pallas.selective_scan import (
    selective_scan_chunk,
    selective_scan_reference,
    selective_state_step,
)

_LANES = 128  # the state's minor dimension (ops/pallas/selective_scan.py)


def sublayers(n_layers: int, self_layers: int | None = None) -> str:
    """``pattern`` for a model of ``n_layers`` whose first ``self_layers``
    (default ``n_layers // 2 + 2``: the published ``mb_per_layer`` 2) are
    the self-decoder: each layer's mixer and then ``D``. Self-decoder:
    ``S`` at even ``l``, ``W`` at odd ``l`` but the last, which is
    ``*``. Cross-decoder: ``U`` at even ``l``, ``C`` at odd."""
    if self_layers is None:
        self_layers = n_layers // 2 + 2
    if self_layers % 2 or n_layers % 2 or not 2 <= self_layers <= n_layers:
        raise ValueError("layers pair up: Mamba then attention, GMU then cross")

    def mixer(layer):
        if layer >= self_layers:
            return "C" if layer % 2 else "U"
        if layer % 2 == 0:
            return "S"
        return "*" if layer == self_layers - 1 else "W"

    return "".join(mixer(layer) + "D" for layer in range(n_layers))


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig(NemotronHConfig):
    vocab_size: int = 200064
    d_model: int = 2560
    pattern: str = sublayers(32)
    norm_eps: float = 1e-5
    layer_norm: bool = True
    tie_word_embeddings: bool = True
    # Differential attention. `n_kv_heads` counts key/value PAIRS (the
    # published 20 heads of 64 are 10 pairs of `head_dim` 128: the
    # cache's cell and the kernels' KV head); a query head is half a
    # pair wide and scores at that width's scale.
    n_heads: int = 40
    n_kv_heads: int = 10
    head_dim: int = 128
    attention_scale: float | None = 0.125  # 64 ** -0.5
    differential: bool = True
    ring_pages: bool = True  # 10 pairs would fill 10 of a tile's 16 rows
    sliding_window: int = 512
    # Mamba-1.
    ssm_state: int = 16
    conv_kernel: int = 4
    ssm_expand: int = 2
    dt_rank: int = 160
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    dense_d_ff: int = 10240
    expert_kind: str = "swiglu"
    # None of `moe_ffn`'s (the base class's defaults would describe
    # experts this model does not have).
    num_experts: int = 0
    top_k: int = 0
    max_seq: int = 262144

    block_kinds: ClassVar[str] = "S*WUCD"

    def __post_init__(self):
        if set(self.pattern) - set(self.block_kinds):
            raise ValueError(
                f"pattern {self.pattern!r}: blocks are of "
                f"{' '.join(self.block_kinds)}"
            )
        mixers = self.pattern[::2]
        first_cross = (
            len(mixers) if self.cross_from is None else self.cross_from // 2
        )
        if (
            set(mixers) - set("S*WUC") or set(self.pattern[1::2]) - set("D")
            or set(mixers[:first_cross]) - set("S*W")
            or set(mixers[first_cross:]) - set("UC")
        ):
            raise ValueError(
                f"pattern {self.pattern!r}: a layer is its mixer and then D; "
                "the self-decoder's (S, W, *) come before the "
                "cross-decoder's (U, C)"
            )
        if first_cross < len(mixers) and (
            self.count("*") != 1 or not self.count("S")
        ):
            raise ValueError(
                "a cross-decoder reads ONE full layer's keys and values and "
                "a Mamba layer's memory"
            )
        if self.n_heads != 4 * self.n_kv_heads or self.head_dim % 2:
            raise ValueError(
                "a key/value pair is read by two pairs of query heads"
            )
        if self.attention_scale != (self.head_dim // 2) ** -0.5:
            raise ValueError("scores are scaled at a query head's width")
        if self.d_inner % _LANES:
            raise ValueError(f"d_inner is held in tiles of {_LANES} channels")

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_rows(self) -> int:
        return self.d_inner // _LANES

    @property
    def cross_from(self) -> int | None:
        """The first sublayer of the cross-decoder: nothing from it on
        writes the cache. None where the pattern has none."""
        found = [i for i, kind in enumerate(self.pattern) if kind in "UC"]
        return found[0] if found else None

    def serving(self):
        from ray_tpu.llm.hybrid_kv import HybridServing

        return HybridServing(self, init_params)

    def num_params(self) -> int:
        """Parameters of the tree as `init_params` makes it (the tied
        embedding once; `lam_init`, a constant of the layer, not
        counted)."""
        d, di, n, r = self.d_model, self.d_inner, self.ssm_state, self.dt_rank
        norm = 2 * d
        pair = self.head_dim
        lam = 4 * (pair // 2) + pair
        wide = self.n_heads * pair // 2
        mixer = {
            "S": (norm + d * 2 * di + (self.conv_kernel + 1) * di
                  + di * (r + 2 * n) + r * di + di + di * n + di + di * d),
            "W": norm + d * 2 * wide + 2 * wide + lam + wide * d + d,
            "U": norm + 2 * d * di,
            "C": norm + d * wide + wide + lam + wide * d + d,
        }
        mixer["*"] = mixer["W"]
        return (
            sum(self.count(kind) * size for kind, size in mixer.items())
            + self.count("D") * (norm + 3 * d * self.dense_d_ff)
            + self.vocab_size * d + norm
        )


PHI4_FLASH_PRESETS: dict[str, Phi4FlashConfig] = {
    # CPU-test scale: every kind of layer (two Mamba, a window, the full
    # layer; two GMUs, two cross layers), the published switches, a
    # window of 8 positions.
    "phi4_flash_tiny": Phi4FlashConfig(
        vocab_size=256, d_model=64, pattern=sublayers(8, 4), n_heads=8,
        n_kv_heads=2, head_dim=16, attention_scale=8**-0.5, sliding_window=8,
        ssm_state=4, dt_rank=4, dense_d_ff=96, max_seq=256,
        dtype=jnp.float32,
    ),
}


def lam_init(layer: int) -> float:
    """``0.8 - 0.6 exp(-0.3 l)`` of the published layer ``l``."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


# ------------------------------------------------------------ parameters
def _norm_leaves(name: str, d: int) -> Params:
    return {name: jnp.zeros((d,), jnp.float32),
            f"{name}_bias": jnp.zeros((d,), jnp.float32)}


@partial(jax.jit, static_argnames="cfg")
def _init_mamba(key, cfg: Phi4FlashConfig) -> Params:
    """Mamba-1's own initialisation: ``dt`` log-uniform in
    [time_step_min, time_step_max] through softplus's inverse, ``A[n, c] =
    -(n + 1)``, ``D = 1``. ``A_log`` is held in the state's layout, [N,
    d_inner / 128, 128]."""
    d, di, dt = cfg.d_model, cfg.d_inner, cfg.dtype
    n, r, k = cfg.ssm_state, cfg.dt_rank, cfg.conv_kernel
    keys = jax.random.split(key, 7)
    step = jnp.exp(
        jax.random.uniform(keys[4], (di,))
        * (math.log(cfg.time_step_max) - math.log(cfg.time_step_min))
        + math.log(cfg.time_step_min)
    )
    step = jnp.maximum(step, cfg.time_step_floor)
    bound = k**-0.5  # a depthwise conv's fan-in is its kernel
    return {
        **_norm_leaves("norm", d),
        "in_proj": _normal(keys[0], (d, 2 * di), d, dt),
        "conv_w": jax.random.uniform(keys[1], (k, di), jnp.float32, -bound, bound),
        "conv_b": jax.random.uniform(keys[2], (di,), jnp.float32, -bound, bound),
        "x_proj": _normal(keys[3], (di, r + 2 * n), di, dt),
        "dt_proj": _normal(keys[5], (r, di), r, dt),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[:, None, None],
            (n, cfg.ssm_rows, _LANES),
        ),
        "D": jnp.ones((di,), jnp.float32),
        "out_proj": _normal(keys[6], (di, d), di, dt),
    }


def _init_lam(key, layer: int, cfg) -> Params:
    """The four ``lam`` vectors (normal, 0.1: Differential Transformer's),
    the norm a pair and the layer's ``lam_init``."""
    half = cfg.head_dim // 2
    return {
        "lam_q1": 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (half,)),
        "lam_k1": 0.1 * jax.random.normal(jax.random.fold_in(key, 2), (half,)),
        "lam_q2": 0.1 * jax.random.normal(jax.random.fold_in(key, 3), (half,)),
        "lam_k2": 0.1 * jax.random.normal(jax.random.fold_in(key, 4), (half,)),
        "sub_norm": jnp.zeros((cfg.head_dim,), jnp.float32),
        "lam_init": jnp.float32(lam_init(layer)),
    }


@partial(jax.jit, static_argnames=("layer", "cross", "cfg"))
def _init_attention(key, layer: int, cross: bool, cfg: Phi4FlashConfig) -> Params:
    """An attention block's tree: ``W_qkv`` (queries alone where
    ``cross``), ``W_o``, their biases, `_init_lam`'s."""
    d, dt = cfg.d_model, cfg.dtype
    wide = cfg.n_heads * cfg.head_dim // 2
    kv = 0 if cross else 2 * cfg.n_kv_heads * cfg.head_dim
    keys = jax.random.split(key, 5)
    return {
        **_norm_leaves("attn_norm", d),
        "wqkv": _normal(keys[0], (d, wide + kv), d, dt),
        "bqkv": 0.02 * jax.random.normal(keys[1], (wide + kv,)),
        "wo": _normal(keys[2], (wide, d), wide, dt),
        "bo": 0.02 * jax.random.normal(keys[3], (d,)),
        **_init_lam(keys[4], layer, cfg),
    }


@partial(jax.jit, static_argnames="cfg")
def _init_gmu(key, cfg: Phi4FlashConfig) -> Params:
    d, di, dt = cfg.d_model, cfg.d_inner, cfg.dtype
    keys = jax.random.split(key, 2)
    return {
        **_norm_leaves("norm", d),
        "w_in": _normal(keys[0], (d, di), d, dt),
        "w_out": _normal(keys[1], (di, d), di, dt),
    }


@partial(jax.jit, static_argnames="cfg")
def _init_dense(key, cfg: Phi4FlashConfig) -> Params:
    d, f, dt = cfg.d_model, cfg.dense_d_ff, cfg.dtype
    keys = jax.random.split(key, 3)
    return {
        **_norm_leaves("norm", d),
        "w_gate": _normal(keys[0], (d, f), d, dt),
        "w_up": _normal(keys[1], (d, f), d, dt),
        "w_down": _normal(keys[2], (f, d), f, dt),
    }


@partial(jax.jit, static_argnames="cfg")
def _init_ends(key, cfg: Phi4FlashConfig) -> Params:
    v, d = cfg.vocab_size, cfg.d_model
    return {
        "tok_emb": (
            jax.random.normal(key, (v, d), jnp.float32) * 0.02
        ).astype(cfg.dtype),
        **_norm_leaves("final_norm", d),
    }


def init_params(key: jax.Array, cfg: Phi4FlashConfig) -> Params:
    """The tree as it is held, one tree a SUBLAYER in ``cfg.pattern``'s
    order (matmul weights in ``cfg.dtype``; norms, biases, the
    convolution, Mamba's per-channel numbers and ``lam``'s in float32), a
    program a sublayer as ``nemotron_h.init_params``. No head: it is the
    embedding."""
    if not cfg.tie_word_embeddings:
        raise ValueError("models/phi4_flash.py holds a tied head")
    params = _init_ends(jax.random.fold_in(key, len(cfg.pattern)), cfg=cfg)

    def block(i, kind):
        k = jax.random.fold_in(key, i)
        if kind in "*WC":
            return _init_attention(k, layer=i // 2, cross=kind == "C", cfg=cfg)
        init = {"S": _init_mamba, "U": _init_gmu, "D": _init_dense}[kind]
        return init(k, cfg=cfg)

    params["blocks"] = tuple(block(i, kind) for i, kind in enumerate(cfg.pattern))
    return params


def _norm(x, p, name: str, cfg):
    return layer_norm(x, p[name], p[f"{name}_bias"], cfg.norm_eps)


# ---------------------------------------------------------------- Mamba-1
def _project_in(u, p, cfg):
    """``[x; z] = u W_in``, each d_inner wide."""
    with jax.named_scope("ssm:in_proj"):
        return jnp.split(u @ p["in_proj"], 2, axis=-1)


def _scan_inputs(x, p, cfg):
    """``[d; B; C] = x W_x`` and ``W_dt d`` (before its bias and
    softplus), of the convolution's output x [.., d_inner]."""
    with jax.named_scope("ssm:in_proj"):
        n, r = cfg.ssm_state, cfg.dt_rank
        low, b_in, c_in = jnp.split(x @ p["x_proj"], [r, r + n], axis=-1)
        return low @ p["dt_proj"], b_in, c_in


def _project_out(y, z, p, cfg):
    with jax.named_scope("ssm:out"):
        gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        return gated.astype(cfg.dtype) @ p["out_proj"]


def mamba1_chunked(u, p, cfg: Phi4FlashConfig, ssm0, conv0, length):
    """The Mamba-1 mixer over many tokens of one sequence. u [T, d]
    (normed input); ssm0 [N, R, 128] float32 and conv0 [K - 1, d_inner]
    the state before u[0]; ``length`` (traced) how many of the T tokens
    are real. Returns ((out [T, d], y [T, d_inner]: the scan's result
    before the gate, which is the memory where this is the last Mamba
    layer), the state and the convolution tail after token ``length -
    1``). Positions from ``length`` on take no step; their own outputs
    mean nothing (and are finite).

    On a TPU the recurrence is ``selective_scan_chunk`` (the state in
    VMEM through the chunk); elsewhere `selective_scan_reference`,
    ``lax.scan`` a token a step: tier 1's path and the kernel's oracle.
    The platform alone decides, as for ``moe_ffn``'s kernels."""
    t = u.shape[0]
    x, z = _project_in(u, p, cfg)
    with jax.named_scope("ssm:conv"):
        k = cfg.conv_kernel
        seq = jnp.concatenate([conv0.astype(x.dtype), x], axis=0)
        conv = p["conv_b"] + sum(
            seq[j: j + t].astype(jnp.float32) * p["conv_w"][j] for j in range(k)
        )
        # Row i of `seq` is the input at position i - (K - 1).
        conv_end = jax.lax.dynamic_slice_in_dim(seq, length, k - 1, axis=0)
        x = jax.nn.silu(conv).astype(cfg.dtype)
    dt, b_in, c_in = _scan_inputs(x, p, cfg)
    with jax.named_scope("ssm:scan"):
        scan = (
            selective_scan_chunk if chip.platform() == "tpu"
            else selective_scan_reference
        )
        y, end = scan(
            x, dt, b_in, c_in, -jnp.exp(p["A_log"]), p["D"], p["dt_bias"],
            ssm0, length,
        )
    return (_project_out(y, z, p, cfg), y), end, conv_end.astype(conv0.dtype)


def _step_operands(u, p, cfg, conv):
    """What one token's state update takes, of u [B, d] and the tail
    conv [B, K - 1, d_inner]: z, x, dt (after its bias and softplus), B,
    C, all float32 but z, and the next tail."""
    x, z = _project_in(u, p, cfg)
    with jax.named_scope("ssm:conv"):
        window = jnp.concatenate(
            [conv, x[:, None].astype(conv.dtype)], axis=1
        )  # [B, K, d_inner]
        out = p["conv_b"] + (
            window.astype(jnp.float32) * p["conv_w"][None]
        ).sum(1)
        x = jax.nn.silu(out).astype(cfg.dtype)
    dt, b_in, c_in = _scan_inputs(x, p, cfg)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    dt = jax.nn.softplus(f32(dt) + p["dt_bias"])
    return z, f32(x), dt, f32(b_in), f32(c_in), window[:, 1:]


def mamba1_step(u, p, cfg: Phi4FlashConfig, ssm, conv):
    """The mixer for ONE token of each of B sequences: u [B, d], ssm [B,
    N, R, 128] float32, conv [B, K - 1, d_inner]. Returns ((out [B, d],
    y [B, d_inner]), ssm, conv) after the token. The state is read,
    updated and read out in float32 elementwise arithmetic."""
    z, x, dt, b_in, c_in, tail = _step_operands(u, p, cfg, conv)
    with jax.named_scope("ssm:update"):
        a = -jnp.exp(p["A_log"]).reshape(cfg.ssm_state, -1)  # [N, d_inner]
        state = ssm.reshape(ssm.shape[0], *a.shape)
        state = (
            state * jnp.exp(dt[:, None, :] * a)
            + (dt * x)[:, None, :] * b_in[:, :, None]
        )
        y = (state * c_in[:, :, None]).sum(1) + p["D"] * x
        y = y.astype(cfg.dtype)
    return (_project_out(y, z, p, cfg), y), state.reshape(ssm.shape), tail


def mamba1_step_live(u, p, cfg: Phi4FlashConfig, stack, layer, conv, order,
                     count):
    """`mamba1_step` on a TPU, for the slots that decode: ``stack`` [L,
    B, N, R, 128] is every layer's state, of which ``stack[layer]`` is
    stepped IN PLACE for the first ``count`` slots of ``order`` and no
    other slot's state is read or written (`selective_state_step`).
    Returns ((out, y), the stack, conv after the token); a slot that
    does not decode gets ``y = D x`` (finite, and dropped)."""
    z, x, dt, b_in, c_in, tail = _step_operands(u, p, cfg, conv)
    with jax.named_scope("ssm:update"):
        stack, y = selective_state_step(
            stack, layer, order, count, x, dt, b_in, c_in, -jnp.exp(p["A_log"])
        )
        y = (y + p["D"] * x).astype(cfg.dtype)
    return (_project_out(y, z, p, cfg), y), stack, tail


# ----------------------------------------------- differential attention
def _pad_queries(q, cfg):
    """q [B, S, H, half] -> [B, S, H, 2 half]: head ``h``'s numbers in
    half ``h % 2`` of its pair's width, zeros in the other, so that its
    score against the pair ``[k[2g]; k[2g+1]]`` is ``q . k[2g + h % 2]``."""
    b, s, heads, half = q.shape
    q = q.reshape(b, s, heads // 2, 2, 1, half)
    place = jnp.eye(2, dtype=q.dtype).reshape(1, 1, 1, 2, 2, 1)
    return (q * place).reshape(b, s, heads, 2 * half)


def diff_inputs(x, p, cfg: Phi4FlashConfig, cross: bool = False):
    """An attention block's padded queries [B, S, H, Dh] and, but for a
    ``cross`` block, its key and value pairs [B, S, pairs, Dh], of x [B,
    S, d] (not normed)."""
    b, s, _ = x.shape
    half = cfg.head_dim // 2
    wide = cfg.n_heads * half
    h = _norm(x, p, "attn_norm", cfg)
    qkv = h @ p["wqkv"] + p["bqkv"].astype(h.dtype)
    q = _pad_queries(qkv[..., :wide].reshape(b, s, cfg.n_heads, half), cfg)
    if cross:
        return q, None, None
    k, v = jnp.split(qkv[..., wide:], 2, axis=-1)
    pairs = (b, s, cfg.n_kv_heads, cfg.head_dim)
    return q, k.reshape(pairs), v.reshape(pairs)


def diff_output(attn, p, cfg: Phi4FlashConfig):
    """From the heads' attention outputs attn [B, S, H, Dh] (head ``2j``
    pair j's first map, ``2j + 1`` its second) to the mixer's output [B,
    S, d]: the subtraction, the norm a pair times ``1 - lam_init``,
    ``W_o``."""
    with jax.named_scope("attn:diff"):
        b, s, heads, width = attn.shape
        maps = attn.astype(jnp.float32).reshape(b, s, heads // 2, 2, width)
        lam = (
            jnp.exp(jnp.dot(p["lam_q1"], p["lam_k1"]))
            - jnp.exp(jnp.dot(p["lam_q2"], p["lam_k2"])) + p["lam_init"]
        )
        out = maps[..., 0, :] - lam * maps[..., 1, :]
        out = rms_norm(out, p["sub_norm"], cfg.norm_eps) * (1.0 - p["lam_init"])
        out = out.astype(cfg.dtype).reshape(b, s, -1)
    return out @ p["wo"] + p["bo"].astype(cfg.dtype)


# ---------------------------------------------------- gated memory unit
def gmu(x, memory, p, cfg: Phi4FlashConfig):
    """``W_2 (m * silu(W_1 LN(x)))``: x [.., d], memory [.., d_inner]
    (the last Mamba layer's ``y`` at the same positions)."""
    h = _norm(x, p, "norm", cfg)
    with jax.named_scope("gmu:gate"):
        gated = memory.astype(jnp.float32) * jax.nn.silu(
            (h @ p["w_in"]).astype(jnp.float32)
        )
    with jax.named_scope("gmu:out"):
        return gated.astype(cfg.dtype) @ p["w_out"]
