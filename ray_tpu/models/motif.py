"""Motif-3-Beta's language model (``model_type: Motif``): grouped
differential attention on a latent cache in every layer, three layers
over a window of 128 keys to one over the whole context, four residual
streams, a PolyNorm FFN, dense in the leading layers and sparse experts
in the rest.

Every layer is TWO sublayers ``F`` (a mixer, then an FFN), each wrapped
by the residual path of ``models/mhc.py`` exactly as
``models/glm5_next.py`` has it: ``h = Hpre X``, ``u = RMSNorm(h)``, ``y =
F(u)`` (held within ``+-hidden_clamp``), ``X <- Hres X + Hpost^T y``.

- ``A`` / ``R``, **the mixer, GDLA** (grouped differential attention,
  arXiv:2510.06949, in Differential Transformer V2's form, over
  multi-head latent attention, arXiv:2405.04434): ``cq = RMSNorm(u
  W_qa)``, ``q = cq W_qb`` ``[80, 192]`` = ``[q_nope 128 | q_pe 64]``;
  ``[c | kpe] = u W_kva``, ``c <- RMSNorm(c)``; ``q_pe`` and ``kpe``
  rotated (plain rotary, split halves, one ``kpe`` for all heads). The
  cached cell is ``[c; kpe]``, 576 numbers held ``cell_width`` (640)
  wide. KV group ``g`` of 16: ``k_g = [c W_uk,g; kpe]``, ``v_g = c
  W_uv,g``. Query heads 0-63 are signal heads, 64-79 noise heads;
  signal head ``j`` and noise head ``64 + j // 4`` read group ``j //
  4``. ``A_h = softmax_t(q_h . k_g(h),t * 192^-0.5) v_g(h),t`` over the
  allowed ``t``; ``lambda = sigmoid(u W_lambda)`` ``[64]``; ``o_j = A_j -
  lambda_j A_{64 + j // 4}``; ``y = (o * sigmoid(u W_g)) W_o``, the gate
  element-wise. Allowed keys: ``A`` (a full layer) ``t <= i``; ``R`` (a
  window layer) ``i - W < t <= i``, ``W = sliding_window``.

  Inside, the heads are kept GROUP-MAJOR, ``[16, 5, .]``: a group's four
  signal heads and then its noise head, so that a kernel's "heads of a
  KV group" are neighbours (`_group_major`) and the subtraction is a
  slice of that axis (`_differential`).

  A prefill chunk attends in the expanded form (keys and values made
  from the cells, 16 groups' and not 80 heads'): the whole context by
  ``ops/pallas/latent_attention.py latent_prefill_attention`` over the
  request's pages, a window layer's band by
  ``ops/pallas/window_attention.py`` over the slot's ring of the last W
  cells and the chunk's own. A decode step attends in the absorbed form
  (``qa_h = q_nope_h W_uk,g^T`` against the cells as they lie): a full
  layer by ``latent_paged_attention`` at 80 rows a slot, a window layer
  over the slot's ring in XLA; the subtraction is taken on the weighted
  cells (it is linear), then 64 ``W_uv`` and not 80.
- ``D`` / ``E``: a dense FFN, or ``models/moe.py``'s ``moe_ffn`` (sigmoid
  scores, the 8 largest, gates renormalised times ``route_scale``, one
  shared expert ungated), each ``W_down (PolyNorm(u W_gate) * (u
  W_up))`` (``moe.poly_norm``; arXiv:2411.03884).

The sizes are those of Motif-Technologies/Motif-3-Beta, the public model
the benchmark serves through this file. The config subclasses
``NemotronHConfig`` for the reason ``models/granite_hybrid.py`` gives.
NOT HERE: the multi-token-prediction module, a backward pass.

ASSUMED (the config names these and no paper spells them out;
``benchmarks/configs/motif3beta-serve1.json`` lists each with its
reason): which heads are the noise heads and how they group; lambda's
form and input; no norm a head after the subtraction; the gate read
from the sublayer's normed input; plain rotary at both layer kinds and
the scale ``192^-0.5``; which layer of a period is full; PolyNorm's
parameter shapes and initial values; where ``hidden_clamp`` applies; no
router bias; the residual path's sizes and initial values.

A norm's weight is stored as ``scale`` and applied as ``1 + scale``
(``ops/norms.py``), as everywhere in the repo.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import ClassVar

import jax
import jax.numpy as jnp

from ray_tpu._private import chip
from ray_tpu.models.mhc import init_hc
from ray_tpu.models.nemotron_h import (
    NemotronHConfig,
    Params,
    _init_ends,
    _normal,
)
from ray_tpu.models.pangu_ultra_moe import pad_to_cell
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.pallas.latent_attention import (
    latent_expand,
    latent_paged_attention,
    latent_prefill_attention,
)
from ray_tpu.ops.pallas.window_attention import band_blocks, window_attention

_NEG_INF = -1e30


def sublayers(n_layers: int, dense: int, period: int, first: int = 0) -> str:
    """``pattern`` for published layers ``first .. first + n_layers - 1``:
    each layer's mixer (``A`` where ``l % period == period - 1``, else
    ``R``), then its FFN (``D`` in the first ``dense`` layers of the
    model, else ``E``)."""
    return "".join(
        ("A" if layer % period == period - 1 else "R")
        + ("D" if layer < dense else "E")
        for layer in range(first, first + n_layers)
    )


@dataclasses.dataclass(frozen=True)
class MotifConfig(NemotronHConfig):
    vocab_size: int = 220160  # rows held, where the vocabulary is sliced
    d_model: int = 4096
    pattern: str = sublayers(53, 2, 4)
    norm_eps: float = 1e-5
    # The residual path (`models/mhc.py`).
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hidden_clamp: float | None = 1e6
    # GDLA: `n_heads` query heads of which the last `noise_heads` are
    # noise heads, over `n_kv_heads` latent KV groups.
    n_heads: int = 80
    noise_heads: int = 16
    n_kv_heads: int = 16
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    sliding_window: int = 128
    # A cache cell is held this many lanes wide, a multiple of
    # (`models/pangu_ultra_moe.py`: 576 -> 640).
    cell_lanes: int = 128
    dense_d_ff: int = 12288
    num_experts: int = 384
    top_k: int = 8
    d_ff: int = 1280
    shared_d_ff: int = 1280
    routed_scaling_factor: float = 2.0
    router_kind: str = "sigmoid"
    expert_kind: str = "polynorm"
    polynorm_scale: float = 0.5
    polynorm_clamp: float = 0.5
    # The other sparse families' boundary between the every-row and the
    # sorted expert form, whose calls have the same two sizes here (a
    # decode step's 16 rows, a chunk's 2,048).
    dense_expert_rows: int = 256
    max_seq: int = 262144

    block_kinds: ClassVar[str] = "ARDE"

    def __post_init__(self):
        super().__post_init__()
        if set(self.pattern[::2]) - set("AR") or set(self.pattern[1::2]) - set("DE"):
            raise ValueError(
                f"pattern {self.pattern!r}: a layer is its mixer (A or R) "
                "and then its FFN (D or E)"
            )
        signal = self.n_heads - self.noise_heads
        if self.noise_heads != self.n_kv_heads or signal % self.n_kv_heads:
            raise ValueError(
                "a KV group is read by one noise head and an equal share of "
                "the signal heads"
            )
        if self.qk_rope_head_dim % 2:
            raise ValueError("the rotary part of a head pairs its dimensions")

    @property
    def d_ff_held(self) -> int:
        return self.d_ff

    @property
    def signal_heads(self) -> int:
        return self.n_heads - self.noise_heads

    @property
    def group_heads(self) -> int:
        """Query heads that read one KV group: its signal heads and its
        noise head."""
        return self.n_heads // self.n_kv_heads

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cell_width(self) -> int:
        return -(-self.latent_dim // self.cell_lanes) * self.cell_lanes

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        return self.qk_head_dim**-0.5

    def serving(self):
        from ray_tpu.llm.hybrid_kv import HybridServing

        return HybridServing(self, init_params)


MOTIF_PRESETS: dict[str, MotifConfig] = {
    # CPU-test scale: one leading dense layer and one whole period (so
    # every letter), the published switches, a window of 8 positions.
    "motif_tiny": MotifConfig(
        vocab_size=256, d_model=64, pattern=sublayers(5, 2, 4, first=1),
        n_heads=10, noise_heads=2, n_kv_heads=2, q_lora_rank=24,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, sliding_window=8, cell_lanes=16, dense_d_ff=96,
        num_experts=8, top_k=3, d_ff=32, shared_d_ff=32,
        dense_expert_rows=8, max_seq=256, dtype=jnp.float32,
    ),
}


# ------------------------------------------------------------ parameters
def _init_poly(shape=()) -> Params:
    """PolyNorm's ``w`` ``[3]`` and ``b`` ``[1]`` an FFN (an expert each
    where ``shape`` counts them): thirds and zero, Motif-2.6B's."""
    return {
        "poly_w": jnp.full((*shape, 3), 1.0 / 3.0, jnp.float32),
        "poly_b": jnp.zeros((*shape, 1), jnp.float32),
    }


@partial(jax.jit, static_argnames="cfg")
def _init_gdla(key, cfg: MotifConfig) -> Params:
    """A mixer's tree: the two low-rank paths with their norms, the 16
    groups' up-projections ``w_uk`` / ``w_uv`` ``[G, rank, .]``, lambda's
    and the gate's matrices, ``W_o``. Every product has unit variance, so
    that lambda spreads over (0.2, 0.8) a token and the noise heads'
    maps differ from the signal heads'."""
    d, dt = cfg.d_model, cfg.dtype
    rq, rkv, groups = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.n_kv_heads
    width = cfg.signal_heads * cfg.v_head_dim
    keys = jax.random.split(key, 9)
    return {
        "attn_norm": jnp.zeros((d,), jnp.float32),
        "wq_a": _normal(keys[0], (d, rq), d, dt),
        "q_norm": jnp.zeros((rq,), jnp.float32),
        "wq_b": _normal(keys[1], (rq, cfg.n_heads * cfg.qk_head_dim), rq, dt),
        "wkv_a": _normal(keys[2], (d, cfg.latent_dim), d, dt),
        "kv_norm": jnp.zeros((rkv,), jnp.float32),
        "w_uk": _normal(keys[3], (groups, rkv, cfg.qk_nope_head_dim), rkv, dt),
        "w_uv": _normal(keys[4], (groups, rkv, cfg.v_head_dim), rkv, dt),
        "w_lambda": _normal(keys[5], (d, cfg.signal_heads), d, dt),
        "wg": _normal(keys[6], (d, width), d, dt),
        "wo": _normal(keys[7], (width, d), width, dt),
        "hc": init_hc(keys[8], cfg),
    }


@partial(jax.jit, static_argnames="cfg")
def _init_dense(key, cfg: MotifConfig) -> Params:
    d, f, dt = cfg.d_model, cfg.dense_d_ff, cfg.dtype
    keys = jax.random.split(key, 4)
    return {
        "norm": jnp.zeros((d,), jnp.float32),
        "w_gate": _normal(keys[0], (d, f), d, dt),
        "w_up": _normal(keys[1], (d, f), d, dt),
        "w_down": _normal(keys[2], (f, d), f, dt),
        **_init_poly(),
        "hc": init_hc(keys[3], cfg),
    }


@partial(jax.jit, static_argnames="cfg")
def _init_experts(key, cfg: MotifConfig) -> Params:
    """An expert FFN's tree: the router as wide as the model's experts,
    in float32 (no bias: none is published); the held experts' and the
    shared expert's three matrices and PolyNorm numbers."""
    d, dt = cfg.d_model, cfg.dtype
    held, f, fs = cfg.n_experts_held, cfg.d_ff, cfg.shared_d_ff
    keys = jax.random.split(key, 8)
    shared = _init_poly()
    return {
        "norm": jnp.zeros((d,), jnp.float32),
        "router": _normal(keys[0], (d, cfg.num_experts), d, jnp.float32),
        "w_gate": _normal(keys[1], (held, d, f), d, dt),
        "w_up": _normal(keys[2], (held, d, f), d, dt),
        "w_down": _normal(keys[3], (held, f, d), f, dt),
        **_init_poly((held,)),
        "shared_gate": _normal(keys[4], (d, fs), d, dt),
        "shared_up": _normal(keys[5], (d, fs), d, dt),
        "shared_down": _normal(keys[6], (fs, d), fs, dt),
        "shared_poly_w": shared["poly_w"],
        "shared_poly_b": shared["poly_b"],
        "hc": init_hc(keys[7], cfg),
    }


_INIT = {"A": _init_gdla, "R": _init_gdla, "D": _init_dense, "E": _init_experts}


def init_params(key: jax.Array, cfg: MotifConfig) -> Params:
    """The tree as it is held, one tree a SUBLAYER in ``cfg.pattern``'s
    order (matmul weights in ``cfg.dtype``; router, norms, PolyNorm's
    numbers and the residual mixing in float32), a program a sublayer as
    ``nemotron_h.init_params``. The head is its own matrix."""
    if cfg.tie_word_embeddings:
        raise ValueError("models/motif.py holds an untied head")
    params = _init_ends(jax.random.fold_in(key, len(cfg.pattern)), cfg=cfg)
    params["blocks"] = tuple(
        _INIT[kind](jax.random.fold_in(key, i), cfg=cfg)
        for i, kind in enumerate(cfg.pattern)
    )
    return params


# ------------------------------------------------------------ the mixer
def _rope(x, positions, theta: float):
    """x [.., D] rotated at ``positions`` (x's leading shape but for
    axes of one, which broadcast), split halves; float32 inside."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def _group_major(heads, cfg):
    """Heads as the weights order them, [N, H, x] (signal heads, then
    noise heads) -> [N, G, H / G, x]: a group's signal heads, then its
    noise head."""
    n, _, width = heads.shape
    groups = cfg.n_kv_heads
    signal, noise = jnp.split(heads, [cfg.signal_heads], axis=1)
    return jnp.concatenate(
        [signal.reshape(n, groups, -1, width), noise[:, :, None]], axis=2
    )


def _gdla_inputs(h, p, cfg, positions):
    """A mixer's inputs of h [N, d] (not normed) at ``positions`` [N]:
    the queries' two parts [N, G, H / G, nope] and [N, G, H / G, rope]
    (rotated), the cells [N, cell_width] in ``cfg.dtype``, lambda [N,
    signal heads] float32 and the gate's pre-activation [N, signal heads
    x v]."""
    u = rms_norm(h, p["attn_norm"], cfg.norm_eps)
    with jax.named_scope("mla:q"):
        cq = rms_norm(u @ p["wq_a"], p["q_norm"], cfg.norm_eps)
        q = (cq @ p["wq_b"]).reshape(-1, cfg.n_heads, cfg.qk_head_dim)
        q_nope, q_pe = jnp.split(
            _group_major(q, cfg), [cfg.qk_nope_head_dim], axis=-1
        )
        q_pe = _rope(q_pe, positions[:, None, None], cfg.rope_theta)
    with jax.named_scope("mla:latent"):
        c, kpe = jnp.split(u @ p["wkv_a"], [cfg.kv_lora_rank], axis=-1)
        c = rms_norm(c, p["kv_norm"], cfg.norm_eps)
        kpe = _rope(kpe, positions, cfg.rope_theta)
        cells = pad_to_cell(
            jnp.concatenate([c, kpe], axis=-1), cfg
        ).astype(cfg.dtype)
    with jax.named_scope("gdla:diff"):
        lam = jax.nn.sigmoid(
            jnp.dot(u, p["w_lambda"], preferred_element_type=jnp.float32)
        )
    with jax.named_scope("attn:gate"):
        gate = u @ p["wg"]
    return q_nope, q_pe, cells, lam, gate


def _differential(heads, lam, cfg):
    """``o_j = A_j - lambda_j A_noise(j)``: heads [N, G, H / G, x] ->
    [N, signal heads, x]. Linear in the heads: on a decode step's
    weighted cells as on a chunk's outputs."""
    with jax.named_scope("gdla:diff"):
        n, groups, _, width = heads.shape
        signal = heads[:, :, :-1].astype(jnp.float32)
        noise = heads[:, :, -1:].astype(jnp.float32)
        out = signal - lam.reshape(n, groups, -1, 1) * noise
        return out.astype(cfg.dtype).reshape(n, -1, width)


def _gdla_out(o, gate, p, cfg):
    """The heads' outputs o [N, signal heads, v] times ``sigmoid(gate)``,
    element-wise, then ``W_o``: [N, d]."""
    with jax.named_scope("attn:gate"):
        gated = (
            o.reshape(gate.shape).astype(jnp.float32)
            * jax.nn.sigmoid(gate.astype(jnp.float32))
        ).astype(cfg.dtype)
    with jax.named_scope("mla:out"):
        return gated @ p["wo"]


def _expand(cells, p, cfg):
    """cells [T, cell_width] -> the groups' keys without their rotary
    part and values, [G, T, nope] and [G, T, v]."""
    with jax.named_scope("mla:expand"):
        c = cells[:, : cfg.kv_lora_rank]
        return (jnp.einsum("tc,gcd->gtd", c, p["w_uk"]),
                jnp.einsum("tc,gcd->gtd", c, p["w_uv"]))


def _heads_first(x):
    """[C, G, r, x] -> [G r, C, x]."""
    c, groups, rep, width = x.shape
    return x.transpose(1, 2, 0, 3).reshape(groups * rep, c, width)


def _attend_dense(q_nope, q_pe, k_nope, kpe, v, hidden, cfg):
    """The expanded attention by dense float32 scores: q [C, G, r, .],
    k_nope and v [G, T, .], kpe [T, rope], ``hidden`` [C, T] True where
    a query does not see a key. What runs off the TPU and the kernels'
    oracle, in float32 throughout (the CPU's dot takes no bfloat16
    operands summed in float32 in these orders). Returns [C, G, r, v]."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    scores = (
        jnp.einsum("cgrd,gtd->grct", f32(q_nope), f32(k_nope))
        + jnp.einsum("cgrd,td->grct", f32(q_pe), f32(kpe))
    ) * cfg.softmax_scale
    probs = jax.nn.softmax(jnp.where(hidden, _NEG_INF, scores), axis=-1)
    return jnp.einsum("grct,gtd->cgrd", probs, f32(v)).astype(v.dtype)


def gdla_prefill_full(h, p, cfg: MotifConfig, pool, base, pages, chunk_pages,
                      start, use_kernel: bool):
    """A full layer's mixer for one chunk of one slot. h [C, d] at
    positions ``start ..`` (page-aligned); ``pool`` the cache's ``cells``
    [pages, P, cell_width], flat over the layers (``base`` this layer's
    first page); ``pages`` the context's table, ``chunk_pages`` the
    chunk's own. Writes the chunk's cells, turns the table's cells back
    into 16 groups' keys and values (an earlier chunk's again) and
    attends them causally: where ``use_kernel`` by `latent_expand` and
    the prefill kernel, neither of which touches a key block past the
    chunk's last; else the whole table under dense scores. Returns (out
    [C, d], pool)."""
    c_len = h.shape[0]
    page = pool.shape[1]
    positions = start + jnp.arange(c_len, dtype=jnp.int32)
    q_nope, q_pe, cells, lam, gate = _gdla_inputs(h, p, cfg, positions)
    with jax.named_scope("mla:latent"):
        pool = pool.at[base + chunk_pages].set(
            cells.reshape(-1, page, cells.shape[-1])
        )
    table = jnp.take(pool, base + pages, axis=0, mode="clip")
    table = table.reshape(-1, table.shape[-1])  # [T, cell_width]
    rank = cfg.kv_lora_rank
    if use_kernel:
        interpret = chip.platform() != "tpu"
        with jax.named_scope("mla:expand"):
            k_nope, v = latent_expand(
                table, p["w_uk"], p["w_uv"], start, n_queries=c_len,
                interpret=interpret,
            )
        with jax.named_scope("mla:attend"):
            heads = latent_prefill_attention(
                _heads_first(q_nope), _heads_first(pad_to_cell(q_pe, cfg)),
                k_nope, table[:, rank:], v, start, scale=cfg.softmax_scale,
                interpret=interpret,
            )  # [H, C, v]
            heads = heads.reshape(
                cfg.n_kv_heads, -1, c_len, heads.shape[-1]
            ).transpose(2, 0, 1, 3)
    else:
        k_nope, v = _expand(table, p, cfg)
        with jax.named_scope("mla:attend"):
            hidden = jnp.arange(table.shape[0])[None, :] > positions[:, None]
            heads = _attend_dense(
                q_nope, q_pe, k_nope, table[:, rank: cfg.latent_dim], v,
                hidden, cfg,
            )
    return _gdla_out(_differential(heads, lam, cfg), gate, p, cfg), pool


def _rolled(rows, first):
    """Rows ``first, first + 1, ..`` of rows [W, x], W of them, around
    the end: a slice of the rows twice over (a gather by index would
    have XLA lay the whole ring out for it, and copy it in and out of
    every program)."""
    w = rows.shape[0]
    return jax.lax.dynamic_slice_in_dim(
        jnp.concatenate([rows, rows], axis=0), first % w, w, axis=0
    )


def gdla_prefill_window(h, p, cfg: MotifConfig, ring, at, start, n_live,
                        use_kernel: bool):
    """A window layer's mixer for one chunk of one slot. h [C, d] at
    positions ``start ..``, of which the first ``n_live`` are real;
    ``ring`` the cache's ``win_cells`` [L, B, W, cell_width], ``at``
    (layer, slot). The slot's W carried cells, put in the order of their
    positions ``start - W .. start - 1``, and the chunk's own are turned
    into keys and values and the chunk attends the band, by the band
    kernel where ``use_kernel`` and blocks divide the shapes; then the
    last W REAL cells go back to the ring, each to its own index
    (`hybrid_kv._window_prefill`'s scheme, over cells). Returns (out [C,
    d], ring)."""
    c_len, w = h.shape[0], cfg.sliding_window
    positions = start + jnp.arange(c_len, dtype=jnp.int32)
    q_nope, q_pe, cells, lam, gate = _gdla_inputs(h, p, cfg, positions)
    rank = cfg.kv_lora_rank
    with jax.named_scope("attn:window"):
        # Position start - W + j lies at ring index (start + j) % W.
        band = jnp.concatenate(
            [_rolled(ring[at], start), cells], axis=0
        )  # [W + C, cell_width]
        k_nope, v = _expand(band, p, cfg)
        if use_kernel and band_blocks(c_len, w) is not None:
            # A key as the kernel takes it: [k_nope | kpe; zeros], the
            # rotary part as the cell holds it; the queries alike.
            groups = cfg.n_kv_heads
            kpe = jnp.broadcast_to(
                band[None, :, rank:], (groups, *band[:, rank:].shape)
            )
            q = jnp.concatenate([q_nope, pad_to_cell(q_pe, cfg)], axis=-1)
            heads = window_attention(
                q.reshape(c_len, cfg.n_heads, -1),
                jnp.concatenate([k_nope, kpe], axis=-1), v, start, window=w,
                scale=cfg.softmax_scale, interpret=chip.platform() != "tpu",
            ).reshape(c_len, groups, -1, cfg.v_head_dim)
        else:
            ahead = jnp.arange(w + c_len)[None, :] - jnp.arange(c_len)[:, None]
            hidden = (ahead < 1) | (ahead > w) | (
                jnp.arange(w + c_len)[None, :] < w - start
            )
            heads = _attend_dense(
                q_nope, q_pe, k_nope, band[:, rank: cfg.latent_dim], v,
                hidden, cfg,
            )
    with jax.named_scope("attn:window_write"):
        # Positions end - W .. end - 1 lie at n_live .. n_live + W - 1 of
        # the band; ring index r takes the one of them that is r mod W.
        last = jax.lax.dynamic_slice_in_dim(band, n_live, w, axis=0)
        ring = ring.at[at].set(_rolled(last, -(start + n_live)))
    return _gdla_out(_differential(heads, lam, cfg), gate, p, cfg), ring


def _absorb(q_nope, q_pe, p, cfg):
    """A decode step's queries against the cells as they lie: ``[q_nope
    W_uk,g^T; q_pe; zeros]`` [B, H, cell_width], group-major."""
    with jax.named_scope("mla:absorb"):
        absorbed = jnp.einsum("bgrd,gcd->bgrc", q_nope, p["w_uk"])
        q = pad_to_cell(jnp.concatenate([absorbed, q_pe], axis=-1), cfg)
        return q.reshape(q.shape[0], cfg.n_heads, -1).astype(cfg.dtype)


def _absorbed_out(weighted, lam, gate, p, cfg):
    """From the heads' weighted cells [B, H, rank] (group-major) to the
    mixer's output [B, d]: the subtraction on the cells, ``W_uv`` of each
    signal head's group, the gate, ``W_o``."""
    b = weighted.shape[0]
    mixed = _differential(
        weighted.reshape(b, cfg.n_kv_heads, cfg.group_heads, -1), lam, cfg
    )
    with jax.named_scope("mla:absorb"):
        o = jnp.einsum(
            "bgrc,gcd->bgrd",
            mixed.reshape(b, cfg.n_kv_heads, -1, mixed.shape[-1]), p["w_uv"],
        )
    return _gdla_out(o.reshape(b, cfg.signal_heads, -1), gate, p, cfg)


def gdla_decode_full(h, p, cfg: MotifConfig, pool, base, geometry, positions,
                     use_kernel: bool):
    """A full layer's mixer for one token of every slot. h [B, d] at
    ``positions`` [B]; ``geometry`` `paged_kv._decode_geometry`'s (a slot
    that does not decode has a table of -1 and writes the dump page).
    Absorbed: by ``latent_paged_attention`` over the pool in place where
    ``use_kernel``, else over the gathered window under a mask. Returns
    (out [B, d], pool)."""
    _, mask, write_pages, off_of, tables = geometry
    q_nope, q_pe, cells, lam, gate = _gdla_inputs(h, p, cfg, positions)
    with jax.named_scope("mla:latent"):
        pool = pool.at[base + write_pages[:, 0], off_of[:, 0]].set(cells)
    q = _absorb(q_nope, q_pe, p, cfg)
    rank = cfg.kv_lora_rank
    with jax.named_scope("mla:attend"):
        if use_kernel:
            weighted = latent_paged_attention(
                q[:, None], pool, base + tables, positions, v_width=rank,
                scale=cfg.softmax_scale, interpret=chip.platform() != "tpu",
            )[:, 0]
        else:
            window = jnp.take(pool, base + tables, axis=0)  # [B, n, P, W]
            window = window.reshape(q.shape[0], -1, window.shape[-1])
            scores = jnp.einsum(
                "bhw,btw->bht", q, window, preferred_element_type=jnp.float32
            ) * cfg.softmax_scale
            probs = jax.nn.softmax(
                jnp.where(mask[:, :1], _NEG_INF, scores), axis=-1
            ).astype(cfg.dtype)
            weighted = jnp.einsum("bht,btc->bhc", probs, window[..., :rank])
    return _absorbed_out(weighted, lam, gate, p, cfg), pool


def gdla_decode_window(h, p, cfg: MotifConfig, ring, layer: int, positions,
                       active):
    """A window layer's mixer for one token of every slot: the cell goes
    to index ``position % W`` of the slot's ring (a slot that does not
    decode writes nothing: it may be mid-prefill, and its ring is that
    prefill's), then the absorbed queries attend the ring. Index ``r``
    holds the newest position ``<= t`` that is ``r mod W``; where that
    is negative the request has not written it and it is masked. Plain
    XLA: the scores are [B, H, W]. Returns (out [B, d], ring)."""
    b, w = h.shape[0], cfg.sliding_window
    q_nope, q_pe, cells, lam, gate = _gdla_inputs(h, p, cfg, positions)
    with jax.named_scope("attn:window_write"):
        index = jnp.where(active, positions % w, w)  # w: dropped
        ring = ring.at[layer, jnp.arange(b), index].set(cells, mode="drop")
    q = _absorb(q_nope, q_pe, p, cfg)
    with jax.named_scope("attn:window"):
        t = positions[:, None]
        held = t - (t - jnp.arange(w, dtype=positions.dtype)[None, :]) % w
        scores = jnp.einsum(
            "bhw,btw->bht", q, ring[layer], preferred_element_type=jnp.float32
        ) * cfg.softmax_scale
        probs = jax.nn.softmax(
            jnp.where((held < 0)[:, None, :], _NEG_INF, scores), axis=-1
        ).astype(cfg.dtype)
        weighted = jnp.einsum(
            "bht,btc->bhc", probs, ring[layer][..., : cfg.kv_lora_rank]
        )
    return _absorbed_out(weighted, lam, gate, p, cfg), ring
