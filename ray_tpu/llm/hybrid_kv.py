"""Serving programs for a model whose blocks differ in kind
(``models/nemotron_h.py``, ``models/granite_hybrid.py``,
``models/qwen3_next.py``): pages for the blocks that attend, per-slot
recurrent state for the blocks that carry one.

A block here is one SUBLAYER, a letter of ``cfg.pattern``: a mixer or
an expert FFN behind its own norm and a residual add. Nemotron-H's
layers are one each; a Granite 4.0-H layer is two (its mixer, then
``E``) and multiplies each sublayer's output, the embedding, the
attention scores and the logits by numbers of its config, which the
programs skip where they are 1 (``_embed``, ``_residual``, ``_logits``).
A Qwen3-Next layer is two as well; its recurrent mixer is another
letter (``G``, the gated delta rule) with its own leaves of the cache,
and its attention block norms, rotates and gates (`_attention_inputs`),
which the other families' configs hold off. A Laguna layer
(``models/laguna.py``) is two as well: its attention is of two kinds,
``*`` over the whole context from pages and ``W`` over the last
``cfg.sliding_window`` positions from per-slot leaves of the cache, each
kind with its own head count and rotary scheme (`_attention_kind`), and
its first layer's FFN is dense (``D``). A GLM-5.3-Flash layer
(``models/glm5_next.py``) is two as well: a third recurrence (``K``, the
delta rule with a decay a key channel) or latent attention over the keys
an indexer picks (``L``), whose cells and pooled index keys are two more
page pools on the SAME block tables; its FFNs clamp their SwiGLU, and
what its programs carry between sublayers is ``cfg.hc_mult`` residual
streams that each sublayer mixes on the way in and out (`_read`,
`_residual`), which the other families' configs hold off. A Motif-3-Beta
layer (``models/motif.py``) is two as well and carries the same streams:
its mixer is latent attention WITH a rotary part whose 80 query heads
share 16 expanded KV groups and subtract one attention map from another,
over the whole context (``A``: cells in one more page pool on the
request's block table, attended by ``ops/pallas/latent_attention.py``'s
two kernels) or over the last ``cfg.sliding_window`` positions (``R``:
a per-slot ring of latent CELLS, not of keys and values); its FFNs are
PolyNorm's (`_dense_ffn`, ``moe_ffn``).

The cache is ONE donated tree with two kinds of per-sequence state:

- ``k``, ``v`` ``[L_attn, num_pages, Hkv, P, Dh]``: `paged_kv.py`'s page
  pool, for the attention blocks only, in its one layout, reached
  through block tables;
- ``ssm`` ``[L_mamba, max_batch, H, P, N]`` float32 and ``conv``
  ``[L_mamba, max_batch, K - 1, conv_dim]``: each decode SLOT's Mamba-2
  state and convolution tail. It is not paged and does not grow. Where
  the pattern has ``G`` blocks, ``gdn`` ``[L_gdn, max_batch, Hv, dk, dv]``
  float32 and ``gdn_conv`` ``[L_gdn, max_batch, K - 1, conv_dim]`` are
  the same for them: a matrix a head. A cache holds the leaves of the
  kinds its pattern has (`_RECURRENT`);
- ``win_k``, ``win_v`` ``[L_window, max_batch, W, Hkv, Dh]``: each
  slot's last ``W = cfg.sliding_window`` keys and values in each window
  layer, the key of position ``t`` at ring index ``t % W`` (positions
  major over heads: a decode step writes one ``[Hkv, Dh]`` row a slot,
  and it is the layout XLA gives the leaf in both programs when asked
  for the other). Where ``cfg.ring_pages`` the two are ``[L_window,
  max_batch * W / P + 1, Hkv, P, Dh]``: a slot's ring is ``W / P`` PAGES
  of the pool's page size in the pool's cell layout (ring index ``r`` at
  cell ``r % P`` of the slot's page ``r // P``), and a layer's last page
  is its dump page. A decode step then writes and attends a ring with
  the pool's own two kernels (`_decode_attention` over this second,
  small pool: a window is the paged kernel's causal mask at position
  ``min(t, W - 1)``), which fix its layout. (Why: a model whose ``Hkv``
  does not fill a tile's rows, 10 pairs for 16, would hold ``[.., W, Hkv,
  Dh]`` 1.6 times its size; XLA then picks ``[.., Hkv, W, Dh]`` for the
  einsums and ``[.., W, Hkv, Dh]`` for the scatter whichever the leaf is
  given as, and the decode program copied both leaves in and out, 1.3 GB
  a step at 8 layers x 32 slots: the compiled text, PR 68.) They do not grow with the context either, and a
  request's pages are the ``*`` layers' alone. (The other design, one pool and pages released behind
  the window, was not taken: a slot's pages would differ by layer kind,
  so one block table a request could not serve both, and the kernels
  that read pages would each need a first page; the ring costs ``W``
  cells a slot and layer whether the request is long or short, 0.1 GB of
  13 at the benchmark's sizes.) What a ring holds that its request did
  not write (a slot's last request's keys, or zeros) lies at positions
  that every reader masks by the TRUE position it computes for the
  index: nothing is cleared;
- ``latent`` ``[L_latent, num_pages, P, rank]`` and ``index``
  ``[L_latent, num_pages, P / pool, Di]``: a token's latent cell and, a
  block of ``pool = cfg.index_kpool`` positions, the indexer's pooled
  key, for the ``L`` blocks only, reached through the request's block
  table like ``k`` and ``v`` (a request's pages are counted once: every
  paged leaf has ``num_pages`` pages a layer). ``index_tail``
  ``[L_latent, max_batch, pool - 1, Di]`` float32 is each slot's last
  ``pool - 1`` indexer keys: a block's pooled key is complete only with
  its last token, which may come in a decode step, so the open block's
  keys wait here, the block is no query's candidate until it is whole
  (`glm5_next._select`), and the step that completes it writes the mean
  of the tail and its own key (`glm5_next.dsa_decode`);
- ``cells`` ``[L_full, num_pages, P, cell_width]``: the ``A`` blocks'
  latent cells ``[c; kpe; zeros]`` (576 numbers held 640 wide), reached
  through the request's block table like the other pools, and
  ``win_cells`` ``[L_window, max_batch, W, cell_width]``: each slot's
  last ``W`` cells in each ``R`` layer, position ``t`` at ring index ``t
  % W``, masked by true position and never cleared, as ``win_k`` /
  ``win_v``. Two kinds of latent state in the one donated tree: the
  page pool counts the full layers only.

Both are carried through the Python loop over the pattern and updated in
place (a block writes its own layer's slot rows; nothing is sliced out
and stacked back).

Two programs. ``prefill_program`` (one jitted program a shape) runs a
prompt, or one chunk of it, for one slot: it takes the slot, where the
chunk starts and the context's TRUE length. From position 0 it starts
from zero state, whatever the slot's last request left; a later chunk starts from the state the chunk
before it left. Positions from the true length on are padding: they take
no step of the recurrence, the convolution tail is the last real
inputs, their expert pairs are left out, and the logits returned are
the last real token's alone. ``hybrid_decode`` advances every slot by
one token and takes the slots that are decoding: a slot that is free or
mid-prefill keeps its state. Attention is `paged_kv.py`'s own
definitions (projections, page writes, the gather path and the two
Pallas kernel paths: a prefill program compiled with ``use_kernel``
attends a table of more than 1,024 keys by
``ops/pallas/prefill_attention.py`` over the request's pages as they
lie, with no scores over the table in HBM), the expert mixer is
``moe_ffn``.
"""

from __future__ import annotations

import contextlib
import functools
from collections.abc import Callable
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu._private import chip
from ray_tpu.llm.paged_kv import (
    _NEG_INF,
    _decode_attention,
    _decode_geometry,
    _flat_pool,
    _gather_page_attention,
    _head,
    _prefill_kernel_attention,
    _sample_tokens,
    _write_pages,
    init_paged_kv,
)
from ray_tpu.llm.serving import Serving, _new_record, _note, _record, leaf_bytes
from ray_tpu.models.glm5_next import (
    dsa_decode,
    dsa_prefill,
    kda_chunked,
    kda_step,
    kda_step_live,
)
from ray_tpu.models.mhc import mhc_mix, mhc_spread
from ray_tpu.models.moe import clamped_swiglu, moe_ffn, poly_glu, poly_terms
from ray_tpu.models.motif import (
    gdla_decode_full,
    gdla_decode_window,
    gdla_prefill_full,
    gdla_prefill_window,
)
from ray_tpu.models.nemotron_h import (
    NemotronHConfig,
    init_params,
    mamba_chunked,
    mamba_step,
    mamba_step_live,
)
from ray_tpu.models.phi4_flash import (
    diff_inputs,
    diff_output,
    gmu,
    mamba1_chunked,
    mamba1_step,
    mamba1_step_live,
)
from ray_tpu.models.qwen3_next import gdn_chunked, gdn_step, gdn_step_live
from ray_tpu.ops.norms import layer_norm, rms_norm
from ray_tpu.ops.pallas.latent_attention import keys_expanded
from ray_tpu.ops.pallas.state_step import live_order
from ray_tpu.ops.pallas.window_attention import (
    band_blocks,
    window_attention,
    window_attention_dense,
)
from ray_tpu.ops.rope import yarn_inv_freq

HybridCache = dict[str, jnp.ndarray]

# A prefill program compiled with ``use_kernel`` attends by the prefill
# kernel where its table holds more keys than this, and by dense scores
# under a mask up to it: there the scores are small (32 heads x 512
# queries x 1,024 keys in float32: 67 MB) and take what the kernel's
# launch and the gather of its pages take (ops/pallas/
# prefill_attention.py has the chip's readings), and every program of
# `nemotron-reason-32` (tables of 64 to 1,024 tokens) lowers as it did
# before the kernel came: in two pairs on the chip that cell read 0.9%
# and 0.2% lower with the kernel in them (PR 39). Above it the scores
# grow with the table (4.3 GB at 2,048 queries x 16,384 keys).
_DENSE_ATTENTION_KEYS = 1024


class _Recurrent(NamedTuple):
    """A kind of recurrent block: its mixer over many tokens, over one
    token of every slot (``step``: states in, states out, and
    `hybrid_decode` writes them back under the mask of the slots that
    decode) and, on a TPU, over one token of the slots that decode
    (``step_live``: the cache's whole stack in and out, updated in place
    by ``ops/pallas/state_step.py``, which owns that mask: a slot that
    does not decode is not read), the cache's leaves for its state and
    its convolution tail, the prefix of its named scopes, and a slot's
    (state shape, convolution channels) from a config. ``memory``: the
    three return ``(out, y)`` for ``out``, ``y`` what the mixer computed
    before its gate, which the programs carry on to the blocks that read
    it (`gmu`)."""

    chunked: Callable
    step: Callable
    step_live: Callable
    state: str
    conv: str
    scope: str
    shapes: Callable
    memory: bool = False


_RECURRENT = {
    "M": _Recurrent(
        mamba_chunked, mamba_step, mamba_step_live, "ssm", "conv", "ssm",
        lambda c: ((c.mamba_heads, c.mamba_head_dim, c.ssm_state), c.conv_dim),
    ),
    "G": _Recurrent(
        gdn_chunked, gdn_step, gdn_step_live, "gdn", "gdn_conv", "gdn",
        lambda c: (
            (c.gdn_value_heads, c.gdn_key_dim, c.gdn_value_dim),
            c.gdn_conv_dim,
        ),
    ),
    "K": _Recurrent(
        kda_chunked, kda_step, kda_step_live, "kda", "kda_conv", "kda",
        lambda c: (
            (c.kda_heads, c.kda_head_dim, c.kda_head_dim), c.kda_conv_dim
        ),
    ),
    # Mamba-1: a decay a channel and state index, the state in
    # `ops/pallas/selective_scan.py`'s layout.
    "S": _Recurrent(
        mamba1_chunked, mamba1_step, mamba1_step_live, "ssm1", "ssm1_conv",
        "ssm", lambda c: ((c.ssm_state, c.ssm_rows, 128), c.d_inner),
        memory=True,
    ),
}

# The leaves that are page pools: a request's block table reaches each.
_PAGED = ("k", "v", "latent", "index", "cells")
# What an ``L`` block reads and writes of the cache, in `dsa_prefill`'s
# and `dsa_decode`'s order.
_LATENT_LEAVES = ("latent", "index", "index_tail")


def init_hybrid_cache(
    cfg: NemotronHConfig, num_pages: int, page_size: int, max_batch: int
) -> HybridCache:
    cache = init_paged_kv(cfg, num_pages, page_size, n_layers=cfg.count("*"))
    for kind, block in _RECURRENT.items():
        if n := cfg.count(kind):
            state_shape, channels = block.shapes(cfg)
            cache[block.state] = jnp.zeros(
                (n, max_batch, *state_shape), jnp.float32
            )
            cache[block.conv] = jnp.zeros(
                (n, max_batch, cfg.conv_kernel - 1, channels), cfg.dtype
            )
    if n := cfg.count("W"):
        ring = (n, max_batch, cfg.sliding_window, cfg.n_kv_heads, cfg.head_dim)
        if cfg.ring_pages:
            # A slot's ring as W / P pages of the pool's page size, in
            # the pool's cell layout; one dump page a layer behind them.
            if cfg.sliding_window % page_size:
                raise ValueError("the page size does not divide the window")
            ring = (n, max_batch * (cfg.sliding_window // page_size) + 1,
                    cfg.n_kv_heads, page_size, cfg.head_dim)
        cache["win_k"] = jnp.zeros(ring, cfg.dtype)
        cache["win_v"] = jnp.zeros(ring, cfg.dtype)
    if n := cfg.count("L"):
        pool, width = cfg.index_kpool, cfg.index_head_dim
        if page_size % pool:
            raise ValueError("index_kpool does not divide the page size")
        cache["latent"] = jnp.zeros(
            (n, num_pages, page_size, cfg.kv_lora_rank), cfg.dtype
        )
        cache["index"] = jnp.zeros(
            (n, num_pages, page_size // pool, width), cfg.dtype
        )
        cache["index_tail"] = jnp.zeros(
            (n, max_batch, pool - 1, width), jnp.float32
        )
    if n := cfg.count("A"):
        cache["cells"] = jnp.zeros(
            (n, num_pages, page_size, cfg.cell_width), cfg.dtype
        )
    if n := cfg.count("R"):
        cache["win_cells"] = jnp.zeros(
            (n, max_batch, cfg.sliding_window, cfg.cell_width), cfg.dtype
        )
    return cache


def _embed(params, tokens, cfg):
    x = params["tok_emb"][tokens]
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    if cfg.hc_mult:
        # A copy a residual stream: [.., n, d] from here to `_logits`.
        x = jnp.broadcast_to(
            x[..., None, :], (*x.shape[:-1], cfg.hc_mult, x.shape[-1])
        )
    return x


def _norm(x, p, cfg, name: str = "norm"):
    """A sublayer's norm as the config has it: RMSNorm, or LayerNorm
    with a bias where ``cfg.layer_norm``."""
    if cfg.layer_norm:
        return layer_norm(x, p[name], p[f"{name}_bias"], cfg.norm_eps)
    return rms_norm(x, p[name], cfg.norm_eps)


def _read(x, p, cfg):
    """A sublayer's input from what the programs carry, and what
    `_residual` needs to write its output back: ``x`` itself and nothing
    for a model of one residual stream; `mhc_mix`'s learned mix of the
    streams [.., n, d] and its two write-back matrices for a model with
    ``cfg.hc_mult`` of them."""
    if not cfg.hc_mult:
        return x, None
    return mhc_mix(x, p["hc"], cfg)


def _residual(x, out, cfg, mix=None):
    """``x + residual_multiplier * out``: a sublayer's output added on
    (``mix``, `_read`'s: spread over the streams by `mhc_spread`), held
    within ``+-cfg.hidden_clamp`` first where the model has one."""
    if cfg.hidden_clamp is not None:
        out = jnp.clip(out, -cfg.hidden_clamp, cfg.hidden_clamp)
    if mix is not None:
        return mhc_spread(x, out, *mix)
    if cfg.residual_multiplier != 1.0:
        out = out * cfg.residual_multiplier
    return x + out


def _experts(x, p, cfg, rows_live, record):
    """An expert block on x [B, S, d], and its counters onto ``record``."""
    h, mix = _read(x, p, cfg)
    out, aux = moe_ffn(
        rms_norm(h, p["norm"], cfg.norm_eps), p, cfg, rows_live=rows_live
    )
    _note(record, aux)
    return _residual(x, out, cfg, mix)


def _carried(cache, k_pages, v_pages, state) -> HybridCache:
    """The cache as a program hands it on: the flat pool back in the
    argument's shape, the recurrent leaves as the loop left them."""
    return {
        "k": k_pages.reshape(cache["k"].shape),
        "v": v_pages.reshape(cache["v"].shape),
        **{name: leaf.reshape(cache[name].shape) if name in _PAGED else leaf
           for name, leaf in state.items()},
    }


def _recurrent_leaves(cache) -> HybridCache:
    """The leaves the loop carries as they are; the latent blocks' two
    pools in a flat view over the layers, as `_flat_pool`'s."""
    return {
        name: (
            leaf.reshape(-1, *leaf.shape[2:]) if name in _PAGED else leaf
        )
        for name, leaf in cache.items() if name not in ("k", "v")
    }


def _attention_kind(cfg, kind: str):
    """What differs between a model's kinds of attention block: (query
    heads, the dimensions of a head that are rotated, theta, YaRN's
    numbers or None) for the letter ``kind``. ``W``'s are fields of the
    one family whose pattern holds the letter."""
    if kind == "W":
        return (cfg.window_heads, cfg.window_rotary_dim,
                cfg.window_rope_theta, None)
    return cfg.n_heads, cfg.rotary_dim, cfg.rope_theta, cfg.rope_yarn


def _rotate(x, positions, rotary_dim, theta, yarn=None):
    """A rotary embedding on the first ``rotary_dim`` dimensions of each
    head of x [B, S, H, Dh] at ``positions`` [B, S] (split halves within
    those; the rest pass through), float32 inside. ``yarn`` (factor,
    original length, beta_fast, beta_slow, attention_factor): YaRN's
    blended frequencies, cos and sin times the last."""
    half = rotary_dim // 2
    if yarn is None:
        inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    else:
        inv_freq = yarn_inv_freq(rotary_dim, theta, *yarn[:4])
    angles = positions.astype(jnp.float32)[..., None, None] * inv_freq
    cos, sin = jnp.cos(angles), jnp.sin(angles)  # [B, S, 1, half]
    if yarn is not None:
        cos, sin = cos * yarn[4], sin * yarn[4]
    x1, x2, rest = jnp.split(
        x.astype(jnp.float32), [half, rotary_dim], axis=-1
    )
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1
    ).astype(x.dtype)


def _attention_inputs(x, p, cfg, positions, kind: str = "*"):
    """An attention block's q [B, S, H, Dh], k and v [B, S, Hkv, Dh] of
    x [B, S, d] at ``positions`` [B, S], and the heads' output gate
    ([B, S, H, Dh], or [B, S, H, 1] where it is one number a head; None
    where the model has none), for a block of the letter ``kind``. What
    a family adds is skipped at its config's off value, so that the
    others' programs are `paged_kv._project_qkv`'s three products and
    nothing else."""
    if cfg.differential:
        # Paired heads (`models/phi4_flash.py`): queries held a pair
        # wide, keys and values as pairs; no rotation, no gate.
        return (*diff_inputs(x, p, cfg, cross=kind == "C"), None)
    b, s, _ = x.shape
    dh = cfg.head_dim
    n_heads, rotary_dim, theta, yarn = _attention_kind(cfg, kind)
    h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
    q = h @ p["wq"]
    gate = None
    if cfg.attn_output_gate:
        # Head by head [query | gate].
        q, gate = jnp.split(q.reshape(b, s, n_heads, 2 * dh), 2, axis=-1)
    elif cfg.head_gate:
        gate = (h @ p["wg"])[..., None]
    q = q.reshape(b, s, n_heads, dh)
    k = (h @ p["wk"]).reshape(b, s, cfg.n_kv_heads, dh)
    v = (h @ p["wv"]).reshape(b, s, cfg.n_kv_heads, dh)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if rotary_dim:
        q = _rotate(q, positions, rotary_dim, theta, yarn)
        k = _rotate(k, positions, rotary_dim, theta, yarn)
    return q, k, v, gate


def _attention_output(attn, gate, p, cfg):
    """attn [B, S, H, Dh] (times ``sigmoid(gate)`` where the model gates
    its heads) through ``W_o``: [B, S, d]."""
    if cfg.differential:
        return diff_output(attn, p, cfg)
    if gate is not None:
        with jax.named_scope("attn:gate"):
            attn = (
                attn * jax.nn.sigmoid(gate.astype(jnp.float32))
            ).astype(cfg.dtype)
    return attn.reshape(*attn.shape[:2], -1) @ p["wo"]


def _full_scope(cfg, reader: str | None = None):
    """The scope a ``*`` block's page write and attention run under where
    the model has window layers beside it, so that a trace tells the two
    kinds apart; none for a model with one kind, whose programs stay as
    they were. ``reader``: a child scope that says which block attends
    the pool layer (``self``: the one that owns and writes it; ``cross``:
    one that reads it with its own queries), where the model has both."""
    if not cfg.count("W"):
        return contextlib.nullcontext()
    if reader is None or not cfg.count("C"):
        return jax.named_scope("attn:full")
    return jax.named_scope(f"attn:full/{reader}")


def _window_prefill(q, k, v, win_k, win_v, at, start, n_live, cfg,
                    use_kernel: bool):
    """A window block's attention for one chunk of one slot, and the
    slot's ring after it. q [C, H, Dh], k and v [C, Hkv, Dh] at positions
    ``start ..``, of which the first ``n_live`` are real; ``win_k`` /
    ``win_v`` the cache's leaves, ``at`` (layer, slot). The chunk attends
    the slot's W carried keys, put in the order of their positions
    ``start - W .. start - 1``, and then its own, by the band kernel
    where ``use_kernel`` and blocks divide the shapes
    (``ops/pallas/window_attention.py``), else by dense scores under the
    same mask. Then the last W REAL positions go back to the ring, each
    to its own index. Returns (attn [C, H, Dh], win_k, win_v)."""
    c, w = q.shape[0], cfg.sliding_window
    # The axis of a slot's ring (and of the band) that positions lie
    # along: [W, Hkv, Dh], or [Hkv, W, Dh] where the rings are pages
    # (`_slot_ring`).
    along = 1 if cfg.ring_pages else 0
    with jax.named_scope("attn:window"):
        # Ring index of position start - W + j.
        order = (start + jnp.arange(w, dtype=jnp.int32)) % w

        def band(ring, new):
            carried = jnp.take(_slot_ring(ring, at, cfg), order, axis=along)
            if along:
                new = new.transpose(1, 0, 2)
            return jnp.concatenate(
                [carried, new.astype(ring.dtype)], axis=along
            )

        keys, values = band(win_k, k), band(win_v, v)  # [W + C, Hkv, Dh]
        if along:
            head_major = (q, keys, values)
        else:
            head_major = (q, keys.transpose(1, 0, 2), values.transpose(1, 0, 2))
        if use_kernel and band_blocks(c, w) is not None:
            attn = window_attention(
                *head_major, start, window=w, scale=cfg.attention_scale,
                interpret=chip.platform() != "tpu",
            )
        else:
            attn = window_attention_dense(
                *head_major, start, window=w, scale=cfg.attention_scale
            )
    with jax.named_scope("attn:window_write"):
        # Positions end - W .. end - 1 lie at n_live .. n_live + W - 1 of
        # the band; ring index r takes the one of them that is r mod W.
        back = (jnp.arange(w, dtype=jnp.int32) - (start + n_live)) % w

        def left(ring, band_):
            last = jax.lax.dynamic_slice_in_dim(band_, n_live, w, axis=along)
            last = jnp.take(last, back, axis=along)
            if not cfg.ring_pages:
                return ring.at[at].set(last)
            hkv, _, dh = last.shape
            pages = last.reshape(hkv, -1, ring.shape[3], dh).transpose(1, 0, 2, 3)
            layer, slot = at
            return jax.lax.dynamic_update_slice(
                ring, pages[None], (layer, slot * pages.shape[0], 0, 0, 0)
            )

        return attn.astype(q.dtype), left(win_k, keys), left(win_v, values)


def _slot_ring(ring, at, cfg):
    """A slot's ring in one layer, by ring index: ``ring[at]`` [W, Hkv,
    Dh], or, where the rings are pages, the slot's W / P pages put
    together, [Hkv, W, Dh]."""
    if not cfg.ring_pages:
        return ring[at]
    layer, slot = at
    per = cfg.sliding_window // ring.shape[3]
    pages = jax.lax.dynamic_slice_in_dim(ring[layer], slot * per, per, axis=0)
    return pages.transpose(1, 0, 2, 3).reshape(
        ring.shape[2], cfg.sliding_window, ring.shape[4]
    )


def _window_decode_pages(q, k, v, win_k, win_v, layer: int, positions, active,
                         cfg, use_kernel: bool):
    """`_window_decode` where the rings are pages: `_decode_attention`
    over the rings as a second pool. A slot's table is its W / P pages of
    the layer; its cell goes to ring index ``position % W`` (a slot that
    is not decoding: to the layer's dump page); it attends cells ``<=
    min(position, W - 1)``, which are the positions ``> position - W``
    the request has written (a softmax does not ask their order)."""
    b = q.shape[0]
    w, page = cfg.sliding_window, win_k.shape[3]
    per, held = w // page, win_k.shape[1]
    slots = jnp.arange(b, dtype=jnp.int32)
    cell = positions % w
    seen = jnp.minimum(positions, w - 1)
    geometry = (
        None,
        jnp.arange(w)[None, None, :] > seen[:, None, None],  # hidden
        jnp.where(active, slots * per + cell // page, held - 1)[:, None],
        (cell % page)[:, None],
        slots[:, None] * per + jnp.arange(per, dtype=jnp.int32)[None, :],
    )

    def flat(ring):
        return ring.reshape(-1, *ring.shape[2:])

    with jax.named_scope("attn:window"):
        attn, flat_k, flat_v = _decode_attention(
            q, k.astype(cfg.dtype), v.astype(cfg.dtype), flat(win_k),
            flat(win_v), layer * held, geometry, seen, cfg, use_kernel,
            cfg.attention_scale,
        )
    return attn, flat_k.reshape(win_k.shape), flat_v.reshape(win_v.shape)


def _window_decode(q, k, v, win_k, win_v, layer: int, positions, active, cfg):
    """A window block's attention for one token of every slot: q [B, 1,
    H, Dh] over each slot's ring after its k, v [B, 1, Hkv, Dh] went to
    index ``position % W`` (a slot that is not decoding writes nothing:
    it may be mid-prefill, and its ring is that prefill's). Ring index
    ``r`` holds the newest position ``<= t`` that is ``r mod W``; where
    that is negative the request has not written it and it is masked.
    Plain XLA: the scores are [B, H, W]. Returns (attn [B, 1, H, Dh],
    win_k, win_v)."""
    b, _, n_heads, dh = q.shape
    w, hkv = cfg.sliding_window, cfg.n_kv_heads
    with jax.named_scope("attn:window_write"):
        cell = jnp.where(active, positions % w, w)  # w: dropped
        slots = jnp.arange(b)
        win_k = win_k.at[layer, slots, cell].set(
            k[:, 0].astype(win_k.dtype), mode="drop"
        )
        win_v = win_v.at[layer, slots, cell].set(
            v[:, 0].astype(win_v.dtype), mode="drop"
        )
    with jax.named_scope("attn:window"):
        t = positions[:, None]
        held = t - (t - jnp.arange(w, dtype=positions.dtype)[None, :]) % w
        scale = dh**-0.5 if cfg.attention_scale is None else cfg.attention_scale
        scores = jnp.einsum(
            "bgrd,bwgd->bgrw", q[:, 0].reshape(b, hkv, n_heads // hkv, dh),
            win_k[layer], preferred_element_type=jnp.float32,
        ) * scale
        scores = jnp.where((held < 0)[:, None, None, :], _NEG_INF, scores)
        probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
        attn = jnp.einsum("bgrw,bwgd->bgrd", probs, win_v[layer])
    return attn.reshape(b, 1, n_heads, dh), win_k, win_v


def _dense_ffn(x, p, cfg):
    """A dense gated FFN sublayer on x [B, S, d]: gated by SiLU, or by
    PolyNorm where that is the model's ``expert_kind``."""
    h, mix = _read(x, p, cfg)
    with jax.named_scope("ffn:dense"):
        h = _norm(h, p, cfg)
        if cfg.expert_kind == "polynorm":
            act = poly_glu(
                h, p["w_gate"], p["w_up"],
                poly_terms(cfg, p["poly_w"], p["poly_b"]), cfg.norm_eps,
            )
        else:
            act = clamped_swiglu(h, p["w_gate"], p["w_up"], cfg.swiglu_limit)
        out = act @ p["w_down"]
    return _residual(x, out, cfg, mix)


def _logits(x, params, cfg):
    """Final norm and the head as the config has them, on the sum of
    the residual streams where the programs carry more than one."""
    if cfg.hc_mult:
        x = x.astype(jnp.float32).sum(-2).astype(x.dtype)
    if cfg.layer_norm:
        x = _norm(x, params, cfg, "final_norm")
        return jnp.einsum(
            "...d,vd->...v", x, params["tok_emb"]
        ).astype(jnp.float32)
    return _head(
        x, params, cfg.norm_eps, cfg.tie_word_embeddings, cfg.logits_scaling
    )


def _hybrid_prefill(
    params,
    tokens: jnp.ndarray,  # [1, C] int32, C = chunk_pages * page_size
    cache: HybridCache,
    pages: jnp.ndarray,  # [n_write_pages] int32: the FULL context table
    start: jnp.ndarray,  # [] int32: position of tokens[0, 0], page-aligned
    slot: jnp.ndarray,  # [] int32: the decode slot this prompt is for
    length: jnp.ndarray,  # [] int32: the context's true length
    cfg: NemotronHConfig,
    n_write_pages: int,
    chunk_pages: int,
    use_kernel: bool,
    self_only: bool = False,
):
    """A prompt (``start`` 0, ``chunk_pages == n_write_pages``) or one
    chunk of it. ``self_only`` (a model with a cross-decoder,
    ``cfg.cross_from``, whose blocks write nothing to the cache): the
    chunk does not hold position ``length - 1``, so the program stops
    where the cross-decoder begins and returns no logits; without it the
    cross-decoder and the head run on that ONE row (`_last_row`). ``use_kernel``: where the table holds more than
    ``_DENSE_ATTENTION_KEYS`` keys the chunk's queries attend the
    context's pages, gathered as they lie, by the prefill kernel;
    otherwise dense float32 scores over the whole table under a mask
    (at a 256-page table and a 2,048-token chunk 4.3 GB of them).
    Returns (logits [1, 1, V] float32 of position ``length - 1`` —
    meaningful in the chunk that holds it —, cache, record; the record
    None where no block leaves one)."""
    c = tokens.shape[1]
    page_size = cache["k"].shape[3]
    num_pages = cache["k"].shape[1]
    window = n_write_pages * page_size
    pos = start + jnp.arange(c, dtype=jnp.int32)[None, :]  # [1, C]
    live = (pos < length)[0]  # [C]
    chunk_slice = jax.lax.dynamic_slice(
        pages, [start // page_size], [chunk_pages]
    )
    by_kernel = use_kernel and window > _DENSE_ATTENTION_KEYS
    if not by_kernel:
        mask = jnp.arange(window)[None, None, :] > pos[:, :, None]
    k_pages, v_pages = _flat_pool(cache)
    state = _recurrent_leaves(cache)
    x = _embed(params, tokens, cfg)
    record = _new_record()
    seen = dict.fromkeys(cfg.block_kinds, 0)  # blocks of each kind so far
    memory = None  # the last `memory` block's y, [C, d_inner]
    cross_from = cfg.cross_from
    for i, (kind, p) in enumerate(
        zip(cfg.pattern, params["blocks"], strict=True)
    ):
        if i == cross_from:
            if self_only:
                break
            # Nothing from here on writes the cache, and one row's
            # logits are returned: that row alone goes on.
            x = _last_row(x, start, length)
            memory = _last_row(memory[None], start, length)
        if kind in _RECURRENT:
            block = _RECURRENT[kind]
            fresh = start == 0
            at = (seen[kind], slot)
            h, mix = _read(x, p, cfg)
            out, s_end, c_end = block.chunked(
                _norm(h, p, cfg)[0], p, cfg,
                jnp.where(fresh, 0.0, state[block.state][at]),
                jnp.where(fresh, 0, state[block.conv][at]),
                jnp.clip(length - start, 0, c),
            )
            if block.memory:
                out, memory = out
            with jax.named_scope(f"{block.scope}:scan"):
                state[block.state] = state[block.state].at[at].set(s_end)
                state[block.conv] = state[block.conv].at[at].set(c_end)
            x = _residual(x, out[None], cfg, mix)
        elif kind == "U":
            x = _residual(x, gmu(x, memory, p, cfg), cfg)
        elif kind == "C":
            # The one row's queries over the request's pages of the `*`
            # block's pool layer (the only one: base 0), up to its own
            # position. Nothing is written.
            q, _, _, _ = _attention_inputs(x, p, cfg, None, kind)
            with _full_scope(cfg, "cross"):
                attn = _attend_pages(
                    q, k_pages, v_pages, pages[None, :],
                    (jnp.arange(window) >= length)[None, None, :],
                    (length - 1)[None], cfg, use_kernel,
                )
            x = _residual(x, _attention_output(attn, None, p, cfg), cfg)
        elif kind == "E":
            x = _experts(x, p, cfg, live, record)
        elif kind == "D":
            x = _dense_ffn(x, p, cfg)
        elif kind == "L":
            h, mix = _read(x, p, cfg)
            out, left, picked = dsa_prefill(
                h[0], p, cfg, tuple(state[leaf] for leaf in _LATENT_LEAVES),
                (seen[kind], slot), seen[kind] * num_pages, pages,
                chunk_slice, start, jnp.clip(length - start, 0, c),
            )
            state.update(zip(_LATENT_LEAVES, left))
            record["selected"].append(picked)
            x = _residual(x, out[None], cfg, mix)
        elif kind == "A":
            h, mix = _read(x, p, cfg)
            out, state["cells"] = gdla_prefill_full(
                h[0], p, cfg, state["cells"], seen[kind] * num_pages, pages,
                chunk_slice, start, use_kernel,
            )
            x = _residual(x, out[None], cfg, mix)
        elif kind == "R":
            h, mix = _read(x, p, cfg)
            out, state["win_cells"] = gdla_prefill_window(
                h[0], p, cfg, state["win_cells"], (seen[kind], slot), start,
                jnp.clip(length - start, 0, c), use_kernel,
            )
            x = _residual(x, out[None], cfg, mix)
        elif kind == "W":
            q, k, v, gate = _attention_inputs(x, p, cfg, pos, kind)
            attn, state["win_k"], state["win_v"] = _window_prefill(
                q[0], k[0], v[0], state["win_k"], state["win_v"],
                (seen[kind], slot), start, jnp.clip(length - start, 0, c),
                cfg, use_kernel,
            )
            x = _residual(x, _attention_output(attn[None], gate, p, cfg), cfg)
        else:
            base = seen[kind] * num_pages
            q, k, v, gate = _attention_inputs(x, p, cfg, pos)  # [1, C, H, Dh]
            with _full_scope(cfg, "self"):
                k_pages, v_pages = _write_pages(
                    k_pages, v_pages, k, v, base + chunk_slice, cfg
                )
                if by_kernel:
                    attn = _prefill_kernel_attention(
                        q,
                        jnp.take(k_pages, base + pages, axis=0, mode="clip"),
                        jnp.take(v_pages, base + pages, axis=0, mode="clip"),
                        start, cfg.attention_scale,
                    )
                else:
                    attn = _gather_page_attention(
                        q, k_pages, v_pages, base + pages[None, :], mask, cfg,
                        cfg.attention_scale,
                    )
            x = _residual(x, _attention_output(attn, gate, p, cfg), cfg)
        seen[kind] += 1
    if self_only:
        carried = _carried(cache, k_pages, v_pages, state)
        return None, carried, _record_or_none(record)
    last = x if cross_from is not None else _last_row(x, start, length)
    carried = _carried(cache, k_pages, v_pages, state)
    return _logits(last, params, cfg), carried, _record_or_none(record)


def _last_row(x, start, length):
    """Row ``length - 1 - start`` of x [1, C, ..]: the last real token's,
    in the chunk that holds it."""
    return jax.lax.dynamic_slice_in_dim(x, length - 1 - start, 1, axis=1)


def _record_or_none(record):
    """`_record`, or None for a program without expert blocks."""
    return _record(record) if record["routes"] else None


def _attend_pages(q, k_pages, v_pages, tables, mask, positions, cfg,
                  use_kernel: bool):
    """Attention of q [B, 1, H, Dh] over pool pages it does NOT write: a
    block that reads another block's keys and values with its own
    queries. ``tables`` [B, n] the pages in the flat pool (>= 0),
    ``positions`` [B] the last position each row may see, ``mask`` [B,
    1, n x P] the gather path's (True = hidden). By the decode kernel
    where ``use_kernel`` (each slot's live pages and no more), else the
    gather path. Returns [B, 1, H, Dh]."""
    if use_kernel:
        from ray_tpu.ops.pallas.paged_attention import paged_attention

        return paged_attention(
            q, k_pages, v_pages, tables, positions,
            n_kv_heads=cfg.n_kv_heads, interpret=chip.platform() != "tpu",
            scale=cfg.attention_scale,
        )
    return _gather_page_attention(
        q, k_pages, v_pages, tables, mask, cfg, cfg.attention_scale
    )


def prefill_program(cfg: NemotronHConfig, n_write_pages: int,
                    chunk_pages: int, use_kernel: bool | None = None,
                    self_only: bool = False):
    """`_hybrid_prefill` jitted for one shape, under a name that says
    which (``hybrid_prefill_<chunk pages>_of_<table pages>``): a trace
    then names each bucket's program, and an instruction name is looked
    up in the text of the program it ran in. A model with a
    cross-decoder has two programs a shape, and their names say which
    half of the model they run: ``hybrid_prefill_self_..`` (``self_only``:
    a chunk that does not hold the prompt's last token) and
    ``hybrid_prefill_cross_..`` (the chunk that does: the cross-decoder
    and the head too). ``use_kernel`` None: as an
    engine on this platform says without being told (a bare TPU: the
    kernel), which is what a caller that lowers the programs for their
    text or their fit wants."""
    if use_kernel is None:
        use_kernel = chip.platform() == "tpu"
    if self_only and cfg.cross_from is None:
        raise ValueError("self_only: the model has no cross-decoder")
    return _prefill_program(
        cfg, n_write_pages, chunk_pages, bool(use_kernel), bool(self_only)
    )


@functools.lru_cache(maxsize=None)
def _prefill_program(cfg, n_write_pages: int, chunk_pages: int,
                     use_kernel: bool, self_only: bool = False):
    def program(params, tokens, cache, pages, start, slot, length):
        return _hybrid_prefill(
            params, tokens, cache, pages, start, slot, length, cfg,
            n_write_pages, chunk_pages, use_kernel, self_only,
        )

    half = ""
    if cfg.cross_from is not None:
        half = "self_" if self_only else "cross_"
    program.__name__ = f"hybrid_prefill_{half}{chunk_pages}_of_{n_write_pages}"
    return jax.jit(program, donate_argnames=("cache",))


@partial(
    jax.jit,
    static_argnames=("cfg", "use_kernel"),
    donate_argnames=("cache",),
)
def hybrid_decode(
    params,
    tokens: jnp.ndarray,  # [B, 1] int32
    cache: HybridCache,
    block_tables: jnp.ndarray,  # [B, max_pages] int32 (-1 = unused)
    positions: jnp.ndarray,  # [B] int32: position tokens[:, 0] writes at
    active: jnp.ndarray,  # [B] bool: the slots that are decoding
    temperature: jnp.ndarray,  # [B] fp32 (0 = greedy)
    rng_key: jnp.ndarray,
    cfg: NemotronHConfig,
    use_kernel: bool = False,
):
    """The decode program: one token a slot, sampled on device. A slot
    that is not ``active`` computes like the others (static shapes) and
    changes nothing that lasts: its state stays as it is, its expert
    pairs are left out, its K/V cell goes to the dump page (its table is
    all -1). Who keeps a recurrent state as it is: on a TPU the state
    kernel (``ops/pallas/state_step.py``, through a block's
    ``step_live``), which visits the decoding slots' state and no other,
    in place in the cache's stack; elsewhere this loop, which writes
    every slot's state back under the mask. The convolution tail (small)
    is written back under the mask here on both. Returns (sampled [B, 1]
    int32, logits [B, V] fp32, cache, record)."""
    page_size = cache["k"].shape[3]
    num_pages = cache["k"].shape[1]
    geometry = _decode_geometry(block_tables, positions, 1, page_size)
    k_pages, v_pages = _flat_pool(cache)
    state = _recurrent_leaves(cache)
    x = _embed(params, tokens, cfg)  # [B, 1, d]
    record = _new_record()
    # Chosen by the platform alone, as `moe_ffn` chooses its kernels.
    live = live_order(active) if chip.platform() == "tpu" else None
    seen = dict.fromkeys(cfg.block_kinds, 0)  # blocks of each kind so far
    memory = None  # the last `memory` block's y, [B, d_inner]
    for kind, p in zip(cfg.pattern, params["blocks"], strict=True):
        if kind in _RECURRENT:
            block, at = _RECURRENT[kind], seen[kind]
            h, mix = _read(x, p, cfg)
            u = _norm(h, p, cfg)[:, 0]
            old_c = state[block.conv][at]
            if live is not None:
                out, state[block.state], new_c = block.step_live(
                    u, p, cfg, state[block.state], at, old_c, *live
                )
            else:
                old_s = state[block.state][at]
                out, new_s, new_c = block.step(u, p, cfg, old_s, old_c)
                with jax.named_scope(f"{block.scope}:update"):
                    state[block.state] = state[block.state].at[at].set(
                        jnp.where(active[:, None, None, None], new_s, old_s)
                    )
            if block.memory:
                out, memory = out
            # The write back is the state update's other half: under
            # its scope, so that its time is read with it.
            with jax.named_scope(f"{block.scope}:update"):
                state[block.conv] = state[block.conv].at[at].set(
                    jnp.where(active[:, None, None], new_c, old_c)
                )
            x = _residual(x, out[:, None], cfg, mix)
        elif kind == "U":
            x = _residual(x, gmu(x, memory[:, None], p, cfg), cfg)
        elif kind == "C":
            # Every slot's query over its pages of the `*` block's pool
            # layer (base 0), the step's own cell among them: that
            # block wrote it. Nothing is written here.
            q, _, _, _ = _attention_inputs(x, p, cfg, None, kind)
            with _full_scope(cfg, "cross"):
                attn = _attend_pages(
                    q, k_pages, v_pages, geometry[4], geometry[1], positions,
                    cfg, use_kernel,
                )
            x = _residual(x, _attention_output(attn, None, p, cfg), cfg)
        elif kind == "E":
            x = _experts(x, p, cfg, active, record)
        elif kind == "D":
            x = _dense_ffn(x, p, cfg)
        elif kind == "L":
            h, mix = _read(x, p, cfg)
            out, left, picked = dsa_decode(
                h[:, 0], p, cfg,
                tuple(state[leaf] for leaf in _LATENT_LEAVES),
                seen[kind], seen[kind] * num_pages, block_tables, positions,
                active,
            )
            state.update(zip(_LATENT_LEAVES, left))
            record["selected"].append(picked)
            x = _residual(x, out[:, None], cfg, mix)
        elif kind == "A":
            h, mix = _read(x, p, cfg)
            out, state["cells"] = gdla_decode_full(
                h[:, 0], p, cfg, state["cells"], seen[kind] * num_pages,
                geometry, positions, use_kernel,
            )
            x = _residual(x, out[:, None], cfg, mix)
        elif kind == "R":
            h, mix = _read(x, p, cfg)
            out, state["win_cells"] = gdla_decode_window(
                h[:, 0], p, cfg, state["win_cells"], seen[kind], positions,
                active,
            )
            x = _residual(x, out[:, None], cfg, mix)
        elif kind == "W":
            q, k, v, gate = _attention_inputs(
                x, p, cfg, positions[:, None], kind
            )
            if cfg.ring_pages:
                attn, state["win_k"], state["win_v"] = _window_decode_pages(
                    q, k, v, state["win_k"], state["win_v"], seen[kind],
                    positions, active, cfg, use_kernel,
                )
            else:
                attn, state["win_k"], state["win_v"] = _window_decode(
                    q, k, v, state["win_k"], state["win_v"], seen[kind],
                    positions, active, cfg,
                )
            x = _residual(x, _attention_output(attn, gate, p, cfg), cfg)
        else:
            q, k, v, gate = _attention_inputs(
                x, p, cfg, positions[:, None]
            )  # [B, 1, H, Dh]
            with _full_scope(cfg, "self"):
                attn, k_pages, v_pages = _decode_attention(
                    q, k.astype(cfg.dtype), v.astype(cfg.dtype), k_pages,
                    v_pages, seen[kind] * num_pages, geometry, positions, cfg,
                    use_kernel, cfg.attention_scale,
                )
            x = _residual(x, _attention_output(attn, gate, p, cfg), cfg)
        seen[kind] += 1
    logits = _logits(x, params, cfg)  # [B, 1, V]
    sampled = _sample_tokens(logits, temperature, rng_key)
    carried = _carried(cache, k_pages, v_pages, state)
    return sampled, logits[:, 0], carried, _record_or_none(record)


def _band_pairs_before(m: int, w: int) -> int:
    """Sum of ``min(t + 1, w)`` over the positions ``t < m``: the (query,
    key) pairs a window layer's arithmetic needs up to position m."""
    return m * (m + 1) // 2 if m <= w else w * (w + 1) // 2 + (m - w) * w


class HybridServing(Serving):
    """What `LLMEngine` serves a `NemotronHConfig` (or a family that
    subclasses it and brings its own ``init_weights``) through."""

    no_speculation = (
        "with recurrent blocks: a rejected draft would need the slot's "
        "state rolled back, which is not written"
    )
    _paged = _PAGED

    def __init__(self, cfg: NemotronHConfig, init_weights=init_params):
        super().__init__(cfg, init_weights, cfg.count("E"))
        self._prefill_programs = self._live_tokens = self._prefill_pairs = 0
        self._window_pairs = self._window_bytes = 0
        self._index_pairs = self._selected_pairs = self._causal_pairs = 0
        self._latent_bytes = self._index_bytes = self._cells_expanded = 0
        # A model with a cross-decoder (`cfg.cross_from`).
        self._self_only_chunks = self._cross_rows = 0
        self._shared_kv_bytes = self._decode_steps = 0
        self._kv_token_bytes = 0

    def init_cache(self, num_pages: int, page_size: int, max_batch: int,
                   shardings=None):
        cache = init_hybrid_cache(self.cfg, num_pages, page_size, max_batch)
        self._window_bytes = leaf_bytes(cache, ("win_k", "win_v", "win_cells"))
        self._latent_bytes = leaf_bytes(cache, ("latent", "cells"))
        self._index_bytes = leaf_bytes(cache, ("index", "index_tail"))
        # One token's keys and values in one pool layer.
        self._kv_token_bytes = leaf_bytes(cache, ("k", "v")) // max(
            cache["k"].shape[0] * num_pages * page_size, 1
        )
        return cache

    def counters(self) -> dict:
        # Over the prefill programs run: how many; the live tokens the
        # chunked scans took, summed over the Mamba blocks and over the
        # gated-delta-rule blocks, and of the latter those whose rule
        # ran in `ops/pallas/gdn_chunk.py` (all of them on a TPU, none
        # off it: `gdn_chunked` asks the platform and nothing else);
        # the causal (query, key) pairs the attention's arithmetic needed, summed over the blocks that
        # attend the whole context (`LlamaServing` counts the same; a
        # program that stops before a cross-decoder attends nothing
        # there, `_count`) and,
        # apart, over the window blocks, where a query at position t
        # needs min(t + 1, W) keys, and the live tokens those blocks
        # took. `window_bytes`: what of the cache's per-slot bytes (the
        # engine's `state_bytes`) is windows.
        # Where the model has them: the tokens the per-channel rule
        # (`K`) took; over the latent blocks (`L`) their tokens, the (query, pooled
        # key) pairs the indexer's arithmetic needed (a query scores the
        # complete blocks before its own), the (query, key) pairs the
        # attention needed after the selection and the causal pairs it
        # would have needed without one; what of the cache is latent
        # cells and what is the indexer's (pooled keys and tails); and
        # the tokens whose streams a sublayer mixed, summed over the
        # sublayers, where the model carries more than one. Where the
        # model's attention is over latent cells with a rotary part (`A`
        # over pages, `R` over per-slot rings; their causal pairs and
        # tokens are `prefill_attn_pairs` and `prefill_window_pairs` /
        # `window_tokens`, their bytes among `latent_bytes` and
        # `window_bytes`): the cells its prefill programs turned back
        # into keys and values, a full layer the table's key blocks up
        # to the chunk's end by the kernels (`keys_expanded`) and the
        # whole table under dense scores, a window layer the ring and
        # the chunk.
        gdn_tokens = self.cfg.count("G") * self._live_tokens
        on_tpu = chip.platform() == "tpu"
        out = {
            **super().counters(),
            "kda_scan_tokens": self.cfg.count("K") * self._live_tokens,
            "dsa_tokens": self.cfg.count("L") * self._live_tokens,
            "dsa_index_pairs": self._index_pairs,
            "dsa_selected_pairs": self._selected_pairs,
            "dsa_causal_pairs": self._causal_pairs,
            "latent_bytes": self._latent_bytes,
            "latent_cells_expanded": self._cells_expanded,
            "index_bytes": self._index_bytes,
            "mhc_tokens": (
                len(self.cfg.pattern) * self._live_tokens
                if self.cfg.hc_mult else 0
            ),
            "prefill_programs": self._prefill_programs,
            "ssm_scan_tokens": (
                self.cfg.count("M") + self.cfg.count("S")
            ) * self._live_tokens,
            "gdn_scan_tokens": gdn_tokens,
            "gdn_kernel_tokens": gdn_tokens if on_tpu else 0,
            "prefill_attn_pairs": self._prefill_pairs,
            "prefill_window_pairs": self._window_pairs,
            "window_tokens": (
                self.cfg.count("W") + self.cfg.count("R")
            ) * self._live_tokens,
            "window_bytes": self._window_bytes,
        }
        if any(self.cfg.count(kind) for kind in _RECURRENT):
            # A decode step's state update follows the platform alone:
            # `ops/pallas/state_step.py` over the decoding slots on a
            # TPU, XLA's masked form elsewhere.
            out["state_step_kernel"] = on_tpu
        if readers := self.cfg.count("C"):
            # A cross-decoder: the prefill programs that stopped before
            # it; the rows that went through it (one a prompt, one a
            # decoding slot a step); how many blocks attend the ONE pool
            # layer (the block that writes it and the cross blocks), and
            # the bytes of it they have to read, all together, over the
            # decode steps and as one step's mean.
            out.update(
                prefill_self_only_chunks=self._self_only_chunks,
                cross_decoder_rows=self._cross_rows,
                shared_kv_reads=readers + 1,
                shared_kv_bytes=self._shared_kv_bytes,
                shared_kv_bytes_per_decode_step=(
                    self._shared_kv_bytes / self._decode_steps
                    if self._decode_steps else 0.0
                ),
            )
        return out

    def _count(self, start: int, width: int, length: int, table: int,
               use_kernel: bool, self_only: bool = False) -> None:
        n = max(min(width, length - start), 0)
        self._prefill_programs += 1
        self._live_tokens += n
        if self_only:
            # The program stops before the cross-decoder, and the full
            # layer is the self-decoder's last: nothing reads what its
            # attention would add to the stream, so the compiler drops
            # the attend (the layer's keys and values are written).
            self._self_only_chunks += 1
        else:
            full = self.cfg.count("*") + self.cfg.count("A")
            self._prefill_pairs += full * (n * start + n * (n + 1) // 2)
            if self.cfg.count("C"):
                self._cross_rows += 1
        if rings := self.cfg.count("R"):
            self._cells_expanded += rings * (self.cfg.sliding_window + width)
        if full := self.cfg.count("A"):
            self._cells_expanded += full * (
                keys_expanded(start, width, table) if use_kernel else table
            )
        if layers := self.cfg.count("W") + self.cfg.count("R"):
            w = self.cfg.sliding_window
            self._window_pairs += layers * (
                _band_pairs_before(start + n, w) - _band_pairs_before(start, w)
            )
        if layers := self.cfg.count("L"):
            pool, top = self.cfg.index_kpool, self.cfg.index_blocks
            t = np.arange(start, start + n, dtype=np.int64)
            before = t // pool  # complete blocks before the query's own
            self._index_pairs += layers * int(before.sum())
            self._selected_pairs += layers * int(
                (np.minimum(before, top) * pool + t % pool + 1).sum()
            )
            self._causal_pairs += layers * int((t + 1).sum())

    def prefill_chunk(self, params, tokens, cache, pages, start, *,
                      n_write_pages, chunk_pages, slot, length,
                      use_kernel=False):
        # A model with a cross-decoder: which half of it this chunk
        # needs is decided here, from where the chunk lies; the engine
        # reads the logits of a prompt's last chunk alone.
        self_only = (
            self.cfg.cross_from is not None
            and int(start) + tokens.shape[1] < length
        )
        self._count(
            int(start), tokens.shape[1], length,
            n_write_pages * tokens.shape[1] // chunk_pages, use_kernel,
            self_only,
        )
        return prefill_program(
            self.cfg, n_write_pages, chunk_pages, use_kernel, self_only
        )(
            params, tokens, cache, pages, start, np.int32(slot),
            np.int32(length),
        )

    def decode(self, params, tokens, cache, block_tables, positions,
               temperature, rng_key, *, use_kernel, stochastic, active):
        if readers := self.cfg.count("C"):
            live = np.asarray(active, bool)
            self._decode_steps += 1
            self._cross_rows += int(live.sum())
            # Each decoding slot's context, its new token included.
            self._shared_kv_bytes += (readers + 1) * self._kv_token_bytes * int(
                (np.asarray(positions)[live] + 1).sum()
            )
        return super().decode(
            params, tokens, cache, block_tables, positions, temperature,
            rng_key, use_kernel=use_kernel, stochastic=stochastic,
            active=active,
        )

    def _decode_one(self, *args, **kwargs):
        return hybrid_decode(*args, **kwargs)  # the module's, as the call finds it
