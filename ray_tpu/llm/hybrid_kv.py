"""Serving programs for a model whose blocks differ in kind
(``models/nemotron_h.py``, ``models/granite_hybrid.py``,
``models/qwen3_next.py``): pages for the blocks that attend, per-slot
recurrent state for the blocks that carry one.

A block here is one SUBLAYER, a letter of ``cfg.pattern``: a mixer or
an expert FFN behind its own norm and a residual add. Nemotron-H's
layers are one each; a Granite 4.0-H layer is two (its mixer, then
``E``) and multiplies each sublayer's output, the embedding, the
attention scores and the logits by numbers of its config, which the
programs skip where they are 1 (``_embed``, ``_residual``, ``_head``).
A Qwen3-Next layer is two as well; its recurrent mixer is another
letter (``G``, the gated delta rule) with its own leaves of the cache,
and its attention block norms, rotates and gates (`_attention_inputs`),
which the other families' configs hold off.

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
  kinds its pattern has (`_RECURRENT`).

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

import functools
from collections.abc import Callable
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu._private import chip
from ray_tpu.llm.paged_kv import (
    _decode_attention,
    _decode_geometry,
    _flat_pool,
    _gather_page_attention,
    _prefill_kernel_attention,
    _sample_tokens,
    _write_pages,
    init_paged_kv,
    kv_cache_bytes,
)
from ray_tpu.models.moe import moe_ffn
from ray_tpu.models.nemotron_h import (
    NemotronHConfig,
    init_params,
    mamba_chunked,
    mamba_step,
    mamba_step_live,
)
from ray_tpu.models.qwen3_next import gdn_chunked, gdn_step, gdn_step_live
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.pallas.state_step import live_order

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
    (state shape, convolution channels) from a config."""

    chunked: Callable
    step: Callable
    step_live: Callable
    state: str
    conv: str
    scope: str
    shapes: Callable


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
}


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
    return cache


def _embed(params, tokens, cfg):
    x = params["tok_emb"][tokens]
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    return x


def _residual(x, out, cfg):
    """``x + residual_multiplier * out``: a sublayer's output added on."""
    if cfg.residual_multiplier != 1.0:
        out = out * cfg.residual_multiplier
    return x + out


def _experts(x, p, cfg, rows_live, record):
    """An expert block on x [B, S, d], and its counters onto ``record``."""
    out, aux = moe_ffn(
        rms_norm(x, p["norm"], cfg.norm_eps), p, cfg, rows_live=rows_live
    )
    _note(record, aux)
    return _residual(x, out, cfg)


def _new_record():
    """What a program's expert blocks leave: `_note` fills, `_record`
    sums."""
    return {"routes": [], "pairs_here": [], "experts_touched": [],
            "sorted_rows": []}


def _note(record, aux):
    """An expert block's counters, from `moe_ffn`'s ``aux``."""
    record["routes"].append(aux["routes"])
    record["pairs_here"].append(aux["expert_load"].sum())
    record["experts_touched"].append((aux["expert_load"] > 0).sum())
    record["sorted_rows"].append(aux["sorted_rows"])


def _record(record):
    """Per program: ``routes`` [L_expert, T, k] (each token's experts, of
    all the model's) and ``counts`` int32[4]: the pairs of live rows
    whose expert is held, the held experts that got a row, the rows the
    sorted form ran its grouped matmuls over and the pairs it was given
    (padding's and absent experts' among them), each summed over the
    expert blocks."""
    return {
        "routes": jnp.stack(record["routes"]),
        "counts": jnp.concatenate([
            jnp.stack(
                [sum(record["pairs_here"]), sum(record["experts_touched"])]
            ),
            sum(record["sorted_rows"]),
        ]).astype(jnp.int32),
    }


def _carried(cache, k_pages, v_pages, state) -> HybridCache:
    """The cache as a program hands it on: the flat pool back in the
    argument's shape, the recurrent leaves as the loop left them."""
    return {
        "k": k_pages.reshape(cache["k"].shape),
        "v": v_pages.reshape(cache["v"].shape),
        **state,
    }


def _recurrent_leaves(cache) -> HybridCache:
    return {
        name: leaf for name, leaf in cache.items() if name not in ("k", "v")
    }


def _rotate(x, positions, cfg):
    """A rotary embedding on the first ``cfg.rotary_dim`` dimensions of
    each head of x [B, S, H, Dh] at ``positions`` [B, S] (split halves
    within those; the rest pass through), float32 inside."""
    half = cfg.rotary_dim // 2
    inv_freq = cfg.rope_theta ** (
        -jnp.arange(half, dtype=jnp.float32) / half
    )
    angles = positions.astype(jnp.float32)[..., None, None] * inv_freq
    cos, sin = jnp.cos(angles), jnp.sin(angles)  # [B, S, 1, half]
    x1, x2, rest = jnp.split(
        x.astype(jnp.float32), [half, cfg.rotary_dim], axis=-1
    )
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1
    ).astype(x.dtype)


def _attention_inputs(x, p, cfg, positions):
    """An attention block's q [B, S, H, Dh], k and v [B, S, Hkv, Dh] of
    x [B, S, d] at ``positions`` [B, S], and the heads' output gate
    [B, S, H, Dh] (None where the model has none). What a family adds
    is skipped at its config's off value, so that the others' programs
    are `paged_kv._project_qkv`'s three products and nothing else."""
    b, s, _ = x.shape
    dh = cfg.head_dim
    h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
    q = h @ p["wq"]
    gate = None
    if cfg.attn_output_gate:
        # Head by head [query | gate].
        q, gate = jnp.split(q.reshape(b, s, cfg.n_heads, 2 * dh), 2, axis=-1)
    q = q.reshape(b, s, cfg.n_heads, dh)
    k = (h @ p["wk"]).reshape(b, s, cfg.n_kv_heads, dh)
    v = (h @ p["wv"]).reshape(b, s, cfg.n_kv_heads, dh)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.rotary_dim:
        q, k = _rotate(q, positions, cfg), _rotate(k, positions, cfg)
    return q, k, v, gate


def _attention_output(attn, gate, p, cfg):
    """attn [B, S, H, Dh] (times ``sigmoid(gate)`` where the model gates
    its heads) through ``W_o``: [B, S, d]."""
    if gate is not None:
        with jax.named_scope("attn:gate"):
            attn = (
                attn * jax.nn.sigmoid(gate.astype(jnp.float32))
            ).astype(cfg.dtype)
    return attn.reshape(*attn.shape[:2], -1) @ p["wo"]


def _head(x, params, cfg=None):
    """Final norm and the head; ``cfg`` where the model ties the head to
    the embedding or divides its logits (`llm/latent_kv.py`'s does
    neither and passes none)."""
    x = rms_norm(
        x, params["final_norm"], 1e-5 if cfg is None else cfg.norm_eps
    )
    if cfg is not None and cfg.tie_word_embeddings:
        logits = jnp.einsum("...d,vd->...v", x, params["tok_emb"])
    else:
        logits = x @ params["lm_head"]
    logits = logits.astype(jnp.float32)
    if cfg is not None and cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


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
):
    """A prompt (``start`` 0, ``chunk_pages == n_write_pages``) or one
    chunk of it. ``use_kernel``: where the table holds more than
    ``_DENSE_ATTENTION_KEYS`` keys the chunk's queries attend the
    context's pages, gathered as they lie, by the prefill kernel;
    otherwise dense float32 scores over the whole table under a mask
    (at a 256-page table and a 2,048-token chunk 4.3 GB of them).
    Returns (logits [1, 1, V] float32 of position ``length - 1`` —
    meaningful in the chunk that holds it —, cache, record)."""
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
    for kind, p in zip(cfg.pattern, params["blocks"], strict=True):
        if kind in _RECURRENT:
            block = _RECURRENT[kind]
            fresh = start == 0
            at = (seen[kind], slot)
            out, s_end, c_end = block.chunked(
                rms_norm(x, p["norm"], cfg.norm_eps)[0], p, cfg,
                jnp.where(fresh, 0.0, state[block.state][at]),
                jnp.where(fresh, 0, state[block.conv][at]),
                jnp.clip(length - start, 0, c),
            )
            with jax.named_scope(f"{block.scope}:scan"):
                state[block.state] = state[block.state].at[at].set(s_end)
                state[block.conv] = state[block.conv].at[at].set(c_end)
            x = _residual(x, out[None], cfg)
        elif kind == "E":
            x = _experts(x, p, cfg, live, record)
        else:
            base = seen[kind] * num_pages
            q, k, v, gate = _attention_inputs(x, p, cfg, pos)  # [1, C, H, Dh]
            k_pages, v_pages = _write_pages(
                k_pages, v_pages, k, v, base + chunk_slice, cfg
            )
            if by_kernel:
                attn = _prefill_kernel_attention(
                    q, jnp.take(k_pages, base + pages, axis=0, mode="clip"),
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
    last = jax.lax.dynamic_slice_in_dim(x, length - 1 - start, 1, axis=1)
    carried = _carried(cache, k_pages, v_pages, state)
    return _head(last, params, cfg), carried, _record(record)


def prefill_program(cfg: NemotronHConfig, n_write_pages: int,
                    chunk_pages: int, use_kernel: bool | None = None):
    """`_hybrid_prefill` jitted for one shape, under a name that says
    which (``hybrid_prefill_<chunk pages>_of_<table pages>``): a trace
    then names each bucket's program, and an instruction name is looked
    up in the text of the program it ran in. ``use_kernel`` None: as an
    engine on this platform says without being told (a bare TPU: the
    kernel), which is what a caller that lowers the programs for their
    text or their fit wants."""
    if use_kernel is None:
        use_kernel = chip.platform() == "tpu"
    return _prefill_program(cfg, n_write_pages, chunk_pages, bool(use_kernel))


@functools.lru_cache(maxsize=None)
def _prefill_program(cfg, n_write_pages: int, chunk_pages: int,
                     use_kernel: bool):
    def program(params, tokens, cache, pages, start, slot, length):
        return _hybrid_prefill(
            params, tokens, cache, pages, start, slot, length, cfg,
            n_write_pages, chunk_pages, use_kernel,
        )

    program.__name__ = f"hybrid_prefill_{chunk_pages}_of_{n_write_pages}"
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
    for kind, p in zip(cfg.pattern, params["blocks"], strict=True):
        if kind in _RECURRENT:
            block, at = _RECURRENT[kind], seen[kind]
            u = rms_norm(x, p["norm"], cfg.norm_eps)[:, 0]
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
            # The write back is the state update's other half: under
            # its scope, so that its time is read with it.
            with jax.named_scope(f"{block.scope}:update"):
                state[block.conv] = state[block.conv].at[at].set(
                    jnp.where(active[:, None, None], new_c, old_c)
                )
            x = _residual(x, out[:, None], cfg)
        elif kind == "E":
            x = _experts(x, p, cfg, active, record)
        else:
            q, k, v, gate = _attention_inputs(
                x, p, cfg, positions[:, None]
            )  # [B, 1, H, Dh]
            attn, k_pages, v_pages = _decode_attention(
                q, k.astype(cfg.dtype), v.astype(cfg.dtype), k_pages,
                v_pages, seen[kind] * num_pages, geometry, positions, cfg,
                use_kernel, cfg.attention_scale,
            )
            x = _residual(x, _attention_output(attn, gate, p, cfg), cfg)
        seen[kind] += 1
    logits = _head(x, params, cfg)  # [B, 1, V]
    sampled = _sample_tokens(logits, temperature, rng_key)
    carried = _carried(cache, k_pages, v_pages, state)
    return sampled, logits[:, 0], carried, _record(record)


class HybridServing:
    """What `LLMEngine` serves a `NemotronHConfig` (or a family that
    subclasses it and brings its own ``init_weights``) through (see
    `paged_kv.LlamaServing` for the convention)."""

    no_speculation = (
        "with recurrent blocks: a rejected draft would need the slot's "
        "state rolled back, which is not written"
    )
    logits_last_only = True  # prefill returns the last real token's logits
    # A prompt's last chunk is padded to the chunk's length (the program
    # takes the true length), so that chunks compile to one shape.
    fixed_chunks = True

    def __init__(self, cfg: NemotronHConfig, init_weights=init_params):
        self.cfg = cfg
        self.pairs_per_token = cfg.top_k * cfg.count("E")
        self.recurrent_blocks = sum(cfg.count(kind) for kind in _RECURRENT)
        self._init_weights = init_weights
        self._prefill_programs = self._live_tokens = self._prefill_pairs = 0

    def init_weights(self, key):
        return self._init_weights(key, self.cfg)

    def logical_axes(self):
        raise NotImplementedError(
            "a mesh: the hybrid programs are written for one chip's share "
            "(experts across chips and their exchange are not)"
        )

    def held_weights(self, params):
        return params  # `init_params` makes the tree as it is held

    def init_cache(self, num_pages: int, page_size: int, max_batch: int,
                   shardings=None):
        return init_hybrid_cache(self.cfg, num_pages, page_size, max_batch)

    cache_bytes = staticmethod(kv_cache_bytes)

    def counters(self) -> dict:
        # Over the prefill programs run: how many; the live tokens the
        # chunked scans took, summed over the Mamba blocks and over the
        # gated-delta-rule blocks; the causal (query, key) pairs the
        # attention's arithmetic needed, summed over the attention
        # blocks (`LlamaServing` counts the same).
        return {
            "prefill_programs": self._prefill_programs,
            "ssm_scan_tokens": self.cfg.count("M") * self._live_tokens,
            "gdn_scan_tokens": self.cfg.count("G") * self._live_tokens,
            "prefill_attn_pairs": self._prefill_pairs,
        }

    def _count(self, start: int, width: int, length: int) -> None:
        n = max(min(width, length - start), 0)
        self._prefill_programs += 1
        self._live_tokens += n
        self._prefill_pairs += self.cfg.count("*") * (
            n * start + n * (n + 1) // 2
        )

    def prefill(self, params, tokens, cache, pages, *, n_write_pages, slot,
                length, use_kernel=False):
        self._count(0, tokens.shape[1], length)
        return prefill_program(
            self.cfg, n_write_pages, n_write_pages, use_kernel
        )(
            params, tokens, cache, pages, np.int32(0), np.int32(slot),
            np.int32(length),
        )

    def prefill_chunk(self, params, tokens, cache, pages, start, *,
                      n_write_pages, chunk_pages, slot, length,
                      use_kernel=False):
        self._count(int(start), tokens.shape[1], length)
        return prefill_program(
            self.cfg, n_write_pages, chunk_pages, use_kernel
        )(
            params, tokens, cache, pages, start, np.int32(slot),
            np.int32(length),
        )

    def decode(self, params, tokens, cache, block_tables, positions,
               temperature, rng_key, *, use_kernel, stochastic, active):
        sampled, logits, cache, record = hybrid_decode(
            params, tokens, cache, block_tables, positions, active,
            temperature, rng_key, cfg=self.cfg, use_kernel=use_kernel,
        )
        # No drafts: the engine's acceptance arrays are [B, 0].
        none = np.zeros((tokens.shape[0], 0), np.int32)
        return sampled, logits, cache, none.astype(bool), none, record
