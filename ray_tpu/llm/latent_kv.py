"""Serving programs for a model with multi-head latent attention
(``models/pangu_ultra_moe.py``, ``models/longcat_flash.py``): ONE latent
page pool, written and read in two forms of the same function.

The cache is one donated array, ``latent [A, num_pages, P, W]`` in
``cfg.dtype``, ``A`` the model's attention sublayers (one a layer for
Pangu, two for LongCat-Flash's double layer, whose sublayer ``j`` of
layer ``i`` has row ``2 i + j``: two cells a token a layer): per token
and attention sublayer the row ``[Nkv(c); rope(kpe)]``,
``kv_lora_rank + qk_rope_head_dim`` numbers (576: 1,152 bytes in bf16)
whatever the number of heads, where per-head keys and values would be
2 x 128 x 128; held W = 640 wide, zeros behind the 576, which is what a
TPU's tiles of 128 lanes make of a row of 576 in any case
(``cfg.cell_width``). Pages, block tables, prefix hashes, preemption and the
step in flight are `LLMEngine`'s as they are: pages hold all of this
model's per-sequence state. The pool is carried through the Python loop
over the layers in a flat view (``[A * num_pages, P, W]``, a bitcast;
each attention sublayer adds its page base to the ids) and updated in
place: one buffer in one layout from a program's argument to its result.

A program walks the pattern's kinds through one attention body (its
closure `attention`, which takes the pool's next row each time it is
called) and `_layer`, which says where a kind calls it: ``D`` / ``E``
once, before the FFN behind its two sandwich norms; ``S`` twice, around
the first dense FFN, with the expert layer read after the first and
added after the second dense FFN.

**Decode is the absorbed form** (``latent_decode``): ``qa_h = q_nope_h
Wuk_h^T`` makes each head's query 512 + 64 wide, the scores are ``qa_h .
c_t + q_pe_h . kpe_t`` against the cells as they lie, the weighted sum
of the cells' first 512 columns is turned into the head's output by
``Wuv_h`` afterwards: no key or value is ever made for a cached token,
and a step reads each live page once (``ops/pallas/latent_attention.py``
on a bare TPU; on a CPU the window is gathered and attended under a
mask, ``_gather_latent_attention``, which is also what the kernel is
tested against).

**Prefill is the expanded form** (``prefill_program``, a whole prompt
or one chunk of it): per (query, key) pair and head the expanded form
costs 192 + 128 multiply-adds and the absorbed one 576 + 512, so with
thousands of queries against each key it pays to turn the latents back
into keys and values, ``c Wuk_h`` and ``c Wuv_h``, read from the pages
(this chunk's own cells too: they were written first). Either way a
chunk's work follows what it attends and not the bucket's width: the
key blocks past the chunk's end are neither expanded nor attended. Off
the TPU (`_attend_expanded`) that is done a block of
``cfg.prefill_key_block`` keys at a time inside a loop with a running
soft-max whose trip count is the context so far, so that no ``[heads,
chunk, context]`` scores and no whole context of expanded keys ever
exist. On a bare TPU (`_attend_expanded_kernel`) by two kernels of
``ops/pallas/latent_attention.py`` that count a chunk's key blocks (of
1,024) by one rule: `latent_expand` writes the keys and values of the
blocks up to the chunk's last into arrays as wide as the table, whose
other blocks it never touches (PR 66; until then two einsums over the
whole table, the part no token had reached with it), and
`latent_prefill_attention` reads no further. Earlier chunks' LIVE
latents are expanded again in every later chunk
(``stats()["latent_tokens_expanded"]``: whole key blocks up to each
chunk's end, in every attention sublayer).

The program takes the context's true length: positions from it on are
padding, their expert pairs are left out, and the logits returned are
the last real token's alone (``logits_last_only``); chunks have one
shape (``fixed_chunks``).
"""

from __future__ import annotations

import functools
import itertools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu._private import chip
from ray_tpu.llm.paged_kv import (
    _NEG_INF,
    _decode_geometry,
    _head,
    _sample_tokens,
)
from ray_tpu.llm.serving import Serving, _new_record, _note, _record
from ray_tpu.models.moe import moe_ffn
from ray_tpu.models.pangu_ultra_moe import (
    LatentShape,
    dense_mlp,
    pad_to_cell,
    project_latent,
    project_q,
)
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import rope_frequencies

LatentCache = dict[str, jnp.ndarray]  # {"latent": [A, num_pages, P, W]}

# Finite mask and initial maximum of the running soft-max, as the
# kernels': exp(x - m) underflows to exactly 0.
_MASK = -1e9
_M_INIT = -1e30


def init_latent_cache(
    cfg: LatentShape, num_pages: int, page_size: int
) -> LatentCache:
    return {
        "latent": jnp.zeros(
            (cfg.attn_sublayers, num_pages, page_size, cfg.cell_width),
            cfg.dtype,
        )
    }


def _flat(cache: LatentCache) -> jnp.ndarray:
    """The pool's pages in a flat view ``[A * num_pages, P, W]`` (a
    bitcast): attention sublayer ``a``'s pages start at ``a *
    num_pages``."""
    pool = cache["latent"]
    return pool.reshape((-1,) + pool.shape[2:])


def _ffn(x, kind, p, cfg, rows_live, record):
    """The layer's second sublayer on x [B, S, d], behind its two norms;
    an expert layer's counters go onto ``record``."""
    h = rms_norm(x, p["norm3"])
    if kind == "D":
        out = dense_mlp(h, p)
    else:
        out, aux = moe_ffn(h, p, cfg, rows_live=rows_live)
        _note(record, aux)
    return x + rms_norm(out, p["norm4"])


def _attn_out(x, heads, p):
    """Heads' outputs [B, S, H, v] through ``Wo`` and the sublayer's
    output norm, where it has one, onto the residual stream."""
    with jax.named_scope("mla:out"):
        out = heads.reshape(*x.shape[:2], -1) @ p["wo"]
    if "norm2" in p:
        out = rms_norm(out, p["norm2"])
    return x + out


def _layer(x, kind, p, cfg, attention, rows_live, record):
    """One layer of ``kind`` on x [B, S, d]. ``attention(x, p)`` is the
    program's attention sublayer on the residual stream, with the tree
    of the sublayer's own leaves; ``rows_live()`` makes the mask of the
    rows that carry a token where the layer's experts are called (a
    program's text then has it where it always had)."""
    if kind != "S":
        return _ffn(attention(x, p), kind, p, cfg, rows_live(), record)
    first, second = p["ffn"]
    x = attention(x, p["attn"][0])
    u = rms_norm(x, first["norm"])
    # The shortcut: the experts read the first dense FFN's input and
    # their output joins the stream behind the second.
    shortcut, aux = moe_ffn(u, p["moe"], cfg, rows_live=rows_live())
    _note(record, aux)
    x = x + dense_mlp(u, first)
    x = attention(x, p["attn"][1])
    return x + dense_mlp(rms_norm(x, second["norm"]), second) + shortcut


def _gather_latent_attention(q, pool, page_index, mask, cfg):
    """The absorbed attention over gathered pages with dense scores: the
    XLA path of a decode step. q [B, K, H, W]; page_index [B, n_pages]
    (>= 0); mask [B, K, window] bool, True = hidden. Returns the
    weighted cells' first ``kv_lora_rank`` columns, [B, K, H, rank]."""
    b, kk = q.shape[:2]
    n_pages, page_size = page_index.shape[1], pool.shape[1]
    cells = jnp.take(pool, page_index, axis=0)  # [B, n, P, W]
    scores = jnp.einsum(
        "bkhw,bnpw->bhknp", q, cells, preferred_element_type=jnp.float32
    ) * cfg.softmax_scale
    scores = scores.reshape(b, cfg.n_heads, kk, n_pages * page_size)
    scores = jnp.where(mask[:, None], _NEG_INF, scores)
    probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
    return jnp.einsum(
        "bhknp,bnpc->bkhc",
        probs.reshape(b, cfg.n_heads, kk, n_pages, page_size),
        cells[..., : cfg.kv_lora_rank],
    )


def _attend_expanded(q_nope, q_pe, pool, page_ids, start, p, cfg):
    """A chunk's queries over the context so far, in the expanded form.

    q_nope [C, H, nope], q_pe [C, H, rope] of positions ``start ..
    start + C - 1``; ``page_ids`` the context's pages in the flat pool,
    which already hold this chunk's cells. Keys and values are made a
    block of keys at a time from the pages' latents; blocks past the
    chunk's last position are not visited. Returns [C, H, v]."""
    c, h = q_nope.shape[:2]
    page_size = pool.shape[1]
    n_pages = page_ids.shape[0]
    block_pages = max(min(cfg.prefill_key_block // page_size, n_pages), 1)
    block = block_pages * page_size
    # The table padded to whole blocks with page 0 (a dump page: finite,
    # and hidden by the causal mask like every key past the chunk).
    n_blocks_max = -(-n_pages // block_pages)
    page_ids = jnp.pad(page_ids, (0, n_blocks_max * block_pages - n_pages))
    q_pos = start + jnp.arange(c, dtype=jnp.int32)
    rank = cfg.kv_lora_rank
    q_nope = q_nope.transpose(1, 0, 2)  # [H, C, nope]
    q_pe = q_pe.transpose(1, 0, 2)

    def one_block(j, carry):
        m_prev, l_prev, acc = carry
        ids = jax.lax.dynamic_slice(page_ids, [j * block_pages], [block_pages])
        cells = pool[ids].reshape(block, -1)  # [keys, W]
        with jax.named_scope("mla:expand"):
            k_nope = jnp.einsum("tc,hcd->htd", cells[:, :rank], p["w_uk"])
            v = jnp.einsum("tc,hcd->htd", cells[:, :rank], p["w_uv"])
        with jax.named_scope("mla:attend"):
            s = jnp.einsum(
                "hqd,htd->hqt", q_nope, k_nope,
                preferred_element_type=jnp.float32,
            ) + jnp.einsum(
                "hqr,tr->hqt", q_pe, cells[:, rank: cfg.latent_dim],
                preferred_element_type=jnp.float32,
            )
            key_pos = j * block + jnp.arange(block, dtype=jnp.int32)
            hidden = key_pos[None, :] > q_pos[:, None]  # [C, keys]
            s = jnp.where(hidden[None], _MASK, s * cfg.softmax_scale)
            m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
            probs = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + probs.sum(-1, keepdims=True)
            acc = acc * alpha + jnp.einsum(
                "hqt,htd->hqd", probs.astype(cfg.dtype), v,
                preferred_element_type=jnp.float32,
            )
        return m_new, l_new, acc

    with jax.named_scope("mla:attend"):
        init = (
            jnp.full((h, c, 1), _M_INIT, jnp.float32),
            jnp.zeros((h, c, 1), jnp.float32),
            jnp.zeros((h, c, cfg.v_head_dim), jnp.float32),
        )
    n_blocks = jnp.minimum(-(-(start + c) // block), n_blocks_max)
    _, l, acc = jax.lax.fori_loop(0, n_blocks, one_block, init)
    with jax.named_scope("mla:attend"):
        return (acc / l).astype(cfg.dtype).transpose(1, 0, 2)


def _attend_expanded_kernel(q_nope, q_pe, pool, page_ids, start, p, cfg):
    """`_attend_expanded` on a bare TPU, by two kernels of
    ``ops/pallas/latent_attention.py`` that count a chunk's key blocks by
    one rule: `latent_expand` turns the table's latents into keys and
    values as far as the chunk's last key block (one layer's, for 16,384
    tokens, are 1.07 GB: the blocks past the chunk are never written,
    and hold whatever the buffers held), and the flash-style kernel
    attends them and reads nothing past that block either. The rotary
    key goes in as the cell holds it, ``[kpe; zeros]``. Returns [C, H,
    v]."""
    from ray_tpu.ops.pallas.latent_attention import (
        latent_expand,
        latent_prefill_attention,
    )

    interpret = chip.platform() != "tpu"
    cells = pool[page_ids].reshape(-1, pool.shape[-1])  # [T, W]
    with jax.named_scope("mla:expand"):
        k_nope, v = latent_expand(
            cells, p["w_uk"], p["w_uv"], start, n_queries=q_nope.shape[0],
            interpret=interpret,
        )
    with jax.named_scope("mla:attend"):
        heads = latent_prefill_attention(
            q_nope.transpose(1, 0, 2),
            pad_to_cell(q_pe, cfg).transpose(1, 0, 2),
            k_nope, cells[:, cfg.kv_lora_rank:], v, start,
            scale=cfg.softmax_scale, interpret=interpret,
        )
        return heads.transpose(1, 0, 2)


def _latent_prefill(
    params,
    tokens: jnp.ndarray,  # [1, C] int32, C = chunk_pages * page_size
    cache: LatentCache,
    pages: jnp.ndarray,  # [n_write_pages] int32: the FULL context table
    start: jnp.ndarray,  # [] int32: position of tokens[0, 0], page-aligned
    length: jnp.ndarray,  # [] int32: the context's true length
    cfg: LatentShape,
    n_write_pages: int,
    chunk_pages: int,
    use_kernel: bool,
):
    """A prompt (``start`` 0, ``chunk_pages == n_write_pages``) or one
    chunk of it. Returns (logits [1, 1, V] float32 of position ``length
    - 1`` — meaningful in the chunk that holds it —, cache, record)."""
    c = tokens.shape[1]
    num_pages, page_size = cache["latent"].shape[1:3]
    pos = start + jnp.arange(c, dtype=jnp.int32)[None, :]  # [1, C]
    live = (pos < length)[0]
    chunk_slice = jax.lax.dynamic_slice(
        pages, [start // page_size], [chunk_pages]
    )
    cos, sin = rope_frequencies(
        cfg.qk_rope_head_dim, n_write_pages * page_size, cfg.rope_theta
    )
    pool = _flat(cache)
    x = params["tok_emb"][tokens]
    record = _new_record()
    rows = itertools.count()  # of the pool, one an attention sublayer
    attend = _attend_expanded_kernel if use_kernel else _attend_expanded

    def attention(x, p):
        nonlocal pool
        base = next(rows) * num_pages
        h = rms_norm(x, p["norm1"])
        q_nope, q_pe = project_q(h, p, cfg, cos, sin, pos)
        with jax.named_scope("mla:latent"):
            cells = project_latent(h, p, cfg, cos, sin, pos)  # [1, C, W]
            pool = pool.at[base + chunk_slice].set(
                cells.reshape(chunk_pages, page_size, -1)
            )
        heads = attend(q_nope[0], q_pe[0], pool, base + pages, start, p, cfg)
        return _attn_out(x, heads[None], p)

    for kind, p in zip(cfg.pattern, params["blocks"], strict=True):
        x = _layer(x, kind, p, cfg, attention, lambda: live, record)
    last = jax.lax.dynamic_slice_in_dim(x, length - 1 - start, 1, axis=1)
    cache = {"latent": pool.reshape(cache["latent"].shape)}
    return _head(last, params), cache, _record(record)


@functools.lru_cache(maxsize=None)
def prefill_program(cfg: LatentShape, n_write_pages: int,
                    chunk_pages: int, use_kernel: bool = False):
    """`_latent_prefill` jitted for one shape, under a name that says
    which (``latent_prefill_<chunk pages>_of_<table pages>``): a trace
    then names each bucket's program."""

    def program(params, tokens, cache, pages, start, length):
        return _latent_prefill(
            params, tokens, cache, pages, start, length, cfg, n_write_pages,
            chunk_pages, use_kernel,
        )

    program.__name__ = f"latent_prefill_{chunk_pages}_of_{n_write_pages}"
    return jax.jit(program, donate_argnames=("cache",))


@partial(
    jax.jit,
    static_argnames=("cfg", "use_kernel"),
    donate_argnames=("cache",),
)
def latent_decode(
    params,
    tokens: jnp.ndarray,  # [B, 1] int32
    cache: LatentCache,
    block_tables: jnp.ndarray,  # [B, max_pages] int32 (-1 = unused)
    positions: jnp.ndarray,  # [B] int32: position tokens[:, 0] writes at
    active: jnp.ndarray,  # [B] bool: the slots that are decoding
    temperature: jnp.ndarray,  # [B] fp32 (0 = greedy)
    rng_key: jnp.ndarray,
    cfg: LatentShape,
    use_kernel: bool = False,
):
    """The decode program: one token a slot in the absorbed form, sampled
    on device. A slot that is not ``active`` computes like the others
    (static shapes) and changes nothing that lasts: its cell goes to the
    dump page (its table is all -1) and its expert pairs are left out.
    Returns (sampled [B, 1] int32, logits [B, V] fp32, cache, record)."""
    b, kk = tokens.shape
    num_pages, page_size = cache["latent"].shape[1:3]
    pos2d, mask, write_pages, off_of, tables = _decode_geometry(
        block_tables, positions, kk, page_size
    )
    cos, sin = rope_frequencies(
        cfg.qk_rope_head_dim, block_tables.shape[1] * page_size,
        cfg.rope_theta,
    )
    pool = _flat(cache)
    x = params["tok_emb"][tokens]  # [B, K, d]
    record = _new_record()
    rows = itertools.count()  # of the pool, one an attention sublayer

    def attention(x, p):
        nonlocal pool
        base = next(rows) * num_pages
        h = rms_norm(x, p["norm1"])
        q_nope, q_pe = project_q(h, p, cfg, cos, sin, pos2d)
        with jax.named_scope("mla:latent"):
            cells = project_latent(h, p, cfg, cos, sin, pos2d)  # [B, K, W]
            pool = pool.at[base + write_pages, off_of].set(cells)
        with jax.named_scope("mla:absorb"):
            absorbed = jnp.einsum("bkhd,hcd->bkhc", q_nope, p["w_uk"])
            q = pad_to_cell(jnp.concatenate([absorbed, q_pe], -1), cfg)
        with jax.named_scope("mla:attend"):
            if use_kernel:
                from ray_tpu.ops.pallas.latent_attention import (
                    latent_paged_attention,
                )

                weighted = latent_paged_attention(
                    q, pool, base + tables, positions,
                    v_width=cfg.kv_lora_rank, scale=cfg.softmax_scale,
                    interpret=chip.platform() != "tpu",
                )
            else:
                weighted = _gather_latent_attention(
                    q, pool, base + tables, mask, cfg
                )
        with jax.named_scope("mla:absorb"):
            heads = jnp.einsum("bkhc,hcd->bkhd", weighted, p["w_uv"])
        return _attn_out(x, heads, p)

    for kind, p in zip(cfg.pattern, params["blocks"], strict=True):
        x = _layer(
            x, kind, p, cfg, attention, lambda: jnp.repeat(active, kk), record
        )
    logits = _head(x, params)  # [B, K, V]
    sampled = _sample_tokens(logits, temperature, rng_key)
    cache = {"latent": pool.reshape(cache["latent"].shape)}
    return sampled, logits[:, 0], cache, _record(record)


class LatentServing(Serving):
    """What `LLMEngine` serves a `PanguUltraMoEConfig` or a
    `LongcatFlashConfig` through; ``init_weights`` is the family's
    initialiser."""

    _paged = ("latent",)

    def __init__(self, cfg: LatentShape, init_weights):
        super().__init__(cfg, init_weights, cfg.count("E") + cfg.count("S"))
        self._tokens_expanded = self._prefill_programs = self._prefill_pairs = 0

    def init_cache(self, num_pages: int, page_size: int, max_batch: int,
                   shardings=None):
        return init_latent_cache(self.cfg, num_pages, page_size)

    def counters(self) -> dict:
        cfg = self.cfg
        return {
            **super().counters(),
            # What the arithmetic needs of a cached token, all attention
            # sublayers (`pool_bytes` has the cells as held,
            # `cell_width` wide).
            "latent_bytes_per_token": cfg.attn_sublayers * cfg.latent_dim
            * jnp.dtype(cfg.dtype).itemsize,
            "latent_tokens_expanded": self._tokens_expanded,
            # Prefill programs run, and the (query, key) pairs they
            # attended, summed over attention sublayers (heads not
            # among them).
            "latent_prefill_programs": self._prefill_programs,
            "latent_prefill_pairs": self._prefill_pairs,
        }

    def prefill_chunk(self, params, tokens, cache, pages, start, *,
                      n_write_pages, chunk_pages, slot, length, use_kernel):
        """One prefill program, and its counters: the (query, key) pairs
        it attends under the causal mask, and the cached tokens it turns
        back into keys and values (whole key blocks up to the chunk's
        end: the kernels' blocks, `keys_expanded`, or the XLA path's),
        in every attention sublayer."""
        cfg, c = self.cfg, tokens.shape[1]
        page_size = cache["latent"].shape[2]
        if use_kernel:
            from ray_tpu.ops.pallas.latent_attention import keys_expanded

            expanded = keys_expanded(int(start), c, n_write_pages * page_size)
        else:
            block = max(cfg.prefill_key_block // page_size, 1) * page_size
            expanded = -(-(int(start) + c) // block) * block
        self._tokens_expanded += cfg.attn_sublayers * expanded
        self._prefill_programs += 1
        self._prefill_pairs += cfg.attn_sublayers * (
            c * int(start) + c * (c + 1) // 2
        )
        return prefill_program(cfg, n_write_pages, chunk_pages, use_kernel)(
            params, tokens, cache, pages, start, np.int32(length)
        )

    def _decode_one(self, *args, **kwargs):
        return latent_decode(*args, **kwargs)  # the module's, as the call finds it
