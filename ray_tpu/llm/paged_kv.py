"""Paged KV cache: block-table attention over a fixed page pool.

The serving engine's one cache and the programs that read and write it
(`LLMEngine` holds no other): the vLLM-style page pool the reference gets
from its serving engine (reference: ray.llm passes engine_kwargs straight
to vLLM,
python/ray/llm/_internal/serve/deployments/llm/vllm/vllm_models.py:234 —
block_size / num_gpu_blocks are vLLM's page knobs). A slab of
max_batch × max_seq slots would cost HBM whatever the sequences' lengths:

- One **page pool**: ``{"k","v": [L, num_pages, Hkv, page_size, Dh]}``,
  each layer's pages HEAD-major and row-major in memory (``[page, head,
  cell, dim]``: the Pallas decode kernel reads one KV head's page tile
  as a contiguous slice — measured ~40% faster than page-major).
  Capacity is a token budget (num_pages × page_size), independent of
  how many requests share it or how long each runs.
- **One buffer, one layout, argument to result.** Every program takes
  the pool donated and carries it through its layer loop
  (``_scan_layers``: a flat ``[L * num_pages, ...]`` view, each layer
  adding its page base to the ids), so writes land in place and no
  layer's pages are sliced out or stacked back. The layout is fixed by
  whoever reads the pages: on the kernel path (``use_kernel=True``: a
  bare TPU) by ``paged_attention``, a Mosaic call whose operands are
  row-major, so the decode write is a Mosaic call too
  (``ops/pallas/kv_cell_write.py``) and XLA never gets to choose; on
  the XLA path (``use_kernel=False``: a ``tp`` mesh, a CPU) scatter and
  gather are both XLA's and it gives them one layout (compiled for a
  TPU: the carried pool is re-laid-out once at the program's entry and
  once at its exit, where it was twice a layer; no cell measures it).
- A **block table** per request: the ordered list of page ids holding
  its tokens. Tables live on the host (numpy, tiny) and ship to the
  device each step as a [B, max_pages] int32 array.
- **Decode**, kernel path: the K cells a slot writes are patched into
  their pages, then ``paged_attention`` reads each slot's live pages in
  place (the pages up to ``positions[b] + K``: a free slot is at
  position 0). XLA path: the cells are scattered (``.at[].set``), each slot's
  pages gathered (jnp.take along the page axis) and attended under a
  mask — static shapes, gather and attention fused, the layout folded
  into the einsums (_gather_page_attention).
- **Prefill** computes K/V with the normal dense program and scatters
  them, whole pages at a time, into freshly-allocated pages (XLA's
  scatter keeps the pool's layout when it writes whole pages). Kernel
  path: a chunk's queries attend the context so far by
  ``prefill_attention`` (ops/pallas/prefill_attention.py), whose keys
  are the request's own pages gathered as they lie (``paged_prefill``:
  the prompt's fresh cells): no scores in HBM and no key past ``start +
  C`` read. XLA path: dense float32 scores, over the whole gathered
  table under a mask in ``paged_prefill_chunk``.
- **Prefix sharing**: full pages whose token prefix hashes equal an
  existing page's are refcounted and reused instead of re-written —
  identical prompt heads across requests occupy one set of pages
  (memory dedup; compute dedup via chunked prefill is future work).

TPU-first notes: everything under jit has static shapes — the gather
width is the per-call max_pages bucket, masked per-slot by true length.
Pool pages are never zeroed on free; stale data is unreachable because
attention masks beyond each slot's length and tables are host-owned.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu._private import chip
from ray_tpu.llm.serving import Serving
from ray_tpu.models.llama import LlamaConfig, Params
from ray_tpu.ops.attention import causal_attention
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import apply_rope, rope_frequencies

_NEG_INF = -2.0e38

PagedKV = dict[str, jnp.ndarray]  # {"k","v": [L, num_pages, Hkv, P, Dh]}

# The live memory-ledger claim for this process's KV pool (one pool
# per process); init_paged_kv closes and replaces it on re-init.
_KV_REG = None


def init_paged_kv(
    cfg: LlamaConfig, num_pages: int, page_size: int = 64,
    n_layers: int | None = None,
) -> PagedKV:
    """``n_layers``: how many of the model's blocks attend, where not
    all do (``llm/hybrid_kv.py``)."""
    shape = (
        cfg.n_layers if n_layers is None else n_layers,
        num_pages, cfg.n_kv_heads, page_size, cfg.head_dim,
    )
    kv = {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
    }
    # Claim the pool in the device-memory ledger (runtime/memory.py):
    # the KV pages are serving's big fixed HBM tenant (the token-budget
    # analogue of the trainer's param/optimizer claim). The live
    # Registration is retained module-level so the claim has an owner:
    # a re-created pool explicitly retires the previous claim instead
    # of relying on tag replacement (TPU404).
    from ray_tpu.runtime import memory as _rmem

    global _KV_REG
    if _KV_REG is not None:
        _KV_REG.close()
    _KV_REG = _rmem.track(
        "llm.paged_kv", kind="kv_cache",
        nbytes=int(kv["k"].nbytes + kv["v"].nbytes),
    )
    _rmem.tag_arrays("llm.paged_kv", "kv_cache", kv)
    return kv


class PageAllocator:
    """Host-side page bookkeeping: free list, per-page refcounts, and the
    prefix-hash → page map for sharing (reference capability: vLLM's
    BlockSpaceManager + prefix caching)."""

    def __init__(self, num_pages: int, page_size: int):
        # `num_pages` counts USABLE pages. Physical page 0 is the DUMP
        # page: inactive decode slots' table entries clamp to it, so
        # their (discarded) writes land somewhere no request owns. The
        # pool must therefore be created with num_pages + 1 physical
        # pages (the engine does).
        self.num_pages = num_pages
        self.page_size = page_size
        self._free: list[int] = list(range(1, num_pages + 1))
        self._refs = np.zeros(num_pages + 1, np.int32)
        # prefix-hash → page id; hash covers ALL tokens up to and
        # including this page (k/v of a position depend on the whole
        # prefix, so equal hash ⇒ identical page contents).
        self._prefix_pages: dict[int, int] = {}
        self._page_hash: dict[int, int] = {}  # page id → its prefix hash

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def alloc(self) -> int:
        page = self._free.pop()
        self._refs[page] = 1
        return page

    def share(self, page: int) -> int:
        self._refs[page] += 1
        return page

    def release(self, page: int) -> None:
        self._refs[page] -= 1
        if self._refs[page] == 0:
            h = self._page_hash.pop(page, None)
            if h is not None and self._prefix_pages.get(h) == page:
                del self._prefix_pages[h]
            self._free.append(page)

    def lookup_prefix(self, prefix_hash: int) -> int | None:
        return self._prefix_pages.get(prefix_hash)

    def register_prefix(self, prefix_hash: int, page: int) -> None:
        self._prefix_pages[prefix_hash] = page
        self._page_hash[page] = prefix_hash


def prefix_hashes(tokens: list[int], page_size: int) -> list[int]:
    """One hash per FULL page, each covering tokens[0 : (i+1)*page]."""
    out = []
    for end in range(page_size, len(tokens) + 1, page_size):
        out.append(hash(tuple(tokens[:end])))
    return out


# ------------------------------------------------------------- programs
# The leaves the programs below multiply by (the embedding is gathered
# from), all in cfg.dtype. The norm scales are not among them:
# `rms_norm` reads them as given.
_MATMUL_BLOCK_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def matmul_weights(params: Params, cfg: LlamaConfig) -> Params:
    """`params` with every weight the serving programs multiply by in
    cfg.dtype, the rest as given. Each program starts with it, so a raw
    fp32 tree (`init_params`) gives what it always gave; `LLMEngine`
    calls it once and holds the result, on which it is the identity: no
    program of an engine then reads an fp32 stack to round it again.
    A weight that a program multiplies by goes into this list."""
    dt = cfg.dtype
    blocks = dict(params["blocks"])
    for name in _MATMUL_BLOCK_LEAVES:
        blocks[name] = blocks[name].astype(dt)
    return {
        **params,
        "tok_emb": params["tok_emb"].astype(dt),
        "lm_head": params["lm_head"].astype(dt),
        "blocks": blocks,
    }


def _project_qkv(x, p, cfg):
    b, s, _ = x.shape
    h = rms_norm(x, p["attn_norm"])
    q = (h @ p["wq"]).reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = (h @ p["wk"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = (h @ p["wv"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    return q, k, v


def _mlp(x, p, cfg):
    h = rms_norm(x, p["mlp_norm"])
    gate = jax.nn.silu(h @ p["w_gate"])
    up = h @ p["w_up"]
    return x + (gate * up) @ p["w_down"]


def _gather_page_attention(q, k_pool, v_pool, page_index, mask, cfg,
                           scale=None):
    """Dense masked attention over gathered pool pages — the XLA
    fallback shared by decode/verify and chunked prefill (one body: a
    numerics change here changes every gather-path caller at once).

    q: [B, Q, H, Dh]; page_index: [B, n_pages] int32 (>= 0);
    mask: [B, Q, window] bool, True = hidden; ``scale`` multiplies the
    scores (``Dh**-0.5`` where None). Returns [B, Q, H, Dh].
    """
    b, q_len = q.shape[0], q.shape[1]
    hkv = cfg.n_kv_heads
    n_rep = cfg.n_heads // hkv
    dh = cfg.head_dim
    n_pages = page_index.shape[1]
    page_size = k_pool.shape[2]
    window = n_pages * page_size
    # Head-major pool gathers to [B, n_pages, Hkv, P, Dh]; the page and
    # cell dims contract/flatten INSIDE the einsums — no materialized
    # layout transpose and no GQA repeat (q is grouped by KV head
    # instead: head h = g*n_rep + r).
    kk = jnp.take(k_pool, page_index, axis=0)
    vv = jnp.take(v_pool, page_index, axis=0)
    qg = q.reshape(b, q_len, hkv, n_rep, dh)
    if scale is None:
        scale = dh**-0.5
    logits = (
        jnp.einsum(
            "bqgrd,bngpd->bgrqnp", qg, kk,
            preferred_element_type=jnp.float32,
        )
        * scale
    ).reshape(b, hkv, n_rep, q_len, window)
    logits = jnp.where(
        mask[:, None, None, :, :], _NEG_INF, logits
    )
    probs = jax.nn.softmax(logits, axis=-1).astype(cfg.dtype)
    attn = jnp.einsum(
        "bgrqnp,bngpd->bqgrd",
        probs.reshape(b, hkv, n_rep, q_len, n_pages, page_size),
        vv,
    )
    return attn.reshape(b, q_len, cfg.n_heads, dh)


def _page_cells(a, page_size: int, cfg):
    """Keys or values ``[1, S, Hkv, Dh]`` as the ``S / page_size`` pages
    they fill, ``[n, Hkv, P, Dh]`` in ``cfg.dtype``: the pool's cells."""
    return a.astype(cfg.dtype).reshape(
        -1, page_size, cfg.n_kv_heads, cfg.head_dim
    ).transpose(0, 2, 1, 3)


def _write_pages(k_pages, v_pages, k, v, page_ids, cfg):
    """A prompt's (or a chunk's) keys and values, ``[1, S, Hkv, Dh]``
    with S = len(page_ids) * page_size, scattered into the pages
    ``page_ids`` of the flat pool as ``[n, Hkv, P, Dh]`` cells."""
    page_size = k_pages.shape[2]
    return (
        k_pages.at[page_ids].set(_page_cells(k, page_size, cfg)),
        v_pages.at[page_ids].set(_page_cells(v, page_size, cfg)),
    )


def _prefill_kernel_attention(q, k_cells, v_cells, start, scale=None):
    """A chunk's attention by the prefill kernel (a bare TPU; interpreted
    elsewhere): q ``[1, C, H, Dh]`` at ``start ..`` over the context's
    pages in order, ``[n, Hkv, P, Dh]``. No scores in HBM, no key past
    ``start + C`` read (ops/pallas/prefill_attention.py)."""
    from ray_tpu.ops.pallas.prefill_attention import prefill_attention

    return prefill_attention(
        q[0], k_cells, v_cells, start,
        interpret=chip.platform() != "tpu", scale=scale,
    )[None]


def _decode_geometry(block_tables, positions, kk_w: int, page_size: int):
    """Where a decode step of K tokens a slot writes and what it may
    see: ``pos2d`` [B, K], the gather path's ``mask`` [B, K, window]
    (True = hidden), each write's physical page and cell
    (``write_pages``, ``off_of`` [B, K]) and the tables with -1 as the
    dump page."""
    max_pages = block_tables.shape[1]
    window = max_pages * page_size
    pos2d = positions[:, None] + jnp.arange(kk_w)[None, :]  # [B, K]
    key_idx = jnp.arange(window)[None, None, :]
    mask = key_idx > pos2d[:, :, None]  # [B, K, window]

    page_of = jnp.minimum(pos2d // page_size, max_pages - 1)  # [B, K]
    off_of = pos2d % page_size
    # Physical pages for each write. Two overflow routes to the dump
    # page 0 (whose contents nobody attends): inactive slots
    # (table -1) and draft positions past the table window — near
    # max_seq a K-wide step can extend beyond capacity, and clamping
    # into the LAST page would corrupt live cells.
    write_pages = jnp.maximum(
        jnp.take_along_axis(block_tables, page_of, axis=1), 0
    )
    write_pages = jnp.where(pos2d < window, write_pages, 0)  # [B, K]
    return pos2d, mask, write_pages, off_of, jnp.maximum(block_tables, 0)


def _decode_attention(q, k, v, k_pages, v_pages, base, geometry, positions,
                      cfg, use_kernel: bool, scale=None):
    """A decode step's attention for one layer whose pages start at
    ``base`` of the flat pool: write all K cells per slot (drafts may
    span a page boundary — each position indexes its own physical
    page), then attend. q [B, K, H, Dh], k and v [B, K, Hkv, Dh] in
    ``cfg.dtype``. The write follows the attention's path, so that one
    party fixes the pool's layout (module docstring). ``scale``
    multiplies the scores (``Dh**-0.5`` where None). Returns (attn
    [B, K, H, Dh], k_pages, v_pages)."""
    _, mask, write_pages, off_of, tables = geometry
    b, kk_w = q.shape[:2]
    if use_kernel:
        # Pallas path: cells patched into their pages in place,
        # slot-major (a slot's drafts on consecutive grid steps, as
        # write_kv_cells needs); pages read in place, GQA-grouped,
        # each slot's own live pages and no more
        # (ops/pallas/paged_attention.py).
        from ray_tpu.ops.pallas.kv_cell_write import write_kv_cells
        from ray_tpu.ops.pallas.paged_attention import paged_attention

        interpret = chip.platform() != "tpu"
        k_pages, v_pages = write_kv_cells(
            k_pages, v_pages,
            k.reshape(b * kk_w, cfg.n_kv_heads, cfg.head_dim),
            v.reshape(b * kk_w, cfg.n_kv_heads, cfg.head_dim),
            (base + write_pages).reshape(-1), off_of.reshape(-1),
            interpret=interpret,
        )
        attn = paged_attention(
            q, k_pages, v_pages, base + tables, positions,
            n_kv_heads=cfg.n_kv_heads, interpret=interpret, scale=scale,
        )
    else:
        # Advanced indices at dims 0 and 2 with the Hkv slice
        # between: result dims are [B, K, Hkv, Dh], matching k.
        k_pages = k_pages.at[base + write_pages, :, off_of, :].set(k)
        v_pages = v_pages.at[base + write_pages, :, off_of, :].set(v)
        attn = _gather_page_attention(
            q, k_pages, v_pages, base + tables, mask, cfg, scale
        )
    return attn, k_pages, v_pages


def _head(x, params, norm_eps=1e-5, tied=False, divisor=1.0):
    """Final norm and the head on x [..., d]: float32 logits, over
    ``divisor``; ``tied``: the head is the embedding."""
    x = rms_norm(x, params["final_norm"], norm_eps)
    if tied:
        logits = jnp.einsum("...d,vd->...v", x, params["tok_emb"])
    else:
        logits = x @ params["lm_head"]
    logits = logits.astype(jnp.float32)
    if divisor != 1.0:
        logits = logits / divisor
    return logits


def _sample_tokens(logits, temperature, rng_key):
    """Per-position sampling of logits [B, K, V]: greedy for temp 0,
    temperature draw otherwise (the full-p sample — used for position
    0, for the bonus token when a whole draft is accepted, and for
    every position on greedy slots). Returns int32 [B, K]."""
    b, kk_w = logits.shape[:2]
    flat = logits.reshape(b * kk_w, -1)
    temp_flat = jnp.repeat(temperature, kk_w)
    keys = jax.random.split(rng_key, b * kk_w)
    greedy = jnp.argmax(flat, axis=-1)
    drawn = jax.vmap(jax.random.categorical)(
        keys, flat / jnp.maximum(temp_flat, 1e-6)[:, None]
    )
    sampled = jnp.where(temp_flat > 0.0, drawn, greedy).astype(jnp.int32)
    return sampled.reshape(b, kk_w)


def _flat_pool(pool: PagedKV):
    """The pool's pages in a flat view, ``[L * num_pages, Hkv, P, Dh]``
    (a bitcast): layer ``i``'s pages start at ``i * num_pages``."""
    n_layers, num_pages = pool["k"].shape[:2]

    def flat(a):
        return a.reshape((n_layers * num_pages,) + a.shape[2:])

    return flat(pool["k"]), flat(pool["v"])


def _scan_layers(body, x, params, pool: PagedKV):
    """Run ``body(x, k_pages, v_pages, p, base) -> (x, k_pages, v_pages)``
    over the layers with the pool as the loop's CARRY: one buffer from
    the program's argument to its result, updated in place.

    ``k_pages`` / ``v_pages`` are the whole pool in a flat view,
    ``[L * num_pages, Hkv, P, Dh]`` (a bitcast), and ``base`` is the
    layer's first page in it: a layer reaches its pages by adding
    ``base`` to page ids, never by slicing itself out. (Scanned as an
    input and stacked as an output, every layer's 100 MB of pages was
    sliced out of one stack and written into another, and the entry
    copied both stacks.)
    """
    n_layers, num_pages = pool["k"].shape[:2]

    def step(carry, layer):
        p, index = layer
        return body(*carry, p, index * num_pages), None

    (x, k_pages, v_pages), _ = jax.lax.scan(
        step,
        (x, *_flat_pool(pool)),
        (params["blocks"], jnp.arange(n_layers, dtype=jnp.int32)),
    )
    return x, {
        "k": k_pages.reshape(pool["k"].shape),
        "v": v_pages.reshape(pool["v"].shape),
    }


@partial(
    jax.jit,
    static_argnames=("cfg", "n_write_pages", "use_kernel"),
    donate_argnames=("pool",),
)
def paged_prefill(
    params,
    tokens: jnp.ndarray,  # [1, S_pad] int32
    pool: PagedKV,
    pages: jnp.ndarray,  # [n_write_pages] int32 page ids for this prompt
    cfg: LlamaConfig,
    n_write_pages: int,
    use_kernel: bool = False,
):
    """Dense prompt pass; K/V scattered into `pages` of the pool.

    S_pad must equal n_write_pages * page_size (caller pads). `pages`
    covers the WHOLE padded prompt including shared-prefix pages: their
    content is rewritten with byte-identical values (K/V at position i
    depend only on tokens <= i), so sharing needs no scatter mask.
    ``use_kernel``: attend by the prefill kernel, the prompt's fresh
    cells its keys (``start`` 0), in place of dense ``[H, S, S]`` scores.
    Returns (logits [1, S_pad, V] fp32, pool).
    """
    params = matmul_weights(params, cfg)
    seq = tokens.shape[1]
    page_size = pool["k"].shape[3]
    cos, sin = rope_frequencies(cfg.head_dim, seq, cfg.rope_theta)
    x = params["tok_emb"][tokens]

    def body(x, k_pages, v_pages, p, base):
        q, k, v = _project_qkv(x, p, cfg)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        if use_kernel:
            attn = _prefill_kernel_attention(
                q, _page_cells(k, page_size, cfg),
                _page_cells(v, page_size, cfg), jnp.int32(0),
            )
        else:
            attn = causal_attention(q, k, v)
        x = x + attn.reshape(x.shape) @ p["wo"]
        x = _mlp(x, p, cfg)
        # [1, S, Hkv, Dh] → [n_pages, Hkv, P, Dh] scatter at page ids.
        k_pages, v_pages = _write_pages(
            k_pages, v_pages, k, v, base + pages, cfg
        )
        return x, k_pages, v_pages

    x, pool = _scan_layers(body, x, params, pool)
    return _head(x, params), pool


@partial(
    jax.jit,
    static_argnames=("cfg", "n_write_pages", "chunk_pages", "use_kernel"),
    donate_argnames=("pool",),
)
def paged_prefill_chunk(
    params,
    tokens: jnp.ndarray,  # [1, C] int32, C = chunk_pages * page_size
    pool: PagedKV,
    pages: jnp.ndarray,  # [n_write_pages] int32: the FULL context table
    start: jnp.ndarray,  # [] int32: global position of tokens[0, 0]
    cfg: LlamaConfig,
    n_write_pages: int,
    chunk_pages: int,
    use_kernel: bool = False,
):
    """One prefill CHUNK: compute K/V for ``tokens`` at positions
    ``start .. start+C-1``, scatter them into the chunk's slice of
    ``pages``, and attend each chunk query over the whole context so
    far (earlier chunks' pages + this chunk, causal within the chunk).

    Splitting prefill this way is what lets the engine interleave a
    long prompt with decode steps instead of stalling every in-flight
    request for the prompt's full dense pass (reference capability:
    vLLM's chunked prefill, which ray.llm buys via engine_kwargs).
    ``start`` must be page-aligned; K/V of a position depend only on
    tokens <= it, so chunking is mathematically exact.

    ``use_kernel``: the request's pages are gathered as they lie (33 MB
    a layer at 8,192 tokens) and the prefill kernel attends the context
    so far; without it, dense float32 scores over the whole table under
    a mask (``_gather_page_attention``).

    Returns (logits [1, C, V] fp32, pool).
    """
    params = matmul_weights(params, cfg)
    c = tokens.shape[1]
    page_size = pool["k"].shape[3]
    window = n_write_pages * page_size
    cos, sin = rope_frequencies(cfg.head_dim, window, cfg.rope_theta)
    pos = start + jnp.arange(c, dtype=jnp.int32)[None, :]  # [1, C]
    x = params["tok_emb"][tokens]
    chunk_slice = jax.lax.dynamic_slice(
        pages, [start // page_size], [chunk_pages]
    )
    key_idx = jnp.arange(window)[None, None, :]
    mask = key_idx > pos[:, :, None]  # [1, C, window]

    def body(x, k_pages, v_pages, p, base):
        q, k, v = _project_qkv(x, p, cfg)  # [1, C, H, Dh]
        q = apply_rope(q, cos, sin, positions=pos)
        k = apply_rope(k, cos, sin, positions=pos)
        k_pages, v_pages = _write_pages(
            k_pages, v_pages, k, v, base + chunk_slice, cfg
        )
        if use_kernel:
            attn = _prefill_kernel_attention(
                q, jnp.take(k_pages, base + pages, axis=0, mode="clip"),
                jnp.take(v_pages, base + pages, axis=0, mode="clip"), start,
            )
        else:
            attn = _gather_page_attention(
                q, k_pages, v_pages, base + pages[None, :], mask, cfg
            )
        x = x + attn.reshape(1, c, -1) @ p["wo"]
        x = _mlp(x, p, cfg)
        return x, k_pages, v_pages

    x, pool = _scan_layers(body, x, params, pool)
    return _head(x, params), pool


@partial(
    jax.jit,
    static_argnames=("cfg", "use_kernel", "stochastic"),
    donate_argnames=("pool",),
)
def paged_verify(
    params,
    tokens: jnp.ndarray,  # [B, K] int32: next token + K-1 draft tokens
    pool: PagedKV,
    block_tables: jnp.ndarray,  # [B, max_pages] int32 (-1 = unused)
    positions: jnp.ndarray,  # [B] int32: position tokens[:, 0] writes at
    temperature: jnp.ndarray,  # [B] fp32 (0 = greedy)
    rng_key: jnp.ndarray,
    cfg: LlamaConfig,
    use_kernel: bool = False,
    stochastic: bool = True,
):
    """The decode program: K tokens per slot in ONE pass over the page
    pool, sampled ON DEVICE (the host receives token ids, not [B, K, V]
    logits). K = 1 is the plain decode step. K > 1 is the speculative
    verify step (reference capability: vLLM's speculative/prompt-lookup
    decoding, the serving engine behind ray.llm): tokens[:, 0] is the
    ordinary next token; tokens[:, 1:] are HOST-PROPOSED draft tokens
    (n-gram prompt lookup — no draft model). The engine accepts the
    longest prefix the model agrees with, advancing up to K tokens per
    dispatch.

    Acceptance inputs are computed ON DEVICE for every slot:

    - greedy slots (temp 0): ``accept[b, j]`` = the model's argmax
      after position j equals draft token j+1 — exactly the original
      host comparison.
    - stochastic slots: exact rejection sampling against the
      prompt-lookup draft's delta distribution q(x) = 1{x = draft}:
      accept with probability min(1, p(draft)/q(draft)) = p(draft),
      and on rejection emit a sample from the residual
      norm(max(p - q, 0)) — i.e. p with the draft token masked out.
      The emitted stream is distributed EXACTLY as sampling from p
      (Leviathan et al.; vLLM's rejection sampler).

    Rejected drafts need no rollback: a rejected position's K/V cell is
    re-written by the next step's scatter BEFORE any query attends that
    position (scatter precedes gather within each layer, and the causal
    mask hides cells beyond each query's position until then).

    Returns (sampled [B, K] int32, logits [B, V] fp32 for position 0,
    pool, accept [B, K-1] bool, rej [B, K-1] int32 residual samples):
    what every caller reads, then speculation's two, which are [B, 0]
    at K = 1 (``stochastic`` then changes nothing but the cache key:
    pass False).
    """
    params = matmul_weights(params, cfg)
    b, kk_w = tokens.shape
    x = params["tok_emb"][tokens]  # [B, K, d]
    page_size = pool["k"].shape[3]
    max_pages = block_tables.shape[1]
    window = max_pages * page_size
    cos, sin = rope_frequencies(cfg.head_dim, window, cfg.rope_theta)

    geometry = _decode_geometry(block_tables, positions, kk_w, page_size)
    pos2d = geometry[0]

    def body(x, k_pages, v_pages, p, base):
        q, k, v = _project_qkv(x, p, cfg)  # [B, K, H, Dh]
        q = apply_rope(q, cos, sin, positions=pos2d)
        k = apply_rope(k, cos, sin, positions=pos2d)
        k = k.astype(cfg.dtype)
        v = v.astype(cfg.dtype)

        attn, k_pages, v_pages = _decode_attention(
            q, k, v, k_pages, v_pages, base, geometry, positions, cfg,
            use_kernel,
        )
        x = x + attn.reshape(b, kk_w, -1) @ p["wo"]
        x = _mlp(x, p, cfg)
        return x, k_pages, v_pages

    x, pool = _scan_layers(body, x, params, pool)
    logits = _head(x, params)

    sampled = _sample_tokens(logits, temperature, rng_key)

    if kk_w > 1:
        # Draft acceptance inputs (see docstring). Positions 0..K-2
        # judge draft tokens 1..K-1.
        drafts = tokens[:, 1:]  # [B, K-1]
        head = logits[:, : kk_w - 1]  # [B, K-1, V] fp32
        head_argmax = jnp.argmax(head, axis=-1)
        acc_greedy = head_argmax == drafts
        if stochastic:
            temp_c = jnp.maximum(temperature, 1e-6)[:, None, None]
            probs = jax.nn.softmax(head / temp_c, axis=-1)
            p_draft = jnp.take_along_axis(
                probs, drafts[:, :, None], axis=-1
            )[..., 0]  # [B, K-1]
            u = jax.random.uniform(
                jax.random.fold_in(rng_key, 1), (b, kk_w - 1)
            )
            accept = jnp.where(
                temperature[:, None] > 0.0, u < p_draft, acc_greedy
            )
            # Residual emission on rejection: p with the draft token
            # masked (stochastic); the plain argmax for greedy
            # (identical to the original host behavior — rejection
            # implies argmax != draft).
            masked = head + jnp.where(
                jax.nn.one_hot(drafts, head.shape[-1], dtype=jnp.bool_),
                _NEG_INF,
                0.0,
            )
            rej_keys = jax.random.split(
                jax.random.fold_in(rng_key, 2), b * (kk_w - 1)
            )
            rej_drawn = jax.vmap(jax.random.categorical)(
                rej_keys,
                (masked / temp_c).reshape(b * (kk_w - 1), -1),
            ).reshape(b, kk_w - 1)
            rej = jnp.where(
                temperature[:, None] > 0.0,
                rej_drawn,
                head_argmax,
            ).astype(jnp.int32)
        else:
            # All-greedy batch (static flag from the engine): the
            # rejection tensors — a [B, K-1, V] softmax, one_hot mask,
            # and b*(K-1) categorical draws — would be dead weight on
            # every dispatch.
            accept = acc_greedy
            rej = head_argmax.astype(jnp.int32)
    else:
        accept = jnp.zeros((b, 0), jnp.bool_)
        rej = jnp.zeros((b, 0), jnp.int32)
    # Only position 0's logits ever reach the host (top_k fallback);
    # shipping [B, K, V] would multiply that transfer by K for nothing.
    return sampled, logits[:, 0], pool, accept, rej


class LlamaServing(Serving):
    """What `LLMEngine` serves a Llama-shaped model through: the page
    pool and the three programs above. These need nothing of the slot,
    the true length or the decoding slots (pages hold all their state,
    and a padded tail or a free slot writes cells nobody attends), so
    they are dropped here but for the count of what prefill attends. No
    expert blocks: no record."""

    no_speculation = None  # `paged_verify` accepts drafts
    logits_last_only = False  # prefill returns every position's logits
    fixed_chunks = False  # the last chunk of a prompt is as long as it is
    _paged = ("k", "v")

    def __init__(self, cfg: LlamaConfig):
        super().__init__(cfg, _init_weights)
        self._prefill_pairs = 0

    def logical_axes(self):
        from ray_tpu.models.llama import param_logical_axes

        return param_logical_axes(self.cfg)

    def held_weights(self, params):
        # The caller's own arrays where nothing is to be cast.
        held = _cast_weights.eval_shape(params, cfg=self.cfg)
        if [x.dtype for x in jax.tree.leaves(held)] == [
            x.dtype for x in jax.tree.leaves(params)
        ]:
            return params
        return _cast_weights(params, cfg=self.cfg)

    def init_cache(self, num_pages: int, page_size: int, max_batch: int,
                   shardings=None):
        make = partial(init_paged_kv, self.cfg, num_pages, page_size)
        if shardings is None:
            return make()
        return jax.jit(make, out_shardings=shardings)()

    def counters(self) -> dict:
        # The causal (query, key) pairs the prefill calls' arithmetic
        # needed, summed over layers (heads not among them): a prompt's
        # own tokens against what lies at or before each, whatever the
        # padding, the table's width or the path computed besides.
        return {**super().counters(), "prefill_attn_pairs": self._prefill_pairs}

    def _count_pairs(self, start: int, width: int, length) -> None:
        n = width if length is None else max(min(width, length - start), 0)
        self._prefill_pairs += self.cfg.n_layers * (
            n * start + n * (n + 1) // 2
        )

    def prefill(self, params, tokens, pool, pages, *, n_write_pages,
                slot=None, length=None, use_kernel=False):
        self._count_pairs(0, tokens.shape[1], length)
        return paged_prefill(
            params, tokens, pool, pages, cfg=self.cfg,
            n_write_pages=n_write_pages, use_kernel=use_kernel,
        )

    def prefill_chunk(self, params, tokens, pool, pages, start, *,
                      n_write_pages, chunk_pages, slot=None, length=None,
                      use_kernel=False):
        self._count_pairs(int(start), tokens.shape[1], length)
        return paged_prefill_chunk(
            params, tokens, pool, pages, start, cfg=self.cfg,
            n_write_pages=n_write_pages, chunk_pages=chunk_pages,
            use_kernel=use_kernel,
        )

    def decode(self, params, tokens, pool, block_tables, positions,
               temperature, rng_key, *, use_kernel, stochastic, active=None):
        return paged_verify(
            params, tokens, pool, block_tables, positions, temperature,
            rng_key, cfg=self.cfg, use_kernel=use_kernel,
            stochastic=stochastic,
        )


_cast_weights = jax.jit(matmul_weights, static_argnames="cfg")


@partial(jax.jit, static_argnames="cfg")
def _init_weights(key, cfg):
    """An engine's own weights, made as it holds them: `init_params`'
    values rounded once, without an fp32 copy of the tree on the device
    beside them."""
    from ray_tpu.models.llama import init_params

    return matmul_weights(init_params(key, cfg), cfg)


def propose_ngram_draft(
    context: list[int] | np.ndarray, k: int, ngram: int = 2
) -> list[int]:
    """Prompt-lookup drafting (host side, no draft model): find the
    most recent earlier occurrence of the last ``ngram`` tokens and
    propose the ``k`` tokens that followed it. Returns [] when no match
    — the verify pass then degenerates to a normal decode step.

    Vectorized: one numpy sliding-window comparison per call — this
    runs per greedy slot per decode step, so a Python slice-compare
    scan would put O(context) interpreter work on the serial host path
    in front of every dispatch."""
    ctx = np.asarray(context, dtype=np.int64)
    n = len(ctx)
    if n < ngram + 1 or k <= 0:
        return []
    tail = ctx[n - ngram:]
    # Window starts eligible as a match: exclude the tail itself.
    hits = ctx[: n - 1 - (ngram - 1)] == tail[0]
    for j in range(1, ngram):
        hits = hits & (ctx[j: n - 1 - (ngram - 1) + j] == tail[j])
    idx = np.nonzero(hits)[0]
    if idx.size == 0:
        return []
    start = int(idx[-1])  # rightmost: recent repetition predicts best
    follow = ctx[start + ngram: start + ngram + k]
    return follow.astype(int).tolist()
