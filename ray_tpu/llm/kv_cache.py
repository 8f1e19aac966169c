"""KV-cached forward passes for autoregressive decoding.

TPU-first: both programs have fully static shapes. The cache is a
[L, B, S_max, Hkv, Dh] ring of slots; prefill writes one slot's prompt,
decode advances every active slot by one token. Padding/garbage cache
entries are never attended (position mask) and are overwritten as
generation proceeds, so no dynamic shapes or host-side cache surgery are
needed — the whole decode loop is two cached XLA programs.

The reference has no native engine (SURVEY.md §2.4: ray.llm wraps vLLM);
this module is the compute core its vLLM dependency provided.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu._private import chip
from ray_tpu.models.llama import LlamaConfig, Params
from ray_tpu.ops.attention import causal_attention
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import apply_rope, rope_frequencies

_NEG_INF = -2.0e38

KVCache = dict[str, jnp.ndarray]  # {"k": [L,B,S,Hkv,Dh], "v": same}


def init_kv_cache(
    cfg: LlamaConfig, max_batch: int, max_seq: int
) -> KVCache:
    shape = (cfg.n_layers, max_batch, max_seq, cfg.n_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
    }


# The leaves the programs below and in `paged_kv.py` multiply by (the
# embedding is gathered from), all in cfg.dtype. The norm scales are not
# among them: `rms_norm` reads them as given.
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


def forward_prefill(
    params: Params,
    tokens: jnp.ndarray,  # [1, S_pad] int32 (one slot's prompt, padded)
    cache: KVCache,
    slot: jnp.ndarray,  # scalar int32: which cache row to fill
    cfg: LlamaConfig,
    use_flash: bool = False,
) -> tuple[jnp.ndarray, KVCache]:
    """Run the prompt through the model, writing K/V into cache[:, slot].

    Returns logits [1, S_pad, V] (caller reads position true_len-1) and
    the updated cache. Padding tokens write garbage K/V beyond true_len —
    harmless: decode masks keys at positions > its own current length and
    overwrites them one by one. ``use_flash`` routes attention through the
    Pallas flash kernel (forward-only path, so no VJP needed).
    """
    params = matmul_weights(params, cfg)
    seq = tokens.shape[1]
    cos, sin = rope_frequencies(cfg.head_dim, seq, cfg.rope_theta)
    x = params["tok_emb"][tokens]
    # The kernel accepts any length (blocks clamp to the largest divisor
    # of seq), but awkward lengths degrade: gate on the FITTED block
    # being MXU-friendly (>=128, multiple of 8) so prime-ish prompt
    # lengths keep the fused dense path instead of 1-wide Pallas tiles.
    from ray_tpu.ops.pallas.flash_attention import DEFAULT_BLOCK, _fit_block

    _blk = _fit_block(DEFAULT_BLOCK, seq)
    flash_ok = use_flash and seq >= 512 and _blk >= 128 and _blk % 8 == 0

    def attend(q, k, v):
        if flash_ok:
            from ray_tpu.ops.pallas import flash_attention

            # interpret mode runs the same kernel on CPU (tests).
            return flash_attention(
                q, k, v, interpret=chip.platform() != "tpu"
            )
        return causal_attention(q, k, v)

    def body(x, layer):
        p, k_row, v_row = layer
        q, k, v = _project_qkv(x, p, cfg)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        attn = attend(q, k, v)
        x = x + attn.reshape(x.shape) @ p["wo"]
        x = _mlp(x, p, cfg)
        # [B=1, S, Hkv, Dh] → write into this layer's [Bmax, Smax, ...] row.
        k_row = jax.lax.dynamic_update_slice(
            k_row, k.astype(cfg.dtype), (slot, 0, 0, 0)
        )
        v_row = jax.lax.dynamic_update_slice(
            v_row, v.astype(cfg.dtype), (slot, 0, 0, 0)
        )
        return x, (k_row, v_row)

    x, (k_cache, v_cache) = jax.lax.scan(
        body, x, (params["blocks"], cache["k"], cache["v"])
    )
    x = rms_norm(x, params["final_norm"])
    logits = (x @ params["lm_head"]).astype(jnp.float32)
    return logits, {"k": k_cache, "v": v_cache}


def forward_decode(
    params: Params,
    tokens: jnp.ndarray,  # [B, 1] int32: current token of every slot
    cache: KVCache,
    positions: jnp.ndarray,  # [B] int32: position each token sits at
    cfg: LlamaConfig,
) -> tuple[jnp.ndarray, KVCache]:
    """One decode step for all slots. Returns logits [B, V] + cache."""
    params = matmul_weights(params, cfg)
    x = params["tok_emb"][tokens]  # [B, 1, d]
    b = tokens.shape[0]
    max_seq = cache["k"].shape[2]
    # Table sized to the CACHE length, not cfg.max_seq: an engine may run
    # with a longer max_seq than the config default, and an out-of-range
    # gather would silently clamp to the last row (wrong rotations).
    cos, sin = rope_frequencies(cfg.head_dim, max_seq, cfg.rope_theta)

    # Keys at index > position are stale (padding or other requests'
    # leftovers); mask them. Index == position is this step's token.
    key_idx = jnp.arange(max_seq)[None, :]  # [1, S]
    mask = key_idx > positions[:, None]  # [B, S] True = masked

    def write_row(row, val, pos):
        # row [Smax, Hkv, Dh], val [1, Hkv, Dh]
        return jax.lax.dynamic_update_slice(row, val, (pos, 0, 0))

    def body(x, layer):
        p, k_row, v_row = layer  # k_row [B, Smax, Hkv, Dh]
        q, k, v = _project_qkv(x, p, cfg)  # q [B,1,H,Dh]
        pos2d = positions[:, None]  # [B, 1]
        q = apply_rope(q, cos, sin, positions=pos2d)
        k = apply_rope(k, cos, sin, positions=pos2d)
        k_row = jax.vmap(write_row)(k_row, k.astype(cfg.dtype), positions)
        v_row = jax.vmap(write_row)(v_row, v.astype(cfg.dtype), positions)

        n_rep = cfg.n_heads // cfg.n_kv_heads
        kk = jnp.repeat(k_row, n_rep, axis=2)  # [B, S, H, Dh]
        vv = jnp.repeat(v_row, n_rep, axis=2)
        scale = cfg.head_dim**-0.5
        logits = (
            jnp.einsum("bqhd,bkhd->bhqk", q, kk, preferred_element_type=jnp.float32)
            * scale
        )  # [B, H, 1, S]
        logits = jnp.where(mask[:, None, None, :], _NEG_INF, logits)
        probs = jax.nn.softmax(logits, axis=-1).astype(cfg.dtype)
        attn = jnp.einsum("bhqk,bkhd->bqhd", probs, vv)
        x = x + attn.reshape(b, 1, -1) @ p["wo"]
        x = _mlp(x, p, cfg)
        return x, (k_row, v_row)

    x, (k_cache, v_cache) = jax.lax.scan(
        body, x, (params["blocks"], cache["k"], cache["v"])
    )
    x = rms_norm(x, params["final_norm"])
    logits = (x @ params["lm_head"]).astype(jnp.float32)
    return logits[:, 0], {"k": k_cache, "v": v_cache}
