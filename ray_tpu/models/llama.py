"""Flagship model: Llama-3-style decoder-only transformer in pure JAX.

GQA attention + RoPE + SwiGLU + RMSNorm, parameters stored fp32 and cast to
bf16 at use (mixed precision), layers stacked on a leading dim and executed
with `lax.scan` (+ optional rematerialization) so XLA compiles one layer
body regardless of depth — static shapes, no Python-level per-layer loop.

Every parameter carries logical axes (see ray_tpu.parallel.sharding) so a
single rule table gives DP/FSDP/TP/SP shardings under pjit. This is the
model behind BASELINE.json configs 2–3 (the reference's equivalent role is
filled by user torch code under TorchTrainer; SURVEY.md section 2.4).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops.attention import causal_attention
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import apply_rope, rope_frequencies
from ray_tpu.parallel.sharding import constrain

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 14336
    max_seq: int = 8192
    rope_theta: float = 500000.0
    dtype: Any = jnp.bfloat16
    # Remat policy for the scanned layer body:
    #   "none"  keep all activations (fastest, most memory)
    #   "full"  recompute everything in backward (least memory)
    #   "dots"  save matmul outputs, recompute elementwise (middle ground;
    #           jax dots_with_no_batch_dims_saveable)
    remat: str = "full"
    # "dense" | "ring" | "ulysses": attention strategy. ring/ulysses need a
    # mesh with sp>1 (built by ray_tpu.train.step.jit_train_step).
    attn_impl: str = "dense"
    # Embedding lookup strategy:
    #   "gather"  table[tokens] — fastest on a single chip
    #   "onehot"  one_hot(tokens) @ table — a matmul, which the SPMD
    #             partitioner handles cleanly when the table is sharded
    #             (vocab on tp, embed on fsdp); a sharded gather instead
    #             triggers "involuntary full rematerialization" (the
    #             compiler replicates the whole activation to reshard)
    #   "auto"    onehot when >1 device is visible, else gather
    embed_impl: str = "auto"
    # RMSNorm (learned weight) over the whole query and key projections,
    # before the split into heads and the rope (OLMoE; its config has no
    # key for it, modeling_olmoe.py does it always). A field of the
    # model's shape like n_kv_heads, not a knob.
    qk_norm: bool = False

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def num_params(self) -> int:
        d, f, v = self.d_model, self.d_ff, self.vocab_size
        attn = d * (self.n_heads * self.head_dim) * 2 + d * (
            self.n_kv_heads * self.head_dim
        ) * 2
        mlp = 3 * d * f
        per_layer = attn + mlp + 2 * d
        return self.n_layers * per_layer + 2 * v * d + d

    def flops_per_token(self, seq: int) -> float:
        """Training (fwd+bwd) FLOPs per token: 6*N_matmul + attention term."""
        d, v = self.d_model, self.vocab_size
        matmul_params = self.num_params() - v * d  # exclude embedding lookup
        attn_flops = 12 * self.n_layers * d * seq  # 6 * 2 * L * d * s
        return 6.0 * matmul_params + attn_flops


PRESETS: dict[str, LlamaConfig] = {
    # CPU-test scale.
    "tiny": LlamaConfig(
        vocab_size=512, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq=256, dtype=jnp.float32, remat="none",
    ),
    # Single-chip graft-entry scale (~125M).
    "mini": LlamaConfig(
        vocab_size=32768, d_model=768, n_layers=12, n_heads=12, n_kv_heads=4,
        d_ff=2048, max_seq=2048,
    ),
    # Single-chip benchmark scale (~430M). head_dim 128 (Llama-3's) over
    # 64: the MXU is 128 wide, so D=64 attention runs both kernel
    # matmuls at half width — same parameter count (h·D and hkv·D
    # unchanged), ~40% faster attention.
    # remat="flash_qkv": keep the flash kernel's residuals (out+lse)
    # AND its q/k/v inputs across the remat boundary — the backward
    # replay skips the whole attention forward (kernel + projections +
    # RoPE). ~97 MB/layer of residuals; measured +10% step throughput
    # over full remat on v5e.
    "bench": LlamaConfig(
        vocab_size=32768, d_model=1024, n_layers=24, n_heads=8, n_kv_heads=4,
        d_ff=4096, max_seq=2048, remat="flash_qkv",
    ),
    # Llama-3-8B (BASELINE.json config 3).
    "llama3_8b": LlamaConfig(),
}


def param_logical_axes(cfg: LlamaConfig) -> Params:
    """Pytree of logical-axis tuples, mirroring init_params' structure."""
    axes = {
        "tok_emb": ("vocab", "embed"),
        "blocks": {
            "attn_norm": ("layers", "embed"),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            "mlp_norm": ("layers", "embed"),
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        },
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }
    if cfg.qk_norm:
        axes["blocks"]["q_norm"] = ("layers", "heads")
        axes["blocks"]["k_norm"] = ("layers", "kv_heads")
    return axes


def init_params(key: jax.Array, cfg: LlamaConfig) -> Params:
    """Initialize fp32 parameters (truncated-normal, 1/sqrt(fan_in)).

    The trainer keeps them fp32 and `forward` casts at use. For serving,
    `LLMEngine` casts the matmul weights to cfg.dtype once and holds
    that tree (`llm/paged_kv.py matmul_weights`)."""
    d, f = cfg.d_model, cfg.d_ff
    hq = cfg.n_heads * cfg.head_dim
    hkv = cfg.n_kv_heads * cfg.head_dim
    L = cfg.n_layers
    keys = jax.random.split(key, 9)

    def w(k, shape, fan_in):
        return (
            jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32)
            * fan_in**-0.5
        )

    params = {
        "tok_emb": w(keys[0], (cfg.vocab_size, d), d),
        "blocks": {
            "attn_norm": jnp.zeros((L, d), jnp.float32),
            "wq": w(keys[1], (L, d, hq), d),
            "wk": w(keys[2], (L, d, hkv), d),
            "wv": w(keys[3], (L, d, hkv), d),
            "wo": w(keys[4], (L, hq, d), hq),
            "mlp_norm": jnp.zeros((L, d), jnp.float32),
            "w_gate": w(keys[5], (L, d, f), d),
            "w_up": w(keys[6], (L, d, f), d),
            "w_down": w(keys[7], (L, f, d), f),
        },
        "final_norm": jnp.zeros((d,), jnp.float32),
        "lm_head": w(keys[8], (d, cfg.vocab_size), d),
    }
    if cfg.qk_norm:
        params["blocks"]["q_norm"] = jnp.zeros((L, hq), jnp.float32)
        params["blocks"]["k_norm"] = jnp.zeros((L, hkv), jnp.float32)
    return params


def _embed(table: jnp.ndarray, tokens: jnp.ndarray, cfg: LlamaConfig):
    """Token embedding lookup. Under a sharded mesh the lookup runs as a
    one-hot matmul: a gather from a (vocab=tp, embed=fsdp)-sharded table
    forces the SPMD partitioner into an involuntary full
    rematerialization (replicate-then-reshard) on the activation, while
    the matmul contraction partitions natively (and rides the MXU). On a
    single chip the plain gather is cheaper."""
    table = table.astype(cfg.dtype)
    impl = cfg.embed_impl
    if impl == "auto":
        impl = "onehot" if jax.device_count() > 1 else "gather"
    if impl == "gather":
        return table[tokens]
    if impl != "onehot":
        raise ValueError(f"unknown embed_impl {cfg.embed_impl!r}")
    onehot = jax.nn.one_hot(tokens, table.shape[0], dtype=table.dtype)
    return onehot @ table


AttnFn = Callable[..., jnp.ndarray]


# (h, layer_params, cfg) -> (out, aux). ``aux`` is the layer's side
# output, any pytree of arrays: 0.0 for the dense FFN, the router's
# losses, loads and routes for MoE. The scan stacks it over layers.
FfnFn = Callable[..., tuple[jnp.ndarray, Any]]


def _dense_ffn(h: jnp.ndarray, p: Params, cfg: LlamaConfig):
    dt = cfg.dtype
    gate = jax.nn.silu(h @ p["w_gate"].astype(dt))
    up = h @ p["w_up"].astype(dt)
    return (gate * up) @ p["w_down"].astype(dt), jnp.float32(0.0)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _int8_ckpt(x: jnp.ndarray, name: str) -> jnp.ndarray:
    """Quantize-through-checkpoint: the value crossing the remat
    boundary is int8 + a per-row fp32 scale (tagged for
    save_only_these_names), halving the residual HBM of a saved bf16
    activation. A custom_vjp (straight-through cotangent) rather than
    the x + stop_gradient(dq - x) identity trick: that formulation
    keeps the UN-quantized x structurally live in the primal output,
    so the backward replay would re-run the producing matmul anyway —
    the primal here depends only on (q, scale), which the policy
    saves."""
    scale = (
        jnp.max(jnp.abs(x), axis=-1, keepdims=True).astype(jnp.float32)
        / 127.0
        + 1e-12
    )
    q = jnp.clip(
        jnp.round(x.astype(jnp.float32) / scale), -127, 127
    ).astype(jnp.int8)
    q = checkpoint_name(q, name)
    scale = checkpoint_name(scale, name + "_scale")
    return (q.astype(jnp.float32) * scale).astype(x.dtype)


def _int8_ckpt_fwd(x, name):
    return _int8_ckpt(x, name), ()


def _int8_ckpt_bwd(name, _res, g):
    return (g,)  # straight-through: quantization grad is identity


_int8_ckpt.defvjp(_int8_ckpt_fwd, _int8_ckpt_bwd)


def _dense_ffn_save(h: jnp.ndarray, p: Params, cfg: LlamaConfig):
    """FFN with bf16-tagged gate-pre/up activations (the unquantized
    sibling of :func:`_dense_ffn_q8`)."""
    dt = cfg.dtype
    gate_pre = checkpoint_name(h @ p["w_gate"].astype(dt), "ffn_gate")
    up = checkpoint_name(h @ p["w_up"].astype(dt), "ffn_up")
    return (jax.nn.silu(gate_pre) * up) @ p["w_down"].astype(dt), (
        jnp.float32(0.0)
    )


def _dense_ffn_q8(h: jnp.ndarray, p: Params, cfg: LlamaConfig):
    """FFN whose gate-pre/up activations cross the remat boundary as
    int8: with their names pinned by the checkpoint policy, the
    backward replay skips BOTH [B,S,d]x[d,ff] forward matmuls
    (the 'int8 saved FFN activations' lever)."""
    dt = cfg.dtype
    gate_pre = _int8_ckpt(h @ p["w_gate"].astype(dt), "ffn_gate")
    up = _int8_ckpt(h @ p["w_up"].astype(dt), "ffn_up")
    return (jax.nn.silu(gate_pre) * up) @ p["w_down"].astype(dt), (
        jnp.float32(0.0)
    )


def _block(
    x: jnp.ndarray,
    p: Params,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    cfg: LlamaConfig,
    attn_fn: AttnFn,
    ffn_fn: FfnFn,
) -> tuple[jnp.ndarray, Any]:
    """Pre-norm attention + FFN sublayers; ffn_fn returns (out, aux) so
    MoE layers (ray_tpu.models.moe) reuse this block unchanged. The
    signature is a scan body's: (carry, layer_params) -> (carry, aux)."""
    b, s, d = x.shape
    dt = cfg.dtype

    x = constrain(x, "batch", "act_seq", "act_embed")
    h = rms_norm(x, p["attn_norm"])
    q = h @ p["wq"].astype(dt)
    k = h @ p["wk"].astype(dt)
    if cfg.qk_norm:
        q, k = rms_norm(q, p["q_norm"]), rms_norm(k, p["k_norm"])
    q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = (h @ p["wv"].astype(dt)).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    attn = checkpoint_name(attn_fn(q, k, v), "attn_out")
    x = x + attn.reshape(b, s, -1) @ p["wo"].astype(dt)

    h = rms_norm(x, p["mlp_norm"])
    ffn_out, aux = ffn_fn(h, p, cfg)
    return x + ffn_out, aux


def forward_with_aux(
    params: Params,
    tokens: jnp.ndarray,
    cfg: LlamaConfig,
    attn_fn: AttnFn | None = None,
    ffn_fn: FfnFn | None = None,
    return_hidden: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """tokens [B, S] int32 → (logits [B, S, V] fp32, the FFN hook's aux
    stacked over layers: float32[L] zeros for the dense FFN).

    With ``return_hidden`` the final-norm hidden states [B, S, d] come
    back instead of logits — the chunked-CE loss projects them to the
    vocabulary a slice at a time so the full [B, S, V] logits (and their
    gradient) never materialize.
    """
    attn_fn = attn_fn or causal_attention
    ffn_fn = ffn_fn or _dense_ffn
    seq = tokens.shape[1]
    cos, sin = rope_frequencies(cfg.head_dim, seq, cfg.rope_theta)

    x = _embed(params["tok_emb"], tokens, cfg)
    x = constrain(x, "batch", "act_seq", "act_embed")

    body = partial(_block, cos=cos, sin=sin, cfg=cfg, attn_fn=attn_fn,
                   ffn_fn=ffn_fn)
    if cfg.remat == "full":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable
        )
    elif cfg.remat == "attn":
        # Save ONLY the attention outputs: the backward pass skips the
        # flash-kernel forward recompute (the most expensive part of the
        # layer to re-run) at a cost of one [B, S, H, D] bf16 residual
        # per layer — the standard selective-remat sweet spot for long
        # sequences.
        body = jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.save_only_these_names(
                "attn_out"
            ),
        )
    elif cfg.remat == "flash":
        # Save the flash kernel's OWN residuals (its output + per-row
        # logsumexp, tagged inside the kernel's custom-vjp fwd): the
        # backward replay then rebuilds only norms/projections/FFN and
        # never re-runs the forward attention kernel — the expensive,
        # O(S^2) part of the recompute. Costs ~one [B,S,H,D] bf16 + one
        # [B,H,S] fp32 residual per layer; everything else stays fully
        # rematerialized.
        body = jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.save_only_these_names(
                "flash_out", "flash_lse"
            ),
        )
    elif cfg.remat == "flash_qkv":
        # "flash" plus the attention INPUTS: the replay also skips the
        # qkv projections + RoPE. ~2x the residual memory of "flash".
        body = jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.save_only_these_names(
                "flash_out", "flash_lse", "flash_qkv"
            ),
        )
    elif cfg.remat == "flash_qkv_ffn":
        # bf16-saved FFN activations (no quantization): same skipped
        # recompute as ffn8 at 2x the residual memory — OOM-bound at
        # bench scale (PROFILE_r03), kept for smaller models.
        if ffn_fn is _dense_ffn:
            ffn_fn = _dense_ffn_save
            body = partial(
                _block, cos=cos, sin=sin, cfg=cfg, attn_fn=attn_fn,
                ffn_fn=ffn_fn,
            )
        body = jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.save_only_these_names(
                "flash_out", "flash_lse", "flash_qkv",
                "ffn_gate", "ffn_up",
            ),
        )
    elif cfg.remat == "flash_qkv_ffn8":
        # "flash_qkv" plus int8-saved FFN activations: the replay skips
        # the two FFN up-projection matmuls too, from residuals stored
        # at half the bf16 footprint (gate over loss parity).
        if ffn_fn is _dense_ffn:
            ffn_fn = _dense_ffn_q8
            body = partial(
                _block, cos=cos, sin=sin, cfg=cfg, attn_fn=attn_fn,
                ffn_fn=ffn_fn,
            )
        body = jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.save_only_these_names(
                "flash_out", "flash_lse", "flash_qkv",
                "ffn_gate", "ffn_gate_scale", "ffn_up", "ffn_up_scale",
            ),
        )
    elif cfg.remat == "dots":
        body = jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        )

    x, aux = jax.lax.scan(body, x, params["blocks"])

    x = rms_norm(x, params["final_norm"])
    if return_hidden:
        return x, aux
    logits = (x @ params["lm_head"].astype(cfg.dtype)).astype(jnp.float32)
    return logits, aux


def forward(
    params: Params,
    tokens: jnp.ndarray,
    cfg: LlamaConfig,
    attn_fn: AttnFn | None = None,
) -> jnp.ndarray:
    """tokens [B, S] int32 → logits [B, S, V] fp32."""
    logits, _ = forward_with_aux(params, tokens, cfg, attn_fn=attn_fn)
    return logits
