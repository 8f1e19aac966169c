"""Nemotron-H: a decoder whose blocks differ in kind.

Every block is ONE mixer behind one RMSNorm and a residual,
``x <- x + mixer(norm(x))``, and the kind of each block is a letter of
``NemotronHConfig.pattern``: ``M`` a Mamba-2 state-space mixer (Dao &
Gu 2024, arXiv:2405.21060), ``E`` sparse experts (``models/moe.py``'s
``moe_ffn``: sigmoid scores, relu^2 experts without a gate matrix, one
shared expert), ``*`` grouped-query attention without a rotary
embedding. The sizes are those of NVIDIA-Nemotron-3-Nano-30B-A3B-BF16
(``model_type: nemotron_h``), the public model the benchmark serves
through this file.

Here: the configuration, an initialiser that makes the tree in the
dtypes it is held in, and the Mamba-2 mixer in its two forms (many
tokens, chunked: the state-space dual form, `_dual_form` in XLA's
operations and on a TPU ``ops/pallas/ssd_chunk.py``'s one call; one
token). The expert mixer is ``moe_ffn``; the
attention mixer is the serving programs' own (``llm/paged_kv.py``),
which ``llm/hybrid_kv.py`` puts together with these over a cache of
pages and per-slot state. Blocks are a tuple of per-block trees, run by
a Python loop over the pattern: no stack is sliced, and no scan needs
the blocks alike.

A norm's weight is stored as ``scale`` and applied as ``1 + scale``
(``ops/norms.py``), as everywhere in the repo.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, ClassVar

import jax
import jax.numpy as jnp

from ray_tpu._private import chip
from ray_tpu.ops.pallas.ssd_chunk import ssd_chunk_rule
from ray_tpu.ops.pallas.state_step import mamba_state_step

Params = dict[str, Any]

PATTERN_30B = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072  # rows held, where the vocabulary is sliced
    d_model: int = 2688
    pattern: str = PATTERN_30B
    # attention blocks
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    # Mamba-2 blocks: d_inner = mamba_heads * mamba_head_dim
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    ssm_groups: int = 8
    ssm_state: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # expert blocks (the names `moe_ffn` reads)
    num_experts: int = 128
    top_k: int = 6
    d_ff: int = 1856
    shared_d_ff: int = 3712
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    experts_held: tuple | None = None  # (first, count); None = all
    router_kind: str = "sigmoid"
    expert_kind: str = "relu2"
    # The routed experts' stacks are HELD with the expert width padded
    # with zeros to a multiple of this (1856 -> 1920): columns that give
    # relu(0)^2 = 0 and rows of W_down that meet them, so the model is
    # the same. Why: a TPU gives bf16[64, 2688, 1856] a layout with the
    # 2688 minor (1856 is 14.5 tiles of 128 lanes), the grouped-matmul
    # kernel takes its weights with the last dimension minor, and XLA
    # then copies the 0.64 GB stack in front of every grouped matmul,
    # in every program (seen in the decode program compiled for a v5e,
    # PR 31). With the last dimension a multiple of 128 the two layouts
    # are one. The tiled layout the kernel wants pads to 1920 in memory
    # anyway.
    expert_lanes: int = 128
    # `moe_ffn` applies every held expert to every row up to this many
    # rows, and sorts pairs into grouped matmuls above it. Measured on a
    # v5e at these widths, 64 experts held, one expert block (my chip
    # run, PR 31): the grouped matmul (`jax.lax.ragged_dot`, the
    # compiler's kernel) takes 10.0 / 13.1 / 14.1 / 15.5 / 15.9 / 17.2 ms
    # at 32 / 64 / 128 / 256 / 512 / 1,024 rows, with groups of a row or
    # two at the decode step's 32; every expert on every row takes 2.0 /
    # 1.9 / 2.0 / 2.3 / 4.4 / 10.2 ms: up to ~240 rows (the chip's ridge
    # for bf16 weights) it is the one read of the 1.3 GB of weights that
    # both forms need, above that 21 times the arithmetic, and still
    # less than the grouped kernel takes. 512 is the engine's chunk.
    # Since PR 34 the every-row form is one kernel on a TPU
    # (`ops/pallas/expert_rows.py`) that reads only the experts that got
    # a row: with 48 / 64 of 64 touched it takes 1.35 / 1.65-1.82 ms at 32
    # to 256 rows and 2.67 / 3.54 at 512 (my chip run, PR 34), under the
    # batched matmul at every row count, so the boundary stays.
    dense_expert_rows: int = 512
    max_seq: int = 262144
    dtype: Any = jnp.bfloat16
    # What a family whose layers are two of these sublayers multiplies
    # in (`models/granite_hybrid.py`): the embedding, each sublayer's
    # output before the residual add, the attention scores (None:
    # head_dim**-0.5) and a divisor of the logits; and whether the head
    # is the embedding's transpose. Python numbers: the serving programs
    # skip each at these values, so this family's programs hold none.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_scale: float | None = None
    logits_scaling: float = 1.0
    tie_word_embeddings: bool = False
    # What a family whose attention is gated and rotated adds to the
    # attention block (`models/qwen3_next.py`), off here and in Granite,
    # whose programs hold none of it: an RMSNorm over each head of q and
    # k, a rotary embedding on the first `rotary_dim` dimensions of each
    # head (0: none), and a `wq` twice as wide whose second half, head by
    # head, gates the heads' output through a sigmoid. `norm_eps` is every
    # RMSNorm's.
    qk_norm: bool = False
    rotary_dim: int = 0
    rope_theta: float = 10000.0
    attn_output_gate: bool = False
    norm_eps: float = 1e-5
    # What a family with two kinds of attention layer adds to the block
    # (`models/laguna.py`), off here and in the families above: a gate of
    # ONE number a head from its own `wg` [d, H], and YaRN's scaling of
    # the rotary embedding, (factor, original length, beta_fast,
    # beta_slow, attention_factor). Its second kind of attention block
    # and its dense FFN are letters of its own pattern, whose fields are
    # its own.
    head_gate: bool = False
    rope_yarn: tuple | None = None
    # What a family with clamped experts and more than one residual
    # stream adds (`models/glm5_next.py`), off here and in the families
    # above, whose programs hold none of it: `moe.clamped_swiglu`'s limit
    # on a gated FFN's two products, and how many residual streams the
    # programs carry (0: the one stream ``x + out``; n: `mhc_mix` reads a
    # sublayer's input from n streams and `mhc_spread` writes it back).
    swiglu_limit: float | None = None
    hc_mult: int = 0
    # A bound every sublayer's output is held within before it joins
    # the residual path (`models/motif.py`; None, here and in the
    # families above: nothing of it in a program).
    hidden_clamp: float | None = None
    # What a family of LayerNorms and paired attention heads adds
    # (`models/phi4_flash.py`), off here and in the families above, whose
    # programs hold none of it: every norm a LayerNorm with a bias, and
    # attention whose heads pair up and subtract one map from the other.
    layer_norm: bool = False
    differential: bool = False
    # Window rings held as pages of the pool's page size in the pool's
    # cell layout, written and attended by the pool's kernels
    # (`llm/hybrid_kv.py`): for a family whose KV heads do not fill a
    # tile's rows.
    ring_pages: bool = False
    # Identity outputs behind the router's experts (`moe.MoEConfig`):
    # none in any family served through this class.
    zero_experts: int = 0

    # The letters `pattern` may hold: a family with another recurrence
    # adds its own (`models/qwen3_next.py`: G).
    block_kinds: ClassVar[str] = "ME*"

    def __post_init__(self):
        if set(self.pattern) - set(self.block_kinds):
            raise ValueError(
                f"pattern {self.pattern!r}: blocks are of "
                f"{' '.join(self.block_kinds)} (a dense MLP block, '-', is "
                "not written)"
            )
        if self.mamba_heads % self.ssm_groups:
            raise ValueError("ssm_groups does not divide mamba_heads")

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def d_ff_held(self) -> int:
        return -(-self.d_ff // self.expert_lanes) * self.expert_lanes

    @property
    def n_experts_held(self) -> int:
        return self.experts_held[1] if self.experts_held else self.num_experts

    def count(self, kind: str) -> int:
        return self.pattern.count(kind)

    @property
    def cross_from(self) -> int | None:
        """The first sublayer of a cross-decoder, whose blocks write
        nothing to the cache (`models/phi4_flash.py`); None: no family
        above has one."""
        return None

    def serving(self):
        """What `LLMEngine` serves this model through: its cache and its
        three programs."""
        from ray_tpu.llm.hybrid_kv import HybridServing

        return HybridServing(self)


NEMOTRON_H_PRESETS: dict[str, NemotronHConfig] = {
    # CPU-test scale, all three kinds of block, the published switches.
    "nemotron_h_tiny": NemotronHConfig(
        vocab_size=256, d_model=64, pattern="ME*EM", n_heads=4,
        n_kv_heads=2, head_dim=16, mamba_heads=8, mamba_head_dim=8,
        ssm_groups=2, ssm_state=16, chunk_size=8, num_experts=8, top_k=3,
        d_ff=32, shared_d_ff=48, max_seq=256, dtype=jnp.float32,
    ),
}


# ------------------------------------------------------------ parameters
def _normal(key, shape, fan_in, dtype):
    return (
        jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
        * fan_in**-0.5
    ).astype(dtype)


@partial(jax.jit, static_argnames=("kind", "cfg"))
def _init_block(key, kind: str, cfg: NemotronHConfig) -> Params:
    """One block's tree, each leaf made and rounded inside this program:
    the float32 draw of an expert stack never outlives it."""
    d, dt = cfg.d_model, cfg.dtype
    keys = jax.random.split(key, 6)
    zeros = jnp.zeros((d,), jnp.float32)
    if kind == "M":
        h, k = cfg.mamba_heads, cfg.conv_kernel
        # `dt` log-uniform in [time_step_min, time_step_max], floored,
        # stored through softplus's inverse; A in [1, 16]; D = 1
        # (Mamba-2's own initialisation; assumed, the config gives the
        # three time-step numbers only).
        step = jnp.exp(
            jax.random.uniform(keys[2], (h,))
            * (math.log(cfg.time_step_max) - math.log(cfg.time_step_min))
            + math.log(cfg.time_step_min)
        )
        step = jnp.maximum(step, cfg.time_step_floor)
        bound = k**-0.5  # a depthwise conv's fan-in is its kernel
        return {
            "norm": zeros,
            "in_proj": _normal(
                keys[0], (d, cfg.d_inner + cfg.conv_dim + h), d, dt
            ),
            "conv_w": jax.random.uniform(
                keys[1], (k, cfg.conv_dim), jnp.float32, -bound, bound
            ),
            "conv_b": jax.random.uniform(
                keys[3], (cfg.conv_dim,), jnp.float32, -bound, bound
            ),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "A_log": jnp.log(jax.random.uniform(keys[4], (h,), minval=1.0,
                                                maxval=16.0)),
            "D": jnp.ones((h,), jnp.float32),
            "gate_norm": jnp.zeros((cfg.d_inner,), jnp.float32),
            "out_proj": _normal(keys[5], (cfg.d_inner, d), cfg.d_inner, dt),
        }
    if kind == "E":
        held, f, fs = cfg.n_experts_held, cfg.d_ff, cfg.shared_d_ff
        pad = cfg.d_ff_held - f
        return {
            "norm": zeros,
            # The router stays as wide as the model's experts, in float32.
            "router": _normal(keys[0], (d, cfg.num_experts), d, jnp.float32),
            # `e_score_correction_bias`: zero, as a fresh model's.
            "router_bias": jnp.zeros((cfg.num_experts,), jnp.float32),
            "w_up": jnp.pad(
                _normal(keys[1], (held, d, f), d, dt), ((0, 0), (0, 0), (0, pad))
            ),
            "w_down": jnp.pad(
                _normal(keys[2], (held, f, d), f, dt), ((0, 0), (0, pad), (0, 0))
            ),
            "shared_up": _normal(keys[3], (d, fs), d, dt),
            "shared_down": _normal(keys[4], (fs, d), fs, dt),
        }
    hq, hkv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    return {
        "attn_norm": zeros,
        "wq": _normal(keys[0], (d, hq), d, dt),
        "wk": _normal(keys[1], (d, hkv), d, dt),
        "wv": _normal(keys[2], (d, hkv), d, dt),
        "wo": _normal(keys[3], (hq, d), hq, dt),
    }


@partial(jax.jit, static_argnames="cfg")
def _init_ends(key, cfg: NemotronHConfig) -> Params:
    k_emb, k_head = jax.random.split(key)
    v, d = cfg.vocab_size, cfg.d_model
    return {
        "tok_emb": (
            jax.random.normal(k_emb, (v, d), jnp.float32) * 0.02
        ).astype(cfg.dtype),
        "final_norm": jnp.zeros((d,), jnp.float32),
        "lm_head": _normal(k_head, (d, v), d, cfg.dtype),
    }


def init_params(key: jax.Array, cfg: NemotronHConfig) -> Params:
    """The tree as it is held: matmul weights in ``cfg.dtype``, the
    router, norms, convolution and the per-head SSM parameters in
    float32. One program per block, so that no more than one block's
    float32 draws exist at a time (at the published widths all expert
    stacks in float32 are 21 GB)."""
    params = _init_ends(jax.random.fold_in(key, len(cfg.pattern)), cfg=cfg)
    params["blocks"] = tuple(
        _init_block(jax.random.fold_in(key, i), kind=kind, cfg=cfg)
        for i, kind in enumerate(cfg.pattern)
    )
    return params


# ---------------------------------------------------------------- Mamba-2
def _project_in(u, p, cfg):
    """``[z | xBC | dt] = u W_in``: widths d_inner | conv_dim | heads."""
    with jax.named_scope("ssm:in_proj"):
        zxbcdt = u @ p["in_proj"]
        return jnp.split(
            zxbcdt, [cfg.d_inner, cfg.d_inner + cfg.conv_dim], axis=-1
        )


def _split_xbc(xbc, cfg):
    """silu'd convolution output -> x [.., H, P], B and C [.., G, N]."""
    g, n = cfg.ssm_groups, cfg.ssm_state
    x, b, c = jnp.split(xbc, [cfg.d_inner, cfg.d_inner + g * n], axis=-1)
    lead = xbc.shape[:-1]
    return (
        x.reshape(*lead, cfg.mamba_heads, cfg.mamba_head_dim),
        b.reshape(*lead, g, n),
        c.reshape(*lead, g, n),
    )


def _steps(dt_raw, p):
    """``dt = softplus(dt + dt_bias)`` and ``A = -exp(A_log)``, float32."""
    return (
        jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"]),
        -jnp.exp(p["A_log"]),
    )


def _project_out(y, z, p, cfg):
    """Gated group norm, then ``W_out``: ``RMSNorm_groups(y * silu(z))``
    over ``ssm_groups`` groups of d_inner / ssm_groups, times the norm's
    weight. y, z: [.., d_inner]."""
    with jax.named_scope("ssm:out"):
        lead = y.shape[:-1]
        gated = y * jax.nn.silu(z.astype(jnp.float32))
        grouped = gated.reshape(*lead, cfg.ssm_groups, -1)
        var = jnp.mean(grouped * grouped, axis=-1, keepdims=True)
        normed = (grouped * jax.lax.rsqrt(var + 1e-5)).reshape(*lead, -1)
        normed = normed * (1.0 + p["gate_norm"])
        return normed.astype(cfg.dtype) @ p["out_proj"]


# `mamba_chunked` scans by `ops/pallas/ssd_chunk.py` from this many
# tokens a program on, and by XLA's form under it. One mixer alone on a
# v5e (scripts/ssd_chunk_layer.py, my chip runs, PR 67), ms XLA's form /
# the call: at Granite's 2,048 tokens 4.76 / 3.10; at Nemotron-3-Nano's
# widths and 512 tokens 0.367 / 0.380 (the rule alone 0.142 / 0.114, but
# the convolution's result is then written whole for the call to read,
# where XLA fuses it into the form's first passes), at 64 tokens 0.078 /
# 0.076; and each shape of the call adds tracing and lowering to a
# program's set-up (`trace_lower_s` 9.8 -> 13.5 s in `nemotron-reason-32`
# with the call in its eight programs, of a `setup_s` of 57). A cell
# stands on either side: `granite-longdoc-16` prefills 2,048 tokens a
# program, `nemotron-reason-32` 64 to 512.
_SCAN_KERNEL_TOKENS = 1024


def _dual_form(x, b_in, c_in, dt, a, d, ssm0, size):
    """The state-space dual form in XLA's own operations, over chunks of
    ``size`` tokens: x [T, H, P], b_in and c_in [T, G, N], dt [T, H] (0
    where a token takes no step), a and d [H], ssm0 [H, P, N]. Returns (y
    [T, H x P] with the skip ``D x`` in it, the state after the last
    token that takes a step)."""
    t, h = dt.shape
    g = b_in.shape[1]
    r = h // g  # heads of a group share its B and C
    c = t // size
    # Chunked, head-major views, heads as (group g, head of group
    # r): time and the head's own dims are the minor ones.
    xdt = (x * dt[..., None]).reshape(c, size, g, r, -1)
    xdt = xdt.transpose(0, 2, 3, 1, 4)  # [c, g, r, l, P]
    b_c = b_in.reshape(c, size, g, -1).transpose(0, 2, 1, 3)  # [c, g, l, N]
    c_c = c_in.reshape(c, size, g, -1).transpose(0, 2, 1, 3)
    cum = jnp.cumsum(
        (dt * a).reshape(c, size, g, r).transpose(0, 2, 3, 1), axis=-1
    )  # [c, g, r, l]
    # Within a chunk: y_l += sum_{s <= l} (C_l . B_s) decay(s -> l)
    # dt_s x_s.
    diff = cum[..., :, None] - cum[..., None, :]  # [c, g, r, l, s]
    causal = jnp.tril(jnp.ones((size, size), bool))
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    cb = jnp.einsum("cgln,cgsn->cgls", c_c, b_c)
    y = jnp.einsum("cgrls,cgrsp->cgrlp", cb[:, :, None] * decay, xdt)
    # What each chunk adds to the state by its end.
    to_end = jnp.exp(cum[..., -1:] - cum)  # [c, g, r, l]
    added = jnp.einsum(
        "cgrlp,cgln->cgrpn", xdt * to_end[..., None], b_c
    )
    # Between chunks: the state each chunk starts from.
    whole = jnp.exp(cum[..., -1])  # [c, g, r]

    def carry(state, chunk):
        decay_c, added_c = chunk
        return state * decay_c[..., None, None] + added_c, state

    start = ssm0.reshape(g, r, *ssm0.shape[1:])
    end, starts = jax.lax.scan(carry, start, (whole, added))
    y = y + jnp.einsum("cgln,cgrpn->cgrlp", c_c, starts) * jnp.exp(
        cum
    )[..., None]
    y = y.transpose(0, 3, 1, 2, 4)  # [c, l, g, r, P]
    y = y.reshape(t, h, -1) + d[:, None] * x
    return y.reshape(t, -1), end.reshape(ssm0.shape)


def mamba_chunked(u, p, cfg: NemotronHConfig, ssm0, conv0, length):
    """The Mamba-2 mixer over many tokens of one sequence (``cfg``: this
    family's config or one with its Mamba fields, as
    ``models/granite_hybrid.py``'s).

    u [T, d] (normed input); ssm0 [H, P, N] float32 and conv0
    [K - 1, conv_dim] the state before u[0]; ``length`` (traced) how many
    of the T tokens are real. Returns (out [T, d], the SSM state and the
    convolution tail after token ``length - 1``). Positions from
    ``length`` on take no step (``dt`` 0 there), so they leave the state
    as it is; their own outputs mean nothing.

    Within a chunk of ``chunk_size`` tokens the recurrence is matrix
    products (the state-space dual form: arXiv:2405.21060 section 6,
    ``ssd_minimal``); between chunks it is the state. Decays, cumulative
    sums and the state are float32; the products take their operands as
    the matmul unit does (bf16 on a TPU), as the published kernels do.

    On a TPU the dual form, between the convolution's output and
    ``_project_out``, is one call of ``ops/pallas/ssd_chunk.py`` (the
    same arithmetic with nothing of shape ``[.., Q, Q]`` in HBM and no
    head-major copy of ``x dt`` or ``y``; PR 67; its docstring has the
    layer-alone table); elsewhere `_dual_form`, XLA's form, which is
    tier 1's path and the kernel's oracle
    (tests/test_ssd_chunk_kernel.py), and a TPU's too for programs
    shorter than `_SCAN_KERNEL_TOKENS`. The platform and the program's
    length decide: no flag, as for ``moe_ffn``'s kernels and the two
    delta rules.
    """
    t = u.shape[0]
    size = min(cfg.chunk_size, t)
    if t % size:
        raise ValueError(f"{t} tokens do not divide into chunks of {size}")
    z, xbc, dt_raw = _project_in(u, p, cfg)

    with jax.named_scope("ssm:conv"):
        k = cfg.conv_kernel
        seq = jnp.concatenate([conv0.astype(xbc.dtype), xbc], axis=0)
        conv = p["conv_b"] + sum(
            seq[j: j + t].astype(jnp.float32) * p["conv_w"][j]
            for j in range(k)
        )
        # Row i of `seq` is the input at position i - (K - 1).
        conv_end = jax.lax.dynamic_slice_in_dim(seq, length, k - 1, axis=0)
        xbc = jax.nn.silu(conv)
        x, b_in, c_in = _split_xbc(xbc, cfg)

    with jax.named_scope("ssm:scan"):
        dt, a = _steps(dt_raw, p)
        dt = jnp.where(jnp.arange(t)[:, None] < length, dt, 0.0)  # [T, H]
        # Chosen by the platform, as `moe_ffn` chooses its kernels, and
        # by the program's length.
        if chip.platform() == "tpu" and t >= _SCAN_KERNEL_TOKENS:
            y, end = ssd_chunk_rule(
                xbc, dt, a, p["D"], ssm0, length, groups=cfg.ssm_groups,
                chunk=size,
            )
        else:
            y, end = _dual_form(x, b_in, c_in, dt, a, p["D"], ssm0, size)
    out = _project_out(y, z, p, cfg)
    return out, end, conv_end.astype(conv0.dtype)


def _step_operands(u, p, cfg, conv):
    """What one token's state update takes, of u [B, d] and the tail
    conv [B, K - 1, conv_dim]: z, x [B, H, P], B and C [B, G, N], the
    window [B, K, conv_dim] whose last K - 1 rows are the next tail."""
    z, xbc, dt_raw = _project_in(u, p, cfg)
    with jax.named_scope("ssm:conv"):
        window = jnp.concatenate(
            [conv, xbc[:, None].astype(conv.dtype)], axis=1
        )  # [B, K, conv_dim]
        out = p["conv_b"] + (
            window.astype(jnp.float32) * p["conv_w"][None]
        ).sum(1)
        x, b_in, c_in = _split_xbc(jax.nn.silu(out), cfg)
    return z, dt_raw, x, b_in, c_in, window


def mamba_step(u, p, cfg: NemotronHConfig, ssm, conv):
    """The mixer for ONE token of each of B sequences: u [B, d], ssm
    [B, H, P, N] float32, conv [B, K - 1, conv_dim]. Returns (out
    [B, d], ssm, conv) after the token. The state is read, updated and
    read out in float32 elementwise arithmetic: no product rounds it."""
    bsz = u.shape[0]
    h, g = cfg.mamba_heads, cfg.ssm_groups
    r = h // g
    z, dt_raw, x, b_in, c_in, window = _step_operands(u, p, cfg, conv)

    with jax.named_scope("ssm:update"):
        dt, a = _steps(dt_raw, p)  # [B, H], [H]
        state = ssm.reshape(bsz, g, r, *ssm.shape[2:])  # [B, g, r, P, N]
        keep = jnp.exp(dt * a).reshape(bsz, g, r, 1, 1)
        xdt = (x * dt[..., None]).reshape(bsz, g, r, -1, 1)
        state = state * keep + xdt * b_in[:, :, None, None, :]
        y = (state * c_in[:, :, None, None, :]).sum(-1)  # [B, g, r, P]
        y = y.reshape(bsz, h, -1) + p["D"][:, None] * x
    out = _project_out(y.reshape(bsz, -1), z, p, cfg)
    return out, state.reshape(ssm.shape), window[:, 1:]


def mamba_step_live(u, p, cfg: NemotronHConfig, stack, layer, conv, order,
                    count):
    """`mamba_step` on a TPU, for the slots that decode: ``stack`` [L, B,
    H, P, N] is every layer's state, of which ``stack[layer]`` is
    stepped IN PLACE for the first ``count`` slots of ``order``
    (``ops/pallas/state_step.py live_order``) and no other slot's state
    is read or written: the masked write-back is the kernel's. Returns
    (out [B, d], the stack, conv after the token); a slot that does not
    decode gets the ``out`` of ``y = D x`` (finite, and dropped)."""
    z, dt_raw, x, b_in, c_in, window = _step_operands(u, p, cfg, conv)
    with jax.named_scope("ssm:update"):
        dt, a = _steps(dt_raw, p)  # [B, H], [H]
        stack, y = mamba_state_step(
            stack, layer, order, count, jnp.exp(dt * a), x * dt[..., None],
            b_in, c_in,
        )
        y = y + p["D"][:, None] * x
    out = _project_out(y.reshape(u.shape[0], -1), z, p, cfg)
    return out, stack, window[:, 1:]
