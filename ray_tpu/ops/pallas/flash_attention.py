"""Flash attention as Pallas TPU kernels — forward AND backward.

Online-softmax attention (Dao et al.) tiled for the MXU: the forward
never materializes the [S, S] score matrix — each (q-block, kv-block)
grid step rescales a running (max, denom, acc) triple held in VMEM
scratch, which persists across the innermost (sequential) grid dimension
on TPU. Causal blocks strictly above the diagonal are skipped entirely,
halving the work. The forward also emits the per-row logsumexp so the
backward can recompute probabilities blockwise (flash-2 style): dq
accumulates over kv blocks, dk/dv over q blocks, all O(S) memory.

The reference has no attention kernels at all (SURVEY.md §5 long-context
row: delegated to vLLM/user code); this is native.

Layout: [B, S, H, D] (the model's convention). GQA in the forward is
handled by index mapping (q head h reads kv head h // n_rep — no
materialized repeat); the backward expands kv to H heads and sums
dk/dv over each group's n_rep q heads afterwards.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG_INF = float("-inf")
# Finite mask value: exp(_MASK - m) underflows to exactly 0 for any
# finite row max m, so masked positions need NO NaN-guard `where` passes
# (with -inf they would: exp(-inf - -inf) = NaN). Kept well inside fp32
# range so (s - m) cannot overflow.
_MASK = -1e9
# Running-max initializer: any real score beats it, and exp(_M_INIT - m)
# underflows to 0 (the first block's rescale factor) without -inf NaNs.
_M_INIT = -1e30
_LANES = 128  # TPU vector lane count: scratch stats are lane-replicated
# Budget for the backward's whole-head dq VMEM slab (S·d·4 bytes); past
# this the kernel switches to HBM fp32 partials (see _bwd_kernel).
_DQ_SLAB_VMEM_BYTES = 4 * 1024 * 1024


# --------------------------------------------------------------- forward
def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
    *, block_q: int, block_kv: int, num_kv: int, causal: bool,
):
    """q is PRE-SCALED by the caller (one cheap [S, D] pass instead of a
    per-block [block_q, block_kv] multiply). Elementwise work is the VPU
    bottleneck at D=64, so the softmax path is kept to the minimum
    passes: masking runs ONLY on blocks the diagonal crosses, and the
    finite _MASK/_M_INIT values make every NaN-guard `where` unnecessary.
    """
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _M_INIT)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _accumulate(masked: bool):
        # Inputs stay in their storage dtype (bf16): the MXU multiplies
        # bf16 at full rate and accumulates fp32 via
        # preferred_element_type — upcasting first would waste VPU
        # passes on [block, D] casts.
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_q, block_kv] fp32
        if masked:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0
            )
            kv_pos = ki * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1
            )
            s = jnp.where(kv_pos > q_pos, _MASK, s)
        m_prev = m_ref[:, 0]  # [block_q]
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])  # masked entries underflow to 0
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot(
            p.astype(v_ref.dtype), v_ref[0],
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    if causal:
        # Block classes: fully above the diagonal → skip; crossed by the
        # diagonal → masked softmax; fully below → unmasked (most blocks
        # at long seq, saving the iota+compare+select passes).
        crossed = jnp.logical_and(
            ki * block_kv < (qi + 1) * block_q,
            (ki + 1) * block_kv - 1 > qi * block_q,
        )
        below = (ki + 1) * block_kv - 1 <= qi * block_q

        @pl.when(crossed)
        def _masked():
            _accumulate(True)

        @pl.when(below)
        def _unmasked():
            _accumulate(False)
    else:
        _accumulate(False)

    @pl.when(ki == num_kv - 1)
    def _finalize():
        l = l_ref[:, 0]
        m = m_ref[:, 0]
        denom = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows → 0 output
        o_ref[0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)
        # logsumexp per row, consumed by the backward kernels.
        lse = jnp.where(l == 0.0, _NEG_INF, m + jnp.log(denom))
        lse_ref[0, 0] = lse.astype(jnp.float32)


def _fwd_call(qr, kr, vr, n_rep, causal, block_q, block_kv, interpret):
    bh, s, d = qr.shape
    num_q, num_kv = s // block_q, s // block_kv
    kernel = functools.partial(
        _fwd_kernel,
        block_q=block_q, block_kv=block_kv, num_kv=num_kv, causal=causal,
    )
    return pl.pallas_call(
        kernel,
        grid=(bh, num_q, num_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec(
                (1, block_kv, d),
                lambda b, qi, ki, n_rep=n_rep: (b // n_rep, ki, 0),
            ),
            pl.BlockSpec(
                (1, block_kv, d),
                lambda b, qi, ki, n_rep=n_rep: (b // n_rep, ki, 0),
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            # [BH, 1, S]: a (1, 1, block_q) block satisfies the TPU
            # (8, 128) tile rule (middle dim equals the array dim).
            pl.BlockSpec((1, 1, block_q), lambda b, qi, ki: (b, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), qr.dtype),
            jax.ShapeDtypeStruct((bh, 1, s), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running max
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running denom
            pltpu.VMEM((block_q, d), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
    )(qr, kr, vr)


# -------------------------------------------------------------- backward
def _recompute_p(q_ref, k_ref, lse_ref, qi, ki, block_q, block_kv, masked):
    """Blockwise softmax recompute from the saved lse. q is pre-scaled
    (see _fwd_kernel); masked entries underflow to exactly 0, so no
    guard passes are needed."""
    s = jax.lax.dot_general(
        q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if masked:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kv_pos = ki * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1
        )
        s = jnp.where(kv_pos > q_pos, _MASK, s)
    lse = lse_ref[0, 0]  # [block_q]; finite for every computed row
    return jnp.exp(s - lse[:, None])


def _bwd_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc,
    *, block_q: int, block_kv: int, num_q: int, num_kv: int, scale: float,
    causal: bool, dq_slab: bool,
):
    """One-pass fused backward: each (kv, q) block pair recomputes p ONCE
    and feeds all three gradients — vs the previous two-kernel backward
    this drops 2 of 7 per-pair MXU passes (the duplicated qk^T and
    do·v^T) and one exp recompute. dk/dv accumulate in [block_kv, d]
    scratch across the inner q sweep. dq has two modes:

    - dq_slab=True (short/medium seq): dq accumulates in a FULL [S, d]
      fp32 VMEM slab (1 MB at S=2048·d=128) persisting across the whole
      kv sweep of one head — no HBM partials exist.
    - dq_slab=False (long seq, slab would blow VMEM): each (kv, q) pair
      writes its fp32 dq contribution to a [num_kv, BH, S, d] partials
      output (every block written exactly once — the expanded-output
      pattern of the public splash kernels) and the caller sums over
      the leading axis."""
    ki = pl.program_id(1)  # kv outer, q inner
    qi = pl.program_id(2)
    q_slice = pl.ds(qi * block_q, block_q)

    @pl.when(qi == 0)
    def _init_kv():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    if dq_slab:
        @pl.when(ki == 0)
        def _init_dq():
            # ki==0 visits every q block (the first kv block is never
            # causal-skipped), so each slice zeroes exactly once a head.
            dq_acc[q_slice, :] = jnp.zeros(
                (block_q, dq_acc.shape[1]), jnp.float32
            )

    def _compute(masked: bool):
        p = _recompute_p(
            q_ref, k_ref, lse_ref, qi, ki, block_q, block_kv, masked
        )
        do = do_ref[0]
        # dv += p^T @ do — p downcast to the MXU dtype (flash-standard)
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0, 0][:, None])
        # dq contribution: ds @ k (q is pre-scaled; the chain-rule scale
        # lands once — at slab write-out, or per partial here).
        contrib = jax.lax.dot(
            ds.astype(k_ref.dtype), k_ref[0],
            preferred_element_type=jnp.float32,
        )
        if dq_slab:
            dq_acc[q_slice, :] += contrib
        else:
            dq_ref[0, 0] = contrib * scale
        # dk += ds^T @ q_scaled — exactly scale·dsᵀ@q, the chain-rule
        # factor rides the pre-scaled q.
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        # q blocks entirely before this kv block see none of it.
        overlaps = (qi + 1) * block_q > ki * block_kv
        crossed = jnp.logical_and(
            overlaps, (ki + 1) * block_kv - 1 > qi * block_q
        )
        below = jnp.logical_and(
            overlaps, (ki + 1) * block_kv - 1 <= qi * block_q
        )

        @pl.when(crossed)
        def _masked():
            _compute(True)

        @pl.when(below)
        def _unmasked():
            _compute(False)

        if not dq_slab:
            # Skipped pairs still own a partials block; the output
            # window holds stale VMEM unless written.
            @pl.when(jnp.logical_not(overlaps))
            def _skipped():
                dq_ref[0, 0] = jnp.zeros_like(dq_ref[0, 0])
    else:
        _compute(False)

    if dq_slab:
        # The dq output block (indexed by qi) is flushed at every visit;
        # only the final kv sweep's value survives, with the full sum.
        dq_ref[0] = (dq_acc[q_slice, :] * scale).astype(dq_ref.dtype)

    @pl.when(qi == num_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_impl(q, k, v, causal, scale, block_q, block_kv, interpret):
    b, s, h, d = q.shape
    hkv = k.shape[2]
    n_rep = h // hkv
    # Pre-scale q once (fused into the transpose by XLA) instead of a
    # per-block [block_q, block_kv] multiply inside the kernel. Costs
    # one bf16 rounding of q when scale is not a power of two (d=128 →
    # 2^-3.5) — the standard flash-kernel tradeoff.
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    qr = qs.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    out, lse = _fwd_call(
        qr, kr, vr, n_rep, causal, block_q, block_kv, interpret
    )
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(
    q, k, v, causal, scale, block_q, block_kv,
    bwd_block_q, bwd_block_kv, interpret,
):
    out, _ = _flash_impl(
        q, k, v, causal, scale, block_q, block_kv, interpret
    )
    b, s, h, d = q.shape
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def _flash_fwd(
    q, k, v, causal, scale, block_q, block_kv,
    bwd_block_q, bwd_block_kv, interpret,
):
    out, lse = _flash_impl(
        q, k, v, causal, scale, block_q, block_kv, interpret
    )
    b, s, h, d = q.shape
    # Residual tags: under jax.checkpoint, a policy that saves
    # "flash_out"/"flash_lse" keeps these across the remat boundary, so
    # the backward replay rebuilds only the (cheap) projections and
    # SKIPS re-running the forward flash kernel — the models' remat
    # mode "flash" (models/llama.py) is built on exactly this.
    from jax.ad_checkpoint import checkpoint_name

    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    # Separately-named q/k/v residual tags let a policy ALSO pin the
    # attention inputs (skipping the projection/RoPE recompute) at
    # ~2x the memory of flash_out alone.
    q = checkpoint_name(q, "flash_qkv")
    k = checkpoint_name(k, "flash_qkv")
    v = checkpoint_name(v, "flash_qkv")
    return (
        out.reshape(b, h, s, d).transpose(0, 2, 1, 3),
        (q, k, v, out, lse),
    )


def _flash_bwd(
    causal, scale, block_q, block_kv, bwd_block_q, bwd_block_kv,
    interpret, res, g,
):
    # The backward sweep has its own optimum (smaller q blocks pipeline
    # the 5-matmul body better than the forward's fatter tiles).
    block_q, block_kv = bwd_block_q, bwd_block_kv
    q, k, v, out, lse = res
    b, s, h, d = q.shape
    hkv = k.shape[2]
    n_rep = h // hkv

    # Kernels consume the pre-scaled q (matches the saved lse; dk then
    # needs no extra scale and dq scales once at finalize).
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    qr = qs.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    # kv stays at Hkv heads: kernels read the shared head via the same
    # bh // n_rep index map as the forward (no materialized repeat).
    kr = k.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    do = g.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    # delta_i = rowsum(dO_i * O_i) — cheap, fused by XLA.
    delta = (do.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
    delta = delta[:, None, :]  # [BH, 1, S] to match the lse layout

    num_q, num_kv = s // block_q, s // block_kv
    # Fused one-pass backward: kv blocks outer, q blocks inner. dk/dv
    # OUTPUTS are per-q-head (grid over B*H) and group-summed below —
    # only they need the n_rep expansion, not the k/v inputs.
    q_spec = pl.BlockSpec((1, block_q, d), lambda bh, ki, qi: (bh, qi, 0))
    kv_in_spec = pl.BlockSpec(
        (1, block_kv, d),
        lambda bh, ki, qi, n_rep=n_rep: (bh // n_rep, ki, 0),
    )
    kv_out_spec = pl.BlockSpec(
        (1, block_kv, d), lambda bh, ki, qi: (bh, ki, 0)
    )
    row_spec = pl.BlockSpec((1, 1, block_q), lambda bh, ki, qi: (bh, 0, qi))
    # The VMEM dq slab scales with S; past the budget (seq ~8k at d=128)
    # fall back to HBM fp32 partials summed outside the kernel (measured
    # ~2% slower at bench shapes; the slab path wins where it fits).
    dq_slab = s * d * 4 <= _DQ_SLAB_VMEM_BYTES
    if dq_slab:
        dq_spec = pl.BlockSpec(
            (1, block_q, d), lambda bh, ki, qi: (bh, qi, 0)
        )
        dq_shape = jax.ShapeDtypeStruct((b * h, s, d), q.dtype)
        dq_scratch = pltpu.VMEM((s, d), jnp.float32)  # whole-head slab
    else:
        dq_spec = pl.BlockSpec(
            (1, 1, block_q, d), lambda bh, ki, qi: (ki, bh, qi, 0)
        )
        dq_shape = jax.ShapeDtypeStruct((num_kv, b * h, s, d), jnp.float32)
        dq_scratch = pltpu.VMEM((8, d), jnp.float32)  # unused dummy
    dq, dk_e, dv_e = pl.pallas_call(
        functools.partial(
            _bwd_kernel, block_q=block_q, block_kv=block_kv, num_q=num_q,
            num_kv=num_kv, scale=scale, causal=causal, dq_slab=dq_slab,
        ),
        grid=(b * h, num_kv, num_q),
        in_specs=[
            q_spec, kv_in_spec, kv_in_spec, q_spec, row_spec, row_spec
        ],
        out_specs=[dq_spec, kv_out_spec, kv_out_spec],
        out_shape=[
            dq_shape,
            jax.ShapeDtypeStruct((b * h, s, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, s, d), v.dtype),
        ],
        scratch_shapes=[
            dq_scratch,
            pltpu.VMEM((block_kv, d), jnp.float32),
            pltpu.VMEM((block_kv, d), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr, do, lse, delta)

    if not dq_slab:
        dq = dq.sum(0).astype(q.dtype)
    dq = dq.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    # Sum each kv group's n_rep expanded gradients back to Hkv heads.
    dk = (
        dk_e.reshape(b, hkv, n_rep, s, d).sum(2).transpose(0, 2, 1, 3)
    ).astype(k.dtype)
    dv = (
        dv_e.reshape(b, hkv, n_rep, s, d).sum(2).transpose(0, 2, 1, 3)
    ).astype(v.dtype)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


# Default tile sizes, tuned on v5e (see flash_attention docstring);
# exported so gating code derives fitted blocks from the SAME value the
# kernel will use.
DEFAULT_BLOCK = 1024
# Backward-sweep tiles (fused one-pass kernel), tuned separately on v5e
# at the bench shapes — the 5-matmul body pipelines best with narrower
# q tiles than the forward.
DEFAULT_BWD_BLOCK_Q = 1024
DEFAULT_BWD_BLOCK_KV = 1024


def _fit_block(requested: int, s: int) -> int:
    """Largest block <= requested that divides s (s itself when s fits).
    Prime-ish lengths collapse to tiny blocks — callers that can choose
    another path should gate on the fitted size (>= 128, multiple of 8)."""
    if s <= requested:
        return s
    for d in range(requested, 0, -1):
        if s % d == 0:
            return d
    return 1


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "block_q", "block_kv", "bwd_block_q", "bwd_block_kv",
        "interpret", "scale",
    ),
)
def flash_attention(
    q: jnp.ndarray,  # [B, S, H, D]
    k: jnp.ndarray,  # [B, S, Hkv, D]
    v: jnp.ndarray,  # [B, S, Hkv, D]
    *,
    causal: bool = True,
    scale: float | None = None,
    # PRECONDITION: post-scale attention scores must stay well inside
    # (-1e9, +inf). The kernel masks with a FINITE -1e9 (so no NaN-guard
    # `where` passes are needed); a real score at or below -1e9 —
    # representable in bf16 up to ~3e38 with pathological/unnormalized
    # activations — would rank BELOW masked positions and silently
    # corrupt the softmax. Normalized transformer activations sit orders
    # of magnitude away from this; interpret=True adds an assertion.
    # DEFAULT_BLOCK (1024/1024) measured fastest on v5e at seq 2048
    # (27ms vs 36ms fwd+bwd for the old 256/512 at B16·H16·D64); blocks
    # clamp to the sequence for short inputs. The fused backward prefers
    # its own (narrower-q) tiles — None inherits the forward blocks.
    block_q: int = DEFAULT_BLOCK,
    block_kv: int = DEFAULT_BLOCK,
    bwd_block_q: int | None = DEFAULT_BWD_BLOCK_Q,
    bwd_block_kv: int | None = DEFAULT_BWD_BLOCK_KV,
    interpret: bool = False,
) -> jnp.ndarray:
    b, s, h, d = q.shape
    hkv = k.shape[2]
    if h % hkv:
        raise ValueError(f"n_heads={h} not divisible by n_kv={hkv}")
    # Largest divisor of the sequence that fits the request — any s
    # works: s <= block keeps one full block (the old fast path);
    # awkward lengths degrade to their largest divisor (prime-ish
    # lengths degrade hard — perf-sensitive callers gate on _fit_block).
    block_q = _fit_block(block_q, s)
    block_kv = _fit_block(block_kv, s)
    bwd_block_q = _fit_block(bwd_block_q or block_q, s)
    bwd_block_kv = _fit_block(bwd_block_kv or block_kv, s)
    # A tiny fitted block (prime-ish seq) means orders-of-magnitude
    # slower Pallas tiles than the MXU-friendly sizes — warn instead of
    # silently cliffing (trace-time only; jit caches per static shape).
    if min(block_q, block_kv) < 128 and s > 128:
        import warnings

        # tpulint: allow(TPU602 reason=once-per-compilation is the intent - the slowdown is a property of the STATIC block sizes, so trace time (one warn per compiled shape, via the jit cache) is exactly the right cadence; per-step emission would spam)
        warnings.warn(
            f"flash_attention: seq={s} only admits blocks "
            f"(q={block_q}, kv={block_kv}) < 128 — expect a severe "
            "slowdown; pad the sequence to a multiple of 128 or use "
            "dense attention for this shape",
            stacklevel=2,
        )
    if scale is None:
        scale = d**-0.5
    if interpret:
        # Debug-mode guard for the finite-mask precondition (see the
        # signature comment). This function is jit-wrapped, so the
        # check rides a host callback (interpret mode is the CPU/debug
        # path — the callback cost is irrelevant there); |scores| is
        # bounded by the product of input maxima.
        bound = (
            jnp.max(jnp.abs(q.astype(jnp.float32)))
            * jnp.max(jnp.abs(k.astype(jnp.float32)))
            * abs(scale)
            * d
        )

        def _host_check(b):
            if float(b) >= 1e8:
                raise AssertionError(
                    f"flash_attention: |scores| can reach {float(b):.3g}"
                    " — within 10x of the -1e9 finite mask (masked "
                    "positions would outrank real ones); normalize the "
                    "inputs or use dense attention"
                )

        jax.debug.callback(_host_check, bound)
    return _flash(
        q, k, v, causal, scale, block_q, block_kv,
        bwd_block_q, bwd_block_kv, interpret,
    )


def make_flash_attention(mesh, batch_axes=("dp", "fsdp"), head_axis="tp"):
    """Build a trainer attention fn running the flash kernel per shard
    under shard_map (batch sharded over the data axes, heads over tp;
    sequence stays local — combine with ring attention for SP). Drop-in
    for ray_tpu.models.llama.forward(attn_fn=...)."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu._private import chip

    # Interpreted only where JAX runs on the CPU (tests, dry runs).
    interpret = chip.platform() != "tpu"
    spec = P(batch_axes, None, head_axis, None)

    def kernel(q, k, v):
        return flash_attention(q, k, v, interpret=interpret)

    if mesh is None or mesh.size == 1:
        return kernel
    # check_vma=False: pallas_call outputs carry no varying-mesh-axes
    # metadata, which the checker would otherwise require.
    return shard_map(
        kernel,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
