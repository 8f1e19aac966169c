"""The residual path of a model that carries ``n = cfg.hc_mult`` streams
(manifold-constrained hyper-connections, mHC, arXiv:2512.24880), for the
families that have one (``models/glm5_next.py``, ``models/motif.py``)
and the pattern programs that run them (``llm/hybrid_kv.py``).

The carried activation is ``X`` ``[n, d]`` a token. Every sublayer ``F``
(`mhc_mix`, `mhc_spread`): ``x~ = RMSNorm(vec(X))`` over all ``n d``
numbers (eps ``hc_eps``, no weight); ``Hpre = sigmoid(a_pre (x~ P_pre)
+ b_pre)`` ``[n]``, ``Hpost = 2 sigmoid(a_post (x~ P_post) + b_post)``
``[n]``, ``Hres = Sinkhorn(exp(a_res mat(x~ P_res) + b_res))`` ``[n,
n]``: ``hc_sinkhorn_iters`` rounds of dividing rows, then columns, by
their sums (+ ``hc_eps``). ``h = Hpre X``, ``y = F(RMSNorm_l(h))``,
``X <- Hres X + Hpost^T y``. ``x~``, the three ``H`` and the mixes are
float32; the streams are carried in ``cfg.dtype``. What is read of a
config: ``hc_mult``, ``hc_sinkhorn_iters``, ``hc_eps``, ``d_model``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ray_tpu._private import chip
from ray_tpu.models.nemotron_h import Params, _normal
from ray_tpu.ops.pallas import mhc_streams

_HIGHEST = jax.lax.Precision.HIGHEST
# Tokens from which the residual path takes its kernels on a TPU
# (`_mhc_by_kernels`): a tile of `ops/pallas/mhc_streams.py`. A prefill
# chunk's 2,048 read 0.288 ms a sublayer there for 1.82 in XLA's form; a
# decode step's slots keep XLA's form, which reads no slower alone
# (0.064 ms a sublayer at 16 rows for the kernels' 0.065, 0.065 at 32
# for 0.076: ten sublayers a call on a v5e, my chip run, PR 64).
_MHC_KERNEL_ROWS = 128


def init_hc(key, cfg) -> Params:
    """A sublayer's residual mixing: ``P`` ``[n d, n | n | n n]`` float32
    (pre, post, res side by side), the three scalars ``a`` and the
    biases ``b``. Assumed initial values, at which the input-dependent
    part counts: ``a`` 1 against a ``P`` whose products with the unit
    ``x~`` have unit variance; ``b_pre`` 0 (each stream read at a half),
    ``b_post`` 0 (written at 1), ``b_res`` 2 on the diagonal (after
    Sinkhorn about 0.7 a stream kept, 0.1 to each other)."""
    n, d = cfg.hc_mult, cfg.d_model
    return {
        "proj": _normal(key, (n * d, 2 * n + n * n), n * d, jnp.float32),
        "scale": jnp.ones((3,), jnp.float32),
        "b_pre": jnp.zeros((n,), jnp.float32),
        "b_post": jnp.zeros((n,), jnp.float32),
        "b_res": 2.0 * jnp.eye(n, dtype=jnp.float32),
    }


def sinkhorn(m, iters: int, eps: float):
    """``m`` [.., n, n] positive -> (nearly) doubly stochastic: ``iters``
    rounds of dividing each row by its sum, then each column by its."""
    def one(_, m):
        m = m / (m.sum(-1, keepdims=True) + eps)
        return m / (m.sum(-2, keepdims=True) + eps)

    return jax.lax.fori_loop(0, iters, one, m)


def _mhc_by_kernels(x) -> bool:
    """Whether the streams x [.., n, d] take ``ops/pallas/mhc_streams.py``:
    on a TPU, from `_MHC_KERNEL_ROWS` tokens on. The platform and the row
    count decide, as `kda_chunked` and `_DENSE_ATTENTION_KEYS` do."""
    return (
        chip.platform() == "tpu"
        and math.prod(x.shape[:-2]) >= _MHC_KERNEL_ROWS
    )


def mhc_mix(x, p, cfg):
    """A sublayer's input from the streams x [.., n, d]: ``h = Hpre X``
    [.., d] in ``x``'s dtype, and what `mhc_spread` writes back by:
    ``Hres`` [.., n, n] and ``Hpost`` [.., n], float32.

    On a TPU one call of ``ops/pallas/mhc_streams.py`` (PR 64); elsewhere
    and under `_MHC_KERNEL_ROWS` tokens XLA's form below, which is tier
    1's path and the kernel's oracle."""
    n = cfg.hc_mult
    with jax.named_scope("mhc:mix"):
        if _mhc_by_kernels(x):
            h, h_res, h_post = mhc_streams.mhc_mix(
                x, p["proj"], p["scale"], p["b_pre"], p["b_post"],
                p["b_res"], iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps,
            )
            return h, (h_res, h_post)
        flat = x.reshape(*x.shape[:-2], -1).astype(jnp.float32)
        var = jnp.mean(flat * flat, axis=-1, keepdims=True)
        unit = flat * jax.lax.rsqrt(var + cfg.hc_eps)
        raw = jnp.dot(unit, p["proj"], precision=_HIGHEST)  # [.., 2n + n n]
        pre, post, res = jnp.split(raw, [n, 2 * n], axis=-1)
        a = p["scale"]
        h_pre = jax.nn.sigmoid(a[0] * pre + p["b_pre"])
        h_post = 2.0 * jax.nn.sigmoid(a[1] * post + p["b_post"])
        h_res = sinkhorn(
            jnp.exp(a[2] * res.reshape(*res.shape[:-1], n, n) + p["b_res"]),
            cfg.hc_sinkhorn_iters, cfg.hc_eps,
        )
        h = jnp.einsum(
            "...n,...nd->...d", h_pre, x.astype(jnp.float32)
        ).astype(x.dtype)
    return h, (h_res, h_post)


def mhc_spread(x, out, h_res, h_post):
    """``Hres X + Hpost^T y``: the streams x [.., n, d] after a sublayer
    whose output is ``out`` [.., d]. On a TPU the other call of
    ``ops/pallas/mhc_streams.py``, by `mhc_mix`'s rule."""
    with jax.named_scope("mhc:spread"):
        if _mhc_by_kernels(x):
            return mhc_streams.mhc_spread(x, out, h_res, h_post)
        mixed = jnp.einsum("...ij,...jd->...id", h_res, x.astype(jnp.float32))
        wrote = h_post[..., None] * out.astype(jnp.float32)[..., None, :]
        return (mixed + wrote).astype(x.dtype)
