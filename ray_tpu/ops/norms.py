"""RMSNorm and LayerNorm, computed in fp32 and cast back — XLA fuses
them into the neighboring matmul's prologue, so no Pallas kernel is
needed."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    orig_dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    normed = x32 * jnp.reciprocal(jnp.sqrt(var + eps))
    out = normed * (1.0 + scale.astype(jnp.float32))
    return out.astype(orig_dtype)


def layer_norm(
    x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray, eps: float = 1e-5
) -> jnp.ndarray:
    """LayerNorm with weight ``1 + scale`` (a norm's weight is stored as
    ``scale``, as `rms_norm`'s) and ``bias``."""
    x32 = x.astype(jnp.float32)
    centred = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(centred), axis=-1, keepdims=True)
    out = centred * jax.lax.rsqrt(var + eps) * (1.0 + scale) + bias
    return out.astype(x.dtype)
