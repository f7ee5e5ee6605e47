"""RMSNorm, as every block of the layer-typed LM applies it."""

import jax
import jax.numpy as jnp

__all__ = ["rmsnorm"]


def rmsnorm(x: jax.Array, w: jax.Array, eps: float,
            unit_offset: bool = False) -> jax.Array:
    """``x / rms(x) * w``, or ``* (1 + w)`` with ``unit_offset`` (the
    published ``norm_add_unit_offset``: the weight is stored around zero)."""
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * (1.0 + w if unit_offset else w)
