"""RMSNorm, as every block of the layer-typed LM applies it."""

import jax
import jax.numpy as jnp

__all__ = ["rmsnorm"]


def rmsnorm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * w
