"""Rotary position embedding with YaRN's stretched frequencies.

A rotary part of width ``d`` turns its pairs by the angle ``position *
inv_freq[i]``: pair ``i`` is ``(2i, 2i+1)`` (the interleaved layout, as
``deepseek_v2`` stores a rotary key) or ``(i, i + d/2)`` (the half layout,
``rotate_half`` of a whole head, as ``evabyte`` and most published code have
it; :func:`apply_rope`'s ``half``). The two differ by a fixed permutation of
a head's columns. Plain RoPE has ``inv_freq_i = base ** (-2i/d)``.
YaRN (``rope_scaling.type == "yarn"``) divides the slow frequencies by
``factor`` and leaves the fast ones, with a linear ramp between the pair
that makes ``beta_fast`` turns over the original context and the pair that
makes ``beta_slow``; it also scales the attention logits by ``mscale ** 2``
(:func:`softmax_scale`). Both are the published ``deepseek_v2`` arithmetic;
the factor it puts on cos/sin, ``mscale(factor, mscale) / mscale(factor,
mscale_all_dim)``, is applied as published (1 where the two are equal).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["inv_freq", "yarn_mscale", "softmax_scale", "rope_tables",
           "apply_rope"]


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def inv_freq(dim: int, base: float, scaling: Optional[dict]) -> np.ndarray:
    """float64 [dim / 2] turning rates, a radian a position."""
    i = np.arange(dim // 2, dtype=np.float64)
    plain = base ** (-2.0 * i / dim)
    if scaling is None:
        return plain
    original = scaling["original_max_position_embeddings"]

    def pair_making(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(pair_making(scaling["beta_fast"])), 0)
    high = min(math.ceil(pair_making(scaling["beta_slow"])), dim - 1)
    ramp = np.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return plain / scaling["factor"] * ramp + plain * (1.0 - ramp)


def softmax_scale(qk_head_dim: int, scaling: Optional[dict]) -> float:
    """``qk_head_dim ** -0.5``, times ``mscale ** 2`` under YaRN."""
    scale = float(qk_head_dim) ** -0.5
    if scaling is not None and scaling.get("mscale_all_dim", 0):
        scale *= yarn_mscale(scaling["factor"],
                             scaling["mscale_all_dim"]) ** 2
    return scale


def rope_tables(length: int, dim: int, base: float,
                scaling: Optional[dict]):
    """(cos, sin) float32 [length, dim / 2] of positions ``0..length-1``."""
    rates = jnp.asarray(inv_freq(dim, base, scaling), jnp.float32)
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * rates[None, :]
    on_tables = 1.0 if scaling is None else \
        yarn_mscale(scaling["factor"], scaling.get("mscale", 1.0)) \
        / yarn_mscale(scaling["factor"], scaling.get("mscale_all_dim", 1.0))
    return jnp.cos(angle) * on_tables, jnp.sin(angle) * on_tables


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
               half: bool = False) -> jax.Array:
    """``x`` [..., S, heads, dim]: pair ``i`` of every head, ``(2i, 2i+1)``
    or with ``half`` ``(i, i + dim/2)``, turned by position's angle ``i``;
    the layout is kept."""
    if half:
        a, b = jnp.split(x, 2, axis=-1)
    else:
        pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
        a, b = pairs[..., 0], pairs[..., 1]
    c, s = cos[:, None, :].astype(x.dtype), sin[:, None, :].astype(x.dtype)
    turned = [a * c - b * s, a * s + b * c]
    if half:
        return jnp.concatenate(turned, axis=-1)
    return jnp.stack(turned, axis=-1).reshape(x.shape)
