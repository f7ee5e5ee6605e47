"""Gated short convolution (``lfm2``'s ``conv`` mixer): two multiplicative
gates around a depthwise causal convolution of a few taps.

``[B | C | x] = n W_in`` (thirds of ``3 x hidden``, in that order); ``z = B *
x``; ``c_t = sum_j w[:, j] * z_{t - (L-1) + j}`` (``L = conv_L_cache`` taps a
channel, zero before the sequence's start, no bias); ``y = C * c``; out ``y
W_out``. No activation: the two gates are the non-linearity. A token sees
itself and the ``L - 1`` before it, and nothing is carried from further back:
the convolution is Mamba-2's (:func:`~.mamba2.causal_conv1d`), without its
bias, its activation and the scan after it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from multiverso_tpu.models.hybrid_lm.mamba2 import causal_conv1d

__all__ = ["shortconv_mixer"]


def shortconv_mixer(p: dict, n: jax.Array, cfg) -> jax.Array:
    """``n`` [B, S, hidden] (already normed) -> the mixer's output."""
    b, c, x = jnp.split(n @ p["in_proj"], 3, axis=-1)
    return (c * causal_conv1d(b * x, p["conv_w"])) @ p["out_proj"]
