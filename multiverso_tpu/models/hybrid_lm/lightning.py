"""Lightning linear attention: causal attention without a softmax, each head
forgetting at its own fixed rate.

Per head ``h`` (``s_h > 0`` fixed, not learned; ``scale = D ** -0.5``)::

    o_t = sum_{j <= t} exp(-s_h (t - j)) * scale * (q_t . k_j) * v_j

which is the state-space recurrence ``H_t = exp(-s_h) H_{t-1} + v_t k_t^T``,
``o_t = H_t (scale q_t)``: :func:`~.mamba2.ssd_chunked` at steps of one, ``a =
-s_h``, ``b = k``, ``c = scale q``, ``x = v``, a group a head and a ``D x D``
state. The program computes it as that chunked scan; the reference
(``benchmark/reference/minicpm-sala-9b-pp8.py``) as the sum over ``j``.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np

from multiverso_tpu.models.hybrid_lm import attention, rope
from multiverso_tpu.models.hybrid_lm.mamba2 import ssd_chunked
from multiverso_tpu.models.hybrid_lm.norm import rmsnorm

__all__ = ["lightning_slopes", "lightning_attention", "lightning_mixer"]


def lightning_slopes(heads: int, layer: int, layers: int,
                     held=None) -> np.ndarray:
    """float32 [held heads]: head ``h`` (1..heads) of layer ``layer``
    (0-based, of ``layers``) decays by ``2 ** (-8 h / heads) * (1 - layer /
    (layers - 1) + 1e-5)`` a position (Lightning Attention's
    ``build_slope_tensor`` with its per-layer factor). ``heads`` counts the
    whole model's; ``held`` names the ``h`` of those that live here (None:
    all), so a chip's share of the heads keeps the rates they were published
    with."""
    h = np.arange(1, heads + 1) if held is None else np.asarray(held)
    base = 2.0 ** (-8.0 * h / heads)
    return (base * (1.0 - layer / max(layers - 1, 1) + 1e-5)).astype(
        np.float32)


def lightning_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        slopes: jax.Array, chunk: int, group: int = 8,
                        interpret: Optional[bool] = None) -> jax.Array:
    """``q``, ``k``, ``v`` [B, S, H, D], ``slopes`` [H] -> [B, S, H, D];
    ``group`` chunks' inner products at a time and ``interpret`` as
    :func:`~.mamba2.ssd_chunked` takes them (Mamba-2's own 8)."""
    return ssd_chunked(v, None, -slopes, k, q * (float(q.shape[-1]) ** -0.5),
                       chunk, group, interpret)


def lightning_mixer(p: dict, n: jax.Array, cfg, slopes: jax.Array,
                    scan_interpret: Optional[bool] = None) -> jax.Array:
    """No bias; an RMSNorm over each query and key head (one weight vector of
    ``lightning_head_dim`` each, shared by the heads), then both turned over
    the whole head in the half layout, plain ``rope_theta``, positions from
    the start of the packed sequence; no activation on ``q``, ``k``, ``v``.
    The heads' outputs side by side take one RMSNorm over all of them, then a
    gate ``sigmoid(n Wg)``, then the output projection. ``slopes``: the
    block's buffer (:func:`lightning_slopes`); ``scan_interpret``:
    :func:`lightning_attention`'s ``interpret``."""
    bsz, s, _ = n.shape
    h, d = cfg.lightning_nh, cfg.lightning_head_dim
    cos, sin = rope.rope_tables(s, d, cfg.rope_theta, None)
    q, k = (rope.apply_rope(
        rmsnorm((n @ p[w]).reshape(bsz, s, h, d), p[norm], cfg.norm_eps),
        cos, sin, True) for w, norm in (("wq", "q_norm"), ("wk", "k_norm")))
    v = (n @ p["wv"]).reshape(bsz, s, h, d)
    with jax.named_scope("lm_lightning_scan"):
        o = lightning_attention(q, k, v, slopes, cfg.lightning_chunk,
                                interpret=scan_interpret)
    y = rmsnorm(o.reshape(bsz, s, h * d), p["o_norm"], cfg.norm_eps)
    return attention.output_gate(y, n, p["wg"]) @ p["wo"]
