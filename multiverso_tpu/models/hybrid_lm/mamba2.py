"""Mamba-2 mixer: the state-space recurrence computed in chunks (SSD).

Per head (``h`` is ``head_dim x state``)::

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T        y_t = h_t C_t + D x_t

The program never steps through tokens. A sequence is cut into chunks of
``chunk`` tokens; inside a chunk the outputs are one masked product ``(C B^T
o L) x`` with ``L[t, s] = exp(sum_{s < r <= t} dt_r A)``; across chunks a
scan carries the ``head_dim x state`` state, each chunk adding what its own
tokens leave behind. The reference (``benchmark/reference/nemotron3-nano-30b-
a3b-ep16.py``) runs the recurrence as written.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from multiverso_tpu.ops import pallas_mamba
from multiverso_tpu.ops.pallas_ssd import scan_kernel_selected, ssd_scan

__all__ = ["mamba2_mixer", "ssd_chunked", "causal_conv1d",
           "gated_group_rmsnorm", "passes_kernel_selected"]


def causal_conv1d(x: jax.Array, w: jax.Array, b: jax.Array = None
                  ) -> jax.Array:
    """Depthwise causal convolution along the sequence: ``x`` [B, S, C],
    ``w`` [C, K], ``out[t] = sum_j w[:, j] x[t - (K-1) + j] + b`` (no ``b``:
    no bias)."""
    k = w.shape[1]
    s = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = b
    for j in range(k):
        tap = xp[:, j:j + s, :] * w[:, j]
        out = tap if out is None else out + tap
    return out


def gated_group_rmsnorm(y: jax.Array, z: jax.Array, w: jax.Array,
                        groups: int, eps: float) -> jax.Array:
    """``RMSNorm_w`` over ``groups`` equal groups of ``y * silu(z)``."""
    y = y * jax.nn.silu(z)
    shape = y.shape
    g = y.reshape(shape[:-1] + (groups, shape[-1] // groups))
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                          + eps)
    return g.reshape(shape) * w


def ssd_chunked(x: jax.Array, dt: Optional[jax.Array], a: jax.Array,
                b: jax.Array, c: jax.Array, chunk: int, group: int = 8,
                interpret: Optional[bool] = None,
                skip: Optional[jax.Array] = None) -> jax.Array:
    """``x`` [B, S, H, P], ``dt`` [B, S, H] (positive; None: steps of one),
    ``a`` [H] (negative), ``b``/``c`` [B, S, G, N] (a group serves H/G
    heads) -> ``y`` [B, S, H, P], with ``skip`` [H] the ``D x`` term too.
    Any S: the tail is padded with ``dt = 0`` tokens, which neither decay nor
    feed the state. ``interpret``: None unless the caller knows the arrays
    to live on ONE device, and then :func:`multiverso_tpu.ops.
    pallas_interpret` of it: shapes the kernels take (:func:`~multiverso_tpu.
    ops.pallas_ssd.scan_kernel_selected`) then run as those, a chunk's
    ``chunk x chunk`` decay planes in VMEM; every other scan in
    ``jax.numpy``, ``group`` chunks' planes at a time."""
    with jax.named_scope("lm_ssd"):
        if interpret is not None and scan_kernel_selected(
                chunk, b.shape[3], x.shape[2] // b.shape[2], x.shape[3],
                *(t.dtype for t in (x, dt, a, b, c, skip) if t is not None)):
            return _ssd_fused(x, dt, a, b, c, chunk, interpret, skip)
        y = _ssd_xla(x, jnp.ones(x.shape[:3], x.dtype) if dt is None else dt,
                     a, b, c, chunk, group)
        return y if skip is None else y + x * skip[:, None]


def _ssd_fused(x, dt, a, b, c, chunk: int, interpret: bool,
               skip) -> jax.Array:
    """The scan by :func:`~multiverso_tpu.ops.pallas_ssd.ssd_scan`: here only
    the padding, ``dt`` and ``dt a`` turned heads before positions, and the
    skip's weight a lane."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    pad = (-s) % chunk
    t = s + pad
    # without a ``dt``, ``a`` a position, and none on the padded tail
    dta = jnp.broadcast_to(a, (bsz, s, h)) if dt is None else dt * a
    if pad:
        x, dt, dta, b, c = (
            u if u is None else jnp.pad(u, ((0, 0), (0, pad)) + ((0, 0),) *
                                        (u.ndim - 2))
            for u in (x, dt, dta, b, c))

    def a_chunk(steps):                                # [B, nc, G, R, L]
        return jnp.moveaxis(
            steps.reshape(bsz, t // chunk, chunk, g, h // g), 2, -1)

    y = ssd_scan(
        x.reshape(bsz, t, h * p), None if dt is None else a_chunk(dt),
        a_chunk(dta), b.reshape(bsz, t, g * n), c.reshape(bsz, t, g * n),
        None if skip is None else jnp.repeat(
            skip.reshape(g, 1, h // g), p, axis=2), p, interpret)
    return y.reshape(bsz, t, h, p)[:, :s]


def _ssd_xla(x, dt, a, b, c, chunk: int, group: int) -> jax.Array:
    """The products inside the chunks run ``group`` chunks at a time
    (each group rematerialised in the backward pass), so the ``chunk x
    chunk`` decay planes of a whole sequence never exist at once."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    r = h // g
    k = min(group, -(-s // chunk))
    pad = (-s) % (chunk * k)
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) *
                               (t.ndim - 2)) for t in (x, dt, b, c))
    nc = (s + pad) // chunk
    x = x.reshape(bsz, nc, chunk, g, r, p)
    dt = dt.reshape(bsz, nc, chunk, g, r)
    b = b.reshape(bsz, nc, chunk, g, n)
    c = c.reshape(bsz, nc, chunk, g, n)
    # heads before positions, so that the chunk x chunk planes are minor
    cum = jnp.cumsum(jnp.moveaxis(dt * a.reshape(g, r), 2, -1), axis=-1)
    xdt = x * dt[..., None]                            # cum: [B, nc, G, R, L]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    @jax.checkpoint
    def inside(chunks):
        """(C B^T o L) x for ``k`` chunks."""
        c_k, b_k, cum_k, xdt_k = chunks
        decay = jnp.exp(jnp.where(
            causal, cum_k[..., :, None] - cum_k[..., None, :], -jnp.inf))
        cb = jnp.einsum("bctgn,bcsgn->bcgts", c_k, b_k)
        return jnp.einsum("bcgrts,bcsgrp->bctgrp",
                          decay * cb[:, :, :, None], xdt_k)

    def grouped(t):
        return jnp.moveaxis(t.reshape((bsz, nc // k, k) + t.shape[2:]), 1, 0)

    y = jax.lax.map(inside, (grouped(c), grouped(b), grouped(cum),
                             grouped(xdt)))
    y = jnp.moveaxis(y, 0, 1).reshape(x.shape)

    # what each chunk leaves behind, and the scan that carries it on
    last = cum[..., -1]                                # [B, nc, G, R]
    to_end = jnp.moveaxis(jnp.exp(last[..., None] - cum), -1, 2)
    left = jnp.einsum("bcsgn,bcsgrp->bcgrpn", b, xdt * to_end[..., None])

    def carry_on(state, chunk_in):
        decay_c, left_c = chunk_in
        return decay_c[..., None, None] * state + left_c, state

    _, before = jax.lax.scan(
        carry_on, jnp.zeros((bsz, g, r, p, n), x.dtype),
        (jnp.moveaxis(jnp.exp(last), 1, 0), jnp.moveaxis(left, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                # state at chunk start
    y = y + jnp.einsum("bctgn,bcgrpn->bctgrp", c, before) \
        * jnp.moveaxis(jnp.exp(cum), -1, 2)[..., None]
    return y.reshape(bsz, nc * chunk, h, p)[:, :s]


def passes_kernel_selected(cfg, *dtypes) -> bool:
    """Whether a Mamba-2 block of ``cfg`` takes its convolution and gated
    norm through :mod:`multiverso_tpu.ops.pallas_mamba`, as far as its widths
    and ``dtypes`` say."""
    return pallas_mamba.mamba_passes_selected(
        cfg.d_inner, cfg.n_groups * cfg.ssm_state_size,
        cfg.d_inner // cfg.n_groups, cfg.conv_kernel, *dtypes)


def mamba2_mixer(p: dict, n: jax.Array, cfg,
                 scan_interpret: Optional[bool] = None) -> jax.Array:
    """``n`` [B, S, hidden] (already normed) -> the mixer's output.
    ``scan_interpret``: :func:`ssd_chunked`'s ``interpret``, and the
    ``interpret`` of the passes on either side of the scan: where it is not
    None, widths :func:`passes_kernel_selected` accepts run the convolution,
    ``silu`` and the cut into ``x | B | C``, and the gated norm, as the fused
    passes of :mod:`multiverso_tpu.ops.pallas_mamba`, which read ``in_proj``'s
    output where it lies; every other call the functions above."""
    bsz, s, _ = n.shape
    h, hp = cfg.mamba_num_heads, cfg.mamba_head_dim
    g, st = cfg.n_groups, cfg.ssm_state_size
    d_inner = cfg.d_inner
    zxbcdt = n @ p["in_proj"]
    fused = scan_interpret is not None and passes_kernel_selected(
        cfg, zxbcdt.dtype, p["conv_w"].dtype, p["conv_b"].dtype,
        p["gnorm"].dtype)
    if fused:
        x, b, c = pallas_mamba.conv_silu_split(
            zxbcdt, p["conv_w"], p["conv_b"], d_inner,
            (d_inner, g * st, g * st), scan_interpret)
    else:
        z = zxbcdt[..., :d_inner]
        xbc = zxbcdt[..., d_inner:d_inner + cfg.conv_dim]
        xbc = jax.nn.silu(causal_conv1d(xbc, p["conv_w"], p["conv_b"]))
        x = xbc[..., :d_inner]
        b = xbc[..., d_inner:d_inner + g * st]
        c = xbc[..., d_inner + g * st:]
    dt = zxbcdt[..., d_inner + cfg.conv_dim:]
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = -jnp.exp(p["A_log"])
    y = ssd_chunked(x.reshape(bsz, s, h, hp), dt, a,
                    b.reshape(bsz, s, g, st), c.reshape(bsz, s, g, st),
                    cfg.chunk_size, interpret=scan_interpret, skip=p["D"])
    y = y.reshape(bsz, s, d_inner)
    y = pallas_mamba.gated_group_norm(
        y, zxbcdt, p["gnorm"], g, cfg.norm_eps, scan_interpret) if fused \
        else gated_group_rmsnorm(y, z, p["gnorm"], g, cfg.norm_eps)
    return y @ p["out_proj"]
