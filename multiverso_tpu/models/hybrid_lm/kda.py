"""Kimi Delta Attention (KDA): linear attention whose state forgets a CHANNEL
at a time and is corrected by the key before it takes the new value (the
delta rule).

Per head (``S`` is ``d_k x d_v``, ``k_t`` a unit vector, ``a_t = exp(g_t)`` in
``(0, 1]^{d_k}``, ``b_t`` in ``(0, 1)``)::

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

:func:`~.mamba2.ssd_chunked` cannot compute it: its decay is one scalar a head
and step and its state takes ``v k^T`` as it comes. The program never steps
through tokens either. With ``u_t = b_t (v_t - S_{t-1}^T Diag(a_t) k_t)`` the
update is ``S_t = Diag(a_t) S_{t-1} + k_t u_t^T``, so inside a chunk of ``C``
positions that starts from ``S_0``, with ``G_i = sum_{l <= i} g_l`` the log
decay from the chunk's start::

    A_ij = sum_c k_ic k_jc exp(G_ic - G_jc)   (j < i)
    B_ij = sum_c q_ic k_jc exp(G_ic - G_jc)   (j <= i)
    (I + Diag(b) A) U = Diag(b) (V - (K o exp G) S_0)
    O = (Q o exp G) S_0 + B U
    S_C = Diag(exp G_C) S_0 + (K o exp(G_C - G))^T U

``U`` is linear in ``S_0``: ``U = U' - W S_0`` with ``U'`` and ``W`` the
solutions for ``Diag(b) V`` and ``Diag(b) (K o exp G)`` of ONE unit-triangular
system a chunk and head (the WY / UT form), solved by its inverse
(:func:`_unit_lower_inverse`). Everything but ``S_0`` is a
chunk's own, computed for all chunks at once (:func:`_inside_chunks`, which
also scales ``Q`` and ``K`` by their decays); a scan over the chunks then
carries the ``d_k x d_v`` state, five products a trip (:func:`_across_chunks`).

The decay is a channel's own, so ``exp(G_i - G_j)`` does not factor into one
scale for ``k_i`` and one for ``k_j`` without leaving float32's range: at the
published bound of -5 a step a 64-position chunk spans ``e^320``. The chunk
is therefore cut into sub-chunks of at most 16 positions (``e^80`` at the
bound; float32 holds ``e^88``: what the bound is for) and every factor is
taken from a point ``R_a`` of the query's sub-chunk ``a``, the MIDDLE of its
own decay: ``exp(G_i - R_a)`` and, for a key of the same sub-chunk, ``exp(R_a
- G_j)`` lie in ``[e^-40, e^40]``, which leaves float32 twenty orders of
magnitude for the entries they multiply (taken from the sub-chunk's START
they would reach ``e^-80``, and a product with an entry of ``1e-3`` is
flushed to zero: measured, the gate's gradient lost 30% at the bound); a key
of an earlier sub-chunk has ``exp(R_a - G_j)`` under ``e^40`` times the
product's own size; keys of later sub-chunks, which no query of ``a`` sees,
are masked BEFORE the exponential. The reference (``benchmark/reference/
ling-3.0-flash-ep32.py``) runs the recurrence as written.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from multiverso_tpu.models.hybrid_lm.mamba2 import causal_conv1d
from multiverso_tpu.models.hybrid_lm.norm import rmsnorm
from multiverso_tpu.ops.pallas_kda import (SUB_CHUNK, kda_kernel_selected,
                                           kda_scan)
from multiverso_tpu.ops.pallas_kda_passes import (delta_rule_inputs,
                                                  gated_head_norm,
                                                  kda_passes_selected)

__all__ = ["kda_mixer", "kda_chunked", "kda_gate", "l2_normalised",
           "passes_kernel_selected", "SUB_CHUNK", "GROUP_ELEMENTS"]

_L2_EPS = 1e-6


def l2_normalised(x: jax.Array) -> jax.Array:
    """``x / max(|x|_2, eps)`` over the last axis (safe at ``x = 0``)."""
    return x * jax.lax.rsqrt(jnp.maximum(
        jnp.sum(jnp.square(x), axis=-1, keepdims=True), _L2_EPS ** 2))


def kda_gate(x: jax.Array, a_log: jax.Array, dt_bias: jax.Array,
             lower_bound: float) -> jax.Array:
    """The log decay a channel under ``kda_safe_gate``: ``x`` [..., H, D]
    (``n W_a`` by heads), ``a_log`` [H], ``dt_bias`` [H, D] -> ``lower_bound *
    sigmoid(exp(a_log) * (x + dt_bias))``, in ``(lower_bound, 0)``, float32."""
    x = x.astype(jnp.float32) + dt_bias
    return lower_bound * jax.nn.sigmoid(jnp.exp(a_log)[:, None] * x)


_HIGHEST = jax.lax.Precision.HIGHEST


def _inside_chunks(q, k, v, g, beta, sub: int):
    """What a chunk computes without its starting state, for any leading axes:
    ``q``, ``k``, ``g`` [..., C, Dk], ``v`` [..., C, Dv], ``beta`` [..., C] ->
    (``U'`` [..., C, Dv], ``W`` [..., C, Dk], ``B`` [..., C, C], ``Q o exp G``
    and ``K o exp(G_C - G)`` [..., C, Dk], ``exp G_C`` [..., Dk, 1])."""
    c, dk = q.shape[-2:]
    ns = c // sub
    lead = q.shape[:-2]
    rows, cols = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    # G, inclusive: a product with a triangle of ones (a windowed sum walks
    # the chunk position by position on the TPU)
    cum = jnp.einsum("ts,...sd->...td", (rows >= cols).astype(g.dtype), g,
                     precision=_HIGHEST)
    by_sub = lead + (ns, sub, dk)
    # R_a: G just before sub-chunk a starts, and from there half way down
    # the sub-chunk's own decay, so that both factors lie about one
    starts = jnp.concatenate(
        [jnp.zeros(lead + (1, dk), cum.dtype),
         cum[..., sub - 1::sub, :][..., :ns - 1, :]], axis=-2)
    starts = starts + 0.5 * jax.lax.stop_gradient(
        cum.reshape(by_sub)[..., -1, :] - starts)
    near = jnp.exp(cum.reshape(by_sub) - starts[..., :, None, :])
    # keys as sub-chunk a's queries see them: [..., a, C, Dk]
    seen = jnp.arange(c) < (jnp.arange(ns)[:, None] + 1) * sub
    far = k[..., None, :, :] * jnp.exp(jnp.where(
        seen[..., None], starts[..., :, None, :] - cum[..., None, :, :],
        -jnp.inf))

    def against_keys(x):
        return jnp.einsum("...asd,...ajd->...asj", x.reshape(by_sub) * near,
                          far).reshape(lead + (c, c))

    a = jnp.where(rows > cols, against_keys(k), 0.0)
    b = jnp.where(rows >= cols, against_keys(q), 0.0)
    rhs = beta[..., None] * jnp.concatenate([v, k * jnp.exp(cum)], axis=-1)
    solved = _unit_lower_inverse(beta[..., None] * a) @ rhs
    last = cum[..., -1:, :]
    return (solved[..., :v.shape[-1]], solved[..., v.shape[-1]:], b,
            q * jnp.exp(cum), k * jnp.exp(last - cum),
            jnp.swapaxes(jnp.exp(last), -1, -2))


def _unit_lower_inverse(n: jax.Array) -> jax.Array:
    """``(I + N)^-1`` for ``N`` [..., C, C] strictly lower triangular, ``C`` a
    power of two: block forward substitution by doubling. With ``X`` the
    inverses of the diagonal blocks of size ``h`` (zero elsewhere) and ``N_h``
    the lower-left ``h x h`` corner of every diagonal block of size ``2h``,
    ``X - X N_h X`` holds the inverses of the blocks of size ``2h`` (``[[A, 0],
    [C, D]]^-1 = [[A^-1, 0], [-D^-1 C A^-1, D^-1]]``): from ``X = I`` up,
    ``log2 C`` levels of two products of whole ``C x C`` matrices at
    ``highest``, a mask a level and no block cut out or put back, subtracting
    nothing but what substitution row by row would. (XLA's own
    ``triangular_solve`` took 41 ms a call on the v5e, 617 ms of a 1,973 ms
    step, and the same levels written with blocks cut out and concatenated as
    much again in copies: PERF.md 6, PR 48.)"""
    c = n.shape[-1]
    rows, cols = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    inv = jnp.broadcast_to(jnp.eye(c, dtype=n.dtype), n.shape)
    h = 1
    while h < c:
        corner = (rows // (2 * h) == cols // (2 * h)) \
            & (rows // h % 2 == 1) & (cols // h % 2 == 0)
        inv = inv - jnp.einsum(
            "...ij,...jk,...kl->...il", inv, jnp.where(corner, n, 0.0), inv,
            precision=_HIGHEST)
        h *= 2
    return inv


def _across_chunks(u0, w, b, q_in, k_out, decay):
    """The walk over the chunks, the state zero before the first: ``u0`` [nc,
    ..., C, Dv], ``w``, ``q_in`` (``Q o exp G``), ``k_out`` (``K o exp(G_C -
    G)``) [nc, ..., C, Dk], ``b`` [nc, ..., C, C], ``decay`` (``exp G_C``) [nc,
    ..., Dk, 1] -> the outputs [nc, ..., C, Dv]."""
    def one_chunk(state, xs):
        u0c, wc, bc, qc, kc, dc = xs
        u = u0c - wc @ state
        return dc * state + jnp.swapaxes(kc, -1, -2) @ u, qc @ state + bc @ u

    state = jnp.zeros(decay.shape[1:-1] + (u0.shape[-1],), u0.dtype)
    return jax.lax.scan(one_chunk, state, (u0, w, b, q_in, k_out, decay))[1]


def kda_chunked(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                beta: jax.Array, chunk: int,
                interpret: Optional[bool] = None) -> jax.Array:
    """``q``, ``k``, ``g`` [B, S, H, Dk] (``g`` the log decay, not positive),
    ``v`` [B, S, H, Dv], ``beta`` [B, S, H] -> ``o`` [B, S, H, Dv], the state
    zero at each sequence's start. Any ``S``: the tail is padded with
    positions that neither decay (``g = 0``) nor write (``beta = 0``).
    ``chunk`` is a power of two (whole sub-chunks of :data:`SUB_CHUNK`, or one
    shorter sub-chunk). What needs no state is computed for all chunks at once
    (:func:`kda_mixer` bounds the working set by the heads it hands in); the
    walk over the chunks is five products a trip. ``interpret``: None unless
    the caller knows the arrays to live on ONE device, and then
    :func:`~multiverso_tpu.ops.pallas_interpret` of it: shapes the kernels
    take (:func:`~multiverso_tpu.ops.pallas_kda.kda_kernel_selected`) then
    walk the chunks with the state and a chunk's planes in VMEM
    (:func:`~multiverso_tpu.ops.pallas_kda.kda_scan`)."""
    bsz, s, h, _ = q.shape
    sub = min(SUB_CHUNK, chunk)
    if chunk & (chunk - 1):
        raise ValueError(f"a KDA chunk of {chunk} is not a power of two")
    pad = (-s) % chunk
    nc = (s + pad) // chunk
    if interpret is not None and kda_kernel_selected(
            chunk, q.shape[3], v.shape[3], h, q.dtype, k.dtype, v.dtype,
            g.dtype, beta.dtype):
        def whole(x):                                # -> [B, nc C, H D]
            return jnp.pad(x.reshape(bsz, s, -1), ((0, 0), (0, pad), (0, 0)))

        o = kda_scan(*(whole(x) for x in (q, k, v, g)), jnp.swapaxes(
            whole(beta).reshape(bsz, nc, chunk, h), 2, 3)[:, :, :, None],
            interpret)
        return o[:, :s].reshape(bsz, s, h, -1)

    def chunks(x):                                   # -> [nc, B, H, C, *]
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((bsz, nc, chunk) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 2, 3), 1, 0)

    o = _across_chunks(*_inside_chunks(
        *(chunks(x) for x in (q, k, v, g)), chunks(beta[..., None])[..., 0],
        sub))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)    # [B, nc, C, H, Dv]
    return o.reshape(bsz, nc * chunk, h, -1)[:, :s]


#: Positions times channels a group of heads at most: a block's heads are
#: independent between the input projections and the sum of the output
#: projection, and every array between the two is [positions, heads a group x
#: head_dim]. 8,192 positions of 32 heads of 128 are four groups of 8.
GROUP_ELEMENTS = 1 << 23
_BY_COLUMNS = ("wq", "wk", "wv", "wa", "wbeta", "wg")
_BY_ROWS = ("conv_q", "conv_k", "conv_v", "A_log", "dt_bias", "wo")
#: The input projections whose outputs the fused passes read side by side.
_JOINED = ("wq", "wk", "wv", "wa")


def _heads_a_group(positions: int, heads: int, head_dim: int) -> int:
    """The most heads (a divisor of ``heads``) whose arrays keep to
    :data:`GROUP_ELEMENTS`, at least one."""
    most = max(1, GROUP_ELEMENTS // (positions * head_dim))
    return max(g for g in range(1, heads + 1)
               if heads % g == 0 and g <= most)


def passes_kernel_selected(cfg, *dtypes) -> bool:
    """Whether a KDA block of ``cfg`` takes the passes on either side of its
    delta rule through :mod:`multiverso_tpu.ops.pallas_kda_passes`, as far as
    its widths and ``dtypes`` say."""
    return kda_passes_selected(cfg.kda_head_dim, cfg.kda_num_heads,
                               cfg.short_conv_kernel_size, *dtypes)


def kda_mixer(p: dict, n: jax.Array, cfg,
              scan_interpret: Optional[bool] = None) -> jax.Array:
    """``n`` [B, S, hidden] (already normed) -> the mixer's output
    (``scan_interpret``: :func:`kda_chunked`'s ``interpret``, and the
    ``interpret`` of the passes on either side of it). ``q``,
    ``k``, ``v``: a projection, a depthwise causal convolution of
    ``short_conv_kernel_size`` taps without bias, ``silu``; ``q`` and ``k``
    L2-normed a head, ``q`` times ``d_k ** -0.5``; NO rotary turn. The log
    decay a channel is :func:`kda_gate` of ``n W_a`` (a full matrix:
    ``no_kda_lora``), ``b = sigmoid(n W_beta)`` one a head. The heads' outputs
    take an RMSNorm a head (``o_norm``, one weight vector of ``kda_head_dim``)
    and a gate ``sigmoid(n W_g)`` one a head, then the output projection.

    Heads are independent from their columns of the input projections to
    their rows of ``W_o``: a sequence long enough that all heads' arrays
    would pass :data:`GROUP_ELEMENTS` takes them a group at a time, each
    group rematerialised in the backward pass, and sums the groups' outputs
    (4.6 GB of temporaries a block at 8,192 positions otherwise: PERF.md 6,
    PR 48).

    Where ``scan_interpret`` is not None, widths and leaves
    :func:`passes_kernel_selected` accepts join ``W_q | W_k | W_v | W_a``
    into ONE product (``w_in``, the group's columns side by side) whose
    output the fused passes of :mod:`multiverso_tpu.ops.pallas_kda_passes`
    read in place; every other call runs :func:`_heads_mixer`'s ``jax.numpy``
    lines."""
    h, d = cfg.kda_num_heads, cfg.kda_head_dim
    per = _heads_a_group(n.shape[1], h, d)
    fused = scan_interpret is not None and passes_kernel_selected(
        cfg, n.dtype, *(p[k].dtype for k in _BY_COLUMNS + _BY_ROWS
                        + ("o_norm",)))
    if per == h:
        if fused:
            p = dict(p, w_in=jnp.concatenate([p[k] for k in _JOINED], axis=1))
        return _heads_mixer(p, n, cfg, h, scan_interpret)
    groups = h // per
    by_group = {k: jnp.moveaxis(
        p[k].reshape(p[k].shape[0], groups, -1), 1, 0) for k in _BY_COLUMNS}
    by_group.update({k: p[k].reshape((groups, -1) + p[k].shape[1:])
                     for k in _BY_ROWS})
    by_group["o_norm"] = jnp.broadcast_to(p["o_norm"], (groups, d))
    if fused:
        by_group["w_in"] = jnp.concatenate(
            [by_group.pop(k) for k in _JOINED], axis=-1)
    return jnp.sum(jax.lax.map(
        jax.checkpoint(lambda pg: _heads_mixer(pg, n, cfg, per,
                                               scan_interpret)),
        by_group), axis=0)


def _heads_mixer(p: dict, n: jax.Array, cfg, h: int,
                 interpret: Optional[bool] = None) -> jax.Array:
    """:func:`kda_mixer` for ``h`` heads: ``p`` holds their columns of the
    input projections (with ``w_in``, the four joined ones' side by side:
    the fused passes' plane), their taps, rates and biases, and their rows
    of ``W_o``; the result is their share of the mixer's output."""
    bsz, s, _ = n.shape
    d = cfg.kda_head_dim
    fused = "w_in" in p
    beta = jax.nn.sigmoid(n @ p["wbeta"])
    if fused:
        u = n @ p["w_in"]
        with jax.named_scope("lm_kda_passes"):
            q, k, v, g = (x.reshape(bsz, s, h, d) for x in delta_rule_inputs(
                u, (p["conv_q"], p["conv_k"], p["conv_v"]), p["A_log"],
                p["dt_bias"], d, cfg.kda_lower_bound, interpret))
    else:
        projected = {w: n @ p[w] for w in _JOINED}
        with jax.named_scope("lm_kda_passes"):
            def heads(w, taps):
                return jax.nn.silu(causal_conv1d(projected[w], p[taps])
                                   ).reshape(bsz, s, h, d)

            q = l2_normalised(heads("wq", "conv_q")) * d ** -0.5
            k = l2_normalised(heads("wk", "conv_k"))
            v = heads("wv", "conv_v")
            g = kda_gate(projected["wa"].reshape(bsz, s, h, d), p["A_log"],
                         p["dt_bias"].reshape(h, d), cfg.kda_lower_bound)
    with jax.named_scope("lm_kda_scan"):
        o = kda_chunked(q, k, v, g, beta, cfg.kda_chunk, interpret)
    gate = n @ p["wg"]
    with jax.named_scope("lm_kda_passes"):
        y = gated_head_norm(o.reshape(bsz, s, h * d), gate, p["o_norm"],
                            cfg.norm_eps, interpret) if fused \
            else (rmsnorm(o, p["o_norm"], cfg.norm_eps)
                  * jax.nn.sigmoid(gate)[..., None]).reshape(bsz, s, h * d)
    return y @ p["wo"]
