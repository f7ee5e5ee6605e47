"""The chunked state-space scan (:func:`~multiverso_tpu.models.hybrid_lm.
mamba2.ssd_chunked`) as Pallas kernels: a chunk's decay planes live in VMEM
and never reach HBM.

Per head (``S`` is ``state x head_dim``; ``cum`` the running sum of ``dt a``
INSIDE a chunk of ``L`` positions, ``last`` its value at the chunk's end)::

    y_t = sum_{s <= t} exp(cum_t - cum_s) (C_t . B_s) xdt_s
          + exp(cum_t) C_t S                          (S: state at chunk start)
    S' = exp(last) S + sum_s exp(last - cum_s) B_s^T xdt_s

One grid step is one (sequence, group, chunk): it loads the group's ``B`` and
``C`` [L, N], its ``R`` heads of ``x`` side by side [L, R P], and ``dt`` and
``dt a`` [R, L]; takes the running sum and ``x dt`` itself; forms ``C B^T``
once; and walks the heads a lane tile (128 lanes: two heads of 64, or one of
128) at a time, so that no tensor is narrower than a lane tile. The chunk axis is the grid's innermost and sequential: the group's
state [N, R P] is carried in VMEM scratch, and neither what a chunk leaves
behind nor the state a chunk starts from is written by the forward pass.
Every ``exp`` is of a DIFFERENCE that is never positive (``cum`` falls along
a chunk), so a head that forgets fast (Lightning's steepest: 90 a chunk)
underflows to zero where a factored ``exp(cum_t) exp(-cum_s)`` would
overflow.

The backward pass keeps the inputs only (:func:`ssd_scan` is a
``jax.custom_vjp``): one forward walk writes each chunk's starting state,
one walk from the last chunk to the first recomputes the planes and carries
the state's gradient. ``cum``'s gradient inside a chunk is taken from ONE
plane, ``Q[t, s]`` added at ``t`` and taken off at ``s`` (as autodiff of the
``jax.numpy`` body has it), so that under the running sum that turns it into
``dt a``'s the pairs that do not straddle a position cancel to the bit; the
shorter ``dy . y - dxdt . xdt`` pairs two products rounded apart, and read
``dense_rel_gap`` 0.33 where this reads the body's 0.13 (PERF.md 6).

Matrix products run at the device's default precision for float32 operands
(one bfloat16 pass on the TPU, as XLA's; float32 under the interpreter, as
XLA's on the CPU), accumulating in float32; masks, ``exp`` and every sum are
float32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["scan_kernel_selected", "ssd_scan"]

_LANES = 128
_NN = ((1,), (0,))      # a @ b
_NT = ((1,), (1,))      # a @ b.T


def scan_kernel_selected(chunk: int, state: int, heads: int, width: int,
                         *dtypes) -> bool:
    """Whether a scan of ``heads`` heads a group, each ``width`` wide, can be
    the kernels', as far as its arrays say: float32, ``chunk`` and ``state``
    whole 128-lane tiles, and the group's heads side by side whole lane tiles
    with no head across a tile's edge. Its caller adds what only it knows:
    the arrays on ONE device (``HybridLM`` reads that off its leaves)."""
    tile = max(width, _LANES)
    return (all(np.dtype(d) == np.dtype(np.float32) for d in dtypes)
            and chunk % _LANES == 0 and state % _LANES == 0
            and tile % width == 0 and (heads * width) % tile == 0)


def _dot(a, b, dims, mxu):
    return jax.lax.dot_general(a.astype(mxu), b.astype(mxu), (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _sum_all(t):
    """[1, 1]."""
    return jnp.sum(jnp.sum(t, axis=1, keepdims=True), axis=0, keepdims=True)


def _running_sum(t, backwards: bool = False):
    """Along the lanes of ``t`` [R, L], by doubling strides: position ``i``
    takes the sum up to and with ``i`` (``backwards``: from ``i`` on)."""
    length = t.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, t.shape, 1)
    stride = 1
    while stride < length:
        t = t + (jnp.where(lane + stride < length,
                           pltpu.roll(t, length - stride, 1), 0.0)
                 if backwards else
                 jnp.where(lane >= stride, pltpu.roll(t, stride, 1), 0.0))
        stride *= 2
    return t


def _square(length: int):
    """Row and column index of a [L, L] plane."""
    return (jax.lax.broadcasted_iota(jnp.int32, (length, length), 0),
            jax.lax.broadcasted_iota(jnp.int32, (length, length), 1))


def _column(row, at, to):
    """[1, L] -> [L, 1]: the row laid on the diagonal and summed (exact)."""
    return jnp.sum(jnp.where(at == to, row, 0.0), axis=1, keepdims=True)


def _row(col, at, to):
    """[L, 1] -> [1, L]."""
    return jnp.sum(jnp.where(at == to, col, 0.0), axis=0, keepdims=True)


def _tiles(cum, dt, heads: int, width: int, at, to):
    """The group's lane tiles: per tile ``(lanes, rows, cols, sels, col_w,
    last_w, dt_w)`` from ``cum`` and ``dt`` [R, L] (``dt`` None: steps of
    one, and ``dt_w`` None): each of its heads' ``cum`` as a row [1, L] and
    as a column [L, 1], the lanes each head owns ([1, tile] bool, None where
    the tile is one head's), and ``cum``, its last value and ``dt`` spread
    over the heads' lanes ([L, tile], [1, tile], [L, tile]; [L, 1], [1, 1],
    [L, 1] where the tile is one head's)."""
    length = cum.shape[1]
    tile = max(width, _LANES)
    per_tile = tile // width
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
    sels = [None] if per_tile == 1 else [
        (lane >= j * width) & (lane < (j + 1) * width)
        for j in range(per_tile)]

    def spread(per_head):
        wide = per_head[0]
        for sel, one in zip(sels[1:], per_head[1:]):
            wide = jnp.where(sel, one, wide)
        return wide

    for i in range(heads * width // tile):
        mine = range(i * per_tile, (i + 1) * per_tile)
        rows = [cum[j:j + 1, :] for j in mine]
        cols = [_column(row, at, to) for row in rows]
        lasts = [jnp.sum(jnp.where(to[:1] == length - 1, row, 0.0), axis=1,
                         keepdims=True) for row in rows]
        yield (slice(i * tile, (i + 1) * tile), rows, cols, sels,
               spread(cols), spread(lasts), None if dt is None else spread(
                   [_column(dt[j:j + 1, :], at, to) for j in mine]))


def _own(sel, t, other):
    """``t`` on the head's own lanes, ``other`` (None: ``t`` too, for a later
    head to replace) elsewhere."""
    return t if sel is None or other is None else jnp.where(sel, t, other)


def _forward_kernel(*refs, heads: int, width: int, mxu, stepped: bool,
                    skipped: bool, states: bool):
    """One (sequence, group, chunk). ``stepped``: there is a ``dt`` (else the
    steps are one); ``skipped``: ``y`` takes ``skip x`` too; ``states``:
    write the state the chunk starts from and no ``y`` (the backward pass's
    first walk)."""
    refs = list(refs)
    state, out_ref = refs.pop(), refs.pop()
    c_ref = None if states else refs.pop(0)
    b_ref, x_ref, dta_ref = refs[:3]
    dt_ref = refs[3] if stepped else None
    skip_ref = refs[-1] if skipped and not states else None

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    b, cum = b_ref[0], _running_sum(dta_ref[0, 0, 0])
    bt = b.T.astype(mxu)
    at, to = _square(b.shape[0])
    if states:
        out_ref[0, 0, 0] = state[...]
    else:
        c = c_ref[0].astype(mxu)        # what is multiplied twice: cast once
        cb = _dot(c, b, _NT, mxu)                           # [t, s]
    for lanes, rows, cols, sels, col_w, last_w, dt_w in _tiles(
            cum, dt_ref[0, 0, 0] if stepped else None, heads, width, at, to):
        x, st = x_ref[0, :, lanes], state[:, lanes]
        xs = x * dt_w if stepped else x
        if not states:
            y, xs_m = None, xs.astype(mxu)
            for row, col, sel in zip(rows, cols, sels):
                plane = jnp.exp(jnp.where(at >= to, col - row, -jnp.inf)) * cb
                y = _own(sel, _dot(plane, xs_m, _NN, mxu), y)
            y = y + _dot(c, st, _NN, mxu) * jnp.exp(col_w)
            out_ref[0, :, lanes] = y + skip_ref[0, :, lanes] * x \
                if skipped else y
        state[:, lanes] = jnp.exp(last_w) * st + _dot(
            bt, xs * jnp.exp(last_w - col_w), _NN, mxu)


def _backward_kernel(*refs, heads: int, width: int, mxu, stepped: bool,
                     skipped: bool):
    """One (sequence, group, chunk), the chunks from the last to the first:
    ``dstate`` is the gradient of the state the chunk leaves behind."""
    refs = list(refs)
    dstate = refs.pop()
    c_ref, b_ref, x_ref, dta_ref = refs[:4]
    del refs[:4]
    dt_ref = refs.pop(0) if stepped else None
    skip_ref = refs.pop(0) if skipped else None
    dy_ref, before_ref, dx_ref, ddta_ref = refs[:4]
    del refs[:4]
    ddt_ref = refs.pop(0) if stepped else None
    db_ref, dc_ref = refs[:2]
    dskip_ref = refs[2] if skipped else None

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    cum = _running_sum(dta_ref[0, 0, 0])
    ct = c_ref[0].T.astype(mxu)
    # what is multiplied more than once is cast once
    c, b = c_ref[0].astype(mxu), b_ref[0].astype(mxu)
    length = c.shape[0]
    cb = _dot(c, b, _NT, mxu)                               # [t, s]
    cbt = _dot(b, c, _NT, mxu)                              # [s, t]
    at, to = _square(length)
    head = jax.lax.broadcasted_iota(jnp.int32, cum.shape, 0)
    dcb = jnp.zeros_like(cb)
    dc = jnp.zeros(c.shape, jnp.float32)
    db = jnp.zeros(b.shape, jnp.float32)
    dcum, ddt = jnp.zeros_like(cum), jnp.zeros_like(cum)
    n_head = 0
    for lanes, rows, cols, sels, col_w, last_w, dt_w in _tiles(
            cum, dt_ref[0, 0, 0] if stepped else None, heads, width, at, to):
        x, dys = x_ref[0, :, lanes], dy_ref[0, :, lanes]
        st, ds = before_ref[0, 0, 0, :, lanes], dstate[:, lanes]
        xs = x * dt_w if stepped else x
        xs_m, dys_m, st_m, ds_m = (t.astype(mxu) for t in (xs, dys, st, ds))
        dxs, inside = None, []
        for row, col, sel in zip(rows, cols, sels):
            decay = jnp.exp(jnp.where(at >= to, col - row, -jnp.inf))
            mine = decay * _dot(_own(sel, dys_m, 0.0), xs_m, _NT, mxu)
            dcb = dcb + mine
            # cum's gradient from inside the chunk: ONE number a (t, s), added
            # at t and taken off at s, so that what does not straddle a
            # position cancels to the bit in the running sum over it
            both = mine * cb
            inside.append((jnp.sum(both, axis=1, keepdims=True),
                           jnp.sum(both, axis=0, keepdims=True)))
            # the same plane with s down and t across
            decay_t = jnp.exp(jnp.where(to >= at, row - col, -jnp.inf))
            dxs = _own(sel, _dot(decay_t * cbt, dys_m, _NN, mxu), dxs)
        grow, shrink = jnp.exp(col_w), jnp.exp(last_w - col_w)
        dz = dys * grow
        came = dz * _dot(c, st_m, _NN, mxu)     # dy . (the state's share of y)
        dz = dz.astype(mxu)
        dc = dc + _dot(dz, st_m, _NT, mxu)
        xe = xs * shrink
        fed = _dot(b, ds_m, _NN, mxu)                       # [L, tile]
        dxs = dxs + fed * shrink
        db = db + _dot(xe, ds_m, _NT, mxu)
        dx = dxs * dt_w if stepped else dxs
        if skipped:
            dx = dx + skip_ref[0, :, lanes] * dys
            dskip_ref[0, 0, 0, :, lanes] = jnp.sum(dys * x, axis=0,
                                                   keepdims=True)
        dx_ref[0, :, lanes] = dx
        kept = jnp.exp(last_w) * ds
        dstate[:, lanes] = kept + _dot(ct, dz, _NN, mxu)
        left = fed * xe             # what the chunk's tokens leave, by exp(-cum)
        for (along, down), sel in zip(inside, sels):
            dlast = _sum_all(_own(sel, kept * st, 0.0)) + _sum_all(
                _own(sel, left, 0.0))
            dcol = along + jnp.sum(_own(sel, came - left, 0.0), axis=1,
                                   keepdims=True) + jnp.where(
                                       at[:, :1] == length - 1, dlast, 0.0)
            dcum = jnp.where(head == n_head, _row(dcol, at, to) - down, dcum)
            if stepped:
                ddt = jnp.where(head == n_head, _row(jnp.sum(
                    _own(sel, dxs * x, 0.0), axis=1, keepdims=True), at, to),
                    ddt)
            n_head += 1
    if stepped:
        ddt_ref[0, 0, 0] = ddt
    ddta_ref[0, 0, 0] = _running_sum(dcum, backwards=True)
    dc_ref[0] = dc + _dot(dcb, b, _NN, mxu)
    db_ref[0] = db + _dot(dcb.T, c, _NN, mxu)


def _specs(x, dta, b, backwards: bool):
    """(grid; the block of a [B, T, G N] array, of a [B, T, G R P] array, of
    a [B, nc, G, R, L] array, of the states [B, nc, G, N, R P], of the
    skip's [G, 1, R P] and of its gradient [B, nc, G, 1, R P]; a state's
    shape)."""
    bsz, nc, g, r, length = dta.shape
    n, rp = b.shape[2] // g, x.shape[2] // g

    def chunk(k):
        return nc - 1 - k if backwards else k

    def a_chunk(*block):
        return pl.BlockSpec((1, 1, 1) + block,
                            lambda i, j, k: (i, chunk(k), j, 0, 0))

    return ((bsz, g, nc),
            pl.BlockSpec((1, length, n), lambda i, j, k: (i, chunk(k), j)),
            pl.BlockSpec((1, length, rp), lambda i, j, k: (i, chunk(k), j)),
            a_chunk(r, length), a_chunk(n, rp),
            pl.BlockSpec((1, 1, rp), lambda i, j, k: (j, 0, 0)),
            a_chunk(1, rp), (n, rp))


def _call(kernel, grid, in_specs, out_specs, out_shape, scratch, interpret):
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM(scratch, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret)


def _shapes(dt, dta, skip, width: int, interpret: bool) -> dict:
    return dict(heads=dta.shape[3], width=width, stepped=dt is not None,
                skipped=skip is not None,
                mxu=jnp.float32 if interpret else jnp.bfloat16)


def _given(*pairs):
    """The first of each pair whose second is not None."""
    return [first for first, second in pairs if second is not None]


# Each walk is a ``jax.jit`` of its own: a step's blocks trace and lower a
# kernel once a shape, not once a block and pass.
@functools.partial(jax.jit, static_argnames=("width", "interpret"))
def _forward(x, dt, dta, b, c, skip, width: int, interpret: bool):
    grid, rows, wide, per_head, _, a_group, _, scratch = _specs(
        x, dta, b, False)
    return _call(
        functools.partial(_forward_kernel, states=False,
                          **_shapes(dt, dta, skip, width, interpret)),
        grid, [rows, rows, wide, per_head] + _given(
            (per_head, dt), (a_group, skip)), wide,
        jax.ShapeDtypeStruct(x.shape, x.dtype), scratch, interpret)(
            c, b, x, dta, *_given((dt, dt), (skip, skip)))


@functools.partial(jax.jit, static_argnames=("width", "interpret"))
def _backward(x, dt, dta, b, c, skip, dy, width: int, interpret: bool):
    """The gradients of ``x``, ``dt``, ``dta``, ``b``, ``c``, ``skip``: first
    the state each chunk starts from ([B, nc, G, N, R P]), then the walk
    back."""
    shape = jax.ShapeDtypeStruct
    grid, rows, wide, per_head, state, _, _, scratch = _specs(
        x, dta, b, False)
    shapes = _shapes(dt, dta, skip, width, interpret)
    before = _call(
        functools.partial(_forward_kernel, states=True, **shapes),
        grid, [rows, wide, per_head] + _given((per_head, dt)), state,
        shape(dta.shape[:3] + scratch, x.dtype), scratch, interpret)(
            b, x, dta, *_given((dt, dt)))
    grid, rows, wide, per_head, state, a_group, a_sum, scratch = _specs(
        x, dta, b, True)
    like = [shape(t.shape, t.dtype) for t in (x, dta, dt, b, c)
            if t is not None]
    out = _call(
        functools.partial(_backward_kernel, **shapes), grid,
        [rows, rows, wide, per_head] + _given(
            (per_head, dt), (a_group, skip)) + [wide, state],
        [wide, per_head] + _given((per_head, dt)) + [rows, rows] + _given(
            (a_sum, skip)),
        like + _given((shape(dta.shape[:3] + (1, x.shape[2] // dta.shape[2]),
                             x.dtype), skip)),
        scratch, interpret)(c, b, x, dta, *_given((dt, dt), (skip, skip)),
                            dy, before)
    dx, ddta, *out = out
    ddt = out.pop(0) if dt is not None else None
    db, dc, *dskip = out
    # a chunk's and sequence's shares of the skip's gradient
    return dx, ddt, ddta, db, dc, (jnp.sum(dskip[0], axis=(0, 1))
                                   if dskip else None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def ssd_scan(x: jax.Array, dt: Optional[jax.Array], dta: jax.Array,
             b: jax.Array, c: jax.Array, skip: Optional[jax.Array],
             width: int, interpret: bool) -> jax.Array:
    """``x`` [B, T, G R P] (a group's ``R`` heads of ``width`` side by
    side), ``dt`` and ``dta`` [B, T / L, G, R, L] (the steps, or None for
    steps of one, and ``dt a``: heads before the positions of a chunk of
    ``L``), ``b``/``c`` [B, T, G N], ``skip`` [G, 1, R P] or None (``y``
    takes ``skip x`` too) -> ``y`` like ``x``. For shapes
    :func:`scan_kernel_selected` accepts; ``interpret``:
    :func:`multiverso_tpu.ops.pallas_interpret` of the arrays' devices."""
    return _forward(x, dt, dta, b, c, skip, width, interpret)


def _ssd_scan_fwd(x, dt, dta, b, c, skip, width, interpret):
    return (_forward(x, dt, dta, b, c, skip, width, interpret),
            (x, dt, dta, b, c, skip))


def _ssd_scan_bwd(width, interpret, saved, dy):
    return _backward(*saved, dy, width, interpret)


ssd_scan.defvjp(_ssd_scan_fwd, _ssd_scan_bwd)
