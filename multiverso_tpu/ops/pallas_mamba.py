"""The memory-bound passes of a Mamba-2 block on either side of its scan
(:func:`~multiverso_tpu.models.hybrid_lm.mamba2.mamba2_mixer`) as Pallas
kernels that read ``in_proj``'s output WHERE IT LIES and write every result
once.

``u`` [B, S, W] is that output, ``z | xBC | dt`` side by side.

:func:`conv_silu_split`: the depthwise causal convolution over ``u``'s
columns ``[start, start + sum(widths))``, its bias, ``silu``, and the cut into
``len(widths)`` arrays (``x``, ``B``, ``C``) as ONE pass a part: grid
(sequence, column tile, sequence tile), a tile's columns taken out of ``u``
by the ``BlockSpec`` (no slice is made), the ``K - 1`` rows before a tile
from a second ``BlockSpec`` of 8 rows on the same array, zero at a sequence's
start. The backward pass takes the parts' gradients and the same columns,
forms the pre-activation again, and writes the columns' gradient once; the
weight's and the bias's gradients add up in their output block, which stays
in VMEM along the sequence axis (the grid's innermost, sequential).

:func:`gated_group_norm`: ``RMSNorm_w`` over groups of ``y * silu(z)`` with
``z`` read out of ``u``'s first columns, a group's lanes reduced in the
kernel; the backward pass reads ``dout``, ``y``, ``z`` and writes ``dy``,
``dz``, the weight's gradient added up the same way.

Both keep their INPUTS only (``jax.custom_vjp``). Either's gradient for ``u``
is its columns padded to ``W``: XLA fuses the sum of the two and of ``dt``'s
into the products that read ``in_proj``'s cotangent, which is never written.

A kernel walks its tile ``_CHUNK`` rows at a time (a rolled loop: what it
holds of them stays in vector registers, and its code stays small).
Everything is float32: operands, sums, ``silu`` and ``rsqrt`` as
:mod:`~multiverso_tpu.models.hybrid_lm.mamba2` writes them.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["mamba_passes_selected", "conv_silu_split", "gated_group_norm"]

_LANES = 128
_HALO = 8           # a sublane tile: what a tile takes of its neighbour
_ROWS = 512         # positions a grid step
_COLS = 512         # columns a grid step, at most
_CHUNK = 32         # rows a loop turn


def mamba_passes_selected(d_inner: int, bc_width: int, group_width: int,
                          taps: int, *dtypes) -> bool:
    """Whether a Mamba-2 block's convolution and gated norm can be the
    kernels', as far as its arrays say: float32, ``d_inner``, ``B``'s and
    ``C``'s width (groups x state) and the norm's group whole 128-lane tiles,
    and the taps before a position within one sublane tile. Its caller adds
    what only it knows: the arrays on ONE device."""
    return (all(np.dtype(d) == np.dtype(np.float32) for d in dtypes)
            and all(n > 0 and n % _LANES == 0
                    for n in (d_inner, bc_width, group_width))
            and d_inner % group_width == 0 and 1 <= taps <= _HALO)


def _tile(width: int, *offsets: int) -> int:
    """The widest column tile up to ``_COLS`` that cuts ``width`` and every
    offset into whole tiles."""
    return max(t for t in range(_LANES, _COLS + 1, _LANES)
               if width % t == 0 and all(o % t == 0 for o in offsets))


def _rows(length: int) -> int:
    """Positions a grid step: ``_ROWS``, or a shorter sequence rounded up to
    whole chunks."""
    return min(_ROWS, -(-length // _CHUNK) * _CHUNK)


def _fold(t):
    """[rows, cols] -> [8, cols]: the sublane tiles added."""
    return jnp.sum(t.reshape(t.shape[0] // _HALO, _HALO, t.shape[1]), axis=0)


def _walk(rows: int, body, carry):
    """``body(first row, carry)`` for each ``_CHUNK`` of a tile's ``rows``."""
    return jax.lax.fori_loop(
        0, rows // _CHUNK,
        lambda i, c: body(pl.multiple_of(i * _CHUNK, _CHUNK), c), carry)


def _stage(ext, x_ref, before_ref, after_ref=None, length: int = 0):
    """A tile between its neighbours' rows in scratch: 8 rows before it
    (zero at a sequence's start), the tile, and 8 rows after it where the
    caller has them; with a ``length`` that ends inside a tile, zero from
    there on (what a block holds past its array's edge is anything)."""
    rows = x_ref.shape[1]
    ext[0:_HALO] = jnp.where(pl.program_id(2) == 0, 0.0, before_ref[0])
    x = x_ref[0]
    if length % rows:
        row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
        x = jnp.where(pl.program_id(2) * rows + row < length, x, 0.0)
    ext[_HALO:_HALO + rows] = x
    if after_ref is not None:
        ext[_HALO + rows:] = after_ref[0]


def _shifted(ref, at, rows: int, taps: int, back: bool):
    """For each tap ``j`` the ``rows`` rows of ``ref`` from row ``at + 8 -
    (K-1-j)`` (``back``: what a convolution reads, ``ref`` holding the tile
    from row 8 on) or from row ``at + (K-1-j)`` (what its gradient reads):
    ONE load from the sublane tile's edge, cut at the taps' offsets (Mosaic
    takes a load at an unaligned sublane offset only where the offset is
    static, which inside a loop it is not)."""
    wide = ref[pl.ds(at, rows + _HALO), :]
    return [wide[_HALO - d:_HALO - d + rows] if back else wide[d:d + rows]
            for d in range(taps - 1, -1, -1)]


def _pre_activation(ext, at, rows: int, w, bias):
    """``bias + sum_j w_j x[t - (K-1) + j]`` for the ``rows`` rows from row
    ``at`` of the tile, and each tap's shifted rows."""
    shifted = _shifted(ext, at, rows, len(w), True)
    out = bias
    for tap, w_j in zip(shifted, w):
        out = tap * w_j if out is None else out + tap * w_j
    return out, shifted


def _conv_forward_kernel(*refs, taps: int, biased: bool):
    """One (sequence, column tile, sequence tile)."""
    x_ref, before_ref, w_ref = refs[:3]
    b_ref = refs[3] if biased else None
    out_ref, ext = refs[-2:]
    _stage(ext, x_ref, before_ref)
    w = [w_ref[j:j + 1, :] for j in range(taps)]
    bias = b_ref[...] if biased else None

    def chunk(at, _):
        pre, _ = _pre_activation(ext, at, _CHUNK, w, bias)
        out_ref[0, pl.ds(at, _CHUNK), :] = pre * jax.nn.sigmoid(pre)
        return 0

    _walk(x_ref.shape[1], chunk, 0)


def _conv_backward_kernel(*refs, taps: int, biased: bool, length: int):
    """One (sequence, column tile, sequence tile). ``g``, the gradient of the
    pre-activation, is taken for the tile's rows and the 8 after it (a row's
    input reaches ``K - 1`` rows on), zero from the sequence's end on."""
    x_ref, before_ref, after_ref, d_ref, d_after_ref, w_ref = refs[:6]
    b_ref = refs[6] if biased else None
    dx_ref, dwb_ref, ext, g_ext = refs[-4:]
    rows = x_ref.shape[1]
    first = pl.program_id(2) * rows
    _stage(ext, x_ref, before_ref, after_ref, length)
    w = [w_ref[j:j + 1, :] for j in range(taps)]
    bias = b_ref[...] if biased else None

    @pl.when(pl.program_id(2) == 0)
    def _():
        dwb_ref[...] = jnp.zeros_like(dwb_ref)

    def g_of(at, n, dout, inside: bool):
        pre, shifted = _pre_activation(ext, at, n, w, bias)
        sig = jax.nn.sigmoid(pre)
        g = dout * (sig * (1.0 + pre * (1.0 - sig)))
        if inside:          # every row before the sequence's end
            return g, shifted
        row = first + at + jax.lax.broadcasted_iota(jnp.int32, g.shape, 0)
        return jnp.where(row < length, g, 0.0), shifted

    def chunk(at, sums):
        g, shifted = g_of(at, _CHUNK, d_ref[0, pl.ds(at, _CHUNK), :],
                          length % rows == 0)
        g_ext[pl.ds(at, _CHUNK), :] = g
        return tuple(acc + _fold(g * t) for acc, t in zip(sums, shifted)) + (
            (sums[-1] + _fold(g),) if biased else ())

    zero = jnp.zeros((_HALO, x_ref.shape[2]), jnp.float32)
    sums = _walk(rows, chunk, (zero,) * (taps + biased))
    for j, acc in enumerate(sums):
        dwb_ref[0, j] += acc
    g_ext[rows:] = g_of(rows, _HALO, d_after_ref[0], False)[0]

    def chunk_dx(at, _):
        dx = None
        for tap, w_j in zip(_shifted(g_ext, at, _CHUNK, taps, False), w):
            dx = tap * w_j if dx is None else dx + tap * w_j
        dx_ref[0, pl.ds(at, _CHUNK), :] = dx
        return 0

    _walk(rows, chunk_dx, 0)


def _call(kernel, grid, in_specs, out_specs, out_shape, scratch, sequential,
          interpret):
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM(s, jnp.float32) for s in scratch],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel",
            "arbitrary" if sequential else "parallel")),
        interpret=interpret)


def _conv_specs(u, first: int, width: int, taps: int, biased: bool):
    """For the part of ``width`` columns from column ``first`` of ``u`` [B,
    S, W]: (grid; the block of a [B, S, width] array of the part's own; of
    the part's tile in ``u``; of the 8 rows before a tile in ``u``; the
    maker of the block of the 8 rows after a tile, in an array whose part
    starts at a given column; the blocks of the taps [K, width] and, with a
    bias, of the bias [1, width]; a tile's shape)."""
    bsz, length, _ = u.shape
    rows, cols = _rows(length), _tile(width, first)
    step, last = rows // _HALO, -(-length // _HALO) - 1

    def tile(at):
        return pl.BlockSpec((1, rows, cols),
                            lambda i, j, k: (i, k, at // cols + j))

    def after(at):
        return pl.BlockSpec((1, _HALO, cols), lambda i, j, k: (
            i, jnp.minimum((k + 1) * step, last), at // cols + j))

    before = pl.BlockSpec((1, _HALO, cols), lambda i, j, k: (
        i, jnp.maximum(k * step - 1, 0), first // cols + j))
    weights = [pl.BlockSpec((taps, cols), lambda i, j, k: (0, j))] + [
        pl.BlockSpec((1, cols), lambda i, j, k: (0, j))] * biased
    return ((bsz, width // cols, pl.cdiv(length, rows)), tile(0),
            tile(first), before, after, weights, (rows, cols))


def _parts(start: int, widths: Tuple[int, ...], w, b):
    """(first column in ``u``, width, the taps [K, width] and, with a bias,
    the bias [1, width]) a part."""
    at = 0
    for width in widths:
        yield start + at, width, (w.T[:, at:at + width],) + (
            () if b is None else (b[None, at:at + width],))
        at += width


# Each pass is a ``jax.jit`` of its own: a step's blocks trace and lower a
# kernel once a shape, not once a block and pass.
@functools.partial(jax.jit, static_argnames=("start", "widths", "interpret"))
def _conv_forward(u, w, b, start: int, widths: Tuple[int, ...],
                  interpret: bool):
    bsz, length, _ = u.shape
    taps = w.shape[1]
    out = []
    for first, width, weights in _parts(start, widths, w, b):
        grid, own, tile, before, _, taken, (rows, cols) = _conv_specs(
            u, first, width, taps, b is not None)
        out.append(_call(
            functools.partial(_conv_forward_kernel, taps=taps,
                              biased=b is not None),
            grid, [tile, before] + taken, own,
            jax.ShapeDtypeStruct((bsz, length, width), u.dtype),
            [(_HALO + rows, cols)], False, interpret)(u, u, *weights))
    return tuple(out)


@functools.partial(jax.jit, static_argnames=("start", "widths", "interpret"))
def _conv_backward(u, w, b, douts, start: int, widths: Tuple[int, ...],
                   interpret: bool):
    """The gradients of ``u`` (its convolved columns only, [B, S, C]), ``w``
    and ``b``."""
    bsz, length, _ = u.shape
    taps = w.shape[1]
    sums = taps + (b is not None)
    dx, dwb = [], []
    for (first, width, weights), dout in zip(_parts(start, widths, w, b),
                                             douts):
        grid, own, tile, before, after, taken, (rows, cols) = _conv_specs(
            u, first, width, taps, b is not None)
        part, acc = _call(
            functools.partial(_conv_backward_kernel, taps=taps,
                              biased=b is not None, length=length),
            grid, [tile, before, after(first), own, after(0)] + taken,
            [own, pl.BlockSpec((1, sums, _HALO, cols),
                               lambda i, j, k: (i, 0, 0, j))],
            [jax.ShapeDtypeStruct((bsz, length, width), u.dtype),
             jax.ShapeDtypeStruct((bsz, sums, _HALO, width), u.dtype)],
            [(_HALO + rows + _HALO, cols), (rows + _HALO, cols)], True,
            interpret)(u, u, u, dout, dout, *weights)
        dx.append(part)
        dwb.append(acc)
    # a sequence's and sublane's shares of the weights' gradients
    dwb = jnp.sum(jnp.concatenate(dwb, axis=-1), axis=(0, 2))   # [K (+ 1), C]
    return (jnp.concatenate(dx, axis=-1), dwb[:taps].T,
            None if b is None else dwb[taps])


def _padded(cols, start: int, width: int):
    """``cols`` at columns ``start`` on of ``width``, zeros beside them."""
    return jnp.pad(cols, ((0, 0), (0, 0),
                          (start, width - start - cols.shape[2])))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def conv_silu_split(u: jax.Array, w: jax.Array, b: Optional[jax.Array],
                    start: int, widths: Tuple[int, ...], interpret: bool
                    ) -> Tuple[jax.Array, ...]:
    """``u`` [B, S, W], ``w`` [C, K], ``b`` [C] or None -> ``silu`` of the
    causal convolution (:func:`~multiverso_tpu.models.hybrid_lm.mamba2.
    causal_conv1d`) of ``u[..., start:start + C]``, cut into arrays of
    ``widths`` (their sum ``C``). For shapes :func:`mamba_passes_selected`
    accepts, ``start`` a whole tile too; ``interpret``:
    :func:`multiverso_tpu.ops.pallas_interpret` of the arrays' devices."""
    return _conv_forward(u, w, b, start, widths, interpret)


def _conv_fwd(u, w, b, start, widths, interpret):
    return _conv_forward(u, w, b, start, widths, interpret), (u, w, b)


def _conv_bwd(start, widths, interpret, saved, douts):
    u, w, b = saved
    dx, dw, db = _conv_backward(u, w, b, tuple(douts), start, widths,
                                interpret)
    return _padded(dx, start, u.shape[2]), dw, db


conv_silu_split.defvjp(_conv_fwd, _conv_bwd)


def _gate(z):
    """``silu(z)`` and its derivative."""
    sig = jax.nn.sigmoid(z)
    return z * sig, sig * (1.0 + z * (1.0 - sig))


def _groups(cols: int, width: int):
    """The lanes of each group of ``width`` in a tile of ``cols``."""
    return [slice(at, at + width) for at in range(0, cols, width)]


def _norm_forward_kernel(y_ref, z_ref, w_ref, out_ref, *, width: int,
                         eps: float):
    """One (sequence, column tile, sequence tile); a tile is whole groups of
    ``width`` lanes."""
    def chunk(at, _):
        rows = pl.ds(at, _CHUNK)
        for lanes in _groups(y_ref.shape[2], width):
            v = y_ref[0, rows, lanes] * _gate(z_ref[0, rows, lanes])[0]
            scale = jax.lax.rsqrt(
                jnp.sum(v * v, axis=1, keepdims=True) / width + eps)
            out_ref[0, rows, lanes] = v * scale * w_ref[:, lanes]
        return 0

    _walk(y_ref.shape[1], chunk, 0)


def _norm_backward_kernel(d_ref, y_ref, z_ref, w_ref, dy_ref, dz_ref, dw_ref,
                          *, width: int, eps: float, length: int):
    cols = y_ref.shape[2]
    first = pl.program_id(2) * y_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def chunk(at, sums):
        rows = pl.ds(at, _CHUNK)
        live = None if length % y_ref.shape[1] == 0 else (
            first + at + jax.lax.broadcasted_iota(
                jnp.int32, (_CHUNK, 1), 0) < length)
        out = []
        for lanes, acc in zip(_groups(cols, width), sums):
            y, dout = y_ref[0, rows, lanes], d_ref[0, rows, lanes]
            gate, dgate = _gate(z_ref[0, rows, lanes])
            v = y * gate
            scale = jax.lax.rsqrt(
                jnp.sum(v * v, axis=1, keepdims=True) / width + eps)
            dn = dout * w_ref[:, lanes]
            dv = scale * dn - v * (scale * scale * scale * (
                jnp.sum(dn * v, axis=1, keepdims=True) / width))
            dy_ref[0, rows, lanes] = dv * gate
            dz_ref[0, rows, lanes] = dv * y * dgate
            dw = dout * v * scale
            out.append(acc + _fold(dw if live is None else jnp.where(
                live, dw, 0.0)))
        return tuple(out)

    zero = jnp.zeros((_HALO, width), jnp.float32)
    sums = _walk(y_ref.shape[1], chunk, (zero,) * (cols // width))
    for lanes, acc in zip(_groups(cols, width), sums):
        dw_ref[0, :, lanes] += acc


def _norm_specs(y, groups: int):
    """(grid, a tile of ``y`` (and of ``z`` in ``u``), the weight's)."""
    bsz, length, d_inner = y.shape
    width = d_inner // groups
    cols = max(c for c in range(width, max(width, _COLS) + 1, width)
               if d_inner % c == 0)
    rows = _rows(length)
    return ((bsz, d_inner // cols, pl.cdiv(length, rows)),
            pl.BlockSpec((1, rows, cols), lambda i, j, k: (i, k, j)),
            pl.BlockSpec((1, cols), lambda i, j, k: (0, j)))


@functools.partial(jax.jit, static_argnames=("groups", "eps", "interpret"))
def _norm_forward(y, u, w, groups: int, eps: float, interpret: bool):
    grid, tile, weight = _norm_specs(y, groups)
    return _call(
        functools.partial(_norm_forward_kernel, width=y.shape[2] // groups,
                          eps=eps),
        grid, [tile, tile, weight], tile,
        jax.ShapeDtypeStruct(y.shape, y.dtype), [], False, interpret)(
            y, u, w[None])


@functools.partial(jax.jit, static_argnames=("groups", "eps", "interpret"))
def _norm_backward(y, u, w, dout, groups: int, eps: float, interpret: bool):
    """The gradients of ``y``, ``z`` ([B, S, d_inner]) and ``w``."""
    grid, tile, weight = _norm_specs(y, groups)
    like = jax.ShapeDtypeStruct(y.shape, y.dtype)
    dy, dz, dw = _call(
        functools.partial(_norm_backward_kernel, width=y.shape[2] // groups,
                          eps=eps, length=y.shape[1]),
        grid, [tile, tile, tile, weight],
        [tile, tile, pl.BlockSpec((1, _HALO, tile.block_shape[2]),
                                  lambda i, j, k: (i, 0, j))],
        [like, like, jax.ShapeDtypeStruct(
            (y.shape[0], _HALO, y.shape[2]), y.dtype)],
        [], True, interpret)(dout, y, u, w[None])
    return dy, dz, jnp.sum(dw, axis=(0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def gated_group_norm(y: jax.Array, u: jax.Array, w: jax.Array, groups: int,
                     eps: float, interpret: bool) -> jax.Array:
    """:func:`~multiverso_tpu.models.hybrid_lm.mamba2.gated_group_rmsnorm` of
    ``y`` [B, S, d_inner] gated by ``z = u[..., :d_inner]``, read out of
    ``u`` [B, S, W] in place. For shapes :func:`mamba_passes_selected`
    accepts."""
    return _norm_forward(y, u, w, groups, eps, interpret)


def _norm_fwd(y, u, w, groups, eps, interpret):
    return _norm_forward(y, u, w, groups, eps, interpret), (y, u, w)


def _norm_bwd(groups, eps, interpret, saved, dout):
    y, u, w = saved
    dy, dz, dw = _norm_backward(y, u, w, dout, groups, eps, interpret)
    return dy, _padded(dz, 0, u.shape[2]), dw


gated_group_norm.defvjp(_norm_fwd, _norm_bwd)
