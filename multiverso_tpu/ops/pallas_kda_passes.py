"""The memory-bound passes of a KDA block on either side of its delta rule
(:func:`~multiverso_tpu.models.hybrid_lm.kda.kda_mixer`) as Pallas kernels
that read ONE projection's output WHERE IT LIES and write every result once,
as :mod:`~multiverso_tpu.ops.pallas_mamba` does for a Mamba-2 block (whose
parts these kernels are built from).

``u`` [B, S, 4 H D] is ``n @ [W_q | W_k | W_v | W_a]`` for ``H`` heads of
``D``, the four parts side by side.

:func:`delta_rule_inputs`: a pass a part, each reading its columns of ``u``
through its ``BlockSpec`` (no slice is made). For ``q``, ``k``, ``v`` the
depthwise causal convolution without bias and ``silu``, the ``K - 1`` rows
before a tile from a second ``BlockSpec`` of 8 rows on the same array; for
``q`` and ``k`` the L2 norm over each head's lanes in the same pass (a head
is whole lane tiles: the reduction never leaves the kernel), ``q`` times
``D ** -0.5``; for the ``W_a`` columns the gate ``bound * sigmoid(exp(A_log)
* (x + dt_bias))``. Outputs [B, S, H D]: the layout the delta rule's kernels
take. The backward passes read the results' gradients and the same columns,
form the pre-activations again and write ``u``'s gradient ONCE: each writes
its part's columns of one [B, S, 4 H D] array, handed on from pass to pass
(``input_output_aliases``: no part is joined to another by a copy); the
taps', the rates' and ``dt_bias``'s gradients add up in an output block that
stays in VMEM along the sequence axis (the grid's innermost, sequential).

:func:`gated_head_norm`: ``RMSNorm_w`` over each head of the delta rule's
output times ``sigmoid`` of the head's gate (``n W_g``, [B, S, H]), one pass;
backward one pass that writes the output's and the gate's gradients, the
weight's added up the same way. A tile is all ``H D`` columns, so that the
gate's block is its whole width.

Both keep their INPUTS only (``jax.custom_vjp``). Everything is float32:
operands, sums, ``silu``, ``sigmoid`` and ``rsqrt`` as
:mod:`~multiverso_tpu.models.hybrid_lm.kda` writes them.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from multiverso_tpu.ops import pallas_mamba
from multiverso_tpu.ops.pallas_mamba import (_CHUNK, _COLS, _HALO, _LANES,
                                             _fold, _gate, _groups,
                                             _pre_activation, _rows,
                                             _shifted, _stage, _walk)

__all__ = ["kda_passes_selected", "delta_rule_inputs", "gated_head_norm"]

_L2_EPS = 1e-6      # ``kda.l2_normalised``'s


def kda_passes_selected(head_dim: int, heads: int, taps: int,
                        *dtypes) -> bool:
    """Whether the passes round a delta rule of ``heads`` heads of
    ``head_dim`` can be the kernels', as far as its arrays say: float32, a
    head whole 128-lane tiles and at most a column tile, and the taps before
    a position within one sublane tile. Its caller adds what only it knows:
    the arrays on ONE device."""
    return (all(np.dtype(d) == np.dtype(np.float32) for d in dtypes)
            and 0 < head_dim <= _COLS and head_dim % _LANES == 0
            and heads >= 1 and 1 <= taps <= _HALO)


def _call(kernel, grid, in_specs, out_specs, out_shape, scratch, sequential,
          interpret, aliases=None):
    """``pallas_mamba._call`` with ``aliases`` (an input handed on as an
    output, which the kernel completes and does not read)."""
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, input_output_aliases=aliases or {},
        scratch_shapes=[pltpu.VMEM(s, jnp.float32) for s in scratch],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel",
            "arbitrary" if sequential else "parallel")),
        interpret=interpret)


# -- before the delta rule ------------------------------------------------------
def _heads_of(t, width: int):
    """Each head's lanes of a tile's rows ``t`` [rows, cols]."""
    return [t[:, lanes] for lanes in _groups(t.shape[1], width)]


def _side_by_side(heads):
    return heads[0] if len(heads) == 1 else jnp.concatenate(heads, axis=1)


def _l2(s, width: int, scale: float, ds=None):
    """``s`` [rows, cols], each head's ``width`` lanes over their L2 norm
    (``kda.l2_normalised``) times ``scale``; with ``ds``, that result's
    gradient, the gradient of ``s``."""
    out = []
    heads = _heads_of(s, width)
    for x, d in zip(heads, heads if ds is None else _heads_of(ds, width)):
        square = jnp.sum(x * x, axis=1, keepdims=True)
        r = jax.lax.rsqrt(jnp.maximum(square, _L2_EPS ** 2))
        if ds is None:
            out.append(x * r * scale)
            continue
        d = d * scale
        # under ``eps`` the norm is the constant's: no gradient through it
        out.append(r * d - jnp.where(
            square > _L2_EPS ** 2,
            x * (r * r * r * jnp.sum(d * x, axis=1, keepdims=True)), 0.0))
    return _side_by_side(out)


def _heads_forward_kernel(x_ref, before_ref, w_ref, out_ref, ext, *,
                          taps: int, width: int, scale):
    """One (sequence, column tile, sequence tile) of ``q``, ``k`` (``scale``
    a number) or ``v`` (None: no norm)."""
    _stage(ext, x_ref, before_ref)
    w = [w_ref[j:j + 1, :] for j in range(taps)]

    def chunk(at, _):
        s = _gate(_pre_activation(ext, at, _CHUNK, w, None)[0])[0]
        out_ref[0, pl.ds(at, _CHUNK), :] = s if scale is None else _l2(
            s, width, scale)
        return 0

    _walk(x_ref.shape[1], chunk, 0)


def _heads_backward_kernel(*refs, taps: int, width: int, scale, length: int):
    """``pallas_mamba._conv_backward_kernel`` with the norm's gradient before
    ``silu``'s. (Where a ``du`` is handed on it is the last input, unread.)"""
    x_ref, before_ref, after_ref, d_ref, d_after_ref, w_ref = refs[:6]
    dx_ref, dw_ref, ext, g_ext = refs[-4:]
    rows = x_ref.shape[1]
    first = pl.program_id(2) * rows
    _stage(ext, x_ref, before_ref, after_ref, length)
    w = [w_ref[j:j + 1, :] for j in range(taps)]

    @pl.when(pl.program_id(2) == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def g_of(at, n, dout, inside: bool):
        pre, shifted = _pre_activation(ext, at, n, w, None)
        s, ds = _gate(pre)
        g = ds * (dout if scale is None else _l2(s, width, scale, dout))
        if inside:          # every row before the sequence's end
            return g, shifted
        row = first + at + jax.lax.broadcasted_iota(jnp.int32, g.shape, 0)
        return jnp.where(row < length, g, 0.0), shifted

    def chunk(at, sums):
        g, shifted = g_of(at, _CHUNK, d_ref[0, pl.ds(at, _CHUNK), :],
                          length % rows == 0)
        g_ext[pl.ds(at, _CHUNK), :] = g
        return tuple(acc + _fold(g * t) for acc, t in zip(sums, shifted))

    zero = jnp.zeros((_HALO, x_ref.shape[2]), jnp.float32)
    for j, acc in enumerate(_walk(rows, chunk, (zero,) * taps)):
        dw_ref[0, j] += acc
    g_ext[rows:] = g_of(rows, _HALO, d_after_ref[0], False)[0]

    def chunk_dx(at, _):
        dx = None
        for tap, w_j in zip(_shifted(g_ext, at, _CHUNK, taps, False), w):
            dx = tap * w_j if dx is None else dx + tap * w_j
        dx_ref[0, pl.ds(at, _CHUNK), :] = dx
        return 0

    _walk(rows, chunk_dx, 0)


def _decay_forward_kernel(x_ref, rate_ref, bias_ref, out_ref, *,
                          bound: float):
    """``kda.kda_gate`` of a tile: ``rate`` is ``exp(A_log)`` a column."""
    def chunk(at, _):
        rows = pl.ds(at, _CHUNK)
        out_ref[0, rows, :] = bound * jax.nn.sigmoid(
            rate_ref[...] * (x_ref[0, rows, :] + bias_ref[...]))
        return 0

    _walk(x_ref.shape[1], chunk, 0)


def _decay_backward_kernel(*refs, bound: float, length: int):
    """``dx`` and, added up along the sequence, ``sum dz y`` (the rate's
    gradient) and ``sum dz`` (``dt_bias``'s over the rate), ``z = rate y``,
    ``y = x + dt_bias``. (A ``du`` handed on is the last input, unread.)"""
    x_ref, d_ref, rate_ref, bias_ref = refs[:4]
    dx_ref, sums_ref = refs[-2:]
    rows = x_ref.shape[1]
    first = pl.program_id(2) * rows

    @pl.when(pl.program_id(2) == 0)
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    def chunk(at, sums):
        at_rows = pl.ds(at, _CHUNK)
        y = x_ref[0, at_rows, :] + bias_ref[...]
        sig = jax.nn.sigmoid(rate_ref[...] * y)
        dz = d_ref[0, at_rows, :] * (bound * sig * (1.0 - sig))
        dzy = dz * y
        if length % rows:
            live = first + at + jax.lax.broadcasted_iota(
                jnp.int32, (_CHUNK, 1), 0) < length
            dz, dzy = jnp.where(live, dz, 0.0), jnp.where(live, dzy, 0.0)
        dx_ref[0, at_rows, :] = dz * rate_ref[...]
        return sums[0] + _fold(dzy), sums[1] + _fold(dz)

    zero = jnp.zeros((_HALO, x_ref.shape[2]), jnp.float32)
    for j, acc in enumerate(_walk(rows, chunk, (zero, zero))):
        sums_ref[0, j] += acc


def _part_specs(u, first: int, width: int, head_dim: int):
    """``pallas_mamba._conv_specs`` for the part of ``width`` columns from
    column ``first`` (a multiple of ``width``) of ``u`` [B, S, W], a column
    tile whole heads of ``head_dim``: (grid; the block of a [B, S, width]
    array of the part's own; the maker of a tile's block in an array whose
    part starts at a given column; of the 8 rows before a tile in ``u``; the
    maker of the block of the 8 rows after a tile; the block of a [n, width]
    array of numbers a column; a tile's shape)."""
    bsz, length, _ = u.shape
    rows = _rows(length)
    cols = max(t for t in range(head_dim, _COLS + 1, head_dim)
               if width % t == 0)
    step, last = rows // _HALO, -(-length // _HALO) - 1

    def tile(at):
        return pl.BlockSpec((1, rows, cols),
                            lambda i, j, k: (i, k, at // cols + j))

    def after(at):
        return pl.BlockSpec((1, _HALO, cols), lambda i, j, k: (
            i, jnp.minimum((k + 1) * step, last), at // cols + j))

    def a_column(n):
        return pl.BlockSpec((n, cols), lambda i, j, k: (0, j))

    before = pl.BlockSpec((1, _HALO, cols), lambda i, j, k: (
        i, jnp.maximum(k * step - 1, 0), first // cols + j))
    return ((bsz, width // cols, pl.cdiv(length, rows)), tile(0), tile,
            before, after, a_column, (rows, cols))


def _scales(head_dim: int):
    """``q``'s, ``k``'s and ``v``'s: a norm's factor, or None without one."""
    return head_dim ** -0.5, 1.0, None


def _by_column(a_log, dt_bias):
    """``exp(A_log)`` a column and ``dt_bias``, [1, H D] each."""
    return (jnp.repeat(jnp.exp(a_log), dt_bias.shape[0] // a_log.shape[0])[
        None], dt_bias[None])


# Each pass is a ``jax.jit`` of its own: a step's blocks trace and lower a
# kernel once a shape, not once a block and pass.
@functools.partial(jax.jit,
                   static_argnames=("head_dim", "bound", "interpret"))
def _inputs_forward(u, taps, a_log, dt_bias, head_dim: int, bound: float,
                    interpret: bool):
    bsz, length, total = u.shape
    width = total // 4
    like = jax.ShapeDtypeStruct((bsz, length, width), u.dtype)
    out = []
    for part, (w, scale) in enumerate(zip(taps, _scales(head_dim))):
        grid, own, tile, before, _, a_column, (rows, cols) = _part_specs(
            u, part * width, width, head_dim)
        out.append(_call(
            functools.partial(_heads_forward_kernel, taps=w.shape[1],
                              width=head_dim, scale=scale),
            grid, [tile(part * width), before, a_column(w.shape[1])], own,
            like, [(_HALO + rows, cols)], False, interpret)(u, u, w.T))
    grid, own, tile, _, _, a_column, _ = _part_specs(
        u, 3 * width, width, head_dim)
    out.append(_call(
        functools.partial(_decay_forward_kernel, bound=bound), grid,
        [tile(3 * width), a_column(1), a_column(1)], own, like, [], False,
        interpret)(u, *_by_column(a_log, dt_bias)))
    return tuple(out)


@functools.partial(jax.jit,
                   static_argnames=("head_dim", "bound", "interpret"))
def _inputs_backward(u, taps, a_log, dt_bias, douts, head_dim: int,
                     bound: float, interpret: bool):
    """The gradients of ``u``, the three parts' taps, ``A_log`` and
    ``dt_bias``."""
    bsz, length, total = u.shape
    width = total // 4
    heads = a_log.shape[0]
    whole = jax.ShapeDtypeStruct(u.shape, u.dtype)
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    du, dtaps = None, []

    def handed_on(n_inputs: int):
        """What a pass after the first adds to its call: ``du`` so far."""
        return ([] if du is None else [anywhere],
                () if du is None else (du,),
                None if du is None else {n_inputs: 0})

    for part, (w, scale, dout) in enumerate(zip(taps, _scales(head_dim),
                                                douts)):
        n = w.shape[1]
        grid, own, tile, before, after, a_column, (rows, cols) = _part_specs(
            u, part * width, width, head_dim)
        spec, arg, aliases = handed_on(6)
        du, dw = _call(
            functools.partial(_heads_backward_kernel, taps=n, width=head_dim,
                              scale=scale, length=length),
            grid, [tile(part * width), before, after(part * width), own,
                   after(0), a_column(n)] + spec,
            [tile(part * width), pl.BlockSpec(
                (1, n, _HALO, cols), lambda i, j, k: (i, 0, 0, j))],
            [whole, jax.ShapeDtypeStruct((bsz, n, _HALO, width), u.dtype)],
            [(_HALO + rows + _HALO, cols), (rows + _HALO, cols)], True,
            interpret, aliases)(u, u, u, dout, dout, w.T, *arg)
        # a sequence's and sublane's shares of the taps' gradients
        dtaps.append(jnp.sum(dw, axis=(0, 2)).T)
    grid, own, tile, _, _, a_column, (rows, cols) = _part_specs(
        u, 3 * width, width, head_dim)
    rate, bias = _by_column(a_log, dt_bias)
    spec, arg, aliases = handed_on(4)
    du, sums = _call(
        functools.partial(_decay_backward_kernel, bound=bound,
                          length=length),
        grid, [tile(3 * width), own, a_column(1), a_column(1)] + spec,
        [tile(3 * width), pl.BlockSpec((1, 2, _HALO, cols),
                                       lambda i, j, k: (i, 0, 0, j))],
        [whole, jax.ShapeDtypeStruct((bsz, 2, _HALO, width), u.dtype)], [],
        True, interpret, aliases)(u, douts[3], rate, bias, *arg)
    d_rate, d_bias = jnp.sum(sums, axis=(0, 2))                 # [H D] each
    return (du, tuple(dtaps),
            jnp.sum(d_rate.reshape(heads, -1), axis=1) * jnp.exp(a_log),
            d_bias * rate[0])


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def delta_rule_inputs(u: jax.Array, taps: Tuple[jax.Array, ...],
                      a_log: jax.Array, dt_bias: jax.Array, head_dim: int,
                      bound: float, interpret: bool
                      ) -> Tuple[jax.Array, ...]:
    """``u`` [B, S, 4 H D] (``n @ [W_q | W_k | W_v | W_a]``), ``taps`` three
    [H D, K] (``q``'s, ``k``'s, ``v``'s), ``a_log`` [H], ``dt_bias`` [H D] ->
    ``q``, ``k``, ``v`` and the log decay ``g``, [B, S, H D] each, as
    :func:`~multiverso_tpu.models.hybrid_lm.kda.kda_mixer` defines them. For
    shapes :func:`kda_passes_selected` accepts; ``interpret``:
    :func:`multiverso_tpu.ops.pallas_interpret` of the arrays' devices."""
    return _inputs_forward(u, taps, a_log, dt_bias, head_dim, bound,
                           interpret)


def _inputs_fwd(u, taps, a_log, dt_bias, head_dim, bound, interpret):
    return (_inputs_forward(u, taps, a_log, dt_bias, head_dim, bound,
                            interpret), (u, taps, a_log, dt_bias))


def _inputs_bwd(head_dim, bound, interpret, saved, douts):
    return _inputs_backward(*saved, tuple(douts), head_dim, bound, interpret)


delta_rule_inputs.defvjp(_inputs_fwd, _inputs_bwd)


# -- after the delta rule -------------------------------------------------------
def _a_head(t, j: int):
    """Column ``j`` of ``t`` [rows, H] as [rows, 1] (a select and a sum: a
    lane is not cut out of a tile)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, t.shape, 1)
    return jnp.sum(jnp.where(lane == j, t, 0.0), axis=1, keepdims=True)


def _out_forward_kernel(o_ref, gate_ref, w_ref, y_ref, *, width: int,
                        eps: float):
    """One (sequence, all columns, sequence tile)."""
    def chunk(at, _):
        rows = pl.ds(at, _CHUNK)
        sig = jax.nn.sigmoid(gate_ref[0, rows, :])
        for j, lanes in enumerate(_groups(o_ref.shape[2], width)):
            o = o_ref[0, rows, lanes]
            scale = jax.lax.rsqrt(
                jnp.sum(o * o, axis=1, keepdims=True) / width + eps)
            y_ref[0, rows, lanes] = o * scale * w_ref[...] * _a_head(sig, j)
        return 0

    _walk(o_ref.shape[1], chunk, 0)


def _out_backward_kernel(d_ref, o_ref, gate_ref, w_ref, do_ref, dgate_ref,
                         dw_ref, *, width: int, eps: float, length: int):
    first = pl.program_id(2) * o_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def chunk(at, acc):
        rows = pl.ds(at, _CHUNK)
        live = None if length % o_ref.shape[1] == 0 else (
            first + at + jax.lax.broadcasted_iota(
                jnp.int32, (_CHUNK, 1), 0) < length)
        sig = jax.nn.sigmoid(gate_ref[0, rows, :])
        lane = jax.lax.broadcasted_iota(jnp.int32, sig.shape, 1)
        dsig = jnp.zeros_like(sig)
        for j, lanes in enumerate(_groups(o_ref.shape[2], width)):
            o, dout = o_ref[0, rows, lanes], d_ref[0, rows, lanes]
            scale = jax.lax.rsqrt(
                jnp.sum(o * o, axis=1, keepdims=True) / width + eps)
            unit = o * scale
            gated = dout * _a_head(sig, j)
            dn = gated * w_ref[...]
            do_ref[0, rows, lanes] = scale * dn - o * (
                scale * scale * scale * (
                    jnp.sum(dn * o, axis=1, keepdims=True) / width))
            dsig = jnp.where(lane == j, jnp.sum(
                dout * unit * w_ref[...], axis=1, keepdims=True), dsig)
            dw = gated * unit
            acc = acc + _fold(dw if live is None else jnp.where(
                live, dw, 0.0))
        dgate_ref[0, rows, :] = dsig * sig * (1.0 - sig)
        return acc

    dw_ref[0] += _walk(o_ref.shape[1], chunk,
                       jnp.zeros((_HALO, width), jnp.float32))


def _out_specs(o, heads: int):
    """(grid, a tile of ``o``: ALL its columns, the gate's block, the
    weight's): as many rows a step as keep a tile to ``_ROWS x _COLS``."""
    bsz, length, width = o.shape
    rows = min(_rows(length), max(
        _CHUNK, pallas_mamba._ROWS * _COLS // width // _CHUNK * _CHUNK))
    return ((bsz, 1, pl.cdiv(length, rows)),
            pl.BlockSpec((1, rows, width), lambda i, j, k: (i, k, 0)),
            pl.BlockSpec((1, rows, heads), lambda i, j, k: (i, k, 0)),
            pl.BlockSpec((1, width // heads), lambda i, j, k: (0, 0)))


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _out_forward(o, gate, w, eps: float, interpret: bool):
    grid, tile, a_head, weight = _out_specs(o, gate.shape[2])
    return _call(
        functools.partial(_out_forward_kernel, width=w.shape[0], eps=eps),
        grid, [tile, a_head, weight], tile,
        jax.ShapeDtypeStruct(o.shape, o.dtype), [], False, interpret)(
            o, gate, w[None])


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _out_backward(o, gate, w, dout, eps: float, interpret: bool):
    """The gradients of ``o``, ``gate`` and ``w``."""
    grid, tile, a_head, weight = _out_specs(o, gate.shape[2])
    width = w.shape[0]
    do, dgate, dw = _call(
        functools.partial(_out_backward_kernel, width=width, eps=eps,
                          length=o.shape[1]),
        grid, [tile, tile, a_head, weight],
        [tile, a_head, pl.BlockSpec((1, _HALO, width),
                                    lambda i, j, k: (i, 0, 0))],
        [jax.ShapeDtypeStruct(o.shape, o.dtype),
         jax.ShapeDtypeStruct(gate.shape, o.dtype),
         jax.ShapeDtypeStruct((o.shape[0], _HALO, width), o.dtype)],
        [], True, interpret)(dout, o, gate, w[None])
    return do, dgate, jnp.sum(dw, axis=(0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def gated_head_norm(o: jax.Array, gate: jax.Array, w: jax.Array, eps: float,
                    interpret: bool) -> jax.Array:
    """``rmsnorm(o, w, eps)`` a head of ``o`` [B, S, H D] (``w`` [D]) times
    ``sigmoid(gate)`` (``gate`` [B, S, H]), as
    :func:`~multiverso_tpu.models.hybrid_lm.kda.kda_mixer` ends. For shapes
    :func:`kda_passes_selected` accepts."""
    return _out_forward(o, gate, w, eps, interpret)


def _out_fwd(o, gate, w, eps, interpret):
    return _out_forward(o, gate, w, eps, interpret), (o, gate, w)


def _out_bwd(eps, interpret, saved, dout):
    return _out_backward(*saved, dout, eps, interpret)


gated_head_norm.defvjp(_out_fwd, _out_bwd)
