"""The plain causal walk of :mod:`multiverso_tpu.models.hybrid_lm.attention`
(no remote key set, no per-query mask) as Pallas kernels: a pair of tiles'
scores, probabilities and their gradients live in VMEM and never reach HBM,
and every output is written once.

Arrays as the ``jax.numpy`` routine has them, float32 in HBM: ``q`` [B, nb,
blk, K, G, D], ``k`` [.., K, D], ``v`` [.., K, Dv]; a kernel sees them as
[B, S, heads x width] (the same bytes) and takes a head's columns as whole
128-lane tiles. One grid step holds ONE head, or TWO where a head's width is
an odd number of half tiles (192-wide keys): each head is then taken out of
the tiles it lies in with the lanes of its neighbour zeroed, so that every
product is over whole tiles and the zeros add nothing.

Scores are kept KEYS DOWN, QUERIES ACROSS (``k q^T``, [tile, tile]): the
running maximum, the normaliser, ``lse`` and ``delta`` are rows, a reduction
over the keys adds vector registers instead of crossing lanes, and of the
seven products of a pair of tiles only ``dq`` turns a plane.

Forward, grid (batch, key-value head, head of its group, query tile): a
key-value head's keys and values are fetched once, zeroed outside the head,
rounded for the MXU and (the values) turned, into VMEM scratch; a step walks
the key tiles its queries see, from the window's first to its own, with the
running maximum, normaliser and output in VMEM, and writes ``out`` and
``lse``. Only the tiles on the diagonal are masked.

Backward, grid (batch, key-value head, head of its group, key tile): a
head's queries and ``dout`` are fetched once into scratch the same way and
its ``dq`` [S, D] stays in VMEM over the key tiles; a step takes one key
tile's ``dk``, ``dv`` over the query tiles that see it (five products a
pair). With one query head a key-value head they are written a tile a step;
with a group the key-value head's whole ``dk``, ``dv`` [S, D] stay in VMEM
over the group and are written once.

Matrix products run at the device's default precision for float32 operands
(one bfloat16 pass on the TPU, as XLA's; float32 under the interpreter, as
XLA's on the CPU), accumulating in float32; masks, ``exp``, maxima, sums,
``lse``, ``delta`` and every accumulator are float32, and so is all that is
stored.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["attention_kernel_selected", "forward", "backward"]

_LANES = 128
_NN = ((1,), (0,))      # a @ b
_NT = ((1,), (1,))      # a @ b.T
_TN = ((0,), (0,))      # a.T @ b
#: What a kernel may take of the v5e's 128 MiB of VMEM, and what the rule
#: lets the resident blocks (double-buffered) and scratch come to.
_VMEM_LIMIT = 100 * 2 ** 20
_VMEM_RESIDENT = 80 * 2 ** 20


def _heads_a_step(width: int, value_width: int) -> int:
    """Heads side by side that fill whole lane tiles of keys AND values."""
    return _LANES // math.gcd(_LANES, width, value_width)


def _tiles(blk: int, length: int, window: Optional[int]):
    """(queries, keys) a tile of either walk. Keys: the largest of 512, 256,
    128 that ``blk`` (hence the padded length, and a window) is a whole
    number of. Queries: two key tiles where the length and the window are
    whole numbers of that (on the chip 1,024 x 512 read 2.40 + 5.13 ms a
    forward and backward where 512 x 512 read 2.66 + 5.43 and 256-key tiles
    3.20 + 5.84: PERF.md 6, PR 46), else one."""
    keys = next(t for t in (512, 256, 128) if blk % t == 0)
    two = length % (2 * keys) == 0 and (window is None
                                        or window % (2 * keys) == 0)
    return keys * (1 + two), keys


def _padded(width: int) -> int:
    """The lanes of the whole tiles a head of ``width`` lies in, where one
    or two heads a step fill whole tiles."""
    return -(-width // _LANES) * _LANES


def attention_kernel_selected(length: int, blk: int, kv_heads: int,
                              group: int, width: int, value_width: int,
                              *dtypes, remote=None, chosen=None) -> bool:
    """Whether a blocked attention call can be the kernels', as far as its
    arrays say: plain causal (no ``remote``, no ``chosen``), float32, ``blk``
    whole 128-lane tiles, heads at least a lane tile wide that fill whole
    tiles one or two a step (two only without a group), and a head's
    ``length`` positions of queries, ``dout`` and ``dq`` within VMEM. Its
    caller adds what only it knows: the arrays on ONE device (``HybridLM``
    reads that off its leaves)."""
    if (remote is not None or chosen is not None or blk % _LANES
            or any(np.dtype(d) != np.dtype(np.float32) for d in dtypes)
            or min(width, value_width) < _LANES):
        return False
    heads = _heads_a_step(width, value_width)
    if heads > 2 or (heads == 2 and (group > 1 or kv_heads % 2)):
        return False
    # the backward's resident blocks: q, dout, dq (and a group's dk, dv)
    # twice over, the rounded q and dout once
    both = heads * (width + value_width)
    resident = length * (8 * (both + heads * width)
                         + 8 * (group > 1) * (width + value_width)
                         + 2 * heads * (_padded(width) + _padded(value_width)))
    return resident <= _VMEM_RESIDENT


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _lanes(p: int, width: int):
    """(the whole lane tiles head ``p`` of a step's heads lies in, as a
    slice of the step's lanes; which of their lanes are the head's own, [1,
    lanes] bool, or None where all are)."""
    first, last = p * width // _LANES, -(-(p + 1) * width // _LANES)
    tiles = slice(first * _LANES, last * _LANES)
    if p * width % _LANES == 0 and (p + 1) * width % _LANES == 0:
        return tiles, None
    lane = first * _LANES + jax.lax.broadcasted_iota(
        jnp.int32, (1, (last - first) * _LANES), 1)
    return tiles, (lane >= p * width) & (lane < (p + 1) * width)


def _own(x, own):
    return x if own is None else jnp.where(own, x, 0.0)


def _rows(tile, size: int):
    return pl.ds(pl.multiple_of(tile * size, size), size)


def _forward_kernel(q_ref, k_ref, v_ref, out_ref, lse_ref, keys, values, *,
                    heads: int, width: int, value_width: int, scale: float,
                    bq: int, bk: int, window: Optional[int], mxu):
    """One (batch, key-value head, head of the group, query tile)."""
    i = pl.program_id(3)

    @pl.when((pl.program_id(2) == 0) & (i == 0))
    def _():
        def fill(j, _):
            rows = _rows(j, bk)
            for p in range(heads):
                tiles, own = _lanes(p, width)
                keys[p, rows, :] = _own(k_ref[0, rows, tiles],
                                        own).astype(mxu)
                tiles, own = _lanes(p, value_width)
                values[p, j] = _own(v_ref[0, rows, tiles],
                                    own).T.astype(mxu)
            return 0

        jax.lax.fori_loop(0, keys.shape[1] // bk, fill, 0)

    start = i * bq
    first = 0 if window is None else start // window * window // bk
    diagonal = start // bk
    at = jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
    to = jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
    if heads > 1:
        out_ref[...] = jnp.zeros_like(out_ref)
    nothing_seen = (jnp.full((1, bq), -jnp.inf, jnp.float32),
                    jnp.zeros((1, bq), jnp.float32),
                    jnp.zeros((values.shape[2], bq), jnp.float32))
    for p in range(heads):
        tiles, own = _lanes(p, width)
        q = _own(q_ref[0, :, tiles], own).astype(mxu)

        def key_tile(j, carry, seen=None, p=p, q=q):
            m, l, acc = carry
            s = _dot(keys[p, _rows(j, bk), :], q, _NT) * scale    # [s, t]
            if seen is not None:
                s = jnp.where(seen, s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
            pr = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            return (m_new, l * alpha + jnp.sum(pr, axis=0, keepdims=True),
                    acc * alpha + _dot(values[p, j], pr.astype(mxu), _NN))

        carry = jax.lax.fori_loop(first, diagonal, key_tile, nothing_seen)
        # the tiles that hold the queries' own positions: the only masks, and
        # every query sees its tile's first key, so the maximum ends finite
        for d in range(bq // bk):
            carry = key_tile(diagonal + d, carry, at + d * bk <= to)
        m, l, acc = carry
        tiles, _ = _lanes(p, value_width)
        if heads > 1:       # zero outside the head's lanes: the values were
            out_ref[0, :, tiles] += (acc / l).T
        else:
            out_ref[0, :, tiles] = (acc / l).T
        lse_ref[0, p, 0] = m + jnp.log(l)


def _backward_kernel(q_ref, k_ref, v_ref, dout_ref, lse_ref, delta_ref,
                     dq_ref, dk_ref, dv_ref, queries, douts, *, heads: int,
                     width: int, value_width: int, group: int, scale: float,
                     bq: int, bk: int, window: Optional[int], mxu):
    """One (batch, key-value head, head of the group, key tile)."""
    g, j = pl.program_id(2), pl.program_id(3)
    tiles_q = queries.shape[1] // bq

    @pl.when(j == 0)
    def _():
        dq_ref[...] = jnp.zeros_like(dq_ref)

        def fill(i, _):
            rows = _rows(i, bq)
            for p in range(heads):
                tiles, own = _lanes(p, width)
                queries[p, rows, :] = _own(q_ref[0, rows, tiles],
                                           own).astype(mxu)
                tiles, own = _lanes(p, value_width)
                douts[p, rows, :] = _own(dout_ref[0, rows, tiles],
                                         own).astype(mxu)
            return 0

        jax.lax.fori_loop(0, tiles_q, fill, 0)

    if group > 1:
        # the key-value head's whole dk, dv stay over its group's heads
        @pl.when((g == 0) & (j == 0))
        def _():
            dk_ref[...] = jnp.zeros_like(dk_ref)
            dv_ref[...] = jnp.zeros_like(dv_ref)

        mine = _rows(j, bk)
    else:
        mine = slice(None)
        if heads > 1:
            dk_ref[...] = jnp.zeros_like(dk_ref)
            dv_ref[...] = jnp.zeros_like(dv_ref)
    start = j * bk
    own_tile = start // bq
    last = tiles_q if window is None else jnp.minimum(
        tiles_q, (start // window + 1) * window // bq)
    at = jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
    to = jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
    no_gradient = (jnp.zeros((bk, queries.shape[2]), jnp.float32),
                   jnp.zeros((bk, douts.shape[2]), jnp.float32))
    for p in range(heads):
        k_tiles, own = _lanes(p, width)
        kj = _own(k_ref[0, :, k_tiles], own).astype(mxu)
        v_tiles, own = _lanes(p, value_width)
        vj = _own(v_ref[0, :, v_tiles], own).astype(mxu)

        def query_tile(i, carry, seen=None, p=p, kj=kj, vj=vj,
                       k_tiles=k_tiles):
            dk, dv = carry
            rows = _rows(i, bq)
            qi, doi = queries[p, rows, :], douts[p, rows, :]
            s = _dot(kj, qi, _NT) * scale                       # [s, t]
            if seen is not None:
                s = jnp.where(seen, s, -jnp.inf)
            pr = jnp.exp(s - lse_ref[0, p, i])
            dv = dv + _dot(pr.astype(mxu), doi, _NN)
            dp = _dot(vj, doi, _NT)
            ds = (pr * (dp - delta_ref[0, p, i]) * scale).astype(mxu)
            dq_ref[0, rows, k_tiles] += _dot(ds, kj, _TN)
            return dk + _dot(ds, qi, _NN), dv

        # the query tile the key tile lies in is the one mask
        carry = query_tile(own_tile, no_gradient,
                           at + (start - own_tile * bq) <= to)
        dk, dv = jax.lax.fori_loop(own_tile + 1, last, query_tile, carry)
        if group > 1 or heads > 1:
            dk_ref[0, mine, k_tiles] += dk
            dv_ref[0, mine, v_tiles] += dv
        else:
            dk_ref[0, mine, k_tiles] = dk
            dv_ref[0, mine, v_tiles] = dv


def _shapes(q, v, blk: int, span: Optional[int], interpret: bool):
    """(the kernels' static arguments; batch, positions, key-value heads,
    group, heads a step)."""
    bsz, nb, _, kv_heads, group, width = q.shape
    heads = _heads_a_step(width, v.shape[-1])
    window = None if span is None else span * blk
    bq, bk = _tiles(blk, nb * blk, window)
    return (dict(heads=heads, width=width, value_width=v.shape[-1], bq=bq,
                 bk=bk, window=window,
                 mxu=jnp.float32 if interpret else jnp.bfloat16),
            bsz, nb * blk, kv_heads, group, heads)


def _call(kernel, grid, in_specs, out_specs, out_shape, scratch, interpret):
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)


# Each pass is a ``jax.jit`` of its own: a step's blocks trace and lower a
# kernel once a shape, not once a block and pass.
@functools.partial(jax.jit,
                   static_argnames=("scale", "blk", "span", "interpret"))
def forward(q, k, v, scale: float, blk: int, span: Optional[int],
            interpret: bool):
    """``q`` [B, nb, blk, K, G, D], ``k`` [B, nb, blk, K, D], ``v`` [B, nb,
    blk, K, Dv] -> (out [B, nb, blk, K, G, Dv], lse [B, K G, tiles, 1,
    tile]): query block ``i`` sees the key blocks ``0 .. i`` or, with
    ``span``, ``i // span * span .. i``, of them the keys at or before each
    query. For shapes :func:`attention_kernel_selected` accepts;
    ``interpret``: :func:`multiverso_tpu.ops.pallas_interpret` of the
    arrays' devices."""
    static, bsz, s, kv_heads, group, heads = _shapes(q, v, blk, span,
                                                     interpret)
    width, value_width, bq, bk = (static[name] for name in (
        "width", "value_width", "bq", "bk"))
    mxu = static["mxu"]

    def head(b, u, h, i):
        return u * group + h

    out, lse = _call(
        functools.partial(_forward_kernel, scale=scale, **static),
        (bsz, kv_heads // heads, group, s // bq),
        [pl.BlockSpec((1, bq, heads * width),
                      lambda b, u, h, i: (b, i, head(b, u, h, i))),
         pl.BlockSpec((1, s, heads * width), lambda b, u, h, i: (b, 0, u)),
         pl.BlockSpec((1, s, heads * value_width),
                      lambda b, u, h, i: (b, 0, u))],
        [pl.BlockSpec((1, bq, heads * value_width),
                      lambda b, u, h, i: (b, i, head(b, u, h, i))),
         pl.BlockSpec((1, heads, 1, 1, bq),
                      lambda b, u, h, i: (b, head(b, u, h, i), i, 0, 0))],
        [jax.ShapeDtypeStruct((bsz, s, kv_heads * group * value_width),
                              q.dtype),
         jax.ShapeDtypeStruct((bsz, kv_heads * group, s // bq, 1, bq),
                              jnp.float32)],
        [pltpu.VMEM((heads, s, _padded(width)), mxu),
         pltpu.VMEM((heads, s // bk, _padded(value_width), bk), mxu)],
        interpret)(q.reshape(bsz, s, -1), k.reshape(bsz, s, -1),
                   v.reshape(bsz, s, -1))
    return out.reshape(q.shape[:-1] + (value_width,)), lse


@functools.partial(jax.jit,
                   static_argnames=("scale", "blk", "span", "interpret"))
def backward(q, k, v, out, lse, dout, scale: float, blk: int,
             span: Optional[int], interpret: bool):
    """The gradients of ``q``, ``k``, ``v`` from what :func:`forward` took
    and gave."""
    static, bsz, s, kv_heads, group, heads = _shapes(q, v, blk, span,
                                                     interpret)
    width, value_width, bq, bk = (static[name] for name in (
        "width", "value_width", "bq", "bk"))
    mxu = static["mxu"]
    # rowsum(dout * out), laid out as lse is
    delta = jnp.moveaxis(jnp.sum(dout * out, axis=-1).reshape(bsz, s, -1),
                         1, 2).reshape(lse.shape)

    def head(b, u, h, j):
        return u * group + h

    def of_head(lanes):
        return pl.BlockSpec((1, s, heads * lanes),
                            lambda b, u, h, j: (b, 0, head(b, u, h, j)))

    def of_keys(lanes):
        # a tile a step, or the key-value head's whole over its group
        return pl.BlockSpec((1, s, lanes), lambda b, u, h, j: (b, 0, u)) \
            if group > 1 else pl.BlockSpec((1, bk, heads * lanes),
                                           lambda b, u, h, j: (b, j, u))

    a_row = pl.BlockSpec((1, heads, s // bq, 1, bq),
                         lambda b, u, h, j: (b, head(b, u, h, j), 0, 0, 0))
    key_tile = [pl.BlockSpec((1, bk, heads * lanes),
                             lambda b, u, h, j: (b, j, u))
                for lanes in (width, value_width)]
    like = [jax.ShapeDtypeStruct((bsz, s, t.size // (bsz * s)), t.dtype)
            for t in (q, k, v)]
    dq, dk, dv = _call(
        functools.partial(_backward_kernel, group=group, scale=scale,
                          **static),
        (bsz, kv_heads // heads, group, s // bk),
        [of_head(width)] + key_tile + [of_head(value_width), a_row, a_row],
        [of_head(width), of_keys(width), of_keys(value_width)], like,
        [pltpu.VMEM((heads, s, _padded(width)), mxu),
         pltpu.VMEM((heads, s, _padded(value_width)), mxu)],
        interpret)(q.reshape(bsz, s, -1), k.reshape(bsz, s, -1),
                   v.reshape(bsz, s, -1), dout.reshape(bsz, s, -1), lse,
                   delta)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)
