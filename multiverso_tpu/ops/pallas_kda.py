"""The chunked delta rule (:func:`~multiverso_tpu.models.hybrid_lm.kda.
kda_chunked`) as Pallas kernels: the ``d_v x d_k`` state and a chunk's planes
live in VMEM across the walk over the chunks, forward and backward.

Per head, a chunk of ``C`` positions that starts from the state ``S`` (``G``
the running sum of the log decay ``g`` inside the chunk, ``b`` the write
strengths; :mod:`~multiverso_tpu.models.hybrid_lm.kda` derives it)::

    A_ij = sum_c k_ic k_jc exp(G_ic - G_jc)   (j < i)
    B_ij = sum_c q_ic k_jc exp(G_ic - G_jc)   (j <= i)
    [U' | W] = (I + Diag(b) A)^-1 Diag(b) [V | K o exp G]
    u = U' - W S
    o = (Q o exp G) S + B u
    S' = Diag(exp G_C) S + (K o exp(G_C - G))^T u

One grid step is one (sequence, :data:`HEADS_A_STEP` heads, chunk): per head
it loads the chunk's ``q``, ``k``, ``g``, ``v`` [C, 128] and ``b`` [1, C],
takes the running sum itself,
forms ``A`` and ``B`` in sub-chunks of :data:`SUB_CHUNK` positions exactly as
the ``jax.numpy`` body does (every ``exp`` in float32, of a difference taken
from the MIDDLE of the query sub-chunk's own decay, keys of later sub-chunks
masked BEFORE the exponential: at the gate's bound of -5 a step a chunk spans
``e^320`` and a factored ``exp(G_i) exp(-G_j)`` overflows), inverts the unit
triangular system and applies it. The chunk axis is the grid's innermost and
sequential; the state is carried TRANSPOSED (``[d_v, d_k]``: the decay of a
chunk then scales lanes, and its gradient is a sum down the sublanes) in VMEM
scratch.

The inverse: the 16 x 16 diagonal blocks by elimination row by row on the
vector unit in float32 (the four blocks at once, a step a pivot row: exactly
what forward substitution subtracts), then the two levels of block forward
substitution by doubling that ``kda._unit_lower_inverse`` ends with, as
products at ``highest``.

The backward pass (:func:`kda_scan` is a ``jax.custom_vjp``) keeps the inputs,
the state each chunk STARTS from and the chunk's inverse, which the forward
pass writes when it runs under differentiation ([chunks, heads, 128, 128] and
[chunks, heads, C, C] float32, short-lived under the head group's
checkpoint): another walk to recompute the states would cost a whole forward
pass, since a state needs its chunk's solved system, and the inverse is two
thirds of a forward grid step's instructions. It walks from the last chunk to
the first, recomputes the chunk's other planes and carries the state's
gradient. The inverse's gradient needs no product with
the inverse: with ``X = T R`` (``T`` the inverse, ``R`` the right-hand sides)
``dN = -T^T dT T^T = -(T^T dX) X^T``. The sub-chunks' middles are constants
(they cancel in exact arithmetic, and the body stops their gradient too).

Matrix products run at the device's default precision for float32 operands
(one bfloat16 pass on the TPU, as XLA's; float32 under the interpreter, as
XLA's on the CPU), accumulating in float32, but the inverse's, which keep
float32 accuracy as the body's do; masks, ``exp`` and every sum are float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["kda_kernel_selected", "kda_scan", "SUB_CHUNK"]

#: Positions a sub-chunk: 16 steps at the gate's bound of -5 are ``e^80``,
#: inside float32 (``kda.SUB_CHUNK`` is this).
SUB_CHUNK = 16
_LANES = 128
_NN = ((1,), (0,))      # a @ b
_NT = ((1,), (1,))      # a @ b.T
_TN = ((0,), (0,))      # a.T @ b
_HIGHEST = jax.lax.Precision.HIGHEST


def kda_kernel_selected(chunk: int, d_k: int, d_v: int, heads: int,
                        *dtypes) -> bool:
    """Whether a delta rule of ``heads`` heads with keys ``d_k`` and values
    ``d_v`` wide can be the kernels', as far as its arrays say: float32, a
    head's keys and values each ONE 128-lane tile, ``chunk`` whole sub-chunks
    of :data:`SUB_CHUNK` and at most a lane tile. Its caller adds what only
    it knows: the arrays on ONE device (``HybridLM`` reads that off its
    leaves)."""
    return (all(np.dtype(d) == np.dtype(np.float32) for d in dtypes)
            and d_k == _LANES and d_v == _LANES and heads >= 1
            and chunk % SUB_CHUNK == 0 and 2 * SUB_CHUNK <= chunk <= _LANES
            and chunk & (chunk - 1) == 0)


def _dot(a, b, dims, mxu):
    return jax.lax.dot_general(a.astype(mxu), b.astype(mxu), (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_f32(a, b):
    return jax.lax.dot_general(a, b, (_NN, ((), ())), precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _running_sum(t, backwards: bool = False):
    """Down the rows of ``t`` [C, D], by doubling strides: row ``i`` takes
    the sum up to and with ``i`` (``backwards``: from ``i`` on)."""
    length = t.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, t.shape, 0)
    stride = 1
    while stride < length:
        t = t + (jnp.where(row + stride < length,
                           pltpu.roll(t, length - stride, 0), 0.0)
                 if backwards else
                 jnp.where(row >= stride, pltpu.roll(t, stride, 0), 0.0))
        stride *= 2
    return t


def _square(length: int):
    """Row and column index of a [C, C] plane."""
    return (jax.lax.broadcasted_iota(jnp.int32, (length, length), 0),
            jax.lax.broadcasted_iota(jnp.int32, (length, length), 1))


def _column(row, at, to):
    """[1, C] -> [C, 1]: the row laid on the diagonal and summed (exact)."""
    return jnp.sum(jnp.where(at == to, row, 0.0), axis=1, keepdims=True)


def _row(col, at, to):
    """[C, 1] -> [1, C]."""
    return jnp.sum(jnp.where(at == to, col, 0.0), axis=0, keepdims=True)


def unit_lower_inverse(n, at, to):
    """``(I + N)^-1`` for ``N`` [C, C] strictly lower triangular (``at``,
    ``to``: :func:`_square`), inside a kernel. The diagonal blocks of
    :data:`SUB_CHUNK` by elimination: pivot row ``i`` of every block, final
    since step ``i - 1``, leaves the rows under it in its block, ``N``'s
    column ``i`` of the block times the row (what substitution row by row
    subtracts, in float32 on the vector unit). Then block forward substitution
    by doubling, ``X - X N_h X`` with ``N_h`` the lower-left corner of every
    block of ``2h`` (:func:`~multiverso_tpu.models.hybrid_lm.kda.
    _unit_lower_inverse`'s last levels), as products at ``highest``."""
    c = n.shape[0]
    # (a sub-chunk is a power of two: shifts and masks, not divisions)
    bits = SUB_CHUNK.bit_length() - 1
    inv = jnp.where(at == to, 1.0, 0.0)
    inside = jnp.where(at >> bits == to >> bits, n, 0.0)
    for i in range(SUB_CHUNK - 1):
        pivot = to & (SUB_CHUNK - 1) == i
        # N's column i of a row's own block, and the block's pivot row
        factor = jnp.sum(jnp.where(pivot, inside, 0.0), axis=1, keepdims=True)
        rows = inv.reshape(c // SUB_CHUNK, SUB_CHUNK, c)[:, i:i + 1, :]
        rows = jnp.broadcast_to(rows, (c // SUB_CHUNK, SUB_CHUNK, c))
        inv = inv - factor * rows.reshape(c, c)
    while 1 << bits < c:
        # rows of the upper half and columns of the lower half of a block of
        # twice the size
        corner = (at >> bits + 1 == to >> bits + 1) \
            & (at >> bits & 1 == 1) & (to >> bits & 1 == 0)
        inv = inv - _dot_f32(inv, _dot_f32(jnp.where(corner, n, 0.0), inv))
        bits += 1
    return inv


def _chunk(q, k, g, v, beta_row, mxu, inv=None):
    """What a chunk computes without its starting state (``inv``: its system's
    inverse, where the forward pass kept it), and what the backward pass
    needs of how: a dict of the sub-chunks'
    ``(rows, near, far, scaled [k; q] rows, scaled keys)``, ``A``, ``B``, the
    inverse, ``b`` as a column, ``exp G``, ``K o exp G``, ``Q o exp G``,
    ``[U' | W]``, ``exp(G_C - G)``, ``K o exp(G_C - G)``, ``exp G_C``."""
    c = q.shape[0]
    at, to = _square(c)
    key = jax.lax.broadcasted_iota(jnp.int32, g.shape, 0)
    cum = _running_sum(g)
    subs, a_rows, b_rows = [], [], []
    for s in range(c // SUB_CHUNK):
        rows = slice(s * SUB_CHUNK, (s + 1) * SUB_CHUNK)
        start = cum[rows.start - 1:rows.start] if s else jnp.zeros_like(
            cum[:1])
        # R_a: half way down the sub-chunk's own decay
        middle = start + 0.5 * (cum[rows.stop - 1:rows.stop] - start)
        near = jnp.exp(cum[rows] - middle)
        far = jnp.exp(jnp.where(key < rows.stop, middle - cum, -jnp.inf))
        scaled = jnp.concatenate([k[rows] * near, q[rows] * near], axis=0)
        keys = k * far
        planes = _dot(scaled, keys, _NT, mxu)               # [2 sub, C]
        a_rows.append(planes[:SUB_CHUNK])
        b_rows.append(planes[SUB_CHUNK:])
        subs.append((rows, near, far, scaled, keys))
    a = jnp.where(at > to, jnp.concatenate(a_rows, axis=0), 0.0)
    b = jnp.where(at >= to, jnp.concatenate(b_rows, axis=0), 0.0)
    beta = _column(beta_row, at, to)
    if inv is None:
        inv = unit_lower_inverse(beta * a, at, to)
    grown = jnp.exp(cum)
    kg = k * grown
    solved = _dot(inv, beta * jnp.concatenate([v, kg], axis=1), _NN, mxu)
    last = cum[c - 1:c]
    left = jnp.exp(last - cum)
    return dict(subs=subs, a=a, b=b, inv=inv, beta=beta, grown=grown,
                kg=kg, qg=q * grown, solved=solved, left=left, kd=k * left,
                decay=jnp.exp(last), at=at, to=to)


def _forward_kernel(q_ref, k_ref, g_ref, v_ref, beta_ref, o_ref, *rest, mxu,
                    states: bool):
    """One (sequence, heads of a step, chunk). ``states``: write the state
    the chunk starts from and its system's inverse too (the backward pass's
    residuals)."""
    state = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    c = v_ref.shape[1]
    for j, (keys, values) in enumerate(_lanes(state)):
        dv = values.stop - values.start
        t = _chunk(q_ref[0, :, keys], k_ref[0, :, keys], g_ref[0, :, keys],
                   v_ref[0, :, values], beta_ref[0, 0, j], mxu)
        st = state[j]
        if states:
            rest[0][0, 0, j] = st
            rest[1][0, 0, j] = t["inv"]
        both = _dot(jnp.concatenate([t["qg"], t["solved"][:, dv:]], axis=0),
                    st, _NT, mxu)                           # [2C, Dv]
        u = t["solved"][:, :dv] - both[c:]
        o_ref[0, :, values] = both[:c] + _dot(t["b"], u, _NN, mxu)
        state[j] = t["decay"] * st + _dot(u, t["kd"], _TN, mxu)


def _lanes(state):
    """Per head of a grid step, the lanes of its keys and of its values in
    the step's blocks (``state``: the scratch [heads, Dv, Dk])."""
    heads, dv, dk = state.shape
    return [(slice(j * dk, (j + 1) * dk), slice(j * dv, (j + 1) * dv))
            for j in range(heads)]


def _backward_kernel(q_ref, k_ref, g_ref, v_ref, beta_ref, do_ref, before_ref,
                     inv_ref, dq_ref, dk_ref, dg_ref, dv_ref, dbeta_ref,
                     dstate, *, mxu):
    """One (sequence, heads of a step, chunk), the chunks from the last to the
    first: ``dstate`` is the gradient of the (transposed) states the chunk
    leaves behind."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    for j, (keys, values) in enumerate(_lanes(dstate)):
        grads = _chunk_backward(
            q_ref[0, :, keys], k_ref[0, :, keys], g_ref[0, :, keys],
            v_ref[0, :, values], beta_ref[0, 0, j], do_ref[0, :, values],
            before_ref[0, 0, j], inv_ref[0, 0, j], dstate[j], mxu)
        (dq_ref[0, :, keys], dk_ref[0, :, keys], dg_ref[0, :, keys],
         dv_ref[0, :, values], dbeta_ref[0, 0, j], dstate[j]) = grads


def _chunk_backward(q, k, g, v, beta_row, do, st, inv, ds, mxu):
    """A chunk's gradients from ``do`` and the gradient ``ds`` of the state
    it leaves behind, ``st`` the state it starts from and ``inv`` its
    system's inverse: (``dq``, ``dk``, ``dg``, ``dv``, ``dbeta`` as a row,
    the gradient of ``st``)."""
    c, dv = v.shape
    t = _chunk(q, k, g, v, beta_row, mxu, inv)
    at, to, beta, solved = t["at"], t["to"], t["beta"], t["solved"]
    w = solved[:, dv:]
    u = solved[:, :dv] - _dot(w, st, _NT, mxu)
    # o = qg S + B u; S' = decay S + kd^T u; u = U' - W S
    du = _dot(t["b"], do, _TN, mxu) + _dot(t["kd"], ds, _NT, mxu)
    db = jnp.where(at >= to, _dot(do, u, _NT, mxu), 0.0)
    dqg = _dot(do, st, _NN, mxu)
    dkd = _dot(u, ds, _NN, mxu)
    ddecay = jnp.sum(ds * st, axis=0, keepdims=True)
    dst = t["decay"] * ds + _dot(
        jnp.concatenate([do, -du], axis=0),
        jnp.concatenate([t["qg"], w], axis=0), _TN, mxu)
    # [U' | W] = T (b [v | kg]), T = (I + b A)^-1
    dsolved = jnp.concatenate([du, -_dot(du, st, _NN, mxu)], axis=1)
    drhs = _dot(t["inv"], dsolved, _TN, mxu)
    dn = jnp.where(at > to, -_dot(drhs, solved, _NT, mxu), 0.0)
    dbeta = jnp.sum(drhs * jnp.concatenate([v, t["kg"]], axis=1), axis=1,
                    keepdims=True) + jnp.sum(dn * t["a"], axis=1,
                                             keepdims=True)
    dkg = beta * drhs[:, dv:]
    da = beta * dn
    # the planes, a sub-chunk of queries at a time
    dq_rows, dk_rows, dcum_rows = [], [], []
    dk = dkg * t["grown"] + dkd * t["left"]
    dcum = dqg * t["qg"] + dkg * t["kg"] - dkd * t["kd"]
    for rows, near, far, scaled, keys in t["subs"]:
        dplanes = jnp.concatenate([da[rows], db[rows]], axis=0)  # [2 sub, C]
        dscaled = _dot(dplanes, keys, _NN, mxu)
        dkeys = _dot(dplanes, scaled, _TN, mxu)
        dk_rows.append(dscaled[:SUB_CHUNK] * near)
        dq_rows.append(dscaled[SUB_CHUNK:] * near)
        # G's gradient from a pair (i, j) of a plane is added at i and taken
        # off at j, and the running sum over it cancels what does not
        # straddle a position only as far as the two agree: give both the
        # factors as the products saw them
        both = dscaled * scaled.astype(mxu).astype(jnp.float32)
        dcum_rows.append(both[:SUB_CHUNK] + both[SUB_CHUNK:])
        dk = dk + dkeys * far
        dcum = dcum - dkeys * keys.astype(mxu).astype(jnp.float32)
    dlast = jnp.sum(dkd * t["kd"], axis=0, keepdims=True) \
        + ddecay * t["decay"]
    row = jax.lax.broadcasted_iota(jnp.int32, dcum.shape, 0)
    dcum = dcum + jnp.concatenate(dcum_rows, axis=0) + jnp.where(
        row == c - 1, dlast, 0.0)
    return (jnp.concatenate(dq_rows, axis=0) + dqg * t["grown"],
            dk + jnp.concatenate(dk_rows, axis=0),
            _running_sum(dcum, backwards=True), beta * drhs[:, :dv],
            _row(dbeta, at, to), dst)


#: Heads a grid step at most: their chains of small products are independent,
#: and the scheduler fills one's waits with another's work (a forward call of
#: 8 heads at 8,192 positions: 2.23 / 2.10 / 2.02 ms at 1 / 2 / 4 heads a
#: step, a forward and backward 3.72 / 3.52 / 3.33; PERF.md 6, PR 49).
HEADS_A_STEP = 4


def _specs(q, v, beta, backwards: bool):
    """(grid; the block of a [B, T, H Dk] array, of a [B, T, H Dv] array, of
    ``beta`` [B, nc, H, 1, C], of the states [B, nc, H, Dv, Dk], of the
    inverses [B, nc, H, C, C]; the scratch's shape, a step's heads'
    states)."""
    bsz, nc, h, _, c = beta.shape
    dk, dv = q.shape[2] // h, v.shape[2] // h
    per = max(n for n in range(1, HEADS_A_STEP + 1) if h % n == 0)

    def chunk(k):
        return nc - 1 - k if backwards else k

    def a_chunk(*block):
        return pl.BlockSpec((1, 1, per) + block,
                            lambda i, j, k: (i, chunk(k), j, 0, 0))

    return ((bsz, h // per, nc),
            pl.BlockSpec((1, c, per * dk), lambda i, j, k: (i, chunk(k), j)),
            pl.BlockSpec((1, c, per * dv), lambda i, j, k: (i, chunk(k), j)),
            a_chunk(1, c), a_chunk(dv, dk), a_chunk(c, c), (per, dv, dk))


def _call(kernel, grid, in_specs, out_specs, out_shape, scratch, interpret):
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM(scratch, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret)


def _mxu(interpret: bool):
    return jnp.float32 if interpret else jnp.bfloat16


# Each walk is a ``jax.jit`` of its own: a step's blocks trace and lower a
# kernel once a shape, not once a block and pass.
@functools.partial(jax.jit, static_argnames=("states", "interpret"))
def _forward(q, k, v, g, beta, states: bool, interpret: bool):
    """``o``, and with ``states`` the state each chunk starts from and its
    system's inverse."""
    shape = jax.ShapeDtypeStruct
    grid, keys, values, per_head, state, inverse, scratch = _specs(
        q, v, beta, False)
    c = beta.shape[-1]
    return _call(
        functools.partial(_forward_kernel, mxu=_mxu(interpret),
                          states=states),
        grid, [keys, keys, keys, values, per_head],
        [values, state, inverse] if states else [values],
        [shape(v.shape, v.dtype)] + [
            shape(beta.shape[:3] + scratch[1:], v.dtype),
            shape(beta.shape[:3] + (c, c), v.dtype)] * states,
        scratch, interpret)(q, k, g, v, beta)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _backward(q, k, v, g, beta, before, inv, do, interpret: bool):
    """The gradients of ``q``, ``k``, ``v``, ``g``, ``beta``."""
    grid, keys, values, per_head, state, inverse, scratch = _specs(
        q, v, beta, True)
    dq, dk, dg, dv, dbeta = _call(
        functools.partial(_backward_kernel, mxu=_mxu(interpret)), grid,
        [keys, keys, keys, values, per_head, values, state, inverse],
        [keys, keys, keys, values, per_head],
        [jax.ShapeDtypeStruct(t.shape, t.dtype) for t in (q, k, g, v, beta)],
        scratch, interpret)(q, k, g, v, beta, do, before, inv)
    return dq, dk, dv, dg, dbeta


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def kda_scan(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
             beta: jax.Array, interpret: bool) -> jax.Array:
    """``q``, ``k``, ``g`` [B, T, H Dk] (the heads side by side; ``g`` the log
    decay), ``v`` [B, T, H Dv], ``beta`` [B, T / C, H, 1, C] (a chunk's
    write strengths, the heads before its positions) -> ``o`` like ``v``, the
    state zero at each sequence's start. For shapes
    :func:`kda_kernel_selected` accepts, ``T`` whole chunks; ``interpret``:
    :func:`multiverso_tpu.ops.pallas_interpret` of the arrays' devices."""
    return _forward(q, k, v, g, beta, False, interpret)[0]


def _kda_scan_fwd(q, k, v, g, beta, interpret):
    o, before, inv = _forward(q, k, v, g, beta, True, interpret)
    return o, (q, k, v, g, beta, before, inv)


def _kda_scan_bwd(interpret, saved, do):
    return _backward(*saved, do, interpret)


kda_scan.defvjp(_kda_scan_fwd, _kda_scan_bwd)
