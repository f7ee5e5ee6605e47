"""Pallas flash-attention block kernel for ring attention's local step.

The hot op of the long-context path (``parallel/sequence.py``): each ring
step attends local queries against the currently-held K/V block. The XLA
formulation (``_block_attn``) materializes the [B, H, Sq, Sk] score block
in HBM each step; this kernel streams Sk tiles through VMEM with the
online-softmax recurrence, so HBM traffic per ring step drops from
O(Sq*Sk) scores to O(Sq*D + Sk*D) rows — the flash-attention trade
(jax's own ``pallas.ops.tpu.flash_attention`` uses the same grid shape
but does not expose the (o, m, l) streaming stats the ring merge needs,
hence this kernel).

Returns UNNORMALIZED ``(o, m, l)`` exactly like ``_block_attn``:
``o = exp(s - m) @ v``, ``m = rowmax(s)``, ``l = rowsum(exp(s - m))`` —
so the caller's cross-ring-step merge is unchanged. Correctness is
asserted against the XLA formulation in interpret mode on CPU
(tests/test_pallas_attention.py); on-chip timing decides adoption
(default OFF until measured — same protocol as the scatter kernels,
ROADMAP perf #3).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30
_LANES = 128    # stats live lane-replicated (TPU tiling wants a 128 lane dim)


def _kernel(offs_ref, q_ref, k_ref, v_ref, bias_ref, o_ref, m_ref, l_ref,
            acc_s, m_s, l_s, *, scale: float, n_k: int, causal: bool,
            block_q: int, block_k: int):
    """One (bh, q-tile, k-tile) grid step; k is the innermost grid dim so
    the VMEM scratch carries the online-softmax state across k tiles."""
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    q = q_ref[0].astype(jnp.float32)                       # [TQ, D]
    k = k_ref[0].astype(jnp.float32)                       # [TK, D]
    v = v_ref[0].astype(jnp.float32)                       # [TK, D]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        # Mask derived from tile ids + global offsets IN the kernel — no
        # [Sq, Sk] bias ever touches HBM (the whole point at long S). The
        # additive -1e30 matches _block_attn's fully-masked convention.
        i = pl.program_id(1)
        q_pos = (offs_ref[0] + i * block_q
                 + jax.lax.broadcasted_iota(jnp.int32,
                                            (block_q, block_k), 0))
        k_pos = (offs_ref[1] + j * block_k
                 + jax.lax.broadcasted_iota(jnp.int32,
                                            (block_q, block_k), 1))
        s = s + jnp.where(k_pos > q_pos, NEG_INF, 0.0)
    if bias_ref is not None:
        s = s + bias_ref[...].astype(jnp.float32)          # [TQ, TK]

    m_prev = m_s[:, :1]                                    # [TQ, 1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)                        # [TQ, 1]
    p = jnp.exp(s - m_new)                                 # [TQ, TK]
    l_new = alpha * l_s[:, :1] + jnp.sum(p, axis=1, keepdims=True)
    acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    # Stats live lane-replicated (TPU tiling wants a 128 lane dim).
    m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
    l_s[...] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(j == n_k - 1)
    def _flush():
        o_ref[0] = acc_s[...]
        m_ref[0] = m_s[...]
        l_ref[0] = l_s[...]


@functools.partial(jax.jit,
                   static_argnames=("scale", "block_q", "block_k",
                                    "interpret", "vma", "causal"))
def flash_block_attn(q: jax.Array, k: jax.Array, v: jax.Array,
                     bias=None, *, scale: float, causal: bool = False,
                     offsets=None, block_q: int = 128, block_k: int = 128,
                     interpret: bool = False, vma=None):
    """Streaming-softmax block attention.

    q: [B, H, Sq, D]; k, v: [B, H, Sk, D]; bias: optional [Sq, Sk]
    additive mask. Returns ``(o [B,H,Sq,D] f32, m [B,H,Sq,1] f32,
    l [B,H,Sq,1] f32)`` — unnormalized, matching ``_block_attn``.
    Shapes must tile: Sq % block_q == 0, Sk % block_k == 0.

    ``causal``: mask ``k_pos > q_pos`` computed INSIDE the kernel from
    ``offsets`` — a traced (2,) int32 ``[q_offset, k_offset]`` giving the
    global positions of this block's first query/key (ring attention
    passes the rotating block offsets; a full-sequence caller passes
    zeros). No [Sq, Sk] mask is ever materialized in HBM.

    ``vma``: mesh axis names the outputs vary over — required when called
    INSIDE a shard_map (jax's check_vma needs the kernel to declare it;
    pass e.g. ``("seq",)``).
    """
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk)
    bh = B * H
    qf = q.reshape(bh, Sq, D)
    kf = k.reshape(bh, Sk, D)
    vf = v.reshape(bh, Sk, D)
    n_q, n_k = Sq // block_q, Sk // block_k

    if offsets is None:
        offsets = jnp.zeros((2,), jnp.int32)
    offsets = jnp.asarray(offsets, jnp.int32)

    grid = (bh, n_q, n_k)
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),   # offsets, grid-invariant
        pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
    ]
    operands = [offsets, qf, kf, vf]
    kw = dict(scale=scale, n_k=n_k, causal=causal,
              block_q=block_q, block_k=block_k)
    if bias is not None:
        in_specs.append(pl.BlockSpec((block_q, block_k),
                                     lambda b, i, j: (i, j)))
        operands.append(bias)
        kernel = functools.partial(_kernel, **kw)
    else:
        kernel = functools.partial(
            lambda offs, qr, kr, vr, *rest, **kws: _kernel(
                offs, qr, kr, vr, None, *rest, **kws), **kw)

    sds_kw = {"vma": frozenset(vma)} if vma else {}
    # The stats leave the kernel lane-replicated, [bh, Sq, 128], exactly as
    # the scratch holds them: a (1, block_q) block of a [bh, Sq] array has a
    # second-minor dim of 1, which Mosaic's (8, 128) block rule refuses.
    out_shape = [
        jax.ShapeDtypeStruct((bh, Sq, D), jnp.float32, **sds_kw),
        jax.ShapeDtypeStruct((bh, Sq, _LANES), jnp.float32, **sds_kw),
        jax.ShapeDtypeStruct((bh, Sq, _LANES), jnp.float32, **sds_kw),
    ]
    out_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0)),
    ]
    scratch = [
        pltpu.VMEM((block_q, D), jnp.float32),
        pltpu.VMEM((block_q, _LANES), jnp.float32),
        pltpu.VMEM((block_q, _LANES), jnp.float32),
    ]
    o, m, l = pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch,
        interpret=interpret)(*operands)
    return (o.reshape(B, H, Sq, D), m[:, :, :1].reshape(B, H, Sq, 1),
            l[:, :, :1].reshape(B, H, Sq, 1))


def supported(q: jax.Array, k: jax.Array,
              block_q: int = 128, block_k: int = 128) -> bool:
    """Shape gate for the ring-attention call site: tiles must divide and
    the head dim should be lane-friendly."""
    return (q.shape[2] % block_q == 0 and k.shape[2] % block_k == 0
            and q.shape[3] % 8 == 0)


# ---------------------------------------------------------------------------
# Paged single-token decode attention (docs/SERVING.md "Decode memory
# hierarchy"). The serving step's XLA formulation gathers every slot's
# pages into a [B, H, G*P, dh] logical cache in HBM before attending —
# bytes MOVED per step stay O(context) even though bytes HELD are paged.
# This kernel removes the materialized gather: the per-slot page table
# rides scalar prefetch, the BlockSpec index_map dereferences it, and
# Mosaic DMAs each physical page straight from the pool into VMEM while
# the online-softmax recurrence streams over pages. Same protocol as the
# kernels above: interpret-mode parity on CPU decides correctness
# (tests/test_pallas_attention.py), on-chip timing decides adoption
# (default OFF in the serving step until measured).
# ---------------------------------------------------------------------------
def _paged_kernel(ptab_ref, len_ref, t_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_s, m_s, l_s, *, scale: float, n_pages: int,
                  page: int, bucket: int):
    """One (slot, logical-page) grid step; the page axis is innermost so
    the VMEM scratch carries the online-softmax state across one slot's
    pages. ``k_ref``/``v_ref`` hold the PHYSICAL page the index_map
    resolved via the prefetched page table."""
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    # Every operand keeps a unit query dim ([H, 1, ...]): a batched
    # dot_general whose lhs has NO free dim ([H, dh] x [H, P, dh]) reaches
    # Mosaic with an empty lhs_non_contracting_dims list, which its
    # attribute parser rejects.
    q = q_ref[0].astype(jnp.float32)                       # [H, 1, dh]
    k = k_ref[0].astype(jnp.float32)                       # [H, P, dh]
    v = v_ref[0].astype(jnp.float32)                       # [H, P, dh]
    # s[h, 0, p] = q[h] . k[h, p]  (batched over heads)
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * scale
    # Slot/position mask computed IN the kernel from the prefetched
    # scalars — the drain path's formula verbatim: a key at logical
    # position r is valid iff r < len (real prompt) or bucket <= r <=
    # bucket + t (generated so far).
    pos = j * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    length = len_ref[b]
    t = t_ref[b]
    valid = (pos < length) | ((pos >= bucket) & (pos <= bucket + t))
    s = s + jnp.where(valid, 0.0, NEG_INF)

    m_prev = m_s[:, :, :1]                                 # [H, 1, 1]
    m_cur = jnp.max(s, axis=2, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                                 # [H, 1, P]
    l_new = alpha * l_s[:, :, :1] + jnp.sum(p, axis=2, keepdims=True)
    acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
        p, v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
    l_s[...] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(j == n_pages - 1)
    def _flush():
        o_ref[0] = acc_s[...] / l_s[:, :, :1]


@functools.partial(jax.jit,
                   static_argnames=("bucket", "page", "scale",
                                    "interpret"))
def paged_decode_attn(q: jax.Array, kp: jax.Array, vp: jax.Array,
                      ptab: jax.Array, lengths: jax.Array,
                      t: jax.Array, *, bucket: int, page: int,
                      scale: float, interpret: bool = False) -> jax.Array:
    """One decode step of attention over paged KV storage.

    q: [B, H, dh] this step's queries (one token per slot); kp/vp:
    [n_phys, H, page, dh] ONE layer's physical page pool; ptab: [B, G]
    int32 logical->physical page table; lengths/t: [B] int32 prompt
    lengths and per-slot step counters. Returns the NORMALIZED
    attention output [B, H, dh] — softmax over each slot's valid keys
    (prompt + generated-so-far), numerically the online-softmax
    refactoring of the serving step's gather-then-attend.

    The page table and mask scalars ride ``PrefetchScalarGridSpec``:
    block index maps dereference ``ptab`` so each grid step DMAs
    exactly one PHYSICAL page — no [B, G*P, dh] logical cache is ever
    materialized in HBM."""
    B, H, dh = q.shape
    G = ptab.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, G),
        in_specs=[
            pl.BlockSpec((1, H, 1, dh),
                         lambda b, j, ptab_r, len_r, t_r: (b, 0, 0, 0)),
            pl.BlockSpec((1, H, page, dh),
                         lambda b, j, ptab_r, len_r, t_r:
                         (ptab_r[b, j], 0, 0, 0)),
            pl.BlockSpec((1, H, page, dh),
                         lambda b, j, ptab_r, len_r, t_r:
                         (ptab_r[b, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, H, 1, dh), lambda b, j, ptab_r, len_r, t_r: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1, dh), jnp.float32),
            pltpu.VMEM((H, 1, _LANES), jnp.float32),
            pltpu.VMEM((H, 1, _LANES), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_kernel, scale=scale, n_pages=G,
                               page=page, bucket=bucket)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, 1, dh), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(ptab, jnp.int32), jnp.asarray(lengths, jnp.int32),
      jnp.asarray(t, jnp.int32), q[:, :, None, :], kp, vp)
    return out[:, :, 0, :]
