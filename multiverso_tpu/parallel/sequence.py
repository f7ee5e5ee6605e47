"""Sequence / context parallelism: ring attention and all-to-all (Ulysses).

The reference predates transformers — it scales the *model* dimension by
row-sharding huge embedding tables (SURVEY.md §5 "Long-context"). This module
supplies the sequence-dimension counterpart as first-class mesh primitives so
the framework covers long-context training:

* :func:`ring_attention` — blockwise attention with K/V shards rotated
  around the ICI ring via ``jax.lax.ppermute``, accumulating in the
  numerically-stable streaming-softmax form. Memory per device is O(S/n);
  the full S x S score matrix never materializes.
* :func:`ulysses_attention` — the all-to-all alternative: resharding
  sequence-parallel activations to head-parallel via two
  ``jax.lax.all_to_all`` hops so each device runs dense attention on full
  sequences for a subset of heads.

Both are pure shard_map programs over a named mesh axis: XLA lowers the
permutes/all-to-alls onto ICI neighbors, which is the entire point of the
design (no host involvement per step).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from multiverso_tpu.ops import pallas_interpret

SEQ_AXIS = "seq"


def _pvary(x, axis):
    """Mark ``x`` as varying over ``axis`` (fresh accumulators are unvarying
    until marked; a scan carry must match the ppermute outputs' type)."""
    return jax.lax.pcast(x, axis, to="varying")


def _resolve_flash(use_flash, sq: int, sk: int, d: int) -> bool:
    """The ONE flash-kernel gate: flag default + tile-shape support.
    Resolved in the WRAPPERS (shapes known pre-shard_map) so check_vma is
    only relaxed when the Pallas kernel genuinely runs."""
    if use_flash is None:
        from multiverso_tpu.utils.configure import get_flag
        # Host config flag read once at trace time — never a traced value.
        use_flash = bool(get_flag("flash_attention"))  # graftlint: disable=implicit-host-sync
    flash = bool(use_flash)  # graftlint: disable=implicit-host-sync
    return flash and sq % 128 == 0 and sk % 128 == 0 and d % 8 == 0


def _block_attn(q, k, v, scale, mask=None):
    """Scores for one (q-block, kv-block) pair plus streaming-softmax stats.
    q: [B, H, Sq, D]; k, v: [B, H, Sk, D]; mask: [Sq, Sk] additive."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if mask is not None:
        s = s + mask[None, None, :, :]
    m = jnp.max(s, axis=-1, keepdims=True)                     # [B,H,Sq,1]
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)                     # [B,H,Sq,1]
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return o, m, l


def ring_attention_block(q_blk: jax.Array, k_blk: jax.Array,
                         v_blk: jax.Array, axis: str, n: int,
                         causal: bool = False,
                         use_flash: Optional[bool] = None,
                         interpret: bool = False) -> jax.Array:
    """The per-device ring-attention body, for use INSIDE a shard_map.

    ``q_blk/k_blk/v_blk``: this device's [B, H, S/n, D] sequence block on a
    mesh whose ``axis`` has size ``n``. Exposed separately so programs that
    already run under a shard_map spanning ``axis`` (e.g. the 1F1B pipeline
    composing PP x SP, ``parallel/pipeline.py``) can run ring attention
    without nesting shard_maps. :func:`ring_attention` is the standalone
    wrapper.

    ``use_flash`` routes the local block step through the Pallas
    flash kernel (``ops/pallas_attention.py`` — streams Sk tiles through
    VMEM instead of materializing the [Sq, Sk] score block in HBM);
    ``None`` reads the ``-flash_attention`` flag (default off until
    on-chip timing adopts it, same protocol as the scatter kernels).
    ``interpret`` is the enclosing mesh's ``ops.pallas_interpret`` verdict
    (the blocks here are tracers and carry no devices).
    """
    use_flash = _resolve_flash(use_flash, q_blk.shape[2], k_blk.shape[2],
                               q_blk.shape[3])
    scale = 1.0 / np.sqrt(q_blk.shape[-1])
    my = jax.lax.axis_index(axis)
    Sq = q_blk.shape[2]

    def body(carry, step):
        o_acc, m_acc, l_acc, k_cur, v_cur = carry
        # ppermute sends i -> i+1, so after `step` rotations this device
        # holds the K/V block that originated on device (my - step) mod n.
        k_blk_idx = jnp.mod(my - step, n)
        if use_flash:
            from multiverso_tpu.ops.pallas_attention import flash_block_attn
            # Causal masking happens INSIDE the kernel from these global
            # offsets — no [Sq, Sk] mask ever materializes in HBM.
            offsets = jnp.stack([my * Sq, k_blk_idx * Sq]) \
                .astype(jnp.int32)
            o, m, l = flash_block_attn(
                q_blk, k_cur, v_cur, scale=float(scale), causal=causal,
                offsets=offsets, interpret=interpret, vma=(axis,))
            o = o.astype(q_blk.dtype)
            m = m.astype(q_blk.dtype)
            l = l.astype(q_blk.dtype)
        else:
            if causal:
                q_pos = my * Sq + jnp.arange(Sq)[:, None]
                k_pos = k_blk_idx * Sq + jnp.arange(Sq)[None, :]
                # Finite large-negative (not -inf): a fully-masked row
                # would otherwise produce exp(-inf - -inf) = nan in the
                # streaming softmax; -1e30 underflows cleanly and the
                # merge's beta factor zeroes the block's contribution.
                mask = jnp.where(k_pos > q_pos, -1e30, 0.0)
            else:
                mask = None
            o, m, l = _block_attn(q_blk, k_cur, v_cur, scale, mask)
        m_new = jnp.maximum(m_acc, m)
        alpha = jnp.exp(m_acc - m_new)
        beta = jnp.exp(m - m_new)
        o_acc = o_acc * alpha + o * beta
        l_acc = l_acc * alpha + l * beta
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_nxt = jax.lax.ppermute(k_cur, axis, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis, perm)
        return (o_acc, m_new, l_acc, k_nxt, v_nxt), None

    B, H, _, D = q_blk.shape
    # Fresh accumulators are "unvarying" over the mesh axis until marked;
    # the carry must match the ppermute outputs' varying type.
    init = (_pvary(jnp.zeros((B, H, Sq, D), q_blk.dtype), axis),
            _pvary(jnp.full((B, H, Sq, 1), -jnp.inf,
                            q_blk.dtype), axis),
            _pvary(jnp.zeros((B, H, Sq, 1), q_blk.dtype), axis),
            k_blk, v_blk)
    (o, _, l, _, _), _ = jax.lax.scan(body, init, jnp.arange(n))
    return o / jnp.maximum(l, 1e-20)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh,
                   axis: str = SEQ_AXIS, causal: bool = False) -> jax.Array:
    """Attention over a sequence sharded across ``axis``.

    Inputs are [B, H, S, D] logically, sharded on S. Each of the n steps
    attends the local queries against the currently-held K/V shard, then
    rotates K/V one neighbor around the ring. Streaming-softmax merging
    keeps exact softmax semantics. With ``causal=True`` the global position
    mask is reconstructed per ring step from the block indices (device i
    holds K/V block ``(i + step) % n`` at step ``step``).
    """
    n = mesh.shape[axis]
    blk = q.shape[2] // n
    use_flash = _resolve_flash(None, blk, blk, q.shape[3])

    interpret = pallas_interpret(mesh.devices.flat)

    def local(q_blk, k_blk, v_blk):
        return ring_attention_block(q_blk, k_blk, v_blk, axis, n,
                                    causal=causal, use_flash=use_flash,
                                    interpret=interpret)

    spec = P(None, None, axis, None)
    # check_vma off on the flash path: jax's interpret/lowering of a
    # pallas_call inside shard_map mixes varying and unvarying internals
    # (jax suggests exactly this workaround in the error it raises).
    fn = jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=not use_flash)
    return fn(q, k, v)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh,
                      axis: str = SEQ_AXIS, causal: bool = False
                      ) -> jax.Array:
    """All-to-all sequence parallelism (the Ulysses layout swap).

    Inputs [B, H, S, D] sharded on S with H divisible by the axis size.
    First all-to-all: seq-sharded -> head-sharded (full sequence per
    device); dense attention (optionally causal — after the layout swap
    every device holds the FULL sequence, so the mask is the plain lower
    triangle, no ring-step reconstruction needed); second all-to-all: back
    to seq-sharded.
    """
    n = mesh.shape[axis]
    scale = 1.0 / np.sqrt(q.shape[-1])
    # After the layout swap every device holds the FULL sequence.
    use_flash = _resolve_flash(None, q.shape[2], q.shape[2], q.shape[3])
    interpret = pallas_interpret(mesh.devices.flat)

    def local(q_blk, k_blk, v_blk):
        # [B, H, S/n, D] -> [B, H/n, S, D]
        def seq_to_head(x):
            return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                      tiled=True)

        def head_to_seq(x):
            return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                      tiled=True)

        qh, kh, vh = seq_to_head(q_blk), seq_to_head(k_blk), seq_to_head(v_blk)
        S = qh.shape[2]
        if use_flash:
            from multiverso_tpu.ops.pallas_attention import flash_block_attn
            # Causal mask computed in-kernel (offsets zero: full sequence).
            o, _, l = flash_block_attn(
                qh, kh, vh, scale=float(scale), causal=causal,
                interpret=interpret, vma=(axis,))
            o = (o / jnp.maximum(l, 1e-20)).astype(qh.dtype)
        else:
            s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * scale
            if causal:
                mask = jnp.tril(jnp.ones((S, S), dtype=bool))
                s = jnp.where(mask[None, None], s, jnp.finfo(s.dtype).min)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhqk,bhkd->bhqd", p, vh)
        return head_to_seq(o)

    spec = P(None, None, axis, None)
    fn = jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=not use_flash)
    return fn(q, k, v)


def reference_attention(q, k, v):
    """Dense single-device reference for testing."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)
