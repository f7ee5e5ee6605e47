"""Causal grouped-query, latent and EVA attention, forward and backward in
blocks.

Neither pass ever holds the ``heads x S x S`` scores: the forward keeps a
running maximum, normaliser and output per query block while it walks the
key blocks it sees (the merge of ``parallel/sequence.py``), and saves the
output and the log-normaliser; the backward walks the same block pairs
again, recomputing each block's probabilities from the saved log-normaliser.
Blocks a query block does not see are never visited.
The walk has two planes under ONE ``jax.custom_vjp`` that saves ``(q, k, v,
out, lse)``: plain ``jax.numpy``, which XLA compiles for the device and which
serves every shape; and, for the plain causal call of float32 arrays on one
device whose heads fill whole lane tiles (:func:`~multiverso_tpu.ops.
pallas_causal_attention.attention_kernel_selected`), the Pallas forward and
backward of ``ops/pallas_causal_attention.py``, which keep a pair of tiles'
scores and the gradient accumulators in VMEM. Both run under the scope
``lm_attn_pairs``.
The values may be narrower or wider than the queries and keys, and the
caller may give the softmax scale: latent attention (:func:`latent_attention_
mixer`) has 192-wide rotary-carrying keys against 128-wide values.

What a query block sees is one of three shapes. Plain causal (``*``, ``L``):
every key block at or before it. Chosen (``S``, :func:`sparse_attention`):
causal, and of those keys the ones in the small key blocks a per-query mask
names, which is DATA (each query and key-value head chooses its blocks from
scores the layer computes itself, :func:`sparse_select`); the mask decides
what is attended, not what is computed: the walk is the causal one. Windowed
with remote keys (EVA, :func:`eva_attention`): the key blocks of its own
aligned window at or before it, and every key of a second, dense key set,
grouped a window's worth to a block, that belongs to an EARLIER window; one
softmax over both. The second set's keys are not positions of the sequence
(EVA: pooled summaries of chunks), so its gradients are sums over every later
window's queries.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from multiverso_tpu.models.hybrid_lm import rope
from multiverso_tpu.models.hybrid_lm.norm import rmsnorm
from multiverso_tpu.ops import pallas_causal_attention

__all__ = ["causal_gqa", "attention_mixer", "latent_attention_mixer",
           "eva_attention", "eva_summaries", "eva_mixer", "in_blocks",
           "sparse_select", "sparse_attention", "sparse_mixer",
           "output_gate"]


def _scores(qi, kj, i, j, blk, scale, chosen=None):
    """Masked scores of query block ``i`` against key block ``j``:
    ``qi`` [B, blk, K, G, D], ``kj`` [B, blk, K, D] -> [B, K, G, blk, blk].
    ``chosen`` [nb, B, K, blk, small key blocks] bool: beside the causal
    mask, what each query of each key-value head chose."""
    s = jnp.einsum("bqkgd,bskd->bkgqs", qi, kj) * scale
    rows = i * blk + jnp.arange(blk)
    cols = j * blk + jnp.arange(blk)
    seen = rows[:, None] >= cols[None, :]
    if chosen is not None:
        per = chosen.shape[-1] // chosen.shape[0]
        mine = jax.lax.dynamic_slice_in_dim(chosen[i], j * per, per, axis=-1)
        seen = seen & jnp.repeat(mine, blk // per, axis=-1)[:, :, None]
    return jnp.where(seen, s, -jnp.inf)


def _remote_scores(qi, kr, scale):
    """Scores against a block of remote keys ``kr`` [B, R, K, D], all of
    them seen: [B, K, G, blk, R]."""
    return jnp.einsum("bqkgd,bskd->bkgqs", qi, kr) * scale


def _block(x, i):
    return jax.lax.dynamic_index_in_dim(x, i, axis=1, keepdims=False)


def _forward(q, k, v, remote, chosen, scale, blk, span):
    """``q`` [B, nb, blk, K, G, D], ``k`` [B, nb, blk, K, D], ``v``
    [B, nb, blk, K, Dv] -> (out [B, nb, blk, K, G, Dv], lse [nb, B, K, G,
    blk]). ``span`` None: query block ``i`` sees key blocks ``0..i``, all of
    their keys at or before each query or, with ``chosen`` (:func:`_scores`),
    those of them a query chose: every query has to have chosen key 0. Else a
    window is ``span`` blocks: it sees those of its window, ``i // span *
    span .. i``, and of ``remote`` = (keys [B, nw, R, K, D], values [B, nw,
    R, K, Dv]) the blocks ``0 .. i // span - 1``, unmasked."""
    bsz, nb, _, kh, g, _ = q.shape
    d = v.shape[-1]

    def query_block(i):
        qi = _block(q, i)

        def merge(carry, s, values, j):
            m, l, acc = carry
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p, _block(values, j))
            return m_new, l * alpha + jnp.sum(p, axis=-1), acc

        def key_block(j, carry):
            return merge(carry, _scores(qi, _block(k, j), i, j, blk, scale,
                                        chosen), v, j)

        carry = jax.lax.fori_loop(
            0 if span is None else i // span * span, i + 1, key_block, (
                jnp.full((bsz, kh, g, blk), -jnp.inf, jnp.float32),
                jnp.zeros((bsz, kh, g, blk), jnp.float32),
                jnp.zeros((bsz, kh, g, blk, d), jnp.float32)))
        if remote is not None:
            # after the window's own keys: the running maximum is finite
            carry = jax.lax.fori_loop(
                0, i // span, lambda r, carry: merge(
                    carry, _remote_scores(qi, _block(remote[0], r), scale),
                    remote[1], r), carry)
        m, l, acc = carry
        out = jnp.einsum("bkgqd->bqkgd", acc / l[..., None])
        return out.astype(q.dtype), m + jnp.log(l)

    out, lse = jax.lax.map(query_block, jnp.arange(nb))
    return jnp.moveaxis(out, 0, 1), lse


def _backward(q, k, v, remote, chosen, out, lse, dout, scale, blk, span):
    nb = q.shape[1]
    delta = jnp.einsum("bnqkgd,bnqkgd->nbkgq", dout.astype(jnp.float32),
                       out.astype(jnp.float32))

    def pull(scores, kj, vj):
        """One block pair's share of the gradients, given the pair's scores:
        ``(i, (dq, dkj, dvj)) -> (dq, dkj, dvj)``."""
        def query_block(i, carry):
            dq, dkj, dvj = carry
            qi, doi = _block(q, i), _block(dout, i)
            p = jnp.exp(scores(qi, i) - lse[i][..., None])
            dvj = dvj + jnp.einsum("bkgqs,bqkgd->bskd", p, doi)
            dp = jnp.einsum("bqkgd,bskd->bkgqs", doi, vj)
            ds = p * (dp - delta[i][..., None]) * scale
            dqi = _block(dq, i) + jnp.einsum("bkgqs,bskd->bqkgd", ds, kj)
            dq = jax.lax.dynamic_update_index_in_dim(dq, dqi, i, axis=1)
            return dq, dkj + jnp.einsum("bkgqs,bqkgd->bskd", ds, qi), dvj

        return query_block

    def key_block(dq, j):
        kj, vj = _block(k, j), _block(v, j)
        dq, dkj, dvj = jax.lax.fori_loop(
            j, nb if span is None else jnp.minimum(nb, (j // span + 1) * span),
            pull(lambda qi, i: _scores(qi, kj, i, j, blk, scale, chosen),
                 kj, vj),
            (dq, jnp.zeros_like(kj), jnp.zeros_like(vj)))
        return dq, (dkj, dvj)

    dq, (dk, dv) = jax.lax.scan(key_block, jnp.zeros_like(q),
                                jnp.arange(nb))
    dk, dv = jnp.moveaxis(dk, 0, 1), jnp.moveaxis(dv, 0, 1)
    if remote is None:
        return dq, dk, dv, None, None

    def remote_block(dq, r):
        # seen by every query block of every LATER window
        kr, vr = _block(remote[0], r), _block(remote[1], r)
        dq, dkr, dvr = jax.lax.fori_loop(
            (r + 1) * span, nb,
            pull(lambda qi, i: _remote_scores(qi, kr, scale), kr, vr),
            (dq, jnp.zeros_like(kr), jnp.zeros_like(vr)))
        return dq, (dkr, dvr)

    dq, (dkr, dvr) = jax.lax.scan(remote_block, dq,
                                  jnp.arange(remote[0].shape[1]))
    return (dq, dk, dv, (jnp.moveaxis(dkr, 0, 1), jnp.moveaxis(dvr, 0, 1)),
            None)


def _fused(q, k, v, remote, chosen, blk, interpret) -> bool:
    """Whether the call is the kernels' (:mod:`multiverso_tpu.ops.
    pallas_causal_attention`): ``interpret`` is None unless the caller knows
    the arrays to live on one device."""
    selected = pallas_causal_attention.attention_kernel_selected
    return interpret is not None and selected(
        q.shape[1] * blk, blk, *q.shape[3:], v.shape[-1], q.dtype, k.dtype,
        v.dtype, remote=remote, chosen=chosen)


def _walk(q, k, v, remote, chosen, scale, blk, span, interpret):
    """(out, lse) on the plane the call's arrays choose; ``lse`` is that
    plane's own, for its backward."""
    with jax.named_scope("lm_attn_pairs"):
        if _fused(q, k, v, remote, chosen, blk, interpret):
            return pallas_causal_attention.forward(q, k, v, scale, blk, span,
                                                   interpret)
        return _forward(q, k, v, remote, chosen, scale, blk, span)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _blocked_attention(q, k, v, remote, chosen, scale, blk, span,
                       interpret=None):
    return _walk(q, k, v, remote, chosen, scale, blk, span, interpret)[0]


def _vjp_fwd(q, k, v, remote, chosen, scale, blk, span, interpret):
    out, lse = _walk(q, k, v, remote, chosen, scale, blk, span, interpret)
    return out, (q, k, v, remote, chosen, out, lse)


def _vjp_bwd(scale, blk, span, interpret, saved, dout):
    q, k, v, remote, chosen, out, lse = saved
    with jax.named_scope("lm_attn_pairs"):
        if _fused(q, k, v, remote, chosen, blk, interpret):
            return pallas_causal_attention.backward(
                q, k, v, out, lse, dout, scale, blk, span, interpret) + (
                    None, None)
        return _backward(q, k, v, remote, chosen, out, lse, dout, scale,
                         blk, span)


_blocked_attention.defvjp(_vjp_fwd, _vjp_bwd)


def in_blocks(x, blk):
    """``x`` [B, S, ...] -> [B, blocks, blk, ...], zero-padded at the end."""
    pad = (-x.shape[1]) % blk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
    return x.reshape((x.shape[0], x.shape[1] // blk, blk) + x.shape[2:])


def causal_gqa(q: jax.Array, k: jax.Array, v: jax.Array, block: int,
               scale: float = None,
               interpret: Optional[bool] = None) -> jax.Array:
    """``q`` [B, S, K, G, D] (G query heads share each of K key-value
    heads), ``k`` [B, S, K, D], ``v`` [B, S, K, Dv] -> [B, S, K, G, Dv];
    softmax over the keys at or before each query, scale ``D ** -0.5``
    unless given. Any S: padded keys lie after every real query, padded
    queries are cut away. ``interpret``: None unless the caller knows the
    arrays to live on one device, then :func:`multiverso_tpu.ops.
    pallas_interpret` of it: shapes the kernels take (:func:`~multiverso_tpu.
    ops.pallas_causal_attention.attention_kernel_selected`) then run as
    those, a pair of tiles' scores in VMEM."""
    bsz, s, kh, g, d = q.shape
    blk = min(block, s)
    out = _blocked_attention(
        in_blocks(q, blk), in_blocks(k, blk), in_blocks(v, blk), None, None,
        float(d) ** -0.5 if scale is None else float(scale), blk, None,
        interpret)
    return out.reshape(bsz, -1, kh, g, v.shape[-1])[:, :s]


def eva_summaries(k: jax.Array, v: jax.Array, phi: jax.Array, mu: jax.Array,
                  chunk: int, scale: float):
    """One pooled key and value per chunk of ``chunk`` positions: ``k``, ``v``
    [B, S, H, D] (S a whole number of chunks; ``k`` already turned by the
    rotary) and a head's learned ``phi``, ``mu`` [H, D] -> ``k~ = sum_j p_j
    k_j + mu``, ``v~ = sum_j p_j v_j`` [B, S / chunk, H, D] with ``p =
    softmax_j(scale * phi . k_j)`` over the chunk."""
    bsz, s, h, d = k.shape
    kc = k.reshape(bsz, s // chunk, chunk, h, d)
    vc = v.reshape(bsz, s // chunk, chunk, h, v.shape[-1])
    p = jax.nn.softmax(jnp.einsum("bnchd,hd->bnch", kc, phi) * scale, axis=2)
    return (jnp.einsum("bnch,bnchd->bnhd", p, kc) + mu,
            jnp.einsum("bnch,bnchd->bnhd", p, vc))


def eva_attention(q: jax.Array, k: jax.Array, v: jax.Array, phi: jax.Array,
                  mu: jax.Array, window: int, chunk: int,
                  block: int) -> jax.Array:
    """EVA as ``evabyte`` fixes it (no sampling, aligned windows, summaries
    only across windows): ``q``, ``k``, ``v`` [B, S, H, D], ``q`` and ``k``
    turned; query ``t`` of window ``w = t // window`` sees the keys ``w *
    window .. t`` one by one and the summary (:func:`eva_summaries`) of every
    chunk of every window before ``w``, in ONE softmax of scale ``D ** -0.5``.
    Any S: positions padded up to a whole window lie in the last window,
    after every real query, and no query sees the last window's summaries;
    up to one window there is no summary and this is causal attention."""
    bsz, s, h, d = q.shape
    scale = float(d) ** -0.5
    if s <= window:
        with jax.named_scope("lm_eva_agg"):
            return causal_gqa(q[:, :, :, None], k, v, block, scale)[:, :, :, 0]
    blk = min(block, window)
    qw, kw, vw = (in_blocks(t, window) for t in (q, k, v))
    nw = qw.shape[1]
    with jax.named_scope("lm_eva_prep"):
        rk, rv = eva_summaries(kw.reshape(bsz, nw * window, h, d),
                               vw.reshape(bsz, nw * window, h, v.shape[-1]),
                               phi, mu, chunk, scale)
        remote = (in_blocks(rk, window // chunk),
                  in_blocks(rv, window // chunk))
    with jax.named_scope("lm_eva_agg"):
        out = _blocked_attention(
            qw.reshape(bsz, -1, blk, h, 1, d), kw.reshape(bsz, -1, blk, h, d),
            vw.reshape(bsz, -1, blk, h, v.shape[-1]), remote, None, scale,
            blk, window // blk)
    return out.reshape(bsz, nw * window, h, v.shape[-1])[:, :s]


def eva_mixer(p: dict, n: jax.Array, cfg) -> jax.Array:
    """No bias; queries and keys turned over the whole head in the published
    half layout (``rotate_half``), plain ``rope_theta``, positions from the
    start of the packed sequence; the summaries pool keys already turned."""
    bsz, s, _ = n.shape
    h, d = cfg.num_attention_heads, cfg.head_dim
    cos, sin = rope.rope_tables(s, d, cfg.rope_theta, None)
    q = rope.apply_rope((n @ p["wq"]).reshape(bsz, s, h, d), cos, sin, True)
    k = rope.apply_rope((n @ p["wk"]).reshape(bsz, s, h, d), cos, sin, True)
    v = (n @ p["wv"]).reshape(bsz, s, h, d)
    o = eva_attention(q, k, v, p["adaptive_phi"], p["adaptive_mu_k"],
                      cfg.window_size, cfg.eva_chunk_size, cfg.attn_block)
    return o.reshape(bsz, s, h * d) @ p["wo"]


def sparse_select(q: jax.Array, k: jax.Array, length: int, cfg) -> jax.Array:
    """What each query attends, chosen by the layer itself (InfLLM-v2; no
    parameter, no gradient): ``q`` [B, nb, blk, K, G, D], ``k`` [B, nb * blk,
    K, D] (zero past ``length``) -> [nb, B, K, blk, key blocks] bool over key
    blocks of ``sparse_block_size``.

    A pooled key ``m`` is the mean of the ``sparse_kernel_size`` keys from
    ``sparse_kernel_stride * m`` on, for every ``m`` whose positions all
    exist; query ``t`` sees it once its last position is at or before ``t``.
    ``softmax_m(scale q_t . kc_m)`` over the seen ``m``, summed over the G
    heads of a key-value head, is a pooled key's weight; a key block's score
    is the largest weight of the pooled keys that reach into it. FORCED are
    the first ``sparse_init_blocks`` blocks and every block that holds one of
    the query's last ``sparse_window_size`` positions; CHOSEN are the
    ``sparse_topk`` highest-scoring blocks that lie wholly before those
    positions and are not forced (all of them if fewer; ties to the lower
    block). A query block at a time: the weights of one are [B, K, G, blk,
    pooled keys] float32, at ``highest`` precision (a choice flips on less
    than a bfloat16 product's rounding)."""
    q, k = jax.lax.stop_gradient(q), jax.lax.stop_gradient(k)
    bsz, nb, blk, kh, g, d = q.shape
    size, stride, cb = (cfg.sparse_kernel_size, cfg.sparse_kernel_stride,
                        cfg.sparse_block_size)
    per, before = cfg.sparse_pool
    nkb = nb * blk // cb
    pooled = max((length - size) // stride + 1, 0)
    parts = k[:, :(pooled + size // stride - 1) * stride].reshape(
        bsz, -1, stride, kh, d).sum(axis=2)
    kc = sum(parts[:, o:o + pooled] for o in range(size // stride)) / size
    m_end = stride * jnp.arange(pooled) + size - 1      # a pooled key's last
    blocks = jnp.arange(nkb)[None, :]
    first, last = cb * blocks, cb * blocks + cb - 1     # a key block's keys
    scale = float(d) ** -0.5

    def query_block(xs):
        i, qi = xs
        t = i * blk + jnp.arange(blk)
        seen = m_end[None, :] <= t[:, None]
        logits = scale * jnp.einsum("bqkgd,bmkd->bkgqm", qi, kc,
                                    precision=jax.lax.Precision.HIGHEST)
        top = jnp.max(jnp.where(seen, logits, -jnp.inf), axis=-1,
                      keepdims=True)
        e = jnp.where(seen, jnp.exp(logits - jnp.where(
            jnp.isfinite(top), top, 0.0)), 0.0)
        weight = jnp.sum(e / jnp.maximum(
            jnp.sum(e, axis=-1, keepdims=True), 1e-30), axis=2)
        # pooled keys ``per * b - before .. per * b + per - 1`` reach into
        # key block b: the largest seen weight among them
        reach = jnp.pad(jnp.where(seen, weight, -jnp.inf),
                        ((0, 0),) * 3 + ((before, per * nkb - pooled),),
                        constant_values=-jnp.inf)
        score = functools.reduce(jnp.maximum, [
            reach[..., o::per][..., :nkb] for o in range(per + before)])
        recent = t[:, None] - (cfg.sparse_window_size - 1)
        forced = (first <= t[:, None]) & (
            (blocks < cfg.sparse_init_blocks) | (last >= recent))
        free = (last < recent) & (blocks >= cfg.sparse_init_blocks)
        values, picked = jax.lax.top_k(jnp.where(free, score, -jnp.inf),
                                       min(cfg.sparse_topk, nkb))
        chosen = jnp.any((picked[..., None] == blocks[0])
                         & jnp.isfinite(values)[..., None], axis=-2)
        return forced | chosen

    return jax.lax.map(query_block, (jnp.arange(nb), jnp.moveaxis(q, 1, 0)))


def sparse_attention(q: jax.Array, k: jax.Array, v: jax.Array, cfg):
    """``q`` [B, S, K, G, D], ``k``, ``v`` [B, S, K, D] -> (out [B, S, K, G,
    D], chosen, pairs). Up to ``sparse_dense_len`` positions: causal attention,
    nothing chosen (``chosen`` None). Longer: one softmax, scale ``D ** -0.5``,
    over the keys at or before a query in the blocks :func:`sparse_select`
    names for it; ``chosen`` [B, K, S, key blocks] bool says which.
    ``pairs`` int32: the (query, key) pairs a head attended (the mean over
    the key-value heads)."""
    bsz, s, kh, g, d = q.shape
    scale = float(d) ** -0.5
    if s <= cfg.sparse_dense_len:
        with jax.named_scope("lm_sparse_attn"):
            out = causal_gqa(q, k, v, cfg.attn_block, scale)
        return out, None, jnp.int32(bsz * s * (s + 1) // 2)
    blk, cb = cfg.attn_block, cfg.sparse_block_size
    qb, kb, vb = (in_blocks(x, blk) for x in (q, k, v))
    nb = qb.shape[1]
    with jax.named_scope("lm_sparse_select"):
        chosen = sparse_select(qb, kb.reshape(bsz, nb * blk, kh, d), s, cfg)
        t = jnp.arange(nb * blk)
        keys = jnp.clip(t[:, None] + 1 - cb * jnp.arange(nb * blk // cb), 0,
                        cb) * (t[:, None] < s)          # of a block, <= t
        pairs = (jnp.sum(chosen * keys.reshape(nb, 1, 1, blk, -1))
                 // kh).astype(jnp.int32)
    with jax.named_scope("lm_sparse_attn"):
        out = _blocked_attention(qb, kb, vb, None, chosen, scale, blk, None)
    chosen = jnp.moveaxis(chosen, 0, 2).reshape(bsz, kh, nb * blk, -1)
    return (out.reshape(bsz, nb * blk, kh, g, d)[:, :s],
            chosen[:, :, :s, :-(-s // cb)], pairs)


def output_gate(o: jax.Array, n: jax.Array, wg: jax.Array) -> jax.Array:
    """A mixer's output ``o`` [B, S, width] times ``sigmoid(n Wg)``, ``n`` the
    block's normed input: what the sparse and the Lightning mixer put before
    their output projection."""
    return o * jax.nn.sigmoid(n @ wg)


def sparse_mixer(p: dict, n: jax.Array, cfg):
    """Block-sparse grouped-query attention (``minicpm4``): no bias, NO
    positions, an RMSNorm over each query and key head (one weight vector of
    ``head_dim`` each, shared by the heads), :func:`sparse_attention`, then a
    gate ``sigmoid(n Wg)`` on its output before the output projection.
    Returns (output, chosen, pairs)."""
    bsz, s, _ = n.shape
    kh = cfg.num_key_value_heads
    g = cfg.num_attention_heads // kh
    q = rmsnorm((n @ p["wq"]).reshape(bsz, s, kh, g, cfg.head_dim),
                p["q_norm"], cfg.norm_eps)
    k = rmsnorm((n @ p["wk"]).reshape(bsz, s, kh, cfg.head_dim),
                p["k_norm"], cfg.norm_eps)
    v = (n @ p["wv"]).reshape(bsz, s, kh, cfg.head_dim)
    o, chosen, pairs = sparse_attention(q, k, v, cfg)
    o = output_gate(o.reshape(bsz, s, cfg.q_dim), n, p["wg"])
    return o @ p["wo"], chosen, pairs


def attention_mixer(p: dict, n: jax.Array, cfg,
                    attn_interpret: Optional[bool] = None) -> jax.Array:
    """Causal grouped-query attention, no bias. As the configuration says:
    with ``attn_qk_norm`` an RMSNorm over each query and key head (one weight
    vector of ``head_dim`` each, shared by the heads); with ``attn_rope`` both
    then turned over the whole head in the half layout (``rotate_half``),
    plain ``rope_theta``, positions from the start of the packed sequence.
    With neither the block has no positions (the published ``nemotron_h`` code
    applies none) and is the function it was before the switches.
    ``attn_interpret``: :func:`causal_gqa`'s ``interpret``."""
    bsz, s, _ = n.shape
    kh, hd = cfg.num_key_value_heads, cfg.head_dim
    g = cfg.num_attention_heads // kh
    if cfg.attn_rope:
        cos, sin = rope.rope_tables(s, hd, cfg.rope_theta, None)

    def heads(w, norm, shape):
        x = n @ p[w]
        if cfg.attn_qk_norm:
            x = rmsnorm(x.reshape(bsz, s, -1, hd), p[norm], cfg.norm_eps)
        if cfg.attn_rope:
            x = rope.apply_rope(x.reshape(bsz, s, -1, hd), cos, sin, True)
        return x.reshape(shape)

    q = heads("wq", "q_norm", (bsz, s, kh, g, hd))
    k = heads("wk", "k_norm", (bsz, s, kh, hd))
    v = (n @ p["wv"]).reshape(bsz, s, kh, cfg.head_dim)
    o = causal_gqa(q, k, v, cfg.attn_block, interpret=attn_interpret)
    return o.reshape(bsz, s, cfg.q_dim) @ p["wo"]


def latent_attention_mixer(p: dict, n: jax.Array, cfg,
                           attn_interpret: Optional[bool] = None
                           ) -> jax.Array:
    """Multi-head latent attention in its training form: keys and values
    are expanded from a ``kv_lora_rank``-wide latent (RMSNorm'd), queries
    come straight from the input (no query latent). A head's query and key
    are ``[nope | rope]``; the rotary key is ONE vector a token, shared by
    all heads, and positions count from the start of the packed sequence.
    The softmax scale carries YaRN's ``mscale ** 2`` (:mod:`.rope`).
    ``attn_interpret``: :func:`causal_gqa`'s ``interpret``."""
    bsz, s, _ = n.shape
    h, nope, rot = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                    cfg.qk_rope_head_dim)
    cos, sin = rope.rope_tables(s, rot, cfg.rope_theta, cfg.rope_scaling)
    q = (n @ p["wq"]).reshape(bsz, s, h, nope + rot)
    q = jnp.concatenate(
        [q[..., :nope], rope.apply_rope(q[..., nope:], cos, sin)], axis=-1)
    kva = n @ p["wkva"]
    latent = rmsnorm(kva[..., :cfg.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rot = rope.apply_rope(kva[..., None, cfg.kv_lora_rank:], cos, sin)
    kv = (latent @ p["wkvb"]).reshape(bsz, s, h, nope + cfg.v_head_dim)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rot, (bsz, s, h, rot))], axis=-1)
    o = causal_gqa(q[:, :, :, None, :], k, kv[..., nope:], cfg.attn_block,
                   rope.softmax_scale(nope + rot, cfg.rope_scaling),
                   attn_interpret)
    return o.reshape(bsz, s, h * cfg.v_head_dim) @ p["wo"]
