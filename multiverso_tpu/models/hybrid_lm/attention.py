"""Causal grouped-query, latent and EVA attention, forward and backward in
blocks.

Neither pass ever holds the ``heads x S x S`` scores: the forward keeps a
running maximum, normaliser and output per query block while it walks the
key blocks it sees (the merge of ``parallel/sequence.py``), and saves the
output and the log-normaliser; the backward walks the same block pairs
again, recomputing each block's probabilities from the saved log-normaliser.
Blocks a query block does not see are never visited.
``ops/pallas_attention.flash_block_attn`` has no backward; this is plain
``jax.numpy`` under ``jax.custom_vjp``, which XLA compiles for the device.
The values may be narrower or wider than the queries and keys, and the
caller may give the softmax scale: latent attention (:func:`latent_attention_
mixer`) has 192-wide rotary-carrying keys against 128-wide values.

What a query block sees is one of two shapes. Plain causal (``*``, ``L``):
every key block at or before it. Windowed with remote keys (EVA,
:func:`eva_attention`): the key blocks of its own aligned window at or before
it, and every key of a second, dense key set, grouped a window's worth to a
block, that belongs to an EARLIER window; one softmax over both. The second
set's keys are not positions of the sequence (EVA: pooled summaries of
chunks), so its gradients are sums over every later window's queries.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from multiverso_tpu.models.hybrid_lm import rope
from multiverso_tpu.models.hybrid_lm.norm import rmsnorm

__all__ = ["causal_gqa", "attention_mixer", "latent_attention_mixer",
           "eva_attention", "eva_summaries", "eva_mixer", "in_blocks"]


def _scores(qi, kj, i, j, blk, scale):
    """Masked scores of query block ``i`` against key block ``j``:
    ``qi`` [B, blk, K, G, D], ``kj`` [B, blk, K, D] -> [B, K, G, blk, blk]."""
    s = jnp.einsum("bqkgd,bskd->bkgqs", qi, kj) * scale
    rows = i * blk + jnp.arange(blk)
    cols = j * blk + jnp.arange(blk)
    return jnp.where(rows[:, None] >= cols[None, :], s, -jnp.inf)


def _remote_scores(qi, kr, scale):
    """Scores against a block of remote keys ``kr`` [B, R, K, D], all of
    them seen: [B, K, G, blk, R]."""
    return jnp.einsum("bqkgd,bskd->bkgqs", qi, kr) * scale


def _block(x, i):
    return jax.lax.dynamic_index_in_dim(x, i, axis=1, keepdims=False)


def _forward(q, k, v, remote, scale, blk, span):
    """``q`` [B, nb, blk, K, G, D], ``k`` [B, nb, blk, K, D], ``v``
    [B, nb, blk, K, Dv] -> (out [B, nb, blk, K, G, Dv], lse [nb, B, K, G,
    blk]). ``span`` None: query block ``i`` sees key blocks ``0..i``. Else a
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
            return merge(carry, _scores(qi, _block(k, j), i, j, blk, scale),
                         v, j)

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


def _backward(q, k, v, remote, out, lse, dout, scale, blk, span):
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
            pull(lambda qi, i: _scores(qi, kj, i, j, blk, scale), kj, vj),
            (dq, jnp.zeros_like(kj), jnp.zeros_like(vj)))
        return dq, (dkj, dvj)

    dq, (dk, dv) = jax.lax.scan(key_block, jnp.zeros_like(q),
                                jnp.arange(nb))
    dk, dv = jnp.moveaxis(dk, 0, 1), jnp.moveaxis(dv, 0, 1)
    if remote is None:
        return dq, dk, dv, None

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
    return dq, dk, dv, (jnp.moveaxis(dkr, 0, 1), jnp.moveaxis(dvr, 0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _blocked_attention(q, k, v, remote, scale, blk, span):
    return _forward(q, k, v, remote, scale, blk, span)[0]


def _vjp_fwd(q, k, v, remote, scale, blk, span):
    out, lse = _forward(q, k, v, remote, scale, blk, span)
    return out, (q, k, v, remote, out, lse)


def _vjp_bwd(scale, blk, span, saved, dout):
    q, k, v, remote, out, lse = saved
    return _backward(q, k, v, remote, out, lse, dout, scale, blk, span)


_blocked_attention.defvjp(_vjp_fwd, _vjp_bwd)


def in_blocks(x, blk):
    """``x`` [B, S, ...] -> [B, blocks, blk, ...], zero-padded at the end."""
    pad = (-x.shape[1]) % blk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
    return x.reshape((x.shape[0], x.shape[1] // blk, blk) + x.shape[2:])


def causal_gqa(q: jax.Array, k: jax.Array, v: jax.Array, block: int,
               scale: float = None) -> jax.Array:
    """``q`` [B, S, K, G, D] (G query heads share each of K key-value
    heads), ``k`` [B, S, K, D], ``v`` [B, S, K, Dv] -> [B, S, K, G, Dv];
    softmax over the keys at or before each query, scale ``D ** -0.5``
    unless given. Any S: padded keys lie after every real query, padded
    queries are cut away."""
    bsz, s, kh, g, d = q.shape
    blk = min(block, s)
    out = _blocked_attention(
        in_blocks(q, blk), in_blocks(k, blk), in_blocks(v, blk), None,
        float(d) ** -0.5 if scale is None else float(scale), blk, None)
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
            vw.reshape(bsz, -1, blk, h, v.shape[-1]), remote, scale, blk,
            window // blk)
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


def attention_mixer(p: dict, n: jax.Array, cfg) -> jax.Array:
    """No bias and no positions (the published ``nemotron_h`` code applies
    none)."""
    bsz, s, _ = n.shape
    kh = cfg.num_key_value_heads
    g = cfg.num_attention_heads // kh
    q = (n @ p["wq"]).reshape(bsz, s, kh, g, cfg.head_dim)
    k = (n @ p["wk"]).reshape(bsz, s, kh, cfg.head_dim)
    v = (n @ p["wv"]).reshape(bsz, s, kh, cfg.head_dim)
    o = causal_gqa(q, k, v, cfg.attn_block)
    return o.reshape(bsz, s, cfg.q_dim) @ p["wo"]


def latent_attention_mixer(p: dict, n: jax.Array, cfg) -> jax.Array:
    """Multi-head latent attention in its training form: keys and values
    are expanded from a ``kv_lora_rank``-wide latent (RMSNorm'd), queries
    come straight from the input (no query latent). A head's query and key
    are ``[nope | rope]``; the rotary key is ONE vector a token, shared by
    all heads, and positions count from the start of the packed sequence.
    The softmax scale carries YaRN's ``mscale ** 2`` (:mod:`.rope`)."""
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
                   rope.softmax_scale(nope + rot, cfg.rope_scaling))
    return o.reshape(bsz, s, h * cfg.v_head_dim) @ p["wo"]
