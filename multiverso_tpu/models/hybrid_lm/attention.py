"""Causal grouped-query and latent attention, forward and backward in blocks.

Neither pass ever holds the ``heads x S x S`` scores: the forward keeps a
running maximum, normaliser and output per query block while it walks the
key blocks at or before it (the merge of ``parallel/sequence.py``), and
saves the output and the log-normaliser; the backward walks the same block
pairs again, recomputing each block's probabilities from the saved
log-normaliser. Blocks above the diagonal are never visited.
``ops/pallas_attention.flash_block_attn`` has no backward; this is plain
``jax.numpy`` under ``jax.custom_vjp``, which XLA compiles for the device.
The values may be narrower or wider than the queries and keys, and the
caller may give the softmax scale: latent attention (:func:`latent_attention_
mixer`) has 192-wide rotary-carrying keys against 128-wide values.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from multiverso_tpu.models.hybrid_lm import rope
from multiverso_tpu.models.hybrid_lm.norm import rmsnorm

__all__ = ["causal_gqa", "attention_mixer", "latent_attention_mixer"]


def _scores(qi, kj, i, j, blk, scale):
    """Masked scores of query block ``i`` against key block ``j``:
    ``qi`` [B, blk, K, G, D], ``kj`` [B, blk, K, D] -> [B, K, G, blk, blk]."""
    s = jnp.einsum("bqkgd,bskd->bkgqs", qi, kj) * scale
    rows = i * blk + jnp.arange(blk)
    cols = j * blk + jnp.arange(blk)
    return jnp.where(rows[:, None] >= cols[None, :], s, -jnp.inf)


def _block(x, i):
    return jax.lax.dynamic_index_in_dim(x, i, axis=1, keepdims=False)


def _forward(q, k, v, scale, blk):
    """``q`` [B, nb, blk, K, G, D], ``k`` [B, nb, blk, K, D], ``v``
    [B, nb, blk, K, Dv] -> (out [B, nb, blk, K, G, Dv], lse [nb, B, K, G,
    blk])."""
    bsz, nb, _, kh, g, _ = q.shape
    d = v.shape[-1]

    def query_block(i):
        qi = _block(q, i)

        def key_block(j, carry):
            m, l, acc = carry
            s = _scores(qi, _block(k, j), i, j, blk, scale)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p, _block(v, j))
            return m_new, l * alpha + jnp.sum(p, axis=-1), acc

        m, l, acc = jax.lax.fori_loop(0, i + 1, key_block, (
            jnp.full((bsz, kh, g, blk), -jnp.inf, jnp.float32),
            jnp.zeros((bsz, kh, g, blk), jnp.float32),
            jnp.zeros((bsz, kh, g, blk, d), jnp.float32)))
        out = jnp.einsum("bkgqd->bqkgd", acc / l[..., None])
        return out.astype(q.dtype), m + jnp.log(l)

    out, lse = jax.lax.map(query_block, jnp.arange(nb))
    return jnp.moveaxis(out, 0, 1), lse


def _backward(q, k, v, out, lse, dout, scale, blk):
    nb = q.shape[1]
    delta = jnp.einsum("bnqkgd,bnqkgd->nbkgq", dout.astype(jnp.float32),
                       out.astype(jnp.float32))

    def key_block(dq, j):
        kj, vj = _block(k, j), _block(v, j)

        def query_block(i, carry):
            dq, dkj, dvj = carry
            qi, doi = _block(q, i), _block(dout, i)
            p = jnp.exp(_scores(qi, kj, i, j, blk, scale)
                        - lse[i][..., None])
            dvj = dvj + jnp.einsum("bkgqs,bqkgd->bskd", p, doi)
            dp = jnp.einsum("bqkgd,bskd->bkgqs", doi, vj)
            ds = p * (dp - delta[i][..., None]) * scale
            dqi = _block(dq, i) + jnp.einsum("bkgqs,bskd->bqkgd", ds, kj)
            dq = jax.lax.dynamic_update_index_in_dim(dq, dqi, i, axis=1)
            return dq, dkj + jnp.einsum("bkgqs,bqkgd->bskd", ds, qi), dvj

        dq, dkj, dvj = jax.lax.fori_loop(
            j, nb, query_block, (dq, jnp.zeros_like(kj), jnp.zeros_like(vj)))
        return dq, (dkj, dvj)

    dq, (dk, dv) = jax.lax.scan(key_block, jnp.zeros_like(q),
                                jnp.arange(nb))
    return dq, jnp.moveaxis(dk, 0, 1), jnp.moveaxis(dv, 0, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _blocked_attention(q, k, v, scale, blk):
    return _forward(q, k, v, scale, blk)[0]


def _vjp_fwd(q, k, v, scale, blk):
    out, lse = _forward(q, k, v, scale, blk)
    return out, (q, k, v, out, lse)


def _vjp_bwd(scale, blk, saved, dout):
    q, k, v, out, lse = saved
    return _backward(q, k, v, out, lse, dout, scale, blk)


_blocked_attention.defvjp(_vjp_fwd, _vjp_bwd)


def causal_gqa(q: jax.Array, k: jax.Array, v: jax.Array, block: int,
               scale: float = None) -> jax.Array:
    """``q`` [B, S, K, G, D] (G query heads share each of K key-value
    heads), ``k`` [B, S, K, D], ``v`` [B, S, K, Dv] -> [B, S, K, G, Dv];
    softmax over the keys at or before each query, scale ``D ** -0.5``
    unless given. Any S: padded keys lie after every real query, padded
    queries are cut away."""
    bsz, s, kh, g, d = q.shape
    blk = min(block, s)
    pad = (-s) % blk
    if pad:
        q, k, v = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                   for t in (q, k, v))
    nb = (s + pad) // blk
    out = _blocked_attention(
        q.reshape(bsz, nb, blk, kh, g, d), k.reshape(bsz, nb, blk, kh, d),
        v.reshape(bsz, nb, blk, kh, v.shape[-1]),
        float(d) ** -0.5 if scale is None else float(scale), blk)
    return out.reshape(bsz, nb * blk, kh, g, v.shape[-1])[:, :s]


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
