"""The layer-typed LM's block-sparse attention (``S``: every query and
key-value head chooses its key blocks from pooled keys), its Lightning linear
attention (``N``: a fixed decay a head, through ``mamba2.ssd_chunked``), the
output gates and norms and the muP scalings, at a small size on the CPU: the
selection against a loop over queries, the mixers against sums written out,
the whole model against the benchmark's plain reference
(benchmark/reference/minicpm-sala-9b-pp8.py) block by block and over two
AdaGrad steps with a bfloat16 control that fails; the PS plane against its
local twin; counters, scopes and names; the configuration file against the
catalog's row."""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from multiverso_tpu.models.hybrid_lm import (DELTA_PROGRAM, HybridLM,
                                             HybridLMConfig,
                                             dense_param_count, init_buffers,
                                             init_params, layer_forward,
                                             make_loss, pack_batch,
                                             param_shapes)
from multiverso_tpu.models.hybrid_lm.attention import (causal_gqa, in_blocks,
                                                       sparse_attention,
                                                       sparse_select)
from multiverso_tpu.models.hybrid_lm.lightning import (lightning_attention,
                                                       lightning_slopes)
from multiverso_tpu.models.hybrid_lm.mamba2 import ssd_chunked
from multiverso_tpu.telemetry.metrics import get_registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "minicpm-sala-9b-pp8"


def _reference():
    path = os.path.join(ROOT, "benchmark", "reference", CONFIG + ".py")
    spec = importlib.util.spec_from_file_location("sala_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()

# hidden 32; sparse: 4 query / 2 key-value heads of 8, pooled keys of 4 every 2,
# key blocks of 4, a window of 8, 1 initial block, 2 chosen, dense up to 16;
# Lightning: 4 heads of 8 in chunks of 8; a feed-forward of 48; muP 12 / 1.4 /
# 8 over 32 published layers; attention blocks of 8 positions
SMALL = dict(hidden_size=32, vocab_size=40, pattern="SDNDNDND", norm_eps=1e-6,
             num_attention_heads=4, num_key_value_heads=2, head_dim=8,
             sparse_kernel_size=4, sparse_kernel_stride=2, sparse_block_size=4,
             sparse_window_size=8, sparse_init_blocks=1, sparse_topk=2,
             sparse_dense_len=16, lightning_nh=4, lightning_nkv=4,
             lightning_head_dim=8, lightning_chunk=8,
             rope_theta=10000.0, intermediate_size=48, hidden_act="silu",
             scale_emb=12.0, scale_depth=1.4, dim_model_base=8,
             published_layers=32, attn_block=8, loss_block=16, ffn_slab=16,
             row_bucket=16)
TOL = dict(loss=2e-5, grad=2e-4, step=2e-4)


def small(**kw) -> HybridLMConfig:
    return HybridLMConfig(**dict(SMALL, **kw))


def sizes(cfg: HybridLMConfig) -> dict:
    return {"pattern": cfg.pattern, "norm_eps": cfg.norm_eps,
            "num_attention_heads": cfg.num_attention_heads,
            "num_key_value_heads": cfg.num_key_value_heads,
            "lightning_nh": cfg.lightning_nh, "rope_theta": cfg.rope_theta,
            "kernel_size": cfg.sparse_kernel_size,
            "kernel_stride": cfg.sparse_kernel_stride,
            "block_size": cfg.sparse_block_size,
            "window_size": cfg.sparse_window_size,
            "init_blocks": cfg.sparse_init_blocks, "topk": cfg.sparse_topk,
            "dense_len": cfg.sparse_dense_len, "scale_emb": cfg.scale_emb,
            "published_layers": cfg.layers,
            "lightning_published_nh": cfg.lightning_published_nh
            or cfg.lightning_nh,
            "lightning_heads": cfg.lightning_heads or tuple(
                range(1, cfg.lightning_nh + 1)),
            "residual_scale": cfg.residual_scale,
            "logit_divisor": cfg.logit_divisor}


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def worst(got_tree, want_tree) -> float:
    return max(rel(g, w) for g, w in zip(jax.tree_util.tree_leaves(got_tree),
                                         jax.tree_util.tree_leaves(want_tree)))


def batch(cfg, seqs=2, length=37, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, (seqs, length)).astype(np.int32)


def off_start(params, seed=11):
    """Every leaf moved off its start, so that no norm is a special case."""
    rng = np.random.default_rng(seed)
    return jax.tree_util.tree_map(
        lambda w: w + 0.05 * jnp.asarray(rng.standard_normal(w.shape),
                                         jnp.float32), params)


def qkv(length, seed=0, kh=2, g=2, d=8):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((1, length, kh, g, d)),
                        jnp.float32),
            jnp.asarray(rng.standard_normal((1, length, kh, d)), jnp.float32),
            jnp.asarray(rng.standard_normal((1, length, kh, d)), jnp.float32))


# -- the selection against a loop over queries --------------------------------
def chosen_by_loop(q, k, cfg):
    """[K, S, blocks] bool, one query and key-value head at a time, in
    float64: the rule as docs/HYBRID_LM.md words it."""
    q, k = np.asarray(q[0], np.float64), np.asarray(k[0], np.float64)
    length, kh, g, d = q.shape
    size, stride, cb = (cfg.sparse_kernel_size, cfg.sparse_kernel_stride,
                        cfg.sparse_block_size)
    window, topk = cfg.sparse_window_size, cfg.sparse_topk
    nblocks = -(-length // cb)
    pooled = [m for m in range(length) if stride * m + size <= length]
    out = np.zeros((kh, length, nblocks), bool)
    for t in range(length):
        seen = [m for m in pooled if stride * m + size - 1 <= t]
        for head in range(kh):
            weight = np.zeros(len(pooled))
            if seen:
                kc = np.stack([k[stride * m:stride * m + size, head].mean(0)
                               for m in seen])
                logits = q[t, head] @ kc.T * d ** -0.5        # [G, seen]
                p = np.exp(logits - logits.max(-1, keepdims=True))
                weight[seen] = (p / p.sum(-1, keepdims=True)).sum(0)
            scores = {}
            for b in range(nblocks):
                holds_recent = cb * b <= t and cb * b + cb - 1 >= t - window + 1
                if b < cfg.sparse_init_blocks or holds_recent:
                    out[head, t, b] = cb * b <= t
                elif cb * b + cb - 1 < t - window + 1:
                    reach = [m for m in seen if stride * m < cb * (b + 1)
                             and stride * m + size > cb * b]
                    scores[b] = max(weight[m] for m in reach)
            for b in sorted(scores, key=lambda b: (-scores[b], b))[:topk]:
                out[head, t, b] = True
    return out


def program_chosen(q, k, cfg):
    blk = cfg.attn_block
    qb, kb = in_blocks(q, blk), in_blocks(k, blk)
    chosen = sparse_select(qb, kb.reshape((1, -1) + k.shape[2:]), q.shape[1],
                           cfg)
    nblocks = -(-q.shape[1] // cfg.sparse_block_size)
    return np.asarray(jnp.moveaxis(chosen, 0, 2).reshape(
        chosen.shape[2], -1, chosen.shape[-1]))[:, :q.shape[1], :nblocks]


# (positions, chosen blocks a query, seed): a whole number of key blocks and
# of attention blocks; neither (37 = 9 blocks of 4 and one position); more
# blocks asked for than there are candidates, for every query; a long
# sequence in which most queries have more candidates than they choose
@pytest.mark.parametrize("length,topk,seed", [
    (32, 2, 0), (37, 2, 1), (37, 64, 2), (90, 3, 3), (61, 1, 4)])
def test_selection_matches_a_loop_over_queries(length, topk, seed):
    cfg = small(sparse_topk=topk)
    q, k, _ = qkv(length, seed)
    want = chosen_by_loop(q, k, cfg)
    np.testing.assert_array_equal(program_chosen(q, k, cfg), want)
    # the reference chooses by its own code (a sort, not a top-k)
    got = ref.select_blocks(q[0].reshape(length, 4, 8), k[0], sizes(cfg))
    np.testing.assert_array_equal(np.asarray(got), want)
    t = np.arange(length)[:, None]
    first = 4 * np.arange(want.shape[-1])[None, :]
    forced = (first <= t) & ((first == 0) | (first + 3 >= t - 7))
    assert (want[0] >= forced).all()                    # every forced block
    free = first + 3 < t - 7
    np.testing.assert_array_equal(          # and of the rest, topk or all
        (want & ~forced).sum(-1)[0],
        np.minimum((free & (first > 0)).sum(-1), topk))


def test_a_query_never_chooses_by_a_pooled_key_that_reaches_past_it():
    """Pooled key ``m`` means over positions ``2m .. 2m + 3``: a query at
    ``t`` inside that range must not see it. Keys after ``t`` are moved far;
    what the queries up to ``t`` chose stays."""
    cfg = small()
    q, k, _ = qkv(48, 5)
    before = program_chosen(q, k, cfg)
    for t in (20, 21, 33):
        moved = k.at[:, t + 1:].multiply(-7.0)
        np.testing.assert_array_equal(
            program_chosen(q, moved, cfg)[:, :t + 1], before[:, :t + 1])
    assert (program_chosen(q, k.at[:, 21:].multiply(-7.0), cfg)
            != before).any()


# -- the sparse attention --------------------------------------------------
def naive_sparse(q, k, v, seen):
    """Full [S, S] logits a head, key ``j`` seen by ``t`` iff ``j <= t`` and
    its block is in ``seen`` [K, S, blocks]."""
    _, s, kh, g, d = q.shape
    mask = jnp.repeat(jnp.asarray(seen), 4, axis=-1)[..., :s] \
        & (jnp.arange(s)[None, :] <= jnp.arange(s)[:, None])
    logits = jnp.einsum("tkgd,jkd->kgtj", q[0], k[0]) * d ** -0.5
    p = jax.nn.softmax(jnp.where(mask[:, None], logits, -jnp.inf), axis=-1)
    return jnp.einsum("kgtj,jkd->tkgd", p, v[0])[None]


@pytest.mark.parametrize("length", [40, 37, 21])
def test_sparse_attention_matches_full_logits_forward_and_gradients(length):
    cfg = small()
    q, k, v = qkv(length, 7)
    seen = chosen_by_loop(q, k, cfg)
    out, chosen, pairs = jax.jit(
        lambda *x: sparse_attention(*x, cfg))(q, k, v)
    np.testing.assert_array_equal(np.asarray(chosen[0]), seen)
    assert rel(out, naive_sparse(q, k, v, seen)) < 1e-5
    w = jnp.asarray(np.random.default_rng(8).standard_normal(out.shape),
                    jnp.float32)
    got = jax.jit(jax.grad(
        lambda *x: jnp.sum(sparse_attention(*x, cfg)[0] * w),
        argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(lambda *x: jnp.sum(naive_sparse(*x, seen) * w),
                            argnums=(0, 1, 2)))(q, k, v)
    assert worst(got, want) < 1e-4
    # pairs a head attended: the mean over the key-value heads of the seen
    # keys at or before each query
    keys = np.clip(np.arange(length)[:, None] + 1
                   - 4 * np.arange(seen.shape[-1]), 0, 4)
    assert int(pairs) == int((seen * keys).sum()) // 2


def test_block_pairs_nobody_chose_in_add_nothing():
    """With a window of one key block and no chosen block, a block of queries
    sees the first block of keys, its own and (its first queries' windows
    reach back) the one before. The walk is the causal one; the mask alone
    keeps every other block pair out, forward and backward."""
    cfg = small(sparse_topk=0, sparse_window_size=4, attn_block=8)
    q, k, v = qkv(64, 9)
    out, chosen, _ = jax.jit(
        lambda *x: sparse_attention(*x, cfg))(q, k, v)
    seen = np.asarray(chosen[0])
    nb = 8
    assert seen.reshape(2, nb, 8, nb, 2).any(axis=(0, 2, 4)).sum() \
        == nb + (nb - 1) + (nb - 2)
    assert rel(out, naive_sparse(q, k, v, seen)) < 1e-5
    got = jax.jit(jax.grad(
        lambda k: jnp.sum(sparse_attention(q, k, v, cfg)[0])))(k)
    want = jax.jit(jax.grad(
        lambda k: jnp.sum(naive_sparse(q, k, v, seen))))(k)
    assert rel(got, want) < 1e-4


@pytest.mark.parametrize("length", [16, 9])
def test_up_to_dense_len_the_sparse_layer_is_causal_attention(length):
    cfg = small()
    q, k, v = qkv(length, 3)
    out, chosen, pairs = sparse_attention(q, k, v, cfg)
    assert chosen is None
    assert int(pairs) == length * (length + 1) // 2
    np.testing.assert_array_equal(out, causal_gqa(q, k, v, cfg.attn_block))
    assert sparse_attention(*qkv(17, 3), cfg)[1] is not None


# -- Lightning attention through the chunked scan -----------------------------
def lightning_by_sums(q, k, v, slopes):
    s, d = q.shape[1], q.shape[-1]
    ago = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    decay = jnp.where(ago >= 0, jnp.exp(-slopes[:, None, None] * ago), 0.0)
    scores = jnp.einsum("bthd,bjhd->bhtj", q, k) * d ** -0.5
    return jnp.einsum("bhtj,bjhd->bthd", scores * decay, v)


@pytest.mark.parametrize("length,chunk,group", [
    (32, 8, 2), (37, 8, 2), (37, 8, 8), (5, 8, 1), (50, 16, 3)])
def test_lightning_through_the_scan_matches_the_double_sum(length, chunk,
                                                           group):
    rng = np.random.default_rng(length)
    q, k, v = (jnp.asarray(rng.standard_normal((2, length, 4, 8)),
                           jnp.float32) for _ in range(3))
    slopes = jnp.asarray(lightning_slopes(4, 1, 32))
    got = jax.jit(lambda *x: lightning_attention(
        *x, slopes, chunk, group))(q, k, v)
    assert rel(got, lightning_by_sums(q, k, v, slopes)) < 1e-5
    w = jnp.asarray(rng.standard_normal(got.shape), jnp.float32)
    grads = jax.jit(jax.grad(lambda *x: jnp.sum(lightning_attention(
        *x, slopes, chunk, group) * w), argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(
        lambda *x: jnp.sum(lightning_by_sums(*x, slopes) * w),
        argnums=(0, 1, 2)))(q, k, v)
    assert worst(grads, want) < 1e-4


def test_decay_slopes_by_hand():
    got = lightning_slopes(32, 1, 32)
    assert got.dtype == np.float32 and got.shape == (32,)
    assert got[0] == pytest.approx(2 ** -0.25 * (1 - 1 / 31 + 1e-5))
    assert got[31] == pytest.approx(2 ** -8 * (1 - 1 / 31 + 1e-5))
    assert lightning_slopes(32, 31, 32)[0] == pytest.approx(2 ** -0.25 * 1e-5)
    np.testing.assert_allclose(got, ref.decay_slopes(range(1, 33), 32, 1, 32),
                               rtol=1e-7)
    cfg = small()
    buffers = init_buffers(cfg)
    assert [b is None for b in buffers] == [k != "N" for k in cfg.pattern]
    for block, layer in ((2, 1), (4, 2), (6, 3)):
        assert cfg.layer_of(block) == layer
        np.testing.assert_array_equal(buffers[block],
                                      lightning_slopes(4, layer, 32))


@pytest.mark.parametrize("held,whole", [
    ((2, 4, 6, 8), 8), ((1, 2, 3, 4), 8), ((5, 6, 7, 8), 8), ((), 0)])
def test_a_share_of_the_heads_keeps_the_rates_they_were_published_with(
        held, whole):
    """A chip that holds some of a Lightning mixer's heads decays each at the
    rate of its PUBLISHED number among the published count, in the program
    and in the reference; it does not renumber them 1..held."""
    cfg = small(lightning_heads=held, lightning_published_nh=whole)
    cfg.validate()
    numbers = np.asarray(held or (1, 2, 3, 4))
    buffers = init_buffers(cfg)
    for block, layer in ((2, 1), (4, 2), (6, 3)):
        want = 2.0 ** (-8.0 * numbers / (whole or 4)) \
            * (1 - layer / 31 + 1e-5)
        np.testing.assert_allclose(buffers[block], want, rtol=1e-6)
        np.testing.assert_array_equal(
            buffers[block],
            lightning_slopes(whole or 4, layer, 32)[numbers - 1])
        np.testing.assert_allclose(
            buffers[block], ref.slopes_of(sizes(cfg), block), rtol=1e-7)


@pytest.mark.parametrize("heads", [
    {}, {"held_lightning_heads": [2, 4]},
    {"held_lightning_heads": [2, 4, 4, 6] + list(range(8, 32, 2))},
    {"held_lightning_heads": list(range(3, 35, 2))}])
def test_a_file_that_holds_fewer_heads_than_published_names_them(heads):
    _, raw = _raw()
    raw = {k: v for k, v in raw.items() if k != "held_lightning_heads"}
    with pytest.raises(Exception, match="Lightning heads"):
        HybridLMConfig.from_dict(dict(raw, **heads))


def test_the_cells_chip_holds_the_even_numbered_published_heads():
    """``minicpm-sala-9b-pp8``: 16 of the 32 published heads, h = 2, 4, ..,
    32, each at ``2 ** (-8 h / 32)`` times its layer's factor, in the program
    and in the reference alike."""
    path, raw = _raw()
    cfg = HybridLMConfig.from_file(path)
    assert raw["held_lightning_heads"] == list(range(2, 33, 2))
    assert "2, 4, .., 32" in raw["deployment"]
    assert "h = 2, 4, .., 32" in raw["assumed"]["decay_slopes"]
    s = ref.sizes_of(raw)
    assert (s["lightning_heads"], s["lightning_published_nh"]) == (
        tuple(range(2, 33, 2)), 32)
    for block, layer in ((2, 1), (4, 2), (6, 3)):
        got = lightning_slopes(cfg.lightning_published_nh, layer, cfg.layers,
                               cfg.lightning_heads)
        want = 2.0 ** (-8.0 * np.arange(2, 33, 2) / 32) \
            * (1 - layer / 31 + 1e-5)
        np.testing.assert_allclose(got, want, rtol=1e-6)
        np.testing.assert_allclose(got, ref.slopes_of(s, block), rtol=1e-7)


def _ssd_chunked_before(x, dt, a, b, c, chunk):
    """``mamba2.ssd_chunked`` as the commit before this file had it (its one
    user then: Mamba-2, 8 chunks at a time), word for word."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    r = h // g
    k = min(8, -(-s // chunk))
    pad = (-s) % (chunk * k)
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) *
                               (t.ndim - 2)) for t in (x, dt, b, c))
    nc = (s + pad) // chunk
    x = x.reshape(bsz, nc, chunk, g, r, p)
    dt = dt.reshape(bsz, nc, chunk, g, r)
    b = b.reshape(bsz, nc, chunk, g, n)
    c = c.reshape(bsz, nc, chunk, g, n)
    cum = jnp.cumsum(jnp.moveaxis(dt * a.reshape(g, r), 2, -1), axis=-1)
    xdt = x * dt[..., None]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    @jax.checkpoint
    def inside(chunks):
        c_k, b_k, cum_k, xdt_k = chunks
        decay = jnp.exp(jnp.where(
            causal, cum_k[..., :, None] - cum_k[..., None, :], -jnp.inf))
        cb = jnp.einsum("bctgn,bcsgn->bcgts", c_k, b_k)
        return jnp.einsum("bcgrts,bcsgrp->bctgrp",
                          decay * cb[:, :, :, None], xdt_k)

    def grouped(t):
        return jnp.moveaxis(t.reshape((bsz, nc // k, k) + t.shape[2:]), 1, 0)

    y = jax.lax.map(inside, (grouped(c), grouped(b), grouped(cum),
                             grouped(xdt)))
    y = jnp.moveaxis(y, 0, 1).reshape(x.shape)
    last = cum[..., -1]
    to_end = jnp.moveaxis(jnp.exp(last[..., None] - cum), -1, 2)
    left = jnp.einsum("bcsgn,bcsgrp->bcgrpn", b, xdt * to_end[..., None])

    def carry_on(state, chunk_in):
        decay_c, left_c = chunk_in
        return decay_c[..., None, None] * state + left_c, state

    _, before = jax.lax.scan(
        carry_on, jnp.zeros((bsz, g, r, p, n), x.dtype),
        (jnp.moveaxis(jnp.exp(last), 1, 0), jnp.moveaxis(left, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)
    y = y + jnp.einsum("bctgn,bcgrpn->bctgrp", c, before) \
        * jnp.moveaxis(jnp.exp(cum), -1, 2)[..., None]
    return y.reshape(bsz, nc * chunk, h, p)[:, :s]


# Mamba-2's tiny case (4 heads in 2 groups, steps that depend on the input)
# and Lightning's (a group a head, steps of one): the ONE scan both use gives
# the first its bits of before
@pytest.mark.parametrize("heads,groups,state,steps", [
    (4, 2, 16, "input"), (4, 4, 8, "ones")])
def test_the_scan_both_mixers_use_is_bit_equal_to_before(heads, groups, state,
                                                         steps):
    rng = np.random.default_rng(heads + groups)
    x = jnp.asarray(rng.standard_normal((2, 21, heads, 8)), jnp.float32)
    dt = jnp.ones((2, 21, heads), jnp.float32) if steps == "ones" else \
        jnp.asarray(rng.uniform(0.001, 0.1, (2, 21, heads)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.1, 2.0, heads), jnp.float32)
    b, c = (jnp.asarray(rng.standard_normal((2, 21, groups, state)),
                        jnp.float32) for _ in range(2))
    run = jax.jit(ssd_chunked, static_argnums=(5,))
    was = jax.jit(_ssd_chunked_before, static_argnums=(5,))
    np.testing.assert_array_equal(run(x, dt, a, b, c, 4),
                                  was(x, dt, a, b, c, 4))
    grad = jax.jit(jax.grad(lambda *t: jnp.sum(ssd_chunked(*t, 4) ** 2),
                            argnums=(0, 1, 3, 4)))
    grad_was = jax.jit(jax.grad(
        lambda *t: jnp.sum(_ssd_chunked_before(*t, 4) ** 2),
        argnums=(0, 1, 3, 4)))
    for got, want in zip(grad(x, dt, a, b, c), grad_was(x, dt, a, b, c)):
        np.testing.assert_array_equal(got, want)


# -- blocks and the whole model against the reference -------------------------
@pytest.mark.parametrize("length", [37, 12])
@pytest.mark.parametrize("kind,block", [("S", 0), ("N", 4), ("D", 1)])
def test_block_matches_reference(kind, block, length):
    cfg = small()
    assert cfg.pattern[block] == kind
    p = off_start(init_params(cfg)["layers"][block], seed=block)
    bias = init_buffers(cfg)[block]
    rng = np.random.default_rng(length)
    u = jnp.asarray(rng.standard_normal((2, length, 32)), jnp.float32)
    w = jnp.asarray(rng.standard_normal(u.shape), jnp.float32)
    s = sizes(cfg)

    def program(p, u):
        return jnp.sum(layer_forward(kind, p, bias, u, cfg, remat=True)[0] * w)

    def reference(p, u):
        return jnp.sum(ref.block(kind, p, u, s, ref.slopes_of(s, block))[0]
                       * w)

    got, got_aux = jax.jit(
        lambda p, u: layer_forward(kind, p, bias, u, cfg))(p, u)
    want, attended = jax.jit(lambda p, u: ref.block(
        kind, p, u, s, ref.slopes_of(s, block)))(p, u)
    assert rel(got, want) < 1e-5
    # the branch is scaled: the block moves the stream by r times the mixer
    assert rel(got - u, (want - u)) < 1e-5 and cfg.residual_scale < 0.25
    if kind == "S" and length > cfg.sparse_dense_len:
        np.testing.assert_array_equal(got_aux["chosen"], attended)
    elif kind == "S":
        assert got_aux["chosen"] is None and attended is None
    assert worst(jax.jit(jax.grad(program, argnums=(0, 1)))(p, u),
                 jax.jit(jax.grad(reference, argnums=(0, 1)))(p, u)) < 1e-4


def _reference_steps(cfg, params0, rows0, batches, compute="float32",
                     storage=None):
    """Two AdaGrad steps of the reference from the model's own start:
    (losses, what the sparse block attended, parameters, rows, first step's
    gradients)."""
    s = sizes(cfg)

    def stored(tree):
        if storage is None:
            return tree
        return jax.tree_util.tree_map(
            lambda x: jnp.asarray(x).astype(storage).astype(jnp.float32),
            tree)

    params = stored(params0)
    rows = np.array(stored(rows0))
    g2 = jax.tree_util.tree_map(jnp.zeros_like, params)
    rows_g2 = np.zeros_like(rows)
    losses, attended, first = [], [], None
    for tokens in batches:
        ids, n, where, targets, mask = pack_batch(tokens, 1)
        loss, chose, gp, grows = ref.value_and_grads(
            params, jnp.asarray(rows[ids]), where, targets, mask, s,
            compute=compute)
        first = first or (gp, grows, ids)
        stepped = jax.tree_util.tree_map(
            lambda w, a, g: ref.adagrad(w, a, g, cfg.adagrad_step),
            params, g2, gp)
        params = stored(jax.tree_util.tree_map(
            lambda w, pair: pair[0], params, stepped))
        g2 = jax.tree_util.tree_map(lambda w, pair: pair[1], params, stepped)
        new_rows, new_g2 = ref.adagrad(rows[ids], rows_g2[ids],
                                       np.asarray(grows), cfg.adagrad_step)
        rows[ids], rows_g2[ids] = stored(new_rows), new_g2
        losses.append(float(loss))
        attended.append(chose)
    return losses, attended, params, rows, first


@pytest.mark.parametrize("length", [37, 16])
def test_whole_model_two_steps_match_reference(length):
    """Loss, every dense leaf's gradient, the touched rows and (past
    ``dense_len``) the chosen blocks, over two steps."""
    cfg = small()
    model = HybridLM(cfg, mode="local")
    model.params = off_start(model.params)
    start = jax.tree_util.tree_map(np.array, model.params)
    rows0 = model.local_rows()
    batches = [batch(cfg, length=length, seed=1),
               batch(cfg, length=length, seed=2)]
    ids, _, where, targets, mask = pack_batch(batches[0], cfg.row_bucket)
    gp, grows = jax.jit(jax.grad(
        make_loss(cfg), argnums=(0, 1), has_aux=True))(
            model.params, jnp.asarray(rows0[ids]), model.buffers, where,
            targets, mask)[0]
    losses, chosen = [], []
    for b in batches:
        losses.append(model.step(b))
        chosen.append(model.last_sparse_chosen)
    want = _reference_steps(cfg, start, rows0, batches)
    want_gp, want_grows, want_ids = want[4]
    flat = jax.tree_util.tree_flatten_with_path(gp)[0]
    for (path, got), leaf in zip(flat, jax.tree_util.tree_leaves(want_gp)):
        assert rel(got, leaf) < TOL["grad"], jax.tree_util.keystr(path)
    assert rel(np.asarray(grows)[:len(want_ids)], want_grows) < TOL["grad"]
    assert max(abs(g - w) / abs(w)
               for g, w in zip(losses, want[0])) < TOL["loss"]
    assert max(worst(model.params, want[2]),
               rel(model.local_rows(), want[3])) < TOL["step"]
    for got, attended in zip(chosen, want[1]):
        assert len(got) == len(attended) == 1
        if length > cfg.sparse_dense_len:
            np.testing.assert_array_equal(got[0], attended[0])
        else:
            assert got[0] is None and attended[0] is None

    low = _reference_steps(cfg, start, rows0, batches, compute="bfloat16",
                           storage="bfloat16")
    control = {"loss": max(abs(g - w) / abs(w)
                           for g, w in zip(low[0], want[0])),
               "step": max(worst(low[2], want[2]), rel(low[3], want[3]))}
    assert control["loss"] > TOL["loss"] or control["step"] > TOL["step"]


# each scaling left out of the program moves the rows' gradient away from the
# reference's (the loss itself, near log(vocabulary) here, hardly tells)
@pytest.mark.parametrize("left_out", [
    dict(scale_emb=1.0), dict(scale_depth=0.0), dict(dim_model_base=0)])
def test_a_mup_scaling_left_out_is_seen(left_out):
    cfg = small()
    params = off_start(init_params(cfg))
    tokens = batch(cfg, seed=3)
    ids, _, where, targets, mask = pack_batch(tokens, 1)
    rows = jnp.asarray(np.random.default_rng(5).standard_normal(
        (len(ids), cfg.hidden_size)), jnp.float32) * 0.1
    want = ref.value_and_grads(params, rows, where, targets, mask,
                               sizes(cfg))[3]

    def row_grads(cfg):
        return jax.jit(jax.grad(lambda rows: make_loss(cfg)(
            params, rows, init_buffers(cfg), where, targets, mask)[0]))(rows)

    assert rel(row_grads(cfg), want) < TOL["grad"]
    assert rel(row_grads(small(**left_out)), want) > 0.1


def test_reference_block_by_block_gradients_are_the_whole_models():
    cfg = small()
    params = off_start(init_params(cfg))
    rng = np.random.default_rng(5)
    tokens = batch(cfg, seed=3)
    ids, _, where, targets, mask = pack_batch(tokens, 1)
    rows = jnp.asarray(rng.standard_normal((len(ids), cfg.hidden_size)),
                       jnp.float32) * 0.1
    s = sizes(cfg)
    loss, attended, gp, grows = ref.value_and_grads(
        params, rows, where, targets, mask, s)
    got = {}
    loss2, attended2, grows2 = ref.grads_by_block(
        lambda i: params["layers"][i],
        lambda: (params["final_norm"], params["head"]),
        rows, where, targets, mask, s, got.__setitem__, inputs_on_host=True)
    assert abs(float(loss2) - float(loss)) < 1e-6
    np.testing.assert_array_equal(attended2[0], attended[0])
    assert rel(grows2, grows) < 1e-5
    assert rel(got["top"][1], gp["head"]) < 1e-5
    for i in range(len(cfg.pattern)):
        assert worst(got[i], gp["layers"][i]) < 1e-5


# -- the planes ------------------------------------------------------------
@pytest.fixture(params=["mesh_of_8", "one_device"])
def table_devices(request):
    import multiverso_tpu as mv
    one = request.param == "one_device"
    mv.init([], devices=jax.devices()[:1] if one else None)
    yield one
    mv.shutdown()


def test_ps_plane_matches_local_twin_bitwise(table_devices):
    cfg = small()
    local, ps = HybridLM(cfg, mode="local"), HybridLM(cfg, mode="ps")
    batches = [batch(cfg, seed=7), batch(cfg, seed=8), batch(cfg, seed=7)]
    assert [local.step(b) for b in batches] == [ps.step(b) for b in batches]
    np.testing.assert_array_equal(local.last_sparse_chosen[0],
                                  ps.last_sparse_chosen[0])
    ids = np.unique(batches[0])
    np.testing.assert_array_equal(ps.pull_rows(ids), local.pull_rows(ids))
    for (name, a), (_, b) in zip(local.dense_leaves(), ps.dense_leaves()):
        np.testing.assert_array_equal(a, b, err_msg=name)


def test_step_spans_counters_scopes_and_program_names():
    """``sala_mfu_share`` reads ``lm.tokens``, ``lm.sparse.pairs``,
    ``lm.sparse.select_pairs`` and ``lm.lightning.chunks`` and the
    ``jit_lm_delta_step`` program; ``sala_sparse_pair_share`` the sparse
    counters; the scope metrics
    ``lm_attention``, ``lm_sparse_select``, ``lm_sparse_attn``,
    ``lm_lightning``: the names are part of the yardstick."""
    cfg = small()
    model = HybridLM(cfg, mode="local")
    tokens = batch(cfg, seed=9)                 # 2 sequences of 37
    reg = get_registry()
    names = ("lm.step", "lm.pull", "lm.compute", "lm.compute.dispatch",
             "lm.compute.sync", "lm.push")
    before = {n: reg.histogram("span." + n).count for n in names}
    counted = ("lm.tokens", "lm.rows_pulled", "lm.attn.pairs",
               "lm.sparse.pairs", "lm.sparse.causal_pairs",
               "lm.sparse.select_pairs", "lm.lightning.chunks",
               "lm.scan.plane.fused", "lm.scan.plane.xla")
    c0 = {n: reg.counter(n).value for n in counted}
    model.step(tokens)
    for n in names:
        assert reg.histogram("span." + n).count == before[n] + 1, n
    moved = {n: reg.counter(n).value - c0[n] for n in counted}
    assert moved["lm.tokens"] == 74 and moved["lm.attn.pairs"] == 0
    assert moved["lm.sparse.causal_pairs"] == 2 * 37 * 38 // 2
    # every query with its 17 .. 1 pooled keys seen: those whose four
    # positions end at or before it
    assert moved["lm.sparse.select_pairs"] == 2 * sum(
        (t - 3) // 2 + 1 for t in range(3, 37))
    seen = np.asarray(model.last_sparse_chosen[0])      # [2, 2, 37, 10]
    keys = np.clip(np.arange(37)[:, None] + 1 - 4 * np.arange(10), 0, 4)
    assert moved["lm.sparse.pairs"] == sum(
        int((s * keys).sum()) // 2 for s in seen)
    assert moved["lm.sparse.pairs"] < moved["lm.sparse.causal_pairs"]
    assert moved["lm.lightning.chunks"] == 3 * 2 * 5
    # three Lightning blocks, heads of 8 in chunks of 8: no scan kernel's
    assert (moved["lm.scan.plane.fused"], moved["lm.scan.plane.xla"]) == (0, 3)
    assert model._hybrid.delta.__name__ == DELTA_PROGRAM
    ids, _, where, targets, mask = pack_batch(tokens, cfg.row_bucket)
    text = model._hybrid.delta.lower(
        model.params, jnp.zeros((len(ids), cfg.hidden_size)), model.buffers,
        where, targets, mask).as_text(debug_info=True)
    assert "module @jit_lm_delta_step" in text
    for scope in ("lm_attention", "lm_sparse_select", "lm_sparse_attn",
                  "lm_lightning", "lm_lightning_scan", "lm_ssd",
                  "lm_dense_ffn",
                  "lm_head_loss", "lm_embed", "lm_scale"):
        assert scope in text, scope
    # short sequences: the sparse block attends densely and counts so
    c0 = {n: reg.counter(n).value for n in counted}
    model.step(batch(cfg, length=12, seed=2))
    moved = {n: reg.counter(n).value - c0[n] for n in counted}
    assert moved["lm.sparse.pairs"] == moved["lm.sparse.causal_pairs"] == 156
    assert moved["lm.sparse.select_pairs"] == 0
    assert model.last_sparse_chosen == [None]


# -- the configuration file ---------------------------------------------------
def _raw():
    path = os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json")
    with open(path) as f:
        return path, json.load(f)


def test_benchmark_configuration_keeps_every_published_width():
    path, raw = _raw()
    cfg = HybridLMConfig.from_file(path)
    assert cfg.pattern == "SDNDNDND" and cfg.hidden_size == 4096
    # this chip's half of each mixer's heads, at their published width
    assert (cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim) == (16, 1, 128)
    assert (cfg.lightning_nh, cfg.lightning_nkv, cfg.lightning_head_dim,
            cfg.rope_theta) == (16, 16, 128, 10000)
    assert (cfg.lightning_heads, cfg.lightning_published_nh) == (
        tuple(range(2, 33, 2)), 32)
    assert (cfg.sparse_kernel_size, cfg.sparse_kernel_stride,
            cfg.sparse_block_size, cfg.sparse_window_size,
            cfg.sparse_init_blocks, cfg.sparse_topk,
            cfg.sparse_dense_len) == (32, 16, 64, 2048, 1, 64, 8192)
    assert cfg.sparse_pool == (4, 1)        # a max-pool of 5, stride 4
    assert (cfg.intermediate_size, cfg.vocab_size) == (16384, 9181)
    assert cfg.norm_eps == 1e-6 and cfg.num_pred_heads == 1
    assert (cfg.scale_emb, cfg.logit_divisor, cfg.layers) == (12, 16.0, 32)
    assert cfg.residual_scale == pytest.approx(1.4 / 32 ** 0.5)
    assert raw["mup_denominator"] == 32        # carried in the file only
    assert raw["reduced"] == [
        "num_hidden_layers", "vocab_size", "num_attention_heads",
        "num_key_value_heads", "lightning_nh", "lightning_nkv"]
    assert raw["published"] == {
        "num_hidden_layers": 32, "vocab_size": 73448,
        "num_attention_heads": 32, "num_key_value_heads": 2,
        "lightning_nh": 32, "lightning_nkv": 32}
    assert 73448 == 8 * 9181 and len(raw["mixer_types"]) == 32
    assert "eight" in raw["deployment"] and "TWO chips" in raw["deployment"]
    assert raw["guarantees"] and set(raw["reduced"]) == set(
        raw["reduced_why"])
    for key in ("sparse_config", "chosen_beside_forced", "decay_slopes",
                "projections", "output_norm_and_gates", "mup_denominator",
                "init", "optimizer", "dtype", "documents",
                "sequence_length"):
        assert key in raw["assumed"], key
    assert ref.pattern_of(raw) == cfg.pattern
    s = ref.sizes_of(raw)
    assert s["residual_scale"] == pytest.approx(cfg.residual_scale)
    assert (s["logit_divisor"], s["published_layers"]) == (16.0, 32)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["source_url"] == raw["source_url"])
        for key, value in row["config"].items():
            if key not in raw["reduced"]:
                assert raw[key] == value, key


def test_dense_parameters_from_shapes_alone():
    """ISSUE 37's arithmetic at the published head counts: a ``minicpm4``
    layer 253,763,840, a ``lightning-attn`` layer 285,225,216, four layers
    1,109,439,488, the head 4,096 x 9,181 and the final norm: 1,147,048,960.
    That did not fit one chip (PERF.md 4); the file holds half of each
    mixer's heads: 994,999,296."""
    raw = _raw()[1]
    whole = HybridLMConfig.from_dict(dict(
        raw, held_lightning_heads=list(range(1, 33)),
        **{k: raw["published"][k] for k in raw["reduced"][2:]}))
    shapes = param_shapes(whole)

    def count(tree):
        return sum(int(np.prod(s)) for s in tree.values())

    blocks = [count(block) for block in shapes["layers"]]
    assert blocks[0] == 52429056 + 4096 and blocks[1] == 201326592 + 4096
    assert blocks[0] + blocks[1] == 253763840
    assert blocks[2] == 83886080 + 256 + 4096 + 4096
    assert blocks[2] + blocks[3] == 285225216
    assert sum(blocks) == 1109439488
    assert shapes["head"] == (4096, 9181)
    assert dense_param_count(whole) == 1147048960
    held = HybridLMConfig.from_dict(raw)
    halves = [count(block) for block in param_shapes(held)["layers"]]
    # the mixers' matrices halve, their norms and the feed-forwards stay
    assert halves[0] == (52429056 - 256) // 2 + 256 + 4096
    assert halves[2] == 83886080 // 2 + 256 + 2048 + 4096
    assert halves[1::2] == blocks[1::2]
    assert dense_param_count(held) == 994999296


def test_published_keys_are_read_by_the_kind_of_mixer():
    raw = dict(_raw()[1])
    raw.update(raw["tiny"])
    cfg = HybridLMConfig.from_dict(raw)
    assert cfg.pattern == "SDNDNDND"[:2 * raw["num_hidden_layers"]]
    # the published list is cut, not rewritten
    assert HybridLMConfig.from_dict(
        dict(raw, num_hidden_layers=1)).pattern == "SD"
    assert HybridLMConfig.from_dict(dict(
        raw, mixer_types=["lightning-attn", "minicpm4"],
        num_hidden_layers=2)).pattern == "NDSD"
    with pytest.raises(ValueError, match="unknown"):
        HybridLMConfig.from_dict(dict(raw, mixer_types=["minicpm4", "mamba"]
                                      + raw["mixer_types"][2:]))
    with pytest.raises(ValueError):         # fewer entries than layers
        HybridLMConfig.from_dict(dict(raw, mixer_types=["minicpm4"]))
    for key, value in (("attn_use_rope", True), ("qk_norm", False),
                       ("attn_use_output_gate", False),
                       ("lightning_use_rope", False),
                       ("use_output_norm", False), ("use_output_gate", False),
                       ("lightning_scale", "1")):
        with pytest.raises(ValueError, match="not implemented"):
            HybridLMConfig.from_dict(dict(raw, **{key: value}))
    for key in ("sparse_config", "lightning_nh", "intermediate_size",
                "head_dim"):
        with pytest.raises(KeyError):
            HybridLMConfig.from_dict(
                {k: v for k, v in raw.items() if k != key})
