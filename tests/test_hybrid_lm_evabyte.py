"""The layer-typed LM's EVA block (exact keys inside a window, pooled chunk
summaries of every earlier window, one softmax over both), the whole-head
rotary in the half layout, the unit-offset norm and the multibyte loss, at a
small size on the CPU: the mixer against a naive one that builds the full
``[S, S + chunks]`` logits with both masks, against causal attention up to one
window, against the benchmark's plain reference
(benchmark/reference/evabyte-6.5b-pp8.py) block by block and over two AdaGrad
steps with a bfloat16 control that fails; the PS plane against its local twin;
counters, scopes and names; the configuration file against the catalog's
row."""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from multiverso_tpu.models.hybrid_lm import (DELTA_PROGRAM, HybridLM,
                                             HybridLMConfig,
                                             dense_param_count, init_params,
                                             layer_forward, make_loss,
                                             pack_batch, param_shapes,
                                             rmsnorm, rope)
from multiverso_tpu.models.hybrid_lm.attention import (causal_gqa,
                                                       eva_attention)
from multiverso_tpu.models.hybrid_lm.model import blocked_cross_entropy
from multiverso_tpu.telemetry.metrics import get_registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "evabyte-6.5b-pp8"


def _reference():
    path = os.path.join(ROOT, "benchmark", "reference", CONFIG + ".py")
    spec = importlib.util.spec_from_file_location("evabyte_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()

# hidden 32, 2 heads of 16, window 8 of 4 chunks of 2, a feed-forward of 48,
# 2 prediction heads over a vocabulary of 24, blocks of 4 positions
SMALL = dict(hidden_size=32, vocab_size=24, pattern="VDVD", norm_eps=1e-5,
             norm_add_unit_offset=True, num_pred_heads=2,
             num_attention_heads=2, num_key_value_heads=2, head_dim=16,
             rope_theta=100000.0, window_size=8, eva_chunk_size=2,
             intermediate_size=48, hidden_act="silu", attn_block=4,
             loss_block=16, ffn_slab=16, row_bucket=16)
TOL = dict(loss=2e-5, grad=2e-4, step=2e-4)


def small(**kw) -> HybridLMConfig:
    return HybridLMConfig(**dict(SMALL, **kw))


def sizes(cfg: HybridLMConfig) -> dict:
    return {"pattern": cfg.pattern, "norm_eps": cfg.norm_eps,
            "num_attention_heads": cfg.num_attention_heads,
            "window_size": cfg.window_size, "chunk_size": cfg.eva_chunk_size,
            "rope_theta": cfg.rope_theta,
            "num_pred_heads": cfg.num_pred_heads}


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def worst(got_tree, want_tree) -> float:
    return max(rel(g, w) for g, w in zip(jax.tree_util.tree_leaves(got_tree),
                                         jax.tree_util.tree_leaves(want_tree)))


def batch(cfg, seqs=2, length=21, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, (seqs, length)).astype(np.int32)


def block_params(cfg, seed=3):
    """A block's seeded leaves with its norm moved off zero and ``phi``,
    ``mu`` large enough to matter."""
    rng = np.random.default_rng(seed)
    p = dict(init_params(cfg)["layers"][0])
    p["norm"] = p["norm"] + 0.1 * rng.standard_normal(
        p["norm"].shape).astype(np.float32)
    for name in ("adaptive_phi", "adaptive_mu_k"):
        if name in p:
            p[name] = jnp.asarray(rng.standard_normal(p[name].shape),
                                  jnp.float32)
    return p, rng


# -- the mixer against the full logits ----------------------------------------
def naive_eva(q, k, v, phi, mu, window, chunk):
    """[S, S + chunks] logits a head, both masks written out."""
    s, h, d = q.shape
    scale = d ** -0.5
    nc = -(-s // chunk)
    pad = nc * chunk - s
    kp = jnp.concatenate([k, jnp.zeros((pad, h, d))]).reshape(nc, chunk, h, d)
    vp = jnp.concatenate([v, jnp.zeros((pad, h, d))]).reshape(nc, chunk, h, d)
    real = (np.arange(nc * chunk) < s).reshape(nc, chunk)
    pool = jax.nn.softmax(jnp.where(
        real[:, :, None], jnp.einsum("nchd,hd->nch", kp, phi) * scale,
        -jnp.inf), axis=1)
    ks = jnp.einsum("nch,nchd->nhd", pool, kp) + mu
    vs = jnp.einsum("nch,nchd->nhd", pool, vp)
    t, j, c = np.arange(s), np.arange(s), np.arange(nc)
    local = (j[None, :] <= t[:, None]) \
        & (j[None, :] // window == t[:, None] // window)
    remote = (c[None, :] * chunk) // window < t[:, None] // window
    logits = jnp.concatenate([jnp.einsum("thd,shd->hts", q, k),
                              jnp.einsum("thd,nhd->htn", q, ks)], -1) * scale
    seen = np.concatenate([local, remote], axis=1)
    probs = jax.nn.softmax(jnp.where(seen[None], logits, -jnp.inf), axis=-1)
    return jnp.einsum("hts,shd->thd", probs, jnp.concatenate([v, vs])), seen


def eva_inputs(length, seed=0, h=2, d=16):
    rng = np.random.default_rng(seed)
    q, k, v = (jnp.asarray(rng.standard_normal((length, h, d)), jnp.float32)
               for _ in range(3))
    phi, mu = (jnp.asarray(rng.standard_normal((h, d)), jnp.float32)
               for _ in range(2))
    w = jnp.asarray(rng.standard_normal((length, h, d)), jnp.float32)
    return (q, k, v, phi, mu), w


@pytest.mark.parametrize("length", [32, 27, 9, 8, 5])
def test_eva_matches_the_full_logits_forward_and_gradients(length):
    """32: whole windows; 27: no multiple of the window (8) or the chunk (2);
    9: the second window holds one query; 8 and 5: one window, no summary."""
    args, w = eva_inputs(length)

    def mine(q, k, v, phi, mu):
        return jnp.sum(w * eva_attention(q[None], k[None], v[None], phi, mu,
                                         8, 2, 4)[0])

    def naive(q, k, v, phi, mu):
        return jnp.sum(w * naive_eva(q, k, v, phi, mu, 8, 2)[0])

    with jax.default_matmul_precision("highest"):
        out = eva_attention(*(a[None] for a in args[:3]), *args[3:], 8, 2,
                            4)[0]
        assert rel(out, naive_eva(*args, 8, 2)[0]) < 1e-5
        got = jax.grad(mine, argnums=range(5))(*args)
        want = jax.grad(naive, argnums=range(5))(*args)
    for name, g, wnt in zip(("q", "k", "v", "phi", "mu"), got, want):
        if length <= 8 and name in ("phi", "mu"):
            assert not np.any(np.asarray(g)) and not np.any(np.asarray(wnt))
        else:
            assert rel(g, wnt) < 1e-4, name
        assert np.all(np.isfinite(np.asarray(g)))


def test_up_to_one_window_eva_is_causal_attention():
    (q, k, v, phi, mu), _ = eva_inputs(8, seed=1)
    got = eva_attention(q[None], k[None], v[None], phi, mu, 8, 2, 4)
    want = causal_gqa(q[None, :, :, None], k[None], v[None], 4)[:, :, :, 0]
    np.testing.assert_array_equal(got, want)


def test_a_windows_first_query_sees_itself_and_every_earlier_summary():
    _, seen = naive_eva(*eva_inputs(27)[0], 8, 2)
    local, remote = seen[:, :27], seen[:, 27:]
    for t in (8, 16, 24):
        assert list(np.flatnonzero(local[t])) == [t]
        assert list(np.flatnonzero(remote[t])) == list(range(t // 2))
    assert not remote[:8].any() and local[7, :8].all()
    # and the program agrees on that query: only its own value and the
    # summaries' move it
    (q, k, v, phi, mu), _ = eva_inputs(27, seed=2)

    def row16(v):
        return jnp.sum(eva_attention(q[None], k[None], v[None], phi, mu, 8,
                                     2, 4)[0, 16])

    touched = np.flatnonzero(np.abs(np.asarray(jax.grad(row16)(v))).sum(
        axis=(1, 2)))
    assert list(touched) == list(range(17))     # 0..15 pooled, 16 itself


# -- the multibyte loss, the norm, the rotary ----------------------------------
def test_multibyte_loss_against_a_loop_over_heads():
    rng = np.random.default_rng(0)
    cfg = small()
    tokens = batch(cfg, seqs=2, length=13, seed=5)
    _, _, _, targets, mask = pack_batch(tokens, 1, heads=3)
    assert targets.shape == mask.shape == (2, 13, 3)
    for h in range(3):
        np.testing.assert_array_equal(targets[:, :12 - h, h],
                                      tokens[:, 1 + h:])
        assert mask[:, :12 - h, h].all() and not mask[:, 12 - h:, h].any()
    one = pack_batch(tokens, 1)
    np.testing.assert_array_equal(one[3], targets[..., 0])
    np.testing.assert_array_equal(one[4], mask[..., 0])
    ahead, exists = ref.multibyte_targets(one[3], one[4], 3)
    np.testing.assert_array_equal(exists, mask)
    np.testing.assert_array_equal(ahead * exists, targets * mask)

    u = jnp.asarray(rng.standard_normal((26, 32)), jnp.float32)
    norm_w = jnp.asarray(0.1 * rng.standard_normal(32), jnp.float32)
    head = jnp.asarray(0.2 * rng.standard_normal((32, 3 * 24)), jnp.float32)
    loss, per_head = blocked_cross_entropy(
        u, norm_w, head, jnp.asarray(targets.reshape(26, 3)),
        jnp.asarray(mask.reshape(26, 3)), 1e-5, 8, True)
    logits = (rmsnorm(u, norm_w, 1e-5, True) @ head).reshape(26, 3, 24)
    total, want = 0.0, []
    for h in range(3):
        logp = jax.nn.log_softmax(logits[:, h], axis=-1)
        nll = -logp[np.arange(26), targets.reshape(26, 3)[:, h]] \
            * mask.reshape(26, 3)[:, h]
        total += float(jnp.sum(nll))
        want.append(float(jnp.sum(nll)) / mask[..., h].sum())
    assert float(loss) == pytest.approx(total / mask.sum(), rel=1e-5)
    np.testing.assert_allclose(per_head, want, rtol=1e-5)


def test_rmsnorm_with_the_unit_offset():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((5, 32)), jnp.float32)
    w = jnp.asarray(0.3 * rng.standard_normal(32), jnp.float32)
    np.testing.assert_allclose(rmsnorm(x, w, 1e-5, True),
                               rmsnorm(x, 1.0 + w, 1e-5), rtol=1e-6)
    np.testing.assert_allclose(rmsnorm(x, w, 1e-5, True),
                               ref.rmsnorm(x, w, 1e-5), rtol=1e-5)
    assert float(jnp.mean(jnp.square(rmsnorm(x, jnp.zeros(32), 1e-5, True)))
                 ) == pytest.approx(1.0, rel=1e-3)
    params = init_params(small())
    assert not np.any(np.asarray(params["final_norm"]))
    assert not np.any(np.asarray(params["layers"][0]["norm"]))
    phi = np.asarray(params["layers"][0]["adaptive_phi"])
    assert phi.shape == (2, 16) and 0 < np.abs(phi).max() <= 16 ** -0.5


def test_half_layout_is_the_interleaved_one_under_a_fixed_permutation():
    rng = np.random.default_rng(2)
    s, h, d = 12, 3, 16
    x = jnp.asarray(rng.standard_normal((s, h, d)), jnp.float32)
    cos, sin = rope.rope_tables(s, d, 100000.0, None)
    to_half = np.concatenate([np.arange(0, d, 2), np.arange(1, d, 2)])
    np.testing.assert_allclose(
        rope.apply_rope(x[..., to_half], cos, sin, half=True),
        rope.apply_rope(x, cos, sin)[..., to_half], atol=1e-6)
    np.testing.assert_allclose(rope.apply_rope(x, cos, sin, half=True),
                               ref.rope(x, 100000.0), atol=1e-5)


# -- each block against the reference -----------------------------------------
@pytest.mark.parametrize("length", [32, 21])
@pytest.mark.parametrize("kind", ["V", "D"])
def test_block_matches_reference(kind, length):
    cfg = small(pattern=kind)
    p, rng = block_params(cfg)
    u = jnp.asarray(rng.standard_normal((2, length, cfg.hidden_size)),
                    jnp.float32)
    w = jnp.asarray(rng.standard_normal(u.shape), jnp.float32)
    s = sizes(cfg)

    def mine(p, u):
        return jnp.sum(layer_forward(kind, p, None, u, cfg)[0] * w)

    def theirs(p, u):
        return jnp.sum(ref.layer(kind, p, u, s)[0] * w)

    out, counts = jax.jit(
        lambda p, u: layer_forward(kind, p, None, u, cfg))(p, u)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, u: ref.layer(kind, p, u, s)[0])(p, u)
        want_grads = jax.jit(jax.grad(theirs, argnums=(0, 1)))(p, u)
    assert counts is None and rel(out, want) < TOL["loss"]
    got = jax.jit(jax.grad(mine, argnums=(0, 1)))(p, u)
    assert worst(got, want_grads) < TOL["grad"]
    if kind == "V":
        for name in ("adaptive_phi", "adaptive_mu_k"):
            assert np.abs(np.asarray(want_grads[0][name])).max() > 1e-4


def test_feed_forward_in_slabs_is_the_feed_forward():
    cfg = small(pattern="D")
    p, rng = block_params(cfg)
    u = jnp.asarray(rng.standard_normal((2, 21, cfg.hidden_size)),
                    jnp.float32)
    whole = layer_forward("D", p, None, u, small(pattern="D", ffn_slab=64))[0]
    for slab in (16, 7):        # 21 positions: two slabs, three padded slabs
        cut = layer_forward("D", p, None, u,
                            small(pattern="D", ffn_slab=slab))[0]
        np.testing.assert_allclose(cut, whole, atol=1e-6)


# -- the whole model ----------------------------------------------------------
def _reference_steps(cfg, params0, rows0, batches, compute="float32",
                     storage=None):
    """Two AdaGrad steps of the reference from the model's own start:
    (losses, heads' losses, parameters, rows, first step's gradients)."""
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
    losses, per_head, first = [], [], None
    for tokens in batches:
        ids, n, where, targets, mask = pack_batch(tokens, 1)
        loss, heads, _, gp, grows = ref.value_and_grads(
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
        per_head.append(np.asarray(heads))
    return losses, per_head, params, rows, first


def test_whole_model_two_steps_match_reference():
    cfg = small()
    model = HybridLM(cfg, mode="local")
    # norms, phi and mu off their start, so that none is a special case
    rng = np.random.default_rng(11)
    model.params = jax.tree_util.tree_map(
        lambda w: w + 0.05 * jnp.asarray(rng.standard_normal(w.shape),
                                         jnp.float32) if w.ndim < 2 or
        w.shape == (2, 16) else w, model.params)
    start = jax.tree_util.tree_map(np.array, model.params)
    rows0 = model.local_rows()
    batches = [batch(cfg, seed=1), batch(cfg, seed=2)]

    ids, _, where, targets, mask = pack_batch(batches[0], cfg.row_bucket,
                                              heads=2)
    (_, (_, heads)), (gp, grows) = jax.jit(jax.value_and_grad(
        make_loss(cfg), argnums=(0, 1), has_aux=True))(
            model.params, jnp.asarray(rows0[ids]), model.buffers, where,
            targets, mask)
    losses, head_losses = [], []
    for b in batches:
        losses.append(model.step(b))
        head_losses.append(model.last_head_losses.copy())
    want = _reference_steps(cfg, start, rows0, batches)
    want_gp, want_grows, want_ids = want[4]
    assert worst(gp, want_gp) < TOL["grad"]
    assert rel(np.asarray(grows)[:len(want_ids)], want_grows) < TOL["grad"]
    assert max(abs(g - w) / abs(w)
               for g, w in zip(losses, want[0])) < TOL["loss"]
    np.testing.assert_allclose(heads, want[1][0], rtol=TOL["loss"])
    np.testing.assert_allclose(head_losses, want[1], rtol=TOL["loss"])
    # the two heads' losses differ, and the loss is their weighted mean
    assert abs(head_losses[0][0] - head_losses[0][1]) > 1e-4
    assert losses[0] == pytest.approx(
        (20 * head_losses[0][0] + 19 * head_losses[0][1]) / 39, rel=1e-5)
    assert max(worst(model.params, want[2]),
               rel(model.local_rows(), want[3])) < TOL["step"]

    low = _reference_steps(cfg, start, rows0, batches, compute="bfloat16",
                           storage="bfloat16")
    control = {"loss": max(abs(g - w) / abs(w)
                           for g, w in zip(low[0], want[0])),
               "step": max(worst(low[2], want[2]), rel(low[3], want[3]))}
    assert control["loss"] > TOL["loss"] or control["step"] > TOL["step"]


def test_reference_block_by_block_gradients_are_the_whole_models():
    cfg = small()
    params = init_params(cfg)
    rng = np.random.default_rng(5)
    tokens = batch(cfg, seed=3)
    ids, _, where, targets, mask = pack_batch(tokens, 1)
    rows = jnp.asarray(rng.standard_normal((len(ids), cfg.hidden_size)),
                       jnp.float32) * 0.02
    s = sizes(cfg)
    loss, heads, _, gp, grows = ref.value_and_grads(
        params, rows, where, targets, mask, s)
    got = {}
    loss2, heads2, aux, grows2 = ref.grads_by_layer(
        lambda i: params["layers"][i],
        lambda: (params["final_norm"], params["head"]),
        rows, where, targets, mask, s, (), got.__setitem__)
    assert abs(float(loss2) - float(loss)) < 1e-6 and aux == 0.0
    np.testing.assert_allclose(heads2, heads, rtol=1e-6)
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
    np.testing.assert_array_equal(local.last_head_losses,
                                  ps.last_head_losses)
    ids = np.unique(batches[0])
    np.testing.assert_array_equal(ps.pull_rows(ids), local.pull_rows(ids))
    for (name, a), (_, b) in zip(local.dense_leaves(), ps.dense_leaves()):
        np.testing.assert_array_equal(a, b, err_msg=name)
    np.testing.assert_array_equal(
        local.local_rows(),
        ps.table.get_rows(np.arange(cfg.vocab_size, dtype=np.int32)))


def test_a_vocabulary_smaller_than_the_bucket_caps_the_bucket():
    cfg = small(row_bucket=1024)
    assert cfg.row_bucket == cfg.vocab_size == 24
    model = HybridLM(cfg, mode="local")
    reg = get_registry()
    before = reg.counter("lm.rows_pulled").value
    tokens = batch(cfg, seed=4)
    ids = pack_batch(tokens, cfg.row_bucket)[0]
    assert len(ids) == 24 and ids.max() < 24
    assert np.isfinite(model.step(tokens))
    assert reg.counter("lm.rows_pulled").value - before \
        == len(np.unique(tokens))
    assert HybridLMConfig(vocab_size=4096).row_bucket == 1024


def test_step_spans_counters_scopes_and_program_names():
    """``evabyte_mfu_share`` reads ``lm.tokens``, ``lm.attn.pairs`` and
    ``lm.eva.summary_pairs`` and the ``jit_lm_delta_step`` program,
    ``eva_summary_pair_share`` the two pair counters: the names are part of the
    yardstick."""
    cfg = small()
    model = HybridLM(cfg, mode="local")
    tokens = batch(cfg, seed=9)                 # 2 sequences of 21
    reg = get_registry()
    names = ("lm.step", "lm.pull", "lm.compute", "lm.compute.dispatch",
             "lm.compute.sync", "lm.push")
    before = {n: reg.histogram("span." + n).count for n in names}
    counted = ("lm.tokens", "lm.rows_pulled", "lm.attn.pairs",
               "lm.eva.summary_pairs", "lm.eva.chunks")
    c0 = {n: reg.counter(n).value for n in counted}
    model.step(tokens)
    for n in names:
        assert reg.histogram("span." + n).count == before[n] + 1, n
    moved = {n: reg.counter(n).value - c0[n] for n in counted}
    assert moved["lm.tokens"] == 42
    assert moved["lm.rows_pulled"] == len(np.unique(tokens))
    # two EVA blocks, two sequences: windows of 8, 8 and 5 positions; the
    # second window's queries see 4 summaries, the third's 8
    seen = naive_eva(*eva_inputs(21)[0], 8, 2)[1]
    assert moved["lm.attn.pairs"] == 2 * 2 * (36 + 36 + 15) \
        == 4 * seen[:, :21].sum()
    assert moved["lm.eva.summary_pairs"] == 2 * 2 * (8 * 4 + 5 * 8) \
        == 4 * seen[:, 21:].sum()
    assert moved["lm.eva.chunks"] == 2 * 2 * 11
    assert model._hybrid.delta.__name__ == DELTA_PROGRAM
    assert model._hybrid.apply.__name__ == "lm_apply"
    ids, _, where, targets, mask = pack_batch(tokens, cfg.row_bucket,
                                              heads=2)
    text = model._hybrid.delta.lower(
        model.params, jnp.zeros((len(ids), cfg.hidden_size)), model.buffers,
        where, targets, mask).as_text(debug_info=True)
    assert "module @jit_lm_delta_step" in text
    for scope in ("lm_eva", "lm_eva_prep", "lm_eva_agg", "lm_dense_ffn"):
        assert scope in text, scope


# -- the configuration file ---------------------------------------------------
def test_benchmark_configuration_keeps_every_published_width():
    path = os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json")
    cfg = HybridLMConfig.from_file(path)
    with open(path) as f:
        raw = json.load(f)
    assert cfg.pattern == "VDVDVDVD" and cfg.hidden_size == 4096
    assert (cfg.num_attention_heads, cfg.head_dim, cfg.window_size,
            cfg.eva_chunk_size, cfg.rope_theta) == (32, 128, 2048, 16, 100000)
    assert (cfg.intermediate_size, cfg.vocab_size, cfg.num_pred_heads) \
        == (11008, 320, 8)
    assert cfg.norm_add_unit_offset and cfg.norm_eps == 1e-5
    assert cfg.init_std == 0.01275 and cfg.rope_scaling is None
    assert cfg.row_bucket == 320        # capped at the vocabulary
    assert cfg.chunk_size == HybridLMConfig().chunk_size    # Mamba-2's own
    assert raw["reduced"] == ["num_hidden_layers"]
    assert raw["published"] == {"num_hidden_layers": 32}
    assert "eight" in raw["deployment"] and raw["guarantees"]
    for key in ("adaptive_phi_mu", "prediction_heads", "init", "optimizer",
                "special_ids", "documents", "dtype", "sequence_length"):
        assert key in raw["assumed"], key
    shapes = param_shapes(cfg)
    count = lambda tree: sum(int(np.prod(s)) for s in tree.values())
    assert [count(layer) for layer in shapes["layers"][:2]] == \
        [67108864 + 4096 + 8192, 135266304 + 4096]
    assert shapes["head"] == (4096, 2560)
    assert dense_param_count(cfg) == 820056064
    assert ref.pattern_of(raw) == cfg.pattern
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["source_url"] == raw["source_url"])
        for key, value in row["config"].items():
            if key not in raw["reduced"]:
                assert raw[key] == value, key


def test_published_keys_are_read_by_the_kind_of_block():
    eva = {"hidden_size": 32, "vocab_size": 24, "num_attention_heads": 2,
           "num_key_value_heads": 2, "attention_class": "eva",
           "chunk_size": 2, "window_size": 8, "rope_theta": 100000,
           "intermediate_size": 48, "num_hidden_layers": 4,
           "hidden_act": "silu", "num_pred_heads": 2,
           "norm_add_unit_offset": True, "rms_norm_eps": 1e-5,
           "rope_scaling": None, "attn_block": 4}
    cfg = HybridLMConfig.from_dict(eva)
    assert cfg.pattern == "VDVDVDVD" and cfg.head_dim == 16
    # EVA's chunk and Mamba-2's do not collide
    assert cfg.eva_chunk_size == 2 and cfg.chunk_size == 8
    assert cfg.num_pred_heads == 2 and cfg.norm_add_unit_offset
    assert not cfg.expert_layers() and cfg.eva_blocks() == 4
    mamba = {"hidden_size": 32, "vocab_size": 24, "mamba_num_heads": 2,
             "mamba_head_dim": 16, "ssm_state_size": 16, "n_groups": 1,
             "conv_kernel": 4, "chunk_size": 4, "time_step_min": 0.001,
             "time_step_max": 0.1, "time_step_floor": 1e-4,
             "hybrid_override_pattern": "MM", "num_hidden_layers": 2,
             "n_routed_experts": 2}
    assert HybridLMConfig.from_dict(mamba).chunk_size == 4
    assert HybridLMConfig.from_dict(mamba).eva_chunk_size == 2
    for key in ("window_size", "chunk_size", "intermediate_size"):
        with pytest.raises(KeyError):
            HybridLMConfig.from_dict(
                {k: v for k, v in eva.items() if k != key})
    with pytest.raises(ValueError):
        HybridLMConfig.from_dict(dict(eva, attention_class="other"))
    with pytest.raises(ValueError):
        HybridLMConfig.from_dict(dict(eva, num_key_value_heads=1))
    with pytest.raises(ValueError):     # one file, two meanings of chunk_size
        HybridLMConfig.from_dict(dict(
            mamba, hybrid_override_pattern="MV", window_size=8,
            num_attention_heads=2, rope_theta=100000))
