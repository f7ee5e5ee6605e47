"""The layer-typed LM's gated short convolution, its ``*`` block with a norm a
head and a rotary turn, the expert block WITHOUT a shared expert and the head
TIED to the embedding table, against the benchmark's plain reference
(benchmark/reference/lfm2-8b-a1b-ep4.py) at a small size on the CPU: every
block's forward and gradients; the convolution against a loop over positions,
and causal; the ``*`` block with both switches off bitwise the function it was;
the share test of the expert block (four shares of 8 of 32 experts add up to
the uncut layer, nothing counted once); the tied rows' delta as the sum of both
uses, rows no token named included; the whole model's two AdaGrad steps with a
bfloat16 control that fails; the PS plane against its local twin; spans,
counters, scopes; and the configuration file against the catalog's row."""
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
from multiverso_tpu.models.hybrid_lm.attention import (attention_mixer,
                                                       causal_gqa)
from multiverso_tpu.models.hybrid_lm.shortconv import shortconv_mixer
from multiverso_tpu.parallel.expert import held_topk_moe
from multiverso_tpu.telemetry.metrics import get_registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "lfm2-8b-a1b-ep4"
CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json")


def _reference():
    path = os.path.join(ROOT, "benchmark", "reference", CONFIG + ".py")
    spec = importlib.util.spec_from_file_location("lfm2_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()

# hidden 64, a convolution of 3 taps, 4 query / 2 key-value heads of 16 normed
# and turned, a dense width of 96, 8 gated experts of 32 top-2 with 2 held and
# none shared, vocabulary 64 tied
SMALL = dict(hidden_size=64, vocab_size=64, pattern="CDCD*ECE", norm_eps=1e-5,
             conv_L_cache=3, num_attention_heads=4, num_key_value_heads=2,
             head_dim=16, attn_qk_norm=True, attn_rope=True,
             rope_theta=1000000.0, intermediate_size=96, router_experts=8,
             held=(0, 1), num_experts_per_tok=2, moe_intermediate_size=32,
             moe_shared_expert_intermediate_size=0, routed_scaling_factor=1.0,
             norm_topk_prob=True, scoring_func="sigmoid", hidden_act="silu",
             tie_word_embeddings=True, expert_bias_update_rate=0.01,
             attn_block=8, moe_block=4, loss_block=16, row_bucket=16)
TOL = dict(loss=2e-5, grad=2e-4, step=2e-4)


def small(**kw) -> HybridLMConfig:
    return HybridLMConfig(**dict(SMALL, **kw))


def sizes(cfg: HybridLMConfig) -> dict:
    return {"pattern": cfg.pattern, "norm_eps": cfg.norm_eps,
            "num_attention_heads": cfg.num_attention_heads,
            "num_key_value_heads": cfg.num_key_value_heads,
            "head_dim": cfg.head_dim, "rope_theta": cfg.rope_theta,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "norm_topk_prob": cfg.norm_topk_prob,
            "routed_scaling_factor": cfg.routed_scaling_factor}


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def worst(got_tree, want_tree) -> float:
    return max(rel(g, w) for g, w in zip(jax.tree_util.tree_leaves(got_tree),
                                         jax.tree_util.tree_leaves(want_tree)))


def batch(cfg, seqs=2, length=21, seed=0, ids=None):
    rng = np.random.default_rng(seed)
    return rng.integers(0, ids or cfg.vocab_size,
                        (seqs, length)).astype(np.int32)


def block_params(cfg, seed=3):
    """A block's seeded leaves with its norms moved off one."""
    rng = np.random.default_rng(seed)
    p = init_params(cfg)["layers"][0]
    return {k: v + 0.1 * rng.standard_normal(v.shape).astype(np.float32)
            if k in ("norm", "q_norm", "k_norm") else v
            for k, v in p.items()}, rng


# -- each block, forward and gradients ----------------------------------------
@pytest.mark.parametrize("length", [16, 21])
@pytest.mark.parametrize("kind", ["C", "*", "D", "E"])
def test_block_matches_reference(kind, length):
    cfg = small(pattern=kind)
    p, rng = block_params(cfg)
    bias = init_buffers(cfg)[0]
    u = jnp.asarray(rng.standard_normal((2, length, cfg.hidden_size)),
                    jnp.float32)
    w = jnp.asarray(rng.standard_normal(u.shape), jnp.float32)
    s = sizes(cfg)

    def mine(p, u):
        return jnp.sum(layer_forward(kind, p, bias, u, cfg)[0] * w)

    def theirs(p, u):
        return jnp.sum(ref.layer(kind, p, bias, u, s, cfg.held)[0] * w)

    out, counts, *every = jax.jit(
        lambda p, u: layer_forward(kind, p, bias, u, cfg))(p, u)
    with jax.default_matmul_precision("highest"):
        want, want_counts = jax.jit(
            lambda p, u: ref.layer(kind, p, bias, u, s, cfg.held))(p, u)
        want_grads = jax.jit(jax.grad(theirs, argnums=(0, 1)))(p, u)
    assert rel(out, want) < TOL["loss"]
    assert worst(jax.jit(jax.grad(mine, argnums=(0, 1)))(p, u),
                 want_grads) < TOL["grad"]
    if kind == "E":
        # the reference counts every expert of the router, as the block does
        # beside its held ones where the selection bias is updated
        np.testing.assert_array_equal(every[0], want_counts)
        np.testing.assert_array_equal(
            counts, np.asarray(want_counts)[list(cfg.held)])
        assert 0 < int(np.sum(counts)) < 2 * length * 2
        assert int(np.sum(every[0])) == 2 * length * 2
    else:
        assert counts is None and not every


# -- the convolution mixer ----------------------------------------------------
@pytest.mark.parametrize("taps", [1, 3, 4])
def test_shortconv_is_the_loop_over_positions(taps):
    """``y_t = C_t * sum_j w[:, j] (B x)_{t-(L-1)+j}``, zero before the
    sequence's start, written as a loop in NumPy."""
    cfg = small(pattern="C", conv_L_cache=taps)
    p = jax.tree_util.tree_map(np.asarray, init_params(cfg)["layers"][0])
    rng = np.random.default_rng(1)
    n = rng.standard_normal((2, 11, cfg.hidden_size)).astype(np.float32)
    got = shortconv_mixer(p, jnp.asarray(n), cfg)
    d = cfg.hidden_size
    want = np.zeros_like(n, np.float64)
    for b in range(n.shape[0]):
        bcx = n[b].astype(np.float64) @ p["in_proj"].astype(np.float64)
        gate_b, gate_c, x = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
        z = gate_b * x
        for t in range(n.shape[1]):
            conv = np.zeros(d)
            for j in range(taps):
                at = t - (taps - 1) + j
                if at >= 0:
                    conv += p["conv_w"][:, j] * z[at]
            want[b, t] = (gate_c[t] * conv) @ p["out_proj"].astype(np.float64)
    assert p["conv_w"].shape == (d, taps) and "conv_b" not in p
    assert rel(got, want) < 1e-5


@pytest.mark.parametrize("changed", [5, 10])
def test_shortconv_is_causal_and_three_taps_deep(changed):
    """A later token changes no earlier output, and reaches exactly the
    ``conv_L_cache`` positions from its own on."""
    cfg = small(pattern="C")
    p = init_params(cfg)["layers"][0]
    rng = np.random.default_rng(2)
    n = rng.standard_normal((1, 12, cfg.hidden_size)).astype(np.float32)
    other = n.copy()
    other[0, changed] += 1.0
    a = np.asarray(shortconv_mixer(p, jnp.asarray(n), cfg))
    b = np.asarray(shortconv_mixer(p, jnp.asarray(other), cfg))
    moved = np.any(a != b, axis=-1)[0]
    assert not moved[:changed].any()
    assert moved[changed:changed + cfg.conv_L_cache].all()
    assert not moved[changed + cfg.conv_L_cache:].any()


# -- the ``*`` block's two switches -------------------------------------------
def _attention_before_the_switches(p, n, cfg):
    """``attention_mixer`` as it was before it read ``attn_qk_norm`` and
    ``attn_rope``: no positions, no norm."""
    bsz, s, _ = n.shape
    kh = cfg.num_key_value_heads
    g = cfg.num_attention_heads // kh
    q = (n @ p["wq"]).reshape(bsz, s, kh, g, cfg.head_dim)
    k = (n @ p["wk"]).reshape(bsz, s, kh, cfg.head_dim)
    v = (n @ p["wv"]).reshape(bsz, s, kh, cfg.head_dim)
    o = causal_gqa(q, k, v, cfg.attn_block)
    return o.reshape(bsz, s, cfg.q_dim) @ p["wo"]


def test_attention_with_both_switches_off_is_the_function_it_was():
    """Bitwise, and the same program text: what ``nemotron_train`` runs."""
    cfg = small(pattern="*", attn_qk_norm=False, attn_rope=False)
    p = init_params(cfg)["layers"][0]
    assert "q_norm" not in p and "k_norm" not in p
    rng = np.random.default_rng(4)
    n = jnp.asarray(rng.standard_normal((2, 21, cfg.hidden_size)),
                    jnp.float32)
    now = jax.jit(lambda p, n: attention_mixer(p, n, cfg))
    was = jax.jit(lambda p, n: _attention_before_the_switches(p, n, cfg))
    np.testing.assert_array_equal(now(p, n), was(p, n))
    assert now.lower(p, n).as_text() == was.lower(p, n).as_text()


@pytest.mark.parametrize("off", ["attn_qk_norm", "attn_rope"])
def test_each_attention_switch_is_seen(off):
    """With one switch off the block is another function than the
    reference's: the norm and the turn are each in the comparison."""
    cfg = small(pattern="*")
    p, rng = block_params(cfg)
    u = jnp.asarray(rng.standard_normal((2, 16, cfg.hidden_size)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.layer("*", p, None, u, sizes(cfg), cfg.held)[0]
    got = layer_forward("*", p, None, u, small(pattern="*", **{off: False}))
    assert rel(got[0] - u, want - u) > 0.05
    assert rel(layer_forward("*", p, None, u, cfg)[0], want) < TOL["loss"]


# -- the share test -----------------------------------------------------------
@pytest.mark.parametrize("side", ["program", "reference"])
def test_four_expert_shares_add_up_to_the_uncut_block(side):
    """The parts that the four shares ``held_experts`` 0..7, 8..15, 16..23,
    24..31 give add up to what the block holding all 32 experts gives, and
    to the uncut reference's whole layer: there is no shared expert, so
    nothing is counted once."""
    whole = small(pattern="E", router_experts=32, held=tuple(range(32)),
                  num_experts_per_tok=4, moe_intermediate_size=16)
    p = init_params(whole)["layers"][0]
    assert not any(k.startswith("s_") for k in p)
    bias = init_buffers(whole)[0]
    rng = np.random.default_rng(2)
    n = jnp.asarray(rng.standard_normal((2, 20, whole.hidden_size)),
                    jnp.float32)
    s = sizes(whole)

    def share(held):
        at = np.asarray(held)
        part = dict(p, w_gate=p["w_gate"][at], w_up=p["w_up"][at],
                    w_down=p["w_down"][at])
        if side == "reference":
            with jax.default_matmul_precision("highest"):
                y, counts = ref.expert_block(part, bias, n, s, held)
                return y.reshape(40, -1), counts[np.asarray(held)]
        return held_topk_moe(
            n.reshape(40, -1), part["router"], bias, part["w_up"],
            part["w_down"], None, None, held, whole.num_experts_per_tok,
            whole.routed_scaling_factor, whole.norm_topk_prob, 4, False,
            "sigmoid", part["w_gate"], None)

    uncut, uncut_counts = share(tuple(range(32)))
    parts = [share(tuple(range(8 * i, 8 * i + 8))) for i in range(4)]
    assert rel(sum(y for y, _ in parts), uncut) < 1e-5
    with jax.default_matmul_precision("highest"):
        whole_reference = ref.expert_block(p, bias, n, s, tuple(range(32)))[0]
    assert rel(sum(y for y, _ in parts),
               whole_reference.reshape(40, -1)) < TOL["loss"]
    np.testing.assert_array_equal(
        np.concatenate([c for _, c in parts]), uncut_counts)
    # every assignment lands on exactly one share
    assert int(np.sum(uncut_counts)) == 40 * whole.num_experts_per_tok
    # and a share alone is not the layer
    assert rel(parts[0][0], uncut) > 0.3


@pytest.mark.parametrize("shared_width", [0, 48])
def test_an_expert_block_has_shared_leaves_only_where_it_has_a_shared_expert(
        shared_width):
    cfg = small(pattern="E",
                moe_shared_expert_intermediate_size=shared_width)
    shapes = param_shapes(cfg)["layers"][0]
    assert {"s_up", "s_down", "s_gate"} <= set(shapes) \
        if shared_width else not any(k.startswith("s_") for k in shapes)
    assert set(shapes) >= {"norm", "router", "w_up", "w_down", "w_gate"}
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.standard_normal((1, 8, cfg.hidden_size)), jnp.float32)
    p = init_params(cfg)["layers"][0]
    grads = jax.grad(lambda p: jnp.sum(layer_forward(
        "E", p, init_buffers(cfg)[0], u, cfg)[0] ** 2))(p)
    # no leaf of width zero rides through the gradient
    assert all(g.size > 0 for g in jax.tree_util.tree_leaves(grads))
    assert set(grads) == set(shapes)


@pytest.mark.parametrize("biased", [True, False])
def test_selection_bias_is_seeded_small_or_zero(biased):
    cfg = small(use_expert_bias=biased)
    buffers = init_buffers(cfg)
    assert [b is not None for b in buffers] == \
        [k == "E" for k in cfg.pattern]
    for b in (b for b in buffers if b is not None):
        assert b.shape == (cfg.router_experts,)
        assert bool(np.any(np.asarray(b) != 0)) == biased
        assert float(np.abs(b).max()) <= 0.01


@pytest.mark.parametrize("counts,moves", [
    ([4, 4, 4, 4], [0, 0, 0, 0]), ([9, 3, 3, 1], [-1, 1, 1, 1]),
    ([0, 0, 0, 16], [1, 1, 1, -1]), ([5, 4, 4, 3], [-1, 0, 0, 1])])
def test_selection_bias_moves_by_the_rate_towards_the_mean_load(counts,
                                                                moves):
    """``b_e += u * sign(mean(c) - c_e)``, in the program and in the
    reference alike, bit for bit."""
    from multiverso_tpu.models.hybrid_lm import updated_expert_bias
    bias = np.array([0.004, -0.01, 0.0, 0.0075], np.float32)
    got = updated_expert_bias(jnp.asarray(bias), np.array(counts), 0.01)
    np.testing.assert_array_equal(
        got, bias + np.float32(0.01) * np.array(moves, np.float32))
    np.testing.assert_array_equal(
        got, ref.updated_bias(jnp.asarray(bias), np.array(counts), 0.01))


@pytest.mark.parametrize("rate", [0.0, 0.01])
def test_a_step_moves_the_selection_bias_only_at_a_rate(rate):
    cfg = small(expert_bias_update_rate=rate)
    model = HybridLM(cfg, mode="local")
    before = [None if b is None else np.array(b) for b in model.buffers]
    model.step(batch(cfg, seed=4))
    for kind, b0, b1 in zip(cfg.pattern, before, model.buffers):
        if kind != "E":
            assert b0 is None and b1 is None
        elif rate:
            moved = np.abs(np.asarray(b1) - b0)
            assert moved.max() > 0 and np.all(
                np.isclose(moved, rate) | (moved == 0))
        else:
            np.testing.assert_array_equal(b1, b0)


@pytest.mark.parametrize("change", [
    {"scoring_func": "softmax"}, {"use_expert_bias": False},
    {"expert_bias_update_rate": -0.01}])
def test_only_a_sigmoid_routers_seeded_bias_is_updated(change):
    with pytest.raises(Exception, match="selection bias"):
        small(**dict({"expert_bias_update_rate": 0.01}, **change)).validate()


# -- the tied head ------------------------------------------------------------
def test_pack_batch_names_every_row_of_the_slice_under_a_tied_head():
    tokens = np.array([[3, 9, 3, 60], [0, 9, 9, 1]], np.int32)
    ids, n, where, targets, mask = pack_batch(tokens, 16, 0, 1, 64)
    np.testing.assert_array_equal(ids, np.arange(64))
    assert ids.dtype == np.int32 and n == 64
    np.testing.assert_array_equal(where, tokens)
    plain = pack_batch(tokens, 16)
    np.testing.assert_array_equal(targets, plain[3])
    np.testing.assert_array_equal(mask, plain[4])
    assert len(plain[0]) == 16 and plain[1] == 5


def test_a_tied_model_has_no_head_leaf_and_counts_its_matrix_once():
    cfg = small()
    untied = small(tie_word_embeddings=False)
    assert "head" not in param_shapes(cfg) and "head" not in init_params(cfg)
    assert dense_param_count(untied) - dense_param_count(cfg) == \
        cfg.hidden_size * cfg.vocab_size
    # the leaves they share are drawn alike: the head is drawn last
    for a, b in zip(jax.tree_util.tree_leaves(init_params(cfg)["layers"]),
                    jax.tree_util.tree_leaves(init_params(untied)["layers"])):
        np.testing.assert_array_equal(a, b)


def test_tied_rows_delta_is_the_embedding_uses_plus_the_head_uses():
    """``value_and_grad`` over the pulled rows sums both uses: the gradient
    with the head's use cut off plus the gradient with the embedding's use
    cut off is the whole; a row no token named has the head's alone, and it
    is not zero."""
    cfg = small()
    params, buffers = init_params(cfg), init_buffers(cfg)
    rng = np.random.default_rng(7)
    table = jnp.asarray(0.02 * rng.standard_normal(
        (cfg.vocab_size, cfg.hidden_size)), jnp.float32)
    tokens = batch(cfg, seed=3, ids=40)         # rows 40..63: no token
    _, _, where, targets, mask = pack_batch(tokens, 1, 0, 1, cfg.vocab_size)
    loss_fn = make_loss(cfg)
    cut = jax.lax.stop_gradient

    def whole(rows):
        return loss_fn(params, rows, buffers, where, targets, mask)[0]

    def embedding_only(rows):
        # the head reads a copy that carries no gradient
        return make_loss(small(tie_word_embeddings=False))(
            dict(params, head=cut(rows).T), rows, buffers, where, targets,
            mask)[0]

    def head_only(rows):
        return make_loss(small(tie_word_embeddings=False))(
            dict(params, head=rows.T), cut(rows), buffers, where, targets,
            mask)[0]

    g, g_emb, g_head = (np.asarray(jax.jit(jax.grad(f))(table))
                        for f in (whole, embedding_only, head_only))
    assert float(whole(table)) == pytest.approx(float(head_only(table)),
                                                rel=1e-6)
    assert rel(g, g_emb + g_head) < 1e-5
    unnamed = np.setdiff1d(np.arange(cfg.vocab_size), tokens)
    assert len(unnamed) >= 24
    assert not g_emb[unnamed].any()
    assert np.all(np.linalg.norm(g[unnamed], axis=-1) > 0)
    np.testing.assert_allclose(g[unnamed], g_head[unnamed], rtol=1e-5,
                               atol=1e-9)
    assert np.linalg.norm(g_emb) > 0.1 * np.linalg.norm(g_head)


def test_a_step_moves_rows_that_no_token_named():
    cfg = small()
    model = HybridLM(cfg, mode="local")
    before = model.local_rows().copy()
    tokens = batch(cfg, seed=5, ids=40)
    model.step(tokens)
    moved = np.any(model.local_rows() != before, axis=-1)
    assert moved.all()
    assert set(np.unique(tokens)) < set(range(cfg.vocab_size))


# -- the whole model ----------------------------------------------------------
def _reference_steps(cfg, params0, buffers, rows0, batches, compute="float32",
                     storage=None):
    """Two AdaGrad steps of the reference from the model's own start, the
    selection bias moved between them: (losses, assignments to every expert,
    parameters, rows, first step's gradients, the bias after the steps)."""
    s = sizes(cfg)
    experts = [i for i, kind in enumerate(cfg.pattern) if kind == "E"]
    buffers = list(buffers)

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
    losses, counts, first = [], [], None
    for tokens in batches:
        _, _, _, targets, mask = pack_batch(tokens, 1)
        loss, c, gp, gtable = ref.value_and_grads(
            params, jnp.asarray(rows), buffers, tokens, targets, mask, s,
            cfg.held, compute=compute)
        first = first or (gp, gtable)
        stepped = jax.tree_util.tree_map(
            lambda w, a, g: ref.adagrad(w, a, g, cfg.adagrad_step),
            params, g2, gp)
        params = stored(jax.tree_util.tree_map(
            lambda w, pair: pair[0], params, stepped))
        g2 = jax.tree_util.tree_map(lambda w, pair: pair[1], params, stepped)
        new_rows, rows_g2 = ref.adagrad(rows, rows_g2, np.asarray(gtable),
                                        cfg.adagrad_step)
        rows, rows_g2 = np.array(stored(new_rows)), np.array(rows_g2)
        losses.append(float(loss))
        counts.append(np.asarray(c))
        for i, per_expert in zip(experts, counts[-1]):
            buffers[i] = ref.updated_bias(buffers[i], per_expert,
                                          cfg.expert_bias_update_rate)
    return losses, counts, params, rows, first, buffers


def test_whole_model_two_steps_match_reference():
    cfg = small()
    model = HybridLM(cfg, mode="local")
    start = jax.tree_util.tree_map(np.array, model.params)
    rows0 = model.local_rows()
    batches = [batch(cfg, seed=1, ids=48), batch(cfg, seed=2, ids=48)]

    # gradients of the first step, before anything moves
    ids, _, where, targets, mask = pack_batch(batches[0], cfg.row_bucket, 0,
                                              1, cfg.vocab_size)
    (_, counts), (gp, grows) = jax.jit(jax.value_and_grad(
        make_loss(cfg), argnums=(0, 1), has_aux=True))(
            model.params, jnp.asarray(rows0[ids]), model.buffers, where,
            targets, mask)
    bias0 = list(model.buffers)
    losses = [model.step(b) for b in batches]
    want = _reference_steps(cfg, start, bias0, rows0, batches)
    want_gp, want_gtable = want[4]
    assert worst(gp, want_gp) < TOL["grad"]
    assert rel(grows, want_gtable) < TOL["grad"]
    held = list(cfg.held)
    np.testing.assert_array_equal(counts[0], want[1][0][:, held])
    np.testing.assert_array_equal(counts[1], want[1][0])
    np.testing.assert_array_equal(model.last_counts, want[1][1][:, held])
    # the selection bias moved twice, by the rate, as the reference's
    for i in (5, 7):
        np.testing.assert_array_equal(model.buffers[i], want[5][i])
        moved = np.abs(np.asarray(model.buffers[i]) - np.asarray(bias0[i]))
        assert set(np.round(moved / 0.01).astype(int)) <= {0, 1, 2} \
            and moved.max() > 0.005
    assert max(abs(g - w) / abs(w)
               for g, w in zip(losses, want[0])) < TOL["loss"]
    # every dense leaf and EVERY row of the slice, the unnamed ones too
    assert worst(model.params, want[2]) < TOL["step"]
    assert rel(model.local_rows() - rows0, want[3] - rows0) < 5e-3
    assert rel(model.local_rows(), want[3]) < TOL["step"]
    unnamed = np.arange(48, cfg.vocab_size)
    assert rel((model.local_rows() - rows0)[unnamed],
               (want[3] - rows0)[unnamed]) < 5e-3

    # the control: the reference computed and stored in bfloat16, put in the
    # program's place, fails at least one of the same tolerances
    low = _reference_steps(cfg, start, bias0, rows0, batches,
                           compute="bfloat16", storage="bfloat16")
    control = {"loss": max(abs(g - w) / abs(w)
                           for g, w in zip(low[0], want[0])),
               "step": max(worst(low[2], want[2]), rel(low[3], want[3]))}
    assert control["loss"] > TOL["loss"] or control["step"] > TOL["step"]


def test_reference_block_by_block_gradients_are_the_whole_models():
    cfg = small()
    params, buffers = init_params(cfg), init_buffers(cfg)
    rng = np.random.default_rng(5)
    tokens = batch(cfg, seed=3)
    _, _, _, targets, mask = pack_batch(tokens, 1)
    table = jnp.asarray(rng.standard_normal(
        (cfg.vocab_size, cfg.hidden_size)), jnp.float32) * 0.02
    s = sizes(cfg)
    loss, counts, gp, gtable = ref.value_and_grads(
        params, table, buffers, tokens, targets, mask, s, cfg.held)
    got = {}
    loss2, counts2, gtable2 = ref.grads_by_layer(
        lambda i: params["layers"][i], lambda: (params["final_norm"],),
        table, buffers, tokens, targets, mask, s, cfg.held, got.__setitem__)
    assert abs(float(loss2) - float(loss)) < 1e-6
    np.testing.assert_array_equal(counts2, counts)
    assert rel(gtable2, gtable) < 1e-5
    assert rel(got["top"][0], gp["final_norm"]) < 1e-5
    for i in range(len(cfg.pattern)):
        assert worst(got[i], gp["layers"][i]) < 1e-5


def test_the_loss_falls():
    cfg = small(adagrad_step=0.01)
    model = HybridLM(cfg, mode="local")
    tokens = batch(cfg, seed=11)
    losses = [model.step(tokens) for _ in range(8)]
    assert losses[-1] < losses[0] - 0.2


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
    device_calls = [get_registry().counter(f"table.group.device_{kind}")
                    for kind in ("pulls", "pushes")]
    before = [c.value for c in device_calls]
    assert [local.step(b) for b in batches] == [ps.step(b) for b in batches]
    assert [c.value - b for c, b in zip(device_calls, before)] == \
        [len(batches) * table_devices] * 2
    # the whole slice a step: one compiled shape whatever the batch names
    assert ps._hybrid.delta._cache_size() == 1 == \
        local._hybrid.delta._cache_size()
    for (name, a), (_, b) in zip(local.dense_leaves(), ps.dense_leaves()):
        np.testing.assert_array_equal(a, b, err_msg=name)
    assert not any("head" in name for name, _ in ps.dense_leaves())
    np.testing.assert_array_equal(
        local.local_rows(),
        ps.table.get_rows(np.arange(cfg.vocab_size, dtype=np.int32)))
    for a, b in zip(jax.tree_util.tree_leaves(local.state),
                    jax.tree_util.tree_leaves(ps.state)):
        np.testing.assert_array_equal(a, b)


def test_step_spans_counters_scopes_and_program_names():
    """``lfm2_mfu_share`` reads the counters and ``jit_lm_delta_step``,
    ``lfm2_conv_device_ms`` the scope ``lm_shortconv``; ``lm.head.tied``
    moves one a step and ``lm.rows_pulled`` the whole slice: the names are
    part of the yardstick."""
    from multiverso_tpu.telemetry import program_scopes
    from multiverso_tpu.telemetry.device_scopes import scope_names
    cfg = small()
    model = HybridLM(cfg, mode="local")
    tokens = batch(cfg, seed=9)
    reg = get_registry()
    names = ("lm.step", "lm.pull", "lm.compute", "lm.compute.dispatch",
             "lm.compute.sync", "lm.push")
    before = {n: reg.histogram("span." + n).count for n in names}
    counted = ("lm.tokens", "lm.rows_pulled", "lm.head.tied",
               "lm.attn.pairs", "lm.moe.assignments_held.l5",
               "lm.moe.max_expert_load.l5", "lm.moe.assignments_held.l7")
    c0 = {n: reg.counter(n).value for n in counted}
    steps = 3
    for _ in range(steps):
        model.step(tokens)
    moved = {n: reg.counter(n).value - c0[n] for n in counted}
    for n in names:
        assert reg.histogram("span." + n).count == before[n] + steps, n
    assert moved["lm.tokens"] == steps * tokens.size
    assert moved["lm.head.tied"] == steps
    assert moved["lm.rows_pulled"] == steps * cfg.vocab_size
    assert len(np.unique(tokens)) < cfg.vocab_size
    # one attention block, two sequences of 21: 21 * 22 / 2 pairs each
    assert moved["lm.attn.pairs"] == steps * 2 * 231
    assert moved["lm.moe.assignments_held.l7"] > 0
    assert model.last_counts.shape == (2, 2)
    assert model._hybrid.delta.__name__ == DELTA_PROGRAM
    scopes = {name for path in program_scopes()["jit_" + DELTA_PROGRAM]
              .values() for name in scope_names(path)}
    assert {"lm_shortconv", "lm_attention", "lm_dense_ffn", "lm_experts",
            "lm_embed", "lm_head_loss", "lm_scale"} <= scopes


def test_an_untied_model_counts_no_tied_head():
    cfg = small(tie_word_embeddings=False)
    model = HybridLM(cfg, mode="local")
    reg = get_registry()
    tied0 = reg.counter("lm.head.tied").value
    rows0 = reg.counter("lm.rows_pulled").value
    tokens = batch(cfg, seed=9)
    model.step(tokens)
    assert reg.counter("lm.head.tied").value == tied0
    assert reg.counter("lm.rows_pulled").value - rows0 == \
        len(np.unique(tokens))
    assert "head" in model.params


# -- the configuration file ---------------------------------------------------
#: The catalog's row for LFM2-8B-A1B (``model-configs`` guide): every number
#: of its ``config``; the three cut keys hold the PUBLISHED value here.
CATALOG = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
           "intermediate_size": 7168, "max_position_embeddings": 128000,
           "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
           "norm_eps": 1e-05, "norm_topk_prob": True,
           "num_attention_heads": 32, "num_dense_layers": 2,
           "num_experts": 32, "num_experts_per_tok": 4,
           "num_hidden_layers": 24, "num_key_value_heads": 8,
           "rope_theta": 1000000, "routed_scaling_factor": 1,
           "use_expert_bias": True, "vocab_size": 65536}
LAYER_TYPES = ["conv" if c == "c" else "full_attention"
               for c in "ccacccacccacccacccaccacc"]
CUT = {"num_hidden_layers": 6, "num_experts": 8, "vocab_size": 16384}


@pytest.fixture(scope="module")
def config_file():
    with open(CONFIG_FILE) as f:
        return json.load(f)


@pytest.mark.parametrize("key", sorted(CATALOG))
def test_config_file_has_the_catalogs_value(config_file, key):
    if key in CUT:
        assert config_file[key] == CUT[key]
        assert config_file["published"][key] == CATALOG[key]
        assert key in config_file["reduced"] \
            and key in config_file["reduced_why"]
    else:
        assert config_file[key] == CATALOG[key]
        assert type(config_file[key]) is type(CATALOG[key])


def test_config_file_states_its_cut_deployment_and_assumptions(config_file):
    c = config_file
    assert c["layer_types"] == LAYER_TYPES and len(LAYER_TYPES) == 24
    assert sorted(c["reduced"]) == sorted(CUT)
    assert c["held_experts"] == list(range(8))
    assert c["tie_word_embeddings"] is True
    assert "four" in c["deployment"] and "expert" in c["deployment"]
    for key in ("tie_word_embeddings", "router_eps", "expert_bias",
                "expert_activation", "init", "embedding_rows", "optimizer",
                "dtype", "documents", "sequence_length"):
        assert key in c["assumed"], key
    assert c["guarantees"]


def test_from_file_gives_the_twelve_blocks_and_the_parameter_count():
    cfg = HybridLMConfig.from_file(CONFIG_FILE)
    assert cfg.pattern == "CDCD*ECECECE"
    assert dense_param_count(cfg) == 535_093_376
    assert (cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim) == (2048, 32, 8, 64)
    assert (cfg.intermediate_size, cfg.moe_intermediate_size,
            cfg.conv_L_cache) == (7168, 1792, 3)
    assert (cfg.router_experts, cfg.held, cfg.num_experts_per_tok) == \
        (32, tuple(range(8)), 4)
    assert cfg.moe_shared_expert_intermediate_size == 0
    assert cfg.attn_qk_norm and cfg.attn_rope and cfg.rope_theta == 1e6
    assert cfg.scoring_func == "sigmoid" and cfg.use_expert_bias
    assert cfg.expert_bias_update_rate == 0.01
    assert cfg.gated_experts and cfg.norm_topk_prob
    assert cfg.routed_scaling_factor == 1 and cfg.norm_eps == 1e-5
    assert cfg.tie_word_embeddings and cfg.vocab_size == 16384
    shapes = param_shapes(cfg)
    assert "head" not in shapes
    by_kind = {k: sum(int(np.prod(s)) for s in shapes["layers"][i].values())
               for i, k in enumerate(cfg.pattern)}
    assert by_kind == {"C": 16_785_408, "*": 10_487_936, "D": 44_042_240,
                       "E": 88_147_968}
    # the reference reads the same file to the same pattern and head size
    with open(CONFIG_FILE) as f:
        s = ref.sizes_of(json.load(f))
    assert s["pattern"] == cfg.pattern and s["head_dim"] == 64


def test_the_published_model_is_8_3_b_with_one_tied_matrix(config_file):
    """The row's "8.3B-A1.5B" holds with ONE 65,536 x 2,048 matrix: the head
    is tied (``assumed``: the key is not in the catalog's ``config``)."""
    c = dict(config_file, **config_file["published"])
    d = c["hidden_size"]
    conv = d * 3 * d + d * c["conv_L_cache"] + d * d + d
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // heads
    attn = 2 * d * heads * hd + 2 * d * kv * hd + 2 * hd + d
    dense = 3 * d * c["intermediate_size"] + d
    expert = 3 * d * c["moe_intermediate_size"]
    block = c["num_experts"] * expert + d * c["num_experts"] + d
    kinds = c["layer_types"]
    body = kinds.count("conv") * conv + kinds.count("full_attention") * attn \
        + c["num_dense_layers"] * dense \
        + (c["num_hidden_layers"] - c["num_dense_layers"]) * block + d
    matrix = c["vocab_size"] * d
    assert matrix == 134_217_728
    assert round((body + matrix) / 1e9, 2) == 8.34
    assert round((body + 2 * matrix) / 1e9, 2) == 8.47
    active = body - 22 * (c["num_experts"] - c["num_experts_per_tok"]) \
        * expert + matrix
    assert round(active / 1e9, 2) == 1.56


@pytest.mark.parametrize("change,message", [
    ({"conv_bias": True}, "conv_bias"),
    ({"layer_types": ["conv", "sliding_attention"] * 12}, "unknown kinds"),
    ({"layer_types": ["conv"] * 3}, "names 3 layers"),
    ({"held_experts": [0, 1, 2]}, "num_experts says 8"),
])
def test_from_dict_refuses_what_it_does_not_implement(config_file, change,
                                                      message):
    with pytest.raises(ValueError, match=message):
        HybridLMConfig.from_dict(dict(config_file, **change))


def test_a_file_that_holds_no_expert_is_dense_in_every_layer(config_file):
    c = {k: v for k, v in config_file.items()
         if k not in ("num_experts", "held_experts")}
    cfg = HybridLMConfig.from_dict(c, vocab_size=64)
    assert cfg.pattern == "CDCD*DCDCDCD"
