"""The layer-typed LM's looped stack (the SAME leaves run ``total_ut_steps``
times, the final norm inside the loop), its sandwich norms, its exit gate and
the exit-weighted loss over every pass's logits, against the benchmark's plain
reference (benchmark/reference/ouro-2.6b-pp8.py) at a small size on the CPU:
every block's forward and gradients; the whole forward; the loss and every
leaf's and row's gradient; a shared leaf's gradient as the SUM over four untied
copies; the rolled loop against the passes unrolled in Python; the exit
distribution; two AdaGrad steps with a bfloat16 control that fails; at one
pass and no post-norm the parent's program text; the PS plane against its
local twin; counters, gauges, scopes; and the configuration file against the
catalog's row."""
import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from multiverso_tpu.models.hybrid_lm import (DELTA_PROGRAM, HybridLM,
                                             HybridLMConfig,
                                             dense_param_count,
                                             exit_distribution,
                                             forward_hidden, init_buffers,
                                             init_params, layer_forward,
                                             looped_hidden, make_loss,
                                             pack_batch, param_shapes,
                                             rmsnorm)
from multiverso_tpu.models.hybrid_lm.model import (
    blocked_cross_entropy, exit_weighted_cross_entropy)
from multiverso_tpu.telemetry.metrics import get_registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "ouro-2.6b-pp8"
CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json")


def _reference():
    path = os.path.join(ROOT, "benchmark", "reference", CONFIG + ".py")
    spec = importlib.util.spec_from_file_location("ouro_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()

# hidden 32, two layers of 2 heads of 16 turned over the whole head and a
# SwiGLU of 48, a norm on every mixer's output, four passes, vocabulary 64
SMALL = dict(hidden_size=32, vocab_size=64, pattern="*D*D", norm_eps=1e-6,
             num_attention_heads=2, num_key_value_heads=2, head_dim=16,
             attn_rope=True, rope_theta=1000000.0, intermediate_size=48,
             post_norm=True, total_ut_steps=4, exit_entropy_weight=0.05,
             hidden_act="silu", attn_block=8, loss_block=16, row_bucket=16)
TOL = dict(loss=2e-5, grad=2e-4, step=2e-4)


def small(**kw) -> HybridLMConfig:
    return HybridLMConfig(**dict(SMALL, **kw))


def sizes(cfg: HybridLMConfig) -> dict:
    return {"pattern": cfg.pattern, "norm_eps": cfg.norm_eps,
            "num_attention_heads": cfg.num_attention_heads,
            "head_dim": cfg.head_dim, "rope_theta": cfg.rope_theta,
            "total_ut_steps": cfg.total_ut_steps,
            "exit_entropy_weight": cfg.exit_entropy_weight}


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


def moved_params(cfg, seed=3):
    """Seeded leaves with every norm moved off one and the gate off zero, so
    that each is seen."""
    rng = np.random.default_rng(seed)

    def move(path, leaf):
        name = path[-1].key
        if "norm" in name or name.startswith("exit_gate"):
            return leaf + 0.1 * rng.standard_normal(leaf.shape).astype(
                np.float32)
        return leaf

    return jax.tree_util.tree_map_with_path(move, init_params(cfg)), rng


def batch_arrays(cfg, tokens, rng):
    ids, _, where, targets, mask = pack_batch(tokens, cfg.row_bucket)
    rows = jnp.asarray(rng.standard_normal((len(ids), cfg.hidden_size)),
                       jnp.float32)
    return rows, where, targets, mask


# -- each block, forward and gradients ----------------------------------------
@pytest.mark.parametrize("length", [16, 21])
@pytest.mark.parametrize("kind", ["*", "D"])
def test_block_matches_reference(kind, length):
    cfg = small(pattern=kind)
    params, rng = moved_params(cfg)
    p = params["layers"][0]
    assert "post_norm" in p and "q_norm" not in p
    u = jnp.asarray(rng.standard_normal((2, length, cfg.hidden_size)),
                    jnp.float32)
    w = jnp.asarray(rng.standard_normal(u.shape), jnp.float32)
    s = sizes(cfg)

    def mine(p, u):
        return jnp.sum(layer_forward(kind, p, None, u, cfg)[0] * w)

    def theirs(p, u):
        return jnp.sum(ref.block(kind, p, u, s) * w)

    out, counts = jax.jit(lambda p, u: layer_forward(kind, p, None, u, cfg))(
        p, u)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, u: ref.block(kind, p, u, s))(p, u)
        want_grads = jax.jit(jax.grad(theirs, argnums=(0, 1)))(p, u)
    assert counts is None
    assert rel(out, want) < TOL["loss"]
    assert worst(jax.jit(jax.grad(mine, argnums=(0, 1)))(p, u),
                 want_grads) < TOL["grad"]


@pytest.mark.parametrize("kind", ["*", "D", "C", "M"])
def test_a_post_norm_is_a_leaf_only_where_the_configuration_has_one(kind):
    with_norm = param_shapes(small(pattern=kind))["layers"][0]
    without = param_shapes(small(pattern=kind, post_norm=False))["layers"][0]
    assert set(with_norm) - set(without) == {"post_norm"}
    assert with_norm["post_norm"] == (SMALL["hidden_size"],)
    assert np.all(np.asarray(
        init_params(small(pattern=kind))["layers"][0]["post_norm"]) == 1.0)


@pytest.mark.parametrize("kind", ["*", "D"])
def test_the_post_norm_is_seen(kind):
    """A block's residual branch has the post-norm's scale, whatever the
    mixer's: u + w2 * y / rms(y)."""
    cfg = small(pattern=kind)
    params, rng = moved_params(cfg)
    p = params["layers"][0]
    u = jnp.asarray(rng.standard_normal((1, 12, cfg.hidden_size)),
                    jnp.float32)
    out = layer_forward(kind, p, None, u, cfg)[0]
    plain = layer_forward(kind, p, None, u, small(pattern=kind,
                                                  post_norm=False))[0]
    # (the mixer's own output is small beside u: the differences round)
    assert rel(out - u, rmsnorm(plain - u, p["post_norm"], cfg.norm_eps)) \
        < 2e-4
    assert rel(out, plain) > 0.1


# -- the exit distribution ----------------------------------------------------
@pytest.mark.parametrize("passes", [2, 3, 4, 6])
def test_exit_distribution_sums_to_one_and_the_last_pass_takes_the_rest(
        passes):
    rng = np.random.default_rng(passes)
    g = jnp.asarray(3.0 * rng.standard_normal((passes, 5, 7)), jnp.float32)
    p = np.asarray(exit_distribution(g), np.float64)
    assert p.shape == g.shape and (p >= 0).all()
    np.testing.assert_allclose(p.sum(axis=0), 1.0, atol=1e-6)
    leave = 1.0 / (1.0 + np.exp(-np.asarray(g, np.float64)))
    np.testing.assert_allclose(p[0], leave[0], rtol=1e-5)
    np.testing.assert_allclose(p[-1], np.prod(1.0 - leave[:-1], axis=0),
                               rtol=1e-5, atol=1e-7)
    # the last pass's own gate logit is not read
    other = g.at[-1].add(5.0)
    np.testing.assert_array_equal(exit_distribution(other),
                                  exit_distribution(g))
    np.testing.assert_allclose(p, ref.exit_distribution(g), rtol=1e-5,
                               atol=1e-7)


def test_a_zero_gate_starts_at_a_half_a_quarter_an_eighth_and_the_rest():
    np.testing.assert_array_equal(
        exit_distribution(jnp.zeros((4, 3))),
        np.broadcast_to(np.array([[.5], [.25], [.125], [.125]]), (4, 3)))
    params = init_params(small())
    assert not np.any(np.asarray(params["exit_gate_w"]))
    assert not np.any(np.asarray(params["exit_gate_b"]))
    assert params["exit_gate_w"].shape == (32,)
    assert params["exit_gate_b"].shape == (1,)


# -- the loop -----------------------------------------------------------------
def _unrolled(params, u, cfg):
    """The passes one after the other, in Python."""
    hs = []
    for _ in range(cfg.total_ut_steps):
        for i, kind in enumerate(cfg.pattern):
            u, _ = layer_forward(kind, params["layers"][i], None, u, cfg,
                                 True)
        u = rmsnorm(u, params["final_norm"], cfg.norm_eps)
        hs.append(u)
    return jnp.stack(hs)


@pytest.mark.parametrize("passes", [2, 4])
def test_whole_forward_matches_reference_and_the_unrolled_passes(passes):
    cfg = small(total_ut_steps=passes)
    params, rng = moved_params(cfg)
    u = jnp.asarray(rng.standard_normal((2, 21, cfg.hidden_size)),
                    jnp.float32)
    buffers = init_buffers(cfg)
    h = jax.jit(lambda p, u: looped_hidden(p, buffers, u, cfg))(params, u)
    assert h.shape == (passes, 2, 21, cfg.hidden_size)
    assert rel(h, jax.jit(lambda p, u: _unrolled(p, u, cfg))(params, u)) \
        < 1e-6
    with jax.default_matmul_precision("highest"):
        want = jnp.stack(ref.hidden_states(params, u, sizes(cfg)))
    assert rel(h, want) < TOL["loss"]
    # a pass's output is normed: it is what the next pass reads
    np.testing.assert_allclose(
        np.mean(np.square(np.asarray(h / params["final_norm"])), axis=-1),
        1.0, rtol=1e-3)


def test_rolled_gradients_are_the_unrolled_ones():
    cfg = small()
    params, rng = moved_params(cfg)
    u = jnp.asarray(rng.standard_normal((2, 21, cfg.hidden_size)),
                    jnp.float32)
    w = jnp.asarray(rng.standard_normal((4,) + u.shape), jnp.float32)
    buffers = init_buffers(cfg)
    rolled = jax.jit(jax.grad(lambda p, u: jnp.sum(
        looped_hidden(p, buffers, u, cfg) * w), argnums=(0, 1)))(params, u)
    plain = jax.jit(jax.grad(lambda p, u: jnp.sum(
        _unrolled(p, u, cfg) * w), argnums=(0, 1)))(params, u)
    used = {"layers": plain[0]["layers"],
            "final_norm": plain[0]["final_norm"]}
    assert worst({k: rolled[0][k] for k in used}, used) < 1e-5
    assert rel(rolled[1], plain[1]) < 1e-5


def test_the_loop_is_rolled_in_the_program():
    """One ``while`` holds the stack: the lowered program names each block's
    scope under ``lm_loop`` and has the blocks once, not four times."""
    cfg = small()
    params = init_params(cfg)
    u = jnp.zeros((1, 16, cfg.hidden_size))

    def text(c):
        return jax.jit(lambda p, u: looped_hidden(
            p, init_buffers(c), u, c)).lower(params, u).as_text()

    four, eight = text(cfg), text(small(total_ut_steps=8))
    assert "stablehlo.while" in four
    assert four.count("dot_general") == eight.count("dot_general")


def test_a_shared_leafs_gradient_is_the_sum_over_four_untied_copies():
    cfg = small()
    params, rng = moved_params(cfg)
    tokens = batch(cfg, seed=2)
    rows, where, targets, mask = batch_arrays(cfg, tokens, rng)
    s = sizes(cfg)

    def untied(copies, top):
        """The reference with pass ``t`` reading ``copies[t]``."""
        u = rows[where]
        hs = []
        for layers in copies:
            for kind, p in zip(s["pattern"], layers):
                u = ref.block(kind, p, u, s)
            u = ref.rmsnorm(u, top["final_norm"], s["norm_eps"])
            hs.append(u)
        return ref.loss_from_states(
            top["head"], top["exit_gate_w"], top["exit_gate_b"],
            [h.reshape(-1, cfg.hidden_size) for h in hs],
            targets.reshape(-1), mask.reshape(-1), s)[0]

    with jax.default_matmul_precision("highest"):
        per_copy = jax.jit(jax.grad(untied))(
            [params["layers"]] * cfg.total_ut_steps, params)
    summed = jax.tree_util.tree_map(lambda *g: sum(g), *per_copy)
    got = jax.jit(jax.grad(make_loss(cfg), has_aux=True))(
        params, rows, init_buffers(cfg), where, targets, mask)[0]
    assert worst(got["layers"], summed) < TOL["grad"]
    # and no single use is the whole of it
    assert worst(got["layers"], per_copy[-1]) > 0.1


# -- the loss -----------------------------------------------------------------
@pytest.mark.parametrize("length,block", [(21, 16), (16, 16), (40, 7)])
def test_exit_weighted_cross_entropy_is_the_sum_of_plain_ones(length, block):
    rng = np.random.default_rng(length)
    passes, d, vocab = 3, 8, 11
    h = jnp.asarray(rng.standard_normal((passes, length, d)), jnp.float32)
    head = jnp.asarray(rng.standard_normal((d, vocab)), jnp.float32)
    targets = jnp.asarray(rng.integers(0, vocab, length), jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, length), jnp.float32)
    weights = jnp.asarray(rng.random((passes, length)), jnp.float32)
    total, per_pass = exit_weighted_cross_entropy(h, head, targets, weights,
                                                  mask, block)
    logits = np.einsum("tnd,dv->tnv", h, head).astype(np.float64)
    lse = np.log(np.exp(logits).sum(-1))
    losses = lse - np.take_along_axis(
        logits, np.asarray(targets)[None, :, None], -1)[..., 0]
    np.testing.assert_allclose(total, (losses * weights).sum(), rtol=1e-5)
    np.testing.assert_allclose(per_pass, (losses * np.asarray(mask)).sum(1),
                               rtol=1e-5)
    # pass by pass it is the blocked loss every other model takes
    ones = jnp.ones(d)
    for t in range(passes):
        plain = blocked_cross_entropy(h[t], ones, head, targets, mask, 1e-12,
                                      block)
        scale = np.sqrt(np.mean(np.square(np.asarray(h[t])), -1,
                                keepdims=True))
        again = exit_weighted_cross_entropy(
            h[t:t + 1] / scale, head, targets, mask[None], mask, block)[1][0]
        np.testing.assert_allclose(plain * max(float(mask.sum()), 1.0),
                                   again, rtol=1e-5)


@pytest.mark.parametrize("passes,beta", [(4, 0.05), (2, 0.0)])
def test_loss_and_gradients_match_reference(passes, beta):
    cfg = small(total_ut_steps=passes, exit_entropy_weight=beta)
    params, rng = moved_params(cfg)
    tokens = batch(cfg, seed=1)
    rows, where, targets, mask = batch_arrays(cfg, tokens, rng)
    (loss, (counts, loop)), (gp, grows) = jax.jit(jax.value_and_grad(
        make_loss(cfg), argnums=(0, 1), has_aux=True))(
            params, rows, init_buffers(cfg), where, targets, mask)
    want_loss, want_loop, want_gp, want_grows = ref.value_and_grads(
        params, rows, where, targets, mask, sizes(cfg))
    assert counts.shape == (0, len(cfg.held))
    assert abs(float(loss) - float(want_loss)) / float(want_loss) \
        < TOL["loss"]
    for name in ("pass_loss", "exit_mass", "exit_entropy"):
        assert rel(loop[name], want_loop[name]) < TOL["loss"], name
    assert loop["pass_loss"].shape == (passes,)
    np.testing.assert_allclose(np.sum(loop["exit_mass"]), 1.0, atol=1e-5)
    assert set(gp) == set(want_gp) == {"layers", "final_norm", "head",
                                       "exit_gate_w", "exit_gate_b"}
    for name in gp:     # the gate's two and the post-norms among them
        assert worst(gp[name], want_gp[name]) < TOL["grad"], name
    assert rel(grows, want_grows) < TOL["grad"]
    assert float(jnp.linalg.norm(gp["exit_gate_w"])) > 0
    assert all(float(jnp.linalg.norm(p["post_norm"])) > 0
               for p in gp["layers"])


def test_the_entropy_term_and_every_pass_are_in_the_loss():
    cfg = small()
    params, rng = moved_params(cfg)
    rows, where, targets, mask = batch_arrays(cfg, batch(cfg, seed=1), rng)
    args = (params, rows, init_buffers(cfg), where, targets, mask)
    loss, (_, loop) = make_loss(cfg)(*args)
    no_entropy = make_loss(small(exit_entropy_weight=0.0))(*args)[0]
    assert float(no_entropy - loss) == pytest.approx(
        0.05 * float(loop["exit_entropy"]), rel=1e-4)
    assert float(no_entropy) == pytest.approx(float(jnp.sum(
        loop["pass_loss"] * loop["exit_mass"])), rel=0.05)
    assert float(loop["exit_entropy"]) > 0.5


def test_reference_block_by_block_gradients_are_the_whole_models():
    cfg = small()
    params, rng = moved_params(cfg, seed=5)
    rows, where, targets, mask = batch_arrays(cfg, batch(cfg, seed=3), rng)
    s = sizes(cfg)
    loss, loop, gp, grows = ref.value_and_grads(params, rows, where, targets,
                                                mask, s)
    got, asked = {}, []

    def get_layer(i):
        asked.append(i)
        return params["layers"][i]

    for on_host in (False, True):
        got.clear()
        loss2, loop2, grows2 = ref.grads_by_layer(
            get_layer, lambda: (params["final_norm"], params["head"],
                                params["exit_gate_w"], params["exit_gate_b"]),
            rows, where, targets, mask, s, got.__setitem__,
            inputs_on_host=on_host)
        assert abs(float(loss2) - float(loss)) < 1e-6
        assert rel(loop2["pass_loss"], loop["pass_loss"]) < 1e-6
        assert rel(grows2, grows) < 1e-5
        for g, name in zip(got["top"], ("final_norm", "head", "exit_gate_w",
                                        "exit_gate_b")):
            assert rel(g, gp[name]) < 1e-5, name
        for i in range(len(cfg.pattern)):
            assert worst(got[i], gp["layers"][i]) < 1e-5
    # a block's leaves are asked for once a pass and direction
    assert len(asked) == 2 * 2 * cfg.total_ut_steps * len(cfg.pattern)


# -- the whole model ----------------------------------------------------------
def _reference_steps(cfg, params0, rows0, batches, compute="float32",
                     storage=None):
    """Two AdaGrad steps of the reference from the model's own start:
    (losses, the passes' numbers, parameters, rows, first step's gradients)."""
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
    losses, loops, first = [], [], None
    for tokens in batches:
        ids, n, where, targets, mask = pack_batch(tokens, 1)
        loss, loop, gp, grows = ref.value_and_grads(
            params, jnp.asarray(rows[ids]), where, targets, mask, s,
            compute=compute)
        first = first or (gp, grows)
        stepped = jax.tree_util.tree_map(
            lambda w, a, g: ref.adagrad(w, a, g, cfg.adagrad_step),
            params, g2, gp)
        params = stored(jax.tree_util.tree_map(
            lambda w, pair: pair[0], params, stepped))
        g2 = jax.tree_util.tree_map(lambda w, pair: pair[1], params, stepped)
        new_rows, new_g2 = ref.adagrad(rows[ids], rows_g2[ids],
                                       np.asarray(grows), cfg.adagrad_step)
        rows[ids], rows_g2[ids] = np.array(stored(new_rows)), \
            np.array(new_g2)
        losses.append(float(loss))
        loops.append(jax.tree_util.tree_map(np.asarray, loop))
    return losses, loops, params, rows, first


def test_whole_model_two_steps_match_reference():
    cfg = small()
    params, _ = moved_params(cfg)
    model = HybridLM(cfg, mode="local", params=params)
    start = jax.tree_util.tree_map(np.array, model.params)
    rows0 = model.local_rows()
    batches = [batch(cfg, seed=1, ids=48), batch(cfg, seed=2, ids=48)]
    losses, passes, masses = [], [], []
    for b in batches:
        losses.append(model.step(b))
        passes.append(np.array(model.last_pass_losses))
        masses.append(np.array(model.last_exit_mass))
    want = _reference_steps(cfg, start, rows0, batches)
    assert max(abs(g - w) / abs(w)
               for g, w in zip(losses, want[0])) < TOL["loss"]
    for got_p, got_m, loop in zip(passes, masses, want[1]):
        assert rel(got_p, loop["pass_loss"]) < TOL["loss"]
        assert rel(got_m, loop["exit_mass"]) < TOL["loss"]
    # every dense leaf (the gate's two and the post-norms among them) and
    # every row
    for name in want[2]:
        assert worst(model.params[name], want[2][name]) < TOL["step"], name
    assert rel(model.params["exit_gate_w"] - start["exit_gate_w"],
               want[2]["exit_gate_w"] - start["exit_gate_w"]) < 5e-3
    assert rel(model.local_rows() - rows0, want[3] - rows0) < 5e-3
    unnamed = np.arange(48, cfg.vocab_size)
    np.testing.assert_array_equal(model.local_rows()[unnamed], rows0[unnamed])

    # the control: the reference computed and stored in bfloat16, put in the
    # program's place, fails at least one of the same tolerances
    low = _reference_steps(cfg, start, rows0, batches, compute="bfloat16",
                           storage="bfloat16")
    control = {"loss": max(abs(g - w) / abs(w)
                           for g, w in zip(low[0], want[0])),
               "step": max(worst(low[2], want[2]), rel(low[3], want[3]))}
    assert control["loss"] > TOL["loss"] or control["step"] > TOL["step"]


def test_the_dense_plane_sees_one_delta_a_leaf_the_sum_of_its_uses():
    """AdaGrad's accumulator after one step is the square of the SUMMED
    gradient (times nothing: lr cancels), not the sum of four squares."""
    cfg = small()
    params, _ = moved_params(cfg)
    model = HybridLM(cfg, mode="local", params=params)
    tokens = batch(cfg, seed=6)
    ids, _, where, targets, mask = pack_batch(tokens, cfg.row_bucket)
    gp = jax.jit(jax.grad(make_loss(cfg), has_aux=True))(
        model.params, jnp.asarray(model.local_rows()[ids]), model.buffers,
        where, targets, mask)[0]
    model.step(tokens)
    for name in ("wq", "post_norm"):
        np.testing.assert_allclose(
            jax.tree_util.tree_leaves(model.state["layers"][0][name])[0][0],
            np.square(np.asarray(gp["layers"][0][name])), rtol=2e-4,
            atol=1e-12)


def test_the_loss_falls():
    cfg = small(adagrad_step=0.01)
    model = HybridLM(cfg, mode="local")
    tokens = batch(cfg, seed=11)
    losses = [model.step(tokens) for _ in range(8)]
    assert losses[-1] < losses[0] - 0.1


# -- one pass, no post-norm: the functions they were --------------------------
def _parent_layer_forward(kind, p, bias, u, cfg, remat=False):
    """A ``*`` / ``D`` block as the parent's ``layer_forward`` wrote it."""
    from multiverso_tpu.models.hybrid_lm.model import _SEQUENCE_MIXERS
    keep = jax.checkpoint if remat else (lambda fn: fn)
    mixer, scope = _SEQUENCE_MIXERS[kind]

    def one_sequence(seq):
        n = rmsnorm(seq[None], p["norm"], cfg.norm_eps,
                    cfg.norm_add_unit_offset)
        return seq + mixer(p, n, cfg)[0]

    with jax.named_scope(scope):
        return jax.lax.map(keep(one_sequence), u), None


def _parent_loss(cfg):
    """``value_and_grad(make_loss)`` of a ``*D`` stack as the parent wrote
    it: one walk, one norm a block, one cross-entropy."""
    def loss_fn(params, rows, buffers, where, targets, mask):
        with jax.named_scope("lm_embed"):
            u = jnp.take(rows, where, axis=0)
        for i, kind in enumerate(cfg.pattern):
            u, _ = _parent_layer_forward(kind, params["layers"][i], None, u,
                                         cfg, True)
        counts = jnp.zeros((0, len(cfg.held)), jnp.int32)
        with jax.named_scope("lm_head_loss"):
            t = u.reshape(-1, cfg.hidden_size)
            tg, mk = targets.reshape(-1), mask.reshape(-1)

            @jax.checkpoint
            def block_loss(ub, tb, mb):
                n = rmsnorm(ub, params["final_norm"], cfg.norm_eps, False)
                logits = (n @ params["head"]).astype(jnp.float32).reshape(
                    (16, -1))
                picked = jnp.take_along_axis(logits, tb[..., None],
                                             axis=-1)[..., 0]
                return jnp.sum(
                    (jax.nn.logsumexp(logits, axis=-1) - picked) * mb,
                    axis=0)

            total, _ = jax.lax.scan(
                lambda total, xs: (total + block_loss(*xs), None),
                jnp.zeros((), jnp.float32),
                (t.reshape(-1, 16, cfg.hidden_size), tg.reshape(-1, 16),
                 mk.reshape(-1, 16)))
            loss = total / jnp.maximum(jnp.sum(mk), 1.0)
        return loss, counts

    return loss_fn


def test_at_one_pass_and_no_post_norm_the_loss_program_is_the_parents():
    cfg = small(total_ut_steps=1, post_norm=False)
    shapes = param_shapes(cfg)
    assert "exit_gate_w" not in shapes
    assert all("post_norm" not in layer for layer in shapes["layers"])
    params = init_params(cfg)
    rng = np.random.default_rng(0)
    tokens = batch(cfg, seqs=2, length=16)
    rows, where, targets, mask = batch_arrays(cfg, tokens, rng)
    args = (params, rows, init_buffers(cfg), where, targets, mask)

    def lowered(fn):
        return jax.jit(jax.value_and_grad(
            fn, argnums=(0, 1), has_aux=True)).lower(*args).as_text()

    now = lowered(make_loss(cfg))
    assert now == lowered(_parent_loss(cfg))
    assert "while" in now and "lm_loop" not in now
    u = jnp.take(rows, where, axis=0)
    out = forward_hidden(params, init_buffers(cfg), u, cfg)
    assert len(out) == 2 and out[0].shape == u.shape


@pytest.mark.parametrize("bad,message", [
    (dict(pattern="*E"), "looped stack"),
    (dict(pattern="SD"), "looped stack"),
    (dict(num_pred_heads=2), "looped stack"),
    (dict(total_ut_steps=0), "at least one"),
    (dict(exit_entropy_weight=-0.1), "not negative"),
])
def test_validate_refuses_what_a_looped_stack_does_not_carry(bad, message):
    from multiverso_tpu.utils.log import FatalError
    with pytest.raises(FatalError, match=message):
        small(**bad).validate()


# -- the planes ------------------------------------------------------------
@pytest.fixture(params=["one_device"])
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
    np.testing.assert_array_equal(local.last_pass_losses,
                                  ps.last_pass_losses)
    for (name, a), (_, b) in zip(local.dense_leaves(), ps.dense_leaves()):
        np.testing.assert_array_equal(a, b, err_msg=name)
    names = [name for name, _ in ps.dense_leaves()]
    assert any("exit_gate_w" in n for n in names)
    assert sum("post_norm" in n for n in names) == len(cfg.pattern)
    np.testing.assert_array_equal(
        local.local_rows(),
        ps.table.get_rows(np.arange(cfg.vocab_size, dtype=np.int32)))
    for a, b in zip(jax.tree_util.tree_leaves(local.state),
                    jax.tree_util.tree_leaves(ps.state)):
        np.testing.assert_array_equal(a, b)


def test_step_counters_gauges_scopes_and_program_names():
    """``ouro_mfu_share`` reads ``lm.tokens``, ``lm.attn.pairs`` (summed over
    block RUNS) and ``lm.loop.passes``; ``ouro_loop_device_ms`` the scope
    ``lm_loop`` and ``ouro_head_loss_device_share`` ``lm_head_loss``: the
    names are part of the yardstick."""
    from multiverso_tpu.telemetry import program_scopes
    from multiverso_tpu.telemetry.device_scopes import scope_names
    cfg = small()
    model = HybridLM(cfg, mode="local")
    tokens = batch(cfg, seed=9)
    reg = get_registry()
    counted = ("lm.tokens", "lm.rows_pulled", "lm.attn.pairs",
               "lm.loop.passes", "lm.loop.block_runs", "lm.head.tied")
    c0 = {n: reg.counter(n).value for n in counted}
    steps = 3
    for _ in range(steps):
        loss = model.step(tokens)
    moved = {n: reg.counter(n).value - c0[n] for n in counted}
    assert moved["lm.tokens"] == steps * tokens.size
    assert moved["lm.rows_pulled"] == steps * len(np.unique(tokens))
    assert moved["lm.loop.passes"] == steps * 4
    assert moved["lm.loop.block_runs"] == steps * 4 * 4
    # two attention blocks run four times, two sequences of 21
    assert moved["lm.attn.pairs"] == steps * 4 * 2 * 2 * 231
    assert moved["lm.head.tied"] == 0
    for t in range(4):
        assert reg.gauge(f"lm.loop.pass_loss.t{t + 1}").last == \
            pytest.approx(float(model.last_pass_losses[t]))
        assert reg.gauge(f"lm.loop.exit_mass.t{t + 1}").last == \
            pytest.approx(float(model.last_exit_mass[t]))
    assert 0.5 < reg.gauge("lm.loop.exit_entropy").last < np.log(4) + 1e-6
    weighed = float(np.sum(model.last_pass_losses * model.last_exit_mass))
    assert loss == pytest.approx(
        weighed - 0.05 * reg.gauge("lm.loop.exit_entropy").last, rel=0.05)
    assert model._hybrid.delta.__name__ == DELTA_PROGRAM
    paths = program_scopes()["jit_" + DELTA_PROGRAM].values()
    scopes = {name for path in paths for name in scope_names(path)}
    assert {"lm_loop", "lm_loop_norm", "lm_exit_gate", "lm_head_loss",
            "lm_attention", "lm_dense_ffn", "lm_embed", "lm_scale"} <= scopes
    # the blocks lie INSIDE the loop's scope, the head and the gate outside
    assert all("lm_loop" in scope_names(path) for path in paths
               if "lm_attention" in scope_names(path))
    assert not any("lm_loop" in scope_names(path) for path in paths
                   if "lm_head_loss" in scope_names(path))


def test_a_one_pass_model_counts_no_loop():
    cfg = small(total_ut_steps=1)
    model = HybridLM(cfg, mode="local")
    reg = get_registry()
    before = reg.counter("lm.loop.passes").value
    pairs = reg.counter("lm.attn.pairs").value
    model.step(batch(cfg, seed=9))
    assert reg.counter("lm.loop.passes").value == before
    assert reg.counter("lm.attn.pairs").value - pairs == 2 * 2 * 231
    assert "exit_gate_w" not in model.params


# -- the configuration file ---------------------------------------------------
#: The catalog's row for Ouro-2.6B (``model-configs`` guide): every number of
#: its ``config``; the file holds each under the same key.
CATALOG = {"head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
           "intermediate_size": 5632, "max_position_embeddings": 65536,
           "max_window_layers": 48, "model_type": "ouro",
           "num_attention_heads": 16, "num_hidden_layers": 48,
           "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
           "rope_scaling": None, "rope_theta": 1000000,
           "sliding_window": None, "tie_word_embeddings": False,
           "total_ut_steps": 4, "early_exit_threshold": 1,
           "use_sliding_window": False, "vocab_size": 49152}


@pytest.fixture(scope="module")
def config_file():
    with open(CONFIG_FILE) as f:
        return json.load(f)


@pytest.mark.parametrize("key", sorted(CATALOG))
def test_file_holds_the_catalogs_row(config_file, key):
    if key == "num_hidden_layers":
        assert config_file[key] == 6
        assert config_file["published"][key] == CATALOG[key]
        assert config_file["reduced"] == [key]
    else:
        assert config_file[key] == CATALOG[key]


def test_file_keeps_the_published_layer_types_whole(config_file):
    assert config_file["layer_types"] == ["full_attention"] * 48
    for key in ("source_url", "deployment", "assumed", "guarantees",
                "reduced_why", "precision", "tiny"):
        assert config_file[key], key
    assert "ByteDance/Ouro-2.6B" in config_file["source_url"]


def test_file_parses_to_the_looped_stack(config_file):
    cfg = HybridLMConfig.from_file(CONFIG_FILE)
    assert cfg.pattern == "*D" * 6
    assert (cfg.total_ut_steps, cfg.post_norm, cfg.attn_rope,
            cfg.attn_qk_norm) == (4, True, True, False)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
            cfg.intermediate_size) == (16, 16, 128, 5632)
    assert (cfg.norm_eps, cfg.rope_theta, cfg.rope_scaling) == \
        (1e-6, 1000000, None)
    assert not cfg.tie_word_embeddings and cfg.vocab_size == 49152
    assert cfg.exit_entropy_weight == 0.05 and cfg.early_exit_threshold == 1
    assert dense_param_count(cfg) == 408_997_889
    shapes = param_shapes(cfg)
    assert set(shapes["layers"][0]) == {"norm", "post_norm", "wq", "wk",
                                        "wv", "wo"}
    assert set(shapes["layers"][1]) == {"norm", "post_norm", "ffn_gate",
                                        "ffn_up", "ffn_down"}
    assert ref.pattern_of(config_file) == cfg.pattern
    assert ref.sizes_of(config_file)["total_ut_steps"] == 4


def test_the_published_depth_counts_the_rows_2_6_billion(config_file):
    whole = HybridLMConfig.from_dict(
        dict(config_file, num_hidden_layers=48))
    assert len(whole.pattern) == 96
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 51_388_416
    table = whole.vocab_size * whole.hidden_size
    assert dense_param_count(whole) == 48 * layer + table + 2048 + 2049
    assert dense_param_count(whole) + table == 2_667_974_657


def test_tiny_preset_parses(config_file):
    cfg = HybridLMConfig.from_dict(dict(config_file, **config_file["tiny"]))
    assert cfg.pattern == "*D*D" and cfg.total_ut_steps == 4
    assert cfg.q_dim == 32 == cfg.hidden_size


# -- the family's switches hang on model_type ---------------------------------
LFM2_FILE = os.path.join(ROOT, "benchmark", "configs", "lfm2-8b-a1b-ep4.json")


def test_lfm2_file_parses_as_before():
    """The ``lfm2_moe`` switches, now read off ``model_type``: the fields
    the parent set for any file with ``layer_types``."""
    cfg = HybridLMConfig.from_file(LFM2_FILE)
    assert cfg.pattern == "CDCD*ECECECE"
    assert (cfg.attn_qk_norm, cfg.attn_rope, cfg.scoring_func) == \
        (True, True, "sigmoid")
    assert (cfg.post_norm, cfg.total_ut_steps, cfg.exit_entropy_weight) == \
        (False, 1, 0.0)
    assert (cfg.rope_theta, cfg.head_dim, cfg.tie_word_embeddings) == \
        (1000000, 64, True)
    assert dense_param_count(cfg) == 535_093_376
    parent_fields = {f.name for f in dataclasses.fields(cfg)} - {
        "post_norm", "total_ut_steps", "exit_entropy_weight",
        "early_exit_threshold"}
    assert {"attn_qk_norm", "attn_rope", "scoring_func"} <= parent_fields


@pytest.mark.parametrize("model_type", [None, "llama", "ouro2"])
def test_layer_types_under_an_unknown_model_type_raises(config_file,
                                                        model_type):
    c = dict(config_file, model_type=model_type)
    if model_type is None:
        del c["model_type"]
    with pytest.raises(ValueError, match="model_type"):
        HybridLMConfig.from_dict(c)


@pytest.mark.parametrize("name", [
    "nemotron3-nano-30b-a3b-ep16", "deepseek-v2-lite-ep4",
    "evabyte-6.5b-pp8", "minicpm-sala-9b-pp8", "lfm2-8b-a1b-ep4"])
def test_the_other_files_run_one_pass_with_no_post_norm(name):
    cfg = HybridLMConfig.from_file(os.path.join(
        ROOT, "benchmark", "configs", name + ".json"))
    assert cfg.total_ut_steps == 1 and not cfg.post_norm
    shapes = param_shapes(cfg)
    assert "exit_gate_w" not in shapes
    assert not any("post_norm" in layer for layer in shapes["layers"])
