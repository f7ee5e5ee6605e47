"""The layer-typed LM's two-block layers (latent attention with a YaRN rotary
key, a dense gated feed-forward, the softmax-routed gated expert block with
its balance loss) against the benchmark's plain reference
(benchmark/reference/deepseek-v2-lite-ep4.py) at a small size on the CPU:
every block's forward and gradients, YaRN's numbers, the whole model's loss,
gradients and two AdaGrad steps with a bfloat16 control that fails, the share
test of the expert block, routing that drops nothing, the PS plane against its
local twin, spans, counters and names, and the configuration file against the
catalog's row."""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from multiverso_tpu.models.hybrid_lm import (DELTA_PROGRAM, HybridLM,
                                             HybridLMConfig, init_params,
                                             layer_forward, make_loss,
                                             pack_batch, param_shapes, rope)
from multiverso_tpu.parallel.expert import held_topk_moe
from multiverso_tpu.telemetry.metrics import get_registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "deepseek-v2-lite-ep4"
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "type": "yarn"}


def _reference():
    path = os.path.join(ROOT, "benchmark", "reference", CONFIG + ".py")
    spec = importlib.util.spec_from_file_location("dsv2_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()

# hidden 64, 4 heads of [16 | 8] against values of 16 from a latent of 32, a
# dense width of 96, 8 gated experts of 32 top-2 with 2 held and two shared,
# vocabulary 64; the rotary part's YaRN ramp is pairs 1..3 of 4
SMALL = dict(hidden_size=64, vocab_size=64, pattern="LDLELE", norm_eps=1e-6,
             num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16, rope_scaling=YARN,
             intermediate_size=96, router_experts=8, held=(0, 1),
             num_experts_per_tok=2, moe_intermediate_size=32,
             moe_shared_expert_intermediate_size=64,
             routed_scaling_factor=1.0, norm_topk_prob=False,
             scoring_func="softmax", hidden_act="silu", aux_loss_alpha=0.001,
             attn_block=8, moe_block=4, loss_block=16, row_bucket=16)
TOL = dict(loss=2e-5, grad=2e-4, step=2e-4)


def small(**kw) -> HybridLMConfig:
    return HybridLMConfig(**dict(SMALL, **kw))


def sizes(cfg: HybridLMConfig) -> dict:
    return {"pattern": cfg.pattern, "norm_eps": cfg.norm_eps,
            "num_attention_heads": cfg.num_attention_heads,
            "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim, "rope_theta": cfg.rope_theta,
            "rope_scaling": cfg.rope_scaling,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "norm_topk_prob": cfg.norm_topk_prob,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "aux_loss_alpha": cfg.aux_loss_alpha}


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
    """A block's seeded leaves with its norms moved off one."""
    rng = np.random.default_rng(seed)
    p = init_params(cfg)["layers"][0]
    return {k: v + 0.1 * rng.standard_normal(v.shape).astype(np.float32)
            if k in ("norm", "kv_norm") else v for k, v in p.items()}, rng


# -- YaRN ---------------------------------------------------------------------
def test_yarn_frequencies_and_scale_are_the_published_numbers():
    f = rope.inv_freq(64, 10000.0, YARN)
    plain = 10000.0 ** (-2.0 * np.arange(32) / 64)
    # pairs up to 10 turn as plain RoPE, pairs from 23 on forty times slower,
    # a linear ramp over the 13 between
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-12)
    np.testing.assert_allclose(f[23:], plain[23:] / 40, rtol=1e-12)
    ramp = (np.arange(11, 23) - 10) / 13
    np.testing.assert_allclose(
        f[11:23], plain[11:23] / 40 * ramp + plain[11:23] * (1 - ramp),
        rtol=1e-12)
    assert rope.yarn_mscale(40, 0.707) == pytest.approx(1.26080, abs=5e-6)
    assert rope.softmax_scale(192, YARN) == pytest.approx(0.114721, abs=5e-7)
    assert rope.softmax_scale(192, None) == 192 ** -0.5
    np.testing.assert_array_equal(rope.inv_freq(64, 10000.0, None), plain)
    np.testing.assert_array_equal(ref.yarn_inv_freq(64, 10000.0, YARN), f)
    full = dict(sizes(small()), qk_nope_head_dim=128, qk_rope_head_dim=64)
    assert ref.softmax_scale(full) == rope.softmax_scale(192, YARN)


def test_rope_turns_pairs_as_the_published_permuted_form_scores():
    """The published code permutes ``(2i, 2i+1)`` into halves and then
    ``rotate_half``s queries and keys alike; turning the pairs in place gives
    every score the same."""
    rng = np.random.default_rng(0)
    s, h, d = 12, 3, 8
    q = rng.standard_normal((s, h, d)).astype(np.float32)
    k = rng.standard_normal((s, 1, d)).astype(np.float32)
    cos, sin = rope.rope_tables(s, d, 10000.0, YARN)

    def published(x):
        x = x.reshape(s, -1, d // 2, 2).transpose(0, 1, 3, 2).reshape(
            s, -1, d)
        c = np.concatenate([cos, cos], -1)[:, None]
        sn = np.concatenate([sin, sin], -1)[:, None]
        half = np.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
        return x * c + half * sn

    mine = np.einsum("thd,sd->hts", rope.apply_rope(jnp.asarray(q), cos, sin),
                     rope.apply_rope(jnp.asarray(k), cos, sin)[:, 0])
    want = np.einsum("thd,sd->hts", published(q), published(k)[:, 0])
    np.testing.assert_allclose(mine, want, atol=1e-5)
    # and a score depends on the positions' difference alone
    same = rope.apply_rope(jnp.asarray(np.repeat(q[:1], s, 0)), cos, sin)
    keys = rope.apply_rope(jnp.asarray(np.repeat(k[:1], s, 0)), cos, sin)
    diag = np.einsum("thd,td->th", same[2:], keys[:-2, 0])
    np.testing.assert_allclose(diag, np.repeat(diag[:1], s - 2, 0), atol=1e-5)


# -- each block, forward and gradients ----------------------------------------
@pytest.mark.parametrize("length", [16, 21])
@pytest.mark.parametrize("kind", ["L", "D", "E"])
def test_block_matches_reference(kind, length):
    cfg = small(pattern=kind)
    p, rng = block_params(cfg)
    u = jnp.asarray(rng.standard_normal((2, length, cfg.hidden_size)),
                    jnp.float32)
    w = jnp.asarray(rng.standard_normal(u.shape), jnp.float32)
    s = sizes(cfg)

    def mine(p, u):
        out, _, *aux = layer_forward(kind, p, None, u, cfg)
        return jnp.sum(out * w) + sum(aux)

    def theirs(p, u):
        out, _, aux = ref.layer(kind, p, u, s, cfg.held)
        return jnp.sum(out * w) + aux

    out, counts, *aux = jax.jit(
        lambda p, u: layer_forward(kind, p, None, u, cfg))(p, u)
    with jax.default_matmul_precision("highest"):
        want, want_counts, want_aux = jax.jit(
            lambda p, u: ref.layer(kind, p, u, s, cfg.held))(p, u)
        want_grads = jax.jit(jax.grad(theirs, argnums=(0, 1)))(p, u)
    assert rel(out, want) < TOL["loss"]
    assert worst(jax.jit(jax.grad(mine, argnums=(0, 1)))(p, u),
                 want_grads) < TOL["grad"]
    if kind == "E":
        np.testing.assert_array_equal(counts, want_counts)
        assert float(aux[0]) == pytest.approx(float(want_aux), rel=1e-5)
        # sum_e f_e P_e is 1 under uniform routing: the term is near alpha
        assert 0.5 * cfg.aux_loss_alpha < float(aux[0]) \
            < 4 * cfg.aux_loss_alpha
    else:
        assert counts is None and not aux


def test_balance_loss_reaches_the_router_through_the_probabilities_only():
    """Against the sum written out: f from the counts (a constant), P the mean
    probability; the gradient on the router is alpha * d(sum f P)."""
    cfg = small(pattern="E", aux_loss_alpha=0.5)
    p, rng = block_params(cfg)
    u = jnp.asarray(rng.standard_normal((2, 12, cfg.hidden_size)),
                    jnp.float32)

    def term(router):
        return layer_forward("E", dict(p, router=router), None, u, cfg)[2]

    def by_hand(router):
        from multiverso_tpu.models.hybrid_lm import rmsnorm
        n = rmsnorm(u, p["norm"], cfg.norm_eps)
        probs = jax.nn.softmax(jnp.einsum(
            "bsd,de->bse", n, router, precision="highest"), axis=-1)
        chosen = np.asarray(jax.lax.top_k(probs, 2)[1])
        f = np.stack([np.bincount(c.reshape(-1), minlength=8)
                      for c in chosen]) * 8 / (2 * 12)
        return 0.5 * jnp.mean(jnp.sum(f * probs.mean(axis=1), axis=-1))

    assert float(term(p["router"])) == pytest.approx(
        float(by_hand(p["router"])), rel=1e-5)
    assert rel(jax.grad(term)(p["router"]),
               jax.grad(by_hand)(p["router"])) < 1e-4


# -- the whole model ----------------------------------------------------------
def _reference_steps(cfg, params0, rows0, batches, compute="float32",
                     storage=None):
    """Two AdaGrad steps of the reference from the model's own start:
    (losses, counts, parameters, rows, first step's gradients, balance
    terms)."""
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
    losses, counts, balance, first = [], [], [], None
    for tokens in batches:
        ids, n, where, targets, mask = pack_batch(tokens, 1)
        loss, c, aux, gp, grows = ref.value_and_grads(
            params, jnp.asarray(rows[ids]), where, targets, mask, s,
            cfg.held, compute=compute)
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
        counts.append(np.asarray(c))
        balance.append(float(aux))
    return losses, counts, params, rows, first, balance


def test_whole_model_two_steps_match_reference():
    cfg = small()
    model = HybridLM(cfg, mode="local")
    start = jax.tree_util.tree_map(np.array, model.params)
    rows0 = model.local_rows()
    batches = [batch(cfg, seed=1), batch(cfg, seed=2)]

    # gradients of the first step, before anything moves
    ids, _, where, targets, mask = pack_batch(batches[0], cfg.row_bucket)
    (_, (counts, aux)), (gp, grows) = jax.jit(jax.value_and_grad(
        make_loss(cfg), argnums=(0, 1), has_aux=True))(
            model.params, jnp.asarray(rows0[ids]), model.buffers, where,
            targets, mask)
    losses = [model.step(b) for b in batches]
    want = _reference_steps(cfg, start, rows0, batches)
    want_gp, want_grows, want_ids = want[4]
    assert worst(gp, want_gp) < TOL["grad"]
    assert rel(np.asarray(grows)[:len(want_ids)], want_grows) < TOL["grad"]
    np.testing.assert_array_equal(counts, want[1][0])
    np.testing.assert_array_equal(model.last_counts, want[1][1])
    assert float(aux) == pytest.approx(want[5][0], rel=1e-5)
    # the loss a step returns carries the balance term
    assert max(abs(g - w) / abs(w)
               for g, w in zip(losses, want[0])) < TOL["loss"]
    assert abs(losses[0] - (want[0][0] - want[5][0])) / want[0][0] \
        > TOL["loss"]
    assert max(worst(model.params, want[2]),
               rel(model.local_rows(), want[3])) < TOL["step"]

    # the control: the reference computed and stored in bfloat16, put in the
    # program's place, fails at least one of the same tolerances
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
    loss, counts, aux, gp, grows = ref.value_and_grads(
        params, rows, where, targets, mask, s, cfg.held)
    got = {}
    loss2, counts2, aux2, grows2 = ref.grads_by_layer(
        lambda i: params["layers"][i],
        lambda: (params["final_norm"], params["head"]),
        rows, where, targets, mask, s, cfg.held, got.__setitem__)
    assert abs(float(loss2) - float(loss)) < 1e-6
    assert abs(float(aux2) - float(aux)) < 1e-8
    np.testing.assert_array_equal(counts2, counts)
    assert rel(grows2, grows) < 1e-5
    assert rel(got["top"][1], gp["head"]) < 1e-5
    for i in range(len(cfg.pattern)):
        assert worst(got[i], gp["layers"][i]) < 1e-5


# -- the share test -----------------------------------------------------------
@pytest.mark.parametrize("side", ["program", "reference"])
def test_expert_shares_add_up_to_the_uncut_block(side):
    """The routed parts that the four shares give, the shared experts counted
    once, add up to what the block holding every expert gives; every share
    computes the same balance loss."""
    whole = small(pattern="E", held=tuple(range(8)))
    p = init_params(whole)["layers"][0]
    rng = np.random.default_rng(2)
    n = jnp.asarray(rng.standard_normal((2, 20, whole.hidden_size)),
                    jnp.float32)
    s = sizes(whole)

    def share(held, shared):
        at = np.asarray(held)
        part = dict(p, w_gate=p["w_gate"][at], w_up=p["w_up"][at],
                    w_down=p["w_down"][at])
        if side == "reference":
            with jax.default_matmul_precision("highest"):
                y, counts, aux = ref.expert_block(part, n, s, held, shared)
                return y.reshape(40, -1), counts, aux
        return held_topk_moe(
            n.reshape(40, -1), part["router"], None, part["w_up"],
            part["w_down"], part["s_up"], part["s_down"], held,
            whole.num_experts_per_tok, whole.routed_scaling_factor,
            whole.norm_topk_prob, 4, shared, "softmax", part["w_gate"],
            part["s_gate"], (whole.aux_loss_alpha, 2))

    uncut, uncut_counts, uncut_aux = share(tuple(range(8)), True)
    parts = [share((2 * i, 2 * i + 1), i == 0) for i in range(4)]
    assert rel(sum(y for y, _, _ in parts), uncut) < 1e-5
    with jax.default_matmul_precision("highest"):
        whole_reference = ref.expert_block(p, n, s, tuple(range(8)))[0]
    assert rel(sum(y for y, _, _ in parts),
               whole_reference.reshape(40, -1)) < TOL["loss"]
    np.testing.assert_array_equal(
        np.concatenate([c for _, c, _ in parts]), uncut_counts)
    assert int(np.sum(uncut_counts)) == 40 * whole.num_experts_per_tok
    for _, _, aux in parts:
        assert float(aux) == pytest.approx(float(uncut_aux), rel=1e-6)


def test_routing_drops_nothing_when_one_expert_takes_half_the_tokens():
    cfg = small(pattern="E")
    p = dict(init_params(cfg)["layers"][0])
    rng = np.random.default_rng(4)
    tokens = 64
    n = rng.standard_normal((tokens, cfg.hidden_size)).astype(np.float32)
    pull = rng.standard_normal(cfg.hidden_size).astype(np.float32)
    n[::2] = 3.0 * pull + 0.1 * n[::2]        # every other token looks alike
    router = np.array(p["router"])
    router[:, 0] = pull                       # and expert 0 wants them
    p["router"] = jnp.asarray(router)
    y, counts = held_topk_moe(
        jnp.asarray(n), p["router"], None, p["w_up"], p["w_down"], p["s_up"],
        p["s_down"], cfg.held, cfg.num_experts_per_tok,
        cfg.routed_scaling_factor, cfg.norm_topk_prob, cfg.moe_block, True,
        "softmax", p["w_gate"], p["s_gate"])
    with jax.default_matmul_precision("highest"):
        want, want_counts, _ = ref.expert_block(
            p, jnp.asarray(n)[None], sizes(cfg), cfg.held)
    assert int(counts[0]) >= tokens // 2
    np.testing.assert_array_equal(counts, want_counts)
    assert rel(y, want[0]) < TOL["loss"]


def test_normalised_softmax_weights_sum_to_the_scaling():
    from multiverso_tpu.parallel.expert import softmax_topk_route
    rng = np.random.default_rng(8)
    n = jnp.asarray(rng.standard_normal((10, 16)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((16, 6)), jnp.float32)
    chosen, w, probs = softmax_topk_route(n, router, 2, 1.5, True)
    np.testing.assert_allclose(np.sum(w, axis=-1), 1.5, rtol=1e-6)
    chosen, w, probs = softmax_topk_route(n, router, 2, 1.0, False)
    np.testing.assert_array_equal(
        w, np.take_along_axis(np.asarray(probs), np.asarray(chosen), -1))
    np.testing.assert_allclose(np.sum(probs, axis=-1), 1.0, rtol=1e-6)


# -- the planes ------------------------------------------------------------
@pytest.fixture(params=["mesh_of_8", "one_device"])
def table_devices(request):
    import multiverso_tpu as mv
    one = request.param == "one_device"
    mv.init([], devices=jax.devices()[:1] if one else None)
    yield one
    mv.shutdown()


def test_ps_plane_matches_local_twin_bitwise(table_devices):
    cfg = small(pattern="LDLE")
    local, ps = HybridLM(cfg, mode="local"), HybridLM(cfg, mode="ps")
    batches = [batch(cfg, seed=7), batch(cfg, seed=8), batch(cfg, seed=7)]
    device_calls = [get_registry().counter(f"table.group.device_{kind}")
                    for kind in ("pulls", "pushes")]
    before = [c.value for c in device_calls]
    assert [local.step(b) for b in batches] == [ps.step(b) for b in batches]
    assert [c.value - b for c, b in zip(device_calls, before)] == \
        [len(batches) * table_devices] * 2
    assert ps._hybrid.delta._cache_size() == local._hybrid.delta._cache_size()
    ids = np.unique(batches[0])
    np.testing.assert_array_equal(ps.pull_rows(ids), local.pull_rows(ids))
    for (name, a), (_, b) in zip(local.dense_leaves(), ps.dense_leaves()):
        np.testing.assert_array_equal(a, b, err_msg=name)
    np.testing.assert_array_equal(
        local.local_rows(),
        ps.table.get_rows(np.arange(cfg.vocab_size, dtype=np.int32)))
    for a, b in zip(jax.tree_util.tree_leaves(local.state),
                    jax.tree_util.tree_leaves(ps.state)):
        np.testing.assert_array_equal(a, b)


def test_step_spans_counters_scopes_and_program_names():
    """``lm_step_ms`` / ``lm_table_ms`` read the spans, ``dsv2_mfu_share`` the
    counters and the ``jit_lm_delta_step`` program, ``lm_apply_device_ms``
    ``jit_lm_apply``: the names are part of the yardstick."""
    cfg = small()
    model = HybridLM(cfg, mode="local")
    tokens = batch(cfg, seed=9)
    reg = get_registry()
    names = ("lm.step", "lm.pull", "lm.compute", "lm.compute.dispatch",
             "lm.compute.sync", "lm.push")
    before = {n: reg.histogram("span." + n).count for n in names}
    counted = ("lm.tokens", "lm.rows_pulled", "lm.attn.pairs",
               "lm.moe.assignments_held.l3", "lm.moe.max_expert_load.l3",
               "lm.moe.assignments_held.l5")
    c0 = {n: reg.counter(n).value for n in counted}
    loss = model.step(tokens)
    for n in names:
        assert reg.histogram("span." + n).count == before[n] + 1, n
    assert reg.counter("lm.tokens").value - c0["lm.tokens"] == tokens.size
    # three attention blocks, two sequences of 21: 21 * 22 / 2 pairs each
    assert reg.counter("lm.attn.pairs").value - c0["lm.attn.pairs"] \
        == 3 * 2 * 231
    for row, layer in zip(model.last_counts, (3, 5)):
        name = f"lm.moe.assignments_held.l{layer}"
        assert reg.counter(name).value - c0[name] == row.sum()
    assert reg.counter("lm.moe.max_expert_load.l3").value \
        - c0["lm.moe.max_expert_load.l3"] == model.last_counts[0].max()
    balance = reg.gauge("lm.moe.balance_loss").last
    assert 0 < balance < 0.1 * loss
    assert model._hybrid.delta.__name__ == DELTA_PROGRAM
    assert model._hybrid.apply.__name__ == "lm_apply"
    ids, _, where, targets, mask = pack_batch(tokens, cfg.row_bucket)
    text = model._hybrid.delta.lower(
        model.params, jnp.zeros((len(ids), cfg.hidden_size)), model.buffers,
        where, targets, mask).as_text(debug_info=True)
    assert "module @jit_lm_delta_step" in text
    for scope in ("lm_mla", "lm_dense_ffn", "lm_experts"):
        assert scope in text, scope


def test_a_model_without_the_term_keeps_its_results_shape():
    """No balance weight: the loss function's second result is the counts
    alone, as for every sigmoid-routed model."""
    cfg = small(aux_loss_alpha=0.0)
    assert not cfg.balanced
    model = HybridLM(cfg, mode="local")
    tokens = batch(cfg, seed=4)
    ids, _, where, targets, mask = pack_batch(tokens, cfg.row_bucket)
    loss, counts = jax.jit(make_loss(cfg))(
        model.params, jnp.asarray(model.local_rows()[ids]), model.buffers,
        where, targets, mask)
    assert counts.shape == (2, 2) and np.isfinite(float(loss))
    assert model.step(tokens) == pytest.approx(float(loss), rel=1e-6)


# -- the configuration file ---------------------------------------------------
def test_benchmark_configuration_keeps_every_published_width():
    path = os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json")
    cfg = HybridLMConfig.from_file(path)
    with open(path) as f:
        raw = json.load(f)
    assert cfg.pattern == "LDLELELELE" and cfg.hidden_size == 2048
    assert cfg.norm_eps == 1e-6 and cfg.rope_scaling == YARN
    assert (cfg.num_attention_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim) == (16, 512, 128, 64, 128)
    assert cfg.router_experts == 64 and cfg.held == tuple(range(16))
    assert cfg.num_experts_per_tok == 6 and cfg.vocab_size == 25600
    assert (cfg.intermediate_size, cfg.moe_intermediate_size,
            cfg.moe_shared_expert_intermediate_size) == (10944, 1408, 2816)
    assert (cfg.scoring_func, cfg.hidden_act, cfg.norm_topk_prob,
            cfg.routed_scaling_factor) == ("softmax", "silu", False, 1)
    assert cfg.aux_loss_alpha == 0.001 and cfg.balanced
    assert "aux_loss_alpha" in raw["assumed"]
    assert raw["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert raw["published"] == {"num_hidden_layers": 27,
                                "n_routed_experts": 64, "vocab_size": 102400}
    assert "four chips" in raw["deployment"] and raw["guarantees"]
    shapes = param_shapes(cfg)
    count = lambda tree: sum(int(np.prod(s)) for s in tree.values())
    assert [count(layer) for layer in shapes["layers"][:4]] == \
        [13765120, 67241984, 13765120, 155846656]
    assert ref.pattern_of(raw) == cfg.pattern
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["source_url"] == raw["source_url"])
        for key, value in row["config"].items():
            if key not in raw["reduced"]:
                assert raw[key] == value, key


def test_pattern_comes_from_the_published_keys():
    base = {"hidden_size": 64, "vocab_size": 64, "num_attention_heads": 4,
            "num_key_value_heads": 4, "head_dim": 16,
            "moe_intermediate_size": 32, "n_routed_experts": 2,
            "num_experts_per_tok": 2, "routed_scaling_factor": 1.0,
            "norm_topk_prob": False, "num_hidden_layers": 4,
            "n_shared_experts": 2, "hidden_act": "silu",
            "scoring_func": "softmax", "intermediate_size": 96}
    latent = dict(base, kv_lora_rank=32, qk_nope_head_dim=16,
                  qk_rope_head_dim=8, v_head_dim=16, rope_theta=10000,
                  first_k_dense_replace=1, moe_layer_freq=1, q_lora_rank=None)
    cfg = HybridLMConfig.from_dict(latent)
    assert cfg.pattern == "LDLELELE" and cfg.gated_experts
    assert cfg.moe_shared_expert_intermediate_size == 64
    assert not cfg.balanced             # the file weighs no balance loss
    assert HybridLMConfig.from_dict(
        dict(latent, first_k_dense_replace=2, moe_layer_freq=2)
    ).pattern == "LDLDLELD"
    plain = dict(base, first_k_dense_replace=0)
    assert HybridLMConfig.from_dict(plain).pattern == "*E*E*E*E"
    # a file has the keys of every kind of block its pattern runs
    for key in ("v_head_dim", "intermediate_size", "num_experts_per_tok"):
        with pytest.raises(KeyError):
            HybridLMConfig.from_dict(
                {k: v for k, v in latent.items() if k != key})
    with pytest.raises(ValueError):
        HybridLMConfig.from_dict(dict(latent, q_lora_rank=1536))
    with pytest.raises(ValueError):
        HybridLMConfig.from_dict(dict(latent, aux_loss_alpha=0.001,
                                      seq_aux=False))
