"""The layer-typed LM's Kimi Delta Attention block (``K``), group-limited
sigmoid routing and latent attention with plain rotary, against the benchmark's
plain reference (benchmark/reference/ling-3.0-flash-ep32.py) at a small size on
the CPU: the chunked delta rule against the token-by-token recurrence, values
and gradients, at chunk lengths that do and do not divide the sequence and with
the gate pinned at its bound for a whole chunk; every block's forward and
gradients; group-limited routing against a brute-force choice, ties included;
the share test of the expert block (four shares of 4 of 16 experts add up to
the uncut layer, the shared expert counted once); ``n_group`` 1 tracing to the
program text the three expert configurations had; the whole model's two AdaGrad
steps with a bfloat16 control that fails; spans, counters, scopes; and the
configuration file against the catalog's row and the issue's counts."""
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
from multiverso_tpu.models.hybrid_lm import kda
from multiverso_tpu.parallel import expert
from multiverso_tpu.parallel.expert import held_topk_moe, kept_groups
from multiverso_tpu.telemetry.metrics import get_registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "ling-3.0-flash-ep32"
CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json")


def _reference():
    path = os.path.join(ROOT, "benchmark", "reference", CONFIG + ".py")
    spec = importlib.util.spec_from_file_location("ling3_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()

# hidden 64; KDA of 4 heads of 16 with 4 taps and chunks of 32 (two sub-chunks
# of 16); latent attention of 4 heads (latent 32, nope 16 + rope 8, value 16);
# a dense width of 96; 16 gated experts of 32 in 4 groups, 2 groups and 2
# experts a token, 2 held, one shared expert of 32; vocabulary 64 untied
SMALL = dict(hidden_size=64, vocab_size=64, pattern="KDKELE", norm_eps=1e-6,
             kda_num_heads=4, kda_head_dim=16, short_conv_kernel_size=4,
             kda_lower_bound=-5.0, kda_chunk=32, num_attention_heads=4,
             kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, rope_theta=6000000.0, intermediate_size=96,
             router_experts=16, held=(0, 1), num_experts_per_tok=2,
             n_group=4, topk_group=2, moe_intermediate_size=32,
             moe_shared_expert_intermediate_size=32,
             routed_scaling_factor=2.5, norm_topk_prob=True,
             scoring_func="sigmoid", hidden_act="silu",
             expert_bias_update_rate=0.01, attn_block=8, moe_block=4,
             loss_block=16, row_bucket=16)
TOL = dict(loss=2e-5, grad=3e-4, step=3e-4)


def small(**kw) -> HybridLMConfig:
    return HybridLMConfig(**dict(SMALL, **kw))


def sizes(cfg: HybridLMConfig) -> dict:
    return {"pattern": cfg.pattern, "norm_eps": cfg.norm_eps,
            "num_attention_heads": cfg.num_attention_heads,
            "head_dim": cfg.kda_head_dim,
            "kda_lower_bound": cfg.kda_lower_bound,
            "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim, "rope_theta": cfg.rope_theta,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "n_group": cfg.n_group, "topk_group": cfg.topk_group,
            "norm_topk_prob": cfg.norm_topk_prob,
            "routed_scaling_factor": cfg.routed_scaling_factor}


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def worst(got_tree, want_tree) -> float:
    return max(rel(g, w) for g, w in zip(jax.tree_util.tree_leaves(got_tree),
                                         jax.tree_util.tree_leaves(want_tree)))


def batch(cfg, seqs=2, length=40, seed=0, ids=None):
    rng = np.random.default_rng(seed)
    return rng.integers(0, ids or cfg.vocab_size,
                        (seqs, length)).astype(np.int32)


def block_params(cfg, seed=3):
    """A block's seeded leaves with its norms moved off one."""
    rng = np.random.default_rng(seed)
    p = init_params(cfg)["layers"][0]
    return {k: v + 0.1 * rng.standard_normal(v.shape).astype(np.float32)
            if k in ("norm", "o_norm", "kv_norm") else v
            for k, v in p.items()}, rng


# -- the chunked delta rule is the recurrence ----------------------------------
def scan_inputs(rng, bsz, length, heads, dk, dv, pinned=0, spread=3.0):
    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    q = kda.l2_normalised(normal(bsz, length, heads, dk)) * dk ** -0.5
    k = kda.l2_normalised(normal(bsz, length, heads, dk))
    g = -5.0 * jax.nn.sigmoid(spread * normal(bsz, length, heads, dk))
    if pinned:      # the gate at its bound for a whole chunk and more
        g = g.at[:, :pinned].set(-5.0)
    return (q, k, normal(bsz, length, heads, dv), g,
            jax.nn.sigmoid(normal(bsz, length, heads)))


def recurrence(q, k, v, g, beta):
    return jax.vmap(ref.delta_rule)(q, k, v, g, beta)


@pytest.mark.parametrize("length,chunk,pinned", [
    (64, 16, 0), (50, 16, 0), (37, 8, 0), (70, 32, 0), (33, 64, 0),
    (128, 64, 64), (100, 64, 80), (96, 32, 96), (300, 128, 0),
    (256, 128, 128)])
def test_chunked_delta_rule_is_the_recurrence(length, chunk, pinned):
    """Values and every gradient, at chunk lengths that do and do not divide
    the sequence (one sub-chunk a chunk, and several); with the gate pinned
    at its bound for a whole chunk (``e^-320`` over 64 positions: every
    ``exp(G_i - G_j)`` has to be formed inside float32) the result is finite,
    and equal."""
    rng = np.random.default_rng(length + chunk)
    args = scan_inputs(rng, 2, length, 3, 8, 6, pinned)
    w = jnp.asarray(rng.standard_normal((2, length, 3, 6)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = kda.kda_chunked(*args, chunk)
        want = recurrence(*args)
        grads = jax.grad(lambda *x: jnp.sum(kda.kda_chunked(*x, chunk) * w),
                         argnums=(0, 1, 2, 3, 4))(*args)
        want_grads = jax.grad(lambda *x: jnp.sum(recurrence(*x) * w),
                              argnums=(0, 1, 2, 3, 4))(*args)
    assert bool(jnp.isfinite(got).all())
    assert all(bool(jnp.isfinite(x).all()) for x in grads)
    assert rel(got, want) < 3e-4
    for mine, theirs in zip(grads, want_grads):
        assert rel(mine, theirs) < 1e-3


def test_a_chunk_is_a_power_of_two():
    rng = np.random.default_rng(0)
    with pytest.raises(ValueError, match="power of two"):
        kda.kda_chunked(*scan_inputs(rng, 1, 40, 1, 4, 4), 24)
    with pytest.raises(Exception, match="power of two"):
        small(kda_chunk=24).validate()


@pytest.mark.parametrize("size", [1, 2, 16, 64, 128])
def test_unit_lower_inverse_is_the_inverse(size):
    """Block forward substitution by doubling, against numpy's inverse: the
    systems of a chunk (a key's products with the keys before it, under
    their decay and ``b``) are no better conditioned than these."""
    rng = np.random.default_rng(size)
    keys = rng.standard_normal((3, size, 8))
    keys /= np.linalg.norm(keys, axis=-1, keepdims=True)
    n = np.tril(np.einsum("bid,bjd->bij", keys, keys), -1) \
        * rng.uniform(0.0, 1.0, (3, size, 1))
    got = kda._unit_lower_inverse(jnp.asarray(n, jnp.float32))
    want = np.linalg.inv(np.eye(size) + n)
    assert rel(got, want) < 1e-5
    assert float(jnp.abs(jnp.triu(got, 1)).max()) == 0.0


def test_without_the_correction_the_state_is_another():
    """``S_t = Diag(a_t) S_{t-1} + b_t k_t v_t^T`` (the delta correction left
    out: plain gated linear attention) is not what the block computes, and the
    comparison sees it."""
    rng = np.random.default_rng(1)
    q, k, v, g, beta = scan_inputs(rng, 1, 48, 2, 8, 8)
    g = 0.01 * g                    # mild, so that exp(-G) stays small
    with jax.default_matmul_precision("highest"):
        want = recurrence(q, k, v, g, beta)
        got = kda.kda_chunked(q, k, v, g, beta, 16)
        cum = jnp.cumsum(g, axis=1)
        plain = jnp.einsum(
            "bthd,bshd,ts,bsh,bshv->bthv", q * jnp.exp(cum),
            k * jnp.exp(-cum), jnp.tril(jnp.ones((48, 48))), beta, v)
    assert rel(got, want) < 3e-4
    assert rel(plain, want) > 0.05


# -- each block, forward and gradients ----------------------------------------
@pytest.mark.parametrize("length", [32, 40])
@pytest.mark.parametrize("kind", ["K", "L", "D", "E"])
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

    with jax.default_matmul_precision("highest"):
        out, counts, *every = jax.jit(
            lambda p, u: layer_forward(kind, p, bias, u, cfg))(p, u)
        want, want_counts = ref.layer(kind, p, bias, u, s, cfg.held)
        grads = jax.jit(jax.grad(mine, argnums=(0, 1)))(p, u)
        want_grads = jax.jit(jax.grad(theirs, argnums=(0, 1)))(p, u)
    assert rel(out, want) < TOL["loss"]
    assert worst(grads, want_grads) < TOL["grad"]
    if kind == "E":
        np.testing.assert_array_equal(counts,
                                      want_counts[np.asarray(cfg.held)])
        np.testing.assert_array_equal(every[0], want_counts)
        assert int(np.sum(want_counts)) == 2 * length * 2
    else:
        assert counts is None and want_counts is None


def test_kda_block_has_no_positions_and_is_causal():
    """A change at position 20 reaches no earlier output (past the rounding
    of its own sub-chunk, whose factors are taken from the middle of the
    sub-chunk's decay), and every later one (the state carries it on)."""
    cfg = small(pattern="K")
    p, rng = block_params(cfg)
    u = jnp.asarray(rng.standard_normal((1, 40, cfg.hidden_size)),
                    jnp.float32)
    out = layer_forward("K", p, None, u, cfg)[0]
    moved = layer_forward("K", p, None, u.at[0, 20].add(1.0), cfg)[0]
    diff = np.abs(np.asarray(out - moved)).max(axis=-1)[0]
    assert np.all(diff[:16] == 0) and np.all(diff[16:20] < 1e-6)
    assert np.all(diff[20:] > 1e-4)


@pytest.mark.parametrize("elements,groups", [(40 * 32, 2), (40 * 16, 4),
                                             (1, 4)])
def test_heads_a_group_at_a_time_are_the_block(monkeypatch, elements, groups):
    """A sequence whose heads' arrays would pass ``GROUP_ELEMENTS`` takes the
    heads a group at a time (their columns of the projections, their taps,
    their rows of ``W_o``): the same output and the same gradients."""
    cfg = small(pattern="K")
    p, rng = block_params(cfg)
    u = jnp.asarray(rng.standard_normal((2, 40, cfg.hidden_size)),
                    jnp.float32)
    w = jnp.asarray(rng.standard_normal(u.shape), jnp.float32)

    def run():
        return jax.value_and_grad(lambda p, u: jnp.sum(
            layer_forward("K", p, None, u, cfg, True)[0] * w),
            argnums=(0, 1))(p, u)

    with jax.default_matmul_precision("highest"):
        whole = run()
        assert kda._heads_a_group(40, 4, 16) == 4
        monkeypatch.setattr(kda, "GROUP_ELEMENTS", elements)
        assert kda._heads_a_group(40, 4, 16) == 4 // groups
        grouped = run()
    assert abs(float(grouped[0]) - float(whole[0])) < 1e-4 * abs(
        float(whole[0]))
    assert worst(grouped[1], whole[1]) < 1e-5


def test_heads_a_group_at_the_published_shapes():
    assert kda._heads_a_group(8192, 32, 128) == 8
    assert kda._heads_a_group(2048, 32, 128) == 32
    assert kda._heads_a_group(8192, 6, 128) == 6
    assert kda._heads_a_group(8192, 12, 128) == 6


# -- group-limited routing -------------------------------------------------------
def brute_force_choice(biased, n_group, topk_group, top_k):
    """Token by token in numpy: the groups ranked by the sum of their two
    largest entries (of equal ones the lower-numbered first), the experts of
    the kept groups ranked by their entry (likewise)."""
    chosen = []
    for row in np.asarray(biased, np.float64):
        groups = row.reshape(n_group, -1)
        score = np.sort(groups, axis=-1)[:, -2:].sum(axis=-1)
        kept = sorted(range(n_group), key=lambda g: (-score[g], g))[
            :topk_group]
        width = groups.shape[1]
        among = [e for e in range(len(row)) if e // width in kept]
        chosen.append(sorted(among, key=lambda e: (-row[e], e))[:top_k])
    return np.asarray(chosen)


@pytest.mark.parametrize("levels", [0, 4, 2])
@pytest.mark.parametrize("shape", [(16, 4, 2, 2), (64, 8, 4, 8),
                                   (512, 8, 4, 8)])
def test_group_limited_choice_is_the_brute_force_one(shape, levels):
    """On seeded scores, exact ones and ones rounded to a few levels so that
    groups and experts tie."""
    experts, n_group, topk_group, top_k = shape
    rng = np.random.default_rng(experts + levels)
    scores = rng.uniform(0.0, 1.0, (50, experts)).astype(np.float32)
    if levels:
        scores = np.round(scores * levels) / levels
    biased = jnp.asarray(scores)
    _, got = jax.lax.top_k(kept_groups(biased, n_group, topk_group), top_k)
    want = brute_force_choice(scores, n_group, topk_group, top_k)
    np.testing.assert_array_equal(np.asarray(got), want)
    # the reference's own choice is the same
    kept = ref.kept_groups(biased, n_group, topk_group)
    _, theirs = jax.lax.top_k(jnp.where(kept, biased, -jnp.inf), top_k)
    np.testing.assert_array_equal(np.asarray(theirs), want)
    # at most ``topk_group`` groups a token
    assert np.all([len(set(row // (experts // n_group))) <= topk_group
                   for row in want])


def _route_before_the_groups(n, router, bias, top_k, scaling, normalize=True,
                             n_group=1, topk_group=1):
    """``sigmoid_topk_route`` as it stood before it knew groups."""
    scores = jax.nn.sigmoid(jnp.dot(
        n.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(scores + bias, top_k)
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if normalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * scaling


@pytest.mark.parametrize("config", ["lfm2-8b-a1b-ep4",
                                    "nemotron3-nano-30b-a3b-ep16",
                                    "deepseek-v2-lite-ep4"])
def test_without_groups_an_expert_block_traces_as_it_did(config, monkeypatch):
    """The three expert configurations have ``n_group`` 1 (or none): their
    expert block's jaxpr is, letter for letter, what it was before
    ``sigmoid_topk_route`` knew groups."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           config + ".json")) as f:
        d = json.load(f)
    d.update(d["tiny"])
    cfg = HybridLMConfig.from_dict(d)
    assert cfg.n_group == 1 and "E" in cfg.pattern
    block = cfg.pattern.index("E")
    p = init_params(cfg)["layers"][block]
    bias = init_buffers(cfg)[block]
    u = jnp.zeros((2, 12, cfg.hidden_size), jnp.float32)

    def traced():
        return str(jax.make_jaxpr(jax.value_and_grad(
            lambda p, u: jnp.sum(layer_forward("E", p, bias, u, cfg)[0]),
            argnums=(0, 1)))(p, u))

    now = traced()
    monkeypatch.setattr(expert, "sigmoid_topk_route",
                        _route_before_the_groups)
    assert traced() == now


# -- the share test -----------------------------------------------------------
@pytest.mark.parametrize("side", ["program", "reference"])
def test_four_expert_shares_add_up_to_the_uncut_block(side):
    """16 experts in 4 groups, 2 groups and 2 experts a token: the parts that
    the four shares ``held_experts`` 0..3, 4..7, 8..11, 12..15 give, the
    shared expert computed by the first alone, add up to what the block
    holding all 16 gives, and to the uncut reference's whole layer."""
    whole = small(pattern="E", held=tuple(range(16)))
    p = init_params(whole)["layers"][0]
    bias = init_buffers(whole)[0]
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
                y, counts = ref.expert_block(part, bias, n, s, held, shared)
                return y.reshape(40, -1), counts[np.asarray(held)]
        return held_topk_moe(
            n.reshape(40, -1), part["router"], bias, part["w_up"],
            part["w_down"], part["s_up"], part["s_down"], held,
            whole.num_experts_per_tok, whole.routed_scaling_factor,
            whole.norm_topk_prob, 4, shared, "sigmoid", part["w_gate"],
            part["s_gate"], None, False, None, whole.n_group,
            whole.topk_group)

    uncut, uncut_counts = share(tuple(range(16)), True)
    parts = [share(tuple(range(4 * i, 4 * i + 4)), i == 0) for i in range(4)]
    assert rel(sum(y for y, _ in parts), uncut) < 1e-5
    with jax.default_matmul_precision("highest"):
        whole_reference = ref.expert_block(p, bias, n, s, tuple(range(16)))[0]
    assert rel(sum(y for y, _ in parts),
               whole_reference.reshape(40, -1)) < TOL["loss"]
    np.testing.assert_array_equal(
        np.concatenate([c for _, c in parts]), uncut_counts)
    # every assignment lands on exactly one share
    assert int(np.sum(uncut_counts)) == 40 * whole.num_experts_per_tok
    # a share alone is not the layer, and the shared expert counted four
    # times is not either
    assert rel(parts[0][0], uncut) > 0.3
    four = [share(tuple(range(4 * i, 4 * i + 4)), True)[0] for i in range(4)]
    assert rel(sum(four), uncut) > 0.1


# -- the whole model: two AdaGrad steps ---------------------------------------
def _reference_steps(cfg, params0, buffers, rows0, batches, compute="float32",
                     storage=None):
    s = sizes(cfg)
    buffers = list(buffers)
    experts = cfg.expert_layers()

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
        loss, c, gp, grows = ref.value_and_grads(
            params, jnp.asarray(rows), buffers, tokens, targets, mask, s,
            cfg.held, compute=compute)
        first = first or (gp, grows)
        stepped = jax.tree_util.tree_map(
            lambda w, a, g: ref.adagrad(w, a, g, cfg.adagrad_step),
            params, g2, gp)
        params = stored(jax.tree_util.tree_map(
            lambda w, pair: pair[0], params, stepped))
        g2 = jax.tree_util.tree_map(lambda w, pair: pair[1], params, stepped)
        new_rows, rows_g2 = ref.adagrad(rows, rows_g2, np.asarray(grows),
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
    ids, _, where, targets, mask = pack_batch(batches[0], 1)
    (_, (counts, every)), (gp, grows) = jax.jit(jax.value_and_grad(
        make_loss(cfg), argnums=(0, 1), has_aux=True))(
            model.params, jnp.asarray(rows0[ids]), model.buffers, where,
            targets, mask)
    bias0 = list(model.buffers)
    losses = [model.step(b) for b in batches]
    want = _reference_steps(cfg, start, bias0, rows0, batches)
    want_gp, want_gtable = want[4]
    assert worst(gp, want_gp) < TOL["grad"]
    assert rel(grows, want_gtable[ids]) < TOL["grad"]
    held = list(cfg.held)
    np.testing.assert_array_equal(counts, want[1][0][:, held])
    np.testing.assert_array_equal(every, want[1][0])
    np.testing.assert_array_equal(model.last_counts, want[1][1][:, held])
    # the selection bias moved twice, by the rate, as the reference's
    for i in cfg.expert_layers():
        np.testing.assert_array_equal(model.buffers[i], want[5][i])
        moved = np.abs(np.asarray(model.buffers[i]) - np.asarray(bias0[i]))
        assert set(np.round(moved / 0.01).astype(int)) <= {0, 1, 2} \
            and moved.max() > 0.005
    assert max(abs(g - w) / abs(w)
               for g, w in zip(losses, want[0])) < TOL["loss"]
    # every dense leaf and the touched rows
    assert worst(model.params, want[2]) < TOL["step"]
    touched = np.unique(np.concatenate(batches))
    assert rel((model.local_rows() - rows0)[touched],
               (want[3] - rows0)[touched]) < 5e-3
    assert rel(model.local_rows(), want[3]) < TOL["step"]

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
    ids, _, where, targets, mask = pack_batch(tokens, 1)
    rows = jnp.asarray(rng.standard_normal((len(ids), cfg.hidden_size)),
                       jnp.float32) * 0.02
    s = sizes(cfg)
    loss, counts, gp, grows = ref.value_and_grads(
        params, rows, buffers, where, targets, mask, s, cfg.held)
    got = {}
    loss2, counts2, grows2 = ref.grads_by_layer(
        lambda i: params["layers"][i],
        lambda: (params["final_norm"], params["head"]), rows, buffers, where,
        targets, mask, s, cfg.held, got.__setitem__)
    assert abs(float(loss2) - float(loss)) < 1e-6
    np.testing.assert_array_equal(counts2, counts)
    assert rel(grows2, grows) < 1e-5
    assert rel(got["top"][0], gp["final_norm"]) < 1e-5
    assert rel(got["top"][1], gp["head"]) < 1e-5
    for i in range(len(cfg.pattern)):
        assert worst(got[i], gp["layers"][i]) < 1e-5


def test_the_loss_falls():
    cfg = small(adagrad_step=0.01)
    model = HybridLM(cfg, mode="local")
    tokens = batch(cfg, seed=11)
    losses = [model.step(tokens) for _ in range(8)]
    assert losses[-1] < losses[0] - 0.2


# -- spans, counters, scopes ---------------------------------------------------
def test_step_counters_scopes_and_program_names():
    cfg = small()
    model = HybridLM(cfg, mode="local")
    names = ("lm.kda.chunks", "lm.kda.plane.xla", "lm.kda.plane.fused",
             "lm.kda.passes.plane.xla", "lm.kda.passes.plane.fused",
             "lm.moe.group_limited", "lm.tokens", "lm.attn.pairs")
    reg = get_registry()
    before = {n: reg.counter(n).value for n in names}
    tokens = batch(cfg, seed=6)
    model.step(tokens)
    got = {n: reg.counter(n).value - before[n] for n in names}
    # two KDA blocks (heads of 16: jax.numpy's plane), two sequences of 40 in
    # chunks of 32; one latent block
    assert got == {"lm.kda.chunks": 2 * 2 * 2, "lm.kda.plane.xla": 2,
                   "lm.kda.plane.fused": 0, "lm.kda.passes.plane.xla": 2,
                   "lm.kda.passes.plane.fused": 0,
                   "lm.moe.group_limited": 2, "lm.tokens": 80,
                   "lm.attn.pairs": 2 * 40 * 41 // 2}
    ids, _, where, targets, mask = pack_batch(tokens, cfg.row_bucket)
    text = jax.jit(jax.value_and_grad(make_loss(cfg), argnums=(0, 1),
                                      has_aux=True)).lower(
        model.params, jnp.zeros((len(ids), cfg.hidden_size)), model.buffers,
        where, targets, mask).as_text(debug_info=True)
    for scope in ("lm_kda", "lm_kda_scan", "lm_kda_passes", "lm_mla",
                  "lm_experts", "lm_route",
                  "lm_dense_ffn", "lm_head_loss"):
        assert scope in text, scope
    assert DELTA_PROGRAM == "lm_delta_step"


def test_heads_of_a_lane_tile_take_the_kernels_and_step_as_the_body(
        monkeypatch):
    """KDA heads of 128 on the leaves' one device: the delta rule walks on
    the kernels and the passes on either side of it are the fused ones (here
    under the Pallas interpreter), each counted ``fused``, and the model
    steps as one kept on ``jax.numpy``'s planes does."""
    cfg = small(pattern="KD", kda_num_heads=2, kda_head_dim=128,
                expert_bias_update_rate=0.0)
    tokens = batch(cfg, seed=8)
    names = ("lm.kda.plane.xla", "lm.kda.plane.fused",
             "lm.kda.passes.plane.xla", "lm.kda.passes.plane.fused")
    reg = get_registry()

    def stepped():
        model = HybridLM(cfg, mode="local")
        before = {n: reg.counter(n).value for n in names}
        losses = [model.step(tokens) for _ in range(2)]
        return model, losses, tuple(reg.counter(n).value - before[n]
                                    for n in names)

    fused, losses, counted = stepped()
    assert fused.mixer_interpret is True and counted == (0, 2, 0, 2)
    # the delta rule on jax.numpy, the passes still the kernels'
    monkeypatch.setattr(kda, "kda_kernel_selected", lambda *a: False)
    passes, between, counted = stepped()
    assert passes.mixer_interpret is True and counted == (2, 0, 0, 2)
    monkeypatch.setattr(kda, "kda_passes_selected", lambda *a: False)
    body, want, counted = stepped()
    assert body.mixer_interpret is None and counted == (2, 0, 2, 0)
    np.testing.assert_allclose(losses, want, rtol=1e-5)
    np.testing.assert_allclose(between, want, rtol=1e-5)
    for got, leaf in zip(jax.tree_util.tree_leaves(fused.params),
                         jax.tree_util.tree_leaves(body.params)):
        assert rel(got, leaf) < 1e-5


@pytest.mark.parametrize("config", ["lfm2-8b-a1b-ep4",
                                    "nemotron3-nano-30b-a3b-ep16",
                                    "minicpm-sala-9b-pp8"])
def test_without_a_kda_block_a_model_traces_as_it_did(config, monkeypatch):
    """A configuration with no ``K`` block: the kernels' rule adds nothing to
    what its leaves' device already decided, and its loss-and-gradient is,
    letter for letter, the jaxpr of a program that knows no delta-rule
    kernel."""
    from multiverso_tpu.models.hybrid_lm import model as model_module
    with open(os.path.join(ROOT, "benchmark", "configs",
                           config + ".json")) as f:
        d = json.load(f)
    d.update(d["tiny"])
    cfg = HybridLMConfig.from_dict(d)
    assert "K" not in cfg.pattern
    assert model_module.kda_kernel_blocks(cfg) == 0
    assert model_module.kda_passes_blocks(cfg) == 0
    tokens = batch(cfg, seed=9)
    ids, _, where, targets, mask = pack_batch(tokens, cfg.row_bucket)

    def traced():
        model = HybridLM(cfg, mode="local")
        return model.mixer_interpret, str(jax.make_jaxpr(jax.value_and_grad(
            make_loss(cfg, moe_rows_interpret=model.moe_rows_interpret,
                      mixer_interpret=model.mixer_interpret),
            argnums=(0, 1), has_aux=True))(
                model.params, jnp.zeros((len(ids), cfg.hidden_size)),
                model.buffers, where, targets, mask))

    now = traced()

    def no_kernel(*a, **kw):
        raise AssertionError("a delta-rule kernel in a model without one")

    monkeypatch.setattr(model_module, "kda_kernel_blocks", lambda cfg: 0)
    monkeypatch.setattr(model_module, "kda_passes_blocks", lambda cfg: 0)
    monkeypatch.setattr(kda, "kda_scan", no_kernel)
    monkeypatch.setattr(kda, "kda_kernel_selected", no_kernel)
    monkeypatch.setattr(kda, "kda_passes_selected", no_kernel)
    monkeypatch.setattr(kda, "delta_rule_inputs", no_kernel)
    assert traced() == now


def test_a_model_without_groups_counts_no_group_limited_block():
    cfg = small(n_group=1, topk_group=1, pattern="KE")
    model = HybridLM(cfg, mode="local")
    reg = get_registry()
    before = reg.counter("lm.moe.group_limited").value
    model.step(batch(cfg, seed=7))
    assert reg.counter("lm.moe.group_limited").value == before


# -- the configuration file ---------------------------------------------------
def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return next(r for r in rows if r["name"] == "Ling-3.0-flash-VL")


#: The catalog row's ``config`` (Ling-3.0-flash-VL), number for number; the
#: three cut keys are under ``published``.
CATALOG = {
    "image_patch_token": 157157, "video_patch_token": 156909,
    "image_start_token": 157158, "video_start_token": 157160,
    "hidden_size": 2560, "intermediate_size": 6144,
    "first_k_dense_replace": 2, "max_position_embeddings": 131072,
    "moe_intermediate_size": 768, "num_experts_per_tok": 8,
    "num_attention_heads": 32, "q_lora_rank": None, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "num_key_value_heads": 32, "rope_theta": 6000000, "rms_norm_eps": 1e-06,
    "head_dim": 128, "partial_rotary_factor": 0.5,
    "moe_router_enable_expert_bias": True, "routed_scaling_factor": 2.5,
    "n_group": 8, "topk_group": 4, "use_qk_norm": True,
    "score_function": "sigmoid", "moe_shared_expert_intermediate_size": 768,
    "layer_group_size": 6, "num_kv_heads_for_linear_attn": 0,
    "group_norm_size": 1, "linear_silu": True, "rotary_dim": 64,
    "use_mla_nope": False, "short_conv_kernel_size": 4, "use_nGPT": False,
    "scale_router_input": False, "value_norm": False, "up_proj_norm": False,
    "gated_attention_proj_granularity_type": "head_wise",
    "mtp_use_kda": False, "no_kda_lora": True, "use_kda_lora": False,
    "kda_safe_gate": True, "kda_lower_bound": -5, "norm_topk_prob": True,
    "expert_swiglu_limit_list": [0] * 35 + [4] * 7,
    "share_expert_swiglu_limit_list": [0] * 34 + [5] * 6 + [7] * 2}
PUBLISHED = {"num_hidden_layers": 42, "num_experts": 512,
             "vocab_size": 157184}


@pytest.fixture(scope="module")
def config_file():
    with open(CONFIG_FILE) as f:
        return json.load(f)


@pytest.mark.parametrize("key", sorted(CATALOG))
def test_config_file_has_the_catalogs_value(config_file, key):
    assert config_file[key] == CATALOG[key]


def test_the_catalogs_row_is_what_this_file_pins():
    row = _catalog_row()
    if row is None:
        pytest.skip("no catalog beside the guides here")
    assert row["config"] == dict(CATALOG, **PUBLISHED)
    with open(CONFIG_FILE) as f:
        assert json.load(f)["source_url"] == row["source_url"]


def test_config_file_states_its_cut_deployment_and_assumptions(config_file):
    c = config_file
    assert c["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert c["published"] == PUBLISHED
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == \
        (6, 8, 19648)
    assert c["held_experts"] == list(range(8))
    assert set(c["reduced_why"]) == set(c["reduced"])
    # ISSUE 48's stated fallback: 16 held experts did not fit (PERF.md 3)
    assert "one expert-parallel rank of 64" in c["deployment"]
    assert "layers 6-41 lie on further ranks" in c["deployment"]
    for key in ("mla_position_in_group", "use_qk_norm", "kda_positions",
                "kda_output_gate", "kda_gate", "group_score", "kda_init",
                "expert_bias_update_rate", "optimizer", "init", "documents",
                "sequence_length", "model_type", "to_check"):
        assert key in c["assumed"], key
    assert c["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert c["precision"] and c["guarantees"] and c["tiny"]


def test_from_file_gives_the_twelve_blocks_and_the_issues_counts(config_file):
    """ISSUE 48's arithmetic, from shapes alone (nothing allocated): its first
    choice of 16 held experts (846,141,216 dense parameters, 10.76 GB with
    the rows), and the fallback the file states, 8 held (707.7 M, 8.49 GB)."""
    cfg = HybridLMConfig.from_file(CONFIG_FILE)
    assert cfg.pattern == "KDKDKEKEKELE"
    assert (cfg.router_experts, cfg.held) == (512, tuple(range(8)))
    rows = cfg.vocab_size * cfg.hidden_size
    assert dense_param_count(cfg) == 657397536 == (
        5 * 52648608 + 31886336 + 2 * 47188480 + 4 * 54397440 + 50298880
        + 2560)
    assert dense_param_count(cfg) + rows == 707696416
    assert round((dense_param_count(cfg) + rows) * 12 / 1e9, 2) == 8.49
    cfg = HybridLMConfig.from_dict(dict(
        config_file, num_experts=16, held_experts=list(range(16))))
    assert (cfg.n_group, cfg.topk_group, cfg.num_experts_per_tok) == (8, 4, 8)
    assert (cfg.scoring_func, cfg.use_expert_bias, cfg.hidden_act) == \
        ("sigmoid", True, "silu")
    assert (cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_lower_bound,
            cfg.short_conv_kernel_size) == (32, 128, -5, 4)
    assert cfg.rope_scaling is None and cfg.rope_theta == 6000000
    assert cfg.norm_eps == 1e-6 and not cfg.tie_word_embeddings
    shapes = param_shapes(cfg)

    def count(block):
        return sum(int(np.prod(s)) for s in block.values())

    by_kind = {k: count(shapes["layers"][cfg.pattern.index(k)])
               for k in "KLDE"}
    assert by_kind == {"K": 52648608, "L": 31886336, "D": 47188480,
                       "E": 101583360}
    kda_block = shapes["layers"][0]
    assert kda_block["wa"] == (2560, 4096)          # no_kda_lora: full
    assert kda_block["wbeta"] == kda_block["wg"] == (2560, 32)
    assert kda_block["conv_q"] == kda_block["conv_k"] == \
        kda_block["conv_v"] == (4096, 4)
    assert (kda_block["A_log"], kda_block["dt_bias"], kda_block["o_norm"]) \
        == ((32,), (4096,), (128,))
    assert shapes["head"] == (2560, 19648)
    assert dense_param_count(cfg) == 846141216 == (
        5 * 52648608 + 31886336 + 2 * 47188480 + 4 * 101583360 + 50298880
        + 2560)
    # with the table's rows: 896.4 M parameters, 10.76 GB at 12 bytes
    total = dense_param_count(cfg) + cfg.vocab_size * cfg.hidden_size
    assert total == 896440096 and round(total * 12 / 1e9, 2) == 10.76


@pytest.mark.parametrize("change,message", [
    ({"expert_swiglu_limit_list": [0, 0, 0, 4, 0, 0]},
     "expert_swiglu_limit_list"),
    ({"share_expert_swiglu_limit_list": [5] * 42},
     "share_expert_swiglu_limit_list"),
    ({"kda_safe_gate": False}, "kda_safe_gate"),
    ({"no_kda_lora": False}, "no_kda_lora"),
    ({"q_lora_rank": 1536}, "q_lora_rank"),
    ({"num_kv_heads_for_linear_attn": 8}, "num_kv_heads_for_linear_attn"),
    ({"gated_attention_proj_granularity_type": "element_wise"},
     "gated_attention_proj_granularity_type")])
def test_from_dict_refuses_what_it_does_not_implement(config_file, change,
                                                      message):
    with pytest.raises(ValueError, match=message):
        HybridLMConfig.from_dict(dict(config_file, **change))


def test_a_clamp_beyond_the_kept_layers_is_no_concern(config_file):
    """The published lists are non-zero from layer 34 on: the six kept layers
    run none, and a file that kept layer 35 would be refused."""
    assert HybridLMConfig.from_dict(config_file).pattern == "KDKDKEKEKELE"
    with pytest.raises(ValueError, match="share_expert_swiglu_limit_list"):
        HybridLMConfig.from_dict(dict(config_file, num_hidden_layers=35))


def test_a_file_that_holds_no_expert_is_dense_in_every_layer(config_file):
    d = dict(config_file, num_experts=0, held_experts=[])
    assert HybridLMConfig.from_dict(d).pattern == "KDKDKDKDKDLD"


def test_the_tiny_sizes_keep_the_pattern_a_period(config_file):
    d = dict(config_file, **config_file["tiny"])
    cfg = HybridLMConfig.from_dict(d)
    assert cfg.pattern == "KDKELE"
    assert (cfg.router_experts, cfg.held, cfg.n_group, cfg.topk_group) == \
        (16, (0, 1), 4, 2)
