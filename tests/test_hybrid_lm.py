"""The layer-typed LM (models/hybrid_lm) against the benchmark's plain
reference (benchmark/reference/nemotron3-nano-30b-a3b-ep16.py) at a small
size on the CPU: every mixer's forward and gradients, the whole model's loss,
gradients and two AdaGrad steps, the share test of the expert layer, the PS
plane against its local twin, routing that drops nothing, and a bfloat16
control that fails."""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from multiverso_tpu.models.hybrid_lm import (DELTA_PROGRAM, HybridLM,
                                             HybridLMConfig, init_buffers,
                                             init_params, layer_forward,
                                             make_loss, pack_batch)
from multiverso_tpu.parallel.expert import held_topk_moe
from multiverso_tpu.telemetry.metrics import get_registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "nemotron3-nano-30b-a3b-ep16"


def _reference():
    path = os.path.join(ROOT, "benchmark", "reference", CONFIG + ".py")
    spec = importlib.util.spec_from_file_location("lm_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()

# hidden 64, 2 Mamba heads of 16, N 16, chunk 8, 8 experts top-2 with 2 held,
# vocabulary 64
SMALL = dict(hidden_size=64, vocab_size=64, pattern="MEM*E",
             mamba_num_heads=2, mamba_head_dim=16, ssm_state_size=16,
             n_groups=1, conv_kernel=4, chunk_size=8, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, router_experts=8,
             held=(0, 1), num_experts_per_tok=2, moe_intermediate_size=32,
             moe_shared_expert_intermediate_size=64, attn_block=8,
             moe_block=4, loss_block=16, row_bucket=16)
TOL = dict(loss=2e-5, grad=2e-4, step=2e-4)


def small(**kw) -> HybridLMConfig:
    return HybridLMConfig(**dict(SMALL, **kw))


def sizes(cfg: HybridLMConfig) -> dict:
    return {"pattern": cfg.pattern, "norm_eps": cfg.norm_eps,
            "mamba_num_heads": cfg.mamba_num_heads,
            "mamba_head_dim": cfg.mamba_head_dim, "n_groups": cfg.n_groups,
            "ssm_state_size": cfg.ssm_state_size,
            "num_attention_heads": cfg.num_attention_heads,
            "num_key_value_heads": cfg.num_key_value_heads,
            "head_dim": cfg.head_dim,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "norm_topk_prob": cfg.norm_topk_prob,
            "routed_scaling_factor": cfg.routed_scaling_factor}


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def worst(got_tree, want_tree) -> float:
    return max(rel(g, w) for g, w in zip(jax.tree_util.tree_leaves(got_tree),
                                         jax.tree_util.tree_leaves(want_tree)))


def batch(cfg, seqs=2, length=21, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, (seqs, length)).astype(np.int32)


# -- each mixer, forward and gradients ----------------------------------------
@pytest.mark.parametrize("length", [16, 21])
@pytest.mark.parametrize("kind,groups", [("M", 1), ("M", 2), ("*", 1),
                                         ("E", 1)])
def test_mixer_matches_reference(kind, groups, length):
    cfg = small(pattern=kind, n_groups=groups)
    p = init_params(cfg)["layers"][0]
    # norms, D and the conv bias start at one / zero: move them off it
    rng = np.random.default_rng(3)
    p = {k: v + 0.1 * rng.standard_normal(v.shape).astype(np.float32)
         if k in ("norm", "gnorm", "D", "conv_b") else v
         for k, v in p.items()}
    bias = init_buffers(cfg)[0]
    u = jnp.asarray(rng.standard_normal((2, length, cfg.hidden_size)),
                    jnp.float32)
    w = jnp.asarray(rng.standard_normal(u.shape), jnp.float32)
    s = sizes(cfg)

    def mine(p, u):
        return jnp.sum(layer_forward(kind, p, bias, u, cfg)[0] * w)

    def theirs(p, u):
        return jnp.sum(ref.layer(kind, p, bias, u, s, cfg.held)[0] * w)

    out, counts = jax.jit(
        lambda p, u: layer_forward(kind, p, bias, u, cfg))(p, u)
    with jax.default_matmul_precision("highest"):
        want, want_counts = jax.jit(
            lambda p, u: ref.layer(kind, p, bias, u, s, cfg.held))(p, u)
        want_grads = jax.jit(jax.grad(theirs, argnums=(0, 1)))(p, u)
    assert rel(out, want) < TOL["loss"]
    assert worst(jax.jit(jax.grad(mine, argnums=(0, 1)))(p, u),
                 want_grads) < TOL["grad"]
    if kind == "E":
        np.testing.assert_array_equal(counts, want_counts)


@pytest.mark.parametrize("length", [8, 21, 40])
def test_reference_closed_form_is_the_recurrence(length):
    rng = np.random.default_rng(1)
    h, p, g, n = 4, 8, 2, 6
    x = jnp.asarray(rng.standard_normal((length, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.5, (length, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 8, h), jnp.float32)
    b = jnp.asarray(rng.standard_normal((length, g, n)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((length, g, n)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        assert rel(ref.ssm_cumulative(x, dt, a, b, c, block=16),
                   ref.ssm_recurrence(x, dt, a, b, c)) < 1e-5


# -- the whole model ----------------------------------------------------------
def _reference_steps(cfg, model0_params, rows0, buffers, batches,
                     compute="float32", storage=None):
    """Two AdaGrad steps of the reference from the model's own start:
    (losses, counts, parameters, rows, first step's gradients)."""
    s = sizes(cfg)

    def stored(tree):
        if storage is None:
            return tree
        return jax.tree_util.tree_map(
            lambda x: jnp.asarray(x).astype(storage).astype(jnp.float32),
            tree)

    params = stored(model0_params)
    rows = np.array(stored(rows0))
    g2 = jax.tree_util.tree_map(jnp.zeros_like, params)
    rows_g2 = np.zeros_like(rows)
    losses, counts, first = [], [], None
    for tokens in batches:
        ids, n, where, targets, mask = pack_batch(tokens, 1)
        loss, c, gp, grows = ref.value_and_grads(
            params, jnp.asarray(rows[ids]), buffers, where, targets, mask, s,
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
    return losses, counts, params, rows, first


def _gaps(model, losses, want):
    want_losses, _, want_params, want_rows, _ = want
    return {"loss": max(abs(g - w) / abs(w)
                        for g, w in zip(losses, want_losses)),
            "step": max(worst(model.params, want_params),
                        rel(model.local_rows(), want_rows))}


def test_whole_model_two_steps_match_reference():
    cfg = small()
    model = HybridLM(cfg, mode="local")
    start = jax.tree_util.tree_map(np.array, model.params)
    rows0 = model.local_rows()
    batches = [batch(cfg, seed=1), batch(cfg, seed=2)]

    # gradients of the first step, before anything moves
    ids, _, where, targets, mask = pack_batch(batches[0], cfg.row_bucket)
    (_, counts), (gp, grows) = jax.jit(jax.value_and_grad(
        make_loss(cfg), argnums=(0, 1), has_aux=True))(
            model.params, jnp.asarray(rows0[ids]), model.buffers, where,
            targets, mask)
    losses = [model.step(b) for b in batches]
    want = _reference_steps(cfg, start, rows0, model.buffers, batches)
    want_gp, want_grows, want_ids = want[4]
    assert worst(gp, want_gp) < TOL["grad"]
    assert rel(np.asarray(grows)[:len(want_ids)], want_grows) < TOL["grad"]
    np.testing.assert_array_equal(counts, want[1][0])
    np.testing.assert_array_equal(model.last_counts, want[1][1])
    gaps = _gaps(model, losses, want)
    assert gaps["loss"] < TOL["loss"] and gaps["step"] < TOL["step"], gaps

    # the control: the reference computed and stored in bfloat16, put in the
    # program's place, fails at least one of the same tolerances
    low = _reference_steps(cfg, start, rows0, model.buffers, batches,
                           compute="bfloat16", storage="bfloat16")
    control = {"loss": max(abs(g - w) / abs(w)
                           for g, w in zip(low[0], want[0])),
               "step": max(worst(low[2], want[2]), rel(low[3], want[3]))}
    assert control["loss"] > TOL["loss"] or control["step"] > TOL["step"]


def test_reference_layer_by_layer_gradients_are_the_whole_models():
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
        lambda: (params["final_norm"], params["head"]),
        rows, buffers, where, targets, mask, s, cfg.held, got.__setitem__)
    assert abs(float(loss2) - float(loss)) < 1e-6
    np.testing.assert_array_equal(counts2, counts)
    assert rel(grows2, grows) < 1e-5
    assert rel(got["top"][1], gp["head"]) < 1e-5
    for i in range(len(cfg.pattern)):
        assert worst(got[i], gp["layers"][i]) < 1e-5


# -- the share test -----------------------------------------------------------
@pytest.mark.parametrize("side", ["program", "reference"])
def test_expert_shares_add_up_to_the_uncut_layer(side):
    """The parts that all shares of the experts give, the shared expert
    counted once, add up to what the layer holding every expert gives."""
    whole = small(pattern="E", held=tuple(range(8)))
    p = init_params(whole)["layers"][0]
    bias = init_buffers(whole)[0]
    rng = np.random.default_rng(2)
    n = jnp.asarray(rng.standard_normal((40, whole.hidden_size)), jnp.float32)
    s = sizes(whole)

    def share(held, shared):
        part = dict(p, w_up=p["w_up"][np.asarray(held)],
                    w_down=p["w_down"][np.asarray(held)])
        if side == "reference":
            with jax.default_matmul_precision("highest"):
                return ref.expert_mixer(part, bias, n, s, held, shared)
        return held_topk_moe(
            n, part["router"], bias, part["w_up"], part["w_down"],
            part["s_up"], part["s_down"], held, whole.num_experts_per_tok,
            whole.routed_scaling_factor, whole.norm_topk_prob, 4, shared)

    uncut, uncut_counts = share(tuple(range(8)), True)
    parts = [share((2 * i, 2 * i + 1), i == 0) for i in range(4)]
    assert rel(sum(y for y, _ in parts), uncut) < 1e-5
    np.testing.assert_array_equal(
        np.concatenate([c for _, c in parts]), uncut_counts)
    assert int(np.sum(uncut_counts)) == 40 * whole.num_experts_per_tok


def test_routing_drops_nothing_when_one_expert_takes_half_the_tokens():
    cfg = small(pattern="E")
    p = dict(init_params(cfg)["layers"][0])
    bias = init_buffers(cfg)[0]
    rng = np.random.default_rng(4)
    tokens = 64
    n = rng.standard_normal((tokens, cfg.hidden_size)).astype(np.float32)
    pull = rng.standard_normal(cfg.hidden_size).astype(np.float32)
    n[::2] = 3.0 * pull + 0.1 * n[::2]        # every other token looks alike
    router = np.array(p["router"])
    router[:, 0] = pull                       # and expert 0 wants them
    p["router"] = jnp.asarray(router)
    y, counts = held_topk_moe(
        jnp.asarray(n), p["router"], bias, p["w_up"], p["w_down"], p["s_up"],
        p["s_down"], cfg.held, cfg.num_experts_per_tok,
        cfg.routed_scaling_factor, cfg.norm_topk_prob, cfg.moe_block)
    with jax.default_matmul_precision("highest"):
        want, want_counts = ref.expert_mixer(p, bias, jnp.asarray(n),
                                             sizes(cfg), cfg.held)
    assert int(counts[0]) >= tokens // 2
    np.testing.assert_array_equal(counts, want_counts)
    assert rel(y, want) < TOL["loss"]


@pytest.mark.parametrize("hidden", [256, 1024])
@pytest.mark.parametrize("kind", ["gated", "relu2"])
def test_grouped_experts_on_the_row_kernel_are_the_xla_planes_bits(kind,
                                                                   hidden):
    """Where a block's result rows land (ISSUE 41): with the DMA row kernel
    (interpreted here) the grouped products give the XLA scatter-add's
    outputs and gradients to the bit, under ``jax.checkpoint``, at 256
    columns (two planes, carried as a whole tile of eight) and at 1,024
    (eight, as they are), with an uneven load (every token on expert 0, a
    few on expert 5: several blocks against one) and a held expert that
    nobody chose."""
    from multiverso_tpu.parallel.expert import (group_held_assignments,
                                                grouped_gated_experts,
                                                grouped_relu2_experts)
    tokens, width, held, block = 96, 64, (0, 2, 5), 8
    rng = np.random.default_rng(41)

    def draw(*shape, scale=0.1):
        return jnp.asarray(rng.standard_normal(shape, dtype=np.float32)
                           * scale)

    chosen = np.stack([np.zeros(tokens, np.int32),
                       np.where(np.arange(tokens) % 7 == 0, 5, 3)], axis=1)
    layout = group_held_assignments(
        jnp.asarray(chosen), draw(tokens, 2, scale=1.0), held, 8, block)
    idx, gates, block_expert, in_use, counts = layout
    assert list(np.asarray(counts)) == [tokens, 0, 14]
    n, w = draw(tokens, hidden, scale=1.0), draw(tokens, hidden, scale=1.0)
    weights = [draw(len(held), hidden, width) for _ in range(
        2 if kind == "gated" else 1)] + [draw(len(held), width, hidden)]
    grouped = grouped_gated_experts if kind == "gated" \
        else grouped_relu2_experts

    def run(rows_interpret):
        @jax.checkpoint
        def experts(n, gates, *weights):
            return grouped(n, gates, *weights, idx, block_expert, in_use,
                           block, rows_interpret)

        def loss(n, gates, *weights):
            y = experts(n, gates, *weights)
            return jnp.sum(y * w), y
        return jax.jit(jax.value_and_grad(
            loss, argnums=tuple(range(2 + len(weights))), has_aux=True))(
                n, gates, *weights)

    (_, y_xla), grads_xla = run(None)
    (_, y_kernel), grads_kernel = run(True)
    assert float(jnp.abs(y_xla).max()) > 0
    np.testing.assert_array_equal(np.asarray(y_xla), np.asarray(y_kernel))
    for a, b in zip(grads_xla, grads_kernel):
        assert float(jnp.abs(a).max()) > 0
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("hidden,plane", [(64, "xla"), (128, "fused")])
def test_step_counts_the_plane_its_expert_blocks_rows_land_on(
        hidden, plane, monkeypatch):
    """``lm.moe.rows.plane.<fused/xla>``, one a step and expert block: the
    row kernel wherever the token accumulator is float32 of whole 128-lane
    planes on the leaves' one device (here under the interpreter), XLA's
    scatter-add at hidden 64. A twin kept on the XLA plane steps to the
    same bits."""
    from multiverso_tpu.models.hybrid_lm import model as model_module
    cfg = small(hidden_size=hidden, pattern="E*E")
    model = HybridLM(cfg, mode="local")
    assert model.moe_rows_interpret == (True if plane == "fused" else None)
    monkeypatch.setattr(model_module, "token_rows_kernel_selected",
                        lambda hidden, dtype: False)
    twin = HybridLM(cfg, mode="local")
    monkeypatch.undo()
    assert twin.moe_rows_interpret is None
    reg = get_registry()
    names = [f"lm.moe.rows.plane.{p}" for p in ("fused", "xla")]
    batches = [batch(cfg, seed=11), batch(cfg, seed=12)]
    before = [reg.counter(n).value for n in names]
    losses = [model.step(b) for b in batches]
    moved = dict(zip(("fused", "xla"), (reg.counter(n).value - b
                                        for n, b in zip(names, before))))
    assert moved == {plane: 2 * len(batches),
                     "xla" if plane == "fused" else "fused": 0}
    assert losses == [twin.step(b) for b in batches]
    for (name, a), (_, b) in zip(model.dense_leaves(), twin.dense_leaves()):
        np.testing.assert_array_equal(a, b, err_msg=name)
    np.testing.assert_array_equal(model.local_rows(), twin.local_rows())


def test_reference_expert_layer_in_token_blocks_is_the_whole(monkeypatch):
    cfg = small(pattern="E")
    p, bias = init_params(cfg)["layers"][0], init_buffers(cfg)[0]
    u = jnp.asarray(np.random.default_rng(6).standard_normal(
        (2, 32, cfg.hidden_size)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, counts = ref.layer("E", p, bias, u, sizes(cfg), cfg.held)
        monkeypatch.setattr(ref, "EXPERT_TOKEN_BLOCK", 16)
        blocked, blocked_counts = ref.layer("E", p, bias, u, sizes(cfg),
                                            cfg.held)
    assert rel(blocked, whole) < 1e-6
    np.testing.assert_array_equal(blocked_counts, counts)


# -- the planes ------------------------------------------------------------
@pytest.fixture(params=["mesh_of_8", "one_device"])
def table_devices(request):
    import multiverso_tpu as mv
    one = request.param == "one_device"
    mv.init([], devices=jax.devices()[:1] if one else None)
    yield one
    mv.shutdown()


def test_ps_plane_matches_local_twin_bitwise(table_devices, monkeypatch):
    """With the table on the dense programs' one device a step is one
    device pull and one device push and no row block visits the host;
    over a mesh it pulls to the host and pushes from it. The twin's bytes
    either way."""
    from multiverso_tpu.tables.table_group import TableGroup
    cfg = small(pattern="ME*")
    local, ps = HybridLM(cfg, mode="local"), HybridLM(cfg, mode="ps")
    batches = [batch(cfg, seed=7), batch(cfg, seed=8), batch(cfg, seed=7)]
    pushed, host_pulls, add_rows, get_rows = [], [], TableGroup.add_rows, \
        TableGroup.get_rows

    def spy_add(self, ids, deltas, option=None):
        pushed.extend(type(d) for d in deltas)
        return add_rows(self, ids, deltas, option)

    def spy_get(self, ids, option=None):
        host_pulls.append(len(ids[0]))
        return get_rows(self, ids, option)
    monkeypatch.setattr(TableGroup, "add_rows", spy_add)
    monkeypatch.setattr(TableGroup, "get_rows", spy_get)
    device_calls = [get_registry().counter(f"table.group.device_{kind}")
                    for kind in ("pulls", "pushes")]
    before = [c.value for c in device_calls]
    assert [local.step(b) for b in batches] == [ps.step(b) for b in batches]
    assert [c.value - b for c, b in zip(device_calls, before)] == \
        [len(batches) * table_devices] * 2
    assert len(host_pulls) == (0 if table_devices else len(batches))
    assert len(pushed) == len(batches) and all(
        issubclass(t, jax.Array) == table_devices for t in pushed)
    # the first step compiles what every later one runs (as the twin, whose
    # arrays are all uncommitted: one program a padded shape)
    assert ps._hybrid.delta._cache_size() == local._hybrid.delta._cache_size()
    assert ps._hybrid.apply._cache_size() == local._hybrid.apply._cache_size()
    monkeypatch.undo()
    ids = np.unique(batches[0])
    np.testing.assert_array_equal(ps.pull_rows(ids), local.pull_rows(ids))
    assert isinstance(ps.pull_rows(ids), np.ndarray)
    for (name, a), (_, b) in zip(local.dense_leaves(), ps.dense_leaves()):
        np.testing.assert_array_equal(a, b, err_msg=name)
    np.testing.assert_array_equal(
        local.local_rows(),
        ps.table.get_rows(np.arange(cfg.vocab_size, dtype=np.int32)))
    for a, b in zip(jax.tree_util.tree_leaves(local.state),
                    jax.tree_util.tree_leaves(ps.state)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("plane", ["xla", "fused"])
def test_step_counts_the_plane_its_scan_blocks_run_on(plane, monkeypatch):
    """``lm.scan.plane.<fused/xla>``, one a step and Mamba-2 block: the scan
    kernels wherever chunk and state are whole 128-lane tiles and a group's
    heads side by side fill lane tiles, on the leaves' one device (here
    under the interpreter: 2 heads of 64, state 128, chunks of 128); the
    ``jax.numpy`` body at the tiny shapes of every other test here. A twin
    kept on the body steps to the same numbers."""
    from multiverso_tpu.models.hybrid_lm import model as model_module
    wide = dict(mamba_head_dim=64, ssm_state_size=128, chunk_size=128) \
        if plane == "fused" else {}
    cfg = small(pattern="M*M", **wide)
    model = HybridLM(cfg, mode="local")
    assert model.mixer_interpret == (True if plane == "fused" else None)
    monkeypatch.setattr(model_module, "scan_kernel_selected",
                        lambda *shape: False)
    # (at these widths the passes round the scan are kernels too: below)
    monkeypatch.setattr(model_module.mamba2, "passes_kernel_selected",
                        lambda *shape: False)
    twin = HybridLM(cfg, mode="local")
    monkeypatch.undo()
    assert twin.mixer_interpret is None
    reg = get_registry()
    names = [f"lm.scan.plane.{p}" for p in ("fused", "xla")]
    batches = [batch(cfg, seed=11), batch(cfg, seed=12)]
    before = [reg.counter(n).value for n in names]
    losses = [model.step(b) for b in batches]
    moved = dict(zip(("fused", "xla"), (reg.counter(n).value - b
                                        for n, b in zip(names, before))))
    assert moved == {plane: 2 * len(batches),
                     "xla" if plane == "fused" else "fused": 0}
    np.testing.assert_allclose(losses, [twin.step(b) for b in batches],
                               rtol=1e-6)
    for (name, a), (_, b) in zip(model.dense_leaves(), twin.dense_leaves()):
        assert rel(a, b) < 1e-5, name
    ids, _, where, targets, mask = pack_batch(batches[0], cfg.row_bucket)
    text = model._hybrid.delta.lower(
        model.params, jnp.zeros((len(ids), cfg.hidden_size)), model.buffers,
        where, targets, mask).as_text(debug_info=True)
    assert ("pallas_call" in text) == (plane == "fused")
    for scope in ("lm_mamba2", "lm_ssd"):
        assert scope in text, scope


# hidden 64; 2 heads of 64 in ONE group, state 128: ``x``, ``B``, ``C`` and
# the norm's group one lane tile each, the chunk of 8 keeps the scan on its
# ``jax.numpy`` body
LANE_TILES = dict(mamba_head_dim=64, ssm_state_size=128)


@pytest.mark.parametrize("plane", ["xla", "fused", "two_devices"])
def test_step_counts_the_plane_its_mamba_blocks_passes_run_on(plane):
    """``lm.mamba.plane.<fused/xla>``, one a step and Mamba-2 block: the
    fused passes round the scan wherever ``d_inner``, ``groups x state`` and
    the norm's group are whole 128-lane tiles, on the leaves' one device
    (here under the interpreter); XLA's at the tiny widths of every other
    test here, and under a mesh axis of two."""
    cfg = small(pattern="M*M", **({} if plane == "xla" else LANE_TILES))
    mesh = {}
    if plane == "two_devices":
        mesh = dict(dp_mesh=jax.sharding.Mesh(
            np.asarray(jax.devices()[:2]), ("dp",)), dp_axis="dp")
    model = HybridLM(cfg, mode="local", **mesh)
    assert model.mixer_interpret == (True if plane == "fused" else None)
    # the compiler is asked to share the blocks' code only where the passes
    # are COMPILED kernels (a chip: ``mixer_interpret`` False)
    assert model._delta_options() is None
    model.mixer_interpret = False
    assert (model._delta_options() is None) == (plane == "xla")
    model.mixer_interpret = True if plane == "fused" else None
    if plane == "two_devices":
        return
    reg = get_registry()
    names = [f"lm.{layer}.plane.{p}" for layer in ("mamba", "scan")
             for p in ("fused", "xla")]
    batches = [batch(cfg, seed=11), batch(cfg, seed=12)]
    before = [reg.counter(n).value for n in names]
    assert all(np.isfinite(model.step(b)) for b in batches)
    moved = [reg.counter(n).value - b for n, b in zip(names, before)]
    steps = 2 * len(batches)
    assert moved == ([steps, 0] if plane == "fused" else [0, steps]) \
        + [0, steps]
    ids, _, where, targets, mask = pack_batch(batches[0], cfg.row_bucket)
    text = model._hybrid.delta.lower(
        model.params, jnp.zeros((len(ids), cfg.hidden_size)), model.buffers,
        where, targets, mask).as_text(debug_info=True)
    assert ("pallas_call" in text) == (plane == "fused")


@pytest.mark.parametrize("remat", [False, True], ids=["kept", "remat"])
def test_mamba_block_on_the_fused_passes_is_the_block_on_xlas(remat):
    """A Mamba-2 block through ``layer_forward``: its loss and every
    gradient with ``mixer_interpret=True`` (the passes' kernels under the
    interpreter) against ``mixer_interpret=None``, at the tiny widths
    rounded up to whole lane tiles, a sequence at a time, rematerialised
    and not."""
    cfg = small(pattern="M", **LANE_TILES)
    p = init_params(cfg)["layers"][0]
    rng = np.random.default_rng(8)
    p = dict(p, conv_b=jnp.asarray(rng.normal(size=p["conv_b"].shape) * 0.3,
                                   jnp.float32))
    u = jnp.asarray(rng.standard_normal((2, 40, cfg.hidden_size)),
                    jnp.float32)

    def run(interpret):
        def loss(p, u):
            return jnp.sum(jnp.sin(layer_forward(
                "M", p, None, u, cfg, remat, mixer_interpret=interpret)[0]))
        return jax.value_and_grad(loss, (0, 1))(p, u)

    (loss, (dp, du)), (want, (wp, wu)) = run(True), run(None)
    assert abs(float(loss) - float(want)) < 1e-5 * abs(float(want))
    assert rel(du, wu) < 1e-5
    for name in sorted(wp):
        assert float(jnp.abs(wp[name]).max()) > 0, name
        assert rel(dp[name], wp[name]) < 1e-5, name


def test_step_spans_counters_and_program_names():
    """``lm_step_ms`` / ``lm_table_ms`` read the spans, ``lm_mfu_share`` the
    counters and the ``jit_lm_delta_step`` program: the names are part of the
    yardstick."""
    cfg = small(pattern="ME*E")
    model = HybridLM(cfg, mode="local")
    tokens = batch(cfg, seed=9)
    reg = get_registry()
    names = ("lm.step", "lm.pull", "lm.compute", "lm.compute.dispatch",
             "lm.compute.sync", "lm.push")
    before = {n: reg.histogram("span." + n).count for n in names}
    counted = ("lm.tokens", "lm.rows_pulled", "lm.moe.assignments_held.l1",
               "lm.moe.max_expert_load.l1", "lm.moe.assignments_held.l3")
    c0 = {n: reg.counter(n).value for n in counted}
    model.step(tokens)
    for n in names:
        assert reg.histogram("span." + n).count == before[n] + 1, n
    assert reg.counter("lm.tokens").value - c0["lm.tokens"] == tokens.size
    assert reg.counter("lm.rows_pulled").value - c0["lm.rows_pulled"] \
        == len(np.unique(tokens))
    for row, layer in zip(model.last_counts, (1, 3)):
        name = f"lm.moe.assignments_held.l{layer}"
        assert reg.counter(name).value - c0[name] == row.sum()
    assert model._hybrid.delta.__name__ == DELTA_PROGRAM
    ids, _, where, targets, mask = pack_batch(tokens, cfg.row_bucket)
    text = model._hybrid.delta.lower(
        model.params, jnp.zeros((len(ids), cfg.hidden_size)), model.buffers,
        where, targets, mask).as_text()
    assert "module @jit_lm_delta_step" in text
    assert model._hybrid.apply.__name__ == "lm_apply"


def test_benchmark_configuration_keeps_every_published_width():
    path = os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json")
    cfg = HybridLMConfig.from_file(path)
    with open(path) as f:
        raw = json.load(f)
    assert cfg.pattern == "MEMEM*EME" and cfg.hidden_size == 2688
    assert (cfg.d_inner, cfg.conv_dim, cfg.in_proj_dim) == (4096, 6144, 10304)
    assert (cfg.q_dim, cfg.kv_dim) == (4096, 256)
    assert cfg.router_experts == 128 and cfg.held == tuple(range(8))
    assert cfg.num_experts_per_tok == 6 and cfg.vocab_size == 16384
    assert (cfg.moe_intermediate_size,
            cfg.moe_shared_expert_intermediate_size) == (1856, 3712)
    assert raw["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert raw["published"] == {"num_hidden_layers": 52,
                                "n_routed_experts": 128,
                                "vocab_size": 131072}
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["source_url"] == raw["source_url"])
        for key, value in row["config"].items():
            if key not in raw["reduced"]:
                assert raw[key] == value, key
