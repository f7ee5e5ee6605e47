"""Driver ``train_lm``: a layer-typed LM (``models/hybrid_lm``) trained through
``HybridLM.step`` on the PS plane: the input embedding a ``MatrixTable`` pulled
and pushed through a ``TableGroup``, the layer stack on the CommPolicy hybrid
step with the server plane's AdaGrad.

Set-up builds ONE model, seeds its embedding table and every dense leaf on the
device from the seed (``seeded.table_jax``), draws the batches the window
cycles, and drives the model through its first two steps, which the plain
reference follows. The window calls ``step`` until the clock passes
``--seconds``; ``step`` returns the loss as a host float after the row push
has executed, so every step is complete when it returns, and the rate is the
tokens of whole steps over the time to the last return.

The reference runs on the chip too, after the first two steps and before the
window, a layer at a time (``reference.grads_by_layer``): beside the
program's resident parameters and accumulators it holds one layer's weights
and gradients, the nine layer inputs and one layer's working set; the first
step's gradients and the stepped parameters wait on the host. That stays
under the window's own peak, so ``peak_hbm_gb`` is the program's.

A run without a TPU is a rehearsal (``benchmark/tests/rehearse.py``; the
command itself refuses to run there): it takes the ``tiny`` sizes that the
configuration's and the mix's files carry.
"""
from __future__ import annotations

import math
import time

import numpy as np

import seeded
import traffic_gen

SPANS = ("lm.step", "lm.pull", "lm.compute", "lm.compute.dispatch",
         "lm.compute.sync", "lm.push")
CHECK_STEPS = 2
EOS = 0                 # stands between documents; Zipf ids are 1..vocab-1
EMB_STREAM, BIAS_STREAM, HEAD_STREAM, LAYER_STREAM = 0, 40, 90, 100
#: Leaves that project back into the residual stream (divided by sqrt(layers)).
OUT_PROJECTIONS = ("out_proj", "wo", "w_down", "s_down")
KIND_NAMES = {"M": "mamba", "E": "experts", "*": "attention"}
#: Leaves drawn from the seed; a leaf's place here is its stream in its layer.
SEEDED_LEAVES = ("in_proj", "conv_w", "A_log", "dt_bias", "out_proj", "wq",
                 "wk", "wv", "wo", "router", "w_up", "w_down", "s_up",
                 "s_down", "head")


# -- sizes: the files', or their ``tiny`` in a rehearsal ----------------------
def sized(ctx):
    """(configuration, mix) as this run uses them."""
    config, traffic = dict(ctx.config), dict(ctx.traffic)
    if ctx.device["platform"] != "tpu":
        config.update(config.get("tiny", {}))
        traffic.update(traffic.get("tiny", {}))
        ctx.limits = traffic.get("limits", ctx.limits)
    return config, traffic


# -- traffic: packed documents ------------------------------------------------
def packed_batches(seed: int, t: dict, vocab: int) -> list:
    """``batches`` x int32 [sequences, seq_len]: each sequence packed from
    documents of log-normal length (median ``doc_median``, clipped), an
    end-of-document id after each, token ids Zipf ranks through the fixed
    permutation of the slice."""
    rng = traffic_gen.rng_for(seed, 7)
    perm = traffic_gen.key_permutation(vocab - 1)
    out = []
    for _ in range(t["batches"]):
        ranks = traffic_gen.zipf_ids(
            rng, t["zipf"], t["sequences"] * t["seq_len"], vocab - 1)
        tokens = (perm[ranks] + 1).reshape(t["sequences"], t["seq_len"])
        for seq in tokens:
            at = 0
            while at < t["seq_len"]:
                at += int(np.clip(round(rng.lognormal(
                    math.log(t["doc_median"]), t["doc_sigma"])),
                    t["doc_min"], t["doc_max"]))
                if at < t["seq_len"]:
                    seq[at] = EOS
                    at += 1
        out.append(tokens.astype(np.int32))
    return out


# -- weights from the seed: the same leaves for the program and the reference -
def make_leaf(seed: int, c: dict, layer, name: str, shape: tuple):
    """One dense leaf on the device. Matrices: uniform of standard deviation
    ``init_std`` (projections back into the stream over sqrt(layers)); norms
    and ``D`` one; ``A`` in [1, 16] and ``dt`` log-uniform in
    [time_step_min, time_step_max] through the inverse softplus."""
    import jax.numpy as jnp
    stream = HEAD_STREAM if layer is None else \
        LAYER_STREAM + 16 * layer + SEEDED_LEAVES.index(name) \
        if name in SEEDED_LEAVES else None
    if name in ("norm", "gnorm", "D", "final_norm"):
        return jnp.ones(shape, jnp.float32)
    if name == "conv_b":
        return jnp.zeros(shape, jnp.float32)
    if name in ("A_log", "dt_bias"):
        u = seeded.rows_np(seed, stream, [0], shape[0], 1.0, "positive")[0]
        if name == "A_log":
            return jnp.asarray(np.log(1.0 + 15.0 * u), jnp.float32)
        lo, hi = math.log(c["time_step_min"]), math.log(c["time_step_max"])
        dt = np.maximum(np.exp(lo + u * (hi - lo)), c["time_step_floor"])
        return jnp.asarray(dt + np.log(-np.expm1(-dt)), jnp.float32)
    scale = 1.0 if name == "conv_w" else c["init_std"] * math.sqrt(12.0)
    if name in OUT_PROJECTIONS:
        scale /= math.sqrt(c["num_hidden_layers"])
    rows = int(np.prod(shape[:-1]))
    return seeded.table_jax(seed, stream, (rows, shape[-1]), scale
                            ).reshape(shape)


def make_layer(seed: int, c: dict, shapes: dict, layer: int) -> dict:
    return {name: make_leaf(seed, c, layer, name, shape)
            for name, shape in shapes["layers"][layer].items()}


def make_top(seed: int, c: dict, shapes: dict) -> tuple:
    return (make_leaf(seed, c, None, "final_norm", shapes["final_norm"]),
            make_leaf(seed, c, None, "head", shapes["head"]))


def make_buffers(seed: int, c: dict, pattern: str, experts: int) -> list:
    """``e_score_correction_bias`` of each expert layer: uniform on +-0.01."""
    import jax.numpy as jnp
    return [jnp.asarray(seeded.rows_np(seed, BIAS_STREAM + i, [0], experts,
                                       0.02)[0]) if kind == "E" else None
            for i, kind in enumerate(pattern)]


def emb_scale(c: dict) -> float:
    return c["init_std"] * math.sqrt(12.0)


# -- the system under test ----------------------------------------------------
def program():
    """The program's package for this kind of model; a program without it
    cannot run the configuration, and says so at once."""
    import harness
    try:
        from multiverso_tpu.models import hybrid_lm
    except ImportError as e:
        raise harness.BenchError(
            f"the program has no multiverso_tpu.models.hybrid_lm ({e}): it "
            "cannot run this configuration") from e
    return hybrid_lm


def build_model(ctx):
    import multiverso_tpu as mv
    HybridLM, HybridLMConfig, param_shapes = (
        program().HybridLM, program().HybridLMConfig,
        program().param_shapes)
    c, _ = sized(ctx)
    mv.init([])
    cfg = HybridLMConfig.from_dict(c, seed=ctx.seed % (2 ** 31 - 1))
    shapes = param_shapes(cfg)
    model = HybridLM(cfg, mode=c["mode"], **seeded_weights(ctx, cfg, shapes))
    seed_table(model, cfg, ctx)
    return model, cfg, shapes


def seeded_weights(ctx, cfg, shapes) -> dict:
    """Every dense leaf and buffer from the seed, made on the device."""
    c, _ = sized(ctx)
    final_norm, head = make_top(ctx.seed, c, shapes)
    return {"params": {"layers": [make_layer(ctx.seed, c, shapes, i)
                                  for i in range(len(cfg.pattern))],
                       "final_norm": final_norm, "head": head},
            "buffers": make_buffers(ctx.seed, c, cfg.pattern,
                                    cfg.router_experts)}


def seed_table(model, cfg, ctx) -> None:
    import jax
    import jax.numpy as jnp
    c, _ = sized(ctx)
    store = model.table.store
    jax.block_until_ready(store.state)
    seeded.reseed_store(store, ctx.seed, EMB_STREAM, emb_scale(c), "centered",
                        cfg.vocab_size)
    store.state = {k: jnp.zeros_like(v) for k, v in store.state.items()}


def reseed_model(model, cfg, shapes, ctx) -> None:
    """A model that has stepped, back to what a new one of this seed is:
    embedding rows and every dense leaf from the seed, accumulators zero.
    The old leaves are dropped first, so that seeding never holds two
    copies."""
    import jax
    seed_table(model, cfg, ctx)
    model.params = model.state = None
    fresh = seeded_weights(ctx, cfg, shapes)
    model.params, model.buffers = fresh["params"], fresh["buffers"]
    model.state = model.fresh_state()
    jax.block_until_ready((model.params, model.state))


def first_steps(model, batches) -> dict:
    """The model through its first steps, by the window's own call."""
    losses, counts = [], []
    for tokens in batches[:CHECK_STEPS]:
        losses.append(model.step(tokens))
        counts.append(model.last_counts.copy())
    return {"losses": losses, "counts": counts}


def setup(ctx):
    import harness
    pack_batch = program().pack_batch
    t0 = time.perf_counter()
    model, cfg, shapes = build_model(ctx)
    c, t = sized(ctx)
    batches = packed_batches(ctx.seed, t, cfg.vocab_size)
    # one compiled shape for every step of the run
    model.min_rows = max(len(pack_batch(b, cfg.row_bucket)[0])
                         for b in batches)
    state = {"model": model, "cfg": cfg, "shapes": shapes,
             "batches": batches, "build_s": time.perf_counter() - t0}
    tw = time.perf_counter()
    state["first"] = first_steps(model, batches)
    state["warm_s"] = time.perf_counter() - tw
    state["peak_first_steps"] = harness.memory_peak_bytes()
    return state


# -- the reference, a layer at a time -----------------------------------------
def group_names(shapes: dict, key) -> list:
    return ["final_norm", "head"] if key == "top" \
        else list(shapes["layers"][key])


def group_of(leaves: dict, key):
    """A group's leaves as the reference takes them."""
    return (leaves["final_norm"], leaves["head"]) if key == "top" else leaves


def reference_run(ctx, state, emit, compute="float32", storage=None) -> dict:
    """The reference through the same first steps from the same seeded
    weights, streaming: ``emit(key, name, after, start)`` is handed every
    dense leaf (``key`` a layer's index or "top") as the second step makes it,
    on the device, and nothing of it is kept. Between the steps only the first
    step's gradients wait, on the host: W0 comes from the seed again, so W1
    and its accumulator are a function of them. On the device at any time:
    one layer's weights and gradients, a leaf's start and end, one layer's
    working set (the layers' inputs wait on the host too). Returns the losses,
    the assignment counts and the touched embedding rows at the start and
    after the steps."""
    import jax
    import jax.numpy as jnp
    pack_batch = program().pack_batch
    ref = ctx.reference
    c, t = sized(ctx)
    cfg, shapes = state["cfg"], state["shapes"]
    s = ref.sizes_of(c)
    rho = c["adagrad_step"]
    ssm = "cumulative" if t["seq_len"] > 1024 else "recurrence"
    buffers = make_buffers(ctx.seed, c, cfg.pattern, cfg.router_experts)

    def stored(x):
        return x if storage is None else \
            jnp.asarray(x).astype(storage).astype(jnp.float32)

    adagrad = jax.jit(lambda w, g2, g: ref.adagrad(w, g2, g, rho))

    def seeded_leaf(key, name):
        layer = None if key == "top" else key
        shape = shapes[name] if key == "top" else shapes["layers"][key][name]
        return stored(make_leaf(ctx.seed, c, layer, name, shape))

    first_grads = {}            # key -> {name: gradient}, on the host

    def after_one_step(key, name):
        """(W0, W1, G1) of one leaf."""
        w0 = seeded_leaf(key, name)
        w1, g2 = adagrad(w0, jnp.zeros_like(w0),
                         jnp.asarray(first_grads[key][name]))
        # waited for: launched ahead, a layer's leaves would all hold their
        # start, gradient and accumulator at once
        return jax.block_until_ready((w0, stored(w1), g2))

    batches = state["batches"][:CHECK_STEPS]
    ids_all = np.unique(np.concatenate([b.reshape(-1) for b in batches]))
    rows0 = seeded.rows_np(ctx.seed, EMB_STREAM, ids_all, cfg.hidden_size,
                           emb_scale(c))
    rows = np.asarray(stored(rows0)).copy()
    rows_g2 = np.zeros_like(rows)
    losses, counts = [], []
    for step, tokens in enumerate(batches):
        ids, _, where, targets, mask = pack_batch(tokens, 1)
        at = np.searchsorted(ids_all, ids)

        def current(key, step=step):
            return group_of({name: seeded_leaf(key, name) if step == 0
                             else after_one_step(key, name)[1]
                             for name in group_names(shapes, key)}, key)

        def on_grad(key, grads, step=step):
            names = group_names(shapes, key)
            grads = dict(zip(names, grads)) if key == "top" else grads
            if step == 0:
                first_grads[key] = {n: np.asarray(grads[n]) for n in names}
                return
            for name in names:
                w0, w1, g2 = after_one_step(key, name)
                emit(key, name, stored(adagrad(w1, g2, grads[name])[0]), w0)

        loss, cnt, grows = ref.grads_by_layer(
            current, lambda: current("top"), jnp.asarray(rows[at]), buffers,
            where, targets, mask, s, cfg.held, on_grad, ssm=ssm,
            compute=compute, inputs_on_host=True)
        new_rows, new_g2 = ref.adagrad(rows[at], rows_g2[at],
                                       np.asarray(grows), rho)
        rows[at], rows_g2[at] = np.asarray(stored(new_rows)), \
            np.asarray(new_g2)
        losses.append(float(loss))
        counts.append(np.asarray(cnt))
    return {"losses": losses, "counts": counts, "rows0": rows0, "rows": rows,
            "ids": ids_all}


class LeafGaps:
    """Per leaf (error norm, norm of the reference's own change), computed
    where the leaves are."""

    def __init__(self, candidate):
        import jax
        import jax.numpy as jnp
        self.candidate = candidate      # (key, name) -> the leaf to judge
        self.norms = {}
        self._norms = jax.jit(lambda a, b, c: (
            jnp.sqrt(jnp.sum(jnp.square(a - b))),
            jnp.sqrt(jnp.sum(jnp.square(b - c)))))

    def __call__(self, key, name, after, start):
        self.norms.setdefault(key, []).append(tuple(
            float(x) for x in self._norms(self.candidate(key, name), after,
                                          start)))


def program_leaves(state):
    params = state["model"].params
    return lambda key, name: params[name] if key == "top" \
        else params["layers"][key][name]


def gaps(got: dict, want: dict, leaf_norms: dict, got_rows,
         pattern: str) -> dict:
    """The numbers compared: each step's loss; the change of every dense leaf
    after the steps (error norm over the norm of the reference's own change
    of that leaf), the worst leaf of all and of each layer kind; the touched
    embedding rows likewise; the assignments per held expert, as the share
    that differs."""
    def norm(x):
        return float(np.linalg.norm(np.asarray(x, np.float64)))

    by_kind = {}
    for key, pairs in leaf_norms.items():
        kind = "top" if key == "top" else KIND_NAMES[pattern[key]]
        by_kind.setdefault(kind, []).extend(
            err / max(moved, 1e-30) for err, moved in pairs)
    out = {"step_loss_rel_gap": max(
        abs(g - w) / abs(w) for g, w in zip(got["losses"], want["losses"])),
        "dense_rel_gap": max(max(v) for v in by_kind.values())}
    for kind, values in by_kind.items():
        out[f"dense_rel_gap.{kind}"] = max(values)
    out["rows_rel_gap"] = norm(got_rows - want["rows"]) / max(
        norm(want["rows"] - want["rows0"]), 1e-30)
    out["expert_counts_rel_gap"] = max(
        float(np.abs(np.asarray(g) - np.asarray(w)).sum())
        / max(float(np.asarray(w).sum()), 1.0)
        for g, w in zip(got["counts"], want["counts"]))
    return out


def program_gaps(ctx, state, also=None) -> tuple:
    """(the program's first steps against the reference's, the reference's
    run)."""
    judge = LeafGaps(program_leaves(state))

    def emit(key, name, after, start):
        judge(key, name, after, start)
        if also is not None:
            also(key, name, after, start)

    want = reference_run(ctx, state, emit)
    rows = state["model"].pull_rows(want["ids"].astype(np.int32))
    return gaps(state["first"], want, judge.norms, rows,
                state["cfg"].pattern), want


def add_checks(ctx, values: dict) -> None:
    for name, value in values.items():
        ctx.checks.add(name, value, ctx.limit(name.split(".")[0]))


def check(state, ctx) -> None:
    """The model's state after its first two steps against the reference's
    forward, backward and AdaGrad on the same seeded weights and batches."""
    import harness
    add_checks(ctx, program_gaps(ctx, state)[0])
    state["peak_check"] = harness.memory_peak_bytes()


def limit_readings(make_ctx, seeds, control_seeds: int) -> list:
    """For ``tools/limits.py``: one model, re-seeded for every seed and driven
    through its first steps; the control is the reference computed and stored
    in bfloat16, put in the program's place (the sound reference's leaves wait
    on the host for it)."""
    pack_batch = program().pack_batch
    ctx0 = make_ctx(seeds[0])
    model, cfg, shapes = build_model(ctx0)
    _, t = sized(ctx0)
    drawn = {seed: packed_batches(seed, dict(t, batches=CHECK_STEPS),
                                  cfg.vocab_size) for seed in seeds}
    model.min_rows = max(len(pack_batch(b, cfg.row_bucket)[0])
                         for bs in drawn.values() for b in bs)
    out = []
    try:
        for i, seed in enumerate(seeds):
            ctx = make_ctx(seed)
            sized(ctx)
            reseed_model(model, cfg, shapes, ctx)
            state = {"model": model, "cfg": cfg, "shapes": shapes,
                     "batches": drawn[seed]}
            state["first"] = first_steps(model, drawn[seed])
            control = i < control_seeds
            kept = {}           # the sound reference's leaves, on the host

            def keep(key, name, after, start):
                kept[key, name] = (np.asarray(after), np.asarray(start))

            sound, want = program_gaps(ctx, state, keep if control else None)
            out.append({"seed": seed, "side": "sound", "gaps": sound})
            if control:
                judge = LeafGaps(None)

                def emit(key, name, after, start):
                    want_after, want_start = kept.pop((key, name))
                    judge.candidate = lambda key, name: after
                    judge(key, name, want_after, want_start)

                low = reference_run(ctx, state, emit, compute="bfloat16",
                                    storage="bfloat16")
                out.append({"seed": seed, "side": "control", "gaps": gaps(
                    low, want, judge.norms, low["rows"], cfg.pattern)})
    finally:
        close(None)
    return out


# -- the window ------------------------------------------------------------
def counter_totals(names) -> dict:
    from multiverso_tpu.telemetry.metrics import get_registry
    return {n: int(get_registry().counter(n).value) for n in names}


def measure(state, ctx) -> dict:
    import jax
    from harness import span_delta, span_totals
    model, batches, cfg = state["model"], state["batches"], state["cfg"]
    order = traffic_gen.rng_for(ctx.seed, 8).permutation(len(batches))
    layers = cfg.expert_layers()
    names = ["lm.tokens", "lm.rows_pulled"] + [
        f"lm.moe.{what}.l{i}" for i in layers
        for what in ("assignments_held", "max_expert_load")]
    spans0, counters0 = span_totals(SPANS), counter_totals(names)
    steps, losses = 0, []
    t0 = time.perf_counter()
    deadline = t0 + ctx.seconds
    while steps == 0 or time.perf_counter() < deadline:
        with jax.profiler.TraceAnnotation("bench.lm_step"):
            loss = model.step(batches[order[steps % len(batches)]])
        losses.append(loss)
        steps += 1
    elapsed = time.perf_counter() - t0
    state["window_losses"] = losses
    counted = {n: v - counters0[n] for n, v in counter_totals(names).items()}
    assigned = sum(counted[f"lm.moe.assignments_held.l{i}"] for i in layers)
    max_load = sum(counted[f"lm.moe.max_expert_load.l{i}"] for i in layers)
    tokens = batches[0].size
    counters = {
        "steps": steps, "elapsed_s": elapsed,
        "step_ms": 1e3 * elapsed / steps,
        "setup_compile_s": state["warm_s"], "build_s": state["build_s"],
        "lm_tokens": counted["lm.tokens"],
        "lm_rows_pulled": counted["lm.rows_pulled"],
        "lm_assignments_held": assigned,
        "lm_seq_len": batches[0].shape[1],
        "first_loss": losses[0], "last_loss": losses[-1],
        "check_loss_0": state["first"]["losses"][0],
        "check_loss_1": state["first"]["losses"][1],
        # the process's peak after the first two steps and after the check:
        # where the second is the first, the check stayed under the program's
        "peak_first_steps_gb": state["peak_first_steps"] / 1e9,
        "peak_check_gb": state.get("peak_check", 0) / 1e9}
    if assigned:
        counters["lm_expert_load_max_over_mean"] = \
            max_load / (assigned / len(cfg.held))
    return {
        "attempted": steps,
        "failed": int(np.sum(~np.isfinite(losses))),
        "metrics": {"train_samples_per_s": steps * tokens / elapsed},
        "spans": span_delta(spans0, span_totals(SPANS)),
        "counters": counters,
    }


def verify(state, ctx) -> None:
    losses = np.asarray(state["window_losses"])
    ctx.checks.add("window_steps_finite", int(np.isfinite(losses).sum()),
                   len(losses), "eq")
    ctx.checks.add("window_steps_counted", state["model"].steps,
                   len(losses) + CHECK_STEPS, "eq")


def close(state) -> None:
    import multiverso_tpu as mv
    mv.shutdown()
