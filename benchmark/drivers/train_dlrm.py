"""Driver ``train_dlrm``: DLRM pull-train-push through ``DLRMModel.step`` on
the PS plane (``mode='ps'``, ``comm_policy='ps'``).

Set-up builds ONE model, seeds its embedding tables on the device and its
dense parameters from the seed, draws the batches the window cycles, and
drives the model through its first two steps, which the plain reference
follows. The window calls ``step`` until the clock passes ``--seconds``;
``step`` returns the loss as a host float, so every step is complete when it
returns, and the rate is whole steps over the time to the last return.
"""
from __future__ import annotations

import time

import numpy as np

import seeded
import traffic_gen

SPANS = ("recsys.pull", "recsys.compute", "recsys.push")
CHECK_STEPS = 2
DENSE_STREAM = 100      # streams 0..fields-1 are the embedding tables


def dense_params_np(ctx, dims) -> list:
    """He-scaled uniform weights from the seed, zero biases."""
    out = []
    for i, (fan_in, fan_out) in enumerate(dims):
        bound = float(np.sqrt(6.0 / max(1, fan_in)))
        W = seeded.rows_np(ctx.seed, DENSE_STREAM + i, np.arange(fan_in),
                           fan_out, 2.0 * bound)
        out.append((W, np.zeros(fan_out, np.float32)))
    return out


def build_model(ctx):
    import multiverso_tpu as mv
    from multiverso_tpu.models.dlrm import DLRMConfig, DLRMModel
    c = ctx.config
    mv.init([])
    cfg = DLRMConfig(
        fields=c["fields"], vocab=c["rows_per_table"],
        embed_dim=c["embed_dim"], dense_dim=c["dense_dim"],
        bottom_mlp=tuple(c["bottom_mlp"]), top_mlp=tuple(c["top_mlp"]),
        learning_rate=c["learning_rate"], adagrad_step=c["adagrad_step"],
        seed=ctx.seed % (2 ** 31 - 1), comm_policy=c["comm_policy"])
    model = DLRMModel(cfg, mode=c["mode"])
    seed_model(model, cfg, ctx)
    return model, cfg


def seed_model(model, cfg, ctx) -> None:
    """Embedding rows (on the device, one jitted call a table) and dense
    parameters from the seed; a model that has stepped gets its accumulators
    back to zero, as a new one has them."""
    import jax
    import jax.numpy as jnp
    c = ctx.config
    for f, table in enumerate(model.tables):
        store = table.store
        # the accumulators come from the host too: wait for them, or their
        # transfers run on into the window
        jax.block_until_ready(store.state)
        seeded.reseed_store(store, ctx.seed, f, c["init_scale"], "centered",
                            c["rows_per_table"])
        if model.steps:
            store.state = {k: jnp.zeros_like(v)
                           for k, v in store.state.items()}
    model.dense_params = [(jnp.asarray(W), jnp.asarray(b))
                          for W, b in dense_params_np(ctx, cfg.layer_dims())]


def setup(ctx):
    c, t = ctx.config, ctx.traffic
    t0 = time.perf_counter()
    model, cfg = build_model(ctx)
    batches = traffic_gen.impression_batches(
        ctx.seed, t["batches"], t["batch"], c["fields"], c["rows_per_table"],
        c["dense_dim"], t["zipf"], t["drift_every"], t["drift_scale"],
        t["affinity_scale"], t["click_bias"])
    state = {"model": model, "cfg": cfg, "batches": batches,
             "build_s": time.perf_counter() - t0}
    # The first steps from the seed, through the window's own call.
    tw = time.perf_counter()
    state["first_losses"] = [model.step(*batches[i])[0]
                             for i in range(CHECK_STEPS)]
    state["warm_s"] = time.perf_counter() - tw
    return state


def program_state(state, ids_by_field) -> dict:
    model = state["model"]
    return {"params": [(np.asarray(W), np.asarray(b))
                       for W, b in model.dense_params],
            "rows": [np.asarray(model.tables[f].get_rows(
                np.asarray(ids, np.int32)))
                for f, ids in enumerate(ids_by_field)]}


def reference_run(ctx, state, **precision):
    """The reference through the same first steps; returns (losses, state at
    the start, state after the steps, touched ids per field)."""
    ref, c = ctx.reference, ctx.config
    batches = state["batches"][:CHECK_STEPS]
    ids_by_field = [np.unique(np.concatenate([b[0][:, f] for b in batches]))
                    for f in range(c["fields"])]
    rows = [seeded.rows_np(ctx.seed, f, ids, c["embed_dim"], c["init_scale"])
            for f, ids in enumerate(ids_by_field)]
    dims = ref.layer_dims(c["dense_dim"], c["bottom_mlp"], c["embed_dim"],
                          c["fields"], c["top_mlp"])
    params = dense_params_np(ctx, dims)
    m = ref.Model(params, ids_by_field, rows, len(c["bottom_mlp"]) + 1,
                  c["learning_rate"], c["adagrad_step"], **precision)
    start = {"params": [(W.copy(), b.copy()) for W, b in m.params],
             "rows": [r.copy() for r in m.rows]}
    losses = [m.step(*b) for b in batches]
    return losses, start, {"params": m.params, "rows": m.rows}, ids_by_field


def gaps(got_losses, got, want_losses, start, want) -> dict:
    """The numbers compared: each step's loss; dense parameters and touched
    rows after the steps, by the worst leaf, against the size of the
    reference's own change of that leaf (or of the median leaf, where that
    is larger: some leaves hardly move)."""
    def norm(x):
        return float(np.linalg.norm(np.asarray(x, np.float64)))

    def worst(got_leaves, want_leaves, start_leaves):
        moved = [norm(w - s) for w, s in zip(want_leaves, start_leaves)]
        floor = float(np.median(moved))
        return max(norm(g - w) / max(m, floor)
                   for g, w, m in zip(got_leaves, want_leaves, moved))

    flat = lambda params: [leaf for Wb in params for leaf in Wb]  # noqa: E731
    return {
        "step_loss_rel_gap": max(abs(g - w) / abs(w)
                                 for g, w in zip(got_losses, want_losses)),
        "dense_rel_gap": worst(flat(got["params"]), flat(want["params"]),
                               flat(start["params"])),
        "rows_rel_gap": worst(got["rows"], want["rows"], start["rows"]),
    }


def check(state, ctx) -> None:
    """The model's state after its first two steps against the reference's
    forward, backward, dense SGD and row AdaGrad with duplicate ids summed."""
    want_losses, start, want, ids = reference_run(ctx, state)
    got = program_state(state, ids)
    for name, value in gaps(state["first_losses"], got, want_losses, start,
                            want).items():
        ctx.checks.add(name, value, ctx.limit(name))


def limit_readings(make_ctx, seeds, control_seeds: int) -> list:
    """For ``tools/limits.py``: one model, re-seeded for every seed and driven
    through its first steps; the control is the reference computed and stored
    in bfloat16, put in the program's place."""
    ctx0 = make_ctx(seeds[0])
    c, t = ctx0.config, ctx0.traffic
    model, cfg = build_model(ctx0)
    out = []
    try:
        for i, seed in enumerate(seeds):
            ctx = make_ctx(seed)
            seed_model(model, cfg, ctx)
            batches = traffic_gen.impression_batches(
                seed, CHECK_STEPS, t["batch"], c["fields"],
                c["rows_per_table"], c["dense_dim"], t["zipf"],
                t["drift_every"], t["drift_scale"], t["affinity_scale"],
                t["click_bias"])
            state = {"model": model, "batches": batches}
            losses = [model.step(*b)[0] for b in batches]
            want_losses, start, want, ids = reference_run(ctx, state)
            out.append({"seed": seed, "side": "sound", "gaps": gaps(
                losses, program_state(state, ids), want_losses, start, want)})
            if i < control_seeds:
                low, _, low_state, _ = reference_run(
                    ctx, state, compute="bfloat16", storage="bfloat16")
                out.append({"seed": seed, "side": "control", "gaps": gaps(
                    low, low_state, want_losses, start, want)})
    finally:
        close(None)
    return out


def measure(state, ctx) -> dict:
    import jax
    from harness import span_delta, span_totals
    model, batches = state["model"], state["batches"]
    order = traffic_gen.rng_for(ctx.seed, 6).permutation(len(batches))
    spans0 = span_totals(SPANS)
    steps, losses = 0, []
    t0 = time.perf_counter()
    deadline = t0 + ctx.seconds
    while steps == 0 or time.perf_counter() < deadline:
        with jax.profiler.TraceAnnotation("bench.dlrm_step"):
            loss, _ = model.step(*batches[order[steps % len(batches)]])
        losses.append(loss)
        steps += 1
    elapsed = time.perf_counter() - t0
    state["window_losses"] = losses
    batch = len(batches[0][2])
    return {
        "attempted": steps,
        "failed": int(np.sum(~np.isfinite(losses))),
        "metrics": {"train_samples_per_s": steps * batch / elapsed},
        "spans": span_delta(spans0, span_totals(SPANS)),
        "counters": {"steps": steps, "elapsed_s": elapsed,
                     "step_ms": 1e3 * elapsed / steps,
                     "setup_compile_s": state["warm_s"],
                     "build_s": state["build_s"]},
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
