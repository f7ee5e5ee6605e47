"""Driver ``train_lm_dsv2``: a layer-typed LM whose layers are two residual
blocks (latent attention, then a dense or an expert feed-forward, the expert
blocks with a balance loss) trained through ``HybridLM.step`` on the PS plane,
as ``train_lm`` trains the one-mixer-a-layer kind: the same entry point, table
plane, hybrid step and window; what is generic of that driver is imported from
it. What differs is what the model's blocks are called: the leaves and how they
are seeded, no router bias, a reference without a state-space mode, the block
kinds the worst leaf is printed for, a balance term beside each loss, and the
query-key pairs counted for the FLOP model.

Set-up builds ONE model, seeds its embedding table and every dense leaf on the
device from the seed, draws the batches the window cycles, and drives the model
through its first two steps, which the plain reference follows on the chip, a
block at a time, under the program's own peak (``train_lm``'s docstring says
how). A run without a TPU is a rehearsal at the files' ``tiny`` sizes.
"""
from __future__ import annotations

import math
import os
import time

import numpy as np

import harness
import seeded
import traffic_gen

base = harness.load_module("drivers", "train_lm", os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
sized, packed_batches, program = base.sized, base.packed_batches, base.program
verify, close = base.verify, base.close
SPANS, CHECK_STEPS = base.SPANS, base.CHECK_STEPS
EMB_STREAM, HEAD_STREAM, LAYER_STREAM = (base.EMB_STREAM, base.HEAD_STREAM,
                                         base.LAYER_STREAM)

NORMS = ("norm", "kv_norm", "final_norm")
#: Leaves that project back into the residual stream.
OUT_PROJECTIONS = ("wo", "ffn_down", "w_down", "s_down")
KIND_NAMES = {"L": "mla", "D": "dense_ffn", "E": "experts"}
BALANCE_GAUGE = "lm.moe.balance_loss"


# -- weights from the seed: the same leaves for the program and the reference -
def matrix_scale(c: dict) -> float:
    """Width of the uniform draw whose standard deviation is ``init_std``."""
    return c["init_std"] * math.sqrt(12.0)


def emb_scale(c: dict) -> float:
    """The embedding rows' own: ``embedding_init_std`` where the file gives
    one."""
    return c.get("embedding_init_std", c["init_std"]) * math.sqrt(12.0)


def seed_table(model, cfg, ctx) -> None:
    import jax
    import jax.numpy as jnp
    c, _ = sized(ctx)
    store = model.table.store
    jax.block_until_ready(store.state)
    seeded.reseed_store(store, ctx.seed, EMB_STREAM, emb_scale(c), "centered",
                        cfg.vocab_size)
    store.state = {k: jnp.zeros_like(v) for k, v in store.state.items()}


def make_leaf(seed: int, c: dict, shapes: dict, layer, name: str):
    """One dense leaf on the device: norms one; matrices uniform of standard
    deviation ``init_std``, projections back into the stream over sqrt(2 x
    the PUBLISHED layers) (the scaled init of the whole model, two residual
    blocks a layer: this chip runs some of its layers). A block's leaves take
    their streams in the order of their names."""
    import jax.numpy as jnp
    group = shapes if layer is None else shapes["layers"][layer]
    shape = group[name]
    if name in NORMS:
        return jnp.ones(shape, jnp.float32)
    stream = HEAD_STREAM if layer is None else \
        LAYER_STREAM + 16 * layer + sorted(group).index(name)
    scale = matrix_scale(c)
    if name in OUT_PROJECTIONS:
        scale /= math.sqrt(2 * c["published"]["num_hidden_layers"])
    rows = int(np.prod(shape[:-1]))
    return seeded.table_jax(seed, stream, (rows, shape[-1]), scale
                            ).reshape(shape)


def seeded_weights(ctx, shapes) -> dict:
    """Every dense leaf from the seed, made on the device."""
    c, _ = sized(ctx)
    return {"layers": [{name: make_leaf(ctx.seed, c, shapes, i, name)
                        for name in block}
                       for i, block in enumerate(shapes["layers"])],
            "final_norm": make_leaf(ctx.seed, c, shapes, None, "final_norm"),
            "head": make_leaf(ctx.seed, c, shapes, None, "head")}


# -- the system under test ----------------------------------------------------
def build_model(ctx):
    import multiverso_tpu as mv
    lm = program()
    c, _ = sized(ctx)
    try:
        cfg = lm.HybridLMConfig.from_dict(c, seed=ctx.seed % (2 ** 31 - 1))
    except (KeyError, TypeError, ValueError) as e:
        raise harness.BenchError(
            f"the program's HybridLMConfig cannot read this configuration "
            f"({type(e).__name__}: {e}): it cannot run it") from e
    mv.init([])
    shapes = lm.param_shapes(cfg)
    model = lm.HybridLM(cfg, mode=c["mode"],
                        params=seeded_weights(ctx, shapes),
                        buffers=[None] * len(cfg.pattern))
    seed_table(model, cfg, ctx)
    return model, cfg, shapes


def reseed_model(model, cfg, shapes, ctx) -> None:
    """A model that has stepped, back to what a new one of this seed is; the
    old leaves are dropped first, so that seeding never holds two copies."""
    import jax
    seed_table(model, cfg, ctx)
    model.params = model.state = None
    model.params = seeded_weights(ctx, shapes)
    model.state = model.fresh_state()
    jax.block_until_ready((model.params, model.state))


def balance_now() -> float:
    from multiverso_tpu.telemetry.metrics import get_registry
    return float(get_registry().gauge(BALANCE_GAUGE).last)


def first_steps(model, batches) -> dict:
    """The model through its first steps, by the window's own call."""
    out = {"losses": [], "counts": [], "balance": []}
    for tokens in batches[:CHECK_STEPS]:
        out["losses"].append(model.step(tokens))
        out["counts"].append(model.last_counts.copy())
        out["balance"].append(balance_now())
    return out


def setup(ctx):
    pack_batch = program().pack_batch
    t0 = time.perf_counter()
    model, cfg, shapes = build_model(ctx)
    _, t = sized(ctx)
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


# -- the reference, a block at a time -----------------------------------------
def reference_run(ctx, state, emit, compute="float32", storage=None) -> dict:
    """The reference through the same first steps from the same seeded
    weights, streaming as ``train_lm.reference_run`` does: ``emit(key, name,
    after, start)`` is handed every dense leaf (``key`` a block's index or
    "top") as the second step makes it, on the device, and nothing of it is
    kept; between the steps only the first step's gradients wait, on the host.
    Returns the losses (balance term included), the balance terms, the
    assignment counts and the touched embedding rows at the start and after
    the steps."""
    import jax
    import jax.numpy as jnp
    pack_batch = program().pack_batch
    ref = ctx.reference
    c, _ = sized(ctx)
    cfg, shapes = state["cfg"], state["shapes"]
    s = ref.sizes_of(c)
    rho = c["adagrad_step"]

    def stored(x):
        return x if storage is None else \
            jnp.asarray(x).astype(storage).astype(jnp.float32)

    adagrad = jax.jit(lambda w, g2, g: ref.adagrad(w, g2, g, rho))

    def seeded_leaf(key, name):
        return stored(make_leaf(ctx.seed, c, shapes,
                                None if key == "top" else key, name))

    first_grads = {}            # key -> {name: gradient}, on the host

    def after_one_step(key, name):
        """(W0, W1, G1) of one leaf."""
        w0 = seeded_leaf(key, name)
        w1, g2 = adagrad(w0, jnp.zeros_like(w0),
                         jnp.asarray(first_grads[key][name]))
        # waited for: launched ahead, a block's leaves would all hold their
        # start, gradient and accumulator at once
        return jax.block_until_ready((w0, stored(w1), g2))

    batches = state["batches"][:CHECK_STEPS]
    ids_all = np.unique(np.concatenate([b.reshape(-1) for b in batches]))
    rows0 = seeded.rows_np(ctx.seed, EMB_STREAM, ids_all, cfg.hidden_size,
                           emb_scale(c))
    rows = np.asarray(stored(rows0)).copy()
    rows_g2 = np.zeros_like(rows)
    out = {"losses": [], "balance": [], "counts": []}
    for step, tokens in enumerate(batches):
        ids, _, where, targets, mask = pack_batch(tokens, 1)
        at = np.searchsorted(ids_all, ids)

        def current(key, step=step):
            return base.group_of(
                {name: seeded_leaf(key, name) if step == 0
                 else after_one_step(key, name)[1]
                 for name in base.group_names(shapes, key)}, key)

        def on_grad(key, grads, step=step):
            names = base.group_names(shapes, key)
            grads = dict(zip(names, grads)) if key == "top" else grads
            if step == 0:
                first_grads[key] = {n: np.asarray(grads[n]) for n in names}
                return
            for name in names:
                w0, w1, g2 = after_one_step(key, name)
                emit(key, name, stored(adagrad(w1, g2, grads[name])[0]), w0)

        loss, cnt, aux, grows = ref.grads_by_layer(
            current, lambda: current("top"), jnp.asarray(rows[at]), where,
            targets, mask, s, cfg.held, on_grad, compute=compute,
            inputs_on_host=True)
        new_rows, new_g2 = ref.adagrad(rows[at], rows_g2[at],
                                       np.asarray(grows), rho)
        rows[at], rows_g2[at] = np.asarray(stored(new_rows)), \
            np.asarray(new_g2)
        out["losses"].append(float(loss))
        out["balance"].append(float(aux))
        out["counts"].append(np.asarray(cnt))
    return dict(out, rows0=rows0, rows=rows, ids=ids_all)


def gaps(got: dict, want: dict, leaf_norms: dict, got_rows,
         pattern: str) -> dict:
    """The numbers compared: each step's loss (balance term included) and its
    balance term alone; the change of every dense leaf after the steps (error
    norm over the norm of the reference's own change of that leaf), the worst
    leaf of all and of each block kind; the touched embedding rows likewise;
    the assignments per held expert, as the share that differs."""
    def norm(x):
        return float(np.linalg.norm(np.asarray(x, np.float64)))

    def worst_rel(name):
        return max(abs(g - w) / abs(w)
                   for g, w in zip(got[name], want[name]))

    by_kind = {}
    for key, pairs in leaf_norms.items():
        kind = "top" if key == "top" else KIND_NAMES[pattern[key]]
        by_kind.setdefault(kind, []).extend(
            err / max(moved, 1e-30) for err, moved in pairs)
    out = {"step_loss_rel_gap": worst_rel("losses"),
           "balance_rel_gap": worst_rel("balance"),
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
    judge = base.LeafGaps(base.program_leaves(state))

    def emit(key, name, after, start):
        judge(key, name, after, start)
        if also is not None:
            also(key, name, after, start)

    want = reference_run(ctx, state, emit)
    rows = state["model"].pull_rows(want["ids"].astype(np.int32))
    return gaps(state["first"], want, judge.norms, rows,
                state["cfg"].pattern), want


def check(state, ctx) -> None:
    """The model's state after its first two steps against the reference's
    forward, backward and AdaGrad on the same seeded weights and batches."""
    base.add_checks(ctx, program_gaps(ctx, state)[0])
    state["peak_check"] = harness.memory_peak_bytes()


def limit_readings(make_ctx, seeds, control_seeds: int) -> list:
    """For ``tools/limits.py``: one model, re-seeded for every seed and driven
    through its first steps; the control is the reference computed and stored
    in bfloat16 (router and softmaxes float32, as the published code keeps
    them), put in the program's place (the sound reference's leaves wait on
    the host for it)."""
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
                judge = base.LeafGaps(None)

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
def measure(state, ctx) -> dict:
    import jax
    from harness import span_delta, span_totals
    model, batches, cfg = state["model"], state["batches"], state["cfg"]
    order = traffic_gen.rng_for(ctx.seed, 8).permutation(len(batches))
    layers = cfg.expert_layers()
    names = ["lm.tokens", "lm.rows_pulled", "lm.attn.pairs"] + [
        f"lm.moe.{what}.l{i}" for i in layers
        for what in ("assignments_held", "max_expert_load")]
    spans0, counters0 = span_totals(SPANS), base.counter_totals(names)
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
    counted = {n: v - counters0[n]
               for n, v in base.counter_totals(names).items()}
    assigned = sum(counted[f"lm.moe.assignments_held.l{i}"] for i in layers)
    max_load = sum(counted[f"lm.moe.max_expert_load.l{i}"] for i in layers)
    tokens = batches[0].size
    counters = {
        "steps": steps, "elapsed_s": elapsed,
        "step_ms": 1e3 * elapsed / steps,
        "setup_compile_s": state["warm_s"], "build_s": state["build_s"],
        "lm_tokens": counted["lm.tokens"],
        "lm_rows_pulled": counted["lm.rows_pulled"],
        "lm_attn_pairs": counted["lm.attn.pairs"],
        "lm_assignments_held": assigned,
        "lm_seq_len": batches[0].shape[1],
        "lm_balance_loss": balance_now(),
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
