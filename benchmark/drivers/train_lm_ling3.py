"""Driver ``train_lm_ling3``: a layer-typed LM whose layers are two residual
blocks (Kimi Delta Attention or, closing each group of layers, latent attention
with a plain rotary key; then a dense feed-forward or a group-limited
sigmoid-routed expert block with one shared expert) and an untied head, trained
through ``HybridLM.step`` on the PS plane as the other LM drivers train theirs:
the same entry point, table plane, hybrid step and window. Imported from them
as they stand: the traffic, the sizes of a rehearsal, the table's seeding, the
seeded selection bias, the window (``train_lm_dsv2.measure``), the per-leaf
comparison with its float32 floor (``train_lm_sala.LeafGaps``), ``verify`` and
``close``. Stated here: what this model's leaves are and how they are seeded,
the two steps of the reference a block at a time with the selection bias moved
between them, the numbers compared (KDA's leaves on a line of their own; the
selection bias after the steps), and the three left-out-mathematics controls
``tools/limits.py`` reads after the bfloat16 one.

Before anything is built the driver asks the program whether it knows the KDA
block: a program from before it reads this configuration's keys as a plain
latent-attention model, and is told to stop instead.
"""
from __future__ import annotations

import contextlib
import gc
import math
import os
import time

import numpy as np

import harness
import seeded

_BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
base = harness.load_module("drivers", "train_lm", _BENCH_DIR)
dsv2 = harness.load_module("drivers", "train_lm_dsv2", _BENCH_DIR)
sala = harness.load_module("drivers", "train_lm_sala", _BENCH_DIR)
sized, packed_batches = base.sized, base.packed_batches
verify, close, seed_table = base.verify, base.close, dsv2.seed_table
CHECK_STEPS = base.CHECK_STEPS

NORMS = ("norm", "o_norm", "kv_norm", "final_norm")
TAPS = ("conv_q", "conv_k", "conv_v")
#: Leaves that project back into the residual stream.
OUT_PROJECTIONS = ("wo", "ffn_down", "w_down", "s_down")
KIND_NAMES = {"K": "kda", "L": "mla", "D": "dense_ffn", "E": "experts"}
COUNTERS = ("lm.kda.chunks", "lm.kda.plane.xla", "lm.moe.group_limited")
LEFT_OUT = ("no_delta", "no_groups", "fixed_bias")


def program():
    """The program's package, if it knows the KDA block."""
    lm = base.program()
    if not hasattr(lm, "KDA"):
        raise harness.BenchError(
            "the program's hybrid_lm knows no Kimi Delta Attention block "
            "(layer_group_size) and no group-limited routing: it cannot run "
            "this configuration")
    return lm


# -- weights from the seed: the same leaves for the program and the reference -
def make_leaf(seed: int, c: dict, shapes: dict, block, name: str):
    """One dense leaf on the device: norms one; a convolution's taps uniform
    on +-0.5; a KDA block's ``A_log`` the log of a uniform draw in
    ``kda_A_init`` a head and its ``dt_bias`` the inverse softplus of a
    log-uniform draw in ``kda_dt_init`` a channel; matrices uniform of
    standard deviation ``init_std``, projections back into the stream over
    sqrt(2 x the PUBLISHED layers) (the scaled init of the whole model, two
    residual blocks a layer). A block's leaves take their streams in the order
    of their names."""
    import jax.numpy as jnp
    group = shapes if block is None else shapes["layers"][block]
    shape = group[name]
    if name in NORMS:
        return jnp.ones(shape, jnp.float32)
    stream = base.HEAD_STREAM if block is None else \
        base.LAYER_STREAM + 16 * block + sorted(group).index(name)
    if name in ("A_log", "dt_bias"):
        u = seeded.rows_np(seed, stream, [0], shape[0], 1.0, "positive")[0]
        lo, hi = c["kda_A_init"] if name == "A_log" else c["kda_dt_init"]
        if name == "A_log":
            return jnp.asarray(np.log(lo + (hi - lo) * u), jnp.float32)
        dt = np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
        return jnp.asarray(dt + np.log(-np.expm1(-dt)), jnp.float32)
    scale = 1.0 if name in TAPS else dsv2.matrix_scale(c)
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


def seeded_buffers(ctx, cfg) -> list:
    """The selection bias of each expert block: uniform on +-0.01."""
    c, _ = sized(ctx)
    return base.make_buffers(ctx.seed, c, cfg.pattern, cfg.router_experts)


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
                        buffers=seeded_buffers(ctx, cfg))
    seed_table(model, cfg, ctx)
    return model, cfg, shapes


def reseed_model(model, cfg, shapes, ctx) -> None:
    """A model that has stepped, back to what a new one of this seed is; the
    old leaves are dropped first, so that seeding never holds two copies."""
    import jax
    seed_table(model, cfg, ctx)
    model.params = model.state = None
    model.params = seeded_weights(ctx, shapes)
    model.buffers = seeded_buffers(ctx, cfg)
    model.state = model.fresh_state()
    jax.block_until_ready((model.params, model.state))


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
    state["first"] = base.first_steps(model, batches)
    state["warm_s"] = time.perf_counter() - tw
    state["peak_first_steps"] = harness.memory_peak_bytes()
    return state


# -- the reference, a block at a time -----------------------------------------
def reference_run(ctx, state, emit, compute="float32", storage=None) -> dict:
    """The reference through the same first steps from the same seeded weights,
    a block at a time (``reference.grads_by_layer``), streaming as
    ``train_lm.reference_run`` does: ``emit(key, name, after, start)`` is
    handed every dense leaf (``key`` a block's index or "top") as the second
    step makes it, on the device, and nothing of it is kept; between the steps
    only the first step's gradients wait, on the host. After each step the
    selection bias of every expert block moves by the step's own assignments
    to every expert. Returns the losses, the assignments per held expert, the
    bias after the steps and the touched embedding rows at the start and after
    the steps."""
    import jax
    import jax.numpy as jnp
    pack_batch = program().pack_batch
    ref = ctx.reference
    c, _ = sized(ctx)
    cfg, shapes = state["cfg"], state["shapes"]
    s, rho = ref.sizes_of(c), c["adagrad_step"]
    buffers = seeded_buffers(ctx, cfg)
    held = np.asarray(cfg.held)

    def stored(x):
        return x if storage is None else \
            jnp.asarray(x).astype(storage).astype(jnp.float32)

    adagrad = jax.jit(lambda w, g2, g: ref.adagrad(w, g2, g, rho))
    first_grads = {}            # key -> {name: gradient}, on the host

    def leaf_at(key, name, step):
        """(W0, W at ``step``, its accumulator) of one leaf, waited for:
        launched ahead, a block's leaves would all be held at once."""
        w0 = stored(make_leaf(ctx.seed, c, shapes,
                              None if key == "top" else key, name))
        if step == 0:
            return w0, w0, jnp.zeros_like(w0)
        w1, g2 = adagrad(w0, jnp.zeros_like(w0),
                         jnp.asarray(first_grads[key][name]))
        return jax.block_until_ready((w0, stored(w1), g2))

    batches = state["batches"][:CHECK_STEPS]
    ids_all = np.unique(np.concatenate([b.reshape(-1) for b in batches]))
    rows0 = seeded.rows_np(ctx.seed, base.EMB_STREAM, ids_all,
                           cfg.hidden_size, dsv2.emb_scale(c))
    rows = np.asarray(stored(rows0)).copy()
    rows_g2 = np.zeros_like(rows)
    out = {"losses": [], "counts": []}
    for step, tokens in enumerate(batches):
        ids, _, where, targets, mask = pack_batch(tokens, 1)
        at = np.searchsorted(ids_all, ids)

        def current(key, step=step):
            return base.group_of(
                {name: leaf_at(key, name, step)[1]
                 for name in base.group_names(shapes, key)}, key)

        def on_grad(key, grads, step=step):
            names = base.group_names(shapes, key)
            grads = dict(zip(names, grads)) if key == "top" else grads
            if step == 0:
                first_grads[key] = {n: np.asarray(grads[n]) for n in names}
                return
            for name in names:
                w0, w1, g2 = leaf_at(key, name, step)
                emit(key, name, stored(adagrad(w1, g2, grads[name])[0]), w0)

        loss, cnt, grows = ref.grads_by_layer(
            current, lambda: current("top"), jnp.asarray(rows[at]), buffers,
            where, targets, mask, s, cfg.held, on_grad, compute=compute,
            inputs_on_host=True)
        new_rows, new_g2 = ref.adagrad(rows[at], rows_g2[at],
                                       np.asarray(grows), rho)
        rows[at], rows_g2[at] = np.asarray(stored(new_rows)), \
            np.asarray(new_g2)
        out["losses"].append(float(loss))
        cnt = np.asarray(cnt)           # [expert blocks, every expert]
        out["counts"].append(cnt[:, held])
        for block, per_expert in zip(cfg.expert_layers(), cnt):
            buffers[block] = ref.updated_bias(
                buffers[block], per_expert, c["expert_bias_update_rate"])
    bias = np.stack([np.asarray(buffers[b]) for b in cfg.expert_layers()])
    return dict(out, rows0=rows0, rows=rows, ids=ids_all, bias=bias)


def gaps(got: dict, want: dict, leaf_norms: dict, got_rows, got_bias,
         pattern: str, rate: float) -> dict:
    """The numbers compared: each step's loss; the change of every dense leaf
    after the steps (error norm over the norm of the reference's own change of
    that leaf), the worst leaf of all and of each block kind; the touched
    embedding rows likewise; the assignments per held expert, as the share
    that differs; the selection bias after the steps, as the share of (expert
    block, expert) entries that stand more than half a ``rate`` from the
    reference's (an expert whose load is within a few assignments of the mean
    may move the other way on the two sides)."""
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
    out["expert_bias_mismatch_share"] = float(np.mean(
        np.abs(np.asarray(got_bias) - want["bias"]) > rate / 2))
    return out


def program_gaps(ctx, state, also=None) -> tuple:
    """(the program's first steps against the reference's, the reference's
    run)."""
    judge = sala.LeafGaps(base.program_leaves(state))

    def emit(key, name, after, start):
        judge(key, name, after, start)
        if also is not None:
            also(key, name, after, start)

    want = reference_run(ctx, state, emit)
    model, cfg = state["model"], state["cfg"]
    rows = model.pull_rows(want["ids"].astype(np.int32))
    bias = np.stack([np.asarray(model.buffers[b])
                     for b in cfg.expert_layers()])
    return gaps(state["first"], want, judge.norms, rows, bias, cfg.pattern,
                cfg.expert_bias_update_rate), want


def check(state, ctx) -> None:
    """The model's state after its first two steps against the reference's
    forward, backward and AdaGrad on the same seeded weights and batches."""
    base.add_checks(ctx, program_gaps(ctx, state)[0])
    state["peak_check"] = harness.memory_peak_bytes()


# -- the controls -------------------------------------------------------------
@contextlib.contextmanager
def left_out(what: str):
    """The program with part of the mathematics left out, for the time a model
    is built and traced under it: ``no_delta`` takes the correction by the key
    out of the KDA state (``S_t = Diag(a_t) S_{t-1} + b_t k_t v_t^T``: inside a
    chunk ``U = Diag(b) V``, nothing of the starting state taken back);
    ``no_groups`` chooses the experts among all groups; ``fixed_bias`` leaves
    the selection bias as seeded."""
    import jax.numpy as jnp
    from multiverso_tpu.models.hybrid_lm import kda, model
    from multiverso_tpu.parallel import expert

    if what == "no_delta":
        holder, name = vars(kda), "_inside_chunks"
        whole = holder[name]

        def swap(q, k, v, g, beta, sub):
            _, w, *rest = whole(q, k, v, g, beta, sub)
            return (beta[..., None] * v, jnp.zeros_like(w), *rest)
    elif what == "no_groups":
        holder, name = vars(expert), "kept_groups"
        whole = holder[name]
        swap = lambda biased, n_group, topk_group: biased  # noqa: E731
    else:
        holder, name = vars(model), "updated_expert_bias"
        whole = holder[name]
        swap = lambda bias, counts, rate: bias  # noqa: E731
    holder[name] = swap
    try:
        yield
    finally:
        holder[name] = whole


def limit_readings(make_ctx, seeds, control_seeds: int) -> list:
    """For ``tools/limits.py``: one model, re-seeded for every seed and driven
    through its first steps, against the reference; for the first
    ``control_seeds`` the reference computed and stored in bfloat16 (router,
    softmax, logits, KDA's decay and state float32) put in the program's place
    (the sound reference's leaves wait on the host for it); then, on the first
    seed, the program built with each part of the mathematics left out
    (:func:`left_out`): the limits must lie under what these read."""
    pack_batch = program().pack_batch
    ctx0 = make_ctx(seeds[0])
    model, cfg, shapes = build_model(ctx0)
    _, t = sized(ctx0)
    drawn = {seed: packed_batches(seed, dict(t, batches=CHECK_STEPS),
                                  cfg.vocab_size) for seed in seeds}
    min_rows = model.min_rows = max(
        len(pack_batch(b, cfg.row_bucket)[0])
        for bs in drawn.values() for b in bs)
    out = []
    try:
        for i, seed in enumerate(seeds):
            ctx = make_ctx(seed)
            sized(ctx)
            reseed_model(model, cfg, shapes, ctx)
            state = {"model": model, "cfg": cfg, "shapes": shapes,
                     "batches": drawn[seed]}
            state["first"] = base.first_steps(model, drawn[seed])
            control = i < control_seeds
            kept = {}           # the sound reference's leaves, on the host

            def keep(key, name, after, start):
                kept[key, name] = (np.asarray(after), np.asarray(start))

            sound, want = program_gaps(ctx, state, keep if control else None)
            out.append({"seed": seed, "side": "sound", "gaps": sound})
            if control:
                judge = sala.LeafGaps(None)

                def emit(key, name, after, start):
                    want_after, want_start = kept.pop((key, name))
                    judge.candidate = lambda key, name: after
                    judge(key, name, want_after, want_start)

                low = reference_run(ctx, state, emit, compute="bfloat16",
                                    storage="bfloat16")
                out.append({"seed": seed, "side": "control", "gaps": gaps(
                    low, want, judge.norms, low["rows"], low["bias"],
                    cfg.pattern, cfg.expert_bias_update_rate)})
        del model, state
    finally:
        close(None)
    ctx = make_ctx(seeds[0])
    for what in LEFT_OUT:
        gc.collect()    # a model and its step hold each other: 10 GB of leaves
        with left_out(what):
            model, cfg, shapes = build_model(ctx)
            model.min_rows = min_rows
            state = {"model": model, "cfg": cfg, "shapes": shapes,
                     "batches": drawn[seeds[0]]}
            try:
                state["first"] = base.first_steps(model, drawn[seeds[0]])
                out.append({"seed": ctx.seed, "side": what,
                            "gaps": program_gaps(ctx, state)[0]})
            finally:
                close(None)
        del model, state
    return out


# -- the window ------------------------------------------------------------
def measure(state, ctx) -> dict:
    before = base.counter_totals(COUNTERS)
    out = dsv2.measure(state, ctx)
    counters = out["counters"]
    counters.update({n.replace(".", "_"): v - before[n]
                     for n, v in base.counter_totals(COUNTERS).items()})
    counters.pop("lm_balance_loss", None)   # this model weighs none
    # a step's phases beside its rate, as ``sala_train`` prints them
    counters.update({"span_" + name.replace(".", "_") + "_ms": ms / n
                     for name, (n, ms) in out["spans"].items() if n})
    return out
