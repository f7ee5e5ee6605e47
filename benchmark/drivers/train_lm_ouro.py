"""Driver ``train_lm_ouro``: a looped LM (ONE stack of two-block layers, rotary
attention then a SwiGLU with a norm on each mixer's input and output, run
``total_ut_steps`` times over the same leaves with the final norm inside the
loop; an exit gate weighing the passes' losses), trained through
``HybridLM.step`` on the PS plane as the other LM drivers train theirs: the
same entry point, table plane, hybrid step and window. Imported from them as
they stand: the traffic, the sizes of a rehearsal, the table's and the
matrices' seeding (``train_lm_dsv2``), the window (``train_lm_dsv2.measure``),
the per-leaf comparison with its float32 floor (``train_lm_sala.LeafGaps``),
``verify`` and ``close``. Stated here: what this model's leaves are and how
they are seeded, the two steps of the reference a block RUN at a time (a
block's gradient whole only after the backward has walked every pass), the
numbers compared (each pass's own loss and exit mass beside the step's; the
gate's two leaves as one unit and the post-norms named beside the worst leaf of
each block kind), and the seven left-out controls ``tools/limits.py`` reads after the
bfloat16 one.

Before anything is built the driver asks the program whether it knows the
loop: a program from before it would read this configuration's
``layer_types`` as another family's, and is told to stop instead.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
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

NORMS = ("norm", "post_norm", "final_norm")
GATE = ("exit_gate_w", "exit_gate_b")
#: The leaves beside the layers, in the order the reference takes them.
TOP = ("final_norm", "head") + GATE
KIND_NAMES = {"*": "attention", "D": "dense_ffn"}
COUNTERS = ("lm.loop.passes", "lm.loop.block_runs")
LEFT_OUT = ("one_pass", "no_post_norm", "norm_not_fed_back", "last_pass_loss",
            "no_entropy", "untied_passes_grad", "dropped_push")


def program():
    """The program's package, if it knows the looped stack."""
    lm = base.program()
    if not hasattr(lm, "looped_hidden"):
        raise harness.BenchError(
            "the program's hybrid_lm knows no looped stack (total_ut_steps, "
            "sandwich norms, exit gate): it cannot run this configuration")
    return lm


# -- weights from the seed: the same leaves for the program and the reference -
def make_leaf(seed: int, c: dict, shapes: dict, block, name: str):
    """One dense leaf on the device: norms one, the exit gate zero, every
    matrix as ``train_lm_dsv2`` seeds it (uniform of standard deviation
    ``init_std``, ``wo`` and ``ffn_down`` over sqrt(2 x the PUBLISHED
    layers))."""
    import jax.numpy as jnp
    group = shapes if block is None else shapes["layers"][block]
    if name in NORMS:
        return jnp.ones(group[name], jnp.float32)
    if name in GATE:
        return jnp.zeros(group[name], jnp.float32)
    return dsv2.make_leaf(seed, c, shapes, block, name)


def seeded_weights(ctx, shapes) -> dict:
    """Every dense leaf from the seed, made on the device."""
    c, _ = sized(ctx)
    return dict(
        {name: make_leaf(ctx.seed, c, shapes, None, name) for name in TOP},
        layers=[{name: make_leaf(ctx.seed, c, shapes, i, name)
                 for name in block}
                for i, block in enumerate(shapes["layers"])])


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


def first_steps(model, batches) -> dict:
    """The model through its first steps, by the window's own call."""
    out = {"losses": [], "pass_losses": [], "exit_mass": []}
    for tokens in batches[:CHECK_STEPS]:
        out["losses"].append(model.step(tokens))
        out["pass_losses"].append(np.array(model.last_pass_losses))
        out["exit_mass"].append(np.array(model.last_exit_mass))
    return out


def min_rows(batches, cfg) -> int:
    """One compiled shape for every step: the largest padded row count."""
    pack_batch = program().pack_batch
    return max(len(pack_batch(b, cfg.row_bucket)[0]) for b in batches)


def setup(ctx):
    t0 = time.perf_counter()
    model, cfg, shapes = build_model(ctx)
    _, t = sized(ctx)
    batches = packed_batches(ctx.seed, t, cfg.vocab_size)
    model.min_rows = min_rows(batches, cfg)
    state = {"model": model, "cfg": cfg, "shapes": shapes,
             "batches": batches, "build_s": time.perf_counter() - t0}
    tw = time.perf_counter()
    state["first"] = first_steps(model, batches)
    state["warm_s"] = time.perf_counter() - tw
    state["peak_first_steps"] = harness.memory_peak_bytes()
    return state


# -- the reference, a block run at a time -------------------------------------
def group_names(shapes: dict, key) -> list:
    return list(TOP) if key == "top" else list(shapes["layers"][key])


def reference_run(ctx, state, emit, compute="float32", storage=None) -> dict:
    """The reference through the same first steps from the same seeded weights,
    a block run at a time (``reference.grads_by_layer``): ``emit(key, name,
    after, start)`` is handed every dense leaf (``key`` a block's index or
    "top") as the second step makes it, on the device, and nothing of it is
    kept. W0 comes from the seed again, so between the steps only the first
    step's gradients wait, on the host, and W1 is made from them a leaf at a
    time, every time a pass asks for the block. Returns
    the losses, each pass's own loss and exit mass, and the touched embedding
    rows at the start and after the steps."""
    import jax
    import jax.numpy as jnp
    pack_batch = program().pack_batch
    ref = ctx.reference
    c, _ = sized(ctx)
    cfg, shapes = state["cfg"], state["shapes"]
    s, rho = ref.sizes_of(c), c["adagrad_step"]

    def stored(x):
        return x if storage is None else \
            jnp.asarray(x).astype(storage).astype(jnp.float32)

    first_grads = {}            # key -> {name: gradient}, on the host

    # W1 and W2 of a leaf straight from its seeded W0 and the steps' gradients
    # (the accumulator starts at zero): one program a leaf, so that beside the
    # program's resident leaves only W0, the gradients and the result are held.
    # The first gradient comes up from the host for this one use: its buffer
    # is given to the result.
    @functools.partial(jax.jit, donate_argnums=(1,))
    def after_one(w0, g1):
        return stored(ref.adagrad(w0, jnp.zeros_like(w0), g1, rho)[0])

    @functools.partial(jax.jit, donate_argnums=(1,))
    def after_two(w0, g1, g):
        w1, g2 = ref.adagrad(w0, jnp.zeros_like(w0), g1, rho)
        return stored(ref.adagrad(stored(w1), g2, g, rho)[0])

    def seeded_leaf(key, name):
        return stored(make_leaf(ctx.seed, c, shapes,
                                None if key == "top" else key, name))

    def leaf_at(key, name, step):
        """One leaf as the step reads it, waited for: launched ahead, a
        block's leaves would all hold their start and gradient at once."""
        w0 = seeded_leaf(key, name)
        if step == 0:
            return w0
        return jax.block_until_ready(
            after_one(w0, jnp.asarray(first_grads[key][name])))

    batches = state["batches"][:CHECK_STEPS]
    ids_all = np.unique(np.concatenate([b.reshape(-1) for b in batches]))
    rows0 = seeded.rows_np(ctx.seed, base.EMB_STREAM, ids_all,
                           cfg.hidden_size, dsv2.emb_scale(c))
    rows = np.asarray(stored(rows0)).copy()
    rows_g2 = np.zeros_like(rows)
    out = {"losses": [], "pass_losses": [], "exit_mass": []}
    for step, tokens in enumerate(batches):
        ids, _, where, targets, mask = pack_batch(tokens, 1)
        at = np.searchsorted(ids_all, ids)

        def current(key, step=step):
            leaves = {name: leaf_at(key, name, step)
                      for name in group_names(shapes, key)}
            return tuple(leaves[n] for n in TOP) if key == "top" else leaves

        def on_grad(key, grads, step=step):
            names = group_names(shapes, key)
            grads = dict(zip(names, grads)) if key == "top" else grads
            if step == 0:
                first_grads[key] = {n: np.asarray(grads[n]) for n in names}
                return
            for name in names:
                w0 = seeded_leaf(key, name)
                emit(key, name, jax.block_until_ready(after_two(
                    w0, jnp.asarray(first_grads[key][name]),
                    grads.pop(name))), w0)

        loss, passes, grows = ref.grads_by_layer(
            current, lambda: current("top"), jnp.asarray(rows[at]), where,
            targets, mask, s, on_grad, compute=compute, inputs_on_host=True)
        new_rows, new_g2 = ref.adagrad(rows[at], rows_g2[at],
                                       np.asarray(grows), rho)
        rows[at], rows_g2[at] = np.asarray(stored(new_rows)), \
            np.asarray(new_g2)
        out["losses"].append(float(loss))
        out["pass_losses"].append(np.asarray(passes["pass_loss"]))
        out["exit_mass"].append(np.asarray(passes["exit_mass"]))
    return dict(out, rows0=rows0, rows=rows, ids=ids_all)


def gaps(got: dict, want: dict, leaf_norms: dict, names: dict, got_rows,
         pattern: str) -> dict:
    """The numbers compared: each step's loss; each pass's own cross-entropy
    and mean exit mass (worst step and pass); the change of every dense leaf
    after the steps (error norm over the norm of the reference's own change of
    that leaf, ``train_lm_sala.LeafGaps``), the worst leaf of all, of each
    block kind and of the post-norms; the exit gate's weight and bias as the
    ONE linear unit they are (the norms taken over both: a bias is one number,
    and one number's change can be as small as two steps of opposite sign
    leave it); the touched embedding rows likewise. ``names[key]`` are
    ``leaf_norms[key]``'s leaves in order."""
    def norm(x):
        return float(np.linalg.norm(np.asarray(x, np.float64)))

    def worst_rel(name):
        g, w = (np.asarray(x[name], np.float64) for x in (got, want))
        return float(np.max(np.abs(g - w) / np.abs(w)))

    by_kind, gate = {}, []
    for key, pairs in leaf_norms.items():
        kind = "top" if key == "top" else KIND_NAMES[pattern[key]]
        for name, (err, moved) in zip(names[key], pairs):
            if name in GATE:
                gate.append((err, moved))
                continue
            value = err / max(moved, 1e-30)
            by_kind.setdefault(kind, []).append(value)
            if name == "post_norm":
                by_kind.setdefault("post_norm", []).append(value)
    out = {"step_loss_rel_gap": worst_rel("losses"),
           "pass_loss_rel_gap": worst_rel("pass_losses"),
           "exit_mass_rel_gap": worst_rel("exit_mass"),
           "exit_gate_rel_gap": norm([e for e, _ in gate]) / max(
               norm([m for _, m in gate]), 1e-30),
           "dense_rel_gap": max(max(v) for v in by_kind.values())}
    for kind, values in by_kind.items():
        out[f"dense_rel_gap.{kind}"] = max(values)
    out["rows_rel_gap"] = norm(got_rows - want["rows"]) / max(
        norm(want["rows"] - want["rows0"]), 1e-30)
    return out


def program_gaps(ctx, state, also=None) -> tuple:
    """(the program's first steps against the reference's, the reference's
    run)."""
    judge = sala.LeafGaps(base.program_leaves(state))
    names = {}

    def emit(key, name, after, start):
        judge(key, name, after, start)
        names.setdefault(key, []).append(name)
        if also is not None:
            also(key, name, after, start)

    want = reference_run(ctx, state, emit)
    rows = state["model"].pull_rows(want["ids"].astype(np.int32))
    return gaps(state["first"], want, judge.norms, names, rows,
                state["cfg"].pattern), want


def check(state, ctx) -> None:
    """The model's state after its first two steps against the reference's
    forward, backward and AdaGrad on the same seeded weights and batches."""
    base.add_checks(ctx, program_gaps(ctx, state)[0])
    state["peak_check"] = harness.memory_peak_bytes()


# -- the controls -------------------------------------------------------------
@contextlib.contextmanager
def left_out(what: str):
    """The program with part of the mathematics left out, for the time a model
    is built and traced under it: ``one_pass`` runs the stack once and hands
    that state to every pass's head and gate; ``no_post_norm`` leaves the norm
    of every mixer's output out; ``norm_not_fed_back`` feeds the next pass the
    state BEFORE the final norm; ``last_pass_loss`` takes the loss from the
    last pass alone; ``no_entropy`` drops the entropy term; ``untied_passes_
    grad`` lets only a leaf's LAST use reach its delta; ``dropped_push`` never
    pushes the rows' deltas."""
    import jax
    import jax.numpy as jnp
    from multiverso_tpu.models.hybrid_lm import model
    name = {"one_pass": "looped_hidden", "no_post_norm": "layer_forward",
            "norm_not_fed_back": "looped_hidden",
            "last_pass_loss": "exit_distribution",
            "no_entropy": "_make_looped_loss",
            "untied_passes_grad": "looped_hidden",
            "dropped_push": "_push_rows"}[what]
    holder = model.HybridLM if what == "dropped_push" else model
    whole = getattr(holder, name)

    def stack(params, buffers, v, cfg, remat):
        for i, kind in enumerate(cfg.pattern):
            v, _ = model.layer_forward(kind, params["layers"][i], buffers[i],
                                       v, cfg, remat)
        return v

    def final(params, v, cfg):
        return model.rmsnorm(v, params["final_norm"], cfg.norm_eps)

    def one_pass(params, buffers, u, cfg, remat=True, scan_interpret=None):
        h = whole(params, buffers, u,
                  dataclasses.replace(cfg, total_ut_steps=1), remat)
        return jnp.broadcast_to(h, (cfg.total_ut_steps,) + h.shape[1:])

    def not_fed_back(params, buffers, u, cfg, remat=True,
                     scan_interpret=None):
        hs = []
        for _ in range(cfg.total_ut_steps):
            u = stack(params, buffers, u, cfg, remat)
            hs.append(final(params, u, cfg))
        return jnp.stack(hs)

    def last_use_only(params, buffers, u, cfg, remat=True,
                      scan_interpret=None):
        held = jax.lax.stop_gradient(params)
        hs = []
        for t in range(cfg.total_ut_steps):
            p = params if t == cfg.total_ut_steps - 1 else held
            u = final(p, stack(p, buffers, u, cfg, remat), cfg)
            hs.append(u)
        return jnp.stack(hs)

    swap = {
        "one_pass": one_pass,
        "no_post_norm": lambda kind, p, bias, u, cfg, *rest, **more: whole(
            kind, p, bias, u, dataclasses.replace(cfg, post_norm=False),
            *rest, **more),
        "norm_not_fed_back": not_fed_back,
        "last_pass_loss": lambda g: jnp.zeros_like(g).at[-1].set(1.0),
        "no_entropy": lambda cfg, *rest: whole(
            dataclasses.replace(cfg, exit_entropy_weight=0.0), *rest),
        "untied_passes_grad": last_use_only,
        "dropped_push": lambda self, ids, delta: None}[what]
    setattr(holder, name, swap)
    try:
        yield
    finally:
        setattr(holder, name, whole)


def limit_readings(make_ctx, seeds, control_seeds: int) -> list:
    """For ``tools/limits.py``: one model, re-seeded for every seed and driven
    through its first steps, against the reference; for the first
    ``control_seeds`` the reference computed and stored in bfloat16 (gate,
    softmaxes and logits float32) put in the program's place (the sound
    reference's leaves wait on the host for it); then, on the first seed, the
    program built with each part of the mathematics left out
    (:func:`left_out`): the limits must lie under what these read."""
    ctx0 = make_ctx(seeds[0])
    model, cfg, shapes = build_model(ctx0)
    _, t = sized(ctx0)
    drawn = {seed: packed_batches(seed, dict(t, batches=CHECK_STEPS),
                                  cfg.vocab_size) for seed in seeds}
    rows = max(min_rows(bs, cfg) for bs in drawn.values())
    model.min_rows = rows
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
                judge, names = sala.LeafGaps(None), {}

                def emit(key, name, after, start):
                    want_after, want_start = kept.pop((key, name))
                    judge.candidate = lambda key, name: after
                    judge(key, name, want_after, want_start)
                    names.setdefault(key, []).append(name)

                low = reference_run(ctx, state, emit, compute="bfloat16",
                                    storage="bfloat16")
                out.append({"seed": seed, "side": "control", "gaps": gaps(
                    low, want, judge.norms, names, low["rows"],
                    cfg.pattern)})
        del model, state
    finally:
        close(None)
    ctx = make_ctx(seeds[0])
    for what in LEFT_OUT:
        gc.collect()    # a model and its step hold each other: 3 GB of leaves
        with left_out(what):
            model, cfg, shapes = build_model(ctx)
            model.min_rows = rows
            state = {"model": model, "cfg": cfg, "shapes": shapes,
                     "batches": drawn[seeds[0]]}
            try:
                state["first"] = first_steps(model, drawn[seeds[0]])
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
    for name in ("lm_balance_loss", "lm_assignments_held"):
        counters.pop(name, None)            # this model has no expert
    model = state["model"]
    for t in range(state["cfg"].total_ut_steps):
        counters[f"pass_loss_t{t + 1}"] = float(model.last_pass_losses[t])
        counters[f"exit_mass_t{t + 1}"] = float(model.last_exit_mass[t])
    # a step's phases beside its rate, as ``sala_train`` prints them
    counters.update({"span_" + name.replace(".", "_") + "_ms": ms / n
                     for name, (n, ms) in out["spans"].items() if n})
    return out
