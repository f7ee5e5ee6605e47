"""Driver ``train_lm_sala``: a layer-typed LM whose layers are two residual blocks
(a block-sparse or a Lightning linear attention mixer, then a dense gated
feed-forward) under muP's scalings, trained through ``HybridLM.step`` on the PS
plane as the other LM drivers train theirs: the same entry point, table plane,
hybrid step and window. Imported from them as they stand: the traffic, the
sizes of a rehearsal, the table's seeding, the window
(``train_lm_dsv2.measure``), ``verify`` and ``close``. Stated here: what this
model's leaves are and how they are seeded (every norm one, no out-projection
divided: the residual scale is the depth scaling), the two steps of the reference
a block at a time, the numbers compared (beside the losses, leaves and rows: the
share of (query, key-value head) rows whose attended key blocks differ from the
reference's), the window's six more counters, and the three
left-out-mathematics controls ``tools/limits.py`` reads after the bfloat16 one.

Before anything is built the driver asks the program whether it knows both kinds
of mixer: a program from before them would read this configuration as a plain
grouped-query model of 13.8 GB, and is told to stop instead.
"""
from __future__ import annotations

import contextlib
import gc
import os
import time

import numpy as np

import harness
import seeded

_BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
base = harness.load_module("drivers", "train_lm", _BENCH_DIR)
dsv2 = harness.load_module("drivers", "train_lm_dsv2", _BENCH_DIR)
sized, packed_batches = base.sized, base.packed_batches
verify, close, seed_table = base.verify, base.close, dsv2.seed_table
CHECK_STEPS = base.CHECK_STEPS

NORMS = ("norm", "q_norm", "k_norm", "o_norm", "final_norm")
KIND_NAMES = {"S": "sparse", "N": "lightning", "D": "dense_ffn"}
COUNTERS = ("lm.sparse.pairs", "lm.sparse.causal_pairs",
            "lm.sparse.select_pairs", "lm.lightning.chunks")
LEFT_OUT = ("no_selection", "no_decay", "no_gates")


def program():
    """The program's package, if it knows both kinds of mixer."""
    lm = base.program()
    if not (hasattr(lm, "SPARSE") and hasattr(lm, "LIGHTNING")):
        raise harness.BenchError(
            "the program's hybrid_lm knows no block-sparse and no Lightning "
            "mixer (mixer_types minicpm4, lightning-attn): it cannot run this "
            "configuration")
    return lm


# -- weights from the seed: the same leaves for the program and the reference -
def make_leaf(seed: int, c: dict, shapes: dict, block, name: str):
    """One dense leaf on the device: norms one; matrices uniform of standard
    deviation ``init_std``, none divided further. A block's leaves take their
    streams in the order of their names."""
    import jax.numpy as jnp
    group = shapes if block is None else shapes["layers"][block]
    shape = group[name]
    if name in NORMS:
        return jnp.ones(shape, jnp.float32)
    stream = base.HEAD_STREAM if block is None else \
        base.LAYER_STREAM + 16 * block + sorted(group).index(name)
    return seeded.table_jax(seed, stream, shape, dsv2.matrix_scale(c))


def seeded_weights(ctx, shapes) -> dict:
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
                        buffers=lm.init_buffers(cfg))
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
    """The model through its first steps, by the window's own call; what the
    sparse blocks' queries attended comes to the host."""
    out = {"losses": [], "attended": []}
    for tokens in batches[:CHECK_STEPS]:
        out["losses"].append(model.step(tokens))
        out["attended"].append([None if a is None else np.asarray(a)
                                for a in model.last_sparse_chosen])
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
    """The reference through the same first steps from the same seeded weights,
    a block at a time (``reference.grads_by_block``): ``emit(key, name, after,
    start)`` is handed every dense leaf (``key`` a block's index or "top") as
    the second step makes it, on the device, and nothing of it is kept. W0
    comes from the seed again, so between the steps only the first step's
    gradients wait, on the host, and W1 and its accumulator are made from
    them a leaf at a time. Returns the losses, what each sparse block's
    queries attended, and the touched embedding rows at the start and after
    the steps."""
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
    out = {"losses": [], "attended": []}
    for step, tokens in enumerate(batches):
        ids, _, where, targets, mask = pack_batch(tokens, 1)
        at = np.searchsorted(ids_all, ids)

        def current(key, step=step):
            return base.group_of({name: leaf_at(key, name, step)[1]
                                  for name in base.group_names(shapes, key)},
                                 key)

        def on_grad(key, grads, step=step):
            names = base.group_names(shapes, key)
            grads = dict(zip(names, grads)) if key == "top" else grads
            if step == 0:
                first_grads[key] = {n: np.asarray(grads[n]) for n in names}
                return
            for name in names:
                w0, w1, g2 = leaf_at(key, name, step)
                emit(key, name, stored(adagrad(w1, g2, grads[name])[0]), w0)

        loss, attended, grows = ref.grads_by_block(
            current, lambda: current("top"), jnp.asarray(rows[at]), where,
            targets, mask, s, on_grad, compute=compute, inputs_on_host=True)
        new_rows, new_g2 = ref.adagrad(rows[at], rows_g2[at],
                                       np.asarray(grows), rho)
        rows[at], rows_g2[at] = np.asarray(stored(new_rows)), \
            np.asarray(new_g2)
        out["losses"].append(float(loss))
        out["attended"].append(attended)
    return dict(out, rows0=rows0, rows=rows, ids=ids_all)


class LeafGaps:
    """Per leaf (error norm, norm of the reference's own change), computed
    where the leaves are, as ``train_lm.LeafGaps`` does, with one floor: a
    change under what float32 resolves of the leaf (its epsilon times the
    leaf's norm: a norm weight at 1.0 whose few-millionths gradient moves a
    tenth of its 128 elements by ONE unit in the last place) counts as that
    much, so that a unit in the last place of difference does not read as a
    third of the change. Such a leaf left unmoved reads under 1."""

    def __init__(self, candidate):
        import jax
        import jax.numpy as jnp
        self.candidate = candidate      # (key, name) -> the leaf to judge
        self.norms = {}
        eps = float(np.finfo(np.float32).eps)

        def norm(x):
            return jnp.sqrt(jnp.sum(jnp.square(x)))

        self._norms = jax.jit(lambda got, after, start: (
            norm(got - after),
            jnp.maximum(norm(after - start), eps * norm(start))))

    def __call__(self, key, name, after, start):
        self.norms.setdefault(key, []).append(tuple(
            float(x) for x in self._norms(self.candidate(key, name), after,
                                          start)))


def mismatch(got, want) -> tuple:
    """(share of (sequence, key-value head, query) rows whose attended key
    blocks differ, share of their (row, block) entries that differ), over the
    steps' sparse blocks; a block that chose on one side only differs
    everywhere."""
    rows = entries = differing_rows = differing = 0
    for got_step, want_step in zip(got, want):
        for g, w in zip(got_step, want_step):
            if g is None and w is None:
                continue
            some = g if w is None else w
            diff = np.ones(some.shape, bool) if g is None or w is None \
                else np.asarray(g) != np.asarray(w)
            rows += diff[..., 0].size
            entries += diff.size
            differing_rows += int(diff.any(axis=-1).sum())
            differing += int(diff.sum())
    return (differing_rows / max(rows, 1), differing / max(entries, 1))


def gaps(got: dict, want: dict, leaf_norms: dict, got_rows,
         pattern: str) -> dict:
    """The numbers compared: each step's loss; the change of every dense leaf
    after the steps (error norm over the norm of the reference's own change of
    that leaf), the worst leaf of all and of each block kind; the touched
    embedding rows likewise; what the sparse blocks' queries attended
    (:func:`mismatch`: by row, and by entry under ``.entries``)."""
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
    by_row, by_entry = mismatch(got["attended"], want["attended"])
    out["selected_blocks_mismatch_share"] = by_row
    out["selected_blocks_mismatch_share.entries"] = by_entry
    return out


def program_gaps(ctx, state, also=None) -> tuple:
    """(the program's first steps against the reference's, the reference's
    run)."""
    judge = LeafGaps(base.program_leaves(state))

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


# -- the controls -------------------------------------------------------------
@contextlib.contextmanager
def left_out(what: str):
    """The program with part of the mathematics left out, for the time a model
    is built and traced under it: ``no_selection`` lets the sparse block attend
    every key at or before a query; ``no_decay`` gives every Lightning head a
    decay of zero; ``no_gates`` takes the gate off both mixers' outputs."""
    import jax.numpy as jnp
    from multiverso_tpu.models.hybrid_lm import attention, model
    module, name = {"no_selection": (attention, "sparse_select"),
                    "no_decay": (model, "lightning_slopes"),
                    "no_gates": (attention, "output_gate")}[what]
    whole = getattr(module, name)

    def every_block(q, k, length, cfg):
        bsz, nb, blk, kh = q.shape[:4]
        return jnp.ones((nb, bsz, kh, blk, nb * blk // cfg.sparse_block_size),
                        bool)

    setattr(module, name, {
        "no_selection": every_block,
        "no_decay": lambda *a: np.zeros_like(whole(*a)),
        "no_gates": lambda o, n, wg: o}[what])
    try:
        yield
    finally:
        setattr(module, name, whole)


def limit_readings(make_ctx, seeds, control_seeds: int) -> list:
    """For ``tools/limits.py``: one model, re-seeded for every seed and driven
    through its first steps, against the reference; for the first
    ``control_seeds`` the reference computed and stored in bfloat16 (every
    softmax, the decay and the logits float32) put in the program's place (the
    sound reference's leaves wait on the host for it); then, on the first
    seed, the program built with each part of the mathematics left out
    (:func:`left_out`): the limits must lie under what these read."""
    pack_batch = program().pack_batch
    ctx0 = make_ctx(seeds[0])
    model, cfg, shapes = build_model(ctx0)
    _, t = sized(ctx0)
    drawn = {seed: packed_batches(seed, dict(t, batches=CHECK_STEPS),
                                  cfg.vocab_size) for seed in seeds}
    min_rows = model.min_rows = max(len(pack_batch(b, cfg.row_bucket)[0])
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
        del model, state
    finally:
        close(None)
    ctx = make_ctx(seeds[0])
    for what in LEFT_OUT:
        gc.collect()    # a model and its step hold each other: 8 GB of leaves
        with left_out(what):
            model, cfg, shapes = build_model(ctx)
            model.min_rows = min_rows
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
    counted = {n.replace(".", "_"): v - before[n]
               for n, v in base.counter_totals(COUNTERS).items()}
    counters = out["counters"]
    counters.update(counted)
    # a step's phases beside its rate: a process that steps slowly for a
    # whole window (PERF.md 6) shows here on which side of the launch
    counters.update({"span_" + name.replace(".", "_") + "_ms": ms / n
                     for name, (n, ms) in out["spans"].items() if n})
    if counted["lm_sparse_causal_pairs"]:
        counters["sala_sparse_pair_share"] = 100.0 * counted[
            "lm_sparse_pairs"] / counted["lm_sparse_causal_pairs"]
    return out
