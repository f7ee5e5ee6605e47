"""Driver ``train_lm_evabyte``: a byte LM whose layers are two residual blocks
(EVA attention, then a dense gated feed-forward) under a head of several
prediction heads, trained through ``HybridLM.step`` on the PS plane as
``train_lm_dsv2`` trains the latent-attention expert model: the same entry
point, table plane, hybrid step, set-up, check and window.

It IS that driver, with the names replaced that say what this model's leaves
and traffic are: a private instance of ``drivers/train_lm_dsv2.py`` is loaded
and four of its module-level names are set (:func:`make_leaf`, :func:`
byte_batches` as its ``packed_batches``, :func:`first_steps`, :func:`gaps`), so
that its ``setup``, ``reference_run``, ``check`` and ``limit_readings`` run as
they are written, on this model. Stated here: the leaf names and how they are
seeded (norms zero under the unit offset; ``phi`` and ``mu`` normal, clamped),
bytes as traffic, the numbers compared (each prediction head's loss; ``phi``
and ``mu`` beside the worst leaf of each block kind), the window's two more
counters, and the two left-out-mathematics controls ``tools/limits.py`` reads
after the bfloat16 one.

The reference's ``grads_by_layer`` answers in the expert models' shape (loss,
counts, balance term, row gradients): its second result is each prediction
head's loss and its third is 0; :func:`gaps` reads them so.
"""
from __future__ import annotations

import contextlib
import importlib.util
import math
import os

import numpy as np

import harness
import seeded

_BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
base = harness.load_module("drivers", "train_lm", _BENCH_DIR)
dsv2 = harness.load_module("drivers", "train_lm_dsv2", _BENCH_DIR)

#: A block's leaves in the order the program's ``param_shapes`` gives them
#: (the order ``reference_run`` emits them in; pinned by the rehearsal).
EVA_LEAVES = ("norm", "wq", "wk", "wv", "adaptive_phi", "adaptive_mu_k", "wo")
KIND_NAMES = {"V": "eva", "D": "dense_ffn"}
LEARNED_VECTORS = ("adaptive_phi", "adaptive_mu_k")
COUNTERS = ("lm.eva.summary_pairs", "lm.eva.chunks")


# -- traffic: packed documents of bytes -------------------------------------------
def byte_batches(seed: int, t: dict, vocab: int) -> list:
    """``train_lm``'s packed documents over the byte values: the ``vocab -
    special_ids`` byte ids follow the special ones, Zipf ranks through the
    fixed permutation of the bytes; the end-of-document id is a special id."""
    specials = t["special_ids"]
    return [np.where(b == base.EOS, t["eos_id"], b + specials - 1).astype(
        np.int32) for b in base.packed_batches(seed, t, vocab - specials + 1)]


# -- weights from the seed ----------------------------------------------------
def make_leaf(seed: int, c: dict, shapes: dict, layer, name: str):
    """One dense leaf on the device: norms ZERO (the scale is ``1 + w``);
    ``phi`` and ``mu`` standard normal (Box-Muller over two seeded streams),
    clamped to [-1, 1], times ``head_dim ** -0.5``; every matrix as
    ``train_lm_dsv2`` seeds it (uniform of standard deviation ``init_std``,
    ``wo`` and ``ffn_down`` over sqrt(2 x the PUBLISHED layers))."""
    import jax.numpy as jnp
    group = shapes if layer is None else shapes["layers"][layer]
    shape = group[name]
    if name in dsv2.NORMS:
        return jnp.zeros(shape, jnp.float32)
    if name not in LEARNED_VECTORS:
        return dsv2.make_leaf(seed, c, shapes, layer, name)
    stream = base.LAYER_STREAM + 16 * layer + sorted(group).index(name)
    u1, u2 = (seeded.table_jax(seed, stream + 8 * i, shape, 1.0, "positive")
              for i in range(2))
    normal = jnp.sqrt(-2.0 * jnp.log1p(-u1)) * jnp.cos(2.0 * math.pi * u2)
    return jnp.clip(normal, -1.0, 1.0) * shape[-1] ** -0.5


def first_steps(model, batches) -> dict:
    """The model through its first steps, by the window's own call."""
    out = {"losses": [], "head_losses": []}
    for tokens in batches[:base.CHECK_STEPS]:
        out["losses"].append(model.step(tokens))
        out["head_losses"].append(np.array(model.last_head_losses))
    return out


def gaps(got: dict, want: dict, leaf_norms: dict, got_rows,
         pattern: str) -> dict:
    """The numbers compared: each step's loss and each prediction head's own
    (worst step; a head left out or a target shifted by one reads of order 1);
    the change of every dense leaf after the steps (error norm over the norm
    of the reference's own change of that leaf), the worst leaf of all, of
    each block kind and ``phi`` and ``mu`` alone; the touched embedding rows
    likewise. ``got`` is the program's :func:`first_steps` or a reference
    run, whose heads' losses stand where an expert model's counts do."""
    def norm(x):
        return float(np.linalg.norm(np.asarray(x, np.float64)))

    def heads_of(run):
        return np.asarray(run.get("head_losses", run.get("counts")),
                          np.float64)

    head_gaps = np.max(np.abs(heads_of(got) - heads_of(want))
                       / np.abs(heads_of(want)), axis=0)
    by_kind = {}
    for key, pairs in leaf_norms.items():
        rel = [err / max(moved, 1e-30) for err, moved in pairs]
        kind = "top" if key == "top" else KIND_NAMES[pattern[key]]
        by_kind.setdefault(kind, []).extend(rel)
        if kind == "eva":
            for name in LEARNED_VECTORS:
                by_kind.setdefault(name, []).append(
                    rel[EVA_LEAVES.index(name)])
    out = {"step_loss_rel_gap": max(
        abs(g - w) / abs(w) for g, w in zip(got["losses"], want["losses"])),
        "head_loss_rel_gap": float(np.max(head_gaps))}
    for h, value in enumerate(head_gaps):
        out[f"head_loss_rel_gap.h{h}"] = float(value)
    out["dense_rel_gap"] = max(max(v) for v in by_kind.values())
    for kind, values in by_kind.items():
        out[f"dense_rel_gap.{kind}"] = max(values)
    out["rows_rel_gap"] = norm(got_rows - want["rows"]) / max(
        norm(want["rows"] - want["rows0"]), 1e-30)
    return out


def _instance(**names):
    """A private instance of ``drivers/train_lm_dsv2.py`` with some of its
    module-level names replaced: its functions look their helpers up in their
    own module, so the replaced ones are what they call."""
    spec = importlib.util.spec_from_file_location(
        "bench_drivers_train_lm_dsv2_for_evabyte", dsv2.__file__)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    vars(mod).update(names)
    return mod


own = _instance(make_leaf=make_leaf, packed_batches=byte_batches,
                first_steps=first_steps, gaps=gaps)
setup, check, verify, close = own.setup, own.check, base.verify, base.close


# -- the left-out-mathematics controls ----------------------------------------
@contextlib.contextmanager
def left_out(what: str):
    """The program with part of EVA's mathematics left out, for the time a
    model is built and traced under it: ``no_summaries`` masks every remote
    summary away (attention inside the windows only); ``frozen_phi_mu`` lets
    no gradient reach the two learned per-head vectors."""
    import jax
    import jax.numpy as jnp
    from multiverso_tpu.models.hybrid_lm import attention
    name = {"no_summaries": "_remote_scores",
            "frozen_phi_mu": "eva_summaries"}[what]
    whole = getattr(attention, name)

    def unseen(qi, kr, scale):
        return jnp.full_like(whole(qi, kr, scale), -jnp.inf)

    def frozen(k, v, phi, mu, *rest):
        return whole(k, v, jax.lax.stop_gradient(phi),
                     jax.lax.stop_gradient(mu), *rest)

    setattr(attention, name, unseen if what == "no_summaries" else frozen)
    try:
        yield
    finally:
        setattr(attention, name, whole)


def limit_readings(make_ctx, seeds, control_seeds: int) -> list:
    """``train_lm_dsv2``'s readings (the sound program over every seed, the
    bfloat16 reference in its place over the first ``control_seeds``), then,
    on the first seed, the program built with each part of the mathematics
    left out (:func:`left_out`), side ``no_summaries`` / ``frozen_phi_mu``:
    the limits must lie under what these read."""
    rows = own.limit_readings(make_ctx, seeds, control_seeds)
    ctx = make_ctx(seeds[0])
    for what in ("no_summaries", "frozen_phi_mu"):
        with left_out(what):
            state = setup(ctx)
            try:
                rows.append({"seed": ctx.seed, "side": what,
                             "gaps": own.program_gaps(ctx, state)[0]})
            finally:
                close(state)
        del state
    return rows


# -- the window ------------------------------------------------------------
def measure(state, ctx) -> dict:
    before = base.counter_totals(COUNTERS)
    out = own.measure(state, ctx)
    summary_pairs, chunks = (v - before[n] for n, v in
                             base.counter_totals(COUNTERS).items())
    counters = out["counters"]
    counters.update(lm_eva_summary_pairs=summary_pairs, lm_eva_chunks=chunks)
    if summary_pairs + counters["lm_attn_pairs"]:
        counters["eva_summary_pair_share"] = 100.0 * summary_pairs / (
            summary_pairs + counters["lm_attn_pairs"])
    return out
