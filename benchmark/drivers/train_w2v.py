"""Driver ``train_w2v``: skip-gram/negative-sampling training through
``Word2Vec.train`` (the device pipeline), on one chip or on a dp x tp mesh.

Set-up builds ONE ``Word2Vec`` with its four tables, seeds them on the device,
draws the corpus blocks, and trains the first block through ``train`` (which
compiles every program the window uses). The window calls ``train`` with a
few whole blocks at a time until the clock passes ``--seconds``: the rate is
the words of the whole completed blocks over the time to the last ``train``'s
return, which follows its ``block_until_ready``.
"""
from __future__ import annotations

import time

import numpy as np

import seeded
import traffic_gen

TABLES = ("input_table", "output_table", "adagrad_in", "adagrad_out")
SPANS = ("w2v.device_block",)
PROBE_ROWS = 256


def _dictionary(vocab: int, total_words: int):
    """A ``Dictionary`` for a synthetic corpus: the counts of
    ``Dictionary.synthetic_zipf`` without its 4M word strings (training by
    ids reads only ``len`` and ``counts``)."""
    from multiverso_tpu.models.word2vec import Dictionary
    d = Dictionary(min_count=1)
    d.counts = traffic_gen.zipf_counts(vocab, total_words).tolist()
    d.words = [""] * vocab
    return d


def build_model(ctx):
    """mv.init + the Word2Vec the configuration and the mix describe."""
    import multiverso_tpu as mv
    from multiverso_tpu.models.word2vec import Word2Vec, Word2VecConfig
    c, t = ctx.config, ctx.traffic
    mv.init([])
    dictionary = _dictionary(c["vocab"], c["corpus_words"])
    cfg = Word2VecConfig(
        embedding_size=c["embedding_size"], window=c["window"],
        negative=c["negative"], sample=c["sample"], sg=True, hs=False,
        optimizer=c["optimizer"], learning_rate=c["learning_rate"],
        param_dtype=c["param_dtype"], epochs=1, pipeline=True,
        device_pipeline=True, batch_size=t.get("batch_size", 8192),
        block_sentences=t.get("block_sentences", 512),
        pad_sentence_length=t.get("pad_sentence_length", 512),
        mesh_data=c.get("mesh_data", 1), mesh_model=c.get("mesh_model", 1),
        dispatch_mode=c.get("dispatch_mode"), seed=ctx.seed % (2 ** 31 - 1))
    return Word2Vec(cfg, dictionary), dictionary


def seed_tables(w2v, ctx) -> None:
    """All four tables from the seed, each one jitted call on the device, in
    whatever sharding the store holds now."""
    c = ctx.config
    for stream, (attr, init) in enumerate(zip(TABLES, c["init"])):
        seeded.reseed_store(getattr(w2v, attr).store, ctx.seed, stream,
                            init["scale"], init["kind"], c["vocab"])


def table_rows(w2v, attr: str, rows) -> np.ndarray:
    return np.asarray(getattr(w2v, attr).get_rows(
        np.asarray(rows, np.int32)))


def setup(ctx):
    t = ctx.traffic
    t0 = time.perf_counter()
    w2v, dictionary = build_model(ctx)
    t1 = time.perf_counter()
    seed_tables(w2v, ctx)
    cdf = traffic_gen.zipf_rank_cdf(ctx.config["vocab"])
    blocks = traffic_gen.corpus_blocks(
        ctx.seed, cdf, t["blocks"], t.get("block_sentences", 512),
        t["sentence_words"])
    state = {"w2v": w2v, "blocks": blocks, "counts": dictionary.counts,
             "model_s": t1 - t0, "build_s": time.perf_counter() - t0}
    # The first steps from the seed, through the window's own call: compiles
    # pair generation, the chunk step and the tail step.
    probe = np.arange(PROBE_ROWS, dtype=np.int32)   # the most frequent words
    before = {a: table_rows(w2v, a, probe) for a in TABLES}
    tw = time.perf_counter()
    stats = w2v.train(sentences=list(blocks[0]))
    state["warm_s"] = time.perf_counter() - tw
    after = {a: table_rows(w2v, a, probe) for a in TABLES}
    state["first_block"] = {
        "stats": stats,
        "changed": min(float(np.linalg.norm(after[a] - before[a]))
                       for a in TABLES)}
    return state


def check(state, ctx) -> None:
    """Against the plain reference. (a) The timed path's first block: every
    word counted, the pairs it drew against their expectation, every table
    moved. (b) Below the block pipeline, whose window and negative draws are
    made at random on the device: the step the chunk programs call, applied
    to the live, freshly re-seeded tables on seeded batches with duplicates,
    against the reference's sg-ns + AdaGrad update of the touched rows."""
    ref, c = ctx.reference, ctx.config
    w2v, first = state["w2v"], state["first_block"]
    block0 = state["blocks"][0]
    ctx.checks.add("first_block_words", first["stats"]["words"],
                   block0.size, "eq")
    want = ref.expected_pairs(
        ref.keep_probability(state["counts"], c["sample"]), block0,
        c["window"])
    ctx.checks.add("first_block_pairs_rel_gap",
                   abs(first["stats"]["pairs"] - want) / want,
                   ctx.limit("first_block_pairs_rel_gap"))
    ctx.checks.add("first_block_tables_moved", first["changed"],
                   ctx.limit("first_block_tables_moved"), "min")
    gaps = step_gaps(w2v, ctx)
    for name, value in gaps.items():
        ctx.checks.add(name, value, ctx.limit(name))


def step_batches(ctx, steps: int):
    """Seeded batches at the timed batch size: Zipf ids (so duplicates are
    many), a masked tail, a fixed rate."""
    c, t = ctx.config, ctx.traffic
    B, K, V = t.get("batch_size", 8192), c["negative"], c["vocab"]
    rng = traffic_gen.rng_for(ctx.seed, 5)
    cdf = traffic_gen.zipf_rank_cdf(V)
    out = []
    for _ in range(steps):
        ids = np.minimum(np.searchsorted(cdf, rng.random((B, 2 + K))),
                         V - 1).astype(np.int32)
        mask = (np.arange(B) < B - rng.integers(1, B // 8)).astype(np.float32)
        out.append((ids[:, 0], ids[:, 1], ids[:, 2:], mask))
    return out


def step_gaps(w2v, ctx, reference_kwargs=None, steps: int = 3) -> dict:
    """Run ``steps`` sg-ns steps through the program on its live tables and
    through the reference on the same seeded rows; returns the numbers
    compared. ``reference_kwargs`` (``storage=...``) computes the reference
    in a lower precision instead: the control, put in the program's place."""
    import jax
    from multiverso_tpu.models.word2vec.model import build_sg_ns_step
    ref, c = ctx.reference, ctx.config
    D, lr = c["embedding_size"], np.float32(c["learning_rate"])
    batches = step_batches(ctx, steps)
    ids_in = np.unique(np.concatenate([b[0] for b in batches]))
    ids_out = np.unique(np.concatenate(
        [np.concatenate([b[1], b[2].ravel()]) for b in batches]))

    def fresh(storage=None):
        rows = [seeded.rows_np(ctx.seed, s, ids, D, c["init"][s]["scale"],
                               c["init"][s]["kind"])
                for s, ids in enumerate((ids_in, ids_out, ids_in, ids_out))]
        return (ref.Rows(ids_in, rows[0], rows[2], storage),
                ref.Rows(ids_out, rows[1], rows[3], storage))

    r_in, r_out = fresh()
    start = {"w_in": r_in.w.copy(), "w_out": r_out.w.copy(),
             "g_in": r_in.g2.copy(), "g_out": r_out.g2.copy()}
    ref_losses, ref_g2_first = [], None
    for b in batches:
        ref_losses.append(ref.step(r_in, r_out, *b, lr))
        if ref_g2_first is None:
            ref_g2_first = (r_in.g2.copy(), r_out.g2.copy())
    want = {"w_in": r_in.w, "w_out": r_out.w, "g_in": r_in.g2,
            "g_out": r_out.g2}

    if reference_kwargs is None:
        seed_tables(w2v, ctx)
        stores = [getattr(w2v, a).store for a in TABLES]
        step = build_sg_ns_step(c["optimizer"] == "adagrad")
        got_losses, got_g2_first = [], None
        for b in batches:
            out = step(*[s.data for s in stores], *b, lr)
            for s, new in zip(stores, out[:4]):
                s.data = new
            got_losses.append(float(out[4]))
            if got_g2_first is None:
                got_g2_first = (table_rows(w2v, "adagrad_in", ids_in),
                                table_rows(w2v, "adagrad_out", ids_out))
        jax.block_until_ready([s.data for s in stores])
        got = {"w_in": table_rows(w2v, "input_table", ids_in),
               "w_out": table_rows(w2v, "output_table", ids_out),
               "g_in": table_rows(w2v, "adagrad_in", ids_in),
               "g_out": table_rows(w2v, "adagrad_out", ids_out)}
    else:
        c_in, c_out = fresh(**reference_kwargs)
        got_losses, got_g2_first = [], None
        for b in batches:
            got_losses.append(ref.step(c_in, c_out, *b, lr))
            if got_g2_first is None:
                got_g2_first = (c_in.g2.copy(), c_out.g2.copy())
        got = {"w_in": c_in.w, "w_out": c_out.w, "g_in": c_in.g2,
               "g_out": c_out.g2}

    def norm(x):
        return float(np.linalg.norm(np.asarray(x, np.float64)))

    # the first gradient as the optimizer got it: the accumulators' gain
    # after one step, as a norm, against the reference's
    grad_gap = max(
        abs(norm(g - s) - norm(w - s)) / norm(w - s)
        for g, w, s in zip(got_g2_first, ref_g2_first,
                           (start["g_in"], start["g_out"])))
    # the rows after the steps, against the size of the reference's change
    rows_gap = max(norm(got[k] - want[k]) / norm(want[k] - start[k])
                   for k in want)
    loss_gap = max(abs(g - w) / abs(w)
                   for g, w in zip(got_losses, ref_losses))
    return {"step_loss_rel_gap": loss_gap, "step_grad_norm_rel_gap": grad_gap,
            "step_rows_rel_gap": rows_gap}


def limit_readings(make_ctx, seeds, control_seeds: int) -> list:
    """For ``tools/limits.py``: one model, every seed's step gaps for the
    sound program and, on the first ``control_seeds`` seeds, for the control
    (the reference with bfloat16 tables in the program's place). The first
    block's pair count is read once per seed from a re-seeded model too."""
    import ml_dtypes
    ctx0 = make_ctx(seeds[0])
    w2v, dictionary = build_model(ctx0)
    ref, c, t = ctx0.reference, ctx0.config, ctx0.traffic
    keep = ref.keep_probability(dictionary.counts, c["sample"])
    cdf = traffic_gen.zipf_rank_cdf(c["vocab"])
    out = []
    try:
        for i, seed in enumerate(seeds):
            ctx = make_ctx(seed)
            seed_tables(w2v, ctx)
            block = traffic_gen.corpus_blocks(
                seed, cdf, 1, t.get("block_sentences", 512),
                t["sentence_words"])[0]
            w2v.trained_words = 0
            stats = w2v.train(sentences=list(block))
            want = ref.expected_pairs(keep, block, c["window"])
            gaps = step_gaps(w2v, ctx)
            gaps["first_block_pairs_rel_gap"] = \
                abs(stats["pairs"] - want) / want
            out.append({"seed": seed, "side": "sound", "gaps": gaps})
            if i < control_seeds:
                out.append({"seed": seed, "side": "control", "gaps": step_gaps(
                    w2v, ctx, reference_kwargs={"storage": ml_dtypes.bfloat16})})
    finally:
        close(None)
    return out


def measure(state, ctx) -> dict:
    """``train`` calls of ``train_call_blocks`` whole blocks each until the
    clock passes ``--seconds``. Each call ends in ``block_until_ready``, so
    the device never runs more than one call behind the clock (in one
    endless call the host runs blocks ahead of the device and the window
    would outlast its length many times)."""
    import jax
    from harness import span_delta, span_totals
    w2v, blocks = state["w2v"], state["blocks"]
    per_call = ctx.traffic.get("train_call_blocks", 4)
    order = traffic_gen.rng_for(ctx.seed, 6).permutation(len(blocks))
    spans0 = span_totals(SPANS)
    w2v.trained_words = 0
    fed, pairs0, stats = 0, None, None
    t0 = time.perf_counter()
    deadline = t0 + ctx.seconds
    while fed == 0 or time.perf_counter() < deadline:
        group = [blocks[order[(fed + i) % len(blocks)]]
                 for i in range(per_call)]
        with jax.profiler.TraceAnnotation("bench.train_blocks"):
            stats = w2v.train(sentences=(s for b in group for s in b))
        fed += per_call
        pairs0 = (pairs0 or 0) + int(stats["pairs"])
    elapsed = time.perf_counter() - t0      # the last train returned
    words = fed * blocks[0].size
    state["window_stats"] = stats
    return {
        "attempted": fed,
        "failed": 0 if stats["words"] == words else fed,
        "metrics": {"train_samples_per_s": words / elapsed},
        "spans": span_delta(spans0, span_totals(SPANS)),
        "counters": {"words": words, "pairs": pairs0, "blocks": fed,
                     "elapsed_s": elapsed, "block_ms": 1e3 * elapsed / fed,
                     "setup_compile_s": state["warm_s"],
                     "model_s": state["model_s"],
                     "build_s": state["build_s"]},
    }


def verify(state, ctx) -> None:
    m = ctx.measured
    ctx.checks.add("window_words_trained", state["window_stats"]["words"],
                   m["counters"]["words"], "eq")
    loss = state["window_stats"]["loss"]
    ctx.checks.add("window_loss_finite", float(np.isfinite(loss)), 1, "eq")


def close(state) -> None:
    import multiverso_tpu as mv
    mv.shutdown()
