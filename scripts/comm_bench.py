#!/usr/bin/env python
"""Three-way CommPolicy bench (ROADMAP item 4 / docs/DESIGN.md).

Measures, on THIS box, word2vec and logreg under each communication
policy (``parallel/comm_policy.py``):

* word2vec: ``ps`` (pull-train-push through the table clients — the
  reference's communicator loop in-process), ``hybrid``/AUTO (sparse
  tables on the fused in-store PS plane + one in-graph collective per
  block for the dense quantities), ``model_average`` (fused replicas,
  per-epoch collective reconcile), plus the fused-host reference leg
  (same batching path as ps, no client round trips) so the pure plane
  cost is isolated.
* logreg: ``ps`` (PSModel push/pull per minibatch), ``allreduce``
  (device-resident weights, in-graph merge, BITWISE-equal params —
  asserted), ``model_average``.

Every leg runs under a reset telemetry registry and embeds its
``comm.*`` counters, so the record carries per-policy bytes/latency
evidence. The AUTO block embeds ``resolve_comm_policy``'s decision log +
probe cache and asserts AUTO matched the fastest measured policy per
table. Writes BENCH_COMM.json; ``--dry-run`` is the tier-1 smoke shape
(witnesses asserted: the hybrid word2vec run must tick BOTH planes).

Numbers are box-relative (CPU here unless a chip is attached) — they
compare policies against each other on equal hardware, never across
boxes.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np  # noqa: E402

_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _HERE)


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _comm_counters() -> dict:
    """The run's comm.* counters (+ latency p50s), compacted."""
    from multiverso_tpu.telemetry import metrics_snapshot
    snap = metrics_snapshot(buckets=False)
    out = {}
    for name, rec in snap.get("counters", {}).items():
        if name.startswith("comm."):
            out[name] = rec.get("value")
    for name, rec in snap.get("histograms", {}).items():
        if name.startswith("comm."):
            out[name + ".p50"] = rec.get("p50")
    return out


def _fresh_telemetry() -> None:
    from multiverso_tpu.telemetry import reset_telemetry
    reset_telemetry()


# ---------------------------------------------------------------------------
# word2vec legs
# ---------------------------------------------------------------------------
def _w2v_shape(dry: bool) -> dict:
    if dry:
        return dict(V=300, D=16, n_sent=60, sent_len=40, batch=256,
                    block_sentences=32, pad=64, warm=4)
    return dict(V=20_000, D=64, n_sent=400, sent_len=250, batch=4096,
                block_sentences=128, pad=256, warm=8)


def bench_word2vec_policies(dry: bool) -> dict:
    import multiverso_tpu as mv
    from multiverso_tpu.models.word2vec import (Dictionary, Word2Vec,
                                                Word2VecConfig)

    sh = _w2v_shape(dry)
    rng = np.random.default_rng(0)
    d, zipf = Dictionary.synthetic_zipf(sh["V"],
                                        sh["n_sent"] * sh["sent_len"])
    sentences = [rng.choice(sh["V"], size=sh["sent_len"], p=zipf)
                 .astype(np.int32) for _ in range(sh["n_sent"])]

    def run(policy, device_pipeline, tag):
        _fresh_telemetry()
        mv.init(["-mesh_shape=server:1"])
        try:
            cfg = Word2VecConfig(
                embedding_size=sh["D"], window=5, negative=5,
                batch_size=sh["batch"], sample=1e-3, sg=True, hs=False,
                optimizer="adagrad", epochs=1, pipeline=not dry,
                device_pipeline=device_pipeline,
                block_sentences=sh["block_sentences"],
                pad_sentence_length=sh["pad"], seed=0,
                comm_policy=policy)
            w2v = Word2Vec(cfg, d)
            w2v.train(sentences=sentences[:sh["warm"]])   # compile warm-up
            w2v.trained_words = 0
            stats = w2v.train(sentences=sentences)
            leg = {"words_per_sec": round(stats["words_per_sec"], 1),
                   "loss": round(stats["loss"], 4),
                   "comm_mode": stats.get("comm_mode"),
                   "policies": dict(w2v.comm_policies),
                   "comm": _comm_counters()}
            _log(f"w2v[{tag}]: {leg['words_per_sec']} words/sec "
                 f"(loss {leg['loss']}) comm={leg['comm']}")
            return leg
        finally:
            mv.shutdown()

    out = {
        "ps": run("ps", False, "ps pull-train-push"),
        "hybrid": run("auto", True, "hybrid (auto)"),
        "model_average": run("model_average", True, "model_average"),
        # Same batching path as ps, zero client round trips: isolates the
        # pure plane cost from the device-pipeline rewrite.
        "fused_host": run(None, False, "fused-host reference"),
    }
    out["hybrid_over_ps"] = round(
        out["hybrid"]["words_per_sec"] / max(out["ps"]["words_per_sec"],
                                             1e-9), 3)
    out["fused_host_over_ps"] = round(
        out["fused_host"]["words_per_sec"] /
        max(out["ps"]["words_per_sec"], 1e-9), 3)
    return out


# ---------------------------------------------------------------------------
# logreg legs
# ---------------------------------------------------------------------------
def bench_logreg_policies(dry: bool) -> dict:
    import multiverso_tpu as mv
    from multiverso_tpu.models.logreg.logreg import LogReg
    from multiverso_tpu.models.logreg.model import LogRegConfig, make_model

    F = 64 if dry else 256
    B = 32 if dry else 64
    N = 20 if dry else 200
    epochs = 2 if dry else 5
    rng = np.random.default_rng(1)
    X = rng.normal(size=(N * B, F + 1)).astype(np.float32)
    X[:, -1] = 1.0
    w_true = rng.normal(size=(F + 1, 1)).astype(np.float32)
    y = (X @ w_true > 0).astype(np.float32).ravel()
    batches = [(X[i * B:(i + 1) * B], y[i * B:(i + 1) * B])
               for i in range(N)]

    weights = {}

    def run(policy, tag):
        _fresh_telemetry()
        mv.init(["-mesh_shape=server:1"])
        try:
            cfg = LogRegConfig(objective="sigmoid", num_feature=F,
                               learning_rate=0.1, minibatch_size=B,
                               epochs=epochs, comm_policy=policy)
            model = make_model(cfg)
            lr = LogReg(cfg, model=model)
            lr.train(batches, epochs=1)     # compile warm-up epoch
            t0 = time.perf_counter()
            losses = lr.train(batches)
            model.sync()
            dt = time.perf_counter() - t0
            weights[tag] = model.get_weights().copy()
            leg = {"updates_per_sec": round(epochs * N / dt, 1),
                   "model": type(model).__name__,
                   "final_loss": round(losses[-1], 6),
                   "comm": _comm_counters()}
            _log(f"logreg[{tag}]: {leg['updates_per_sec']} updates/sec "
                 f"({leg['model']}, loss {leg['final_loss']}) "
                 f"comm={leg['comm']}")
            return leg
        finally:
            mv.shutdown()

    out = {"ps": run("ps", "ps"),
           "allreduce": run("allreduce", "allreduce"),
           "model_average": run("model_average", "model_average")}
    # The parity contract the tests pin: warm-up + timed epochs see the
    # same batch sequence, so ps and allreduce params must agree BITWISE.
    out["allreduce_bitwise_eq_ps"] = bool(
        np.array_equal(weights["ps"], weights["allreduce"]))
    out["allreduce_over_ps"] = round(
        out["allreduce"]["updates_per_sec"] /
        max(out["ps"]["updates_per_sec"], 1e-9), 3)
    return out


# ---------------------------------------------------------------------------
# model_average convergence vs averaging period (ROADMAP 5d)
# ---------------------------------------------------------------------------
def bench_ma_convergence(dry: bool) -> dict:
    """Loss trajectory of the model_average plane at 2-3 averaging
    periods on logreg, so AUTO's decision table can weigh QUALITY, not
    just wall-clock: model_average trades a staleness window (the
    period) for zero per-step communication, and this leg measures what
    that window costs in loss. Two replicas are simulated in-process —
    each trains a device-resident LocalModel on its own half of the
    minibatch stream and every P steps the replicas average weights
    (plain mean, exactly ``model_average_arrays`` across processes). The
    ``sequential`` row is the single-model reference trajectory (what
    the PS plane computes when one worker owns the whole stream)."""
    from multiverso_tpu.models.logreg.model import LocalModel, LogRegConfig

    F = 64 if dry else 256
    B = 32 if dry else 64
    N = 40 if dry else 200          # minibatches per epoch
    epochs = 2 if dry else 5
    replicas = 2
    periods = (1, 4) if dry else (1, 8, 32)
    rng = np.random.default_rng(5)
    X = rng.normal(size=(N * B, F + 1)).astype(np.float32)
    X[:, -1] = 1.0
    w_true = rng.normal(size=(F + 1, 1)).astype(np.float32)
    y = (X @ w_true > 0).astype(np.float32).ravel()
    batches = [(X[i * B:(i + 1) * B], y[i * B:(i + 1) * B])
               for i in range(N)]

    def full_loss(w: np.ndarray) -> float:
        """Mean sigmoid cross-entropy over the whole stream — one
        comparable quality number per leg."""
        z = (X @ w).ravel()
        return float(np.mean(np.logaddexp(0.0, z) - y * z))

    def cfg():
        return LogRegConfig(objective="sigmoid", num_feature=F,
                            learning_rate=0.1, minibatch_size=B,
                            epochs=epochs)

    def run_ma(period: int) -> dict:
        models = [LocalModel(cfg()) for _ in range(replicas)]
        epoch_losses = []
        merged = None
        for _ in range(epochs):
            losses, rounds = [], 0
            for i in range(0, N, replicas):
                for r in range(replicas):
                    if i + r < N:
                        Xb, yb = batches[i + r]
                        losses.append(float(models[r].update(Xb, yb)))
                rounds += 1
                if rounds % period == 0:
                    merged = np.mean([m.get_weights() for m in models],
                                     axis=0)
                    for m in models:
                        m.set_weights(merged)
            # epoch-boundary reconcile (the plane's sync() semantics)
            merged = np.mean([m.get_weights() for m in models], axis=0)
            for m in models:
                m.set_weights(merged)
            epoch_losses.append(round(float(np.mean(losses)), 6))
        return {"period": period,
                "epoch_mean_loss": epoch_losses,
                "final_full_loss": round(full_loss(merged), 6)}

    def run_sequential() -> dict:
        model = LocalModel(cfg())
        epoch_losses = []
        for _ in range(epochs):
            losses = [float(model.update(Xb, yb)) for Xb, yb in batches]
            epoch_losses.append(round(float(np.mean(losses)), 6))
        return {"epoch_mean_loss": epoch_losses,
                "final_full_loss":
                    round(full_loss(model.get_weights()), 6)}

    seq = run_sequential()
    legs = [run_ma(p) for p in periods]
    init_loss = full_loss(np.zeros((F + 1, 1), np.float32))
    out = {"replicas": replicas, "epochs": epochs,
           "minibatches_per_epoch": N,
           "initial_full_loss": round(init_loss, 6),
           "sequential": seq, "periods": legs,
           "quality_gap_vs_sequential": {
               str(leg["period"]): round(
                   leg["final_full_loss"] - seq["final_full_loss"], 6)
               for leg in legs}}
    _log(f"ma_convergence: seq final {seq['final_full_loss']}, "
         + ", ".join(f"P={leg['period']} -> {leg['final_full_loss']}"
                     for leg in legs))
    return out


# ---------------------------------------------------------------------------
# AUTO decision evidence
# ---------------------------------------------------------------------------
def auto_evidence(w2v: dict, logreg: dict) -> dict:
    """Canonical-shape resolutions + the per-table fastest-policy cross
    check the acceptance criteria name. AUTO never picks model_average
    (it changes semantics), so 'fastest' compares the same-semantics
    planes: ps vs allreduce/hybrid."""
    import multiverso_tpu as mv
    from multiverso_tpu.core.zoo import Zoo
    from multiverso_tpu.parallel import comm_policy as cp

    _fresh_telemetry()
    cp.reset_decisions()
    mv.init(["-mesh_shape=server:1"])
    try:
        mesh = Zoo.get().mesh
        canonical = {
            "w2v_embedding_50000x128":
                cp.resolve_comm_policy((50_000, 128), np.float32,
                                       sparse=True, mesh=mesh,
                                       table="w2v_embedding_50000x128"),
            "logreg_weights_785x1":
                cp.resolve_comm_policy((785, 1), np.float32, sparse=False,
                                       mesh=mesh,
                                       table="logreg_weights_785x1"),
            "wordcount_1":
                cp.resolve_comm_policy((1,), np.int64, sparse=False,
                                       mesh=mesh, table="wordcount_1"),
            "hbm_scale_1Mx128":
                cp.resolve_comm_policy((1_000_000, 128), np.float32,
                                       sparse=False, mesh=mesh,
                                       table="hbm_scale_1Mx128"),
            "override_wins":
                cp.resolve_comm_policy((785, 1), np.float32, sparse=False,
                                       explicit="ps", mesh=mesh,
                                       table="override_wins"),
        }
        evidence = cp.decision_evidence()
    finally:
        mv.shutdown()

    # Per-table AUTO-vs-measured cross check: the logreg weight table's
    # AUTO choice against the measured model-level winner, and word2vec's
    # AUTO mode (hybrid: sparse tables stay ps) against the measured
    # hybrid-vs-ps wall clock.
    lr_fastest = ("allreduce" if logreg["allreduce"]["updates_per_sec"]
                  >= logreg["ps"]["updates_per_sec"] else "ps")
    w2v_fastest = ("hybrid" if w2v["hybrid"]["words_per_sec"]
                   >= w2v["ps"]["words_per_sec"] else "ps")
    return {
        "canonical": canonical,
        "evidence": evidence,
        "auto_matches_fastest": {
            "logreg_weights": {
                "auto": canonical["logreg_weights_785x1"],
                "measured_fastest": lr_fastest,
                "match": canonical["logreg_weights_785x1"] == lr_fastest},
            "w2v_tables": {
                "auto": "hybrid (sparse=ps, dense=allreduce)",
                "measured_fastest": w2v_fastest,
                "match": w2v_fastest == "hybrid"},
        },
    }


def check_witnesses(w2v: dict, logreg: dict,
                    ma_conv: dict | None = None) -> dict:
    """The tier-1 witnesses: the hybrid word2vec run really ran BOTH
    planes, and every leg moved bytes on its own plane."""
    hybrid = w2v["hybrid"]["comm"]
    ma_block = {}
    if ma_conv is not None:
        init = ma_conv["initial_full_loss"]
        ma_block["ma_convergence_all_periods_improve"] = all(
            leg["final_full_loss"] < init for leg in ma_conv["periods"])
    return {
        **ma_block,
        "hybrid_ps_adds_nonzero":
            hybrid.get("comm.ps.bytes", 0) > 0 and
            hybrid.get("comm.ps.ops", 0) > 0,
        "hybrid_allreduce_bytes_nonzero":
            hybrid.get("comm.allreduce.bytes", 0) > 0,
        "ps_leg_ps_bytes_nonzero":
            w2v["ps"]["comm"].get("comm.ps.bytes", 0) > 0,
        "ma_leg_ma_bytes_nonzero":
            w2v["model_average"]["comm"]
            .get("comm.model_average.bytes", 0) > 0,
        "logreg_allreduce_bytes_nonzero":
            logreg["allreduce"]["comm"].get("comm.allreduce.bytes", 0) > 0,
        "logreg_allreduce_bitwise_eq_ps":
            logreg["allreduce_bitwise_eq_ps"],
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dry-run", action="store_true",
                    help="tiny shapes; tier-1 smoke (witnesses asserted)")
    ap.add_argument("--out", default=None,
                    help="record path (default BENCH_COMM.json at the "
                    "repo root on full runs; dry runs only write when "
                    "--out is given)")
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    _log(f"backend: {dev.platform} x {len(jax.devices())}")

    w2v = bench_word2vec_policies(args.dry_run)
    logreg = bench_logreg_policies(args.dry_run)
    ma_conv = bench_ma_convergence(args.dry_run)
    auto = auto_evidence(w2v, logreg)
    witnesses = check_witnesses(w2v, logreg, ma_conv)

    try:
        rev = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             capture_output=True, text=True,
                             cwd=_HERE).stdout.strip() or "?"
    except OSError:     # no git here (the chip tool's copy is not a repo)
        rev = "?"
    record = {
        "metric": "comm_policy_bench", "schema": 1,
        "dry_run": bool(args.dry_run),
        "platform": dev.platform, "cpu_cores": os.cpu_count(),
        "date": time.strftime("%Y-%m-%d %H:%M UTC", time.gmtime()),
        "git": rev,
        "word2vec": w2v, "logreg": logreg,
        "ma_convergence": ma_conv,
        "auto": auto, "witnesses": witnesses,
    }

    out_path = args.out
    if out_path is None and not args.dry_run:
        out_path = os.path.join(_HERE, "BENCH_COMM.json")
    if out_path:
        with open(out_path, "w") as f:
            json.dump(record, f, indent=1)
        _log(f"record written: {out_path}")
    print(json.dumps(record))
    if not all(witnesses.values()):
        _log(f"WITNESS FAILURE: {witnesses}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
