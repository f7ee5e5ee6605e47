#!/usr/bin/env python
"""Benchmark harness — prints ONE JSON line with the headline metric.

Headline: WordEmbedding (skip-gram, negative sampling) training throughput in
words/sec on one TPU chip — the reference's de facto north-star workload
(``Applications/WordEmbedding``; the reference publishes no updates/sec
number, BASELINE.md).

Also measured (reported on stderr): the matrix-table row-update throughput,
the port of ``Test/test_matrix_perf.cpp:32-80`` (1M x 50 float matrix,
10%-row Add/Get sweeps).

Run it on the chip, from the repo root: ``python bench.py``. It measures a
TPU and nothing else: with no TPU it exits non-zero before any leg, a leg
that raises fails the run, and a device kind without published peaks is an
error. (Turning this into the ``workloads`` matrix is ROADMAP A1.)
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# Published per-chip peaks, keyed by ``jax.devices()[0].device_kind`` — the
# yardstick for the utilization model (the reference publishes no
# updates/sec, so a roofline model is the only defensible comparison).
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s
# HBM per chip). A device that is not in the table is an error, not a
# default.
_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes": 819e9},
}


def _peaks(device_kind: str) -> dict:
    if device_kind not in _PEAKS:
        raise SystemExit(
            f"bench.py: no published peaks for device_kind "
            f"{device_kind!r}; add it to _PEAKS with its source "
            f"(known: {sorted(_PEAKS)})")
    return _PEAKS[device_kind]


def _sg_ns_roofline(pairs_per_sec: float, D: int, K: int,
                    param_bytes: int, peaks: dict) -> dict:
    """FLOPs + HBM-traffic model for one sg-ns pair with AdaGrad.

    FLOPs: forward dots u·v_pos / u·v_neg (2(1+K)D), grads wrt u and v
    (4(1+K)D), AdaGrad square/denom/step (~4(2+K)D).
    Bytes: row gathers of w_in/w_out ((2+K) rows) and the f32 AdaGrad
    accumulators, plus read-modify-write scatters of both (2x).
    Word2vec is gather/scatter-bound: MFU is expected to be tiny and HBM
    utilization is the real roofline.
    """
    flops_per_pair = 6 * (1 + K) * D + 4 * (2 + K) * D
    bytes_per_pair = (2 + K) * D * (3 * param_bytes + 3 * 4)
    flops = pairs_per_sec * flops_per_pair
    bw = pairs_per_sec * bytes_per_pair
    return {
        "model_flops_per_sec": round(flops),
        "mfu_vs_bf16_peak": round(flops / peaks["bf16_flops"], 6),
        "model_hbm_bytes_per_sec": round(bw),
        "hbm_utilization": round(bw / peaks["hbm_bytes"], 4),
        # Every record carries the achieved table traffic and its % of
        # the chip's HBM peak.
        "achieved_bytes_per_sec": round(bw),
        "pct_hbm_roofline": round(100.0 * bw / peaks["hbm_bytes"], 2),
    }


def bench_word2vec(peaks: dict) -> tuple:
    """Synthetic-corpus skip-gram training; returns (words/sec, roofline)."""
    import jax

    import multiverso_tpu as mv
    from multiverso_tpu.models.word2vec import (Dictionary, Word2Vec,
                                                Word2VecConfig)

    rng = np.random.default_rng(0)
    vocab_size = 50_000
    n_sent, sent_len = 2_000, 500      # 1M words
    # Zipfian word frequencies like natural text.
    d, zipf = Dictionary.synthetic_zipf(vocab_size, n_sent * sent_len)
    sentences = [rng.choice(vocab_size, size=sent_len, p=zipf)
                 .astype(np.int32) for _ in range(n_sent)]

    def run(param_dtype: str, compact: bool = True,
            batch_size: int = 8192, dispatch_mode=None) -> tuple:
        cfg = Word2VecConfig(embedding_size=128, window=5, negative=5,
                             batch_size=batch_size, sample=1e-3, sg=True,
                             hs=False, optimizer="adagrad", epochs=1,
                             pipeline=True, device_pipeline=True,
                             block_sentences=512, pad_sentence_length=512,
                             param_dtype=param_dtype, compact_pairs=compact,
                             dispatch_mode=dispatch_mode, seed=0)
        w2v = Word2Vec(cfg, d)
        # Warm-up compiles the step outside the timer.
        w2v.train(sentences=sentences[:4])
        w2v.trained_words = 0
        stats = w2v.train(sentences=sentences)
        pair_rate = stats["pairs"] / max(stats["seconds"], 1e-9)
        roof = _sg_ns_roofline(pair_rate, D=128, K=5,
                               param_bytes=2 if param_dtype == "bfloat16"
                               else 4, peaks=peaks)
        _log(f"word2vec[{param_dtype}{'' if compact else ',nocompact'}"
             f"{',b' + str(batch_size) if batch_size != 8192 else ''}"
             f"{',' + dispatch_mode if dispatch_mode else ''}]: "
             f"{stats['words']} words in {stats['seconds']:.2f}s -> "
             f"{stats['words_per_sec']:.0f} words/sec "
             f"({pair_rate:.3g} pairs/sec, "
             f"MFU {roof['mfu_vs_bf16_peak']:.2%}, "
             f"HBM {roof['hbm_utilization']:.1%}, "
             f"loss {stats['loss']:.4f})")
        return stats["words_per_sec"], roof

    headline, roofline = run("float32")
    # Larger chunks may amortize the known in-loop de-optimization
    # (ROADMAP perf #2) as a pure config win: the HEADLINE is the best
    # f32 configuration (the framework's best throughput — per-config
    # numbers all land in the evidence log and the JSON secondary).
    batch_sweep = {"w2v_words_per_sec_b8192": round(headline, 1)}
    for batch in (32_768, 65_536):
        wps, roof = run("float32", batch_size=batch)
        batch_sweep[f"w2v_words_per_sec_b{batch}"] = round(wps, 1)
        if wps > headline:
            headline, roofline = wps, roof
            roofline = dict(roofline, headline_batch_size=batch)
    roofline = dict(roofline, **batch_sweep)
    for dtype, compact in (("bfloat16", True), ("float32", False)):
        wps, _ = run(dtype, compact)
        if dtype == "bfloat16" and compact:
            # bf16 words/sec rides the JSON next to f32: halved
            # gather/scatter bytes is the top roofline lever, so its
            # measured effect must be recorded.
            roofline = dict(roofline, w2v_words_per_sec_bf16=round(wps, 1))

    # Dispatch-mode timing: the same corpus/seed once per explicit mode,
    # so one bench run settles in-graph loop vs host pipeline.
    mode_stats = {}
    for mode in ("in_graph", "pipelined_host"):
        wps, roof = run("float32", dispatch_mode=mode)
        mode_stats[f"w2v_words_per_sec_{mode}"] = round(wps, 1)
        if wps > headline:
            headline = wps
            extras = {k: v for k, v in roofline.items()
                      if k not in roof and k != "headline_batch_size"}
            roofline = dict(roof, **extras, headline_dispatch_mode=mode)
    roofline = dict(roofline, **mode_stats)

    # dp x tp sharded step when more than one device is attached (the
    # multi-chip path; on one chip the loss-identity is covered by
    # tests/test_word2vec.py::test_sharded_dpxtp_matches_single_device_*).
    n_dev = len(jax.devices())
    if n_dev > 1:
        model_ax = 2 if n_dev % 2 == 0 else 1
        # mesh_data must divide block_sentences (512): use the largest
        # power of two that fits, so 3- or 6-device hosts still run.
        data_ax = n_dev // model_ax
        while data_ax & (data_ax - 1):
            data_ax -= 1
        cfg = Word2VecConfig(
            embedding_size=128, window=5, negative=5, batch_size=8192,
            sample=1e-3, sg=True, hs=False, optimizer="adagrad",
            epochs=1, pipeline=True, device_pipeline=True,
            block_sentences=512, pad_sentence_length=512,
            mesh_data=data_ax, mesh_model=model_ax, seed=0)
        w2v = Word2Vec(cfg, d)
        w2v.train(sentences=sentences[:4])
        w2v.trained_words = 0
        stats = w2v.train(sentences=sentences)
        _log(f"word2vec[sharded dp{data_ax}xtp{model_ax}]: "
             f"{stats['words_per_sec']:.0f} words/sec "
             f"(loss {stats['loss']:.4f})")
    return headline, roofline


def bench_big_vocab() -> None:
    """North-star scale check (stderr only): 1M-row vocab tables — the
    reference's headline WordEmbedding model is 21M vocab across a PS
    cluster (`Applications/WordEmbedding/README.md:12`); 1M x 128 x 4
    tables = 2GB HBM exercises the same row-sharded shape on one chip.
    Zero-egress image: corpus is synthetic Zipf (text8-shaped ranks)."""
    import multiverso_tpu as mv
    from multiverso_tpu.models.word2vec import (Dictionary, Word2Vec,
                                                Word2VecConfig)

    rng = np.random.default_rng(3)
    vocab_size = 1_000_000
    n_sent, sent_len = 500, 500      # 250K words: a scale probe, not a fit
    d, zipf = Dictionary.synthetic_zipf(vocab_size, int(1e8))
    sentences = [rng.choice(vocab_size, size=sent_len, p=zipf)
                 .astype(np.int32) for _ in range(n_sent)]
    cfg = Word2VecConfig(embedding_size=128, window=5, negative=5,
                         batch_size=8192, sample=1e-3, sg=True, hs=False,
                         optimizer="adagrad", epochs=1, pipeline=True,
                         device_pipeline=True, block_sentences=512,
                         pad_sentence_length=512, seed=0)
    w2v = Word2Vec(cfg, d)
    w2v.train(sentences=sentences[:4])
    w2v.trained_words = 0
    stats = w2v.train(sentences=sentences)
    _log(f"word2vec[1M vocab]: {stats['words_per_sec']:.0f} words/sec "
         f"(loss {stats['loss']:.4f})")


def bench_matrix_table() -> float:
    """Port of Test/test_matrix_perf.cpp:45-80: 1M x 50 matrix, Add sweeps
    at 10%..100% row coverage with a *different* random row set each
    iteration (the reference varies coverage and rows; identical operands
    would let XLA/dispatch caching flatter the number). Returns updates/sec
    at the reference's 10% point."""
    import jax
    import jax.numpy as jnp

    import multiverso_tpu as mv
    from multiverso_tpu.core.options import AddOption

    NROW, NCOL = 1_000_000, 50
    table = mv.create_table(mv.MatrixTableOption(NROW, NCOL,
                                                 name="perf_matrix"))
    store = table.store
    rng = np.random.default_rng(1)
    opt = AddOption()
    iters = 10
    result = 0.0
    for coverage in (0.1, 0.5, 1.0):
        n_rows = int(NROW * coverage)
        row_sets = [jnp.asarray(rng.integers(0, NROW, size=n_rows)
                                .astype(np.int32)) for _ in range(iters)]
        delta = jnp.ones((n_rows, NCOL), dtype=jnp.float32)
        store.apply_rows(row_sets[0], delta, opt)   # compile
        store.block()
        t0 = time.perf_counter()
        for i in range(iters):
            store.apply_rows(row_sets[i % len(row_sets)], delta, opt)
        store.block()
        dt = time.perf_counter() - t0
        updates_per_sec = iters * n_rows * NCOL / dt
        _log(f"matrix table[{coverage:.0%} rows]: {iters}x{n_rows} row-adds "
             f"in {dt:.3f}s -> {updates_per_sec:.3g} param updates/sec")
        if coverage == 0.1:
            result = updates_per_sec
    # Get-rows leg (includes the device-to-host readback)
    n_get = 100_000
    t0 = time.perf_counter()
    got = table.get_rows(np.asarray(rng.integers(0, NROW, size=n_get),
                                    dtype=np.int32))
    dt = time.perf_counter() - t0
    _log(f"matrix table: {n_get // 1000}K-row Get in {dt:.2f}s "
         f"({got.nbytes / dt / 1e6:.0f} MB/s to host)")
    return result


def bench_serving() -> float:
    """Serving-plane micro-bench (docs/SERVING.md): batched row lookups
    through the full request plane — client socket, batcher coalescing,
    device gather, framed reply — against the perf_matrix-sized table.
    The full closed-loop harness (QPS pacing, deadline distributions,
    overload shed curves) is ``scripts/serve_bench.py``; this leg keeps a
    single steady-state lookup QPS riding along with every chip bench."""
    import threading

    import multiverso_tpu as mv
    from multiverso_tpu.serving import ServingClient, ServingService

    # Deliberately small: tables registered in the Zoo live until
    # shutdown, and the word2vec/roofline legs run after this one — a
    # 1M-row serving table would pin ~200MB of HBM under them. 100K rows
    # still exercises the full plane (socket, batcher, device gather).
    NROW, NCOL, KEYS = 100_000, 32, 16
    table = mv.create_table(mv.MatrixTableOption(NROW, NCOL,
                                                 name="serve_bench_matrix"))
    service = ServingService()
    service.register_runner(table.serving_runner(), buckets=(16,),
                            max_batch=8, max_wait_ms=1.0)
    rng = np.random.default_rng(2)
    n_threads, n_per = 4, 200
    done = []
    lock = threading.Lock()

    def worker(seed):
        cli = ServingClient(*service.address)
        r = np.random.default_rng(seed)
        try:
            for _ in range(n_per):
                cli.lookup(r.integers(0, NROW, KEYS).astype(np.int32),
                           deadline_ms=10_000, timeout=120)
            with lock:
                done.append(n_per)
        finally:
            cli.close()

    warm = ServingClient(*service.address)   # compile outside the window
    warm.lookup(rng.integers(0, NROW, KEYS).astype(np.int32),
                deadline_ms=10_000, timeout=120)
    warm.close()
    t0 = time.perf_counter()
    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    dt = time.perf_counter() - t0
    service.close()
    qps = sum(done) / dt if dt > 0 else 0.0
    _log(f"serving: {sum(done)} x {KEYS}-row lookups over "
         f"{n_threads} clients in {dt:.2f}s -> {qps:.0f} lookups/sec")
    return qps


def bench_xla_rows() -> None:
    """XLA's row scatter-add on the bench table shape (stderr only). The
    Pallas row-DMA and tiled legs it stood beside went with their kernels
    in PR 43; their last reading is in PERF.md 6."""
    import time as _time

    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(2)
    table = jnp.zeros((100_000, 128), dtype=jnp.float32)
    ids = jnp.asarray(np.sort(rng.integers(0, 100_000, size=8192))
                      .astype(np.int32))
    deltas = jnp.ones((8192, 128), dtype=jnp.float32)

    xla = jax.jit(lambda t, i, d: t.at[i].add(d), donate_argnums=0)
    t = xla(table, ids, deltas)
    jax.block_until_ready(t)
    t0 = _time.perf_counter()
    for _ in range(20):
        t = xla(t, ids, deltas)
    jax.block_until_ready(t)
    xla_ms = (_time.perf_counter() - t0) / 20 * 1000
    _log(f"row scatter-add 8192x128 into 100Kx128: XLA {xla_ms:.2f}ms")


def main() -> int:
    import jax

    import multiverso_tpu as mv

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    _log(f"backend: platform={dev.platform} device_kind={dev.device_kind} "
         f"devices={device['count']} "
         f"compile_cache={jax.config.jax_compilation_cache_dir}")
    if dev.platform != "tpu":
        _log("bench.py measures the chip: no TPU here, nothing run "
             "(a CPU number must never stand in for a chip number)")
        return 2
    peaks = _peaks(dev.device_kind)

    mv.init([])
    try:
        updates_per_sec = bench_matrix_table()
        bench_xla_rows()
        serve_qps = bench_serving()
        words_per_sec, roofline = bench_word2vec(peaks)
        bench_big_vocab()
    finally:
        mv.shutdown()

    # Bench records embed a compact telemetry snapshot (no bucket arrays):
    # the run's Dashboard monitors (p50/p95/p99) and gauges travel with the
    # headline number, so regressions diff via scripts/telemetry_report.py
    # against any -telemetry_dir run (docs/OBSERVABILITY.md).
    from multiverso_tpu.telemetry import metrics_snapshot
    telemetry = metrics_snapshot(buckets=False)
    # Three-way CommPolicy legs (scripts/comm_bench.py; docs/DESIGN.md
    # "CommPolicy") — captured AFTER the snapshot because each leg runs
    # under a reset telemetry registry.
    from scripts.comm_bench import (auto_evidence, bench_logreg_policies,
                                    bench_ma_convergence,
                                    bench_word2vec_policies)
    comm_block = {"word2vec": bench_word2vec_policies(False),
                  "logreg": bench_logreg_policies(False)}
    comm_block["auto"] = auto_evidence(comm_block["word2vec"],
                                       comm_block["logreg"])
    comm_block["ma_convergence"] = bench_ma_convergence(False)
    # Sharded-optimizer-state + fused-stateful-kernel legs
    # (scripts/state_bench.py; docs/DESIGN.md "Sharded updater state"). On
    # a 1-device chip the replica axis is absent and the memory leg records
    # that instead of a reduction.
    from scripts.state_bench import (bench_sharded_parity_witness,
                                     bench_state_memory,
                                     bench_stateful_sparse)
    state_block = {
        "state_memory": bench_state_memory(False),
        "stateful_sparse": bench_stateful_sparse(False),
        "sharded_parity": bench_sharded_parity_witness(False),
    }
    print(json.dumps({
        "metric": "w2v_words_per_sec",
        "value": round(words_per_sec, 1),
        "unit": "words/sec/chip",
        "device": device,
        "achieved_bytes_per_sec": roofline.get("achieved_bytes_per_sec"),
        "pct_hbm_roofline": roofline.get("pct_hbm_roofline"),
        "secondary": {"matrix_param_updates_per_sec": round(updates_per_sec),
                      "serve_lookup_qps": round(serve_qps, 1),
                      **roofline,
                      "comm_policy": comm_block,
                      "state_sharding": state_block,
                      "telemetry": telemetry},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
