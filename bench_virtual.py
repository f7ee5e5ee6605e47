#!/usr/bin/env python
"""CPU-relative perf trend (VERDICT r4 #3) — runs with NO chip attached.

Measures, on the 8-device virtual CPU mesh (the same harness the test
suite and ``dryrun_multichip`` use):

1. the sharded dp4 x tp2 word2vec step at a realistic table shape
   (V=1M, D=128 — 0.5 GB per embedding table, the chip-bench shape), and
2. a single-device run of the same model (the ratio sharded/single is the
   machine-load-independent signal), and
3. the 2-process distributed word2vec path (real processes, framed-TCP PS
   wire, ``apps/word2vec_main -world_size=2``) words/sec.

Every number here is **CPU-relative**: it is NEVER comparable to a chip
number. Its only purpose is the round-over-round trend — a regression in
the sharded or distributed path moves these with no chip attached.
Appends one record per run to
BENCH_VIRTUAL_HISTORY.jsonl and rewrites BENCH_VIRTUAL.json; prints ONE
JSON line like bench.py.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import time

# This harness IS the virtual CPU mesh: it sets the environment for itself
# and the phases it spawns before any jax import, and refuses any other
# platform in main() (these numbers must never come from a chip).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags +
                               " --xla_force_host_platform_device_count=8")

import numpy as np  # noqa: E402


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)




def run_mesh_phase(mesh_data: int, mesh_model: int, tag: str) -> float:
    """One Word2Vec run at V=1M, D=128 on the virtual mesh. Runs in its OWN
    process (``--phase``): on a 1-core host, compiling a second program
    while an 8-device in-process collective is still draining starves
    XLA's 40s rendezvous and aborts the process — isolation makes each
    phase's thread pool its own."""
    import multiverso_tpu as mv
    from multiverso_tpu.models.word2vec import (Dictionary, Word2Vec,
                                                Word2VecConfig)

    rng = np.random.default_rng(0)
    vocab_size = 1_000_000
    n_sent, sent_len = 64, 256                    # trend probe, not a fit
    d, zipf = Dictionary.synthetic_zipf(vocab_size, n_sent * sent_len)
    sentences = [rng.choice(vocab_size, size=sent_len, p=zipf)
                 .astype(np.int32) for _ in range(n_sent)]

    # The "single device" leg pins the table-store mesh to ONE device too
    # (as on a real 1-chip host) — otherwise tables shard over all 8
    # virtual devices and every chunked dispatch is an 8-wide in-process
    # collective, which deadlocks XLA's rendezvous on a 1-core box.
    n_mesh = mesh_data * mesh_model
    mv.init([f"-mesh_shape=server:{n_mesh}"] if n_mesh == 1 else [])
    try:
        cfg = Word2VecConfig(embedding_size=128, window=5, negative=5,
                             batch_size=4096, sample=1e-3, sg=True, hs=False,
                             optimizer="adagrad", epochs=1, pipeline=True,
                             device_pipeline=True, block_sentences=32,
                             pad_sentence_length=256, mesh_data=mesh_data,
                             mesh_model=mesh_model, seed=0)
        w2v = Word2Vec(cfg, d)
        w2v.train(sentences=sentences[:2])        # compile outside the timer
        w2v.trained_words = 0
        stats = w2v.train(sentences=sentences)
        _log(f"virtual w2v[{tag}]: {stats['words']} words in "
             f"{stats['seconds']:.1f}s -> {stats['words_per_sec']:.0f} "
             f"words/sec (loss {stats['loss']:.4f})")
        return stats["words_per_sec"]
    finally:
        mv.shutdown()


def run_matrix_phase() -> float:
    """CPU-relative port of the reference perf harness shape
    (Test/test_matrix_perf.cpp:45-80, scaled down): row-update throughput
    through the table layer on the virtual mesh. Catches regressions in
    the apply_rows path (dispatch, dedup, donation) between chip windows.
    Prints updates/sec at 10% coverage as the last stdout line."""
    import jax.numpy as jnp

    import multiverso_tpu as mv
    from multiverso_tpu.core.options import AddOption

    NROW, NCOL, ITERS = 200_000, 50, 5
    mv.init([])
    try:
        table = mv.create_table(mv.MatrixTableOption(NROW, NCOL,
                                                     name="vperf_matrix"))
        store = table.store
        rng = np.random.default_rng(1)
        opt = AddOption()
        n_rows = NROW // 10
        row_sets = [jnp.asarray(rng.integers(0, NROW, size=n_rows)
                                .astype(np.int32)) for _ in range(ITERS)]
        delta = jnp.ones((n_rows, NCOL), dtype=jnp.float32)
        store.apply_rows(row_sets[0], delta, opt)     # compile
        store.block()
        t0 = time.perf_counter()
        for i in range(ITERS):
            store.apply_rows(row_sets[i], delta, opt)
        store.block()
        dt = time.perf_counter() - t0
        ups = ITERS * n_rows * NCOL / dt
        _log(f"virtual matrix[10% of {NROW}x{NCOL}]: "
             f"{ups:.3g} param updates/sec")
        return ups
    finally:
        mv.shutdown()


def _spawn_phase(phase: str, timeout_s: int = 1200):
    """Run one mesh phase as a subprocess; its words/sec is the last
    stdout line. Returns None (never a fake 0.0) when the phase fails,
    hangs, or prints something unparseable — a missing point must not
    masquerade as a 100% regression in the trend line."""
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), f"--phase={phase}"],
            capture_output=True, text=True, timeout=timeout_s,
            cwd=os.path.dirname(os.path.abspath(__file__)))
    except subprocess.TimeoutExpired:
        _log(f"phase {phase} TIMED OUT after {timeout_s}s — no record")
        return None
    sys.stderr.write(proc.stderr[-2000:])
    if proc.returncode != 0:
        _log(f"phase {phase} FAILED rc={proc.returncode} — no record")
        return None
    try:
        return float(proc.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        _log(f"phase {phase} printed no parseable words/sec "
             f"(last stdout: {proc.stdout.strip()[-200:]!r}) — no record")
        return None


def bench_sharded_vs_single() -> dict:
    """dp4 x tp2 on the 8-device mesh vs single-device, V=1M, D=128 —
    each in an isolated subprocess. Failed phases record null, not 0."""
    sharded = _spawn_phase("sharded")
    single = _spawn_phase("single")
    out = {"dp4xtp2_words_per_sec":
           round(sharded, 1) if sharded else None,
           "single_dev_words_per_sec":
           round(single, 1) if single else None}
    if sharded and single:
        out["sharded_over_single"] = round(sharded / single, 3)
    return out


def bench_distributed_2proc(tmp_dir: str) -> dict:
    """Real-2-process distributed path via the app CLI (PS wire traffic)."""
    from multiverso_tpu.models.word2vec import Dictionary

    rng = np.random.default_rng(1)
    vocab, n_sent, sent_len = 2000, 1500, 20
    d, zipf = Dictionary.synthetic_zipf(vocab, n_sent * sent_len)
    corpus = os.path.join(tmp_dir, "corpus.txt")
    with open(corpus, "w") as f:
        for _ in range(n_sent):
            ids = rng.choice(vocab, size=sent_len, p=zipf)
            f.write(" ".join(d.words[i] for i in ids) + "\n")

    out = os.path.join(tmp_dir, "vectors.txt")
    t0 = time.perf_counter()
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "multiverso_tpu.apps.word2vec_main",
             f"-train_file={corpus}", f"-output_file={out}", "-size=64",
             "-window=4", "-negative=5", "-min_count=1", "-epoch=1",
             "-sample=0", "-world_size=2", "-batch_size=2048"],
            capture_output=True, text=True, timeout=900,
            cwd=os.path.dirname(os.path.abspath(__file__)))
    except subprocess.TimeoutExpired:
        _log("distributed 2-proc run TIMED OUT — no record")
        return {"dist2_words_per_sec": None, "dist2_error": "timeout"}
    wall = time.perf_counter() - t0
    text = proc.stdout + proc.stderr
    if proc.returncode != 0:
        _log(f"distributed 2-proc run FAILED rc={proc.returncode}:\n"
             f"{text[-2000:]}")
        return {"dist2_words_per_sec": None, "dist2_error": "nonzero exit"}
    rates = [float(m) for m in
             re.findall(r"rank \d+ trained: (\d+(?:\.\d+)?) words/sec", text)]
    if not rates:
        # A reworded log line must surface as a missing point, never as a
        # fake 0.0 "regression" in the trend record.
        _log("distributed 2-proc run printed no parseable per-rank "
             f"words/sec — no record (tail: {text.strip()[-300:]!r})")
        return {"dist2_words_per_sec": None,
                "dist2_error": "no parseable rank rates"}
    total = round(sum(rates), 1)
    _log(f"virtual w2v[2-process distributed]: per-rank {rates} -> "
         f"{total} words/sec aggregate ({wall:.1f}s wall incl. spawn)")
    return {"dist2_words_per_sec": total,
            "dist2_per_rank": [round(r, 1) for r in rates]}


def main() -> None:
    here = os.path.dirname(os.path.abspath(__file__))
    import tempfile

    import jax
    n_dev = len(jax.devices())
    _log(f"backend: {jax.devices()[0].platform} x {n_dev} (virtual)")
    assert jax.devices()[0].platform == "cpu", "virtual bench must be CPU"

    phase = next((a.split("=", 1)[1] for a in sys.argv[1:]
                  if a.startswith("--phase=")), None)
    if phase == "sharded":
        print(run_mesh_phase(4, 2, "dp4xtp2, 8-dev CPU mesh"))
        return
    if phase == "single":
        print(run_mesh_phase(1, 1, "single CPU device"))
        return
    if phase == "matrix":
        print(run_matrix_phase())
        return

    shard = bench_sharded_vs_single()
    matrix = _spawn_phase("matrix", timeout_s=600)
    with tempfile.TemporaryDirectory() as td:
        dist = bench_distributed_2proc(td)

    record = {
        "metric": "w2v_words_per_sec_virtual_cpu",
        "value": shard["dp4xtp2_words_per_sec"],
        "unit": "words/sec (8-device VIRTUAL CPU mesh — not chip-comparable)",
        "vs_baseline": 0.0,
        "secondary": {**shard, **dist,
                      "matrix_updates_per_sec":
                      round(matrix) if matrix else None,
                      "cpu_cores": os.cpu_count(),
                      "date": time.strftime("%Y-%m-%d %H:%M UTC",
                                            time.gmtime())},
    }
    try:
        rev = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             capture_output=True, text=True,
                             cwd=here).stdout.strip() or "?"
    except OSError:     # no git here (the chip tool's copy is not a repo)
        rev = "?"
    record["secondary"]["git"] = rev

    hist_path = os.path.join(here, "BENCH_VIRTUAL_HISTORY.jsonl")
    prev = None
    if os.path.exists(hist_path):
        try:
            with open(hist_path) as f:
                lines = [json.loads(ln) for ln in f if ln.strip()]
            if lines:
                prev = lines[-1]["value"]
        except (OSError, ValueError, KeyError):
            pass
    if prev and record["value"]:
        record["vs_baseline"] = round(record["value"] / prev, 3)
    with open(hist_path, "a") as f:
        f.write(json.dumps(record) + "\n")
    with open(os.path.join(here, "BENCH_VIRTUAL.json"), "w") as f:
        json.dump(record, f, indent=1)
    print(json.dumps(record))


if __name__ == "__main__":
    main()
