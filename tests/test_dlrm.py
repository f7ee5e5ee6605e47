"""DLRM online-recommender subsystem tests (ISSUE 20, docs/RECSYS.md).

Five contracts:

* **PS-vs-local bitwise parity** — the PS-backed hybrid step and the
  local twin produce IDENTICAL bytes (scores, dense params, every
  embedding table) because both planes run the same jitted programs and
  the same server-side adagrad row math.
* **Sharded-state parity** — the same bitwise equality holds when the
  server runs with ``-state_sharding=on`` over a multi-seat mesh (the
  updater's row math is layout-invariant; test_state_sharding.py proves
  the primitive, this proves the model end-to-end).
* **Streaming AUC** — the histogram estimator tracks the exact
  rank-based AUC within binning error, and nails separable/degenerate
  cases.
* **Serve-during-train staleness bound** — a live-table serving runner
  with a HotRowCache under the real BSP clock serves cached rows only
  within the configured staleness bound; once training advances the
  clock past the bound, the cache misses and the fresh bytes flow.
* **recsys_bench dry run** — the committed BENCH_RECSYS record shape is
  reproducible: training sustained while serving answered lookups with
  zero errors, a monotone freshness curve, int8 quality within
  tolerance.
"""

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

import multiverso_tpu as mv
from multiverso_tpu.core.options import AddOption, MatrixTableOption
from multiverso_tpu.models.dlrm import (DLRMConfig, DLRMModel,
                                        ImpressionStream, StreamConfig,
                                        StreamingAUC, exact_auc)
from multiverso_tpu.serving.cache import HotRowCache

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SMALL = dict(fields=3, vocab=64, embed_dim=8, dense_dim=4,
              bottom_mlp=(8,), top_mlp=(8,))


def _run_parity(steps: int = 4, batch: int = 16,
                rows_on_device: bool = False) -> None:
    """Drive PS model and local twin on identical impression sequences;
    every observable must match bitwise. ``rows_on_device``: which form
    of pull and push the PS model's step takes on this mesh (the twin's
    rows never leave its device)."""
    from multiverso_tpu.telemetry.metrics import get_registry
    device_calls = [get_registry().counter(f"table.group.device_{kind}")
                    for kind in ("pulls", "pushes")]
    before = [c.value for c in device_calls]
    cfg = DLRMConfig(**_SMALL)
    scfg = StreamConfig(fields=cfg.fields, vocab=cfg.vocab,
                        dense_dim=cfg.dense_dim, zipf=1.3, seed=1,
                        drift_every=0)
    ps = DLRMModel(cfg, mode="ps")
    local = DLRMModel(cfg, mode="local")
    s_ps, s_lo = ImpressionStream(scfg), ImpressionStream(scfg)
    for i in range(steps):
        b_ps, b_lo = s_ps.batch(batch), s_lo.batch(batch)
        # zipf=1.3 guarantees duplicate ids within a batch — the
        # combine_duplicate_rows path must agree across planes too.
        loss_ps, sc_ps = ps.step(b_ps.ids, b_ps.dense, b_ps.labels)
        loss_lo, sc_lo = local.step(b_lo.ids, b_lo.dense, b_lo.labels)
        assert loss_ps == loss_lo, (i, loss_ps, loss_lo)
        assert np.array_equal(sc_ps, sc_lo), \
            (i, np.abs(sc_ps - sc_lo).max())
    all_rows = np.arange(cfg.vocab, dtype=np.int32)
    for f in range(cfg.fields):
        t_ps = ps.tables[f].get_rows(all_rows)
        t_lo = local.local_rows(f)
        assert np.array_equal(t_ps, t_lo), \
            (f, np.abs(t_ps - t_lo).max())
    for (w_ps, b_ps_), (w_lo, b_lo_) in zip(ps.dense_params,
                                            local.dense_params):
        assert np.array_equal(np.asarray(w_ps), np.asarray(w_lo))
        assert np.array_equal(np.asarray(b_ps_), np.asarray(b_lo_))
    assert [c.value - b for c, b in zip(device_calls, before)] == \
        [steps * rows_on_device] * 2


@pytest.mark.parametrize("devices", ["mesh_of_8", "one_device"])
def test_ps_local_bitwise_parity(devices):
    """Over the default mesh the step pulls to the host and pushes from it;
    with the tables on the dense programs' one device the rows stay
    there. The same bytes as the twin's either way."""
    import jax
    one = devices == "one_device"
    mv.init([], devices=jax.devices()[:1] if one else None)
    try:
        _run_parity(rows_on_device=one)
    finally:
        mv.shutdown()


def test_ps_local_parity_under_state_sharding():
    """Same parity with the adagrad g2 state SHARDED across a mesh —
    the layout must never leak into the row math (64 rows / 2 server
    seats divide evenly, the =on requirement)."""
    mv.init(["-mesh_shape=server:2,worker:2", "-state_sharding=on"])
    try:
        _run_parity(steps=3)
    finally:
        mv.shutdown()


def test_streaming_auc_tracks_exact():
    rng = np.random.default_rng(7)
    scores = rng.random(4000)
    labels = (rng.random(4000) < scores).astype(np.float32)
    want = exact_auc(scores, labels)
    auc = StreamingAUC(bins=2048)
    for i in range(0, len(scores), 500):     # streamed in chunks
        auc.update(scores[i:i + 500], labels[i:i + 500])
    assert abs(auc.value() - want) < 2e-3, (auc.value(), want)
    assert auc.positives + auc.negatives == 4000


def test_streaming_auc_edges():
    auc = StreamingAUC(bins=64)
    assert math.isnan(auc.value())           # no data
    auc.update(np.array([0.9, 0.8]), np.array([1.0, 1.0]))
    assert math.isnan(auc.value())           # one class only
    auc = StreamingAUC(bins=64)
    auc.update(np.array([0.1, 0.2, 0.8, 0.9]),
               np.array([0.0, 0.0, 1.0, 1.0]))
    assert auc.value() == pytest.approx(1.0)  # perfectly separable
    auc = StreamingAUC(bins=64)
    auc.update(np.array([0.8, 0.9, 0.1, 0.2]),
               np.array([0.0, 0.0, 1.0, 1.0]))
    assert auc.value() == pytest.approx(0.0)  # perfectly inverted


def test_serve_during_train_staleness_bound():
    """BSP-clocked live serving: a staleness-1 cache may serve rows one
    training tick old, but once the clock advances past the bound the
    runner must refetch — and the refetched bytes are the TRAINED rows.
    Adds from both (threaded-worker) seats advance the add clock without
    blocking; serving reads never gate (serving_runner contract)."""
    rows, cols = 32, 4
    mv.init(["-sync=true"], num_local_workers=2)
    try:
        table = mv.create_table(MatrixTableOption(
            num_row=rows, num_col=cols, random_init=True, seed=5,
            updater="adagrad", name="stale_bound", comm_policy="ps"))
        cache = HotRowCache(16, staleness=1)
        runner = table.serving_runner(cache=cache)
        plain = table.serving_runner()          # uncached fresh reader
        keys = np.arange(8, dtype=np.int32)
        batch, lengths = keys[None, :], np.array([8])

        def serve():
            return runner.slice_result(runner.run(batch, lengths), 0, 8)

        def fresh():
            return plain.slice_result(plain.run(batch, lengths), 0, 8)

        def train_tick(value):
            delta = np.full((len(keys), cols), value, np.float32)
            for w in range(2):
                table.add_rows(keys, delta,
                               AddOption(worker_id=w, learning_rate=0.1,
                                         rho=0.1))

        v0 = serve().copy()                      # populates cache @ clock 0
        assert runner.try_cached(keys) is not None

        train_tick(0.5)                          # clock -> 1: bound edge
        hit = runner.try_cached(keys)
        assert hit is not None, "within staleness bound must still hit"
        assert np.array_equal(hit, v0), "bounded hit serves the old bytes"

        train_tick(0.5)                          # clock -> 2: past bound
        assert runner.try_cached(keys) is None, \
            "stale beyond the bound must miss"
        v2 = serve()
        assert not np.array_equal(v2, v0), "refetch must see training"
        assert np.array_equal(v2, fresh()), "refetch serves live bytes"
        # The miss re-populated the cache at the current clock.
        hit = runner.try_cached(keys)
        assert hit is not None and np.array_equal(hit, v2)
    finally:
        mv.shutdown()


def test_recsys_span_taxonomy():
    """ISSUE 20 satellite: the online loop's spans land in the
    critical-path phase taxonomy — a synthetic recsys.step trace
    decomposes with zero unattributed residual."""
    from multiverso_tpu.telemetry.critical_path import (decompose,
                                                        phase_for_span)
    assert phase_for_span("recsys.pull") == "collect"
    assert phase_for_span("recsys.compute") == "device"
    assert phase_for_span("recsys.push") == "dispatch"
    assert phase_for_span("recsys.publish") == "wire"
    assert phase_for_span("recsys.score") == "device"
    assert phase_for_span("recsys.step") is None      # container

    trace = [
        {"name": "recsys.step", "ts": 0, "dur": 10_000, "args": {}},
        {"name": "recsys.pull", "ts": 0, "dur": 2_000,
         "args": {"parent": "r"}},
        {"name": "recsys.compute", "ts": 2_000, "dur": 6_000,
         "args": {"parent": "r"}},
        {"name": "recsys.push", "ts": 8_000, "dur": 2_000,
         "args": {"parent": "r"}},
    ]
    led = decompose(trace, publish=False)
    assert led is not None and led["root"] == "recsys.step"
    assert led["phases"] == {"collect": 2.0, "device": 6.0,
                             "dispatch": 2.0}
    assert led["unattributed_ms"] == 0.0
    assert led["conserved"] is True


def test_recsys_bench_dry_run(tmp_path):
    """The committed-record shape end-to-end: train-while-serve with
    zero serve errors, monotone freshness, int8 within tolerance."""
    out = tmp_path / "BENCH_RECSYS.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "scripts", "recsys_bench.py"),
         "--dry-run", f"--out={out}"],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]

    record = json.loads(out.read_text())
    assert record["schema"] == "multiverso_tpu.bench_recsys/v1"
    assert record["ok"] is True and record["failures"] == []
    assert record["serve"]["errors"] == 0
    assert record["serve"]["requests"] > 0
    assert record["train"]["updates_per_sec"] > 0
    assert record["train"]["publishes"] >= 3
    # Monotone freshness with fresh strictly above frozen: the measured
    # proof that publishing fresher tables buys quality under drift.
    aucs = [lane["auc"] for lane in record["freshness"]]
    assert all(a >= b - 1e-9 for a, b in zip(aucs, aucs[1:])), aucs
    assert aucs[0] > aucs[-1], aucs
    assert record["quant"]["auc_delta"] <= 0.01
    # The trend point landed beside the record for bench_guard.
    history = tmp_path / "BENCH_SERVE_HISTORY.jsonl"
    assert history.exists()
    line = json.loads(history.read_text().splitlines()[-1])
    assert line["benchmark"] == "recsys_online"
