#!/usr/bin/env python
"""Closed-loop load generator for the serving plane — single replica or
a whole fleet.

Single-process mode (default, PR 5's harness): one in-process
ServingService over a synthetic embedding table, N paced client threads.

Fleet mode (``--replicas N``): a router SUBPROCESS (control plane + data
proxy — its own pid, so stitched traces really cross client -> router ->
replica) plus N replica SUBPROCESSES (real process isolation — each
replica owns its GIL and its jax dispatch), driven through a hedged,
ring-routed FleetClient over the wire APIs only. Extras:

* ``--drain-drill``  — rolling-drain every replica mid-load (wire
  ``Fleet_Drain``); the bench counts request failures during the drain
  window (the zero-drop claim is measured, not asserted by fiat).
* ``--fault-drill``  — SIGKILL one replica at half-time; errors and the
  post-kill p99 quantify how well hedging + failover mask the death.
* parity check       — routed lookups (both affinity and split mode)
  compared bitwise against the same seeded table computed locally.
* ``--baseline``     — path to a previous record; the new record embeds
  ``scaleout_vs_baseline`` (aggregate-QPS ratio at equal offered load).
* ``--qps-sweep A:B:STEP`` — one untraced load window per offered-QPS
  point, recorded as ``qps_sweep`` in the SAME record (one
  BENCH_SERVE_HISTORY.jsonl line carries the whole achieved-vs-offered
  knee), with per-point bench-client CPU%% and a WARNING when the knee
  is the bench box, not the server (client CPU-bound).
* ``--pipeline-depth/--cache-rows/--hot-frac`` — the PR-9 serving
  optimizations: device dispatch pipeline depth (auto = measured-latency
  table), hot-row LRU cache size, and a zipf-ish hot-key fraction so the
  cache has something to hit (0 keeps the uniform workload for
  record-to-record comparability).
* distributed tracing — the load runs in INTERLEAVED untraced/traced
  windows (A,B,A,B — drift in box load cancels out of the comparison);
  the record carries both QPS numbers (sampling overhead measured, not
  guessed), a per-stage p50/p95/p99 breakdown derived from the stitched
  traces, the K slowest requests' cross-process stage timelines, and
  the router's ``Fleet_Stats`` cluster rollup.

Every record is written to ``--out`` AND appended to
``BENCH_SERVE_HISTORY.jsonl`` next to it (mirroring
BENCH_VIRTUAL_HISTORY.jsonl), so serving throughput has a trajectory
like the training benches.

    python scripts/serve_bench.py --qps 600 --threads 12 --duration 10
    python scripts/serve_bench.py --replicas 3 --qps 600 --threads 12 \\
        --fault-drill --drain-drill
    python scripts/serve_bench.py --dry-run --replicas 2   # tier-1 smoke
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import platform
import signal
import subprocess
import sys
import tempfile
import threading
import traceback
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


from multiverso_tpu.utils.chips import child_env, take_chip  # noqa: E402


# ---------------------------------------------------------------------------
# Shared plumbing
# ---------------------------------------------------------------------------
def _chaos_shards(args) -> int:
    return 2 if args.dry_run else 4


def _chip_holders(args) -> int:
    """Processes of this run that hold a device at the same time: the
    replicas, this process, and the drills' PS shard seats."""
    seats = 0
    if args.recovery_drill:
        seats = 1
    if args.chaos_drill:
        seats = max(seats, _chaos_shards(args))
    return args.replicas + 1 + seats


def _percentiles(lat_ms) -> dict:
    lat = np.asarray(lat_ms, dtype=np.float64)
    if not lat.size:
        return {"p50": 0.0, "p95": 0.0, "p99": 0.0, "mean": 0.0, "max": 0.0}
    return {"p50": float(np.percentile(lat, 50)),
            "p95": float(np.percentile(lat, 95)),
            "p99": float(np.percentile(lat, 99)),
            "mean": float(lat.mean()), "max": float(lat.max())}


def _metric_families(prefixes) -> dict:
    from multiverso_tpu.telemetry import get_registry
    snap = get_registry().snapshot(buckets=False)
    return {
        "counters": {k: v for k, v in snap["counters"].items()
                     if k.startswith(prefixes)},
        "gauges": {k: v for k, v in snap["gauges"].items()
                   if k.startswith(prefixes)},
        "histograms": {k: v for k, v in snap["histograms"].items()
                       if k.startswith(prefixes)},
    }


def _emit(record: dict, out_path: str) -> None:
    """Write the record and append it to the history trend file beside
    it — every serve_bench run leaves a trajectory point."""
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
    history = os.path.join(os.path.dirname(os.path.abspath(out_path)),
                           "BENCH_SERVE_HISTORY.jsonl")
    with open(history, "a") as f:
        f.write(json.dumps(record, separators=(",", ":")) + "\n")
    print(json.dumps({
        "benchmark": record["benchmark"],
        "replicas": record["config"].get("replicas", 0),
        "offered_qps": record["offered_qps"],
        "achieved_qps": round(record["achieved_qps"], 1),
        "p50_ms": round(record["latency_ms"]["p50"], 3),
        "p95_ms": round(record["latency_ms"]["p95"], 3),
        "p99_ms": round(record["latency_ms"]["p99"], 3),
        "shed_rate": round(record["shed_rate"], 4),
        "out": out_path,
    }))


class _LoadStats:
    """Latency/error accounting shared by the client threads."""

    def __init__(self):
        self.lock = threading.Lock()
        self.latencies: list = []
        self.sheds = 0
        self.errors = 0
        self.sent = 0
        self.error_times: list = []

    def ok(self, dt_s: float) -> None:
        with self.lock:
            self.latencies.append(dt_s * 1e3)
            self.sent += 1

    def shed(self) -> None:
        with self.lock:
            self.sheds += 1
            self.sent += 1

    def error(self, t: float) -> None:
        with self.lock:
            self.errors += 1
            self.error_times.append(t)
            self.sent += 1


def _key_sampler(rows: int, keys_per_req: int, hot_frac: float,
                 hot_keys: int, zipf_alpha: float = 0.0):
    """Per-request key draw: uniform over the table, except a
    ``hot_frac`` fraction of requests draws all its keys from a fixed
    ``hot_keys``-row hot set (the workload skew a hot-row cache exists
    for; 0.0 = the original uniform workload, bitwise-comparable with
    older records).

    ``zipf_alpha > 1`` switches to a Zipf(alpha) key stream over the
    whole table — the power-law shape real user/item traffic follows —
    with frequency ranks mapped through a FIXED permutation so the
    planted hot keys are specific, known row ids scattered across the
    table (``sample.hot_ids``: the true hottest ids, rank order). The
    hot-key sketch recovery witness asserts against these."""
    hot = min(max(int(hot_keys), 1), rows)

    if zipf_alpha > 0.0:
        if zipf_alpha <= 1.0:
            raise SystemExit("--zipf ALPHA must be > 1 (Zipf exponent)")
        perm = np.random.default_rng(0xC0FFEE).permutation(rows) \
            .astype(np.int32)

        def sample(r: np.random.Generator) -> np.ndarray:
            ranks = (r.zipf(zipf_alpha, keys_per_req) - 1) % rows
            return perm[ranks]
        sample.hot_ids = perm[:16].tolist()
        return sample

    def sample(r: np.random.Generator) -> np.ndarray:
        if hot_frac > 0.0 and r.random() < hot_frac:
            return r.integers(0, hot, keys_per_req).astype(np.int32)
        return r.integers(0, rows, keys_per_req).astype(np.int32)
    return sample


def _run_load(do_request, stats: _LoadStats, threads: int, qps: float,
              duration_s: float, rows: int, keys_per_req: int,
              sample_keys=None) -> float:
    """Closed-loop pacing: each thread owns qps/threads; a slow reply
    eats into that thread's budget. Returns the measured elapsed time."""
    from multiverso_tpu.serving import ShedError

    if sample_keys is None:
        sample_keys = _key_sampler(rows, keys_per_req, 0.0, 1)
    interval = threads / max(qps, 1e-6)
    stop_at = [0.0]

    def client_loop(seed: int) -> None:
        r = np.random.default_rng(seed)
        while time.monotonic() < stop_at[0]:
            keys = sample_keys(r)
            t0 = time.monotonic()
            try:
                do_request(keys)
                stats.ok(time.monotonic() - t0)
            except ShedError:
                stats.shed()
            except Exception:  # noqa: BLE001 - the bench classifies, the
                stats.error(time.monotonic())   # drill asserts on counts
            slack = interval - (time.monotonic() - t0)
            if slack > 0:
                time.sleep(slack)

    t_start = time.monotonic()
    stop_at[0] = t_start + duration_s
    workers = [threading.Thread(target=client_loop, args=(i,), daemon=True)
               for i in range(threads)]
    for t in workers:
        t.start()
    for t in workers:
        t.join(timeout=duration_s + 60)
    return time.monotonic() - t_start


# ---------------------------------------------------------------------------
# Distributed-trace analysis: stitched per-stage attribution + slow-request
# timelines (docs/OBSERVABILITY.md "Distributed tracing").
# ---------------------------------------------------------------------------
_STAGE_SPANS = {
    "admit_wait": "serve.admit_wait",
    "batch_form": "serve.batch_form",
    "device": "serve.device",
    "reply": "serve.reply",
    "server_total": "serve.request",
    "proxy": "fleet.proxy",
}


def _set_sample_rate(rate: float) -> None:
    from multiverso_tpu.utils.configure import set_flag
    set_flag("telemetry_sample_rate", float(rate))


def _pcts(vals) -> dict:
    arr = np.asarray(vals, dtype=np.float64)
    if not arr.size:
        return {"count": 0, "p50": 0.0, "p95": 0.0, "p99": 0.0}
    return {"count": int(arr.size),
            "p50": round(float(np.percentile(arr, 50)), 4),
            "p95": round(float(np.percentile(arr, 95)), 4),
            "p99": round(float(np.percentile(arr, 99)), 4)}


def _stage_breakdown(spans) -> dict:
    """Per-stage latency percentiles DERIVED FROM TRACES (not the server
    histograms — these are the sampled exemplars, attributable to
    specific requests). ``proxy_hop`` is client-observed attempt time
    minus server residency: the wire + framing + routing overhead of
    one hop."""
    by_name: dict = {}
    by_span: dict = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append(e["dur"] / 1e3)
        by_span[(e["args"]["trace"], e["args"].get("span"))] = e
    out = {stage: _pcts(by_name.get(name, []))
           for stage, name in _STAGE_SPANS.items()}
    hops = []
    for e in spans:
        if e["name"] != "serve.request":
            continue
        parent = by_span.get((e["args"]["trace"], e["args"].get("parent")))
        if parent is not None and parent["name"] in ("fleet.attempt",
                                                     "serve.client"):
            hops.append(max(parent["dur"] - e["dur"], 0) / 1e3)
    out["proxy_hop"] = _pcts(hops)
    return out


def _slowest_timelines(spans, idx, k: int) -> list:
    """The K slowest stitched requests, each as a cross-process stage
    timeline (the "where did THIS p99 request spend its time" answer).
    Single-span traces are skipped: a tail exemplar whose head decision
    dropped the downstream spans has no stage timeline to show — it
    stays in the stitched file, but the slow-K block is for stages."""
    ranked = sorted(((tid, info) for tid, info in idx.items()
                     if info["parented_ok"] and info["n_spans"] >= 2),
                    key=lambda kv: -kv[1]["dur_us"])[:max(k, 0)]
    out = []
    for tid, info in ranked:
        evs = sorted((e for e in spans if e["args"]["trace"] == tid),
                     key=lambda e: e.get("ts", 0))
        t_base = evs[0]["ts"] if evs else 0
        stages = []
        for e in evs:
            entry = {"name": e["name"], "pid": int(e.get("pid", 0)),
                     "t_rel_ms": round((e["ts"] - t_base) / 1e3, 4),
                     "dur_ms": round(e["dur"] / 1e3, 4)}
            for key in ("member", "attempt", "hedge", "shed"):
                if key in e.get("args", {}):
                    entry[key] = e["args"][key]
            stages.append(entry)
        out.append({"trace_id": tid,
                    "total_ms": round(info["dur_us"] / 1e3, 4),
                    "n_spans": info["n_spans"], "pids": info["pids"],
                    "stages": stages})
    return out


def _trace_smoke(spans, idx) -> dict:
    """The tier-1 acceptance probes: (a) one sampled request stitched to
    a single correctly-parented trace spanning >= 3 processes, (b) a
    hedged request whose duplicate attempts appear as tagged siblings."""
    best = None
    for tid, info in idx.items():
        if not info["parented_ok"]:
            continue
        key = (len(info["pids"]), info["n_spans"])
        if best is None or key > best[0]:
            best = (key, tid, info)
    smoke = {"found": best is not None}
    if best is not None:
        _, tid, info = best
        smoke.update({"trace_id": tid, "n_spans": info["n_spans"],
                      "n_pids": len(info["pids"]),
                      "parented_ok": info["parented_ok"],
                      "root_name": info["root_name"]})
    by_parent: dict = {}
    for e in spans:
        if e["name"] == "fleet.attempt":
            by_parent.setdefault(
                (e["args"]["trace"], e["args"].get("parent")),
                []).append(e)
    hedged = {"found": False}
    for (tid, _parent), sibs in by_parent.items():
        if len(sibs) >= 2 and any(s["args"].get("hedge") for s in sibs):
            hedged = {"found": True, "trace_id": tid,
                      "n_attempts": len(sibs),
                      "hedge_tags": sorted(int(s["args"].get("hedge", 0))
                                           for s in sibs)}
            break
    smoke["hedged_siblings"] = hedged
    return smoke


def _trace_report(tdir: str, k: int, probe_traces=None) -> dict:
    """Stitch every per-process trace under ``tdir`` and distill the
    bench-record tracing block."""
    from multiverso_tpu.telemetry import (analyze_critical_paths,
                                          stitch_traces, trace_index)
    paths = glob.glob(os.path.join(tdir, "trace-*.json"))
    stitched_path = os.path.join(tdir, "stitched.json")
    stitched = stitch_traces(paths, out_path=stitched_path)
    spans = [e for e in stitched["traceEvents"]
             if e.get("ph") == "X" and e.get("args", {}).get("trace")]
    idx = trace_index(spans)
    # Critical-path decomposition (ISSUE 18): every stitched trace's
    # phase ledger, with the conservation rate and the published
    # residual. The probe sub-report restricts to the paced attribution
    # probe's traces — low-load serial requests whose scheduling gaps
    # are small, so conservation there is the acceptance gate.
    cp = analyze_critical_paths(spans, slow_k=k)
    if probe_traces:
        want = set(probe_traces)
        cp["probe"] = analyze_critical_paths(
            [e for e in spans if e["args"]["trace"] in want],
            slow_k=k, publish=False)
    # Witness that the residual actually reached the metrics plane
    # (decompose publishes latency.unattributed per trace).
    from multiverso_tpu.telemetry import get_registry
    cp["published_residual"] = \
        get_registry().histogram("latency.unattributed").snapshot()
    return {
        "n_trace_files": len(paths),
        "n_traces": len(idx),
        "n_spans": len(spans),
        "stitched_path": stitched_path,
        "stage_breakdown": _stage_breakdown(spans),
        "critical_path": cp,
        "slowest": _slowest_timelines(spans, idx, k),
        "trace_smoke": _trace_smoke(spans, idx),
    }


def _export_local_trace(tdir: str) -> None:
    """Write THIS process's span buffer as trace-<pid>.json beside the
    replicas' exporter output, so the stitch sees the client half."""
    from multiverso_tpu.telemetry import export_chrome_trace
    export_chrome_trace(os.path.join(tdir, f"trace-{os.getpid()}.json"))


# ---------------------------------------------------------------------------
# Observability legs (ISSUE 13): steady-state overhead A/B of the
# timeseries+alerts+watchdog plane, a deterministic synthetic SLO-breach
# witness of the burn-rate state machine, and the bench process's own
# watchdog steady-state (trips must stay 0 when nothing is wedged).
# ---------------------------------------------------------------------------
def _observability_ab(args, run_window) -> dict:
    """Interleaved A/B (plain, observed, plain, observed): QPS with the
    alert engine + watchdog monitor running vs without. The watchdog
    BEATS run in both legs (they are unconditional attribute stores in
    the daemon loops); the A/B isolates the ticker + monitor threads —
    the part ``-telemetry_alerts``/``-telemetry_flight`` can turn off."""
    from multiverso_tpu.telemetry import (set_sketch_enabled,
                                          start_alert_engine,
                                          start_watchdog,
                                          stop_alert_engine,
                                          stop_watchdog)
    from multiverso_tpu.telemetry.sketch import get_sketch_hub
    dur = max(args.duration / 2, 1.0)
    n = {"plain": 0, "observed": 0}
    elapsed = {"plain": 0.0, "observed": 0.0}
    # Restore the operator's flag choice after each leg, not a
    # hardcoded True — `-telemetry_sketch=false` must survive the A/B.
    sketch_was_enabled = get_sketch_hub().enabled
    for _round in range(2):
        for mode in ("plain", "observed"):
            if mode == "observed":
                start_alert_engine(interval_s=0.25)
                start_watchdog()
            # The traffic sketch records in-line on the serving hot
            # paths (one list-append per batch/hit); the plain leg turns
            # THAT off too, so the A/B bounds the whole ISSUE-14 plane —
            # appends AND tick-time folding — not just the ticker.
            set_sketch_enabled(mode == "observed" and sketch_was_enabled)
            stats = _LoadStats()
            el = run_window(stats, dur)
            set_sketch_enabled(sketch_was_enabled)
            if mode == "observed":
                stop_alert_engine()
                stop_watchdog()
            n[mode] += len(stats.latencies)
            elapsed[mode] += el
    qps_plain = n["plain"] / elapsed["plain"] if elapsed["plain"] else 0.0
    qps_obs = n["observed"] / elapsed["observed"] \
        if elapsed["observed"] else 0.0
    overhead = round(100.0 * (1.0 - qps_obs / qps_plain), 2) \
        if qps_plain > 0 else 0.0
    return {"qps_plain": round(qps_plain, 1),
            "qps_observed": round(qps_obs, 1),
            "overhead_pct": overhead,
            "windows": 4, "window_s": dur}


def _slo_breach_probe(args) -> dict:
    """Deterministic synthetic SLO breach against the SHIPPED burn-rate
    state machine: manual ticks (no wall clock) drive a clean baseline,
    one tolerated spike, then a sustained breach that must fire within
    the fast window, then a recovery that must resolve. Observations go
    into a histogram OUTSIDE the serve.* family: the record embeds
    `_metric_families(("serve.",))`, and a serve.*-named synthetic
    histogram would fold its fake 500ms tail into every downstream
    serve-latency aggregation of the published record."""
    from multiverso_tpu.telemetry import (AlertManager, BurnRateRule,
                                          TimeseriesStore, get_registry)
    hist_name = "bench.synthetic_slo"
    fast, slow = 5, 30
    store = TimeseriesStore()
    rule = BurnRateRule("serve.slo_burn", hist_name, slo_ms=50.0,
                        budget=0.05, fast_windows=fast, slow_windows=slow,
                        burn_threshold=2.0, min_count=8,
                        for_windows=2, clear_windows=3)
    # shared_telemetry=False: this probe's synthetic firings must not
    # pollute the process's real telemetry.alerts.* counters or the
    # flight ring (a later postmortem would show a fake alert).
    mgr = AlertManager(store, [rule], shared_telemetry=False)
    h = get_registry().histogram(hist_name)
    clock = [0.0]

    def window(good, bad):
        for _ in range(good):
            h.observe(1.0)
        for _ in range(bad):
            h.observe(500.0)
        clock[0] += 1.0
        store.tick(now=clock[0])
        mgr.evaluate()

    for _ in range(slow):
        window(20, 0)
    baseline_quiet = not mgr.active()
    window(0, 20)                       # one spike
    spike_tolerated = not mgr.active()
    windows_to_fire = 0
    while not mgr.active() and windows_to_fire < 2 * slow:
        window(0, 20)
        windows_to_fire += 1
    fired = bool(mgr.active())
    while mgr.active() and clock[0] < 4 * slow:
        window(20, 0)
    return {"synthetic": True,
            "baseline_quiet": baseline_quiet,
            "spike_tolerated": spike_tolerated,
            "fired": fired,
            # +1: the spike window already counts toward the breach.
            "windows_to_fire": windows_to_fire + 1,
            "fast_windows": fast,
            "fired_within_fast_window": fired
            and windows_to_fire + 1 <= fast,
            "resolved": not mgr.active()}


def _hotkey_probe(args, do_request) -> dict:
    """Traffic-microscope recovery witness (ISSUE 14): drive a Zipf key
    stream with KNOWN planted hot keys through the LIVE serving path
    (admission -> cache -> device), then ask the sketch hub which keys
    were hot. The record asserts >= 9 of the 10 planted hottest ids were
    recovered and sketch memory stayed under its configured bound —
    through the full pipeline, cache hits included, not a unit harness."""
    from multiverso_tpu.serving import ShedError
    from multiverso_tpu.telemetry import get_sketch_hub

    alpha = args.zipf if args.zipf > 1.0 else 1.5
    sampler = _key_sampler(args.rows, args.keys_per_req, 0.0, 1,
                           zipf_alpha=alpha)
    planted = [int(k) for k in sampler.hot_ids[:10]]
    hub = get_sketch_hub()
    base = hub.summary("serve.lookup")["keys"]
    r = np.random.default_rng(7)
    n_req = 1500
    deadline = time.monotonic() + 30.0
    sent = 0
    # Unpaced closed loop: the probe wants key VOLUME, not a QPS number.
    while sent < n_req and time.monotonic() < deadline:
        try:
            do_request(sampler(r))
        except ShedError:
            pass        # shed keys still went through admission; fine
        sent += 1
    hub.flush()
    traffic = hub.summary("serve.lookup", topn=max(
        32, 2 * len(planted)))
    recovered = [k for k, _, _ in
                 (tuple(row) for row in traffic["topk"])
                 if k in set(planted)]
    advisor = hub.advise("serve.lookup", max(args.cache_rows, 1))
    from multiverso_tpu.telemetry import get_registry
    reg = get_registry()
    hits = reg.counter("serve.cache.hit").value
    lookups = hits + reg.counter("serve.cache.miss").value \
        + reg.counter("serve.cache.stale").value
    return {
        "alpha": alpha,
        "n_requests": sent,
        "keys_observed": traffic["keys"] - base,
        "planted": planted,
        "recovered": sorted(recovered),
        "recovered_count": len(recovered),
        "top1_share": traffic["top1_share"],
        "memory_bytes": hub.memory_bytes(),
        "memory_bound": hub.memory_bound(),
        "memory_ok": hub.memory_bytes() <= hub.memory_bound(),
        # Cache-headroom advisor next to the measured rate: the CDF-
        # predicted hit rate of the CURRENT -serve_cache_rows capacity.
        "advisor": {
            "cache_rows": args.cache_rows,
            "predicted_hit_rate": advisor.get("predicted_hit_rate", 0.0),
            "predicted_hit_rate_2x": advisor.get(
                "predicted_hit_rate_2x", 0.0),
            "measured_hit_rate": round(hits / lookups, 4)
            if lookups else 0.0,
        },
    }


# ---------------------------------------------------------------------------
# Decode memory hierarchy leg (ISSUE 11 / docs/SERVING.md): paged KV vs
# preallocated users-per-chip at a fixed simulated HBM budget, prefix-cache
# reuse witness, f32/bf16/int8 storage comparison — all with the bitwise
# parity witness embedded (paged f32 tokens == drain-path tokens).
# ---------------------------------------------------------------------------
_HBM_BUDGET_BYTES = 256 * 1024 * 1024    # the simulated per-chip KV budget


def _decode_workload(rng, n_req: int, bucket: int, prefix_frac: float,
                     shared_prompt):
    """Long-tail context lengths: most prompts short, a tail near the
    bucket — the workload where max-shape preallocation wastes the most
    HBM. A ``prefix_frac`` fraction repeats ONE shared prompt (the
    prefix-heavy skew a prompt cache exists for)."""
    prompts = []
    for _ in range(n_req):
        if prefix_frac > 0.0 and rng.random() < prefix_frac:
            prompts.append(list(shared_prompt))
        elif rng.random() < 0.25:               # the long tail
            n = int(rng.integers(max(bucket * 3 // 4, 2), bucket + 1))
            prompts.append(rng.integers(1, 60, n).tolist())
        else:                                    # the short head
            n = int(rng.integers(1, max(bucket // 4, 2)))
            prompts.append(rng.integers(1, 60, n).tolist())
    return prompts


def _drive_decode(batcher, prompts, deadline_ms: float = 120_000):
    t0 = time.monotonic()
    futs = [batcher.submit(np.asarray(p, np.int32),
                           deadline_ms=deadline_ms) for p in prompts]
    toks = [f.wait(300).tolist() for f in futs]
    return toks, time.monotonic() - t0


def _decode_memory_leg(args) -> dict:
    """Runs in-process (the memory hierarchy is engine-level — wire
    framing would only add noise to a bytes-resident comparison)."""
    import jax

    from multiverso_tpu.models.attention_lm import LMConfig, init_params
    from multiverso_tpu.serving import (AttentionLMRunner,
                                        ContinuousBatcher, page_plan,
                                        pages_of)
    from multiverso_tpu.telemetry import get_registry

    small = bool(args.dry_run)
    lm_cfg = LMConfig(vocab=61, dim=32, heads=4, layers=2, seq=128)
    max_new = 4 if small else 8
    max_batch = 4 if small else 8
    bucket = 32 if small else 64
    page = max(4, min(int(args.kv_page), bucket // 8))
    n_req = 12 if small else 48
    prefix_frac = args.prefix_frac if args.prefix_frac > 0 else 0.5

    params = {k: np.asarray(v) for k, v in init_params(
        lm_cfg, jax.random.PRNGKey(0)).items()}
    runner = AttentionLMRunner(params, lm_cfg, max_new=max_new,
                               max_batch=max_batch)
    rng = np.random.default_rng(7)
    shared_prompt = rng.integers(1, 60, bucket // 3).tolist()
    prompts = _decode_workload(rng, n_req, bucket, prefix_frac,
                               shared_prompt)

    # Drain-path reference tokens (the parity oracle) for a sample.
    def solo(prompt):
        mat = np.zeros((max_batch, bucket), np.int32)
        mat[0, :len(prompt)] = prompt
        lens = np.zeros(max_batch, np.int32)
        lens[0] = len(prompt)
        return runner.run(mat, lens)[0].tolist()

    sample = [shared_prompt, prompts[0], prompts[-1]]
    oracle = [solo(p) for p in sample]

    n_logical = pages_of(bucket + max_new, page)
    prealloc_slot_bytes = (2 * lm_cfg.layers * lm_cfg.heads
                           * (bucket + max_new)
                           * (lm_cfg.dim // lm_cfg.heads) * 4)

    # Marginal page cost per request WITH prefix sharing: the first
    # occurrence of the shared prompt pays full backing, every repeat
    # pays only its private gen pages.
    seen = set()
    marginal = []
    for p in prompts:
        plan = page_plan(len(p), bucket, max_new, page)
        key = tuple(p)
        if key in seen:
            marginal.append(len(plan.private))
        else:
            seen.add(key)
            marginal.append(plan.n_backed)

    def _prefix_counters() -> dict:
        snap = get_registry().snapshot(buckets=False)
        return {k: snap["counters"].get(f"serve.prefix.{k}",
                                        {}).get("value", 0)
                for k in ("hits", "prefill_skipped", "shared_pages")}

    def run_one(kv_dtype: str, prefix_entries: int) -> dict:
        pfx0 = _prefix_counters()
        cb = ContinuousBatcher(runner, buckets=(bucket,),
                               max_batch=max_batch, max_queue=4 * n_req,
                               paged=True, page=page, kv_dtype=kv_dtype,
                               prefix_entries=prefix_entries)
        try:
            cb.warmup()
            toks, elapsed = _drive_decode(cb, prompts)
            sample_toks = {}
            for p, want in zip(sample, oracle):
                got = cb.submit(np.asarray(p, np.int32),
                                deadline_ms=120_000).wait(300).tolist()
                sample_toks[str(p[:4])] = {"got": got, "want": want,
                                           "equal": got == want}
        finally:
            cb.close()
        page_bytes = cb.pool.page_bytes()
        backed = [page_plan(len(p), bucket, max_new, page).n_backed
                  for p in prompts]
        avg_user_bytes = float(np.mean(backed)) * page_bytes
        shared_user_bytes = float(np.mean(marginal)) * page_bytes
        users_paged = int(_HBM_BUDGET_BYTES // max(avg_user_bytes, 1))
        users_shared = int(_HBM_BUDGET_BYTES // max(shared_user_bytes, 1))
        users_prealloc = int(_HBM_BUDGET_BYTES // prealloc_slot_bytes)
        return {
            "kv_dtype": kv_dtype,
            "prefix_entries": prefix_entries,
            "decode_qps": round(len(prompts) / elapsed, 1),
            "page_bytes": page_bytes,
            "avg_backed_pages_per_user": round(float(np.mean(backed)), 2),
            "pages_per_slot_max": n_logical,
            # Per-POOL high-water mark: slot-held pages plus whatever
            # the prefix store retains (0 when prefix_entries == 0 —
            # the pure-paging held-bytes witness).
            "pages_used_max": int(cb.pool.max_used),
            "users_per_chip_paged": users_paged,
            "users_per_chip_prefix_shared": users_shared,
            "users_per_chip_prealloc": users_prealloc,
            "users_per_chip_ratio": round(users_paged
                                          / max(users_prealloc, 1), 2),
            "parity_witness": sample_toks,
            # Per-RUN deltas (the registry counters are process-wide).
            "prefix": {k: v - pfx0[k]
                       for k, v in _prefix_counters().items()},
            "tokens": toks,
        }

    # Phase A — pure-paging witness (no prefix store): peak resident
    # pages must undercut max-shape backing for every slot, and the f32
    # tokens must be bitwise-equal to the drain path.
    paging = run_one("f32", prefix_entries=0)
    # Phase B — prefix-reuse witness: the shared-prompt burst must hit.
    prefixed = run_one("f32", prefix_entries=64)
    dtypes = [] if small and args.kv_dtype == "f32" \
        else sorted({args.kv_dtype} - {"f32"})
    if args.decode_bench:
        dtypes = ["bf16", "int8"]
    runs = {"f32": paging, "f32+prefix": prefixed}
    for dt in dtypes:
        runs[dt] = run_one(dt, prefix_entries=0)
    f32_tokens = paging["tokens"]
    for name, run in runs.items():
        if name not in ("f32", "f32+prefix"):
            run["token_rows_equal_f32"] = sum(
                int(a == b) for a, b in zip(run["tokens"], f32_tokens))
        run.pop("tokens", None)
    parity_ok = all(v["equal"]
                    for v in paging["parity_witness"].values())
    witness = {
        "paged_f32_bitwise_vs_drain": parity_ok,
        "prefix_hits_ok": prefixed["prefix"]["hits"] >= 1,
        # HBM held must beat per-slot max-shape: peak pages resident
        # (pure paging, no cache retention) stayed below full backing
        # for every slot.
        "paged_held_ok": paging["pages_used_max"]
        < max_batch * n_logical,
    }
    return {
        "bucket": bucket, "max_new": max_new, "max_batch": max_batch,
        "page": page, "n_requests": n_req,
        "prefix_frac": round(prefix_frac, 3),
        "hbm_budget_bytes": _HBM_BUDGET_BYTES,
        "prealloc_slot_bytes": prealloc_slot_bytes,
        "witness": witness,
        "runs": runs,
    }


# ---------------------------------------------------------------------------
# Single-process mode (PR 5's harness, kept as the no-fleet baseline)
# ---------------------------------------------------------------------------
def run_single(args) -> dict:
    from multiverso_tpu.serving import (HotRowCache, ServingClient,
                                        ServingService, SparseLookupRunner)
    from multiverso_tpu.core.table import ServerStore
    from multiverso_tpu.core.updater import get_updater
    from multiverso_tpu.utils.configure import set_flag
    import jax

    from multiverso_tpu.parallel.mesh import build_mesh, log_backend

    set_flag("serve_wire_dtype", args.wire_dtype)
    if args.overload:
        args.qps *= 20.0
        args.deadline_ms = min(args.deadline_ms, 20.0)

    rng = np.random.default_rng(0)
    mesh = build_mesh(jax.devices(), spec="")
    log_backend(mesh.devices.flat)
    store = ServerStore(
        "serve_bench", (args.rows, args.cols), np.float32,
        get_updater(np.float32, "default"), mesh, num_workers=1,
        init_array=rng.normal(size=(args.rows, args.cols))
        .astype(np.float32))
    buckets = tuple(int(b) for b in args.buckets.split(","))

    cache = HotRowCache(args.cache_rows, args.cache_staleness) \
        if args.cache_rows > 0 else None
    service = ServingService()
    # Constant clock: the bench table is immutable, so every cached row
    # is eternally fresh by construction (a live training table would
    # carry the real BSP clock here).
    service.register_runner(SparseLookupRunner(
        store, clock_fn=lambda: (0.0, 0.0), cache=cache),
                            buckets=buckets,
                            max_batch=args.max_batch,
                            max_wait_ms=args.max_wait_ms,
                            max_queue=args.admission,
                            pipeline_depth=args.pipeline_depth)

    warm = ServingClient(*service.address)
    warm.lookup(rng.integers(0, args.rows, args.keys_per_req)
                .astype(np.int32), deadline_ms=10_000, timeout=120)
    warm.close()

    # Attribution plane (ISSUE 18): the continuous profiler feeds the
    # serve-plane roofline verdict its CPU attribution; priming both
    # plane baselines here makes the end-of-run verdicts classify the
    # whole load window, not a 1s trailing floor.
    from multiverso_tpu.telemetry import start_profiler
    from multiverso_tpu.telemetry.roofline import verdict as _rl_verdict
    start_profiler()
    _rl_verdict("serve")
    _rl_verdict("client")

    clients = [ServingClient(*service.address) for _ in range(args.threads)]
    next_client = [0]
    pick_lock = threading.Lock()
    local = threading.local()

    def do_request(keys):
        cli = getattr(local, "cli", None)
        if cli is None:
            with pick_lock:
                # Modulo: the load now runs in TWO phases (untraced +
                # traced), each with fresh threads — the second phase's
                # threads must wrap back onto the same client pool.
                local.cli = cli = clients[next_client[0] % len(clients)]
                next_client[0] += 1
        cli.lookup(keys, deadline_ms=args.deadline_ms, timeout=30)

    # Interleaved untraced/traced load windows (A,B,A,B): traced-vs-
    # untraced QPS measures sampling overhead with slow drift in box
    # load cancelled out, not baked into one side of the comparison.
    from multiverso_tpu.telemetry import TraceBuffer, get_trace_buffer
    get_trace_buffer().set_capacity(TraceBuffer.EXPORT_CAPACITY)
    sampler = _key_sampler(args.rows, args.keys_per_req, args.hot_frac,
                           args.hot_keys, zipf_alpha=args.zipf)
    stats_un, stats = _LoadStats(), _LoadStats()
    elapsed_un = elapsed = 0.0
    cpu0 = _proc_cpu_s(os.getpid())
    for _half in range(2):
        _set_sample_rate(0.0)
        elapsed_un += _run_load(do_request, stats_un, args.threads,
                                args.qps, args.duration / 2, args.rows,
                                args.keys_per_req, sampler)
        _set_sample_rate(args.sample_rate)
        elapsed += _run_load(do_request, stats, args.threads, args.qps,
                             args.duration / 2, args.rows,
                             args.keys_per_req, sampler)
    qps_untraced = len(stats_un.latencies) / elapsed_un \
        if elapsed_un > 0 else 0.0
    cpu_pct = round(100 * (_proc_cpu_s(os.getpid()) - cpu0)
                    / max(elapsed_un + elapsed, 1e-6), 1)
    _set_sample_rate(0.0)

    # Pipeline-overlap + cache-hit probes (the tier-1 smoke's acceptance
    # witnesses): a concurrent burst that must reach window depth >= 2,
    # and a repeated-key pair whose second lookup must answer host-side.
    probe = _overlap_probe(args, clients[0], rng)

    sweep = None
    if args.qps_sweep:
        def at_qps(q, stats_s, dur):
            return _run_load(do_request, stats_s, args.threads, q, dur,
                             args.rows, args.keys_per_req, sampler)
        sweep = _run_qps_sweep(args, at_qps,
                               lambda: {"bench": _proc_cpu_s(os.getpid())},
                               cores=os.cpu_count())

    # Observability legs (ISSUE 13): steady-state overhead A/B of the
    # alerts+watchdog plane against the LIVE service, plus the
    # deterministic synthetic burn-rate witness.
    observability = None
    if args.dry_run or args.obs_ab:
        from multiverso_tpu.telemetry import get_registry
        trips0 = get_registry().counter("telemetry.watchdog.trips").value

        def ab_window(stats_w, dur):
            return _run_load(do_request, stats_w, args.threads, args.qps,
                             dur, args.rows, args.keys_per_req, sampler)
        observability = {
            "ab": _observability_ab(args, ab_window),
            "attribution_ab": _attribution_ab(args, ab_window),
            "slo_breach": _slo_breach_probe(args),
            # Stuck-free steady state: the bench process runs the
            # batcher/collector/exporter loops — none may have tripped.
            "watchdog": {
                "trips": get_registry().counter(
                    "telemetry.watchdog.trips").value - trips0,
                "loops": float(get_registry().gauge(
                    "telemetry.watchdog.loops").last),
            },
        }
        start_profiler()    # the A/B's last leg stopped the singleton;
                            # the end-of-run roofline verdict wants it

    # Hot-key sketch recovery + cache-headroom advisor witness
    # (ISSUE 14): planted-Zipf stream through the live serving path.
    hotkeys = None
    if args.dry_run or args.zipf > 0.0:
        hotkeys = _hotkey_probe(args, do_request)

    # Critical-path attribution probe (ISSUE 18) — LAST load against the
    # live service, so its paced traces land at the tail of the span
    # buffer, then the per-plane roofline verdicts over the whole run.
    probe_traces = _attribution_probe(args, clients[0])
    roofline = {"serve": _rl_verdict("serve"),
                "client": _rl_verdict("client")}
    # Snapshot the tail exemplars NOW: the decode leg below runs its own
    # (untraced) requests through the serve reservoir and its ~100 ms
    # decode batches would evict every resolvable lookup exemplar.
    from multiverso_tpu.telemetry import exemplar_payload, profile_state
    exemplars = exemplar_payload("serve")

    for cli in clients:
        cli.close()
    service.close()

    # Decode memory hierarchy leg AFTER the lookup service closed (no
    # GIL contention into the bytes-resident comparison). Dry-run always
    # runs it (the prefix-burst + paged-held tier-1 witnesses);
    # --decode-bench runs the full f32/bf16/int8 comparison.
    decode_block = None
    if args.dry_run or args.decode_bench:
        decode_block = _decode_memory_leg(args)

    record = _make_record("serve_lookup", args, stats, elapsed,
                          _metric_families(("serve.",)))
    record["process_cpu_pct"] = {"bench": cpu_pct}
    record["pipeline"] = probe
    # Attribution embeds (ISSUE 18): per-plane bound verdicts, the
    # slowest-request exemplar ledgers (trace ids resolvable against the
    # stitched file below), and the process profile aggregate.
    record["roofline"] = roofline
    record["exemplars"] = exemplars
    prof = profile_state()
    if prof is not None:
        record["profile"] = {k: v for k, v in prof.items()
                             if k != "stacks"}
        record["profile"]["n_stacks"] = len(prof.get("stacks", {}))
    if observability is not None:
        record["observability"] = observability
    if hotkeys is not None:
        record["hotkeys"] = hotkeys
    if sweep is not None:
        record["qps_sweep"] = sweep
    if decode_block is not None:
        record["decode_memory"] = decode_block
    if args.dry_run:
        # graftsan witness leg AFTER the serve.* family snapshot above,
        # so its toy locks never leak into the bench's own metrics.
        record["lockwitness"] = _lockwitness_leg(args)
    if args.recovery_drill:
        # Single mode runs the PS-side halves only (the replica
        # self-heal leg needs a fleet).
        record["recovery"] = {"wal": _wal_recovery_leg(args),
                              "wal_overhead": _wal_overhead_ab(args)}
    tdir = args.telemetry_dir or tempfile.mkdtemp(prefix="serve_trace_")
    _export_local_trace(tdir)
    record["tracing"] = _tracing_block(args, tdir, record["achieved_qps"],
                                       qps_untraced, probe_traces)
    return record


def _overlap_probe(args, client, rng) -> dict:
    """Drive the service hard enough to PROVE the optimizations engaged:
    a 4x-max_batch concurrent burst (the dispatch window must reach
    occupancy >= 2 — pipelining, not the serialized path) and a repeated
    identical lookup (the second must count a cache hit when the cache
    is on). The smoke asserts on this block so neither can silently
    regress."""
    from multiverso_tpu.telemetry import get_registry

    from multiverso_tpu.serving import ShedError

    keys = rng.integers(0, args.rows, args.keys_per_req).astype(np.int32)
    results = [client.request_async(keys, deadline_ms=10_000)
               for _ in range(max(4 * args.max_batch, 16))]
    for res in results:
        try:
            res.wait(60)
        except ShedError:
            pass    # a burst past the admission bound sheds by design
    # Same keys twice back-to-back: miss-populate, then a pure host hit.
    client.lookup(keys, deadline_ms=10_000, timeout=60)
    client.lookup(keys, deadline_ms=10_000, timeout=60)
    reg = get_registry()
    g = reg.gauge("serve.pipeline.inflight").snapshot()
    return {
        "depth": float(reg.gauge("serve.pipeline.depth").last),
        "max_inflight": float(g["max"]),
        "backpressure": reg.counter("serve.pipeline.backpressure").value,
        "cache_hits": reg.counter("serve.cache.hit").value,
        "cache_misses": reg.counter("serve.cache.miss").value,
        "overlap_ok": bool(g["max"] >= 2.0),
        "cache_hit_ok": bool(reg.counter("serve.cache.hit").value >= 1
                             or args.cache_rows <= 0),
    }


def _parse_sweep(spec: str):
    try:
        lo, hi, step = (int(x) for x in spec.split(":"))
        ok = lo > 0 and hi >= lo and step > 0
    except ValueError:
        ok = False
    if not ok:
        raise SystemExit(f"bad --qps-sweep '{spec}' (want A:B:STEP, e.g. "
                         "100:700:100)")
    return list(range(lo, hi + 1, step))


def _run_qps_sweep(args, run_at_qps, cpu_probe, cores: int) -> dict:
    """One short untraced load window per offered-QPS point; the whole
    achieved-vs-offered curve lands in ONE history record. Each point
    carries the bench client's CPU%% so the record can say when the KNEE
    is the bench box, not the server (ROADMAP 2(a): on a small host the
    client saturates first and the curve measures the box)."""
    points = []
    dur = max(2.0, args.duration / 2) if not args.dry_run else 1.0
    for offered in _parse_sweep(args.qps_sweep):
        stats = _LoadStats()
        c0 = cpu_probe()
        elapsed = run_at_qps(float(offered), stats, dur)
        c1 = cpu_probe()
        cpu_pct = {k: round(100 * (c1[k] - c0[k]) / max(elapsed, 1e-6), 1)
                   for k in c1}
        with stats.lock:
            lat = list(stats.latencies)
            sheds, errs = stats.sheds, stats.errors
        pct = _percentiles(lat)
        achieved = len(lat) / elapsed if elapsed > 0 else 0.0
        points.append({
            "offered_qps": offered,
            "achieved_qps": round(achieved, 1),
            "ratio": round(achieved / offered, 3) if offered else 0.0,
            "p50_ms": round(pct["p50"], 3),
            "p99_ms": round(pct["p99"], 3),
            "n_shed": sheds, "n_error": errs,
            "cpu_pct": cpu_pct,
        })
    # Knee = end of the CONTIGUOUS passing prefix: a noisy recovery
    # after the first failing point must not inflate the record.
    knee = None
    for p in points:
        if p["ratio"] < 0.9:
            break
        knee = p["offered_qps"]
    out = {"points": points, "knee_qps": knee,
           "knee_ratio_threshold": 0.9}
    # Client-bound warning, via the roofline classifier (replaces the
    # PR-9 ad-hoc CPU%% threshold): at the first point past the knee,
    # classify the bench client's plane from its measured CPU — a
    # ``host`` verdict while every server-side process has headroom
    # means the measured ceiling is the load generator/box, not the
    # serving plane.
    from multiverso_tpu.telemetry.roofline import classify
    past = [p for p in points if knee is None
            or p["offered_qps"] > knee] or points[-1:]
    if past:
        p = past[0]
        bench = p["cpu_pct"].get("bench", 0.0)
        servers = [v for k, v in p["cpu_pct"].items() if k != "bench"]
        bound = classify({"qps": p["achieved_qps"],
                          "host_cpu": bench / 100.0})
        out["client_bound"] = bound
        if bound == "host" and (not servers or max(servers) < 80.0):
            out["warning"] = (
                f"bench client host-bound at {p['offered_qps']} offered "
                f"QPS (roofline verdict 'host': client {bench}%, max "
                f"server {max(servers) if servers else 'n/a'}% of one "
                f"core, {cores} cores): the knee measures the bench "
                "box, not the serving plane")
    return out


def _tracing_block(args, tdir: str, qps_traced: float,
                   qps_untraced: float, probe_traces=None) -> dict:
    overhead = round(100.0 * (1.0 - qps_traced / qps_untraced), 2) \
        if qps_untraced > 0 else 0.0
    return {
        "sample_rate": args.sample_rate,
        "qps_traced": round(qps_traced, 1),
        "qps_untraced": round(qps_untraced, 1),
        "overhead_pct": overhead,
        "telemetry_dir": tdir,
        **_trace_report(tdir, args.slow_k, probe_traces),
    }


def _attribution_probe(args, client, n: int = 40) -> list:
    """Paced, guaranteed-sampled serial requests for the critical-path
    conservation witness. Serial + paced matters: the ledger's phases
    are measured spans, so the residual is pure scheduling gap — under
    concurrent load those gaps are queueing someone else caused, while
    here they must stay under the conservation tolerance. Returns the
    probe requests' trace ids (the ``tracing.critical_path.probe``
    sub-report restricts to exactly these)."""
    from multiverso_tpu.serving import ShedError
    _set_sample_rate(1.0)
    rng = np.random.default_rng(23)
    traces = []
    for _ in range(n):
        keys = rng.integers(0, args.rows, args.keys_per_req) \
            .astype(np.int32)
        try:
            res = client.request_async(keys, deadline_ms=10_000)
            res.wait(60)
        except ShedError:
            continue
        ctx = getattr(res, "ctx", None)
        if ctx is not None and getattr(ctx, "sampled", False):
            traces.append(ctx.trace_hex)
        time.sleep(0.004)
    # Tail-exemplar leg: a SAMPLED concurrent burst. The burst queues on
    # itself, so its stragglers land in the slowest-N reservoir with
    # trace ids the stitched file can resolve — the "why was p99 slow"
    # evidence chain from exemplar to cross-process timeline. Burst
    # traces stay OUT of the conservation probe set: their residual is
    # send-lock convoy the serial probe exists to avoid.
    keys = rng.integers(0, args.rows, args.keys_per_req).astype(np.int32)
    burst = [client.request_async(keys, deadline_ms=10_000)
             for _ in range(max(8 * args.max_batch, 64))]
    for res in burst:
        try:
            res.wait(60)
        except ShedError:
            pass    # past the admission bound: shedding is the design
    _set_sample_rate(0.0)
    return traces


def _attribution_ab(args, run_window) -> dict:
    """Interleaved A/B (plain, attributed, plain, attributed): QPS with
    the continuous profiler + exemplar reservoirs running vs without.
    The unconditional stage histograms run in BOTH legs (they predate
    this plane); the A/B isolates what ``-telemetry_profile`` /
    ``-telemetry_exemplars`` can turn off — the acceptance bound is
    <= 1% on a quiet box."""
    from multiverso_tpu.telemetry import (set_exemplars_enabled,
                                          start_profiler, stop_profiler)
    dur = max(args.duration / 2, 1.0)
    n = {"plain": 0, "attributed": 0}
    elapsed = {"plain": 0.0, "attributed": 0.0}
    for _round in range(2):
        for mode in ("plain", "attributed"):
            set_exemplars_enabled(mode == "attributed")
            if mode == "attributed":
                start_profiler()
            stats = _LoadStats()
            el = run_window(stats, dur)
            if mode == "attributed":
                stop_profiler()
            set_exemplars_enabled(None)
            n[mode] += len(stats.latencies)
            elapsed[mode] += el
    qps_plain = n["plain"] / elapsed["plain"] if elapsed["plain"] else 0.0
    qps_attr = n["attributed"] / elapsed["attributed"] \
        if elapsed["attributed"] else 0.0
    overhead = round(100.0 * (1.0 - qps_attr / qps_plain), 2) \
        if qps_plain > 0 else 0.0
    return {"qps_plain": round(qps_plain, 1),
            "qps_attributed": round(qps_attr, 1),
            "overhead_pct": overhead,
            "windows": 4, "window_s": dur}


# ---------------------------------------------------------------------------
# Fleet mode: router AND replicas as subprocesses (three distinct pids on
# the data path — the stitched traces prove client -> router -> replica)
# ---------------------------------------------------------------------------
def _spawn_router(args, tdir: str, addr_file: str,
                  port: int = 0) -> subprocess.Popen:
    lifetime = args.duration * 3 + 300  # three load windows
    cmd = [sys.executable, "-m", "multiverso_tpu.apps.fleet_main",
           "-fleet_role=router",
           f"-fleet_heartbeat_ms={args.heartbeat_ms}",
           f"-fleet_liveness_misses={args.liveness_misses}",
           "-fleet_proxy=true",
           f"-fleet_addr_file={addr_file}",
           f"-serve_duration={lifetime}",
           f"-telemetry_dir={tdir}",
           "-telemetry_interval=2",
           # Fast alert windows: the fault drill asserts the router's
           # heartbeat-loss alert within a 4s dry-run drill window.
           "-telemetry_alerts=true", "-telemetry_flight=true",
           "-telemetry_ts_interval=0.25"]
    if port:
        # The router-kill round respawns on the SAME port so replicas
        # and clients reconnect through connect_with_backoff unchanged.
        cmd.append(f"-fleet_port={port}")
    if getattr(args, "hotkey_replicas", 0):
        cmd.append(f"-fleet_hotkey_replicas={args.hotkey_replicas}")
    if getattr(args, "rebalance", False):
        # Drill-friendly knobs: the imbalance streak + cooldown must fit
        # inside one bench window, not an operator's steady state.
        cmd += ["-fleet_rebalance=true",
                "-fleet_rebalance_ratio=1.4",
                "-fleet_rebalance_windows=2",
                "-fleet_rebalance_cooldown_s=2.0",
                "-fleet_rebalance_vnodes=8"]
    return subprocess.Popen(cmd, cwd=_REPO)


def _spawn_replica(args, router_addr, idx: int,
                   tdir: str) -> subprocess.Popen:
    lifetime = args.duration * 3 + 300  # generous: parent stops at exit
    # --slo-drill: replica-0 gets an unreachable SLO so its burn-rate
    # alert PROVABLY fires under real load and rides its heartbeat into
    # Fleet_Stats/fleet_top (the end-to-end alert-shipping witness).
    slo_ms = 0.01 if args.slo_drill and idx == 0 else None
    cmd = [sys.executable, "-m", "multiverso_tpu.apps.fleet_main",
           "-fleet_role=replica",
           f"-fleet_router={router_addr[0]}:{router_addr[1]}",
           f"-fleet_member_id=replica-{idx}",
           f"-fleet_synthetic={args.rows}x{args.cols}@0",
           f"-serve_buckets={args.buckets}",
           f"-serve_max_batch={args.max_batch}",
           f"-serve_max_wait_ms={args.max_wait_ms}",
           f"-serve_admission={args.admission}",
           f"-serve_wire_dtype={args.wire_dtype}",
           f"-serve_pipeline_depth={args.pipeline_depth}",
           f"-serve_cache_rows={args.cache_rows}",
           f"-serve_cache_staleness={args.cache_staleness}",
           f"-serve_cache_mem_budget={getattr(args, 'cache_mem_budget', 0)}",
           f"-serve_duration={lifetime}",
           f"-telemetry_dir={tdir}",
           "-telemetry_interval=2",
           "-telemetry_alerts=true", "-telemetry_flight=true",
           "-telemetry_ts_interval=0.25",
           # Attribution plane (ISSUE 18): the replica's continuous
           # profiler feeds its serve-plane roofline verdict, which
           # ships on the heartbeat into Fleet_Stats.
           "-telemetry_profile=true"]
    if slo_ms is not None:
        cmd.append(f"-serve_slo_ms={slo_ms}")
    return subprocess.Popen(
        cmd, cwd=_REPO,
        env=child_env(idx, _chip_holders(args), "serve_bench"))


def _wait_addr_file(path: str, procs, timeout_s: float = 120.0):
    deadline = time.monotonic() + timeout_s
    while not os.path.exists(path):
        if any(p.poll() is not None for p in procs):
            raise RuntimeError("a fleet process exited during bring-up")
        if time.monotonic() > deadline:
            raise RuntimeError(f"router never wrote {path}")
        time.sleep(0.05)
    host, port = open(path).read().split(":")
    return (host, int(port))


def _shutdown_procs(procs) -> None:
    """SIGINT first — the graceful path that lets each process write its
    final telemetry snapshot + trace — then escalate."""
    for p in procs:
        if p.poll() is None:
            p.send_signal(signal.SIGINT)
    deadline = time.monotonic() + 30
    for p in procs:
        if p.poll() is None:
            try:
                p.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                p.terminate()
    for p in procs:
        if p.poll() is None:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


def _proc_cpu_s(pid: int) -> float:
    """Cumulative user+sys CPU seconds of one process (linux /proc)."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            parts = f.read().split()
        return (int(parts[13]) + int(parts[14])) / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return 0.0


def _run_fleet_load(fleet, stats: _LoadStats, slots: int, qps: float,
                    duration_s: float, rows: int, keys_per_req: int,
                    deadline_ms: float, sample_keys=None) -> float:
    """Slot-based closed loop: ``slots`` virtual clients, each firing its
    next request when the previous completes (or after its pacing slack).
    Initiation work spreads across the reply reader threads instead of a
    thread per virtual client — at a few hundred QPS on a small box, a
    12-thread pacing pool spends more CPU convoying on the GIL than
    serving requests (measured: the thread model peaked ~200 QPS where
    this model reaches ~550 on the same hardware)."""
    from multiverso_tpu.fleet.hedge import default_scheduler
    from multiverso_tpu.serving import ShedError

    if sample_keys is None:
        sample_keys = _key_sampler(rows, keys_per_req, 0.0, 1)
    sched = default_scheduler()
    interval = slots / max(qps, 1e-6)
    lock = threading.Lock()
    live = [slots]
    all_done = threading.Event()
    rngs = [np.random.default_rng(1000 + i) for i in range(slots)]
    t_start = time.monotonic()
    end_at = t_start + duration_s

    def retire():
        with lock:
            live[0] -= 1
            if live[0] == 0:
                all_done.set()

    def fire(slot: int):
        if time.monotonic() >= end_at:
            retire()
            return
        keys = sample_keys(rngs[slot])
        ts = time.monotonic()

        def cb(result, _t=ts, _s=slot):
            now = time.monotonic()
            if isinstance(result, ShedError):
                stats.shed()
            elif isinstance(result, BaseException):
                stats.error(now)
            else:
                stats.ok(now - _t)
            slack = interval - (now - _t)
            if slack > 0:
                sched.call_later(slack, lambda: fire(_s))
            else:
                fire(_s)

        try:
            fleet.lookup_async(keys, cb, deadline_ms)
        except Exception:  # noqa: BLE001 - a fully-dead fleet still ends
            stats.error(time.monotonic())   # the run instead of hanging it
            retire()

    for s in range(slots):
        fire(s)
    all_done.wait(duration_s + 120)
    return time.monotonic() - t_start


def _parity_check(fleet, table, rows: int, keys_per_req: int) -> bool:
    """Routed lookups — affinity AND split — must be bitwise-equal to a
    direct gather of the same seeded table."""
    rng = np.random.default_rng(7)
    for split in (False, True):
        for _ in range(8):
            keys = rng.integers(0, rows, keys_per_req).astype(np.int32)
            got = fleet.lookup(keys, deadline_ms=10_000, split=split,
                               timeout=60)
            if got.shape != table[keys].shape or \
                    not np.array_equal(got, table[keys]):
                return False
    return True


def _wire_rolling_drain(router_addr, fleet, timeout_s: float = 60.0) -> bool:
    """Operator-path rolling drain: trigger over ``Fleet_Drain`` and poll
    the routing table's monotonic per-member ``drains_completed`` — the
    bench drives the fleet exactly the way an operator would."""
    from multiverso_tpu.fleet import request_drain
    before = {m["id"]: int(m.get("drains_completed", 0))
              for m in fleet.routing().members}
    if not before:
        return False
    ack = request_drain(router_addr, timeout_s=timeout_s)
    if not ack.get("started"):
        return False
    deadline = time.monotonic() + timeout_s * (len(before) + 1)
    while time.monotonic() < deadline:
        table = {m["id"]: m for m in fleet.refresh().members}
        pending = [mid for mid in before
                   if mid in table
                   and (int(table[mid].get("drains_completed", 0))
                        <= before[mid] or table[mid].get("draining"))]
        if not pending:
            return True
        time.sleep(0.05)
    return False


def _trace_smoke_requests(args, fleet, router_addr) -> None:
    """A few guaranteed-sampled requests for the stitched-trace probes:
    a ring-SPLIT lookup (fans across both replicas), a forced-hedge
    lookup (duplicate attempts as tagged siblings), and a PROXIED lookup
    through the router subprocess (client -> router -> replica: three
    distinct pids in one trace)."""
    from multiverso_tpu.fleet import FleetClient
    from multiverso_tpu.serving import ServingClient
    _set_sample_rate(1.0)
    rng = np.random.default_rng(11)
    keys = rng.integers(0, args.rows, args.keys_per_req).astype(np.int32)
    for _ in range(3):
        fleet.lookup(keys, deadline_ms=10_000, split=True, timeout=60)
    hedger = FleetClient(router_addr, hedge=0.0,
                         refresh_s=args.heartbeat_ms / 1e3,
                         rpc_timeout_ms=args.rpc_timeout_ms or None)
    try:
        for _ in range(4):
            hedger.lookup(keys, deadline_ms=10_000, timeout=60)
    finally:
        hedger.close()
    proxy_cli = ServingClient(*router_addr)
    try:
        for _ in range(3):
            proxy_cli.lookup(keys, deadline_ms=10_000, timeout=60)
    finally:
        proxy_cli.close()


def _await_fleet_alert(router_addr, match, timeout_s: float = 15.0):
    """Poll the router's rollup until ``match(stats)`` is truthy; returns
    ``(fired, last_stats)`` — the one poll-fetch-retry loop behind every
    alert-shipping witness (heartbeat loss, SLO burn)."""
    from multiverso_tpu.fleet import fetch_fleet_stats
    deadline = time.monotonic() + timeout_s
    last = None
    while time.monotonic() < deadline:
        try:
            st = fetch_fleet_stats(router_addr)
        except Exception:  # noqa: BLE001 - transient mid-drill; retry
            time.sleep(0.2)
            continue
        last = st
        if match(st):
            return True, st
        time.sleep(0.2)
    return False, last


def _await_heartbeat_loss(router_addr, timeout_s: float = 15.0) -> dict:
    """Until the router's own alert engine reports the heartbeat-loss
    alert the kill must have caused (the dead replica cannot report its
    own absence — detection lives on the router)."""
    fired, st = _await_fleet_alert(
        router_addr,
        lambda st: any(a.get("name") == "fleet.heartbeat_loss"
                       for a in st.get("router_alerts", [])),
        timeout_s=timeout_s)
    return {"fired": fired,
            "router_alerts": (st or {}).get("router_alerts", [])}


def _skew_drill(args, fleet, router_addr) -> dict:
    """Shard-imbalance detection witness (ISSUE 14): drive a window
    where EVERY request carries the same key set, so ring affinity
    routes the whole stream to one owner replica. The replicas'
    heartbeat-shipped key rates diverge, the router's sweep publishes a
    p99-to-mean shard-load ratio near the replica count, and its
    ``fleet.shard_imbalance`` rule must FIRE and ship into
    ``Fleet_Stats`` (``router_alerts``) while the skew lasts. The alert
    poll runs concurrently with the load — the alert is transient, it
    resolves once the skew stops."""
    from multiverso_tpu.serving import ShedError

    hot = np.arange(min(args.keys_per_req, 8), dtype=np.int32)
    result: dict = {}

    def poll():
        fired, st = _await_fleet_alert(
            router_addr,
            lambda st: any(a.get("name") == "fleet.shard_imbalance"
                           for a in st.get("router_alerts", [])),
            timeout_s=25.0)
        result["fired"] = fired
        if st is not None:
            result["router_alerts"] = st.get("router_alerts", [])
            result["shard_load_ratio"] = st.get("fleet", {}).get(
                "shard_load_ratio", 0.0)
            result["per_replica_keys_rate"] = {
                rid: row.get("keys_rate", 0.0)
                for rid, row in st.get("replicas", {}).items()}

    poller = threading.Thread(target=poll, daemon=True)
    poller.start()
    deadline = time.monotonic() + 25.0
    n = 0
    while time.monotonic() < deadline and poller.is_alive():
        try:
            fleet.lookup(hot, deadline_ms=args.deadline_ms, timeout=30)
        except Exception:  # noqa: BLE001 - sheds/timeouts don't matter:
            pass           # the drill needs key VOLUME, not clean QPS
        n += 1
    poller.join(timeout=30)
    result.setdefault("fired", False)
    result["skewed_requests"] = n
    return result


def _rebalance_drill(args, fleet, router_addr) -> dict:
    """Skew SELF-HEALING witness (ISSUE 17) — the actuation half of the
    PR-14 detection drill: drive the same fully-skewed stream (every
    request carries one fixed key set, so ring affinity lands it all on
    one owner) and keep it running while the router's actuators respond
    — hot-key replication spreads the confident hot keys over extra
    ring owners (clients round-robin replicated reads), and the
    rebalancer migrates vnode arcs off the hot owner if imbalance
    persists. PASS = the actuators ENGAGED (keys replicated or arcs
    migrated) and ``fleet.shard_load_ratio`` sits under the 1.3 bar
    after a sustained skewed window, with ZERO client errors for the
    whole drill (replication is pure routing; migration drains through
    the zero-downtime hot-swap lifecycle)."""
    from multiverso_tpu.fleet import fetch_fleet_stats

    hot = np.arange(min(args.keys_per_req, 8), dtype=np.int32)
    stop = threading.Event()
    errors = [0]
    n_req = [0]
    last_error = [""]

    def load():
        while not stop.is_set():
            try:
                fleet.lookup(hot, deadline_ms=max(args.deadline_ms, 500),
                             timeout=30)
            except Exception as exc:  # noqa: BLE001 - every failure
                errors[0] += 1        # counts: the witness claims ZERO
                last_error[0] = f"{type(exc).__name__}: {exc}"[:200]
            n_req[0] += 1

    loaders = [threading.Thread(target=load, daemon=True)
               for _ in range(2)]
    for t in loaders:
        t.start()
    t0 = time.monotonic()
    deadline = t0 + (45.0 if args.dry_run else 90.0)
    min_run_s = 8.0     # the ratio must HOLD under sustained skew, not
    worst = 1.0         # just read low before the stream ramped
    healed = False
    last: dict = {}
    path: list = []
    while time.monotonic() < deadline:
        try:
            st = fetch_fleet_stats(router_addr)
        except Exception:  # noqa: BLE001 - router busy under load
            time.sleep(0.5)
            continue
        last = st
        f = st.get("fleet", {})
        ratio = float(f.get("shard_load_ratio", 1.0))
        path.append(round(ratio, 2))
        worst = max(worst, ratio)
        engaged = (int(f.get("hotkey_replicated", 0)) > 0
                   or int((f.get("rebalance") or {})
                          .get("overrides", 0)) > 0)
        if engaged and ratio < 1.3 and time.monotonic() - t0 >= min_run_s:
            healed = True
            break
        time.sleep(0.5)
    stop.set()
    for t in loaders:
        t.join(timeout=60)
    f = last.get("fleet", {})
    return {
        "healed": healed,
        "worst_ratio": round(worst, 3),
        "final_ratio": round(float(f.get("shard_load_ratio", 0.0)), 3),
        "ratio_path": path[-40:],
        "hotkey_replicated": int(f.get("hotkey_replicated", 0)),
        "rebalance": f.get("rebalance", {}),
        "client_errors": errors[0],
        "last_client_error": last_error[0],
        "skewed_requests": n_req[0],
    }


def _handoff_kill_probe(args, fleet, router_addr, procs, table) -> dict:
    """Opportunistic SIGKILL-mid-handoff probe: keep the skew up so the
    rebalancer starts another migration, and the moment the stats
    rollup shows one in flight, SIGKILL the donor replica. The fleet
    must keep serving bitwise-correct rows (full-copy replicas:
    ownership moved to the target BEFORE the donor died; acked-write
    durability through the same window is the WAL-through-migration
    witness in tests/test_rebalance.py). Migration windows are short on
    a quiet box, so catching one is best effort — ``caught`` records
    whether the kill landed mid-flight."""
    from multiverso_tpu.fleet import fetch_fleet_stats

    hot = np.arange(min(args.keys_per_req, 8), dtype=np.int32)
    stop = threading.Event()

    def load():
        while not stop.is_set():
            try:
                fleet.lookup(hot, deadline_ms=1000, timeout=30)
            except Exception:  # noqa: BLE001 - volume, not cleanliness
                pass

    loader = threading.Thread(target=load, daemon=True)
    loader.start()
    victim = None
    deadline = time.monotonic() + 20.0
    try:
        while time.monotonic() < deadline and victim is None:
            try:
                st = fetch_fleet_stats(router_addr, timeout_s=5)
            except Exception:  # noqa: BLE001 - router busy under load
                time.sleep(0.1)
                continue
            for rid, row in st.get("replicas", {}).items():
                if int(row.get("migrations", 0)) > 0:
                    idx = int(rid.rsplit("-", 1)[-1])
                    if idx < len(procs) and procs[idx].poll() is None:
                        victim = rid
                        procs[idx].kill()
                        break
            time.sleep(0.05)
    finally:
        stop.set()
        loader.join(timeout=30)
    if victim is None:
        return {"caught": False}
    time.sleep(1.0)     # let the sweep take the corpse out of the ring
    ok = _parity_check(fleet, table, args.rows, args.keys_per_req)
    return {"caught": True, "killed": victim,
            "post_kill_parity": bool(ok)}


def _rebalance_ab(args, tdir) -> dict:
    """Static-vs-actuated A/B on the SAME fully-skewed stream (ISSUE 17
    headline): two fresh mini-fleets run back to back on the quiet
    post-teardown box — leg A with the actuators off (ring affinity
    concentrates the hot set on one owner, the others idle), leg B with
    hot-key replication + rebalancing on — and one record carries both
    achieved-QPS legs. The actuated leg ends with the mid-handoff kill
    probe."""
    from multiverso_tpu.fleet import FleetClient, fetch_fleet_stats

    rng = np.random.default_rng(0)
    table = rng.normal(size=(args.rows, args.cols)).astype(np.float32)
    hot = np.arange(min(args.keys_per_req, 8), dtype=np.int32)
    replicas = max(2, args.replicas)
    legs: dict = {}
    for name, actuated in (("static", False), ("actuated", True)):
        a = argparse.Namespace(**vars(args))
        a.rebalance = actuated
        a.hotkey_replicas = (args.hotkey_replicas or 1) if actuated else 0
        # The actuated leg is the WHOLE closed loop, cache leg included:
        # with a byte budget set, give the autosizer a seed capacity so
        # the replicated hot set also serves host-side. (On a 1-core CI
        # box replication alone can't raise box-bound QPS — spreading
        # load across processes sharing one core is throughput-neutral;
        # the cache leg is what cuts per-request work.)
        if actuated and args.cache_mem_budget and not args.cache_rows:
            a.cache_rows = 256
        a.slo_drill = False     # _spawn_replica reads it; no skewed SLO
        sub = os.path.join(tdir, f"ab_{name}")
        os.makedirs(sub, exist_ok=True)
        addr_file = os.path.join(sub, "router_addr")
        router = _spawn_router(a, sub, addr_file)
        procs: list = []
        fleet = None
        try:
            addr = _wait_addr_file(addr_file, [router])
            procs = [_spawn_replica(a, addr, i, sub)
                     for i in range(replicas)]
            # Hedge OFF: under saturation adaptive hedging would itself
            # spread the hot set to the idle replica and mask the very
            # contrast the A/B measures (routing policy, nothing else).
            fleet = FleetClient(addr, hedge="off",
                                refresh_s=a.heartbeat_ms / 1e3,
                                hot_staleness=float(a.cache_staleness))
            deadline = time.monotonic() + 240
            while len(fleet.refresh().members) < replicas:
                if any(p.poll() is not None for p in procs) \
                        or router.poll() is not None:
                    raise RuntimeError("A/B fleet exited during bring-up")
                if time.monotonic() > deadline:
                    raise RuntimeError("A/B fleet never formed")
                time.sleep(0.05)
            for _ in range(10):     # warm connections + decode path
                fleet.lookup(hot, deadline_ms=10_000, timeout=60)
            # Give the actuated leg's replicator a skewed baseline to
            # promote from BEFORE the timed window — the A/B measures
            # actuated steady state, not promotion latency.
            settle = time.monotonic() + (4.0 if actuated else 0.5)
            while time.monotonic() < settle:
                try:
                    fleet.lookup(hot, deadline_ms=10_000, timeout=60)
                except Exception:  # noqa: BLE001 - settle is best effort
                    pass
            # Offer well past one owner's capacity: the static leg must
            # SATURATE on its single affinity owner for the actuated
            # leg's extra owners to show up as achieved QPS.
            stats = _LoadStats()
            elapsed = _run_fleet_load(
                fleet, stats, max(args.threads, 8), args.qps * 4,
                max(4.0, args.duration / 2), args.rows,
                args.keys_per_req, max(args.deadline_ms, 200),
                lambda _rng: hot)
            st = {}
            try:
                st = fetch_fleet_stats(addr)
            except Exception:  # noqa: BLE001 - leg stats are additive
                pass
            fb = st.get("fleet", {})
            with stats.lock:
                legs[name] = {
                    "achieved_qps":
                        round(len(stats.latencies) / elapsed, 1)
                        if elapsed > 0 else 0.0,
                    "n_ok": len(stats.latencies),
                    "n_shed": stats.sheds,
                    "n_error": stats.errors,
                    "shard_load_ratio":
                        round(float(fb.get("shard_load_ratio", 0.0)), 3),
                    "hotkey_replicated":
                        int(fb.get("hotkey_replicated", 0)),
                    "rebalance": fb.get("rebalance", {}),
                }
            if actuated:
                legs[name]["kill_mid_handoff"] = _handoff_kill_probe(
                    a, fleet, addr, procs, table)
        finally:
            if fleet is not None:
                fleet.close()
            _shutdown_procs(procs + [router])
    a_qps = legs["static"]["achieved_qps"]
    b_qps = legs["actuated"]["achieved_qps"]
    legs["qps_ratio"] = round(b_qps / a_qps, 3) if a_qps > 0 else None
    # Box honesty (the bench_guard rule): spreading a hot set over more
    # owners shows up as QPS only when there are cores for the extra
    # owners to run on. On a 1-core CI box every process shares the one
    # core, so qps_ratio ~ 1 is the physics and the actuation witness
    # is the shard_load_ratio contrast instead (static ~2.0, actuated
    # ~1.0 — same stream, load actually spread).
    legs["box_cores"] = os.cpu_count() or 1
    return legs


def _router_kill_round(args, router_box, router_addr, addr_file,
                       procs, tdir, fleet) -> dict:
    """Control-plane kill round (ISSUE 17 chaos satellite): SIGKILL the
    ROUTER under live lookup load, respawn it on the SAME port, and
    require (a) every live replica rejoins — their heartbeat loops
    re-dial through connect_with_backoff, (b) the client keeps serving
    from its last routing table through the outage with errors confined
    to the recovery window, and (c) routed reads answer normally
    afterwards. The respawned router's version counter restarts; the
    client's reconnected-feed handling must accept the regressed table
    rather than route from the stale one forever."""
    from multiverso_tpu.fleet import fetch_fleet_stats

    live = [f"replica-{i}" for i, p in enumerate(procs)
            if p.poll() is None]
    stats = _LoadStats()
    load_s = max(6.0, args.duration)
    loader = threading.Thread(
        target=_run_fleet_load,
        args=(fleet, stats, args.threads, args.qps, load_s,
              args.rows, args.keys_per_req, args.deadline_ms),
        daemon=True)
    loader.start()
    time.sleep(load_s * 0.3)
    t_kill = time.monotonic()
    old = router_box[0]
    old.kill()
    try:
        old.wait(timeout=30)
    except subprocess.TimeoutExpired:
        pass
    # The respawn on the SAME port must not trip over a stale announce.
    try:
        os.remove(addr_file)
    except OSError:
        pass
    router_box[0] = _spawn_router(args, tdir, addr_file,
                                  port=router_addr[1])
    rejoined, t_rec = False, None
    deadline = time.monotonic() + 120
    delay = 0.05
    while time.monotonic() < deadline:
        try:
            st = fetch_fleet_stats(router_addr, timeout_s=5)
            if all(m in st.get("replicas", {}) for m in live):
                rejoined, t_rec = True, time.monotonic()
                break
        except Exception:  # noqa: BLE001 - port still closed mid-boot
            pass
        time.sleep(delay)
        delay = min(delay * 2.0, 0.5)
    loader.join(timeout=load_s + 120)
    window_s = (args.liveness_misses * args.heartbeat_ms) / 1e3
    t_end = (t_rec if t_rec is not None else time.monotonic()) + window_s
    with stats.lock:
        in_window = sum(1 for t in stats.error_times
                        if t_kill <= t <= t_end)
        outside = sum(1 for t in stats.error_times
                      if not (t_kill <= t <= t_end))
        window = {"n_ok": len(stats.latencies), "n_shed": stats.sheds,
                  "n_error": stats.errors}
    return {
        "rejoined_all": rejoined,
        "recovery_s": round(t_rec - t_kill, 3)
        if t_rec is not None else None,
        "errors_in_recovery_window": in_window,
        "errors_outside_window": outside,
        "window": window,
    }


def _await_postmortem(tdir: str, victim_pid: int,
                      timeout_s: float = 20.0) -> dict:
    """Wait for the victim's postmortem dump and schema-validate it —
    the fault drill's 'the corpse left an artifact' witness."""
    from multiverso_tpu.telemetry import validate_postmortem
    path = os.path.join(tdir, f"postmortem-{victim_pid}.json")
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline and not os.path.exists(path):
        time.sleep(0.1)
    if not os.path.exists(path):
        return {"found": False, "valid": False, "path": path}
    try:
        with open(path) as f:
            pm = json.load(f)
        validate_postmortem(pm)
    except (OSError, ValueError) as e:
        return {"found": True, "valid": False, "path": path,
                "error": str(e)}
    return {"found": True, "valid": True, "path": path,
            "reason_kind": pm["reason"]["kind"],
            "signal": pm["reason"].get("signal_name"),
            "n_threads": len(pm["threads"]),
            "n_log_lines": len(pm["flight"]["logs"])}


# ---------------------------------------------------------------------------
# Recovery drill (ISSUE 15): durable PS shards + supervisor self-healing
# ---------------------------------------------------------------------------
def _ensure_mv_runtime() -> None:
    """The WAL legs build DistributedArrayTable client seats in the
    bench process, which needs the Zoo runtime the serving-only paths
    never start. Idempotent."""
    import multiverso_tpu as mv
    from multiverso_tpu.core.zoo import Zoo
    if not Zoo.get().started:
        mv.init([])


class _FileMembershipView:
    """Fleet-view adapter for a lone PS seat: 'membership' is the addr
    file the seat writes AFTER its recovery completes (attach WAL ->
    restore -> replay -> announce -> write), so the supervisor sees the
    seat exactly when clients can."""

    def __init__(self, addr_file: str, member_id: str):
        self.addr_file = addr_file
        self.member_id = member_id

    def stats(self):
        rows = {self.member_id: {"alerts": []}} \
            if os.path.exists(self.addr_file) else {}
        return {"replicas": rows, "router_alerts": []}

    def drain(self, member_id, timeout_s=30.0):
        return False                        # one seat: never scaled down


def _spawn_ps_shard(args, parent_addr, tmp: str, addr_file: str,
                    size: int) -> subprocess.Popen:
    if os.path.exists(addr_file):
        os.remove(addr_file)                # stale announce must not
    cmd = [sys.executable, "-m",           # count as recovered
           "multiverso_tpu.apps.ps_shard_main",
           "-rank=1",
           f"-ps_peers={parent_addr[0]}:{parent_addr[1]},127.0.0.1:1",
           "-ps_table_id=912", f"-ps_table_size={size}",
           "-wal=true", f"-wal_dir={tmp}/wal", "-wal_sync_acks=true",
           f"-checkpoint_dir={tmp}/ckpt", "-ps_checkpoint_every_s=1.0",
           f"-ps_addr_file={addr_file}", "-serve_duration=600",
           "-telemetry_alerts=false", "-telemetry_flight=false"]
    return subprocess.Popen(
        cmd, cwd=_REPO,
        env=child_env(args.replicas + 1, _chip_holders(args), "serve_bench"))


def _lockwitness_leg(args) -> dict:
    """graftsan witness leg (dry-run): a small witnessed workload in
    this process — a WAL group commit (the ``wal.io -> wal.staging``
    pair) plus a two-lock nest — must record acquisition-order edges,
    populate the ``lock.*`` hold-time histograms, and observe ZERO
    inversions. The A/B half is structural, not statistical: with the
    witness OFF, ``make_lock`` must hand back the bare ``threading``
    primitive — the exact type, no wrapper — so the overhead when off
    is exactly zero by construction."""
    import threading as _threading

    from multiverso_tpu.core.wal import WriteAheadLog
    from multiverso_tpu.telemetry import get_registry
    from multiverso_tpu.telemetry.lockwitness import (check_inversions,
                                                      observed_edges,
                                                      reset_lockwitness)
    from multiverso_tpu.utils.locks import make_lock, set_witness_enabled

    # A/B gate first, while the witness is off (the bench default).
    set_witness_enabled(False)
    try:
        ab_off_is_bare = type(make_lock("bench.ab")) \
            is type(_threading.Lock())
    finally:
        set_witness_enabled(None)

    set_witness_enabled(True)
    reset_lockwitness()
    try:
        wal = WriteAheadLog(tempfile.mkdtemp(prefix="witness_wal_"))
        for i in range(128):
            wal.append(b"witness-%03d" % i)
        wal.append(b"commit", sync=True)
        wal.close()
        outer, inner = make_lock("bench.outer"), make_lock("bench.inner")
        for _ in range(64):
            with outer:
                with inner:
                    pass
        edges = {f"{s} -> {d}": n
                 for (s, d), n in sorted(observed_edges().items())}
        cycles = check_inversions(postmortem=False)
        held = {name: {"count": snap["count"],
                       "p95_ms": snap["p95"]}
                for name, snap in get_registry().snapshot(
                    buckets=False)["histograms"].items()
                if name.startswith("lock.") and snap["count"]}
    finally:
        set_witness_enabled(None)
    return {"ab_off_is_bare_lock": ab_off_is_bare,
            "inversions": len(cycles),
            "cycles": [" -> ".join(c + (c[0],)) for c in cycles],
            "edges": edges, "held_ms": held}


def _wal_recovery_leg(args) -> dict:
    """SIGKILL a WAL-journaled PS shard mid-stream; a ReplicaSupervisor
    respawns it through the recovery path (checkpoint + WAL replay);
    assert the resumed world's table equals the acked add stream EXACTLY
    and record time-to-recover. ``-wal_sync_acks`` is on, so every acked
    add is durable — parity is exact, not windowed."""
    from multiverso_tpu.fleet import ReplicaSupervisor
    from multiverso_tpu.parallel.ps_service import (DistributedArrayTable,
                                                    PSService)

    _ensure_mv_runtime()
    size = 256
    tmp = tempfile.mkdtemp(prefix="wal_drill_")
    addr_file = os.path.join(tmp, "seat1.addr")
    svc0 = PSService()
    sup = None
    result: dict = {"size": size}
    try:
        child = _spawn_ps_shard(args, svc0.address, tmp, addr_file, size)
        deadline = time.monotonic() + 120
        while not os.path.exists(addr_file):
            if child.poll() is not None:
                raise RuntimeError("ps shard exited during bring-up")
            if time.monotonic() > deadline:
                raise RuntimeError("ps shard never announced")
            time.sleep(0.05)
        host, port = open(addr_file).read().split(":")
        peers = [svc0.address, (host, int(port))]
        table = DistributedArrayTable(912, size, svc0, peers, rank=0)

        sup = ReplicaSupervisor(
            _FileMembershipView(addr_file, "ps-1"),
            lambda slot: _spawn_ps_shard(args, svc0.address, tmp, addr_file,
                                         size),
            member_prefix="ps-", min_replicas=1, max_replicas=1,
            cooldown_s=0.5, poll_s=0.1, join_grace_s=60.0)
        sup.adopt(1, child)
        sup.start()

        rng = np.random.default_rng(0)
        acked = np.zeros(size, np.float32)

        def burst(n):
            for _ in range(n):
                d = rng.integers(1, 5, size).astype(np.float32)
                table.add(d)                # synchronous: ack == applied
                acked[:] += d

        burst(30)
        time.sleep(1.5)                     # let a checkpoint+prune land
        burst(30)
        # Abrupt death mid-stream; the supervisor must notice the corpse
        # and respawn through the recovery path while the client's
        # directory-retry loop rides out the gap.
        os.remove(addr_file)
        child.send_signal(signal.SIGKILL)
        t_kill = time.monotonic()
        burst(30)                           # spans the outage + recovery
        t_first_ok = time.monotonic()
        guard = time.monotonic() + 60       # announce already happened
        while not os.path.exists(addr_file) and time.monotonic() < guard:
            time.sleep(0.02)
        got = np.asarray(table.get())
        parity = bool(np.array_equal(got, acked))
        status = sup.status()
        result.update({
            "parity_ok": parity,
            "acked_adds": 90,
            "time_to_recover_s": round(t_first_ok - t_kill, 3),
            "supervisor_respawns": status["respawns"],
            "respawn_trigger": next(
                (e["trigger"] for e in status["events"]
                 if e["kind"] == "respawn"), None),
        })
    finally:
        if sup is not None:
            sup.stop()
            _shutdown_procs([h for h in sup.slots().values()
                             if isinstance(h, subprocess.Popen)])
        svc0.close()
    return result


def _wal_overhead_ab(args) -> dict:
    """WAL hot-path cost on the PS add plane. Two measurements:

    * ``overhead_pct`` (the acceptance number, <= 2%): the DISPATCH-
      THREAD cost — a micro-timed ``append`` of the exact record shape
      the service logs (raw wire frame, crc + lsn + stage) against the
      measured plain add round trip. Deterministic and reproducible;
      this is the "hot path stays one list-append" claim, priced.
    * ``end_to_end_overhead_pct``: a burst-interleaved (about 10 ms
      alternation, order swapped per round, ratio of totals) live A/B
      of plain vs group-commit-journaled worlds, WITH the background
      commit cost included. On the 1-core CI box this number is box-
      noise-limited (a same-world toggle measured the noise at +-10%,
      larger than the effect); the percentile spread ships in the
      record so the noise floor is a stated fact, not a hidden one.
    """
    from multiverso_tpu.core import wal as wal_mod
    from multiverso_tpu.parallel.ps_service import (DistributedArrayTable,
                                                    PSService)

    _ensure_mv_runtime()
    size = 256
    # Fleet mode runs this after teardown: let shutdown-time telemetry
    # writes and exiting subprocesses drain before timing.
    time.sleep(1.0 if args.dry_run else 3.0)

    def build(with_wal, tid):
        s0, s1 = PSService(), PSService()
        if with_wal:
            s1.attach_wal(tempfile.mkdtemp(prefix="wal_ab_"),
                          flush_interval_ms=25.0)   # the -wal_flush_ms
                                                    # deployment default
        peers = [s0.address, s1.address]
        t0 = DistributedArrayTable(tid, size, s0, peers, rank=0)
        DistributedArrayTable(tid, size, s1, peers, rank=1)
        return (s0, s1), t0

    closers_a, table_a = build(False, 920)
    closers_b, table_b = build(True, 921)
    delta = np.ones(size, np.float32)
    try:
        for t in (table_a, table_b):
            for _ in range(50):
                t.add(delta)                # warm connections + jits
        # Plain round-trip latency (the denominator of the hot-path %).
        n_lat = 200 if args.dry_run else 500
        t0 = time.perf_counter()
        for _ in range(n_lat):
            table_a.add(delta)
        plain_roundtrip_us = (time.perf_counter() - t0) / n_lat * 1e6

        # Hot-path microbench: append the REAL record the service logs
        # (its WAL's last record = the raw wire frame of one add), on a
        # throwaway log with the flusher parked so only the staged-
        # append path is timed.
        closers_b[1]._wal.flush()           # commit BEFORE reading: a
        sample = None                       # fast warm-up can finish
        for _, payload in wal_mod.replay(   # inside one group-commit
                closers_b[1]._wal.directory):   # window, and the micro
            sample = payload                # must price a REAL frame
        if sample is None:
            sample = b"x" * 1300            # unreachable fallback
        scratch = wal_mod.WriteAheadLog(
            tempfile.mkdtemp(prefix="wal_hot_"),
            flush_interval_ms=10_000_000)
        n_hot = 20_000
        t0 = time.perf_counter()
        for _ in range(n_hot):
            scratch.append(sample)
        hot_path_us = (time.perf_counter() - t0) / n_hot * 1e6
        scratch.close()
        overhead = hot_path_us / plain_roundtrip_us * 100

        # End-to-end corroboration: ~10ms alternating bursts, ratio of
        # totals (commit/fsync cost included).
        burst = 20
        rounds = 60 if args.dry_run else 160
        acc = {"plain": 0.0, "wal": 0.0}
        counts = {"plain": 0, "wal": 0}
        for k in range(rounds):
            pair = (("plain", table_a), ("wal", table_b))
            if k % 2:                       # order swaps: within-round
                pair = pair[::-1]           # drift hits each side equally
            for name, t in pair:
                t_start = time.perf_counter()
                for _ in range(burst):
                    t.add(delta)
                acc[name] += time.perf_counter() - t_start
                counts[name] += burst
        plain_rate = counts["plain"] / acc["plain"]
        wal_rate = counts["wal"] / acc["wal"]
        e2e = (plain_rate - wal_rate) / plain_rate * 100
    finally:
        for c in (*closers_a, *closers_b):
            c.close()
    return {"overhead_pct": round(overhead, 2),
            "hot_path_us_per_add": round(hot_path_us, 2),
            "plain_roundtrip_us": round(plain_roundtrip_us, 1),
            "record_bytes": len(sample),
            "adds_per_sec_plain": round(plain_rate, 1),
            "adds_per_sec_wal": round(wal_rate, 1),
            "end_to_end_overhead_pct": round(e2e, 2),
            "mode": "group_commit_async"}


def _replica_recovery_drill(args, router_addr, procs, tdir) -> dict:
    """Self-healing witnessed end-to-end: SIGKILL a serving replica
    under load with a ReplicaSupervisor armed; the router's heartbeat
    loss drives an automatic replacement that rejoins the ring; assert
    membership converges back and count client-visible errors after the
    hedging window. Returns the drill record; replaces the victim's
    entry in ``procs`` with the respawned handle."""
    from multiverso_tpu.fleet import (RemoteFleetView, ReplicaSupervisor,
                                      fetch_fleet_stats)
    from multiverso_tpu.fleet.client import FleetClient

    live = {i: p for i, p in enumerate(procs) if p.poll() is None}
    view = RemoteFleetView(router_addr)

    class _RemoteHandle:
        """Hide process liveness from the supervisor: a cross-host
        supervisor cannot poll a remote pid, so the replacement MUST be
        driven by the router's fleet.heartbeat_loss alert — the literal
        acceptance chain (alert fires -> automatic replacement). stop/
        poll pass through for teardown accounting only."""

        def __init__(self, proc):
            self.proc = proc

        def poll(self):
            return None             # "alive" as far as the healer knows

        def terminate(self):
            self.proc.terminate()

    sup = ReplicaSupervisor(
        view, lambda slot: _spawn_replica(args, router_addr, slot, tdir),
        min_replicas=len(live), max_replicas=len(live),
        cooldown_s=1.0, poll_s=0.2, join_grace_s=120.0)
    for i, p in live.items():
        sup.adopt(i, _RemoteHandle(p))
    sup.start()

    hedge = args.hedge if args.hedge in ("adaptive", "off") \
        else float(args.hedge)
    fleet = FleetClient(router_addr, hedge=hedge,
                        refresh_s=args.heartbeat_ms / 1e3,
                        rpc_timeout_ms=args.rpc_timeout_ms or None)
    dstats = _LoadStats()
    drill_state: dict = {}
    duration = max(args.duration, 6.0)

    def drill():
        time.sleep(duration * 0.25)
        victim_slot = min(live)
        victim = live[victim_slot]
        t_kill = time.monotonic()
        victim.send_signal(signal.SIGKILL)
        drill_state["victim"] = f"replica-{victim_slot}"
        drill_state["t_kill"] = t_kill
        deadline = time.monotonic() + duration + 120
        # Phase 1 — the supervisor actually ACTED (the victim's row
        # lingers in the rollup until the sweep, so "member present"
        # alone would declare recovery before the death was even
        # noticed — the first drill run recorded a bogus 6ms).
        while time.monotonic() < deadline:
            if sup.status()["respawns"] >= 1:
                break
            time.sleep(0.05)
        # Phase 2 — the REPLACEMENT is back in the rollup: warmed,
        # joined, ring re-routed. Presence alone suffices here: the
        # supervisor only respawns a member the sweep already removed
        # (phase 1 is the absence proof), and the SIGKILLed original
        # cannot re-heartbeat, so any later presence IS the replacement.
        while time.monotonic() < deadline:
            try:
                st = fetch_fleet_stats(router_addr)
                if f"replica-{victim_slot}" in st.get("replicas", {}):
                    drill_state["t_recovered"] = time.monotonic()
                    return
            except Exception:  # noqa: BLE001 - transient poll failure
                pass
            time.sleep(0.05)

    driller = threading.Thread(target=drill, daemon=True)
    driller.start()
    elapsed = _run_fleet_load(fleet, dstats, args.threads, args.qps,
                              duration, args.rows, args.keys_per_req,
                              args.deadline_ms)
    driller.join(timeout=240)
    fleet.close()
    status = sup.status()
    sup.stop()
    # Hand the (possibly respawned) handles back for shutdown/accounting
    # (unwrap the poll-hiding adapters — teardown needs the real Popen).
    for i, h in sup.slots().items():
        if i < len(procs):
            procs[i] = getattr(h, "proc", h)

    out = {"killed": drill_state.get("victim"),
           "signal": "SIGKILL",
           "supervisor_respawns": status["respawns"],
           "respawn_trigger": next(
               (e["trigger"] for e in status["events"]
                if e["kind"] == "respawn"), None)}
    if "t_recovered" in drill_state:
        t_kill = drill_state["t_kill"]
        t_rec = drill_state["t_recovered"]
        hedge_window_s = (args.liveness_misses * args.heartbeat_ms) / 1e3
        with dstats.lock:
            after_window = sum(
                1 for t in dstats.error_times
                if t > t_rec + hedge_window_s)
            after_kill = sum(1 for t in dstats.error_times if t > t_kill)
        out.update({
            "recovered": True,
            "time_to_recover_s": round(t_rec - t_kill, 3),
            "errors_after_kill": after_kill,
            "errors_after_recovery_and_hedge_window": after_window,
            "hedge_window_s": hedge_window_s,
        })
    else:
        out["recovered"] = False
    with dstats.lock:
        out["window"] = {
            "achieved_qps": round(len(dstats.latencies) / elapsed, 1)
            if elapsed > 0 else 0.0,
            "n_ok": len(dstats.latencies),
            "n_shed": dstats.sheds,
            "n_error": dstats.errors,
        }
    return out


# ---------------------------------------------------------------------------
# Chaos drill (ISSUE 16): kill-any-subset over the recoverable fleet
# ---------------------------------------------------------------------------
def _slot_signal(sup, slot: int, signum) -> None:
    """Deliver a signal to the CURRENT occupant of a supervised slot —
    after a respawn the original Popen is a corpse; later chaos rounds
    must hit the replacement."""
    handle = sup.slots().get(slot)
    if handle is None:
        raise ProcessLookupError(f"slot {slot} not supervised")
    getattr(handle, "proc", handle).send_signal(signum)


def _elastic_round(seed: int) -> dict:
    """Elastic worker leave+rejoin witness: a worker joins the LIVE
    clock group (drained to the epoch floor), leaves, and a later join
    REUSES its slot — the group re-forms at each step with the
    membership version advancing (core/sync_coordinator.py; the
    cross-process Control_Elastic path is covered by
    tests/test_elastic_fuzz.py)."""
    from multiverso_tpu.core.sync_coordinator import SyncCoordinator

    sc = SyncCoordinator(2, name=f"chaos{seed}", leave_timeout_s=5.0)
    for w in (0, 1):            # mid-epoch: the join must drain to floor
        sc.acquire_add(w)
        sc.commit_add(w)
    base = sc.status()
    w = sc.join()
    joined = sc.status()
    sc.leave(w)
    left = sc.status()
    w2 = sc.join()
    rejoined = sc.status()
    return {
        "joined_slot": w, "rejoined_slot": w2,
        "slot_reused": w2 == w,
        "world": [base["world"], joined["world"], left["world"],
                  rejoined["world"]],
        "versions": [base["version"], joined["version"],
                     left["version"], rejoined["version"]],
        "reformed": (joined["world"] == 3 and left["world"] == 2
                     and rejoined["world"] == 3 and w2 == w
                     and rejoined["version"] == base["version"] + 3),
        "quorum_evictions": rejoined["quorum_evictions"],
    }


def _chaos_drill(args, router_addr, procs, tdir, fleet,
                 router_box=None, addr_file=None) -> dict:
    """Seeded kill-any-subset drill over BOTH planes (ISSUE 16): a
    supervised multi-shard PS fleet takes a live training stream while
    the serving fleet takes lookup load; each round the ChaosEngine
    SIGKILLs/SIGSTOPs a random subset of PS shards (+ possibly SIGKILLs
    a serving replica) under an optional lossy client link, and the
    drill asserts the fleet converges back to FULL membership with the
    acked add stream intact EXACTLY (zero acked-write loss — every
    killed shard recovered checkpoint+WAL bitwise) and serving errors
    confined to the documented recovery+hedge windows. A seeded subset
    of shard seats runs with an injected WAL fsync delay the whole time
    (the slow-disk fault). Replaces respawned serving handles in
    ``procs``.

    ISSUE 20: the ROUTER is a kill candidate too (it was the last
    spared singleton). When the seeded draw takes it, the drill
    respawns it on the same port (the `_router_kill_round` recipe) and
    requires every live member to reconnect-with-backoff through the
    outage — with the training plane's zero-acked-loss parity still
    exact, since PS adds never route through the serving router."""
    from multiverso_tpu.fleet import (ChaosEngine, PSShardFleet,
                                      RemoteFleetView, ReplicaSupervisor,
                                      fetch_fleet_stats)

    _ensure_mv_runtime()
    seed = args.chaos_seed
    shards = _chaos_shards(args)
    rounds = args.chaos_rounds or (2 if args.dry_run else 3)
    size = 128
    srng = np.random.default_rng(seed)
    slow = sorted(int(r) for r in srng.choice(
        np.arange(1, shards + 1), size=max(1, shards // 2),
        replace=False))
    psf = PSShardFleet(
        shards=shards, first_chip=args.replicas,
        table_id=916, table_size=size, sync_acks=True,
        checkpoint_every_s=1.0, join_grace_s=120.0,
        extra_seat_args={r: ["-wal_fsync_delay_ms=10"] for r in slow})
    psf.start()

    # Serving plane healer: same shape as the recovery drill — remote
    # view so heartbeat loss (not pid liveness) drives replacement.
    serving_live = {i: p for i, p in enumerate(procs)
                    if p.poll() is None}

    class _RemoteHandle:
        def __init__(self, proc):
            self.proc = proc

        def poll(self):
            return None

        def terminate(self):
            self.proc.terminate()

    sup = ReplicaSupervisor(
        RemoteFleetView(router_addr),
        lambda slot: _spawn_replica(args, router_addr, slot, tdir),
        min_replicas=len(serving_live), max_replicas=len(serving_live),
        cooldown_s=1.0, poll_s=0.2, join_grace_s=120.0)
    for i, p in serving_live.items():
        sup.adopt(i, _RemoteHandle(p))
    sup.start()

    engine = ChaosEngine(seed=seed, kinds=("kill", "pause", "net_drop"),
                         max_pause_s=1.5, max_drop_rate=0.25)
    for r in range(1, psf.shards + 1):
        engine.register_kill(
            f"ps-{r}", lambda sig, r=r: psf.kill(r, sig))
    for i in serving_live:
        engine.register_kill(
            f"replica-{i}", lambda sig, i=i: _slot_signal(sup, i, sig),
            kinds=("kill",))
    if router_box is not None:
        # Control-plane seat: kill-only (a paused router is the
        # liveness detector pausing itself — nothing to witness).
        engine.register_kill(
            "router", lambda sig: router_box[0].send_signal(sig),
            kinds=("kill",))

    # Live training plane: a paced add stream whose every ack is
    # durable (-wal_sync_acks on every seat); `acked` is ground truth
    # for the per-round parity gate. The mutex makes quiesce exact: the
    # parity reader takes it, so no add is half-accounted.
    acked = np.zeros(size, np.float32)
    trng = np.random.default_rng(seed + 1)
    train_stop = threading.Event()
    train_gate = threading.Event()
    train_gate.set()
    train_mutex = threading.Lock()
    train_errors: list = []
    n_adds = [0]

    def train():
        while not train_stop.is_set():
            train_gate.wait(timeout=1.0)
            if train_stop.is_set() or not train_gate.is_set():
                continue
            d = trng.integers(1, 4, size).astype(np.float32)
            with train_mutex:
                try:
                    psf.table.add(d)        # synchronous: ack == applied
                except Exception:  # noqa: BLE001 - any failed add
                    # makes parity unprovable; recorded and asserted 0
                    train_errors.append(traceback.format_exc(limit=12))
                    continue
                acked[:] += d
                n_adds[0] += 1
            time.sleep(0.01)

    trainer = threading.Thread(target=train, daemon=True)
    trainer.start()

    hedge_window_s = (args.liveness_misses * args.heartbeat_ms) / 1e3
    round_records = []
    try:
        for rnd in range(rounds):
            faults = engine.plan_round(
                window_s=min(2.0, max(0.5, args.duration / 4)))
            serving_kill = any(f.kind == "kill" and
                               (f.target or "").startswith("replica-")
                               for f in faults)
            router_kill = any(f.kind == "kill" and f.target == "router"
                              for f in faults)
            sstats = _LoadStats()
            load_s = max(6.0, args.duration)
            loader = threading.Thread(
                target=_run_fleet_load,
                args=(fleet, sstats, args.threads, args.qps, load_s,
                      args.rows, args.keys_per_req, args.deadline_ms),
                daemon=True)
            alert_state: dict = {}

            def poll_alert():
                alert_state["heartbeat_loss"] = \
                    _await_heartbeat_loss(router_addr, timeout_s=30)

            poller = None
            # The heartbeat-loss detector lives IN the router: a round
            # that kills the router cannot also demand the router's
            # alert fired (the respawn starts a fresh alert engine).
            if serving_kill and not router_kill:
                poller = threading.Thread(target=poll_alert, daemon=True)
                poller.start()
            loader.start()
            t0 = time.monotonic()
            applied = engine.run_round(faults)
            if router_kill:
                # Same-port respawn, the `_router_kill_round` recipe:
                # reap the corpse, clear the stale announce, relaunch.
                old_router = router_box[0]
                try:
                    old_router.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    pass
                try:
                    os.remove(addr_file)
                except OSError:
                    pass
                router_box[0] = _spawn_router(args, tdir, addr_file,
                                              port=router_addr[1])
            ps_ok = psf.wait_converged(timeout_s=180)
            t_ps = time.monotonic()
            serve_ok, t_serve = True, time.monotonic()
            if serving_kill or router_kill:
                serve_ok = False
                deadline = time.monotonic() + 180
                while time.monotonic() < deadline:
                    try:
                        st = fetch_fleet_stats(router_addr)
                        if all(f"replica-{i}" in st.get("replicas", {})
                               for i in serving_live):
                            serve_ok, t_serve = True, time.monotonic()
                            break
                    except Exception:  # noqa: BLE001 - router busy or
                        pass           # link fault still reverting
                    time.sleep(0.1)
            loader.join()
            if poller is not None:
                poller.join(timeout=35)
            # Quiesce the training stream and take the parity gate:
            # acked MUST equal the recovered world exactly, every round.
            train_gate.clear()
            with train_mutex:
                got = np.asarray(psf.table.get())
                parity = bool(np.array_equal(got, acked))
            train_gate.set()
            t_conv = max(t_ps, t_serve)
            with sstats.lock:
                errs_outside = sum(
                    1 for t in sstats.error_times
                    if not (t0 <= t <= t_conv + hedge_window_s))
                window = {"n_ok": len(sstats.latencies),
                          "n_shed": sstats.sheds,
                          "n_error": sstats.errors}
            round_records.append({
                "faults": applied,
                "converged": bool(ps_ok and serve_ok),
                "ps_converge_s": round(t_ps - t0, 3),
                "serving_converge_s":
                    round(t_serve - t0, 3)
                    if (serving_kill or router_kill) else None,
                "router_killed": router_kill,
                "parity_ok": parity,
                "acked_adds": n_adds[0],
                "serving_errors_outside_window": errs_outside,
                "serving_window": window,
                "heartbeat_loss_alert":
                    alert_state.get("heartbeat_loss")
                    if (serving_kill and not router_kill) else None,
            })
    finally:
        train_stop.set()
        train_gate.set()
        trainer.join(timeout=60)
        ps_status = psf.status()
        psf.close()
        sup.stop()
        for i, h in sup.slots().items():
            if i < len(procs):
                procs[i] = getattr(h, "proc", h)

    elastic = _elastic_round(seed)
    return {
        "seed": seed,
        "shards": shards,
        "serving_replicas": len(serving_live),
        "rounds": round_records,
        "slow_disk_seats": slow,
        "converged_all_rounds": all(r["converged"]
                                    for r in round_records),
        "zero_acked_loss": (all(r["parity_ok"] for r in round_records)
                            and not train_errors),
        "acked_adds": n_adds[0],
        "train_errors": train_errors[:10],
        "ps_supervisor": ps_status.get("supervisor"),
        "ps_events": ps_status.get("events", []),
        "serving_respawns": sup.status()["respawns"],
        "elastic": elastic,
    }


def run_fleet(args) -> dict:
    from multiverso_tpu.fleet import FleetClient, fetch_fleet_stats
    from multiverso_tpu.telemetry import TraceBuffer, get_trace_buffer

    rng = np.random.default_rng(0)
    table = rng.normal(size=(args.rows, args.cols)).astype(np.float32)
    tdir = args.telemetry_dir or tempfile.mkdtemp(prefix="serve_trace_")
    os.makedirs(tdir, exist_ok=True)
    addr_file = os.path.join(tdir, "router_addr")

    router_proc = _spawn_router(args, tdir, addr_file)
    # Boxed so the chaos router-kill round can swap in the respawned
    # handle and teardown still reaps the RIGHT process.
    router_box = [router_proc]
    procs: list = []
    fleet = None
    record = None
    try:
        router_addr = _wait_addr_file(addr_file, [router_proc])
        procs = [_spawn_replica(args, router_addr, i, tdir)
                 for i in range(args.replicas)]

        # argparse hands --hedge over as a string; FleetClient only honors
        # a fixed delay when given a NUMBER (a numeric string would
        # silently mean "adaptive").
        hedge = args.hedge if args.hedge in ("adaptive", "off") \
            else float(args.hedge)
        fleet = FleetClient(router_addr, hedge=hedge,
                            refresh_s=args.heartbeat_ms / 1e3,
                            rpc_timeout_ms=args.rpc_timeout_ms or None,
                            hot_staleness=float(args.cache_staleness))
        deadline = time.monotonic() + 240
        while len(fleet.refresh().members) < args.replicas:
            if any(p.poll() is not None for p in procs) \
                    or router_proc.poll() is not None:
                raise RuntimeError("a fleet process exited during "
                                   "bring-up")
            if time.monotonic() > deadline:
                raise RuntimeError("fleet replicas never joined")
            time.sleep(0.05)

        # Warm the data-path connections + reply decode before timing.
        _set_sample_rate(0.0)
        for _ in range(10):
            fleet.lookup(rng.integers(0, args.rows, args.keys_per_req)
                         .astype(np.int32), deadline_ms=10_000, timeout=60)

        # Roofline baseline for the bench client's own plane — the
        # end-of-run verdict then classifies the whole load window.
        from multiverso_tpu.telemetry.roofline import verdict as _rl_verdict
        _rl_verdict("client")

        parity_ok = _parity_check(fleet, table, args.rows,
                                  args.keys_per_req)
        sampler = _key_sampler(args.rows, args.keys_per_req,
                               args.hot_frac, args.hot_keys,
                               zipf_alpha=args.zipf)

        # Interleaved untraced/traced load windows (A,B,A,B), all
        # DRILL-FREE: traced-vs-untraced QPS measures sampling overhead
        # with slow drift in box load cancelled out — not drain
        # disruption, not whichever phase drew the noisier seconds. The
        # drills get their own window below.
        get_trace_buffer().set_capacity(TraceBuffer.EXPORT_CAPACITY)
        stats_un, stats = _LoadStats(), _LoadStats()
        elapsed_un = elapsed = 0.0
        cpu0 = {"bench": _proc_cpu_s(os.getpid()),
                "router": _proc_cpu_s(router_proc.pid),
                **{f"replica-{i}": _proc_cpu_s(p.pid)
                   for i, p in enumerate(procs)}}
        for _half in range(2):
            _set_sample_rate(0.0)
            elapsed_un += _run_fleet_load(
                fleet, stats_un, args.threads, args.qps,
                args.duration / 2, args.rows, args.keys_per_req,
                args.deadline_ms, sampler)
            _set_sample_rate(args.sample_rate)
            elapsed += _run_fleet_load(
                fleet, stats, args.threads, args.qps, args.duration / 2,
                args.rows, args.keys_per_req, args.deadline_ms, sampler)
        qps_untraced = len(stats_un.latencies) / elapsed_un \
            if elapsed_un > 0 else 0.0
        wall = elapsed_un + elapsed
        cpu_pct = {"bench": round(100 * (_proc_cpu_s(os.getpid())
                                         - cpu0["bench"]) / wall, 1),
                   "router": round(100 * (_proc_cpu_s(router_proc.pid)
                                          - cpu0["router"]) / wall, 1),
                   **{f"replica-{i}":
                      round(100 * (_proc_cpu_s(p.pid)
                                   - cpu0[f"replica-{i}"]) / wall, 1)
                      for i, p in enumerate(procs)}}

        # Offered-QPS sweep (one curve, one history record) — untraced,
        # after the headline windows so it cannot contaminate them.
        sweep = None
        if args.qps_sweep:
            def fleet_at_qps(q, stats_s, dur):
                return _run_fleet_load(fleet, stats_s, args.threads, q,
                                       dur, args.rows, args.keys_per_req,
                                       args.deadline_ms, sampler)

            def fleet_cpu():
                return {"bench": _proc_cpu_s(os.getpid()),
                        "router": _proc_cpu_s(router_proc.pid),
                        **{f"replica-{i}": _proc_cpu_s(p.pid)
                           for i, p in enumerate(procs)
                           if p.poll() is None}}
            sweep = _run_qps_sweep(args, fleet_at_qps, fleet_cpu,
                                   cores=os.cpu_count())

        # Cache-hit witness for the fleet smoke: the same keys twice in a
        # row land on the same replica (ring affinity), so the second
        # lookup must answer from its hot-row cache when enabled.
        if args.cache_rows > 0:
            from multiverso_tpu.serving import ShedError
            hot = rng.integers(0, args.rows, args.keys_per_req) \
                .astype(np.int32)
            for _ in range(3):
                try:
                    fleet.lookup(hot, deadline_ms=10_000, timeout=60)
                except ShedError:
                    pass    # a drain-lagged replica may shed one; the
                            # witness only needs one hit to land

        # Guaranteed-sampled probes + the cluster rollup BEFORE the
        # drills (ISSUE 13 reorder): the hedged-sibling and 2-replica
        # Fleet_Stats witnesses need the full fleet alive, and the fault
        # drill is about to kill a replica for good.
        _trace_smoke_requests(args, fleet, router_addr)
        fleet_stats = fetch_fleet_stats(router_addr)

        # SLO-burn alert shipping witness (--slo-drill): replica-0 runs
        # with an unreachable SLO, so the headline load must have fired
        # its burn alert — poll the ROUTER's rollup until the replica's
        # heartbeat-shipped alert shows in Fleet_Stats.
        slo_breach = None
        if args.slo_drill:
            def _r0_burn(st):
                return any(a.get("name") == "serve.slo_burn"
                           for a in st.get("replicas", {})
                           .get("replica-0", {}).get("alerts", []))
            fired, st = _await_fleet_alert(router_addr, _r0_burn,
                                           timeout_s=20)
            if fired:
                slo_breach = {"fired": True, "replica": "replica-0",
                              "alerts": st["replicas"]["replica-0"]
                              ["alerts"],
                              "alerts_active_fleet":
                              st["fleet"].get("alerts_active", 0)}
                fleet_stats = st    # the rollup WITH the alert
            else:
                slo_breach = {"fired": False, "replica": "replica-0",
                              "alerts": []}

        # Shard-imbalance drill (ISSUE 14): skew the whole key stream
        # onto one ring owner; the router's imbalance alert must fire
        # and ship into Fleet_Stats. BEFORE the fault drill — the skew
        # needs every replica alive to have a balanced baseline to
        # diverge from.
        skew = None
        if args.skew_drill:
            skew = _skew_drill(args, fleet, router_addr)

        # Skew self-heal drill (ISSUE 17): same stream shape, but now
        # the router's actuators are expected to CLOSE the loop the
        # skew drill only detects. Needs the actuators enabled.
        rebal_heal = None
        if args.rebalance_drill and args.replicas >= 2 \
                and (args.rebalance or args.hotkey_replicas):
            rebal_heal = _rebalance_drill(args, fleet, router_addr)

        # Recovery drill (ISSUE 15), replica leg — BEFORE the fault
        # drill, so the full fleet is alive: the kill is masked by
        # hedging/failover while the supervisor replaces the victim
        # (the self-healing headline), and the supervisor never has to
        # reason about the fault drill's deliberately-dead corpse. The
        # PS/WAL legs run AFTER fleet teardown: their A/B needs a quiet
        # box (three heartbeating subprocesses on the 1-core CI box
        # swung per-window rates +-40%).
        recovery = None
        if args.recovery_drill:
            recovery = {
                "replica": _replica_recovery_drill(args, router_addr,
                                                   procs, tdir),
            }

        # Phase C — drill window: fresh load with the drain/fault drills
        # running against it (drained + killed replicas also land in the
        # traces, since sampling stays on).
        drill: dict = {}
        if args.drain_drill or (args.fault_drill and len(procs) > 1):
            dstats = _LoadStats()
            drill_state: dict = {}

            def drills():
                # Drain drill at 30% of the window: rolling-drain the
                # whole fleet (wire-triggered, the operator path) while
                # load runs; count request errors in the window.
                if args.drain_drill:
                    time.sleep(args.duration * 0.3)
                    with dstats.lock:
                        e0 = dstats.errors
                    t0 = time.monotonic()
                    ok = _wire_rolling_drain(router_addr, fleet,
                                             timeout_s=60)
                    with dstats.lock:
                        e1 = dstats.errors
                    drill_state["drain"] = {
                        "completed": bool(ok),
                        "duration_s": round(time.monotonic() - t0, 3),
                        "failed_requests": e1 - e0,
                    }
                # Fault drill at 60%: abrupt-kill one replica under
                # load. SIGABRT instead of SIGKILL (ISSUE 13): the
                # victim's fatal-signal handler dumps a postmortem and
                # then re-raises the signal with SIG_DFL, so death is
                # exactly as abrupt (no drain, no goodbye, in-flight
                # requests dropped — the masking story is unchanged)
                # but the corpse leaves an artifact.
                if args.fault_drill and len(procs) > 1:
                    now = time.monotonic()
                    target = args.duration * 0.6 - (now - t_start[0])
                    if target > 0:
                        time.sleep(target)
                    victim = procs[-1]
                    t_kill = time.monotonic()
                    victim.send_signal(signal.SIGABRT)
                    drill_state["t_kill"] = t_kill
                    drill_state["victim_pid"] = victim.pid
                    # Poll for the router's heartbeat-loss alert NOW,
                    # while the load window still runs: the alert is
                    # transient (fires once on the death, resolves after
                    # ~5s of quiet), so a poll that only starts after a
                    # long load window would find it already resolved
                    # and wrongly record a detection failure.
                    drill_state["heartbeat_loss"] = _await_heartbeat_loss(
                        router_addr)

            t_start = [time.monotonic()]
            driller = threading.Thread(target=drills, daemon=True)
            driller.start()
            t_start[0] = time.monotonic()
            d_elapsed = _run_fleet_load(fleet, dstats, args.threads,
                                        args.qps, args.duration,
                                        args.rows, args.keys_per_req,
                                        args.deadline_ms)
            driller.join(timeout=120)

            drill = {k: v for k, v in drill_state.items()
                     if k not in ("t_kill", "victim_pid",
                                  "heartbeat_loss")}
            if "t_kill" in drill_state:
                t_kill = drill_state["t_kill"]
                window_s = (args.liveness_misses
                            * args.heartbeat_ms) / 1e3
                with dstats.lock:
                    in_window = sum(1 for t in dstats.error_times
                                    if t_kill <= t <= t_kill + window_s)
                    after = sum(1 for t in dstats.error_times
                                if t > t_kill)
                drill["fault"] = {
                    "killed": "replica-%d" % (len(procs) - 1),
                    "signal": "SIGABRT",
                    "errors_after_kill": after,
                    "errors_in_liveness_window": in_window,
                    "errors_past_window": after - in_window,
                    "liveness_window_s": window_s,
                    # Detection + artifact evidence (ISSUE 13): the
                    # router must ALERT on the death and the victim
                    # must leave a parseable postmortem. The alert poll
                    # ran in the drill thread, concurrent with the kill;
                    # the fallback covers a drill thread that died
                    # before storing its result.
                    "heartbeat_loss_alert": drill_state.get(
                        "heartbeat_loss") or _await_heartbeat_loss(
                            router_addr),
                    "postmortem": _await_postmortem(
                        tdir, drill_state["victim_pid"]),
                }
            with dstats.lock:
                drill["window"] = {
                    "achieved_qps": round(len(dstats.latencies)
                                          / d_elapsed, 1)
                    if d_elapsed > 0 else 0.0,
                    "n_ok": len(dstats.latencies),
                    "n_shed": dstats.sheds,
                    "n_error": dstats.errors,
                }

        # Chaos drill (ISSUE 16): seeded kill-any-subset over a
        # supervised multi-shard PS fleet under live training, with the
        # serving fleet taking lookup load (and possibly losing a
        # replica) at the same time. Runs after the scripted drills so
        # its random subset never fights their deterministic victims.
        chaos = None
        if args.chaos_drill:
            chaos = _chaos_drill(args, router_addr, procs, tdir, fleet,
                                 router_box=router_box,
                                 addr_file=addr_file)
            # Control-plane leg AFTER the subset rounds (the serving
            # supervisor is stopped by then — a router outage must not
            # race a healer that reads membership through the router).
            chaos["router_kill"] = _router_kill_round(
                args, router_box, router_addr, addr_file, procs, tdir,
                fleet)

        record = _make_record("serve_fleet_lookup", args, stats, elapsed,
                              _metric_families(("serve.", "fleet.")))
        if recovery is not None:
            record["recovery"] = recovery
        if chaos is not None:
            record["chaos"] = chaos
        if rebal_heal is not None:
            record["rebalance"] = {"self_heal": rebal_heal}
        record["parity_ok"] = bool(parity_ok)
        record["replicas"] = args.replicas
        record["cpu_cores"] = os.cpu_count()
        record["process_cpu_pct"] = cpu_pct
        record["fleet_stats"] = fleet_stats
        per = fleet_stats.get("replicas", {})
        record["pipeline"] = {
            "depth_flag": args.pipeline_depth,
            "max_inflight": max(
                [p.get("pipeline_inflight_max", 0.0)
                 for p in per.values()], default=0.0),
            "cache_hits": int(fleet_stats.get("fleet", {})
                              .get("cache_hits", 0)),
        }
        # Watchdog steady state, measured where the monitored daemon
        # loops actually RUN — the replica + router subprocesses (the
        # bench client process registers no watchdog handles, so its own
        # counter can only ever read 0 and proves nothing). Trips ship
        # on the heartbeat into the rollup; merge the pre-drill and
        # post-drill rollups per replica (max of each) — the fault
        # drill's victim is swept from the ring, so the final rollup
        # alone would silently DROP any trips it reported before dying.
        final_stats = fleet_stats
        try:
            final_stats = fetch_fleet_stats(router_addr)
        except Exception:  # noqa: BLE001 - router gone at teardown edge
            pass
        trips_by: dict = {}
        for st in (fleet_stats, final_stats):
            for rid, row in st.get("replicas", {}).items():
                trips_by[rid] = max(trips_by.get(rid, 0),
                                    int(row.get("watchdog_trips", 0)))
        record["observability"] = {
            "slo_breach": slo_breach,
            "skew": skew,
            "watchdog": {
                "fleet_trips": sum(trips_by.values()),
                "router_trips": max(
                    int(fleet_stats.get("router_watchdog_trips", 0)),
                    int(final_stats.get("router_watchdog_trips", 0))),
                "monitored_replicas": len(trips_by),
            },
        }
        if sweep is not None:
            record["qps_sweep"] = sweep
        # Attribution embeds (ISSUE 18): the bench client classifies its
        # own plane locally; each replica's serve-plane verdict + tail
        # exemplars arrived on the heartbeat and sit in the rollup.
        client_verdict = _rl_verdict(
            "client", overrides={"qps": record["achieved_qps"],
                                 "host_cpu":
                                 cpu_pct.get("bench", 0.0) / 100.0})
        record["roofline"] = {
            "client": client_verdict,
            "replicas": {rid: row.get("roofline", {})
                         for rid, row in
                         fleet_stats.get("replicas", {}).items()},
        }
        record["exemplars"] = fleet_stats.get("fleet", {}) \
            .get("exemplars", [])
        # Box-constraint honesty via the roofline verdict (replaces the
        # PR-9 ad-hoc CPU%% threshold): a host-bound bench client while
        # every replica has headroom means the achieved number measures
        # the bench box (ROADMAP 2(a)), and the record says so.
        replica_cpu = [v for k, v in cpu_pct.items()
                       if k.startswith("replica")]
        if client_verdict["bound"] == "host" and replica_cpu \
                and max(replica_cpu) < 80.0:
            record["warning"] = (
                f"bench client host-bound (roofline verdict 'host': "
                f"client {cpu_pct['bench']}%, max replica "
                f"{max(replica_cpu)}% of one core): achieved QPS is "
                "capped by the load generator/box, not the serving "
                "plane")
        if drill:
            record["drill"] = drill
        if args.baseline and os.path.exists(args.baseline):
            with open(args.baseline) as f:
                base = json.load(f)
            if base.get("achieved_qps"):
                record["scaleout_vs_baseline"] = {
                    "baseline_replicas": base.get("replicas",
                                                  base["config"]
                                                  .get("replicas", 1)),
                    "baseline_achieved_qps": base["achieved_qps"],
                    "ratio": round(record["achieved_qps"]
                                   / base["achieved_qps"], 3),
                }
    finally:
        if fleet is not None:
            fleet.close()
        # Graceful stop so every process flushes its final trace — the
        # stitch below reads what they wrote.
        _shutdown_procs(procs + [router_box[0]])
    if record.get("recovery") is not None:
        # PS-side durability legs on the now-quiet box (see above).
        record["recovery"]["wal"] = _wal_recovery_leg(args)
        record["recovery"]["wal_overhead"] = _wal_overhead_ab(args)
    if args.rebalance_drill:
        # Static-vs-actuated zipf A/B on the quiet box (same reasoning
        # as the WAL legs: mini-fleets must not fight the main fleet
        # for cores).
        record.setdefault("rebalance", {})["ab"] = _rebalance_ab(args,
                                                                 tdir)
    _export_local_trace(tdir)
    record["tracing"] = _tracing_block(args, tdir, record["achieved_qps"],
                                       qps_untraced)
    return record


def _make_record(benchmark: str, args, stats: _LoadStats,
                 elapsed: float, metrics: dict) -> dict:
    with stats.lock:
        lat = list(stats.latencies)
        n_shed, n_err, total = stats.sheds, stats.errors, stats.sent
    n_ok = len(lat)
    return {
        # v3: + tracing block (sample_rate, traced/untraced QPS,
        # stage_breakdown, slowest-K stitched timelines, trace_smoke)
        # and fleet_stats rollup embed in fleet mode.
        # v4: + pipeline block (window depth/occupancy + cache hit
        # witnesses), optional qps_sweep (achieved-vs-offered knee with
        # per-point CPU%) and client-CPU-bound warning.
        # v5: + decode_memory block (paged-vs-prealloc users-per-chip at
        # a fixed simulated HBM budget, prefix-reuse witness, kv-dtype
        # comparison, bitwise parity witness embedded).
        # v6: + observability block (alerts/watchdog overhead A/B,
        # synthetic SLO-breach burn-rate witness, watchdog steady
        # state), fleet drill.fault gains heartbeat_loss_alert +
        # postmortem (SIGABRT fault drill), fleet_stats rows carry
        # per-replica alerts + router_alerts.
        # v7: + hotkeys block (planted-Zipf sketch recovery +
        # cache-headroom advisor), observability.skew (shard-imbalance
        # detect-and-ship drill), fleet_stats rows carry keys_rate/
        # skew/hot_keys + fleet shard_load_ratio, and a `box`
        # fingerprint (scripts/bench_guard.py warns instead of failing
        # when the box changed under a record).
        # v8: + recovery block (--recovery-drill): wal leg (SIGKILL'd
        # journaled PS shard, supervisor respawn, recovered-bytes
        # parity + time-to-recover), wal_overhead A/B (group-commit
        # hot-path cost, acceptance <= 2%), and fleet-mode replica leg
        # (SIGKILL under load -> heartbeat-loss -> automatic
        # replacement joins the ring; errors after the hedging window).
        # v9: + chaos block (--chaos-drill): seeded kill-any-subset
        # rounds over a supervised multi-shard PS fleet (per-round
        # faults/convergence/parity, zero_acked_loss, slow-disk seats)
        # plus the elastic worker leave+rejoin round; config grows
        # chaos_seed/chaos_rounds/rpc_timeout_ms.
        # v10: + rebalance block (--rebalance-drill): skew self-heal
        # witness (shard_load_ratio back under the imbalance bar with
        # zero client errors while the skewed stream still runs) and
        # the static-vs-actuated zipf A/B legs; chaos gains the
        # router-kill round (SIGKILL the router, respawn on the same
        # port, replicas + clients reconnect via connect_with_backoff);
        # config grows hotkey_replicas/rebalance/cache_mem_budget.
        # v11: + attribution layer (ISSUE 18): tracing.critical_path
        # (per-trace phase ledgers, conservation rate, published
        # residual, paced-probe sub-report), roofline (per-plane bound
        # verdicts — client locally, replica serve planes via the
        # heartbeat rollup), exemplars (slowest-request phase ledgers
        # with resolvable trace ids), profile (sampling-profiler
        # summary), observability.attribution_ab (ledger+profiler
        # overhead A/B, acceptance <= 1%); the client-CPU-bound
        # warnings now come from the roofline classifier.
        # v12: + lockwitness (graftsan, ISSUE 19): dry-run witness leg —
        # observed acquisition-order edges, lock.* hold-time histograms,
        # inversions (must be 0), and the structural witness-off A/B
        # (make_lock hands back the bare threading primitive).
        "schema": "multiverso_tpu.bench_serve/v12",
        "benchmark": benchmark,
        "time_unix": time.time(),
        "box": {"cores": os.cpu_count(),
                "machine": platform.machine(),
                "python": platform.python_version()},
        "config": {k: (v if not isinstance(v, tuple) else list(v))
                   for k, v in vars(args).items()},
        "offered_qps": args.qps,
        "achieved_qps": n_ok / elapsed if elapsed > 0 else 0.0,
        "latency_ms": _percentiles(lat),
        "n_ok": n_ok,
        "n_shed": n_shed,
        "n_error": n_err,
        "shed_rate": n_shed / total if total else 0.0,
        "error_rate": n_err / total if total else 0.0,
        "serve_metrics": metrics,
    }


def main() -> int:
    # Serving-plane processes are IO multiplexers juggling many short
    # GIL slices; CPython's default 5ms switch interval convoys them
    # (request p50 inflates toward the switch interval). 0.5ms measured
    # ~2x on the 2-core CI box. fleet_main does the same for replicas.
    sys.setswitchinterval(5e-4)
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--rows", type=int, default=100_000)
    p.add_argument("--cols", type=int, default=64)
    p.add_argument("--keys-per-req", type=int, default=8)
    p.add_argument("--buckets", default="8,16,32,64")
    p.add_argument("--max-batch", type=int, default=8)
    p.add_argument("--max-wait-ms", type=float, default=2.0)
    p.add_argument("--admission", type=int, default=64)
    p.add_argument("--threads", type=int, default=4)
    p.add_argument("--qps", type=float, default=500.0,
                   help="target aggregate request rate")
    p.add_argument("--duration", type=float, default=5.0)
    p.add_argument("--deadline-ms", type=float, default=100.0)
    p.add_argument("--wire-dtype", default="f32", choices=("f32", "bf16"))
    p.add_argument("--pipeline-depth", default="auto",
                   help="device dispatch pipeline depth: int, or 'auto' "
                   "for the measured-latency decision table; 0 = "
                   "serialized dispatch (the pre-PR-9 path)")
    p.add_argument("--cache-rows", type=int, default=0,
                   help="hot-row LRU cache capacity in rows (0 = off)")
    p.add_argument("--cache-staleness", type=int, default=0,
                   help="max clock-tick age a cached row may serve")
    p.add_argument("--hot-frac", type=float, default=0.0,
                   help="fraction of requests drawing all keys from a "
                   "fixed hot set (cache workload skew; 0 keeps the "
                   "uniform workload for record comparability)")
    p.add_argument("--hot-keys", type=int, default=64,
                   help="size of the hot key set --hot-frac draws from")
    p.add_argument("--zipf", type=float, default=0.0,
                   help="ALPHA > 1: draw keys Zipf(ALPHA) over the whole "
                   "table through a fixed rank permutation — the "
                   "power-law stream real traffic follows; also arms "
                   "the hot-key sketch recovery witness (0 = off)")
    p.add_argument("--skew-drill", action="store_true",
                   help="fleet mode: route a whole window to ONE ring "
                   "owner and assert the router's fleet.shard_imbalance "
                   "alert fires and ships into Fleet_Stats")
    p.add_argument("--prefix-frac", type=float, default=0.0,
                   help="decode-memory leg: fraction of decode requests "
                   "repeating one shared prompt (0 = leg default 0.5)")
    p.add_argument("--kv-dtype", default="f32",
                   choices=("f32", "bf16", "int8"),
                   help="decode-memory leg: paged KV storage dtype to "
                   "compare against f32")
    p.add_argument("--kv-page", type=int, default=16,
                   help="decode-memory leg: KV page size in positions")
    p.add_argument("--decode-bench", action="store_true",
                   help="run the full decode-memory leg (paged vs "
                   "prealloc users-per-chip, f32/bf16/int8) in single "
                   "mode")
    p.add_argument("--qps-sweep", default="",
                   help="A:B:STEP offered-QPS sweep recorded as the "
                   "achieved-vs-offered knee in one history record")
    p.add_argument("--overload", action="store_true",
                   help="drive QPS past capacity with tight deadlines to "
                   "exercise the shed path (single-process mode)")
    p.add_argument("--replicas", type=int, default=0,
                   help="N>=1: fleet mode — router + N replica "
                   "subprocesses behind a hedged FleetClient")
    p.add_argument("--hotkey-replicas", type=int, default=0,
                   help="fleet mode: replicate each confident hot key "
                   "to this many extra ring owners (router-side skew "
                   "actuator; 0 = off)")
    p.add_argument("--rebalance", action="store_true",
                   help="fleet mode: enable vnode drain-and-handoff "
                   "rebalancing when imbalance survives replication")
    p.add_argument("--cache-mem-budget", type=int, default=0,
                   help="per-replica hot-row cache memory budget in "
                   "bytes: the sketch advisor auto-sizes "
                   "-serve_cache_rows inside it (0 = fixed capacity)")
    p.add_argument("--rebalance-drill", action="store_true",
                   help="fleet mode: skew self-heal witness (actuators "
                   "must bring shard_load_ratio back under the "
                   "imbalance bar with zero client errors) plus the "
                   "static-vs-actuated zipf A/B legs (ISSUE 17)")
    p.add_argument("--hedge", default="adaptive",
                   help="fleet hedge policy: adaptive|off|<ms>")
    p.add_argument("--heartbeat-ms", type=float, default=50.0)
    p.add_argument("--liveness-misses", type=int, default=4)
    p.add_argument("--drain-drill", action="store_true",
                   help="rolling-drain every replica mid-load")
    p.add_argument("--fault-drill", action="store_true",
                   help="abrupt-kill one replica mid-load (SIGABRT: as "
                   "sudden as SIGKILL for the fleet, but the victim's "
                   "fatal-signal handler leaves a postmortem dump); the "
                   "record asserts a router heartbeat-loss alert fired "
                   "and the dump parsed")
    p.add_argument("--recovery-drill", action="store_true",
                   help="durability drill (ISSUE 15): SIGKILL a "
                   "WAL-journaled PS shard mid-stream and (fleet mode) a "
                   "serving replica under load; a ReplicaSupervisor "
                   "respawns both through the recovery path; the record "
                   "asserts recovered-bytes parity, time-to-recover, and "
                   "zero errors after the hedging window, plus a WAL "
                   "hot-path A/B (acceptance <= 2%)")
    p.add_argument("--slo-drill", action="store_true",
                   help="give replica-0 an unreachable SLO so its "
                   "burn-rate alert provably fires under load and ships "
                   "via heartbeat into Fleet_Stats/fleet_top")
    p.add_argument("--chaos-drill", action="store_true",
                   help="chaos drill (ISSUE 16): seeded kill-any-subset "
                   "over a supervised multi-shard PS fleet under live "
                   "training + serving load (fleet/chaos.py); each round "
                   "asserts convergence to full membership, zero "
                   "acked-write loss (WAL parity exact), and serving "
                   "errors confined to the recovery+hedge window; ends "
                   "with an elastic worker leave+rejoin round")
    p.add_argument("--chaos-seed", type=int, default=16,
                   help="chaos schedule seed: the same seed replays the "
                   "same faults (targets, kinds, offsets)")
    p.add_argument("--chaos-rounds", type=int, default=0,
                   help="chaos rounds; 0 = auto (2 dry-run, 3 full)")
    p.add_argument("--rpc-timeout-ms", type=float, default=0.0,
                   help="per-RPC deadline for bench FleetClients; an "
                   "attempt outliving it is abandoned and retried "
                   "against the next ring owner (0 = off)")
    p.add_argument("--obs-ab", action="store_true",
                   help="run the observability overhead A/B leg "
                   "(alerts+watchdog on vs off) in single mode")
    p.add_argument("--baseline", default="",
                   help="previous record to compute scaleout ratio against")
    p.add_argument("--sample-rate", type=float, default=0.05,
                   help="head-based trace sampling rate for the traced "
                   "load phase (the untraced reference phase always runs "
                   "at 0)")
    p.add_argument("--slow-k", type=int, default=5,
                   help="record the K slowest stitched request timelines")
    p.add_argument("--telemetry-dir", default="",
                   help="trace/snapshot directory shared by every fleet "
                   "process (default: a fresh temp dir)")
    p.add_argument("--out", default=os.path.join(_REPO, "BENCH_SERVE.json"))
    p.add_argument("--dry-run", action="store_true",
                   help="seconds-on-CPU smoke: tiny table, short run")
    args = p.parse_args()

    if args.dry_run:
        args.rows, args.cols = 2000, 16
        args.threads, args.qps = 2, 300.0
        args.duration = 4.0 if args.replicas else 1.5
        args.deadline_ms = 500.0
        args.sample_rate = 1.0      # the smoke asserts on stitched traces
        # The smoke also asserts the optimizations ENGAGED: pipeline
        # overlap (inflight >= 2) and a recorded cache hit.
        if args.cache_rows <= 0:
            args.cache_rows = 1024
        if args.replicas and args.chaos_drill:
            # An explicit --chaos-drill dry-run exercises ONLY the
            # chaos leg (the tier-1 smoke's shape): the scripted drills
            # would fight the random subset for victims and blow the
            # smoke's time budget.
            pass
        elif args.replicas:
            args.drain_drill = True
            # ...and the observability plane (ISSUE 13): the fault
            # drill's heartbeat-loss alert + postmortem witnesses and
            # the SLO-burn alert-shipping witness.
            args.slo_drill = True
            if args.replicas >= 2:
                args.fault_drill = True
                # ...and the traffic microscope (ISSUE 14): the
                # shard-imbalance detect-and-ship witness needs >= 2
                # replicas for a ratio to exist.
                args.skew_drill = True
                # ...and the durability spine (ISSUE 15): WAL recovery
                # parity + supervisor replacement witnesses.
                args.recovery_drill = True

    # One process per chip: before anything here touches jax, this process
    # takes the chip after the replicas' (it is the server in single mode
    # and the PS client seat in the drills) and leaves the rest to them.
    take_chip(args.replicas, _chip_holders(args), "serve_bench")
    record = run_fleet(args) if args.replicas >= 1 else run_single(args)
    _emit(record, args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
