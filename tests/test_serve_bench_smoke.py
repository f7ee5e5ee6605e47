"""Tier-1 smokes for the serving tooling surface.

``scripts/serve_bench.py --dry-run`` must stay runnable on CPU (the full
QPS numbers only mean something on a quiet box, but the harness itself —
service bring-up, pacing loop, percentile record — must not bit-rot), and
the ``serve_main`` CLI must stand up a replica-backed service end to end
from a checkpoint directory."""

import json
import os
import subprocess
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_REPO, "scripts", "serve_bench.py")


def test_serve_bench_dry_run_cpu(tmp_path):
    out = tmp_path / "BENCH_SERVE.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, _BENCH, "--dry-run", f"--out={out}"],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["benchmark"] == "serve_lookup"
    record = json.loads(out.read_text())
    # v9: + chaos block (--chaos-drill seeded kill-any-subset rounds);
    # config grows chaos_seed/chaos_rounds/rpc_timeout_ms
    assert record["schema"] == "multiverso_tpu.bench_serve/v12"
    assert record["box"]["cores"] >= 1
    lat = record["latency_ms"]
    assert set(lat) >= {"p50", "p95", "p99", "mean", "max"}
    assert lat["p50"] <= lat["p95"] <= lat["p99"] <= lat["max"]
    assert record["n_ok"] > 0
    assert 0.0 <= record["shed_rate"] <= 1.0
    assert record["achieved_qps"] > 0
    # tracing block: both QPS numbers + a trace-derived stage breakdown
    tracing = record["tracing"]
    assert tracing["qps_untraced"] > 0 and tracing["qps_traced"] > 0
    breakdown = tracing["stage_breakdown"]
    for stage in ("admit_wait", "batch_form", "device", "reply"):
        assert breakdown[stage]["count"] > 0, stage
        assert breakdown[stage]["p50"] <= breakdown[stage]["p95"] \
            <= breakdown[stage]["p99"]
    assert tracing["slowest"], "no slow-request timelines recorded"
    slow = tracing["slowest"][0]
    assert slow["n_spans"] >= 3 and slow["stages"]
    # the serve.* metric family rides along with the record
    assert any(k.startswith("serve.latency.")
               for k in record["serve_metrics"]["histograms"])
    assert "serve.queue_depth" in record["serve_metrics"]["gauges"]
    # PR-9 acceptance witnesses: the dispatch pipeline genuinely
    # OVERLAPPED (window occupancy reached >= 2 — not the serialized
    # path) and the hot-row cache recorded a hit. Either silently
    # regressing to the old path fails tier-1 here.
    pipe = record["pipeline"]
    assert pipe["depth"] >= 2, pipe
    assert pipe["max_inflight"] >= 2, pipe
    assert pipe["overlap_ok"] is True, pipe
    assert pipe["cache_hits"] >= 1, pipe
    assert pipe["cache_hit_ok"] is True, pipe
    assert "serve.pipeline.inflight" in record["serve_metrics"]["gauges"]
    # ISSUE-11 acceptance witnesses: the dry run forces a prefix-heavy
    # decode workload (shared-prompt burst) — the prefix cache must
    # record hits, paged f32 decode must be bitwise-equal to the drain
    # path, and peak pages resident must stay BELOW max-shape backing
    # for every slot (the decode memory hierarchy cannot silently
    # regress to preallocation).
    # ISSUE-13 acceptance witnesses: the observability plane measured
    # its own cost (A/B legs recorded — the number is box-noisy on 1
    # core, so the smoke bounds it loosely; full runs gate at 1%), the
    # synthetic SLO breach drove the shipped burn-rate state machine
    # through quiet -> tolerated spike -> fired-within-fast-window ->
    # resolved, and a stuck-free steady state tripped NO watchdog.
    obs = record["observability"]
    assert obs["ab"]["qps_plain"] > 0 and obs["ab"]["qps_observed"] > 0
    assert obs["ab"]["overhead_pct"] < 15.0, obs["ab"]
    slo = obs["slo_breach"]
    assert slo["baseline_quiet"] is True
    assert slo["spike_tolerated"] is True
    assert slo["fired"] is True
    assert slo["fired_within_fast_window"] is True, slo
    assert slo["resolved"] is True
    assert obs["watchdog"]["trips"] == 0, obs["watchdog"]
    # ISSUE-14 acceptance witnesses: the hot-key sketch recovered the
    # planted Zipf hot keys through the LIVE serving path (admission ->
    # cache -> device), its memory stayed under the configured bound,
    # and the cache-headroom advisor reported predicted-vs-measured hit
    # rates. The A/B above now also brackets the sketch's record()
    # appends (the plain leg disables them), so the <=1% full-run
    # acceptance covers this plane too.
    hk = record["hotkeys"]
    assert hk["recovered_count"] >= 9, hk
    assert hk["memory_ok"] is True, hk
    assert hk["memory_bytes"] <= hk["memory_bound"]
    assert hk["keys_observed"] > 0
    adv = hk["advisor"]
    assert 0.0 < adv["predicted_hit_rate"] <= 1.0, adv
    assert adv["predicted_hit_rate_2x"] >= adv["predicted_hit_rate"]
    assert "measured_hit_rate" in adv
    dm = record["decode_memory"]
    wit = dm["witness"]
    assert wit["paged_f32_bitwise_vs_drain"] is True, dm
    assert wit["prefix_hits_ok"] is True, dm
    assert wit["paged_held_ok"] is True, dm
    f32 = dm["runs"]["f32"]              # pure-paging witness run
    pref = dm["runs"]["f32+prefix"]      # shared-prompt burst run
    assert pref["prefix"]["hits"] >= 1
    assert pref["prefix"]["prefill_skipped"] >= 1
    assert f32["pages_used_max"] \
        < dm["max_batch"] * f32["pages_per_slot_max"]
    assert f32["users_per_chip_paged"] > f32["users_per_chip_prealloc"]
    assert pref["users_per_chip_prefix_shared"] \
        >= f32["users_per_chip_paged"]
    # ISSUE-18 acceptance witnesses: the attribution layer's phase
    # ledgers conserve on the paced probe (phases sum within 10% of
    # measured e2e, residual published into latency.unattributed), the
    # slowest-request exemplars carry trace ids resolvable against the
    # stitched trace file, every serving plane got a roofline verdict,
    # and the ledger+profiler A/B recorded its own overhead (box-noisy
    # on 1 core, so the smoke bounds it loosely; full runs gate at 1%).
    cp = record["tracing"]["critical_path"]
    probe = cp["probe"]
    assert probe["n_decomposed"] >= 10, probe
    assert probe["unattributed"]["mean_frac"] <= 0.10, probe
    assert probe["conserved_frac"] >= 0.5, probe
    assert cp["published_residual"]["count"] > 0, cp["published_residual"]
    assert cp["phases"].get("device", {}).get("total_ms", 0) > 0, cp
    ex = record["exemplars"]
    assert len(ex) > 0
    stitched = json.load(open(record["tracing"]["stitched_path"]))
    ids = {e.get("args", {}).get("trace")
           for e in stitched["traceEvents"] if e.get("ph") == "X"}
    assert any(e.get("trace") in ids for e in ex), ex
    assert all("phases" in e and e["total_ms"] > 0 for e in ex), ex
    rl = record["roofline"]
    for plane in ("serve", "client"):
        assert rl[plane]["bound"] in (
            "dispatch", "host", "wire", "device", "idle"), rl
    ab = obs["attribution_ab"]
    assert ab["qps_plain"] > 0 and ab["qps_attributed"] > 0
    assert ab["overhead_pct"] < 15.0, ab
    prof = record["profile"]
    assert prof["samples"] > 0 and prof["n_stacks"] > 0, prof
    # graftsan acceptance witnesses: the dry run's witness leg first
    # proves the OFF path hands out bare threading primitives (zero
    # overhead by construction — there is no instrumented code to pay
    # for), then drives a WAL commit + a nested lock pair under the
    # witness and records hold-time histograms with ZERO observed
    # inversions.
    lw = record["lockwitness"]
    assert lw["ab_off_is_bare_lock"] is True, lw
    assert lw["inversions"] == 0, lw
    assert lw["cycles"] == [], lw
    assert lw["edges"], lw
    held = lw["held_ms"]
    assert any(k.startswith("lock.wal.") for k in held), held
    for name, h in held.items():
        assert h["count"] > 0, (name, h)


def test_serve_main_cli_end_to_end(tmp_path):
    """serve_main: checkpoint dir in, bound address out, lookups served
    from the frozen replica — the full handoff through the real CLI."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    ckpt_dir = tmp_path / "ckpts"
    # Write a checkpoint with a driver process (the CLI reads, not shares,
    # the runtime).
    prep = subprocess.run(
        [sys.executable, "-c", f"""
import numpy as np
import multiverso_tpu as mv
from multiverso_tpu.core.checkpoint import save_all
mv.init([])
t = mv.create_table(mv.MatrixTableOption(num_row=32, num_col=4,
                                         name="served"))
t.add_rows(np.arange(32, dtype=np.int32),
           np.arange(128, dtype=np.float32).reshape(32, 4))
save_all({str(ckpt_dir)!r}, step=3)
mv.shutdown()
"""],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=240)
    assert prep.returncode == 0, prep.stdout + prep.stderr

    addr_file = tmp_path / "addr"
    proc = subprocess.Popen(
        [sys.executable, "-m", "multiverso_tpu.apps.serve_main",
         f"-checkpoint_dir={ckpt_dir}", "-serve_table=served",
         "-serve_buckets=4,8", "-serve_max_wait_ms=1",
         f"-serve_addr_file={addr_file}", "-serve_duration=45"],
        cwd=_REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    try:
        deadline = time.time() + 120
        while not addr_file.exists():
            assert proc.poll() is None, proc.communicate()[0][-3000:]
            assert time.time() < deadline, "serve_main never bound"
            time.sleep(0.1)
        host, port = addr_file.read_text().split(":")

        from multiverso_tpu.serving import ServingClient
        cli = ServingClient(host, int(port))
        try:
            q = np.asarray([0, 7, 31], np.int32)
            got = cli.lookup(q, deadline_ms=10_000, timeout=60)
            want = np.stack([np.arange(r * 4, r * 4 + 4) for r in q]) \
                .astype(np.float32)
            np.testing.assert_array_equal(got, want)
        finally:
            cli.close()
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
