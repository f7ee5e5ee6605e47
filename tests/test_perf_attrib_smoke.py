"""Tier-1 smoke for the perf-attribution harness.

``scripts/perf_attrib.py`` is the designated tie-breaker for the in-graph
loop de-optimization (docs/BENCHMARK.md Round 4) and runs for real only
inside a live-chip window — without an off-chip smoke it can bit-rot
between windows (and HAD never executed before one). ``--dry-run``
shrinks every leg to seconds on CPU."""

import glob
import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SCRIPT = os.path.join(_REPO, "scripts", "perf_attrib.py")


def test_perf_attrib_dry_run_cpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    tdir = tmp_path / "telemetry"
    proc = subprocess.run([sys.executable, _SCRIPT, "--dry-run",
                           f"--telemetry-dir={tdir}"],
                          cwd=_REPO, env=env, capture_output=True,
                          text=True, timeout=240)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = proc.stdout
    # every formulation leg reported a number (E legitimately skips when
    # the dry-run vocab is already sub-table-sized)
    for leg in ("A standalone", "B fori-full", "C fori-gather",
                "D fori-scatter", "F fori-sub"):
        assert leg in out, f"missing leg {leg!r}:\n{out}"
    assert out.count("ms/chunk") >= 5
    # telemetry snapshots + Chrome trace are emitted alongside the numbers
    from multiverso_tpu.telemetry import (validate_chrome_trace,
                                          validate_snapshot)
    snaps = sorted(glob.glob(str(tdir / "metrics-*.json")))
    assert snaps, f"no telemetry snapshots in {tdir}"
    with open(snaps[-1]) as f:
        snap = json.load(f)
    validate_snapshot(snap)
    spans = [n for n, h in snap["histograms"].items()
             if n.startswith("span.perf_attrib.") and h["count"]]
    assert spans, sorted(snap["histograms"])
    traces = glob.glob(str(tdir / "trace-*.json"))
    assert len(traces) == 1
    with open(traces[0]) as f:
        trace = json.load(f)
    validate_chrome_trace(trace)
    assert any(e.get("ph") == "X" for e in trace["traceEvents"])


def test_graftlint_json_output_stays_parseable():
    """Ride-along for the dry-run smoke: the graftlint ``--format json``
    path is part of the CI tooling surface (editors / report diffing),
    so its schema must stay machine-parseable even when the tree is
    clean and the findings list is empty."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    script = os.path.join(_REPO, "scripts", "graftlint.py")
    proc = subprocess.run(
        [sys.executable, script, "--format", "json",
         os.path.join(_REPO, "multiverso_tpu", "analysis")],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["version"] == 1
    assert isinstance(payload["findings"], list)
    assert {"files", "suppressed", "baselined", "stale_baseline",
            "parse_errors"} <= set(payload)
