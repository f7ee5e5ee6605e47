"""CPU rehearsal: every cell of BENCHMARK.json at tiny sizes, through the same
run.py and harness (the x4 cell on four virtual devices), prints a last line
with exactly the contract's keys; a cell, a configuration, a mix and a
per-layer metric can be added as files and one entry each; and without a TPU
the real command fails and prints no result."""
import json
import os
import subprocess
import sys

import pytest

import tiny

HERE = os.path.dirname(os.path.abspath(__file__))
KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def _bench():
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _run(tmp, workload, trace, chips=1, seed=2 ** 31 + 11, seconds=1.5):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false")
    if chips > 1:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            f" --xla_force_host_platform_device_count={chips}")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse.py"), str(tmp),
         "--workload", workload, "--seed", str(seed), "--seconds",
         str(seconds), "--trace", str(trace)],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


@pytest.mark.parametrize("cell", [w["name"] for w in _bench()["workloads"]])
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearses_on_cpu(tmp_path, cell, trace):
    bench = _bench()
    entry = next(w for w in bench["workloads"] if w["name"] == cell)
    result, out = _run(tmp_path, cell, trace, chips=entry["chips"])
    assert set(result) == KEYS | ({"breakdown"} & set(result))
    assert result["correct"] is True, out[-3000:]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert DEVICE_KEYS <= set(result["device"])
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] >= entry["chips"]
    kind = "per_layer" if trace else "end_to_end"
    listed = {m["name"]: m for m in bench[kind]
              if "workloads" not in m or cell in m["workloads"]}
    assert result["metrics"], "a run reports at least one metric"
    for name, m in result["metrics"].items():
        assert name in listed and m["unit"] == listed[name]["unit"]
        assert isinstance(m["value"], float)
    if trace:
        assert {"busy_s", "window_s"} <= set(result["device"])
        # the CPU has no device plane: trace metrics are left out, the
        # span and clock metrics are there
        assert "setup_compile_s" in result["metrics"]
    else:
        assert set(result["metrics"]) == set(listed)
        assert "check compiles_in_window: 0.0 == 0 ok" in out


def test_new_cell_from_files_alone(tmp_path):
    """A configuration, a traffic mix, a per-layer metric and a cell, added
    as new files and one new entry each, run with no edit to any file."""
    root, bench_dir = tiny.make_root(str(tmp_path / "root"))
    with open(os.path.join(bench_dir, "configs", "dlrm-criteo-tb.json")) as f:
        config = json.load(f)
    config.update(name="dlrm-small-fields", fields=3)
    with open(os.path.join(bench_dir, "configs", "dlrm-small-fields.json"),
              "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench_dir, "traffic",
                           "impressions_zipf_b2048.json")) as f:
        traffic = json.load(f)
    traffic.update(batch=32, zipf=1.5)
    with open(os.path.join(bench_dir, "traffic", "impressions_hot_b32.json"),
              "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(bench_dir, "layer_metrics", "dlrm_step_ms.json"),
              "w") as f:
        json.dump({"name": "dlrm_step_ms", "layer": "DLRM step", "unit": "ms",
                   "better": "lower", "source": "host_clock",
                   "moves": "train_samples_per_s",
                   "workloads": ["dlrm_small"], "reader": "counter",
                   "args": {"name": "step_ms"}}, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "dlrm-small-fields", "source": "test",
        "file": "benchmark/configs/dlrm-small-fields.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "dlrm_small", "config": "dlrm-small-fields",
        "traffic": "impressions_hot_b32", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "dlrm_step_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "DLRM step",
        "moves": "train_samples_per_s", "workloads": ["dlrm_small"]})
    for m in bench["end_to_end"]:
        if m["name"] == "train_samples_per_s":
            m["workloads"].append("dlrm_small")
    with open(path, "w") as f:
        json.dump(bench, f)
    result, out = _run(tmp_path, "dlrm_small", 1)
    assert result["correct"] is True, out[-3000:]
    assert result["metrics"]["dlrm_step_ms"]["value"] > 0
    result, _ = _run(tmp_path, "dlrm_small", 0)
    assert result["metrics"]["train_samples_per_s"]["value"] > 0


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(tiny.BENCH_DIR, "run.py"),
         "--workload", "dlrm_train", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300, cwd=tiny.ROOT)
    assert proc.returncode == 2
    assert "not a TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_benchmark_json_and_metric_files_agree():
    bench = _bench()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    ends = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        with open(os.path.join(tiny.BENCH_DIR, "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        for key in ("layer", "unit", "better", "source", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        assert spec.get("workloads") == m.get("workloads")
        assert m["moves"] in ends
        assert os.path.isfile(os.path.join(tiny.BENCH_DIR, "readers",
                                           spec["reader"] + ".py"))
    for c in bench["configs"]:
        with open(os.path.join(tiny.ROOT, c["file"])) as f:
            config = json.load(f)
        assert config["reduced"] == c["reduced"]
        assert os.path.isfile(os.path.join(
            tiny.BENCH_DIR, "reference", config["reference"] + ".py"))
    for w in bench["workloads"]:
        with open(os.path.join(tiny.BENCH_DIR, "traffic",
                               w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        assert os.path.isfile(os.path.join(tiny.BENCH_DIR, "drivers",
                                           traffic["driver"] + ".py"))
