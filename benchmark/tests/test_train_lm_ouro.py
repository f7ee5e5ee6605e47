"""The cell ``ouro_train`` (driver ``train_lm_ouro``) on the CPU: rehearsed tiny
through run.py, traced and not (the sizes are the ``tiny`` keys its own files
carry); the lower-precision control and the seven left-out controls fail the
rehearsal's limits while the sound program passes; with the timed path broken
underneath (a dropped push, a skipped block, each left-out piece of the loop's
mathematics) a whole run comes out ``correct: false``; a program without the
looped stack is told to stop before anything is built; the FLOP model by hand
and at the cell's own size; the reader of ``ouro_mfu_share`` on a made-up
trace; the new files against their ``BENCHMARK.json`` entries."""
import json
import os
import subprocess
import sys
import types

import pytest

import harness
import tiny
import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "ouro_train"
CONFIG = "ouro-2.6b-pp8"
MIX = "lm_packed_s8192_b1_loop4"
CHECKS = ("step_loss_rel_gap", "pass_loss_rel_gap", "exit_mass_rel_gap",
          "exit_gate_rel_gap", "dense_rel_gap.attention",
          "dense_rel_gap.dense_ffn", "dense_rel_gap.top",
          "dense_rel_gap.post_norm", "rows_rel_gap")
NEW_METRICS = ("ouro_mfu_share", "ouro_loop_device_ms",
               "ouro_head_loss_device_share")


@pytest.fixture()
def tiny_root(tmp_path):
    return tiny.make_root(str(tmp_path / "root"))


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearses_on_cpu(tmp_path, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse.py"), str(tmp_path),
         "--workload", CELL, "--seed", str(2 ** 31 + 11), "--seconds", "1.5",
         "--trace", str(trace)],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, proc.stdout[-3000:]
    assert result["attempted"] > 0 and result["failed"] == 0
    if trace:
        # the CPU has no device plane: span and counter metrics only
        assert {"lm_step_ms", "lm_table_ms", "setup_compile_s",
                "setup_table_host_s"} <= set(result["metrics"])
        assert not {"ouro_mfu_share", "ouro_loop_device_ms",
                    "ouro_head_loss_device_share",
                    "lm_delta_device_ms"} & set(result["metrics"])
    else:
        assert set(result["metrics"]) == {"train_samples_per_s",
                                          "peak_hbm_gb", "setup_s"}
        assert "check compiles_in_window: 0.0 == 0 ok" in proc.stdout
        for name in CHECKS:
            assert f"check {name}:" in proc.stdout
        info = json.loads(next(
            line for line in proc.stdout.splitlines()
            if line.startswith("info "))[5:])["counters"]
        steps = info["steps"]
        assert info["lm_tokens"] == steps * 2 * 40
        assert info["lm_loop_passes"] == steps * 4
        assert info["lm_loop_block_runs"] == steps * 4 * 4
        # two attention blocks run four times, two sequences of 40
        assert info["lm_attn_pairs"] == steps * 4 * 2 * 2 * 40 * 41 // 2
        assert sum(info[f"exit_mass_t{t}"] for t in (1, 2, 3, 4)) == \
            pytest.approx(1.0, abs=1e-5)
        assert "lm_balance_loss" not in info
        assert "lm_assignments_held" not in info


def test_the_controls_fail_the_limits(tiny_root):
    root, bench_dir = tiny_root
    ctx, driver = harness.open_cell(CELL, 0, 1.0, False, require_chip=False,
                                    root=root, bench_dir=bench_dir)
    seeds = [5, 2 ** 31 + 6]
    rows = driver.limit_readings(
        lambda seed: harness.Context(ctx.cell, ctx.config, ctx.traffic, seed,
                                     1.0, False, ctx.device, bench_dir),
        seeds, len(seeds))
    limits = ctx.traffic["tiny"]["limits"]
    assert [r["side"] for r in rows] == ["sound", "control"] * 2 + list(
        driver.LEFT_OUT)
    for row in rows:
        passed = all(v <= limits[k.split(".")[0]]
                     for k, v in row["gaps"].items())
        assert passed == (row["side"] == "sound"), row
    by_side = {r["side"]: r["gaps"] for r in rows}
    # a post-norm nobody reads never moves: its leaf reads 1
    assert by_side["no_post_norm"]["dense_rel_gap.post_norm"] > 0.9
    # the loss from the last pass alone: the gate gets no gradient
    assert by_side["last_pass_loss"]["exit_gate_rel_gap"] > 0.9
    assert by_side["last_pass_loss"]["exit_mass_rel_gap"] > 0.5
    assert by_side["one_pass"]["pass_loss_rel_gap"] > limits[
        "pass_loss_rel_gap"]
    assert by_side["no_entropy"]["exit_gate_rel_gap"] > limits[
        "exit_gate_rel_gap"]
    assert by_side["untied_passes_grad"]["dense_rel_gap.attention"] > \
        limits["dense_rel_gap"]
    assert by_side["dropped_push"]["rows_rel_gap"] > 0.9


def _run(root, bench_dir, seed=9):
    return harness.run_cell(CELL, seed, 1.0, False, require_chip=False,
                            root=root, bench_dir=bench_dir)


def _failed(capsys):
    return [line for line in capsys.readouterr().out.splitlines()
            if "FAILED" in line]


def test_sound_cell_passes_in_process(tiny_root):
    assert _run(*tiny_root)["correct"] is True


@pytest.mark.parametrize("skipped", ["*", "D"])
def test_block_that_is_skipped(tiny_root, monkeypatch, skipped):
    from multiverso_tpu.models.hybrid_lm import model
    whole = model.layer_forward

    def without(kind, p, bias, u, cfg, remat=False, **more):
        if kind != skipped:
            return whole(kind, p, bias, u, cfg, remat, **more)
        return u, None
    monkeypatch.setattr(model, "layer_forward", without)
    assert _run(*tiny_root)["correct"] is False


@pytest.mark.parametrize("what,check", [
    ("one_pass", "pass_loss_rel_gap"),
    ("no_post_norm", "dense_rel_gap.post_norm"),
    ("norm_not_fed_back", "step_loss_rel_gap"),
    ("last_pass_loss", "exit_gate_rel_gap"),
    ("no_entropy", "exit_gate_rel_gap"),
    ("untied_passes_grad", "dense_rel_gap.attention"),
    ("dropped_push", "rows_rel_gap")])
def test_mathematics_that_is_left_out(tiny_root, capsys, what, check):
    driver = harness.load_module("drivers", "train_lm_ouro")
    with driver.left_out(what):
        assert _run(*tiny_root)["correct"] is False
    assert any(check in line for line in _failed(capsys))
    # and the program is whole again afterwards
    assert _run(*tiny_root)["correct"] is True


def test_program_without_the_loop_is_told_to_stop(tiny_root, monkeypatch):
    """The parent reads this file's ``layer_types`` as another family's and
    would build a QK-normed one-pass model: the driver asks before it builds
    anything."""
    import multiverso_tpu as mv
    from multiverso_tpu.models import hybrid_lm
    monkeypatch.delattr(hybrid_lm, "looped_hidden")
    monkeypatch.setattr(mv, "init", lambda *a, **k: pytest.fail(
        "the tables were reached"))
    with pytest.raises(harness.BenchError, match="cannot run"):
        _run(*tiny_root)


def _cell_config():
    with open(os.path.join(tiny.BENCH_DIR, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def test_flop_model_by_hand():
    import lm_models_ouro
    c = {"hidden_size": 8, "num_hidden_layers": 3, "intermediate_size": 7,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 2,
         "vocab_size": 11}
    tokens, pairs, passes = 20, 330, 2
    want = {"attn_projections": tokens * 2 * 3 * (2 * 8 * (4 + 4) * 2
                                                  + 2 * 4 * 2 * 8),
            "attn_scores": pairs * 4 * 2 * 2 * 2,
            "dense_ffn": tokens * 2 * 3 * 6 * 8 * 7,
            "head": tokens * 2 * 2 * 8 * 11}
    assert lm_models_ouro.forward_parts(c, tokens, pairs, passes) == want
    assert lm_models_ouro.train_flops(
        c, {"lm_tokens": tokens, "lm_attn_pairs": pairs,
            "lm_loop_passes": passes}) == 3 * sum(want.values())


def test_flop_model_at_the_cells_own_size():
    """ISSUE 45's arithmetic: 100.2 TFLOP a step of 8,192 tokens: products
    with the six layers' matrices 4 x 15.2 = 60.6, causal pairs 24 block runs
    x 0.825 = 19.8, four heads' logits 19.8."""
    import lm_models_ouro
    c = _cell_config()
    tokens, passes = 8192, 4
    pairs = passes * 6 * 8192 * 8193 // 2
    parts = {k: 3 * v for k, v in lm_models_ouro.forward_parts(
        c, tokens, pairs, passes).items()}
    assert sum(parts.values()) == pytest.approx(100.2e12, rel=1e-3)
    assert parts["attn_projections"] + parts["dense_ffn"] == \
        pytest.approx(60.6e12, rel=1e-3)
    assert parts["attn_scores"] == pytest.approx(19.8e12, rel=1e-3)
    assert parts["head"] == pytest.approx(19.8e12, rel=1e-3)
    # every dense parameter but the norms and the gate multiplies once a
    # token and pass
    products = sum(parts.values()) - parts["attn_scores"]
    assert products == 3 * 2 * tokens * passes * (
        408_997_889 - 25 * 2048 - 2049)


def test_model_mfu_reader_on_a_made_up_trace():
    import byte_models
    import lm_models_ouro
    reader = harness.load_module("readers", "model_mfu")
    config = _cell_config()
    # three runs of the step program of 1.2 s, one cut by the window's edge
    modules = {0: [(0.0, 1.2e9, "jit_lm_delta_step(1)"),
                   (1.3e9, 2.5e9, "jit_lm_delta_step(1)"),
                   (2.5e9, 2.52e9, "jit_lm_apply(2)"),
                   (2.6e9, 3.8e9, "jit_lm_delta_step(1)")]}
    per_step = {"lm_tokens": 8192, "lm_attn_pairs": 24 * 8192 * 8193 // 2,
                "lm_loop_passes": 4}
    counters = dict({k: 4 * v for k, v in per_step.items()}, steps=4)
    ctx = types.SimpleNamespace(
        trace_data=trace_reduce.Trace({}, modules, []),
        trace_window=(-1.0, 3.0e9), config=config,
        device={"platform": "tpu", "kind": "TPU v5 lite"},
        measured={"counters": counters})
    needed = lm_models_ouro.train_flops(config, per_step)
    peak = byte_models.peaks("TPU v5 lite")["bf16_flops_per_s"]
    got = reader.read(ctx, pattern="lm_delta_step", flops="lm_models_ouro")
    assert got == pytest.approx(100.0 * needed / 1.2 / peak)
    assert 0 < got < 100
    # nothing to read: a program without the loop's counter
    ctx.measured = {"counters": {"steps": 4, "lm_tokens": 4 * 8192,
                                 "lm_attn_pairs": 4}}
    assert reader.read(ctx, pattern="lm_delta_step",
                       flops="lm_models_ouro") is None


def test_new_files_name_their_cell_as_the_benchmark_does():
    """At least these names: later PRs append to the lists."""
    bench = harness.load_benchmark()
    for name in NEW_METRICS:
        entry = harness.find(bench["per_layer"], name, "metric")
        spec = harness.load_json("layer_metrics", name)
        for key in ("unit", "better", "source", "layer", "moves"):
            assert spec[key] == entry[key], (name, key)
        assert CELL in entry["workloads"] and CELL in spec["workloads"]
        assert harness.load_module("readers", spec["reader"]).read
    assert harness.load_json("layer_metrics", "ouro_loop_device_ms")[
        "args"] == {"module": "lm_delta_step", "scope": "lm_loop"}
    assert harness.load_json(
        "layer_metrics", "ouro_head_loss_device_share")["args"] == {
            "module": "lm_delta_step", "scope": "lm_head_loss",
            "per": "share"}
    reported = {m["name"] for m in harness.metrics_of_cell(
        bench["per_layer"], CELL)}
    assert reported >= set(NEW_METRICS) | {
        "setup_compile_s", "setup_table_host_s", "train_device_idle_share",
        "idle_unattributed_share.train", "lm_step_ms", "lm_table_ms",
        "lm_delta_device_ms", "lm_apply_device_ms", "lm_attn_device_ms",
        "lm_ffn_device_ms", "lm_head_loss_device_ms"}
    # scopes this model's program does not have, and the share whose scope
    # list lacks the loop's own scopes
    assert not reported & {"lm_mamba_device_ms", "lm_experts_device_ms",
                           "lm_expert_load_max_over_mean",
                           "lm_unscoped_device_share"}
    cell = harness.find(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, MIX, 1)
    assert CELL in harness.find(bench["end_to_end"], "train_samples_per_s",
                                "metric")["workloads"]
    entry = harness.find(bench["configs"], CONFIG, "config")
    assert entry["reduced"] == _cell_config()["reduced"] == \
        ["num_hidden_layers"]
    assert entry["source"] == _cell_config()["source_url"]
    mix = harness.load_json("traffic", MIX)
    assert (mix["sequences"], mix["seq_len"], mix["batches"]) == (1, 8192, 8)
    assert set(mix["limits"]) == set(mix["tiny"]["limits"]) \
        <= set(mix["limits_why"])


def test_reference_imports_nothing_of_the_program():
    path = os.path.join(tiny.BENCH_DIR, "reference", CONFIG + ".py")
    with open(path) as f:
        text = f.read()
    assert "multiverso_tpu" not in text.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in text
