"""The cell ``evabyte_train`` (driver ``train_lm_evabyte``) on the CPU: rehearsed
tiny through run.py, traced and not (the sizes are the ``tiny`` keys its own
files carry); the lower-precision control and the two left-out-mathematics
controls fail the rehearsal's limits while the sound program passes; with the
timed path broken underneath (a dropped push, a skipped block, the summaries
masked away, ``phi`` and ``mu`` frozen, the positions left out, a prediction
head's target shifted by one) a whole run comes out ``correct: false``; the
byte traffic; the FLOP model by hand and at the cell's own size; the reader of
``evabyte_mfu_share`` on a made-up trace."""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

import harness
import tiny
import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "evabyte_train"
CONFIG = "evabyte-6.5b-pp8"
MIX = "lm_packed_s16384_b1_bytes"
CHECKS = ("step_loss_rel_gap", "head_loss_rel_gap", "head_loss_rel_gap.h0",
          "head_loss_rel_gap.h1", "dense_rel_gap.eva",
          "dense_rel_gap.dense_ffn", "dense_rel_gap.top",
          "dense_rel_gap.adaptive_phi", "dense_rel_gap.adaptive_mu_k",
          "rows_rel_gap")


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
        assert {"lm_step_ms", "lm_table_ms", "eva_summary_pair_share",
                "setup_compile_s", "setup_table_host_s"} \
            <= set(result["metrics"])
        assert not {"evabyte_mfu_share", "lm_delta_device_ms",
                    "lm_apply_device_ms", "lm_expert_load_max_over_mean"} \
            & set(result["metrics"])
    else:
        assert set(result["metrics"]) == {"train_samples_per_s",
                                          "peak_hbm_gb", "setup_s"}
        assert "check compiles_in_window: 0.0 == 0 ok" in proc.stdout
        for name in CHECKS:
            assert f"check {name}:" in proc.stdout
        info = json.loads(next(
            line for line in proc.stdout.splitlines()
            if line.startswith("info "))[5:])["counters"]
        # two EVA blocks, one sequence of 44: five windows of 8 and one of 4;
        # window w's queries see 4 w summaries
        assert info["lm_attn_pairs"] == info["steps"] * 2 * (5 * 36 + 10)
        assert info["lm_eva_summary_pairs"] == info["steps"] * 2 * 4 * (
            8 * (1 + 2 + 3 + 4) + 4 * 5)
        assert info["lm_eva_chunks"] == info["steps"] * 2 * 22
        assert info["eva_summary_pair_share"] == pytest.approx(
            100 * 800 / (800 + 380))


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
    assert [r["side"] for r in rows] == ["sound", "control"] * 2 + [
        "no_summaries", "frozen_phi_mu"]
    for row in rows:
        passed = all(v <= limits[k.split(".")[0]]
                     for k, v in row["gaps"].items())
        assert passed == (row["side"] == "sound"), row
    by_side = {r["side"]: r["gaps"] for r in rows}
    # at this size the summaries hardly move the loss: the leaves tell
    assert by_side["no_summaries"]["dense_rel_gap.eva"] > \
        limits["dense_rel_gap"]
    # a leaf the program never moved reads 1
    for name in ("adaptive_phi", "adaptive_mu_k"):
        assert by_side["frozen_phi_mu"][f"dense_rel_gap.{name}"] == \
            pytest.approx(1.0)
    # the program's leaves are in the order the driver states
    from multiverso_tpu.models.hybrid_lm import HybridLMConfig, param_shapes
    config = dict(ctx.config, **ctx.config["tiny"])
    shapes = param_shapes(HybridLMConfig.from_dict(config))
    assert tuple(shapes["layers"][0]) == driver.EVA_LEAVES


def _run(root, bench_dir, seed=9):
    return harness.run_cell(CELL, seed, 1.0, False, require_chip=False,
                            root=root, bench_dir=bench_dir)


def _failed(capsys):
    return [line for line in capsys.readouterr().out.splitlines()
            if "FAILED" in line]


def test_sound_cell_passes_in_process(tiny_root):
    assert _run(*tiny_root)["correct"] is True


def test_push_that_is_dropped(tiny_root, monkeypatch, capsys):
    from multiverso_tpu.models.hybrid_lm import HybridLM
    monkeypatch.setattr(HybridLM, "_push_rows",
                        lambda self, ids, delta: None)
    assert _run(*tiny_root)["correct"] is False
    assert any("rows_rel_gap" in line for line in _failed(capsys))


@pytest.mark.parametrize("skipped", ["V", "D"])
def test_block_that_is_skipped(tiny_root, monkeypatch, skipped):
    from multiverso_tpu.models.hybrid_lm import model
    whole = model.layer_forward

    def without(kind, p, bias, u, cfg, remat=False):
        return (u, None) if kind == skipped else whole(kind, p, bias, u, cfg,
                                                       remat)
    monkeypatch.setattr(model, "layer_forward", without)
    assert _run(*tiny_root)["correct"] is False


@pytest.mark.parametrize("what,leaf", [("no_summaries", "dense_rel_gap.eva"),
                                       ("frozen_phi_mu",
                                        "dense_rel_gap.adaptive_phi")])
def test_mathematics_that_is_left_out(tiny_root, capsys, what, leaf):
    driver = harness.load_module("drivers", "train_lm_evabyte")
    with driver.left_out(what):
        assert _run(*tiny_root)["correct"] is False
    assert any(leaf in line for line in _failed(capsys))


def test_positions_that_are_left_out(tiny_root, monkeypatch, capsys):
    from multiverso_tpu.models.hybrid_lm import rope
    monkeypatch.setattr(rope, "apply_rope",
                        lambda x, cos, sin, half=False: x)
    assert _run(*tiny_root)["correct"] is False
    assert any("dense_rel_gap.eva" in line for line in _failed(capsys))


def test_prediction_head_whose_target_is_shifted_by_one(tiny_root,
                                                        monkeypatch, capsys):
    from multiverso_tpu.models.hybrid_lm import model
    whole = model.pack_batch

    def shifted(tokens, bucket, min_rows=0, heads=1):
        ids, n, where, targets, mask = whole(tokens, bucket, min_rows, heads)
        if heads > 1:   # head 1 told to predict what head 0 predicts
            targets = targets.copy()
            targets[..., 1] = targets[..., 0]
        return ids, n, where, targets, mask
    monkeypatch.setattr(model, "pack_batch", shifted)
    assert _run(*tiny_root)["correct"] is False
    failed = _failed(capsys)
    assert any("head_loss_rel_gap.h1" in line for line in failed)
    assert not any("head_loss_rel_gap.h0" in line for line in failed)


def test_byte_traffic_from_the_seed():
    driver = harness.load_module("drivers", "train_lm_evabyte")
    mix = harness.load_json("traffic", MIX)
    t = dict(mix, batches=2)
    a, b = (driver.byte_batches(seed, t, 320) for seed in (7, 7))
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], driver.byte_batches(8, t, 320)[0])
    tokens = np.concatenate([x.reshape(-1) for x in a])
    assert a[0].shape == (1, 16384) and a[0].dtype == np.int32
    bytes_, ends = tokens[tokens >= 64], tokens[tokens < 64]
    assert set(ends) == {mix["eos_id"]} and 64 <= bytes_.min() \
        and bytes_.max() <= 319
    # documents of some 4,000 bytes: a handful of ends a sequence
    assert 2 <= len(ends) <= 40
    # Zipf: the commonest byte value is a sizeable share, most values occur
    counts = np.bincount(bytes_, minlength=320)[64:]
    assert counts.max() > 0.05 * len(bytes_) and (counts > 0).sum() > 200


def test_flop_model_by_hand():
    import lm_models_evabyte
    c = {"hidden_size": 8, "num_hidden_layers": 3, "intermediate_size": 7,
         "num_pred_heads": 2, "vocab_size": 11}
    tokens, pairs, summary = 20, 165, 40
    want = {"eva_projections": tokens * 3 * 4 * 2 * 8 * 8,
            "eva_local_scores": pairs * 4 * 8,
            "eva_summary_scores": summary * 4 * 8,
            "dense_ffn": tokens * 3 * 6 * 8 * 7,
            "head": tokens * 2 * 8 * 2 * 11}
    assert lm_models_evabyte.forward_parts(c, tokens, pairs, summary) == want
    assert lm_models_evabyte.train_flops(
        c, {"lm_tokens": tokens, "lm_attn_pairs": pairs,
            "lm_eva_summary_pairs": summary}) == 3 * sum(want.values())


def _cell_config():
    with open(os.path.join(tiny.BENCH_DIR, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def test_flop_model_at_the_cells_own_size():
    """ISSUE 32's arithmetic: 80.6 TFLOP of products with the parameters,
    4.7 of attention pairs (24.1 M a block, the summaries 30.4% of them)."""
    import lm_models_evabyte
    tokens = 16384
    local, summary = 8 * 2048 * 2049 // 2, 128 * 2048 * 28
    assert (local, summary) == (16785408, 7340032)
    assert 100 * summary / (local + summary) == pytest.approx(30.4, abs=0.05)
    parts = lm_models_evabyte.forward_parts(_cell_config(), tokens,
                                            4 * local, 4 * summary)
    scores = parts["eva_local_scores"] + parts["eva_summary_scores"]
    products = sum(parts.values()) - scores
    # every dense parameter but the norms and the learned vectors multiplies
    assert products == 2 * tokens * (820056064 - 8 * 4096 - 4096 - 4 * 8192)
    assert 3 * products == pytest.approx(80.6e12, rel=2e-3)
    assert 3 * scores == pytest.approx(4.74e12, rel=2e-3)
    assert scores / sum(parts.values()) == pytest.approx(0.056, abs=0.001)


def test_model_mfu_reader_on_a_made_up_trace():
    import byte_models
    import lm_models_evabyte
    reader = harness.load_module("readers", "model_mfu")
    config = _cell_config()
    # three runs of the step program of 1 s, one cut by the window's edge
    modules = {0: [(0.0, 1e9, "jit_lm_delta_step(1)"),
                   (1.1e9, 2.1e9, "jit_lm_delta_step(1)"),
                   (2.1e9, 2.15e9, "jit_lm_apply(2)"),
                   (2.2e9, 3.2e9, "jit_lm_delta_step(1)")]}
    per_step = {"lm_tokens": 16384, "lm_attn_pairs": 4 * 16785408,
                "lm_eva_summary_pairs": 4 * 7340032}
    counters = dict({k: 4 * v for k, v in per_step.items()}, steps=4)
    ctx = types.SimpleNamespace(
        trace_data=trace_reduce.Trace({}, modules, []),
        trace_window=(-1.0, 2.5e9), config=config,
        device={"platform": "tpu", "kind": "TPU v5 lite"},
        measured={"counters": counters})
    needed = lm_models_evabyte.train_flops(config, per_step)
    peak = byte_models.peaks("TPU v5 lite")["bf16_flops_per_s"]
    got = reader.read(ctx, pattern="lm_delta_step",
                      flops="lm_models_evabyte")
    assert got == pytest.approx(100.0 * needed / 1.0 / peak)
    assert 0 < got < 100
    # nothing to read: a program without the counters (the parent's)
    ctx.measured = {"counters": {"steps": 4, "lm_tokens": 4 * 16384,
                                 "lm_attn_pairs": 1}}
    assert reader.read(ctx, pattern="lm_delta_step",
                       flops="lm_models_evabyte") is None
    share = harness.load_module("readers", "counter")
    assert share.read(ctx, name="eva_summary_pair_share") is None


def test_new_files_name_their_cell_as_the_benchmark_does():
    bench = harness.load_benchmark()
    for name in ("evabyte_mfu_share", "eva_summary_pair_share"):
        entry = harness.find(bench["per_layer"], name, "metric")
        spec = harness.load_json("layer_metrics", name)
        for key in ("unit", "better", "source", "layer", "moves",
                    "workloads"):
            assert spec[key] == entry[key], (name, key)
        assert entry["workloads"] == [CELL]
    reported = {m["name"] for m in harness.metrics_of_cell(
        bench["per_layer"], CELL)}
    assert reported == {
        "setup_compile_s", "setup_table_host_s", "train_device_idle_share",
        "idle_unattributed_share.train", "lm_step_ms", "lm_table_ms",
        "lm_delta_device_ms", "lm_apply_device_ms", "evabyte_mfu_share",
        "eva_summary_pair_share"}
    assert bench["workloads"][-1]["name"] == CELL
    cell = harness.find(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, MIX, 1)
    entry = harness.find(bench["configs"], CONFIG, "config")
    assert entry["reduced"] == _cell_config()["reduced"]
    assert entry["source"] == _cell_config()["source_url"]
    mix = harness.load_json("traffic", MIX)
    assert set(mix["limits"]) == set(mix["tiny"]["limits"]) \
        <= set(mix["limits_why"])
