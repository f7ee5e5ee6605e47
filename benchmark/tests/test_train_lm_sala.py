"""The cell ``sala_train`` (driver ``train_lm_sala``) on the CPU: rehearsed tiny
through run.py, traced and not (the sizes are the ``tiny`` keys its own files
carry); the lower-precision control and the three left-out-mathematics controls
fail the rehearsal's limits while the sound program passes; with the timed path
broken underneath (a dropped push, a skipped block, the selection, the decay or
the gates left out, a muP scaling left out) a whole run comes out ``correct:
false``; a program without the two mixers is told to stop before anything is
built; the FLOP model by hand and at the cell's own size; the reader of
``sala_mfu_share`` on a made-up trace."""
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
CELL = "sala_train"
CONFIG = "minicpm-sala-9b-pp8"
MIX = "lm_packed_s16384_b1_long"
CHECKS = ("step_loss_rel_gap", "dense_rel_gap.sparse",
          "dense_rel_gap.lightning", "dense_rel_gap.dense_ffn",
          "dense_rel_gap.top", "rows_rel_gap",
          "selected_blocks_mismatch_share",
          "selected_blocks_mismatch_share.entries")
NEW_METRICS = ("sala_mfu_share", "sala_sparse_device_ms",
               "sala_select_device_share", "sala_lightning_device_ms",
               "sala_sparse_pair_share")


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
        assert {"lm_step_ms", "lm_table_ms", "sala_sparse_pair_share",
                "setup_compile_s",
                "setup_table_host_s"} <= set(result["metrics"])
        assert not {"sala_mfu_share", "sala_sparse_device_ms",
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
        # one sparse block, one sequence of 44: pooled keys of 4 every 2
        steps = info["steps"]
        assert info["lm_sparse_causal_pairs"] == steps * 44 * 45 // 2
        assert info["lm_sparse_select_pairs"] == steps * sum(
            (t - 3) // 2 + 1 for t in range(3, 44))
        assert info["lm_lightning_chunks"] == steps * 6     # ceil(44 / 8)
        assert 0 < info["lm_sparse_pairs"] < info["lm_sparse_causal_pairs"]
        assert info["sala_sparse_pair_share"] == pytest.approx(
            100 * info["lm_sparse_pairs"] / info["lm_sparse_causal_pairs"])


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
    # every query attending everything differs from the reference everywhere
    assert by_side["no_selection"]["selected_blocks_mismatch_share"] > 0.5
    for what, kind in (("no_decay", "lightning"), ("no_gates", "sparse")):
        assert by_side[what][f"dense_rel_gap.{kind}"] > \
            limits["dense_rel_gap"]


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


@pytest.mark.parametrize("skipped", ["S", "N", "D"])
def test_block_that_is_skipped(tiny_root, monkeypatch, skipped):
    from multiverso_tpu.models.hybrid_lm import model
    whole = model.layer_forward

    def without(kind, p, bias, u, cfg, remat=False):
        if kind != skipped:
            return whole(kind, p, bias, u, cfg, remat)
        return (u, {"chosen": None, "pairs": np.zeros((1,), np.int32)}) \
            if kind == "S" else (u, None)
    monkeypatch.setattr(model, "layer_forward", without)
    assert _run(*tiny_root)["correct"] is False


@pytest.mark.parametrize("what,check", [
    ("no_selection", "selected_blocks_mismatch_share"),
    ("no_decay", "dense_rel_gap.lightning"),
    ("no_gates", "dense_rel_gap.sparse")])
def test_mathematics_that_is_left_out(tiny_root, capsys, what, check):
    driver = harness.load_module("drivers", "train_lm_sala")
    with driver.left_out(what):
        assert _run(*tiny_root)["correct"] is False
    assert any(check in line for line in _failed(capsys))


@pytest.mark.parametrize("key", ["scale_emb", "scale_depth",
                                 "dim_model_base"])
def test_mup_scaling_that_is_left_out(tiny_root, monkeypatch, key):
    from multiverso_tpu.models.hybrid_lm import HybridLMConfig
    whole = HybridLMConfig.from_dict.__func__

    def without(cls, d, **kw):
        return whole(cls, {k: v for k, v in d.items() if k != key}, **kw)
    monkeypatch.setattr(HybridLMConfig, "from_dict", classmethod(without))
    assert _run(*tiny_root)["correct"] is False


def test_program_without_the_mixers_is_told_to_stop(tiny_root, monkeypatch):
    """The parent reads unknown keys as nothing and would build a plain
    grouped-query model: the driver asks before it builds anything."""
    import multiverso_tpu as mv
    from multiverso_tpu.models import hybrid_lm
    monkeypatch.delattr(hybrid_lm, "SPARSE")
    monkeypatch.setattr(mv, "init", lambda *a, **k: pytest.fail(
        "the tables were reached"))
    with pytest.raises(harness.BenchError, match="cannot run"):
        _run(*tiny_root)


def _cell_config():
    with open(os.path.join(tiny.BENCH_DIR, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def test_flop_model_by_hand():
    import lm_models_sala
    c = {"hidden_size": 8, "num_hidden_layers": 3, "intermediate_size": 7,
         "mixer_types": ["minicpm4", "lightning-attn", "lightning-attn",
                         "minicpm4"],
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 2,
         "lightning_nh": 2, "lightning_head_dim": 4, "lightning_chunk": 4,
         "vocab_size": 11}
    tokens, pairs, select, chunks = 20, 165, 40, 10
    want = {"sparse_projections": tokens * 1 * 2 * 8 * (3 * 8 + 2 * 4),
            "sparse_scores": pairs * 4 * 4 * 2,
            "sparse_selection": select * 4 * 2 * 2,
            "lightning_projections": tokens * 2 * 5 * 2 * 8 * 8,
            "lightning_scan": chunks * 2 * (10 * 4 * 4 + 4 * 4 * 16),
            "dense_ffn": tokens * 3 * 6 * 8 * 7,
            "head": tokens * 2 * 8 * 11}
    assert lm_models_sala.forward_parts(c, tokens, pairs, select,
                                        chunks) == want
    assert lm_models_sala.train_flops(
        c, {"lm_tokens": tokens, "lm_sparse_pairs": pairs,
            "lm_sparse_select_pairs": select,
            "lm_lightning_chunks": chunks}) == \
        3 * (sum(want.values()) - want["sparse_selection"]) \
        + want["sparse_selection"]


def _pairs_by_the_rule(length=16384, block=64, window=2048, topk=64):
    """(query, key) pairs a head attends: the first block, the blocks that hold
    a query's last 2,048 positions, and 64 earlier ones (all where fewer)."""
    t = np.arange(length)
    first_recent = np.maximum(t - window + 1, 0) // block
    recent = t + 1 - first_recent * block
    free = np.maximum(first_recent - 1, 0)      # wholly earlier, not block 0
    return int((recent + np.where(first_recent > 0, block, 0)
                + np.minimum(free, topk) * block).sum())


def test_flop_model_at_the_cells_own_size():
    """At the published head counts ISSUE 37's arithmetic: 112.8 TFLOP of
    products with the parameters, 4.05 of the sparse block's pairs (82.4 M of
    134.2 M a head: 61%), 0.07 of its selection, about 0.4 of the three scans.
    The cell holds half of each mixer's heads: 100.1 TFLOP, the mechanisms
    2.3% of it."""
    import lm_models_sala
    held = _cell_config()
    whole = dict(held, **{k: held["published"][k] for k in (
        "num_attention_heads", "num_key_value_heads", "lightning_nh")})
    tokens = 16384
    pairs = _pairs_by_the_rule()
    causal = tokens * (tokens + 1) // 2
    assert 100 * pairs / causal == pytest.approx(61.4, abs=0.3)
    select = sum((t - 31) // 16 + 1 for t in range(31, tokens))
    assert select == pytest.approx(8.4e6, rel=0.01)
    parts = lm_models_sala.forward_parts(whole, tokens, pairs, select,
                                         3 * 128)
    products = sum(parts[k] for k in (
        "sparse_projections", "lightning_projections", "dense_ffn", "head"))
    # every dense parameter but the norms multiplies
    norms = 9 * 4096 + 8 * 128 + 3 * 4096
    assert products == 2 * tokens * (1147048960 - norms)
    assert 3 * products == pytest.approx(112.8e12, rel=2e-3)
    assert 3 * parts["sparse_scores"] == pytest.approx(4.05e12, rel=5e-3)
    assert parts["sparse_selection"] == pytest.approx(0.069e12, rel=0.02)
    assert 3 * parts["lightning_scan"] == pytest.approx(0.46e12, rel=0.02)
    counts = {"lm_tokens": tokens, "lm_sparse_pairs": pairs,
              "lm_sparse_select_pairs": select, "lm_lightning_chunks": 384}
    assert lm_models_sala.train_flops(whole, counts) == pytest.approx(
        117.4e12, rel=5e-3)
    parts = lm_models_sala.forward_parts(held, tokens, pairs, select, 384)
    products = sum(parts[k] for k in (
        "sparse_projections", "lightning_projections", "dense_ffn", "head"))
    assert products == 2 * tokens * (
        994999296 - 9 * 4096 - 8 * 128 - 3 * 2048)
    total = lm_models_sala.train_flops(held, counts)
    assert total == pytest.approx(100.1e12, rel=5e-3)
    assert (total - 3 * products) / total == pytest.approx(0.023, abs=0.002)


def test_model_mfu_reader_on_a_made_up_trace():
    import byte_models
    import lm_models_sala
    reader = harness.load_module("readers", "model_mfu")
    config = _cell_config()
    # three runs of the step program of 2 s, one cut by the window's edge
    modules = {0: [(0.0, 2e9, "jit_lm_delta_step(1)"),
                   (2.1e9, 4.1e9, "jit_lm_delta_step(1)"),
                   (4.1e9, 4.15e9, "jit_lm_apply(2)"),
                   (4.2e9, 6.2e9, "jit_lm_delta_step(1)")]}
    per_step = {"lm_tokens": 16384, "lm_sparse_pairs": _pairs_by_the_rule(),
                "lm_sparse_select_pairs": 8380416,
                "lm_lightning_chunks": 384}
    counters = dict({k: 4 * v for k, v in per_step.items()}, steps=4)
    ctx = types.SimpleNamespace(
        trace_data=trace_reduce.Trace({}, modules, []),
        trace_window=(-1.0, 4.5e9), config=config,
        device={"platform": "tpu", "kind": "TPU v5 lite"},
        measured={"counters": counters})
    needed = lm_models_sala.train_flops(config, per_step)
    peak = byte_models.peaks("TPU v5 lite")["bf16_flops_per_s"]
    got = reader.read(ctx, pattern="lm_delta_step", flops="lm_models_sala")
    assert got == pytest.approx(100.0 * needed / 2.0 / peak)
    assert 0 < got < 100
    # nothing to read: a program without the counters (the parent's)
    ctx.measured = {"counters": {"steps": 4, "lm_tokens": 4 * 16384}}
    assert reader.read(ctx, pattern="lm_delta_step",
                       flops="lm_models_sala") is None
    share = harness.load_module("readers", "counter")
    assert share.read(ctx, name="sala_sparse_pair_share") is None


def test_new_files_name_their_cell_as_the_benchmark_does():
    bench = harness.load_benchmark()
    for name in NEW_METRICS:
        entry = harness.find(bench["per_layer"], name, "metric")
        spec = harness.load_json("layer_metrics", name)
        for key in ("unit", "better", "source", "layer", "moves",
                    "workloads"):
            assert spec[key] == entry[key], (name, key)
        assert entry["workloads"] == [CELL]
    reported = {m["name"] for m in harness.metrics_of_cell(
        bench["per_layer"], CELL)}
    assert reported == set(NEW_METRICS) | {
        "setup_compile_s", "setup_table_host_s", "train_device_idle_share",
        "idle_unattributed_share.train", "lm_step_ms", "lm_table_ms",
        "lm_delta_device_ms", "lm_apply_device_ms", "lm_attn_device_ms",
        "lm_ffn_device_ms", "lm_head_loss_device_ms"}
    cell = harness.find(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, MIX, 1)
    assert CELL in harness.find(bench["end_to_end"], "train_samples_per_s",
                                "metric")["workloads"]
    entry = harness.find(bench["configs"], CONFIG, "config")
    assert entry["reduced"] == _cell_config()["reduced"]
    assert entry["source"] == _cell_config()["source_url"]
    mix = harness.load_json("traffic", MIX)
    assert set(mix["limits"]) == set(mix["tiny"]["limits"]) \
        <= set(mix["limits_why"])
