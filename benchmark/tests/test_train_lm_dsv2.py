"""The cell ``dsv2lite_train`` (driver ``train_lm_dsv2``) on the CPU: rehearsed
tiny through run.py, traced and not (the sizes are the ``tiny`` keys its own
files carry); the lower-precision control fails the rehearsal's limits while
the sound program passes; with the timed path broken underneath (a dropped
push, a skipped block, an expert left out, the balance term left out, the
positions left out) a whole run comes out ``correct: false``; the FLOP model
by hand; the reader of ``dsv2_mfu_share`` on a made-up trace."""
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
CELL = "dsv2lite_train"
CONFIG = "deepseek-v2-lite-ep4"


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
        assert {"lm_step_ms", "lm_table_ms", "lm_expert_load_max_over_mean",
                "setup_compile_s", "setup_table_host_s"} \
            <= set(result["metrics"])
        assert not {"dsv2_mfu_share", "lm_delta_device_ms",
                    "lm_apply_device_ms"} & set(result["metrics"])
        assert result["metrics"]["lm_expert_load_max_over_mean"]["value"] \
            >= 1.0
    else:
        assert set(result["metrics"]) == {"train_samples_per_s",
                                          "peak_hbm_gb", "setup_s"}
        assert "check compiles_in_window: 0.0 == 0 ok" in proc.stdout
        for name in ("step_loss_rel_gap", "balance_rel_gap",
                     "dense_rel_gap.mla", "dense_rel_gap.dense_ffn",
                     "dense_rel_gap.experts", "dense_rel_gap.top",
                     "rows_rel_gap", "expert_counts_rel_gap"):
            assert f"check {name}:" in proc.stdout
        info = json.loads(next(
            line for line in proc.stdout.splitlines()
            if line.startswith("info "))[5:])["counters"]
        # three attention blocks, two sequences of 40: 820 pairs each a step
        assert info["lm_attn_pairs"] == info["steps"] * 3 * 2 * 820
        assert 0 < info["lm_balance_loss"] < 0.01


def test_lower_precision_control_fails_the_limits(tiny_root):
    root, bench_dir = tiny_root
    ctx, driver = harness.open_cell(CELL, 0, 1.0, False, require_chip=False,
                                    root=root, bench_dir=bench_dir)
    seeds = [5, 2 ** 31 + 6]
    rows = driver.limit_readings(
        lambda seed: harness.Context(ctx.cell, ctx.config, ctx.traffic, seed,
                                     1.0, False, ctx.device, bench_dir),
        seeds, len(seeds))
    limits = ctx.traffic["tiny"]["limits"]
    assert len(rows) == 2 * len(seeds)
    for row in rows:
        passed = all(v <= limits[k.split(".")[0]]
                     for k, v in row["gaps"].items())
        assert passed == (row["side"] == "sound"), row


def _run(root, bench_dir, seed=9):
    return harness.run_cell(CELL, seed, 1.0, False, require_chip=False,
                            root=root, bench_dir=bench_dir)


def test_sound_cell_passes_in_process(tiny_root):
    assert _run(*tiny_root)["correct"] is True


def test_push_that_is_dropped(tiny_root, monkeypatch, capsys):
    from multiverso_tpu.models.hybrid_lm import HybridLM
    monkeypatch.setattr(HybridLM, "_push_rows",
                        lambda self, ids, delta: None)
    assert _run(*tiny_root)["correct"] is False
    out = capsys.readouterr().out
    assert "check rows_rel_gap" in out and "FAILED" in out


@pytest.mark.parametrize("skipped", ["L", "D"])
def test_block_that_is_skipped(tiny_root, monkeypatch, skipped):
    from multiverso_tpu.models.hybrid_lm import model
    whole = model.layer_forward

    def without(kind, p, bias, u, cfg, remat=False):
        return (u, None) if kind == skipped else whole(kind, p, bias, u, cfg,
                                                       remat)
    monkeypatch.setattr(model, "layer_forward", without)
    assert _run(*tiny_root)["correct"] is False


def test_expert_that_is_left_out(tiny_root, monkeypatch, capsys):
    from multiverso_tpu.models.hybrid_lm import model
    whole = model.held_topk_moe

    def one_short(n, router, bias, w_up, w_down, *rest):
        return whole(n, router, bias, w_up, w_down.at[-1].set(0.0), *rest)
    monkeypatch.setattr(model, "held_topk_moe", one_short)
    assert _run(*tiny_root)["correct"] is False
    assert "FAILED" in capsys.readouterr().out


def test_balance_term_that_is_left_out(tiny_root, monkeypatch, capsys):
    from multiverso_tpu.models.hybrid_lm import model
    whole = model.held_topk_moe

    def unbalanced(*args):
        y, counts, balance = whole(*args)
        return y, counts, 0.0 * balance
    monkeypatch.setattr(model, "held_topk_moe", unbalanced)
    assert _run(*tiny_root)["correct"] is False
    out = capsys.readouterr().out
    failed = [line for line in out.splitlines() if "FAILED" in line]
    assert any("balance_rel_gap" in line for line in failed)
    assert any("step_loss_rel_gap" in line for line in failed)


def test_positions_that_are_left_out(tiny_root, monkeypatch, capsys):
    from multiverso_tpu.models.hybrid_lm import rope
    monkeypatch.setattr(rope, "apply_rope", lambda x, cos, sin: x)
    assert _run(*tiny_root)["correct"] is False
    out = capsys.readouterr().out
    assert any("dense_rel_gap.mla" in line and "FAILED" in line
               for line in out.splitlines())


def test_flop_model_by_hand():
    import lm_models_dsv2
    c = {"hidden_size": 8, "num_attention_heads": 2, "qk_nope_head_dim": 3,
         "qk_rope_head_dim": 2, "v_head_dim": 4, "kv_lora_rank": 5,
         "num_hidden_layers": 3, "first_k_dense_replace": 1,
         "moe_layer_freq": 1, "intermediate_size": 7,
         "moe_intermediate_size": 6, "n_shared_experts": 2,
         "n_routed_experts": 2, "published": {"n_routed_experts": 9},
         "vocab_size": 11}
    tokens, pairs, assigned = 20, 165, 13
    mla = 2 * 8 * 2 * 5 + 2 * 8 * (5 + 2) + 2 * 5 * 2 * (3 + 4) \
        + 2 * 2 * 4 * 8
    want = {"mla_projections": tokens * 3 * mla,
            "mla_scores": pairs * 2 * 2 * (5 + 4),
            "dense_ffn": tokens * 1 * 6 * 8 * 7,
            "router_and_shared": tokens * 2 * (2 * 8 * 9 + 6 * 8 * 2 * 6),
            "routed_experts": assigned * 6 * 8 * 6,
            "head": tokens * 2 * 8 * 11}
    assert lm_models_dsv2.forward_parts(c, tokens, pairs, assigned) == want
    assert lm_models_dsv2.train_flops(
        c, {"lm_tokens": tokens, "lm_attn_pairs": pairs,
            "lm_assignments_held": assigned, "lm_seq_len": 10}) \
        == 3 * sum(want.values())


def _cell_config():
    with open(os.path.join(tiny.BENCH_DIR, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def test_flop_model_at_the_cells_own_size():
    """ISSUE 30's arithmetic: 40.8 TFLOP a step, latent attention 42% of it
    (its scores 25%), the expert blocks 29%."""
    import lm_models_dsv2
    tokens, seq = 16384, 8192
    pairs = 5 * 2 * seq * (seq + 1) // 2
    held = 4 * tokens * 6 // 4          # a quarter of the experts, by the mean
    parts = lm_models_dsv2.forward_parts(_cell_config(), tokens, pairs, held)
    total = sum(parts.values())
    assert 3 * total == pytest.approx(40.8e12, rel=5e-3)
    assert (parts["mla_projections"] + parts["mla_scores"]) / total \
        == pytest.approx(0.42, abs=0.005)
    assert parts["mla_scores"] / total == pytest.approx(0.25, abs=0.005)
    assert (parts["router_and_shared"] + parts["routed_experts"]) / total \
        == pytest.approx(0.29, abs=0.005)


def test_model_mfu_reader_on_a_made_up_trace():
    import byte_models
    import lm_models_dsv2
    reader = harness.load_module("readers", "model_mfu")
    config = _cell_config()
    # three runs of the step program of 0.5 s, one cut by the window's edge
    modules = {0: [(0.0, 5e8, "jit_lm_delta_step(1)"),
                   (6e8, 11e8, "jit_lm_delta_step(1)"),
                   (11e8, 11.5e8, "jit_lm_apply(2)"),
                   (12e8, 17e8, "jit_lm_delta_step(1)")]}
    per_step = {"lm_tokens": 16384, "lm_attn_pairs": 5 * 8192 * 8193,
                "lm_assignments_held": 4 * 24000}
    counters = dict({k: 4 * v for k, v in per_step.items()}, steps=4,
                    lm_seq_len=8192, first_loss=10.0)
    ctx = types.SimpleNamespace(
        trace_data=trace_reduce.Trace({}, modules, []),
        trace_window=(-1.0, 14e8), config=config,
        device={"platform": "tpu", "kind": "TPU v5 lite"},
        measured={"counters": counters})
    needed = lm_models_dsv2.train_flops(config, per_step)
    peak = byte_models.peaks("TPU v5 lite")["bf16_flops_per_s"]
    got = reader.read(ctx, pattern="lm_delta_step", flops="lm_models_dsv2")
    assert got == pytest.approx(100.0 * needed / 0.5 / peak)
    assert 0 < got < 100
    # the apply program by the other new metrics' reader
    ms = harness.load_module("readers", "module_mean_ms")
    assert ms.read(ctx, pattern="lm_apply") == pytest.approx(50.0)
    assert ms.read(ctx, pattern="lm_delta_step") == pytest.approx(500.0)
    # nothing to read: a program without the counters, a trace without it
    ctx.measured = {"counters": {"steps": 4, "lm_tokens": 4 * 16384}}
    assert reader.read(ctx, pattern="lm_delta_step",
                       flops="lm_models_dsv2") is None
    ctx.measured = {"counters": counters}
    ctx.trace_data = trace_reduce.Trace({}, {}, [])
    assert reader.read(ctx, pattern="lm_delta_step",
                       flops="lm_models_dsv2") is None


def test_new_metric_files_name_their_cell_as_the_benchmark_does():
    bench = harness.load_benchmark()
    for name in ("dsv2_mfu_share", "lm_delta_device_ms",
                 "lm_apply_device_ms"):
        entry = harness.find(bench["per_layer"], name, "metric")
        spec = harness.load_json("layer_metrics", name)
        for key in ("unit", "better", "source", "layer", "moves",
                    "workloads"):
            assert spec[key] == entry[key], (name, key)
        assert entry["workloads"] == [CELL]
    cell = harness.find(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "lm_packed_s8192_b2_dsv2", 1)
    entry = harness.find(bench["configs"], CONFIG, "config")
    assert entry["reduced"] == _cell_config()["reduced"]
    assert entry["source"] == _cell_config()["source_url"]
