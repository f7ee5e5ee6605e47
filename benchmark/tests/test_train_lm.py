"""The cell ``nemotron_train`` (driver ``train_lm``) on the CPU: rehearsed tiny
through run.py, traced and not (the sizes are the ``tiny`` keys its own files
carry); the lower-precision control fails the rehearsal's limits while the sound
program passes; with the timed path broken underneath (a dropped push, a
skipped layer, an expert left out) a whole run comes out ``correct: false``;
the FLOP model by hand; the reader of ``lm_mfu_share`` on a made-up trace."""
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
CELL = "nemotron_train"


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
                "setup_compile_s"} <= set(result["metrics"])
        assert "lm_mfu_share" not in result["metrics"]
        assert result["metrics"]["lm_expert_load_max_over_mean"]["value"] \
            >= 1.0
    else:
        assert set(result["metrics"]) == {"train_samples_per_s",
                                          "peak_hbm_gb", "setup_s"}
        assert "check compiles_in_window: 0.0 == 0 ok" in proc.stdout
        for name in ("step_loss_rel_gap", "dense_rel_gap.mamba",
                     "dense_rel_gap.attention", "dense_rel_gap.experts",
                     "dense_rel_gap.top", "rows_rel_gap",
                     "expert_counts_rel_gap"):
            assert f"check {name}:" in proc.stdout


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


def test_push_that_is_dropped(tiny_root, monkeypatch, capsys):
    from multiverso_tpu.models.hybrid_lm import HybridLM
    monkeypatch.setattr(HybridLM, "_push_rows",
                        lambda self, ids, delta: None)
    assert _run(*tiny_root)["correct"] is False
    out = capsys.readouterr().out
    assert "check rows_rel_gap" in out and "FAILED" in out


def test_layer_that_is_skipped(tiny_root, monkeypatch):
    from multiverso_tpu.models.hybrid_lm import model
    whole = model.layer_forward

    def no_attention(kind, p, bias, u, cfg, remat=False):
        return (u, None) if kind == "*" else whole(kind, p, bias, u, cfg,
                                                   remat)
    monkeypatch.setattr(model, "layer_forward", no_attention)
    assert _run(*tiny_root)["correct"] is False


def test_expert_that_is_left_out(tiny_root, monkeypatch, capsys):
    from multiverso_tpu.models.hybrid_lm import model
    whole = model.held_topk_moe

    def one_short(n, router, bias, w_up, w_down, *rest):
        return whole(n, router, bias, w_up, w_down.at[-1].set(0.0), *rest)
    monkeypatch.setattr(model, "held_topk_moe", one_short)
    assert _run(*tiny_root)["correct"] is False
    assert "FAILED" in capsys.readouterr().out


def test_sound_cell_passes_in_process(tiny_root):
    assert _run(*tiny_root)["correct"] is True


def test_flop_model_by_hand():
    import lm_models
    c = {"hidden_size": 8, "hybrid_override_pattern": "ME*M",
         "num_hidden_layers": 3, "mamba_num_heads": 2, "mamba_head_dim": 4,
         "ssm_state_size": 3, "n_groups": 1, "conv_kernel": 4,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 5,
         "n_routed_experts": 2, "published": {"n_routed_experts": 6},
         "moe_shared_expert_intermediate_size": 7, "n_shared_experts": 1,
         "moe_intermediate_size": 9, "vocab_size": 11}
    tokens, seq, assigned = 20, 10, 13
    mamba = 2 * 8 * (8 + 14 + 2) + 2 * 8 * 8 + 2 * 14 * 4 + 5 * 2 * 4 * 3
    attn = 2 * 8 * (4 + 4) * 5 + 2 * 20 * 8
    scores = 2 * (10 * 11 // 2) * 4 * 5 * 4
    experts = 2 * 8 * 6 + 4 * 8 * 7
    want = tokens * (mamba + attn + experts + 2 * 8 * 11) + scores \
        + assigned * 4 * 8 * 9
    assert lm_models.forward_flops(c, tokens, seq, assigned) == want
    assert lm_models.train_flops(c, tokens, seq, assigned) == 3 * want


def test_lm_mfu_reader_on_a_made_up_trace():
    import byte_models
    import lm_models
    reader = harness.load_module("readers", "lm_mfu")
    with open(os.path.join(tiny.BENCH_DIR, "configs",
                           "nemotron3-nano-30b-a3b-ep16.json")) as f:
        config = json.load(f)
    # three runs of the step program of 0.5 s, one cut by the window's edge
    modules = {0: [(0.0, 5e8, "jit_lm_delta_step(1)"),
                   (6e8, 11e8, "jit_lm_delta_step(1)"),
                   (11e8, 11.5e8, "jit_lm_apply(2)"),
                   (12e8, 17e8, "jit_lm_delta_step(1)")]}
    ctx = types.SimpleNamespace(
        trace_data=trace_reduce.Trace({}, modules, []),
        trace_window=(-1.0, 14e8), config=config,
        device={"platform": "tpu", "kind": "TPU v5 lite"},
        measured={"counters": {"steps": 4, "lm_tokens": 4 * 16384,
                               "lm_seq_len": 8192,
                               "lm_assignments_held": 4 * 4 * 6000}})
    per_step = lm_models.train_flops(config, 16384, 8192, 4 * 6000)
    peak = byte_models.peaks("TPU v5 lite")["bf16_flops_per_s"]
    got = reader.read(ctx, pattern="lm_delta_step")
    assert got == pytest.approx(100.0 * per_step / 0.5 / peak)
    assert 0 < got < 100
    # nothing to read: a program without the counters, a trace without it
    ctx.measured = {"counters": {"steps": 4}}
    assert reader.read(ctx, pattern="lm_delta_step") is None
    ctx.measured = {"counters": {"steps": 4, "lm_tokens": 1}}
    ctx.trace_data = trace_reduce.Trace({}, {}, [])
    assert reader.read(ctx, pattern="lm_delta_step") is None
