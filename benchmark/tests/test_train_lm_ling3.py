"""The cell ``ling3_train`` (driver ``train_lm_ling3``) on the CPU: rehearsed
tiny through run.py, traced and not (the sizes are the ``tiny`` keys its own
files carry); the lower-precision control and the three left-out-mathematics
controls (the delta correction, the groups, the selection bias's update) fail
the rehearsal's limits while the sound program passes; with the timed path
broken underneath (a dropped push, a skipped block, a held expert left out, any
of the three controls) a whole run comes out ``correct: false``; a program
without the KDA block is told to stop before anything is built; the FLOP model
by hand and at the cell's own size; the reader of ``ling3_mfu_share`` on a
made-up trace; the new files against their ``BENCHMARK.json`` entries."""
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
CELL = "ling3_train"
CONFIG = "ling-3.0-flash-ep32"
MIX = "lm_packed_s8192_b2_ling3"
CHECKS = ("step_loss_rel_gap", "dense_rel_gap.kda", "dense_rel_gap.mla",
          "dense_rel_gap.dense_ffn", "dense_rel_gap.experts",
          "dense_rel_gap.top", "rows_rel_gap", "expert_counts_rel_gap",
          "expert_bias_mismatch_share")
NEW_METRICS = ("ling3_mfu_share", "ling3_kda_device_ms",
               "ling3_kda_scan_device_ms", "ling3_route_device_ms")


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
                "setup_compile_s",
                "setup_table_host_s"} <= set(result["metrics"])
        assert not (set(NEW_METRICS) | {"lm_delta_device_ms"}) \
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
        steps = info["steps"]
        assert info["lm_tokens"] == steps * 2 * 40
        # one latent-attention block, two sequences of 40
        assert info["lm_attn_pairs"] == steps * 2 * 40 * 41 // 2
        # two KDA blocks, two sequences of 40 in chunks of 32; two expert
        # blocks choose among their groups
        assert info["lm_kda_chunks"] == steps * 2 * 2 * 2
        assert info["lm_kda_plane_xla"] == steps * 2
        assert info["lm_moe_group_limited"] == steps * 2
        assert 0 < info["lm_assignments_held"] <= steps * 2 * 80 * 2
        assert "lm_balance_loss" not in info


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
    # the delta correction left out: KDA's own leaves say so
    assert by_side["no_delta"]["dense_rel_gap.kda"] > limits["dense_rel_gap"]
    # the groups left out: other experts are chosen
    assert by_side["no_groups"]["expert_counts_rel_gap"] > 0.01
    # the bias left as seeded: every entry that moved stands a rate or two off
    assert by_side["fixed_bias"]["expert_bias_mismatch_share"] > 0.3


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


@pytest.mark.parametrize("skipped", ["K", "L", "D", "E"])
def test_block_that_is_skipped(tiny_root, monkeypatch, skipped):
    import jax.numpy as jnp
    from multiverso_tpu.models.hybrid_lm import model
    whole = model.layer_forward

    def without(kind, p, bias, u, cfg, remat=False):
        if kind != skipped:
            return whole(kind, p, bias, u, cfg, remat)
        return (u, jnp.zeros(len(cfg.held), jnp.int32),
                jnp.zeros(cfg.router_experts, jnp.int32)) if kind == "E" \
            else (u, None)
    monkeypatch.setattr(model, "layer_forward", without)
    assert _run(*tiny_root)["correct"] is False


def test_held_expert_that_is_left_out(tiny_root, monkeypatch, capsys):
    from multiverso_tpu.models.hybrid_lm import model
    whole = model.held_topk_moe

    def one_short(n, router, bias, w_up, w_down, *rest):
        return whole(n, router, bias, w_up, w_down.at[-1].set(0.0), *rest)
    monkeypatch.setattr(model, "held_topk_moe", one_short)
    assert _run(*tiny_root)["correct"] is False
    assert any("dense_rel_gap.experts" in line for line in _failed(capsys))


@pytest.mark.parametrize("what,check", [
    ("no_delta", "dense_rel_gap.kda"),
    ("no_groups", "expert_counts_rel_gap"),
    ("fixed_bias", "expert_bias_mismatch_share")])
def test_mathematics_that_is_left_out(tiny_root, capsys, what, check):
    driver = harness.load_module("drivers", "train_lm_ling3")
    with driver.left_out(what):
        assert _run(*tiny_root)["correct"] is False
    assert any(check in line for line in _failed(capsys))
    # and the program is whole again afterwards
    assert _run(*tiny_root)["correct"] is True


def test_program_without_the_block_is_told_to_stop(tiny_root, monkeypatch):
    """The parent reads unknown keys as nothing and would build a plain
    latent-attention model: the driver asks before it builds anything."""
    import multiverso_tpu as mv
    from multiverso_tpu.models import hybrid_lm
    monkeypatch.delattr(hybrid_lm, "KDA")
    monkeypatch.setattr(mv, "init", lambda *a, **k: pytest.fail(
        "the tables were reached"))
    with pytest.raises(harness.BenchError, match="cannot run"):
        _run(*tiny_root)


def _cell_config():
    with open(os.path.join(tiny.BENCH_DIR, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def test_flop_model_by_hand():
    import lm_models_ling3
    c = {"hidden_size": 8, "num_hidden_layers": 4, "layer_group_size": 3,
         "first_k_dense_replace": 1, "num_attention_heads": 2, "head_dim": 4,
         "short_conv_kernel_size": 4, "kv_lora_rank": 6,
         "qk_nope_head_dim": 4, "qk_rope_head_dim": 2, "v_head_dim": 4,
         "intermediate_size": 7, "moe_intermediate_size": 5,
         "moe_shared_expert_intermediate_size": 3, "num_experts": 2,
         "published": {"num_experts": 6}, "vocab_size": 11}
    tokens, pairs, held = 20, 110, 13
    # layers 0, 1, 3 are KDA, layer 2 latent attention; layer 0 dense
    want = {
        "kda_projections": tokens * 3 * (2 * 8 * 8 * 5 + 2 * 8 * 2 * 2
                                         + 3 * 2 * 8 * 4),
        "kda_recurrence": tokens * 3 * 2 * 4 * 2 * 4 * 4,
        "mla_projections": tokens * 1 * (2 * 8 * 2 * 6 + 2 * 8 * (6 + 2)
                                         + 2 * 6 * 2 * (4 + 4)
                                         + 2 * 2 * 4 * 8),
        "mla_scores": pairs * 2 * 2 * (6 + 4),
        "dense_ffn": tokens * 1 * 6 * 8 * 7,
        "router_and_shared": tokens * 3 * (2 * 8 * 6 + 6 * 8 * 3),
        "routed_experts": held * 6 * 8 * 5,
        "head": tokens * 2 * 8 * 11}
    assert lm_models_ling3.forward_parts(c, tokens, pairs, held) == want
    assert lm_models_ling3.train_flops(
        c, {"lm_tokens": tokens, "lm_attn_pairs": pairs,
            "lm_assignments_held": held}) == 3 * sum(want.values())


def test_flop_model_at_the_cells_own_size():
    """ISSUE 48's arithmetic, at its first choice of 16 held experts: about
    52 TFLOP a step of 16,384 tokens at the mean load (8 x 16 / 512
    assignments a token and expert block land here): the five KDA blocks'
    products 25.8 and their recurrence 1.0, latent attention 3.1 + 4.1 in its
    pairs, dense feed-forwards 9.3, head 4.9, routers and shared experts 2.8,
    held experts 0.6. With the 8 the file holds the experts are 0.29 and the
    step 51.5."""
    import lm_models_ling3
    c = _cell_config()
    tokens = 2 * 8192
    pairs = 2 * 8192 * 8193 // 2
    parts = lm_models_ling3.forward_parts(c, tokens, pairs,
                                          4 * tokens * 8 * 16 / 512)
    tera = {k: 3 * v / 1e12 for k, v in parts.items()}
    assert sum(tera.values()) == pytest.approx(51.8, abs=0.05)
    assert tera["kda_projections"] == pytest.approx(25.87, abs=0.05)
    assert tera["kda_recurrence"] == pytest.approx(1.03, abs=0.01)
    assert tera["mla_projections"] == pytest.approx(3.13, abs=0.01)
    assert tera["mla_scores"] == pytest.approx(4.12, abs=0.01)
    assert tera["dense_ffn"] == pytest.approx(9.28, abs=0.01)
    assert tera["head"] == pytest.approx(4.94, abs=0.01)
    assert tera["router_and_shared"] == pytest.approx(2.83, abs=0.01)
    assert tera["routed_experts"] == pytest.approx(0.58, abs=0.01)
    assert c["num_experts"] == 8
    at_the_file = lm_models_ling3.forward_parts(c, tokens, pairs,
                                                4 * tokens * 8 * 8 / 512)
    assert 3 * at_the_file["routed_experts"] / 1e12 == \
        pytest.approx(0.29, abs=0.01)
    assert 3 * sum(at_the_file.values()) / 1e12 == \
        pytest.approx(51.5, abs=0.05)
    # every dense parameter but norms, taps, gates' biases and the absent
    # experts multiplies once a token (the held experts once an assignment)
    products = sum(parts.values()) - parts["mla_scores"] \
        - parts["kda_recurrence"] - parts["routed_experts"]
    small = 5 * (32 + 4096 + 128 + 2560) + (2560 + 512) + 6 * 2560 + 2560
    experts = 4 * 8 * 3 * 2560 * 768
    assert products == 2 * tokens * (657397536 - small - experts)


def test_model_mfu_reader_on_a_made_up_trace():
    import byte_models
    import lm_models_ling3
    reader = harness.load_module("readers", "model_mfu")
    config = _cell_config()
    # three runs of the step program of 0.8 s, one cut by the window's edge
    modules = {0: [(0.0, 0.8e9, "jit_lm_delta_step(1)"),
                   (0.9e9, 1.7e9, "jit_lm_delta_step(1)"),
                   (1.7e9, 1.72e9, "jit_lm_apply(2)"),
                   (1.8e9, 2.6e9, "jit_lm_delta_step(1)")]}
    per_step = {"lm_tokens": 16384, "lm_attn_pairs": 2 * 8192 * 8193 // 2,
                "lm_assignments_held": 16384}
    counters = dict({k: 4 * v for k, v in per_step.items()}, steps=4)
    ctx = types.SimpleNamespace(
        trace_data=trace_reduce.Trace({}, modules, []),
        trace_window=(-1.0, 2.0e9), config=config,
        device={"platform": "tpu", "kind": "TPU v5 lite"},
        measured={"counters": counters})
    needed = lm_models_ling3.train_flops(config, per_step)
    peak = byte_models.peaks("TPU v5 lite")["bf16_flops_per_s"]
    got = reader.read(ctx, pattern="lm_delta_step", flops="lm_models_ling3")
    assert got == pytest.approx(100.0 * needed / 0.8 / peak)
    assert 0 < got < 100
    # nothing to read: a program without the counters
    ctx.measured = {"counters": {"steps": 4, "lm_tokens": 4 * 16384}}
    assert reader.read(ctx, pattern="lm_delta_step",
                       flops="lm_models_ling3") is None


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
    for name, scope in (("ling3_kda_device_ms", "lm_kda"),
                        ("ling3_kda_scan_device_ms", "lm_kda_scan"),
                        ("ling3_route_device_ms", "lm_route")):
        assert harness.load_json("layer_metrics", name)["args"] == {
            "module": "lm_delta_step", "scope": scope}
    reported = {m["name"] for m in harness.metrics_of_cell(
        bench["per_layer"], CELL)}
    assert reported >= set(NEW_METRICS) | {
        "setup_compile_s", "setup_table_host_s", "train_device_idle_share",
        "idle_unattributed_share.train", "lm_step_ms", "lm_table_ms",
        "lm_delta_device_ms", "lm_apply_device_ms", "lm_attn_device_ms",
        "lm_attn_pairs_device_ms", "lm_experts_device_ms", "lm_ffn_device_ms",
        "lm_head_loss_device_ms", "lm_expert_load_max_over_mean"}
    # scopes this model's program does not have, and the list that lacks
    # ``lm_kda`` (a benchmark PR's to extend: PERF.md 7)
    assert not reported & {"lm_mamba_device_ms", "lm_scan_device_ms",
                           "lfm2_mfu_share", "lm_unscoped_device_share"}
    cell = harness.find(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, MIX, 1)
    assert "1/16" in cell["why"] and len(cell["why"]) <= 200
    assert CELL in harness.find(bench["end_to_end"], "train_samples_per_s",
                                "metric")["workloads"]
    entry = harness.find(bench["configs"], CONFIG, "config")
    assert entry["reduced"] == _cell_config()["reduced"]
    assert entry["source"] == _cell_config()["source_url"]
    mix = harness.load_json("traffic", MIX)
    assert (mix["sequences"], mix["seq_len"], mix["batches"]) == (2, 8192, 8)
    assert set(mix["limits"]) == set(mix["tiny"]["limits"]) \
        <= set(mix["limits_why"])


def test_reference_imports_nothing_of_the_program():
    path = os.path.join(tiny.BENCH_DIR, "reference", CONFIG + ".py")
    with open(path) as f:
        text = f.read()
    assert "multiverso_tpu" not in text.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in text
