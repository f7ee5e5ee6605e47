"""The reader ``scope_device`` on hand-built traces (nested ``while`` self
time, two programs, four devices, a window that cuts a run, an instruction
the map lacks, a scope the executable lacks, a program without the registry),
on a real registered program's own map, and the ten metric files that name
it against their ``BENCHMARK.json`` entries."""
import json
import os
import types

import pytest

import harness
import tiny
from trace_reduce import Trace

MS = 1e6        # the trace's axis is nanoseconds
WINDOW = (0.0, 100 * MS)

DELTA = {
    "while.1": "jit(lm_delta_step)/jvp(lm_mla)/while",
    "fusion.7": "jit(lm_delta_step)/jvp(lm_mla)/while/body/dot_general",
    "fusion.8": "jit(lm_delta_step)/transpose(jvp(lm_mla))/while/body/"
                "checkpoint/rematted_computation/mul",
    "fusion.9": "jit(lm_delta_step)/transpose(jvp(lm_head_loss))/while/body/"
                "checkpoint/dot_general",
    "fusion.10": "jit(lm_delta_step)/lm_eva/lm_eva_agg/exp",
    "copy.3": "jit(lm_delta_step)/mul",
}
APPLY = {"fusion.7": "jit(lm_apply)/lm_head_loss/sqrt"}


def _reader():
    return harness.load_module("readers", "scope_device")


def _ctx(trace, window=WINDOW):
    return types.SimpleNamespace(trace_data=trace, trace_window=window)


def _events(events):
    return sorted((a * MS, b * MS, text) for a, b, text in events)


def _op(name):
    return f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop"


@pytest.fixture
def maps(monkeypatch):
    """What the program would answer: set by each test."""
    from multiverso_tpu.telemetry import device_scopes
    held = {"jit_lm_delta_step": dict(DELTA), "jit_lm_apply": dict(APPLY)}
    monkeypatch.setattr(device_scopes, "program_scopes", lambda: held)
    return held


def _one_run(start):
    """A delta program of 40 ms from ``start``: a while of 24 ms holding
    fusion.7 (10) and fusion.8 (6), so 8 ms its own; then fusion.9 (5),
    fusion.10 (4), copy.3 (3) and an instruction the map lacks (2)."""
    s = start
    return [(s, s + 24, _op("while.1")), (s + 1, s + 11, _op("fusion.7")),
            (s + 12, s + 18, _op("fusion.8")),
            (s + 24, s + 29, _op("fusion.9")),
            (s + 29, s + 33, _op("fusion.10")),
            (s + 33, s + 36, _op("copy.3")),
            (s + 36, s + 38, _op("bitcast.99"))]


def test_self_time_by_scope_over_whole_runs(maps):
    ops = (_one_run(-10) + _one_run(30)
           + [(70, 75, _op("fusion.7"))] + _one_run(80))
    modules = [(-10, 30, "jit_lm_delta_step(11)"),     # cut by the window
               (30, 70, "jit_lm_delta_step(11)"),
               (70, 76, "jit_lm_apply(12)"),
               (80, 120, "jit_lm_delta_step(11)")]     # cut at its end
    ctx = _ctx(Trace({0: _events(ops)}, {0: _events(modules)}, []))
    read = _reader().read
    # the while's own 8 ms count once, what it holds is not counted twice
    assert read(ctx, "lm_delta_step", "lm_mla") == pytest.approx(24.0)
    assert read(ctx, "lm_delta_step", "lm_head_loss") == pytest.approx(5.0)
    assert read(ctx, "lm_delta_step", "lm_eva_agg|lm_eva_prep") \
        == pytest.approx(4.0)
    assert read(ctx, "lm_delta_step", "lm_eva") == pytest.approx(4.0)
    assert read(ctx, "lm_delta_step", "lm_mla|lm_head_loss",
                per="share") == pytest.approx(100.0 * 29 / 40)
    # under none of the scopes: the copy, and the instruction the map lacks
    assert read(ctx, "lm_delta_step", "lm_head_loss",
                unscoped="lm_mla|lm_eva|lm_head_loss", per="share") \
        == pytest.approx(100.0 * 5 / 40)
    # a name is a whole component: "lm" or "lm_ml" is no scope here
    assert read(ctx, "lm_delta_step", "lm|lm_ml|mla") is None
    # the other program's fusion.7 is its own
    assert read(ctx, "lm_apply", "lm_head_loss") == pytest.approx(5.0)
    assert read(ctx, "lm_apply", "lm_head_loss", per="share") \
        == pytest.approx(100.0 * 5 / 6)
    # the scopes and the remainder add up to what the operations covered
    parts = [read(ctx, "lm_delta_step", s) for s in
             ("lm_mla", "lm_head_loss", "lm_eva")]
    rest = read(ctx, "lm_delta_step", "lm_mla",
                unscoped="lm_mla|lm_eva|lm_head_loss")
    assert sum(parts) + rest == pytest.approx(38.0)


def test_a_scope_the_executable_lacks_reads_none(maps, capsys):
    """An executable cached before ``lm_embed`` was written: the metric is
    left out and a line says so; 0 would read as "costs nothing"."""
    ops = _one_run(30)
    ctx = _ctx(Trace({0: _events(ops)},
                     {0: _events([(30, 70, "jit_lm_delta_step(11)")])}, []))
    read = _reader().read
    assert read(ctx, "lm_delta_step", "lm_embed") is None
    assert read(ctx, "lm_delta_step", "lm_embed",
                unscoped="lm_mla|lm_embed", per="share") is None
    assert "lm_embed" in capsys.readouterr().out
    assert read(ctx, "no_such_step", "lm_mla") is None
    assert "no registered program" in capsys.readouterr().out
    # the scope is there but its program never ran whole in the window
    late = _ctx(Trace({0: _events(_one_run(90))},
                      {0: _events([(90, 130, "jit_lm_delta_step(11)")])}, []))
    assert read(late, "lm_delta_step", "lm_mla") is None


def test_without_a_trace_a_device_or_the_registry(maps, monkeypatch, capsys):
    read = _reader().read
    assert read(_ctx(None, None), "lm_delta_step", "lm_mla") is None
    host_only = Trace({}, {}, [(0.0, 5 * MS, "lm.step")])
    assert read(_ctx(host_only), "lm_delta_step", "lm_mla") is None
    assert read(_ctx(host_only), "lm_delta_step", "lm_mla",
                devices="all") is None
    ctx = _ctx(Trace({0: _events(_one_run(30))},
                     {0: _events([(30, 70, "jit_lm_delta_step(11)")])}, []))
    assert read(ctx, "lm_delta_step", "lm_mla", devices=2) is None
    assert capsys.readouterr().out == ""
    # a program from before the registry: nothing to import
    import sys
    import multiverso_tpu.telemetry as telemetry
    monkeypatch.delattr(telemetry, "device_scopes")
    monkeypatch.setitem(sys.modules,
                        "multiverso_tpu.telemetry.device_scopes", None)
    assert read(ctx, "lm_delta_step", "lm_mla") is None
    assert "no map" in capsys.readouterr().out


def test_four_devices_with_unequal_times(maps, capsys):
    maps["jit_block_step"] = {
        "fusion.1": "jit(block_step)/while/body/w2v_rows/w2v_rows_out/"
                    "shard_map/pallas_call",
        "all-reduce.2": "jit(block_step)/while/body/w2v_gather/gather"}
    ops, modules = {}, {}
    for device, rows_ms in enumerate((12.0, 4.0, 12.0, 4.0)):
        ops[device] = _events([
            (10, 10 + rows_ms, _op("fusion.1")),
            (10 + rows_ms, 30, "%all-reduce.2 = f32[8]{0} all-reduce(%x)")])
        modules[device] = _events([(10, 30, "jit_block_step(5)")])
    ctx = _ctx(Trace(ops, modules, []))
    read = _reader().read
    assert read(ctx, "block_step", "w2v_rows") == pytest.approx(12.0)
    assert read(ctx, "block_step", "w2v_rows", devices=1) \
        == pytest.approx(4.0)
    assert read(ctx, "block_step", "w2v_gather|w2v_grads", devices=1) \
        == pytest.approx(16.0)
    assert read(ctx, "block_step", "w2v_rows", devices="all") \
        == pytest.approx(12.0 / 8.0)
    out = capsys.readouterr().out
    assert '"0": 12.0' in out and '"3": 4.0' in out
    # the largest instructions of a program are listed once a trace
    assert out.count("largest of block_step") == 1
    # and once more the largest of those outside every scope
    assert read(ctx, "block_step", "w2v_rows", unscoped="w2v_rows") \
        == pytest.approx(8.0)
    assert read(ctx, "block_step", "w2v_rows", unscoped="w2v_rows",
                per="share") == pytest.approx(40.0)
    out = capsys.readouterr().out
    assert out.count("outside every scope") == 1
    assert "all-reduce.2" in out and "fusion.1" not in out


def test_on_a_registered_programs_own_map():
    """The names the reader joins on are the compiled module's own: a traced
    run of the program is made up from its entry computation's
    instructions."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from multiverso_tpu.telemetry import device_scopes

    @jax.jit
    def scoped_step(w, x):
        with jax.named_scope("layer_a"):
            h = jnp.tanh(x @ w)
        with jax.named_scope("layer_b"):
            return jnp.sum(jnp.sin(h) ** 2)

    args = (np.ones((8, 8), np.float32), np.ones((4, 8), np.float32))
    device_scopes.reset_device_scopes()
    device_scopes.register_program(scoped_step, args)
    try:
        paths = device_scopes.program_scopes()["jit_scoped_step"]
        names_a = [n for n, p in paths.items() if "/layer_a/" in p]
        names_b = [n for n, p in paths.items() if "/layer_b/" in p]
        assert names_a and names_b
        ops = [(10 + i, 11 + i, _op(n))
               for i, n in enumerate(names_a + names_b)]
        ctx = _ctx(Trace({0: _events(ops)},
                         {0: _events([(10, 60, "jit_scoped_step(7)")])}, []))
        read = _reader().read
        assert read(ctx, "scoped_step", "layer_a") \
            == pytest.approx(len(names_a))
        assert read(ctx, "scoped_step", "layer_b") \
            == pytest.approx(len(names_b))
        assert read(ctx, "scoped_step", "layer_c") is None
    finally:
        device_scopes.reset_device_scopes()


NEW = {
    "lm_attn_device_ms": ["nemotron_train", "dsv2lite_train",
                          "evabyte_train"],
    "lm_mamba_device_ms": ["nemotron_train"],
    "lm_experts_device_ms": ["nemotron_train", "dsv2lite_train"],
    "lm_ffn_device_ms": ["dsv2lite_train", "evabyte_train"],
    "lm_head_loss_device_ms": ["nemotron_train", "dsv2lite_train",
                               "evabyte_train"],
    "lm_unscoped_device_share": ["nemotron_train", "dsv2lite_train",
                                 "evabyte_train"],
    "eva_attn_device_share": ["evabyte_train"],
    "w2v_rows_device_ms": ["w2v_train", "w2v_train_x4"],
    "w2v_gather_device_ms": ["w2v_train", "w2v_train_x4"],
    "x4_rows_shard_max_over_mean": ["w2v_train_x4"],
}


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_metric_file_agrees_with_its_entry(name):
    """Each file agrees with its BENCHMARK.json entry (appended after the
    metrics that were there), names this reader, and the reader takes the
    file's arguments: on an empty context it finds nothing and does not
    raise."""
    spec = harness.load_json("layer_metrics", name)
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = harness.find(bench["per_layer"], name, "metric")
    for key in ("layer", "unit", "better", "source", "moves", "workloads"):
        assert spec[key] == entry[key], (name, key)
    assert entry["workloads"] == NEW[name]
    assert (entry["source"], entry["moves"], entry["better"]) == \
        ("device_trace", "train_samples_per_s", "lower")
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"][:38]}
    assert [m["name"] for m in bench["per_layer"][-10:]].count(name) == 1
    assert spec["reader"] == "scope_device"
    assert _reader().read(_ctx(None, None), **spec["args"]) is None
