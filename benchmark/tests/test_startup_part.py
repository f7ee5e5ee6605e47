"""The reader of the program's start-up timeline (``startup_part``) and the
seven metric files that name it."""
import json
import os
import types

import pytest

import harness

NAMES = {"setup_ready_s": "ready_s",
         "setup_before_program_s": "before_program+import",
         "setup_build_s": "build",
         "setup_compile_inside_s": "compile",
         "setup_cache_miss_count": "cache_misses",
         "setup_first_unit_s": "first_unit",
         "setup_outside_program_s": "outside_program"}


@pytest.fixture
def timeline():
    """A process that built, compiled one program under its unit and got
    ready; the timeline as it was is put back after."""
    import jax
    import jax.numpy as jnp
    from multiverso_tpu.telemetry import reset_telemetry, span, startup
    reset_telemetry()
    with span("x.build"):
        pass
    with span("lm.compute"):
        jax.jit(lambda x: x * 2 + 1)(jnp.ones(4)).block_until_ready()
    startup.mark_ready(("lm.compute",))
    yield startup
    reset_telemetry()


def test_the_metric_files_name_the_reader_and_the_benchmark_lists_them():
    bench = harness.load_benchmark()
    cells = [w["name"] for w in bench["workloads"]]
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert set(NAMES) <= set(entries)       # at least these seven
    for name, part in NAMES.items():
        spec = harness.load_json("layer_metrics", name)
        assert (spec["reader"], spec["args"]) == ("startup_part",
                                                  {"part": part})
        entry = entries[name]
        assert entry == {k: spec[k] for k in entry}
        assert (entry["layer"], entry["moves"], entry["better"],
                entry["source"]) == ("start-up and placement", "setup_s",
                                     "lower", "program_span")
        assert entry["unit"] == ("programs" if name.endswith("_count")
                                 else "s")
        assert entry["workloads"] == cells  # set-up is in every cell


def test_every_part_is_read_and_the_parts_sum_to_ready_s(timeline, capsys):
    reader = harness.load_module("readers", "startup_part")
    ctx = types.SimpleNamespace()
    got = {name: reader.read(ctx, part) for name, part in NAMES.items()}
    assert all(v is not None for v in got.values())
    rep = timeline.report()
    assert got["setup_ready_s"] == rep["ready_s"]
    assert got["setup_before_program_s"] == pytest.approx(
        rep["parts"]["before_program"] + rep["parts"]["import"])
    assert got["setup_compile_inside_s"] > 0
    assert got["setup_cache_miss_count"] == 0       # the cache is off here
    every = [reader.read(ctx, p) for p in rep["parts"]]
    assert sum(every) == pytest.approx(got["setup_ready_s"], abs=1e-6)
    # the first call of a run printed the timeline, the others nothing
    lines = capsys.readouterr().out.splitlines()
    assert [ln.split()[0] for ln in lines].count("startup") == 1
    head = next(ln for ln in lines if ln.startswith("startup "))
    for field in ("ready_s=", "compile=", "first_unit=", "outside_program=",
                  "transfers_landed_s=", "cache_misses="):
        assert field in head
    compiles = [ln.split() for ln in lines
                if ln.startswith("startup.compile ")]
    assert compiles and all(len(c) == 7 for c in compiles)
    assert compiles[-1][2] == "off" and compiles[-1][-1] == "under=lm.compute"


def test_a_program_without_a_timeline_reads_none(timeline, monkeypatch):
    reader = harness.load_module("readers", "startup_part")
    timeline.reset()                        # never got ready
    assert reader.read(types.SimpleNamespace(), "build") is None
    import sys
    monkeypatch.setitem(sys.modules, "multiverso_tpu.telemetry.startup", None)
    import multiverso_tpu.telemetry as telemetry
    monkeypatch.delattr(telemetry, "startup")
    assert reader.read(types.SimpleNamespace(), "build") is None   # a parent
