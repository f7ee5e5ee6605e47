"""The four readers of the program's phases (``annotation_stat``,
``idle_under``, ``module_mean_ms``, ``span_total_s``) on hand-built traces and
on the recorded v5e trace, and the metric files that name them."""
import json
import os
import types

import pytest

import harness
import tiny
import trace_reduce
from trace_reduce import Trace

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "probe_v5e.xplane.pb")
MS = 1e6        # the trace's axis is nanoseconds


def _reader(name):
    return harness.load_module("readers", name)


def _ctx(trace, window):
    return types.SimpleNamespace(trace_data=trace, trace_window=window)


def _trace(busy, annotations, modules=None):
    """Device 0 busy over ``busy`` (ms intervals); annotations and modules
    as (start ms, end ms, name)."""
    scale = lambda evs: [(a * MS, b * MS, n) for a, b, n in evs]  # noqa: E731
    return Trace({0: scale([(a, b, "%fusion.1 = f32[8] fusion()")
                            for a, b in busy])},
                 {0: scale(modules or [])}, sorted(scale(annotations)))


WINDOW = (0.0, 100 * MS)


def test_a_gap_is_split_between_two_threads_annotations():
    """Idle 10..90 ms (80 ms). One thread's phase covers 10..40, another
    thread's 30..70: each reads its own part of the gap, their union reads
    once what they share, and the enclosing bench annotation takes nothing
    from either."""
    tr = _trace([(0, 10), (90, 100)],
                [(0, 100, "bench.serve_wait"),
                 (10, 40, "serve.batcher.idle"),
                 (30, 70, "serve.collector.wait")])
    idle = _reader("idle_under")
    ctx = _ctx(tr, WINDOW)
    assert idle.read(ctx, r"^serve\.batcher\.idle$") == pytest.approx(37.5)
    assert idle.read(ctx, r"^serve\.collector\.wait$") == pytest.approx(50.0)
    assert idle.read(ctx, r"^serve\.") == pytest.approx(75.0)   # 10..70
    assert idle.read(ctx, unattributed=True) == pytest.approx(25.0)
    # the winner-takes-all rule of trace_reduce gives the whole gap away
    assert trace_reduce.Trace.idle_gaps(tr, *WINDOW) == pytest.approx(
        {"bench.serve_wait": 0.080})
    assert idle.read(ctx, r"^no\.such\.span$") is None


def test_a_nested_annotation_reads_its_own_part():
    """``recsys.pull`` 0..60 encloses two table phases; the device works
    20..30 inside the first one."""
    tr = _trace([(20, 30), (60, 100)],
                [(0, 60, "recsys.pull"),
                 (5, 35, "table.get_rows.dispatch"),
                 (35, 55, "table.get_rows.sync")])
    idle = _reader("idle_under")
    ctx = _ctx(tr, WINDOW)                      # idle: 0..20 and 30..60
    assert idle.read(ctx, r"^table\.get_rows\.dispatch$") == \
        pytest.approx(100 * 20 / 50)            # 5..20 and 30..35
    assert idle.read(ctx, r"^table\.get_rows\.sync$") == pytest.approx(40.0)
    assert idle.read(ctx, r"^recsys\.pull$") == pytest.approx(100.0)
    assert idle.read(ctx, unattributed=True) == pytest.approx(0.0)


def test_an_annotation_straddling_the_windows_edge():
    tr = _trace([(40, 50)],
                [(-20, 10, "serve.batcher.idle"),       # cut by the start
                 (20, 30, "serve.batcher.idle"),
                 (60, 80, "serve.batcher.idle"),
                 (95, 130, "serve.batcher.idle")])      # cut by the end
    stat = _reader("annotation_stat")
    ctx = _ctx(tr, WINDOW)
    pat = r"^serve\.batcher\.idle$"
    assert stat.read(ctx, pat, "mean_ms") == pytest.approx(15.0)  # whole ones
    assert stat.read(ctx, pat, "max_ms") == pytest.approx(20.0)
    assert stat.read(ctx, pat, "window_share") == pytest.approx(45.0)
    assert stat.read(ctx, r"^absent$", "max_ms") is None
    assert stat.read(ctx, r"^absent$", "window_share") is None
    with pytest.raises(ValueError):
        stat.read(ctx, pat, "median_ms")
    # idle 0..40 and 50..100: the clipped parts 0..10, 20..30, 60..80, 95..100
    assert _reader("idle_under").read(ctx, pat) == pytest.approx(50.0)


def test_unattributed_with_only_bench_names_reads_100():
    tr = _trace([(0, 1)], [(0, 100, "bench.serve_wait"),
                           (0, 100, "bench.window")])
    assert _reader("idle_under").read(_ctx(tr, WINDOW), unattributed=True) \
        == pytest.approx(100.0)


def test_readers_find_nothing_without_a_trace_or_a_device():
    idle, stat, mod = (_reader(n) for n in
                       ("idle_under", "annotation_stat", "module_mean_ms"))
    none = _ctx(None, None)
    assert idle.read(none, "x") is None and stat.read(none, "x") is None
    assert mod.read(none, "x") is None
    host_only = Trace({}, {}, [(0.0, 5 * MS, "recsys.pull")])
    ctx = _ctx(host_only, (0.0, 10 * MS))
    assert idle.read(ctx, "recsys") is None         # the CPU has no device
    assert idle.read(ctx, unattributed=True) is None
    assert mod.read(ctx, "block_step") is None
    assert stat.read(ctx, "recsys", "mean_ms") == pytest.approx(5.0)
    busy = _trace([(0, 100)], [(0, 100, "recsys.pull")])
    assert idle.read(_ctx(busy, WINDOW), "recsys") is None   # never idle


def test_module_mean_is_over_whole_runs_inside_the_window():
    tr = _trace([], [], modules=[
        (-5, 5, "jit_block_step(1)"),           # cut by the window's start
        (10, 30, "jit_block_step(1)"), (30, 60, "jit_block_step(1)"),
        (60, 61, "jit_gather(2)"), (90, 120, "jit_block_step(1)")])
    mod = _reader("module_mean_ms")
    assert mod.read(_ctx(tr, WINDOW), "block_step") == pytest.approx(25.0)
    assert mod.read(_ctx(tr, WINDOW), "^jit_gather$") == pytest.approx(1.0)
    assert mod.read(_ctx(tr, WINDOW), "no_such_program") is None
    assert mod.read(_ctx(tr, WINDOW), "block_step", device=3) is None


@pytest.fixture(scope="module")
def recorded():
    return Trace.from_file(TRACE)


def test_on_the_recorded_v5e_trace(recorded):
    """Ten steps (``bench.probe_step``: a gather and a scatter-add) each
    followed by 10 ms of sleep (``bench.probe_sleep``)."""
    tr = recorded
    win = tr.annotation_window("bench.probe_step")
    ctx = _ctx(tr, win)
    idle, stat, mod = (_reader(n) for n in
                       ("idle_under", "annotation_stat", "module_mean_ms"))
    assert stat.read(ctx, r"^bench\.probe_sleep$", "mean_ms") == \
        pytest.approx(10.4, abs=0.3)
    assert stat.read(ctx, r"^bench\.probe_sleep$", "max_ms") < 11.0
    sleep = idle.read(ctx, r"^bench\.probe_sleep$")
    step = idle.read(ctx, r"^bench\.probe_step$")
    # the two names tile the window; the steps overlap the device's work
    assert sleep + step == pytest.approx(100.0, abs=0.5)
    assert 80.0 < sleep < 85.0 and 15.0 < step < 20.0
    # split at the edges: each gap starts under a step (its launch and its
    # wait for the device) and ends under the sleep, and the gap-by-gap
    # attribution gives all of it to the sleep
    whole = tr.idle_gaps(*win)
    assert 100.0 * whole["bench.probe_sleep"] / sum(whole.values()) > 98.0
    # only the benchmark's own names: every idle second is unattributed
    assert idle.read(ctx, unattributed=True) == pytest.approx(100.0)
    share = stat.read(ctx, r"^bench\.probe_sleep$", "window_share")
    assert 80.0 < share < 88.0
    scat = [(b - a) / 1e6 for a, b, n in tr.modules[0]
            if "scat" in n and a >= win[0] and b <= win[1]]
    assert mod.read(ctx, "scat") == pytest.approx(sum(scat) / len(scat))
    assert 0.5 < mod.read(ctx, "scat") < 0.7      # 6.16 ms over ten runs


def test_span_total_reads_the_programs_histograms():
    from multiverso_tpu.telemetry import reset_telemetry
    from multiverso_tpu.telemetry.metrics import get_registry
    reset_telemetry()
    reader = _reader("span_total_s")
    names = ["table.host_init", "table.device_put"]
    assert reader.read(None, names) is None     # a program without them
    get_registry().histogram("span.table.host_init").observe(1500.0)
    get_registry().histogram("span.table.host_init").observe(500.0)
    get_registry().histogram("span.table.device_put").observe(250.0)
    assert reader.read(None, names) == pytest.approx(2.25)
    assert reader.read(None, ["table.device_put"]) == pytest.approx(0.25)
    reset_telemetry()


NEW = ["serve_request_ms", "serve_reply_ms", "serve_dispatch_ms",
       "serve_collect_ms", "serve_idle_no_request_share",
       "sketch_fold_max_ms", "idle_unattributed_share.serve",
       "idle_unattributed_share.train", "dlrm_idle_get_dispatch_share",
       "dlrm_idle_get_sync_share", "dlrm_idle_add_dispatch_share",
       "dlrm_idle_add_sync_share", "w2v_block_device_ms",
       "setup_table_host_s"]


@pytest.mark.parametrize("name", NEW)
def test_new_metric_file_loads_and_names_a_reader(name):
    """Each file agrees with its BENCHMARK.json entry, names a reader that
    is there, and that reader takes the file's arguments: on an empty
    context it finds nothing and does not raise."""
    spec = harness.load_json("layer_metrics", name)
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = harness.find(bench["per_layer"], name, "metric")
    for key in ("layer", "unit", "better", "source", "moves", "workloads"):
        assert spec[key] == entry[key], key
    cells = {w["name"] for w in bench["workloads"]}
    moved = harness.find(bench["end_to_end"], entry["moves"], "metric")
    for cell in entry["workloads"]:
        assert cell in cells
        assert cell in moved.get("workloads", cells)
    reader = _reader(spec["reader"])
    ctx = types.SimpleNamespace(trace_data=None, trace_window=None,
                                measured={})
    if spec["reader"] != "span_total_s":
        assert reader.read(ctx, **spec["args"]) is None
