"""``trace_reduce`` against a small trace recorded on a TPU v5e (ten steps of
a jitted gather and a jitted scatter-add under ``bench.probe_step``, each
followed by 10 ms of sleep under ``bench.probe_sleep``), and the byte models
against hand-worked numbers."""
import os

import pytest

import byte_models
import trace_reduce

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "probe_v5e.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return trace_reduce.Trace.from_file(TRACE)


def _naive_union_ns(intervals):
    """Length of a union by sweeping the sorted end points."""
    points = sorted([(a, 1) for a, _ in intervals]
                    + [(b, -1) for _, b in intervals])
    depth, last, out = 0, None, 0.0
    for t, d in points:
        if depth > 0:
            out += t - last
        depth += d
        last = t
    return out


def test_layout_of_the_recorded_trace(trace):
    assert trace.devices() == [0]
    assert len(trace.modules[0]) == 20          # 10 gathers, 10 scatters
    assert len(trace.ops[0]) == 60
    assert {n for _, _, n in trace.annotations} == {
        "bench.probe_step", "bench.probe_sleep"}


def test_busy_union_and_idle_share(trace):
    ops = [(a, b) for a, b, _ in trace.ops[0]]
    assert trace_reduce.total(trace.busy(0)) == pytest.approx(
        _naive_union_ns(ops))
    assert trace.busy_s() == pytest.approx(0.00766703, rel=1e-6)
    win = trace.annotation_window("bench.probe_step")
    window_s = (win[1] - win[0]) / 1e9
    assert window_s == pytest.approx(0.111941568, rel=1e-6)
    idle = trace.idle_share(win)
    assert idle == pytest.approx(1.0 - trace.busy_s(win) / window_s)
    assert 0.92 < idle < 0.94
    # clipping: half the window holds about half the busy time
    half = (win[0], (win[0] + win[1]) / 2)
    assert 0.4 < trace.busy_s(half) / trace.busy_s(win) < 0.6


def test_time_per_operation(trace):
    ops = trace.op_seconds(0)
    assert ops["fusion_in_jit_scat"] == pytest.approx(0.006154121, rel=1e-6)
    assert ops["copy-done_in_jit_gather"] == pytest.approx(0.001352692,
                                                           rel=1e-6)
    assert sum(ops.values()) == pytest.approx(
        sum(b - a for a, b, _ in trace.ops[0]) / 1e9)
    top = trace_reduce.top(ops, 2)
    assert [k for k, _ in top] == ["fusion_in_jit_scat",
                                   "copy-done_in_jit_gather"]


def test_time_of_matching_operations(trace):
    """The collective share is this reduction with a pattern of collective
    names; the recorded one-chip trace has none, so the pattern is checked
    on the copies it does have, and a collective pattern reads zero."""
    copies = [(a, b) for a, b, t in trace.ops[0]
              if trace_reduce.op_name(t).startswith("copy")]
    assert trace.matching_seconds("^copy", 0) == pytest.approx(
        _naive_union_ns(copies) / 1e9)
    assert trace.matching_seconds("^(all-reduce|all-gather)", 0) == 0.0
    assert trace.matching_seconds("scat", 0, "module") == pytest.approx(
        0.00615833, rel=1e-5)


def test_idle_gaps_go_to_the_annotation_over_them(trace):
    win = trace.annotation_window("bench.probe_step")
    gaps = trace.idle_gaps(*win)
    assert sum(gaps.values()) == pytest.approx(
        (win[1] - win[0]) / 1e9 - trace.busy_s(win))
    assert gaps["bench.probe_sleep"] > 0.09     # ten sleeps of 10 ms
    assert gaps["bench.probe_sleep"] > 10 * gaps["bench.probe_step"]
    skipped = trace.idle_gaps(*win, skip=("bench.probe_sleep",))
    assert "bench.probe_sleep" not in skipped and "no_span" in skipped


def test_op_and_module_names():
    assert trace_reduce.op_name(
        "%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop") == "fusion"
    assert trace_reduce.op_name("%all-reduce-start.1 = ...") \
        == "all-reduce-start"
    assert trace_reduce.module_name("jit_gather(6856287926899424905)") \
        == "jit_gather"


def test_byte_models_by_hand():
    # one sg-ns pair at D=128, K=5, float32: 7 rows x 128 x 24 B
    assert byte_models.sgns_adagrad_bytes_per_pair(128, 5) == 21_504
    assert byte_models.sgns_adagrad_bytes_per_pair(128, 5, 2) \
        == 7 * 128 * 18
    assert byte_models.sgns_adagrad_flops_per_pair(128, 5) \
        == 6 * 6 * 128 + 4 * 7 * 128
    assert byte_models.gather_bytes_per_row(128) == 1024
    peaks = byte_models.peaks("TPU v5 lite")
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert peaks["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        byte_models.peaks("TPU v9 imaginary")
