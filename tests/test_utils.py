"""Log / CHECK / Timer / Dashboard tests (reference unittest altitude)."""

import time

import pytest

from multiverso_tpu.utils.dashboard import Dashboard, monitor, monitored
from multiverso_tpu.utils.log import FatalError, check, check_notnull, log
from multiverso_tpu.utils.timer import Timer


def test_check_passes_and_fails():
    check(True)
    with pytest.raises(FatalError):
        check(False, "boom")


def test_check_notnull():
    assert check_notnull(5) == 5
    with pytest.raises(FatalError):
        check_notnull(None, "thing")


def test_timer_elapses():
    t = Timer()
    time.sleep(0.01)
    assert t.elapse() >= 5.0  # ms
    t.start()
    assert t.elapse() < 5.0


def test_monitor_counts():
    with monitor("unit_test_op"):
        time.sleep(0.005)
    with monitor("unit_test_op"):
        time.sleep(0.005)
    m = Dashboard.get("unit_test_op")
    assert m.count == 2
    assert m.total_ms >= 5.0
    assert m.average_ms > 0
    assert "unit_test_op" in Dashboard.watch("unit_test_op")


def test_monitored_decorator():
    @monitored("deco_op")
    def f(x):
        return x * 2

    assert f(21) == 42
    assert Dashboard.get("deco_op").count == 1


def test_display_contains_all():
    Dashboard.get("a").add(1.0)
    Dashboard.get("b").add(2.0)
    report = Dashboard.display()
    assert "[a]" in report and "[b]" in report


def test_log_file_sink(tmp_path):
    path = str(tmp_path / "mv.log")
    log.set_log_file(path)
    try:
        log.info("sink check %d", 42)
    finally:
        log.set_log_file(None)
    content = open(path).read()
    assert "sink check 42" in content and "[INFO]" in content


def test_log_levels_filter(capsys):
    from multiverso_tpu.utils.log import LogLevel
    log.set_level(LogLevel.ERROR)
    try:
        log.info("hidden message")
        log.error("shown message")
    finally:
        log.set_level(LogLevel.INFO)
    out = capsys.readouterr()
    assert "hidden message" not in out.out
    assert "shown message" in out.err


def test_span_annotates_a_region_and_counts_it():
    """The one ``jax.profiler`` wrapper (``utils/profiler.annotate`` was a
    second): a named region under ``TraceAnnotation``, counted once."""
    from multiverso_tpu.telemetry import get_registry, span
    with span("annotated_region"):
        pass
    assert get_registry().histogram("span.annotated_region").count == 1
