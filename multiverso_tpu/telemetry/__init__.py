"""Telemetry subsystem: histograms, counters, gauges, spans, exporters.

The metrics layer behind the Dashboard (``utils/dashboard.py`` monitors
are histogram-backed through this package) plus cross-actor tracing:

* :func:`histogram` / :func:`counter` / :func:`gauge` — named metrics in
  the process-global registry (``metrics.py``);
* :func:`span` — host-side begin/end regions exported as Chrome
  trace-event JSON, nested under ``jax.profiler.TraceAnnotation``
  (``spans.py``);
* :func:`register_program` / :func:`program_scopes` — a device program's
  ``jax.named_scope`` parts read back from its compiled text, for the
  reader of a device trace (``device_scopes.py``);
* ``startup.mark_ready`` / ``startup.report`` — the start-up timeline:
  the process's start to its first completed unit by its parts, and one
  ``compile.program`` record for each program jax compiled or fetched
  (``startup.py``);
* :func:`start_exporter` / ``-telemetry_dir`` — periodic JSON snapshot +
  trace export, with a multi-worker merge tool (``export.py``,
  ``scripts/telemetry_report.py``).

See docs/OBSERVABILITY.md for the metric catalog and schemas.
"""

from multiverso_tpu.telemetry.alerts import (AlertEngine, AlertManager,
                                             AlertRule, BurnRateRule,
                                             ImbalanceRule,
                                             SaturationRule, StragglerRule,
                                             ThresholdRule,
                                             active_alert_summaries,
                                             default_serving_rules,
                                             maybe_start_observability_from_flags,
                                             start_alert_engine,
                                             stop_alert_engine)
from multiverso_tpu.telemetry.context import (TraceContext, activate,
                                              child_of, current_context,
                                              maybe_new_root, new_root)
from multiverso_tpu.telemetry.critical_path import (CONCURRENT_PHASES,
                                                    PHASES, SPAN_PHASES,
                                                    ExemplarReservoir,
                                                    all_exemplar_payloads,
                                                    analyze_critical_paths,
                                                    decompose,
                                                    exemplar_payload,
                                                    exemplars_enabled,
                                                    get_reservoir,
                                                    phase_for_span,
                                                    reset_critical_path,
                                                    set_exemplars_enabled)
from multiverso_tpu.telemetry.device_scopes import (program_scopes,
                                                    register_program)
from multiverso_tpu.telemetry.profile import (PROFILE_SCHEMA, FoldedStacks,
                                              SamplingProfiler,
                                              get_profiler, merge_profiles,
                                              plane_for_thread,
                                              profile_state, reset_profile,
                                              start_profiler, stop_profiler)
from multiverso_tpu.telemetry.roofline import (BOUND_CODES, BOUNDS,
                                               classify, plane_reading,
                                               reset_roofline, verdict)
from multiverso_tpu.telemetry.flight import (POSTMORTEM_SCHEMA,
                                             FlightRecorder,
                                             WatchdogHandle,
                                             build_postmortem,
                                             dump_postmortem,
                                             flight_recorder,
                                             install_crash_handlers,
                                             start_watchdog, stop_watchdog,
                                             validate_postmortem,
                                             watchdog_handles,
                                             watchdog_register,
                                             watchdog_scope)
from multiverso_tpu.telemetry.sketch import (CountMinSketch, SketchHub,
                                             SpaceSaving, TrafficSketch,
                                             coverage_at, get_sketch_hub,
                                             load_ratio, record_keys,
                                             set_sketch_enabled)
from multiverso_tpu.telemetry.timeseries import TimeseriesStore
from multiverso_tpu.telemetry.export import (SNAPSHOT_SCHEMA,
                                             TelemetryExporter,
                                             build_chrome_trace,
                                             export_chrome_trace,
                                             maybe_start_exporter_from_flags,
                                             merge_traces, metrics_snapshot,
                                             reset_telemetry, start_exporter,
                                             stitch_traces, stop_exporter,
                                             trace_index,
                                             validate_chrome_trace,
                                             validate_snapshot)
from multiverso_tpu.telemetry.metrics import (Counter, Gauge, Histogram,
                                              MetricsRegistry, counter,
                                              gauge, get_registry, histogram)
from multiverso_tpu.telemetry.spans import (TraceBuffer, current_identity,
                                            emit_span, get_trace_buffer,
                                            phase, span)

__all__ = [
    "SNAPSHOT_SCHEMA", "TelemetryExporter", "build_chrome_trace",
    "export_chrome_trace", "maybe_start_exporter_from_flags",
    "merge_traces", "metrics_snapshot", "reset_telemetry", "start_exporter",
    "stitch_traces", "stop_exporter", "trace_index",
    "validate_chrome_trace", "validate_snapshot",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "counter", "gauge",
    "get_registry", "histogram",
    "TraceBuffer", "current_identity", "emit_span", "get_trace_buffer",
    "phase", "span", "program_scopes", "register_program",
    "TraceContext", "activate", "child_of", "current_context",
    "maybe_new_root", "new_root",
    "AlertEngine", "AlertManager", "AlertRule", "BurnRateRule",
    "ImbalanceRule", "SaturationRule", "StragglerRule", "ThresholdRule",
    "CountMinSketch", "SketchHub", "SpaceSaving", "TrafficSketch",
    "coverage_at", "get_sketch_hub", "load_ratio", "record_keys",
    "set_sketch_enabled",
    "active_alert_summaries", "default_serving_rules",
    "maybe_start_observability_from_flags", "start_alert_engine",
    "stop_alert_engine",
    "POSTMORTEM_SCHEMA", "FlightRecorder", "WatchdogHandle",
    "build_postmortem", "dump_postmortem", "flight_recorder",
    "install_crash_handlers", "start_watchdog", "stop_watchdog",
    "validate_postmortem", "watchdog_handles", "watchdog_register",
    "watchdog_scope", "TimeseriesStore",
    "CONCURRENT_PHASES", "PHASES", "SPAN_PHASES", "ExemplarReservoir",
    "all_exemplar_payloads", "analyze_critical_paths", "decompose",
    "exemplar_payload", "exemplars_enabled", "get_reservoir",
    "phase_for_span", "reset_critical_path", "set_exemplars_enabled",
    "PROFILE_SCHEMA", "FoldedStacks", "SamplingProfiler", "get_profiler",
    "merge_profiles", "plane_for_thread", "profile_state", "reset_profile",
    "start_profiler", "stop_profiler",
    "BOUND_CODES", "BOUNDS", "classify", "plane_reading", "reset_roofline",
    "verdict",
]
