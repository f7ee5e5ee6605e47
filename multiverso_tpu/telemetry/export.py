"""Snapshot/trace export: periodic JSON snapshots + Chrome trace files.

File layout under ``telemetry_dir`` (one set per PROCESS — ranks of a
multi-worker run share the directory and never collide because every
filename carries the pid):

* ``metrics-<pid>-<seq>.json`` — one metrics snapshot per export cycle
  (schema below); the final one is written at exporter stop, so even a
  run shorter than the export interval leaves >= 1 snapshot.
* ``trace-<pid>.json`` — Chrome trace-event JSON
  (``chrome://tracing`` / Perfetto loadable), REWRITTEN atomically each
  cycle so a crashed run keeps its latest trace.

Snapshot schema (``SNAPSHOT_SCHEMA``)::

    {"schema": ".../v1", "pid": int, "rank": int, "seq": int,
     "time_unix": float,
     "histograms": {name: {count, sum_ms, min_ms, max_ms, mean_ms,
                           p50, p95, p99,
                           bucket_lo_ms, bucket_base, bucket_counts}},
     "gauges":     {name: {last, min, max, mean, samples}},
     "counters":   {name: {value}}}

``merge_traces`` concatenates per-process trace files into one multi-track
trace (timestamps are epoch microseconds, so tracks align without clock
surgery); ``scripts/telemetry_report.py`` wraps it as a CLI.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import Dict, Iterable, List, Optional

from multiverso_tpu.telemetry.metrics import get_registry
from multiverso_tpu.telemetry.spans import (TraceBuffer, _reset_identity_cache,
                                            current_identity,
                                            get_trace_buffer)

__all__ = ["SNAPSHOT_SCHEMA", "metrics_snapshot", "build_chrome_trace",
           "export_chrome_trace", "merge_traces", "stitch_traces",
           "trace_index", "validate_chrome_trace",
           "validate_snapshot", "TelemetryExporter", "start_exporter",
           "stop_exporter", "maybe_start_exporter_from_flags",
           "reset_telemetry"]

SNAPSHOT_SCHEMA = "multiverso_tpu.telemetry.snapshot/v1"


_tmp_counter = itertools.count()


def _atomic_write_json(path: str, payload: Dict) -> None:
    # Counter-qualified tmp name: two threads writing the SAME target
    # (exporter loop vs stop) never interleave into one tmp file.
    tmp = f"{path}.tmp.{os.getpid()}.{next(_tmp_counter)}"
    with open(tmp, "w") as f:
        # dumps-then-write, NOT json.dump(f): dump() always takes the
        # pure-Python chunked iterencode path (_one_shot=False), which
        # for a full span ring is ~half a million generator frames —
        # each one a GIL yield point, so concurrent span emitters
        # convoy a single snapshot write into tens of seconds. The
        # one-shot C encoder serializes the same payload in one call.
        f.write(json.dumps(payload))
    os.replace(tmp, path)


def metrics_snapshot(buckets: bool = True, seq: int = 0) -> Dict:
    """One structured snapshot of every registered metric + identity.
    When the alert engine runs, the snapshot additionally embeds the
    active-alert summary and the trailing timeseries windows (additive
    sections — ``validate_snapshot`` ignores keys it does not know)."""
    ident = current_identity()
    # Publish the span ring's cumulative eviction tally before the
    # registry read so this snapshot carries it (the ring itself counts
    # lock-locally; see TraceBuffer.record).
    get_registry().gauge("telemetry.spans.dropped").set(
        get_trace_buffer().dropped)
    snap = get_registry().snapshot(buckets=buckets)
    snap["schema"] = SNAPSHOT_SCHEMA
    snap["pid"] = ident["pid"]
    snap["rank"] = ident.get("rank", 0)
    snap["seq"] = seq
    snap["time_unix"] = time.time()
    try:
        from multiverso_tpu.telemetry import alerts as _alerts
        eng = _alerts.engine()
        if eng is not None:
            snap["alerts"] = eng.manager.snapshot()
            snap["timeseries"] = eng.store.snapshot(last_n=30)
    except Exception:  # noqa: BLE001 - the alert embed is attribution;
        pass           # a broken engine must not cost the base snapshot
    try:
        from multiverso_tpu.telemetry.sketch import get_sketch_hub
        hub = get_sketch_hub()
        hub.flush()     # unticked processes still export fresh sketches
        if hub.surfaces():
            snap["sketches"] = hub.snapshot()
    except Exception:  # noqa: BLE001 - additive section, same contract
        pass
    try:
        from multiverso_tpu.telemetry.critical_path import \
            all_exemplar_payloads
        ex = all_exemplar_payloads()
        if ex:
            snap["exemplars"] = ex
    except Exception:  # noqa: BLE001 - additive section, same contract
        pass
    try:
        from multiverso_tpu.telemetry import startup
        snap["startup"] = startup.report()
    except Exception:  # noqa: BLE001 - additive section, same contract
        pass
    try:
        from multiverso_tpu.telemetry.profile import profile_state
        prof = profile_state()
        if prof is not None and prof.get("samples"):
            snap["profile"] = prof
    except Exception:  # noqa: BLE001 - additive section, same contract
        pass
    return snap


def build_chrome_trace() -> Dict:
    """Chrome trace-event JSON object for THIS process's span buffer."""
    ident = current_identity()
    buf = get_trace_buffer()
    events = buf.events()
    pids = sorted({e["pid"] for e in events}) or [ident["pid"]]
    meta = [{"ph": "M", "name": "process_name", "pid": p, "tid": 0,
             "args": {"name": f"multiverso_tpu rank={ident.get('rank', 0)} "
                              f"pid={p}"}}
            for p in pids]
    return {"traceEvents": meta + events,
            "displayTimeUnit": "ms",
            "otherData": {"schema": "chrome-trace-events/json",
                          "dropped_events": buf.dropped}}


def export_chrome_trace(path: str) -> Dict:
    trace = build_chrome_trace()
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    _atomic_write_json(path, trace)
    return trace


def merge_traces(paths: Iterable[str], out_path: Optional[str] = None
                 ) -> Dict:
    """Merge per-process Chrome traces (multi-worker run) into one.

    Events keep their pids (one track group per process); duplicate
    process_name metadata collapses to one entry per pid. Timestamps are
    epoch microseconds in every exporter-written file, so no rebasing is
    needed."""
    events: List[Dict] = []
    meta_by_pid: Dict[int, Dict] = {}
    dropped = 0
    for path in sorted(paths):
        with open(path) as f:
            trace = json.load(f)
        dropped += int(trace.get("otherData", {})
                       .get("dropped_events", 0) or 0)
        for ev in trace.get("traceEvents", []):
            if ev.get("ph") == "M" and ev.get("name") == "process_name":
                meta_by_pid.setdefault(int(ev.get("pid", 0)), ev)
            else:
                events.append(ev)
    events.sort(key=lambda e: e.get("ts", 0))
    merged = {"traceEvents": list(meta_by_pid.values()) + events,
              "displayTimeUnit": "ms",
              "otherData": {"schema": "chrome-trace-events/json",
                            "dropped_events": dropped}}
    if out_path:
        _atomic_write_json(out_path, merged)
    return merged


# ---------------------------------------------------------------------------
# Cross-process trace stitching (distributed tracing; docs/OBSERVABILITY.md
# "Distributed tracing"). Span events carry args.trace/span/parent from
# telemetry/context.py; stitching groups them by trace id and synthesizes
# Chrome FLOW events (ph "s"/"f") for every parent->child edge that crosses
# a process boundary, so Perfetto draws the request's hop arrows.
# ---------------------------------------------------------------------------
def _span_events(traces: Iterable[Dict]) -> List[Dict]:
    out = []
    for trace in traces:
        for ev in trace.get("traceEvents", []):
            if ev.get("ph") == "X" and \
                    isinstance(ev.get("args"), dict) and \
                    ev["args"].get("trace"):
                out.append(ev)
    return out


def trace_index(events: Iterable[Dict]) -> Dict[str, Dict]:
    """Per-trace summary over span events: span/pid counts, root, total
    duration, and whether every non-root parent link resolves — the
    "correctly parented" check the smoke asserts."""
    by_trace: Dict[str, List[Dict]] = {}
    for ev in events:
        by_trace.setdefault(ev["args"]["trace"], []).append(ev)
    out: Dict[str, Dict] = {}
    for tid, evs in by_trace.items():
        span_ids = {e["args"].get("span") for e in evs}
        roots = [e for e in evs if not e["args"].get("parent")]
        orphans = [e for e in evs
                   if e["args"].get("parent")
                   and e["args"]["parent"] not in span_ids]
        root = min(roots, key=lambda e: e.get("ts", 0)) if roots else None
        out[tid] = {
            "n_spans": len(evs),
            "pids": sorted({int(e.get("pid", 0)) for e in evs}),
            "names": sorted({e.get("name", "") for e in evs}),
            "root_name": root.get("name") if root else None,
            "dur_us": int(root.get("dur", 0)) if root else
            max((int(e.get("dur", 0)) for e in evs), default=0),
            "n_roots": len(roots),
            "n_orphans": len(orphans),
            "parented_ok": bool(roots) and not orphans,
        }
    return out


def stitch_traces(paths: Iterable[str], trace_id: Optional[str] = None,
                  out_path: Optional[str] = None) -> Dict:
    """Merge per-process trace files into ONE trace keyed by trace id:
    keeps only span events that carry a trace context (optionally just
    ``trace_id``), sorts them on the shared epoch time axis, and adds a
    flow-event pair for every parent->child edge whose endpoints live in
    different processes. The result answers "where did this request
    spend its time" across client, router, and replicas in one Perfetto
    view."""
    traces = []
    for path in sorted(paths):
        with open(path) as f:
            traces.append(json.load(f))
    events = _span_events(traces)
    if trace_id is not None:
        events = [e for e in events if e["args"]["trace"] == trace_id]
    events.sort(key=lambda e: e.get("ts", 0))
    by_span: Dict[tuple, Dict] = {}
    for ev in events:
        by_span[(ev["args"]["trace"], ev["args"].get("span"))] = ev
    flows: List[Dict] = []
    flow_seq = 0
    for ev in events:
        parent_span = ev["args"].get("parent")
        if not parent_span:
            continue
        parent = by_span.get((ev["args"]["trace"], parent_span))
        if parent is None or parent.get("pid") == ev.get("pid"):
            continue
        flow_seq += 1
        common = {"cat": "trace_flow", "name": "hop", "id": flow_seq}
        flows.append({**common, "ph": "s", "ts": parent.get("ts", 0),
                      "pid": parent.get("pid", 0),
                      "tid": parent.get("tid", 0)})
        flows.append({**common, "ph": "f", "bp": "e",
                      "ts": max(ev.get("ts", 0), parent.get("ts", 0)),
                      "pid": ev.get("pid", 0), "tid": ev.get("tid", 0)})
    pids = sorted({int(e.get("pid", 0)) for e in events})
    meta = [{"ph": "M", "name": "process_name", "pid": p, "tid": 0,
             "args": {"name": f"multiverso_tpu pid={p}"}} for p in pids]
    stitched = {"traceEvents": meta + events + flows,
                "displayTimeUnit": "ms",
                "otherData": {"schema": "chrome-trace-events/json",
                              "stitched_by": "trace_id",
                              "n_traces": len(trace_index(events))}}
    if out_path:
        _atomic_write_json(out_path, stitched)
    return stitched


def validate_chrome_trace(trace: Dict) -> None:
    """Raise ``ValueError`` unless ``trace`` is loadable by
    chrome://tracing / Perfetto (JSON object format). Shared by the schema
    unit test and the end-to-end smoke so they cannot drift apart."""
    if not isinstance(trace, dict):
        raise ValueError("trace must be a JSON object")
    events = trace.get("traceEvents")
    if not isinstance(events, list):
        raise ValueError("trace.traceEvents must be a list")
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            raise ValueError(f"traceEvents[{i}] is not an object")
        ph = ev.get("ph")
        if not isinstance(ph, str) or not ph:
            raise ValueError(f"traceEvents[{i}] missing 'ph'")
        if not isinstance(ev.get("pid"), int):
            raise ValueError(f"traceEvents[{i}] missing integer 'pid'")
        if ph == "M":
            if not isinstance(ev.get("name"), str):
                raise ValueError(f"traceEvents[{i}] metadata missing name")
            continue
        if ph == "X":
            if not isinstance(ev.get("name"), str) or not ev["name"]:
                raise ValueError(f"traceEvents[{i}] missing 'name'")
            if not isinstance(ev.get("tid"), int):
                raise ValueError(f"traceEvents[{i}] missing integer 'tid'")
            ts, dur = ev.get("ts"), ev.get("dur")
            if not isinstance(ts, (int, float)) or ts < 0:
                raise ValueError(f"traceEvents[{i}] bad 'ts' {ts!r}")
            if not isinstance(dur, (int, float)) or dur < 0:
                raise ValueError(f"traceEvents[{i}] bad 'dur' {dur!r}")
        elif ph in ("s", "f"):
            # Flow events (stitched cross-process hops): need an id and
            # a timestamp; "f" additionally binds to the enclosing slice.
            if "id" not in ev:
                raise ValueError(f"traceEvents[{i}] flow event missing id")
            if not isinstance(ev.get("ts"), (int, float)):
                raise ValueError(f"traceEvents[{i}] flow event missing ts")
        else:
            raise ValueError(f"traceEvents[{i}] unexpected phase {ph!r}")


def validate_snapshot(snap: Dict) -> None:
    """Raise ``ValueError`` unless ``snap`` matches ``SNAPSHOT_SCHEMA``."""
    if snap.get("schema") != SNAPSHOT_SCHEMA:
        raise ValueError(f"bad snapshot schema {snap.get('schema')!r}")
    for key in ("pid", "rank", "seq"):
        if not isinstance(snap.get(key), int):
            raise ValueError(f"snapshot missing integer '{key}'")
    for section, fields in (("histograms", ("count", "p50", "p95", "p99",
                                            "max_ms")),
                            ("gauges", ("last", "samples")),
                            ("counters", ("value",))):
        body = snap.get(section)
        if not isinstance(body, dict):
            raise ValueError(f"snapshot missing section '{section}'")
        for name, m in body.items():
            for field in fields:
                if field not in m:
                    raise ValueError(
                        f"{section}[{name!r}] missing field '{field}'")


class TelemetryExporter:
    """Background thread writing snapshots/trace every ``interval``
    seconds, plus a final write at :meth:`stop`. Keeps the newest
    ``keep_snapshots`` snapshot files per process (the trace file is a
    single atomically-rewritten path already) so a week-long run cannot
    fill the directory with dead history."""

    def __init__(self, out_dir: str, interval: float = 10.0,
                 keep_snapshots: int = 50):
        self.out_dir = out_dir
        self.interval = max(float(interval), 0.05)
        self.keep_snapshots = max(int(keep_snapshots), 1)
        self._seq = 0
        # Serializes write_once between the loop thread and stop(): the
        # join below is time-bounded, so the two may overlap on slow disks.
        self._write_lock = threading.Lock()
        self._stop = threading.Event()
        os.makedirs(out_dir, exist_ok=True)
        # Only AFTER the directory exists (the one init step that can
        # raise) is there really a consumer: widen the span ring to full
        # depth. Widening first would leave a caller that catches the
        # OSError with a 20x ring nothing ever drains.
        get_trace_buffer().set_capacity(TraceBuffer.EXPORT_CAPACITY)
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="telemetry-exporter")
        self._thread.start()

    def _loop(self) -> None:
        from multiverso_tpu.telemetry.flight import watchdog_scope
        with watchdog_scope("telemetry-exporter",
                            timeout_s=max(60.0, 6 * self.interval)) as wd:
            while not self._stop.wait(self.interval):
                wd.beat()
                try:
                    self.write_once()
                except OSError:
                    # A full/readonly disk must never kill training —
                    # but the plane counts its own failures.
                    get_registry().counter(
                        "telemetry.export.failures").inc()

    def write_once(self) -> str:
        with self._write_lock:
            t0 = time.perf_counter()
            self._seq += 1
            pid = os.getpid()
            snap = metrics_snapshot(seq=self._seq)
            path = os.path.join(self.out_dir,
                                f"metrics-{pid}-{self._seq:05d}.json")
            _atomic_write_json(path, snap)
            _atomic_write_json(
                os.path.join(self.out_dir, f"trace-{pid}.json"),
                build_chrome_trace())
            expired = self._seq - self.keep_snapshots
            if expired > 0:
                try:
                    os.remove(os.path.join(
                        self.out_dir, f"metrics-{pid}-{expired:05d}.json"))
                except OSError:
                    pass    # already pruned / never written
            # Exporter self-observability: a slow disk shows up as a
            # rising write latency BEFORE it shows up as lost snapshots.
            get_registry().histogram("telemetry.export.write_ms").observe(
                (time.perf_counter() - t0) * 1e3)
            return path

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5)
        try:
            self.write_once()   # final snapshot: short runs still export
        except OSError:
            get_registry().counter("telemetry.export.failures").inc()


_exporter: Optional[TelemetryExporter] = None
_exporter_lock = threading.Lock()


def start_exporter(out_dir: str, interval: float = 10.0
                   ) -> TelemetryExporter:
    """Idempotent per directory; restarting with a new dir stops the old
    exporter first (writing its final snapshot)."""
    global _exporter
    with _exporter_lock:
        if _exporter is not None:
            if os.path.abspath(_exporter.out_dir) == os.path.abspath(
                    out_dir):
                return _exporter
            _exporter.stop()
        _exporter = TelemetryExporter(out_dir, interval)
        return _exporter


def stop_exporter() -> None:
    global _exporter
    with _exporter_lock:
        if _exporter is not None:
            _exporter.stop()
            _exporter = None


def maybe_start_exporter_from_flags() -> bool:
    """Start the exporter when ``-telemetry_dir`` is set (apps CLI path).
    Returns whether an exporter is running."""
    from multiverso_tpu.utils.configure import get_flag
    out_dir = get_flag("telemetry_dir")
    if not out_dir:
        return False
    start_exporter(out_dir, float(get_flag("telemetry_interval")))
    return True


def reset_telemetry() -> None:
    """Test isolation: stop the exporter, alert engine and watchdog,
    drop every metric, span, and flight event."""
    from multiverso_tpu.telemetry.alerts import stop_alert_engine
    from multiverso_tpu.telemetry.critical_path import reset_critical_path
    from multiverso_tpu.telemetry.flight import reset_flight
    from multiverso_tpu.telemetry.lockwitness import reset_lockwitness
    from multiverso_tpu.telemetry.profile import reset_profile
    from multiverso_tpu.telemetry.roofline import reset_roofline
    from multiverso_tpu.telemetry.sketch import reset_sketches
    from multiverso_tpu.telemetry.startup import reset as reset_startup
    stop_alert_engine()
    reset_flight()
    stop_exporter()
    reset_sketches()
    reset_lockwitness()
    reset_profile()
    reset_critical_path()
    reset_roofline()
    reset_startup()
    get_registry().reset()
    buf = get_trace_buffer()
    buf.clear()
    buf.set_capacity(TraceBuffer.DEFAULT_CAPACITY)
    _reset_identity_cache()
