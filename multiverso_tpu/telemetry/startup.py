"""Start-up timeline: where the seconds from the process's start to its
first completed unit of work went, and every program jax compiled or fetched
on the way (docs/OBSERVABILITY.md "Start-up timeline and compile records").

Nothing here is a second tracer. The regions are plain :func:`span`\\ s where
the work happens (``startup.backend``, ``*.build``, ``table.host_init``,
...); this module adds what no ``with`` block can bracket:

* two events built from stamps, ``startup.pre_import`` (the kernel's record
  of the process's start to the first line of the package's import) and
  ``startup.import`` (first line to last), recorded by :func:`imported`;
* one ``compile.program`` record for each program jax traces, lowers and
  compiles or fetches from its persistent cache, from the three
  ``jax.monitoring`` listeners registered when this module is imported;
* :func:`mark_ready`, which a steady entry point calls when its first unit
  has completed: it copies the ring once, and :func:`report` partitions
  ``[process start, ready]`` on the importing thread into self times;
* :func:`watch_transfers`, one thread that stamps when the arrays a
  ``ServerStore`` put on the device had all landed.

After :func:`mark_ready` a steady step pays the read of :data:`ready`; the
listeners run only when jax compiles.
"""

from __future__ import annotations

import collections
import os
import threading
import time
import weakref
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from jax import monitoring

from multiverso_tpu.telemetry.metrics import get_registry
from multiverso_tpu.telemetry.spans import _event, get_trace_buffer

__all__ = ["ready", "imported", "mark_ready", "report", "watch_transfers",
           "reset"]

#: Whether the first unit has completed. A steady entry point reads this
#: and nothing else of the timeline: ``if not startup.ready: ...``.
ready = False

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_SAVED = "/jax/compilation_cache/compile_time_saved_sec"

#: The parts of ``[process start, ready]``, in the order a second is booked
#: where several regions cover it: the first that does takes it (a compile
#: inside a build is ``compile``, a table inside it ``tables``, what is left
#: of the build ``build``). ``outside_program`` is what none covers.
PARTS = ("compile", "tables", "backend", "first_unit", "build", "import",
         "before_program", "other_program")
_STAGE_HISTOGRAMS = {"trace": "span.compile.trace",
                     "lower": "span.compile.lower",
                     "backend": "span.compile.backend",
                     "fetch": "span.compile.fetch"}
_TABLE_SPANS = ("table.host_init", "table.device_put")
_OWN_EVENTS = ("compile.program", "startup.pre_import", "startup.import")
#: Compile records kept from before ready, and from after it.
_KEEP_BEFORE, _KEEP_AFTER = 4096, 16
#: Traces a thread holds until its next lowering picks its own among them.
_KEEP_TRACES = 512
_POLL_S = 0.05
_UNDER_TRIES = 3
#: A ring event's start is its exit's wall clock less a monotonic duration,
#: a record's is jax's own ``time.time()``: containment allows them this.
_CLOCK_SLACK_S = 1e-3


def _process_start() -> Tuple[float, str]:
    """Wall-clock time of the process's start by the kernel's record:
    ``/proc/self/stat`` field 22 (ticks after boot) against the boot clock
    now. (``/proc/stat``'s ``btime`` would do for the second operand but
    counts whole seconds.) Where that cannot be read, now: :func:`imported`
    moves it to the import's first line."""
    try:
        with open("/proc/self/stat") as f:
            stat = f.read()
        ticks = int(stat[stat.rindex(")") + 2:].split()[19])
        age = time.clock_gettime(time.CLOCK_BOOTTIME) \
            - ticks / os.sysconf("SC_CLK_TCK")
        return time.time() - max(age, 0.0), "kernel"
    except (OSError, ValueError, IndexError, AttributeError):
        return time.time(), "import"


def _tid() -> int:
    return threading.get_ident() % (1 << 31)       # a ring event's ``tid``


class _Timeline:
    """The process's one timeline. The stamps are facts of the process and
    outlive :func:`reset`; the records and the copy of the ring do not."""

    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.t_process, self.process_from = _process_start()
        self.t_import0 = self.t_import1 = None
        self.backend_ready_at_import = False
        self.main_tid = _tid()
        self.pending = threading.local()    # a thread's trace / lower / cache
        self.generation = 0                 # of the watcher's thread
        self.clear()

    def clear(self) -> None:
        self.before: collections.deque = collections.deque(
            maxlen=_KEEP_BEFORE)
        self.after: collections.deque = collections.deque(maxlen=_KEEP_AFTER)
        self.t_ready: Optional[float] = None
        self.unit: Tuple[str, ...] = ()
        self.events: List[Dict] = []
        self.report: Optional[Dict] = None
        self.watched: List[weakref.ref] = []
        self.watching = False
        self.t_landed: Optional[float] = None
        self.generation += 1


_tl = _Timeline()


# -- the package's import -----------------------------------------------------
def imported(t_import0: float, backend_ready: bool) -> None:
    """Called on the last line of the package's import with what its first
    line stamped: records ``startup.pre_import`` and ``startup.import``."""
    now = time.time()
    tl = _tl
    tl.t_import0, tl.t_import1 = t_import0, now
    tl.backend_ready_at_import = bool(backend_ready)
    tl.main_tid = _tid()
    if tl.t_process > t_import0:
        tl.t_process, tl.process_from = t_import0, "import"
    pre_ms, import_ms = (t_import0 - tl.t_process) * 1e3, \
        (now - t_import0) * 1e3
    ring, registry = get_trace_buffer(), get_registry()
    ring.record(_event(
        "startup.pre_import", tl.t_process * 1e6, pre_ms,
        {"from": tl.process_from, "backend_ready": bool(backend_ready)},
        None))
    ring.record(_event("startup.import", t_import0 * 1e6, import_ms, {},
                       None))
    registry.histogram("span.startup.pre_import").observe(pre_ms)
    registry.histogram("span.startup.import").observe(import_ms)


# -- compile records ----------------------------------------------------------
def _alnum(name: str) -> str:
    return "".join(ch for ch in name if ch.isalnum())


def _on_event(event: str, **kw) -> None:
    if event == _CACHE_ASKED:
        _tl.pending.cache = "miss"      # until a hit says otherwise
    elif event == _CACHE_HIT:
        _tl.pending.cache = "hit"


def _on_duration(event: str, duration: float, **kw) -> None:
    if event == _CACHE_SAVED:
        _tl.pending.saved_s = float(duration)


def _on_time_span(event: str, t0: float, t1: float, fun_name: str = "",
                  **kw) -> None:
    pending = _tl.pending
    if event == _TRACE:
        traces = getattr(pending, "traces", None)
        if traces is None:
            traces = pending.traces = collections.deque(maxlen=_KEEP_TRACES)
        traces.append((str(fun_name), t0, t1))
    elif event == _LOWER:
        # The program's own trace is the last to END before its lowering
        # starts: the jitted functions it calls end theirs inside it, and
        # what the lowering rules trace lies inside the lowering.
        module, trace = _alnum(str(fun_name)), None
        for name, a, b in getattr(pending, "traces", ()):
            if b <= t0 + _CLOCK_SLACK_S and module.endswith(_alnum(name)) \
                    and (trace is None or b > trace[1]):
                trace = (a, b)
        if getattr(pending, "traces", None):
            pending.traces.clear()
        pending.lower = (str(fun_name), t0, t1, trace)
    elif event == _BACKEND:
        lower = getattr(pending, "lower", None)
        if lower is not None and lower[0] != str(fun_name):
            lower = None
        cache = getattr(pending, "cache", None) or "off"
        saved_s = getattr(pending, "saved_s", None)
        pending.lower = pending.cache = pending.saved_s = None
        _record(str(fun_name), lower, (t0, t1), cache, saved_s)


def _record(program: str, lower, backend: Tuple[float, float], cache: str,
            saved_s: Optional[float]) -> None:
    """One program compiled or fetched: the record, its ring event, four
    histograms, three counters."""
    stages = {"backend": backend}
    if lower is not None:
        stages["lower"] = lower[1:3]
        if lower[3] is not None:
            stages["trace"] = lower[3]
    secs = {k: max(b - a, 0.0) for k, (a, b) in stages.items()}
    hit = cache == "hit"
    rec = {"program": program, "cache": cache,
           "trace_s": secs.get("trace", 0.0),
           "lower_s": secs.get("lower", 0.0),
           "backend_s": 0.0 if hit else secs["backend"],
           "fetch_s": secs["backend"] if hit else 0.0,
           "t0": min(a for a, _ in stages.values()), "t1": backend[1],
           "tid": _tid(), "under": None, "tries": 0,
           "segments": sorted(stages.values())}
    if hit and saved_s is not None:
        rec["saved_s"] = saved_s
    registry = get_registry()
    if hit:
        secs["fetch"] = secs.pop("backend")
    for stage, s in secs.items():
        registry.histogram(_STAGE_HISTOGRAMS[stage]).observe(s * 1e3)
    registry.counter("compile.programs").inc()
    if cache != "off":
        registry.counter("compile.cache_hits" if hit
                         else "compile.cache_misses").inc()
    attrs = {k: rec[k] for k in ("program", "cache", "trace_s", "lower_s",
                                 "backend_s", "fetch_s")}
    get_trace_buffer().record(_event(
        "compile.program", rec["t0"] * 1e6, (rec["t1"] - rec["t0"]) * 1e3,
        attrs, None))
    if ready:
        registry.counter("compile.after_ready").inc()
        _tl.after.append(rec)
    else:
        _tl.before.append(rec)


monitoring.register_event_listener(_on_event)
monitoring.register_event_duration_secs_listener(_on_duration)
monitoring.register_event_time_span_listener(_on_time_span)


# -- transfers landed ----------------------------------------------------------
def _landed(ref: weakref.ref) -> bool:
    array = ref()
    if array is None:
        return True
    try:
        return bool(array.is_deleted() or array.is_ready())
    except Exception:  # noqa: BLE001 - a buffer gone under the poll landed
        return True


def watch_transfers(arrays: Iterable) -> None:
    """Hand the timeline the arrays a store just put on the device. One
    daemon thread polls ``is_ready()`` (an array freed, deleted or donated
    counts as landed; the arrays are held weakly) until all have landed or
    the process is ready, and stamps the last landing. No-op once ready."""
    if ready:
        return
    tl = _tl
    with tl.lock:
        tl.watched.extend(weakref.ref(a) for a in arrays)
        if tl.watching:
            return
        tl.watching = True
        generation = tl.generation
    threading.Thread(target=_watch, args=(generation,), daemon=True,
                     name="startup-transfers").start()


def _watch(generation: int) -> None:
    tl = _tl
    while True:
        with tl.lock:
            if tl.generation != generation:
                return
            if ready or not tl.watched:
                tl.watching = False
                return
            refs = list(tl.watched)
        landed = {id(r) for r in refs if _landed(r)}
        if landed:
            now = time.time()
            with tl.lock:
                if tl.generation != generation:
                    return
                tl.watched = [r for r in tl.watched if id(r) not in landed]
                tl.t_landed = now
            continue        # another put may have come meanwhile
        time.sleep(_POLL_S)


# -- ready, and the partition ----------------------------------------------------
def mark_ready(unit: Sequence[str] = ()) -> None:
    """The first unit through a steady entry point has completed. ``unit``
    names the spans it is made of (the caller's own: ``w2v.device_block``;
    ``<prefix>.pull`` / ``.compute`` / ``.push``; ``serve.warmup``): the
    first of them on the importing thread opens ``first_unit``, which runs
    to now. Idempotent; everything after the first call is a no-op."""
    global ready
    tl = _tl
    with tl.lock:
        if ready:
            return
        now = time.time()
        tl.t_ready, tl.unit = now, tuple(unit)
        tl.events = get_trace_buffer().events()
        ready = True
    get_registry().gauge("startup.ready_s").set(now - tl.t_process)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _partition(regions: Dict[str, List[Tuple[float, float]]], t0: float,
               t1: float) -> Dict[str, float]:
    """Self times: each part gets the seconds of ``[t0, t1]`` that its
    regions cover and no earlier part of :data:`PARTS` does."""
    parts, covered, booked = {}, [], 0.0
    for part in PARTS:
        clipped = [(max(a, t0), min(b, t1)) for a, b in regions.get(part, ())
                   if min(b, t1) > max(a, t0)]
        covered = _union(covered + clipped)
        total = sum(b - a for a, b in covered)
        parts[part] = total - booked
        booked = total
    parts["outside_program"] = (t1 - t0) - booked
    return parts


def _spans(events: Iterable[Dict]) -> Dict[int, List[Dict]]:
    """The ring's span events by thread, the timeline's own left out."""
    by_tid: Dict[int, List[Dict]] = {}
    for ev in events:
        if ev.get("ph") == "X" and ev["name"] not in _OWN_EVENTS:
            by_tid.setdefault(ev.get("tid"), []).append(ev)
    return by_tid


def _public(rec: Dict, spans: Dict[int, List[Dict]]) -> Dict:
    """The record as the report gives it. ``under``, the innermost span
    that was open on the record's thread, is looked up by containment on
    the first :data:`_UNDER_TRIES` reads (the enclosing span's event closes
    after the record; what is found is kept, because the ring evicts, and
    a record under no span stops costing a walk of the ring a snapshot)."""
    if rec["under"] is None and rec["tries"] < _UNDER_TRIES:
        rec["tries"] += 1
        lo, hi = (rec["t0"] + _CLOCK_SLACK_S) * 1e6, \
            (rec["t1"] - _CLOCK_SLACK_S) * 1e6
        inside = [ev for ev in spans.get(rec["tid"], ())
                  if ev["ts"] <= lo and ev["ts"] + ev["dur"] >= hi]
        if inside:
            rec["under"] = min(inside, key=lambda ev: ev["dur"])["name"]
    return {k: v for k, v in rec.items() if k not in ("segments", "tries")}


def report() -> Dict:
    """The timeline as one JSON-able object (the snapshot's ``startup``
    key). Always: ``ready``, the compile records so far, what came after
    ready. Once ready also ``ready_s``, ``parts`` (they sum to ``ready_s``)
    and the numbers beside them."""
    tl = _tl
    with tl.lock:
        is_ready, out = tl.t_ready is not None, tl.report
        before, after = list(tl.before), list(tl.after)
        pending = len(tl.watched)
    if out is None:
        out = _build(tl, before, tl.events if is_ready
                     else get_trace_buffer().events(), is_ready)
        if is_ready:
            tl.report = out         # nothing of it changes after ready
    ring = _spans(get_trace_buffer().events()) if any(
        r["under"] is None and r["tries"] < _UNDER_TRIES for r in after) \
        else {}
    return dict(out, transfers_pending=pending, after_ready={
        "count": int(get_registry().counter("compile.after_ready").value),
        "last": [_public(r, ring) for r in after]})


def _build(tl: _Timeline, records: List[Dict], events,
           is_ready: bool) -> Dict:
    spans = _spans(events)
    out = {"ready": is_ready, "process_start_from": tl.process_from,
           "backend_ready_at_import": tl.backend_ready_at_import,
           "compiles": [_public(r, spans) for r in records],
           "programs": len(records),
           "cache_hits": sum(r["cache"] == "hit" for r in records),
           "cache_misses": sum(r["cache"] == "miss" for r in records)}
    if not is_ready:
        return out
    t0, t1, main = tl.t_process, tl.t_ready, tl.main_tid
    regions: Dict[str, List[Tuple[float, float]]] = {p: [] for p in PARTS}
    if tl.t_import0 is not None:
        regions["before_program"].append((t0, tl.t_import0))
        regions["import"].append((tl.t_import0, tl.t_import1))
    unit_t0 = None
    for ev in spans.get(main, ()):
        name, a = ev["name"], ev["ts"] / 1e6
        b = a + ev["dur"] / 1e6
        if name in tl.unit:
            unit_t0 = a if unit_t0 is None else min(unit_t0, a)
        elif name in _TABLE_SPANS:
            regions["tables"].append((a, b))
        elif name == "startup.backend":
            regions["backend"].append((a, b))
        elif name.endswith(".build"):
            regions["build"].append((a, b))
        else:
            regions["other_program"].append((a, b))
    if unit_t0 is not None:
        regions["first_unit"].append((unit_t0, t1))
    other_s = 0.0
    for rec in records:
        if rec["tid"] == main:
            regions["compile"].extend(rec["segments"])
        else:
            other_s += sum(b - a for a, b in rec["segments"])
    out.update(
        ready_s=t1 - t0, parts=_partition(regions, t0, t1),
        compile_other_threads_s=other_s,
        transfers_landed_s=None if tl.t_landed is None
        else tl.t_landed - t0)
    return out


def reset() -> None:
    """Test isolation (``reset_telemetry``): drop the records, the copy of
    the ring and the watcher's arrays; not ready again. The stamps of the
    process and of the import stay, and so do the listeners."""
    global ready
    with _tl.lock:
        _tl.clear()
        ready = False
