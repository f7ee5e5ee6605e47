"""Host-side span API + Chrome trace-event buffer.

``span(name, **attrs)`` records a begin/end pair as one Chrome
trace-event "complete" event (``ph: "X"``) with process/thread identity
and the framework's worker/server identity in ``args`` — and nests the
region under ``jax.profiler.TraceAnnotation`` so the same name shows up
in the XLA device trace (TensorBoard/xprof) when a profiler capture is
active. Timestamps are wall-clock microseconds (Unix epoch), so traces
exported by different processes of one run merge on a common time axis
(the multi-worker merge tool just concatenates events; see
``export.merge_traces``).

Every span also feeds the ``span.<name>`` histogram in the metrics
registry, so trace-level detail and snapshot-level percentiles never
disagree about what was measured.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, List, Optional

from multiverso_tpu.telemetry import context as trace_context
from multiverso_tpu.telemetry.context import TraceContext
from multiverso_tpu.telemetry.metrics import get_registry

__all__ = ["span", "phase", "emit_span", "TraceBuffer",
           "get_trace_buffer", "current_identity"]


class TraceBuffer:
    """Bounded, thread-safe RING of Chrome trace events: when full, the
    OLDEST events are evicted (and counted as dropped) so the exported
    trace always covers the most recent window — the one an operator
    opens after a stall or crash. A long run never OOMs its own
    observability layer."""

    # Small by default: with no exporter consuming the buffer, a span-heavy
    # run must not pin hundreds of MB of event dicts. start_exporter widens
    # it to EXPORT_CAPACITY (there IS a consumer then).
    DEFAULT_CAPACITY = 10_000
    EXPORT_CAPACITY = 200_000

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        import collections
        self.capacity = capacity
        self._lock = threading.Lock()
        self._events: "collections.deque[Dict]" = \
            collections.deque(maxlen=capacity)
        self.dropped = 0

    def set_capacity(self, capacity: int) -> None:
        import collections
        with self._lock:
            if capacity == self.capacity:
                return
            self.capacity = capacity
            self._events = collections.deque(self._events, maxlen=capacity)

    def record(self, event: Dict) -> None:
        with self._lock:
            if len(self._events) == self.capacity:
                self.dropped += 1       # deque evicts the oldest
            self._events.append(event)
        # The cumulative drop tally is PUBLISHED by the samplers
        # (exporter snapshot / timeseries tick) as the
        # telemetry.spans.dropped gauge — a full ring is the PERMANENT
        # steady state of a long traced run, so a per-drop registry
        # counter here would put a global-lock acquisition on every
        # sampled span for the rest of the process lifetime.

    def events(self) -> List[Dict]:
        with self._lock:
            return list(self._events)

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self.dropped = 0


_buffer: Optional[TraceBuffer] = None
_buffer_lock = threading.Lock()


def get_trace_buffer() -> TraceBuffer:
    global _buffer
    with _buffer_lock:
        if _buffer is None:
            _buffer = TraceBuffer()
        return _buffer


_identity_cache: Optional[Dict] = None


def current_identity() -> Dict:
    """Best-effort worker/server identity for span/snapshot attribution.
    Never raises and never forces runtime bring-up — telemetry must work
    in a bare process (unit tests, scripts) exactly as in a full rank.
    Cached once the runtime has started (identity is fixed after init);
    re-probed until then so early spans pick the rank up later."""
    global _identity_cache
    if _identity_cache is not None:
        return _identity_cache
    ident: Dict = {"pid": os.getpid()}
    started = False
    try:
        from multiverso_tpu.core.zoo import Zoo
        zoo = Zoo._instance
        if zoo is not None and getattr(zoo, "started", False):
            started = True
            ident["rank"] = int(zoo.rank())
            ident["worker_id"] = int(zoo.worker_id())
            ident["server_id"] = int(zoo.server_id())
    except Exception:  # noqa: BLE001 - identity is attribution, not control
        started = False
    if "rank" not in ident:
        try:
            from multiverso_tpu.utils.configure import get_flag
            ident["rank"] = int(get_flag("rank"))
        except Exception:  # noqa: BLE001
            ident["rank"] = 0
    if started:
        _identity_cache = ident
    return ident


def _reset_identity_cache() -> None:
    global _identity_cache
    _identity_cache = None


# ``jax.profiler.TraceAnnotation``, resolved ONCE per process (not by an
# ``import jax`` in a ``try`` on every call); None when jax is not
# importable: telemetry stays usable without an accelerator runtime.
try:
    from jax.profiler import TraceAnnotation as _Annotation
except Exception:  # noqa: BLE001 - profiling sugar must never break
    _Annotation = None

# A region's entry and exit run with COLD caches (the region's own work,
# or another thread's, evicted them), where every object touched and
# every frame entered costs several times what it does in a loop: so
# the process's one registry and the threads' context stacks are bound
# once, here.
_REGISTRY = get_registry()
_ctx_tls = trace_context._tls


def _clean_attrs(attrs: Dict) -> Dict:
    return {k: (v if isinstance(v, (int, float, bool, str)) or v is None
                else str(v))
            for k, v in attrs.items()}


def _trace_args(args: Dict, ctx: TraceContext) -> Dict:
    args["trace"] = ctx.trace_hex
    args["span"] = ctx.span_hex
    if ctx.parent_id:
        args["parent"] = f"{ctx.parent_id:016x}"
    if ctx.hedge:
        args["hedge"] = 1
        args["attempt"] = ctx.hedge
    return args


def _event(name: str, ts_us: float, dur_ms: float, attrs: Dict,
           ctx: Optional[TraceContext]) -> Dict:
    """One Chrome "complete" event, wall-clock stamped (Unix epoch
    microseconds, so processes merge on one axis)."""
    ident = current_identity()
    args = _clean_attrs(attrs)
    args["rank"] = ident.get("rank", 0)
    if ctx is not None:
        _trace_args(args, ctx)
    return {
        "name": name,
        "ph": "X",
        "ts": int(ts_us),
        "dur": max(int(dur_ms * 1e3), 0),
        "pid": ident["pid"],
        "tid": threading.get_ident() % (1 << 31),
        "cat": "multiverso_tpu",
        "args": args,
    }


class span:  # noqa: N801 - used as ``with span("name"):``, a verb
    """Named host-side region ON THE THREAD THAT RUNS IT: a
    ``jax.profiler.TraceAnnotation`` (the profiler's clock: in a traced
    run the region sits on its thread's own line, beside the device's
    lines) + one observation of the ``span.<name>`` latency histogram +
    a Chrome trace event in the ring.

    The one way to time a region. A REQUEST's stages, whose begin and end
    straddle threads, are :func:`emit_span` events built from the same
    clock readings: the handle exposes ``t0`` / ``t1``
    (``time.monotonic()`` at entry / exit) so a call site reads each
    boundary once. ``attrs`` may be added to inside the body
    (``with span("x") as s: s.attrs["n"] = n``). No region sits under two
    whole-region timers.

    When a :class:`~multiverso_tpu.telemetry.context.TraceContext` is
    active on this thread, the region becomes a CHILD span of it (and the
    child is the current context for the body, so nested spans and
    wire-propagated requests parent correctly); an UNSAMPLED context
    still times the histogram but skips the trace buffer — head-based
    sampling keeps the request hot path cheap. With no active context the
    event is recorded unconditionally, with no trace fields (but see
    :class:`phase`).

    Metrics read the HISTOGRAMS, never the ring: the ring holds the last
    ``TraceBuffer.DEFAULT_CAPACITY`` events (one traced lookup window at
    sample rate 1 emits more), widened only by the exporter."""

    __slots__ = ("name", "attrs", "t0", "t1", "_ctx", "_ann")

    #: Record the ring event when NO context is active on the thread.
    RING_WITHOUT_CONTEXT = True

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs
        self.t0 = self.t1 = 0.0
        self._ctx: Optional[TraceContext] = None
        self._ann = None

    def __enter__(self) -> "span":
        stack = _ctx_tls.stack
        if stack:
            self._ctx = trace_context.child_of(stack[-1])
            stack.append(self._ctx)
        if _Annotation is not None:
            self._ann = _Annotation(self.name)
            self._ann.__enter__()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        t1 = self.t1 = time.monotonic()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        ctx = self._ctx
        if ctx is not None:
            _ctx_tls.stack.pop()
        dur_ms = (t1 - self.t0) * 1e3
        if ctx.sampled if ctx is not None else self.RING_WITHOUT_CONTEXT:
            get_trace_buffer().record(_event(
                self.name, (time.time() - t1 + self.t0) * 1e6, dur_ms,
                self.attrs, ctx))
        # Span names are literal at every call site (the documented
        # component.operation convention — cardinality lives in attrs).
        # graftlint: disable=unbounded-metric-name
        _REGISTRY.histogram("span." + self.name).observe(dur_ms)


class phase(span):  # noqa: N801 - ``with phase("thread.step"):``
    """A :class:`span` of a thread's steady cycle (a batch's form /
    dispatch / collect, a table call's dispatch / sync, an idle wait:
    hundreds to thousands a second): its ring event is recorded only
    under a SAMPLED context, never unconditionally. The profiler and the
    ``span.<name>`` histograms carry the phases; the ring keeps the
    sampled request stages and tail exemplars it exists for, and an idle
    server does not fill it."""

    __slots__ = ()
    RING_WITHOUT_CONTEXT = False


def emit_span(name: str, ctx: Optional[TraceContext], t0_mono: float,
              dur_ms: float, force: bool = False, **attrs) -> None:
    """Record a COMPLETED span from explicit timestamps — for stages whose
    begin/end straddle threads or callbacks (batcher admit-wait, device
    window, reply leg), where a ``with`` block can't wrap the region.

    ``ctx`` IS the span's identity (build one with ``child_of(parent)``);
    ``t0_mono`` is the ``time.monotonic()`` start. Skipped entirely for
    an unsampled context unless ``force`` (tail-exemplar path: shed /
    error / slow requests get recorded even when head-unsampled). The
    ``span.<name>`` histogram observes only when the event records, so
    span-derived percentiles always describe the events in the trace."""
    if ctx is None or not (ctx.sampled or force):
        return
    if force and not ctx.sampled:
        attrs["tail"] = 1
    dur_ms = max(float(dur_ms), 0.0)
    get_trace_buffer().record(_event(
        name, (time.time() - time.monotonic() + t0_mono) * 1e6, dur_ms,
        attrs, ctx))
    # Same convention as span(): literal names, cardinality in attrs.
    # graftlint: disable=unbounded-metric-name
    _REGISTRY.histogram("span." + name).observe(dur_ms)
