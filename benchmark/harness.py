"""The one harness every cell runs through. Everything that belongs to one
configuration, traffic mix, driver, per-layer metric or reference is a file
found by its name in ``BENCHMARK.json``; nothing here lists them.

A run: load the cell, check the device, let the driver set up (build the
system under test, seed the weights, warm every shape), compare with the plain
reference (not counted in ``setup_s``), measure one window (traced or not),
compare what the window produced, print every number compared beside its
limit, and print one JSON object as the last line.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
WINDOW_ANNOTATION = "bench.window"


class BenchError(Exception):
    """The run cannot give a result (no chip, unknown cell, ...)."""


# -- files found by name -------------------------------------------------------
def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(kind: str, name: str, bench_dir: str = BENCH_DIR) -> dict:
    path = os.path.join(bench_dir, kind, name + ".json")
    if not os.path.isfile(path):
        raise BenchError(f"no {kind} file {path}")
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, bench_dir: str = BENCH_DIR):
    path = os.path.join(bench_dir, kind, name + ".py")
    if not os.path.isfile(path):
        raise BenchError(f"no {kind} module {path}")
    mod_name = "bench_%s_%s" % (kind, "".join(
        ch if ch.isalnum() else "_" for ch in name))
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def find(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchError(f"no {what} named {name!r} in BENCHMARK.json")


def metrics_of_cell(entries, cell: str) -> list:
    """Metrics of a BENCHMARK.json list that this cell reports."""
    return [m for m in entries
            if "workloads" not in m or cell in m["workloads"]]


# -- jax: compile cache, device, compile counter -------------------------------------
_compiles = [0]
_listening = [False]
# a program compiled, or fetched from the persistent cache: either way it was
# not ready when it was called
_COMPILE_EVENTS = ("backend_compile_duration", "cache_retrieval_time_sec")


def configure_jax() -> None:
    """Compile cache at ``$JAX_COMPILATION_CACHE_DIR`` or
    ``<checkout>/.jax_cache`` (a fixed path: it is part of the cache's key),
    every program cached whatever its compile time, and a counter of programs
    compiled or fetched, so that the window can show it made none."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if not _listening[0]:
        from jax import monitoring

        def on_duration(event: str, duration: float, **kw) -> None:
            if event.endswith(_COMPILE_EVENTS):
                _compiles[0] += 1
        monitoring.register_event_duration_secs_listener(on_duration)
        _listening[0] = True


def compile_count() -> int:
    return _compiles[0]


def device_record(chips: int, require_chip: bool) -> dict:
    import jax
    devs = jax.devices()
    rec = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if require_chip:
        if rec["platform"] != "tpu":
            raise BenchError(f"jax found platform={rec['platform']!r}, not "
                             "a TPU; nothing measured")
        if rec["count"] < chips:
            raise BenchError(f"the cell needs {chips} chips, jax found "
                             f"{rec['count']}")
    elif rec["count"] < chips:
        raise BenchError(f"the cell needs {chips} devices, jax found "
                         f"{rec['count']}")
    return rec


def memory_peak_bytes() -> int:
    import jax
    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


# -- the comparison that decides `correct` ------------------------------------------
class Checks:
    """Every number compared, beside its limit. ``mode``: ``max`` (value may
    not pass the limit), ``min`` (may not fall under it), ``eq``."""

    def __init__(self) -> None:
        self.rows: list = []

    def add(self, name: str, value, limit, mode: str = "max") -> bool:
        value = float(value)
        ok = {"max": value <= limit, "min": value >= limit,
              "eq": value == limit}[mode]
        ok = bool(ok) and value == value        # NaN never passes
        self.rows.append({"name": name, "value": value, "limit": limit,
                          "mode": mode, "ok": ok})
        return ok

    @property
    def ok(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)

    def print(self) -> None:
        for r in self.rows:
            sign = {"max": "<=", "min": ">=", "eq": "=="}[r["mode"]]
            print(f"check {r['name']}: {r['value']!r} {sign} "
                  f"{r['limit']!r} {'ok' if r['ok'] else 'FAILED'}",
                  flush=True)


class Context:
    """What a driver and a reader are given."""

    def __init__(self, cell: dict, config: dict, traffic: dict, seed: int,
                 seconds: float, trace: bool, device: dict,
                 bench_dir: str = BENCH_DIR):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds, self.trace = int(seed), float(seconds), trace
        self.device = device
        self.bench_dir = bench_dir
        self.checks = Checks()
        self.limits = traffic.get("limits", {})
        self.reference = load_module(
            "reference", config.get("reference", cell["config"]), bench_dir)
        # filled by the run
        self.measured: Dict[str, Any] = {}
        self.trace_data = None
        self.trace_window = None        # (t0, t1) ns on the trace's axis
        self.window_s = 0.0

    def limit(self, name: str) -> float:
        if name not in self.limits:
            raise BenchError(f"traffic file gives no limit for {name!r}")
        return self.limits[name]


# -- span totals of the program's own telemetry -------------------------------------
def span_totals(names) -> Dict[str, tuple]:
    """(count, summed ms) of the program's ``span.<name>`` histograms."""
    from multiverso_tpu.telemetry.metrics import get_registry
    out = {}
    for n in names:
        h = get_registry().histogram(f"span.{n}")
        out[n] = (int(h.count), float(h.sum))
    return out


def span_delta(before: Dict[str, tuple], after: Dict[str, tuple]) -> dict:
    return {n: (after[n][0] - before[n][0], after[n][1] - before[n][1])
            for n in after}


# -- tracing -----------------------------------------------------------------
@contextlib.contextmanager
def traced_window(ctx: Context):
    """The measured window: under ``bench.window`` always, inside a profiler
    trace when the run is a traced one."""
    import jax
    trace_dir = None
    if ctx.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(WINDOW_ANNOTATION):
            yield
    finally:
        ctx.window_s = time.perf_counter() - t0
        if trace_dir is not None:
            jax.profiler.stop_trace()
            try:
                import trace_reduce
                ctx.trace_data = trace_reduce.Trace.from_dir(trace_dir)
                ctx.trace_window = ctx.trace_data.annotation_window(
                    WINDOW_ANNOTATION) or ctx.trace_data.span()
            finally:
                shutil.rmtree(trace_dir, ignore_errors=True)


def read_layer_metrics(ctx: Context, entries) -> dict:
    """Each per-layer metric through the reader its own file names; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in entries:
        spec = load_json("layer_metrics", m["name"], ctx.bench_dir)
        reader = load_module("readers", spec["reader"], ctx.bench_dir)
        value = reader.read(ctx, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def breakdown(ctx: Context) -> Optional[dict]:
    import trace_reduce
    tr = ctx.trace_data
    if tr is None or not tr.devices() or ctx.trace_window is None:
        return None
    dev = tr.devices()[0]
    gaps = tr.idle_gaps(*ctx.trace_window, device=dev,
                        skip=(WINDOW_ANNOTATION,))
    return {"device_ops": trace_reduce.top(tr.op_seconds(dev)),
            "idle_gaps": trace_reduce.top(gaps)}


# -- one run ------------------------------------------------------------------
def open_cell(workload: str, seed: int, seconds: float, trace: bool,
              require_chip: bool = True, root: str = ROOT,
              bench_dir: str = BENCH_DIR, bench: Optional[dict] = None):
    """(context, driver) of one cell: its entry, configuration and mix loaded,
    jax configured, the device checked."""
    bench = bench or load_benchmark(root)
    cell = find(bench["workloads"], workload, "workload")
    cfg_entry = find(bench["configs"], cell["config"], "config")
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    traffic = load_json("traffic", cell["traffic"], bench_dir)
    configure_jax()
    device = device_record(cell["chips"], require_chip)
    ctx = Context(cell, config, traffic, seed, seconds, trace, device,
                  bench_dir)
    return ctx, load_module("drivers", traffic["driver"], bench_dir)


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             require_chip: bool = True, t_process: Optional[float] = None,
             root: str = ROOT, bench_dir: str = BENCH_DIR) -> dict:
    """Run one cell once; returns the result object (see run.py)."""
    t_process = time.perf_counter() if t_process is None else t_process
    bench = load_benchmark(root)
    ctx, driver = open_cell(workload, seed, seconds, trace, require_chip,
                            root, bench_dir, bench)
    traffic, device = ctx.traffic, ctx.device
    if trace:
        ctx.seconds = min(ctx.seconds,
                          float(traffic.get("trace_seconds", ctx.seconds)))

    state = driver.setup(ctx)
    try:
        t = time.perf_counter()
        driver.check(state, ctx)            # reference: not part of set-up
        check_s = time.perf_counter() - t
        compiles0 = compile_count()
        setup_s = time.perf_counter() - t_process - check_s
        with traced_window(ctx):
            ctx.measured = driver.measure(state, ctx)
        compiles = compile_count() - compiles0
        t = time.perf_counter()
        driver.verify(state, ctx)
        check_s += time.perf_counter() - t
        ctx.checks.add("compiles_in_window", compiles, 0, "eq")
        peak = memory_peak_bytes()
    finally:
        driver.close(state)

    measured = ctx.measured
    values = dict(measured.get("metrics", {}))
    values["setup_s"] = setup_s
    values["peak_hbm_gb"] = peak / 1e9
    if trace:
        metrics = read_layer_metrics(
            ctx, metrics_of_cell(bench["per_layer"], workload))
    else:
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]}
                   for m in metrics_of_cell(bench["end_to_end"], workload)
                   if values.get(m["name"]) is not None}
    device = dict(device, memory_peak_bytes=peak)
    result = {"correct": ctx.checks.ok,
              "attempted": int(measured["attempted"]),
              "failed": int(measured["failed"]),
              "metrics": metrics, "device": device}
    if trace:
        busy = ctx.trace_data.busy_s(ctx.trace_window) \
            if ctx.trace_data is not None else 0.0
        device.update(busy_s=busy, window_s=ctx.window_s)
        bd = breakdown(ctx)
        if bd is not None:
            result["breakdown"] = bd
    info = {"workload": workload, "seed": int(seed), "seconds": ctx.seconds,
            "setup_s": setup_s, "check_s": check_s, "window_s": ctx.window_s,
            "cpu_count": os.cpu_count(),
            "counters": {k: v for k, v in measured.get("counters", {}).items()
                         if isinstance(v, (int, float))}}
    print("info " + json.dumps(info), flush=True)
    ctx.checks.print()
    return result
