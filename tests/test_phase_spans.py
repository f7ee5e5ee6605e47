"""Thread-phase spans (ISSUE 24): every phase a serving thread, a table call,
a DLRM step, a store's construction and the sketch fold go through is a
``span()``, so it is observed by its ``span.<name>`` histogram and, in a
profiled run, sits on its own thread's line on the profiler's clock. The
per-request stage spans (``emit_span``) are built from the same clock
readings."""
import glob
import os
import threading
import time

import numpy as np
import pytest

import multiverso_tpu as mv
from multiverso_tpu.serving import (ServingClient, ServingService,
                                    SparseLookupRunner)
from multiverso_tpu.telemetry import (activate, get_trace_buffer, new_root,
                                      phase, span)
from multiverso_tpu.telemetry.metrics import get_registry
from multiverso_tpu.utils.configure import set_flag


def _count(name: str) -> int:
    return int(get_registry().histogram(f"span.{name}").count)


def _sum_ms(name: str) -> float:
    return float(get_registry().histogram(f"span.{name}").sum)


def _table(rows=64, cols=8):
    table = mv.create_table(mv.MatrixTableOption(num_row=rows, num_col=cols))
    table.add_rows(np.arange(rows, dtype=np.int32),
                   np.random.default_rng(0).normal(size=(rows, cols))
                   .astype(np.float32))
    return table


class _SlowCollect(SparseLookupRunner):
    """A device that takes 30 ms a batch: the dispatch window fills."""

    def collect(self, handle):
        time.sleep(0.03)
        return super().collect(handle)


SERIALIZED = ("serve.batcher.idle", "serve.batcher.coalesce",
              "serve.batcher.form", "serve.batch", "serve.conn.submit")
PIPELINED = ("serve.batcher.idle", "serve.batcher.coalesce",
             "serve.batcher.form", "serve.batcher.dispatch",
             "serve.collector.wait", "serve.collector.collect",
             "serve.collector.deliver", "serve.conn.submit")


def _serve(depth, lookups=6, runner_cls=None, max_batch=4, max_wait_ms=5.0,
           threads=1):
    """One small served lookup workload; returns (table, replies ok)."""
    table = _table()
    runner = table.serving_runner() if runner_cls is None else \
        runner_cls(table.store)
    svc = ServingService()
    svc.register_runner(runner, buckets=(8,), max_batch=max_batch,
                        max_wait_ms=max_wait_ms, pipeline_depth=depth)
    cli = ServingClient(*svc.address)
    ok = []

    def hit():
        for i in range(lookups):
            q = np.asarray([i, 63 - i, 7], np.int32)
            ok.append(np.array_equal(
                cli.lookup(q, deadline_ms=20_000), table.get_rows(q)))

    try:
        workers = [threading.Thread(target=hit) for _ in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=120)
    finally:
        cli.close()
        svc.close()
    return ok


@pytest.mark.parametrize("name", SERIALIZED)
def test_serialized_loop_phase_is_observed(mv_env, name):
    ok = _serve(depth=0)
    assert ok and all(ok)
    assert _count(name) > 0, name
    # the pipelined loop's phases belong to threads this loop does not run
    assert _count("serve.batcher.dispatch") == 0
    assert _count("serve.collector.collect") == 0


@pytest.mark.parametrize("name", PIPELINED)
def test_pipelined_loop_phase_is_observed(mv_env, name):
    # one request a batch and a slow device: the window of two fills and the
    # batcher waits for a slot under serve.batcher.coalesce
    ok = _serve(depth=2, runner_cls=_SlowCollect, max_batch=1, threads=4,
                lookups=3)
    assert ok and all(ok)
    assert _count(name) > 0, name
    assert _count("serve.batch") == 0       # the serialized loop's run phase
    # once a batch, not once a request: 12 requests, one a batch
    assert _count("serve.batcher.dispatch") == 12
    assert _count("serve.collector.collect") == 12
    assert _count("serve.conn.submit") == 12


@pytest.mark.parametrize("depth", [0, 2])
def test_stage_spans_share_the_phases_clock_readings(mv_env, depth,
                                                     monkeypatch):
    """The batch-form STAGE of a sampled request and the batcher's form PHASE
    are one pair of clock readings, and so are collect and the collector's
    phase: same duration to the microsecond."""
    from multiverso_tpu.serving import batcher, pipeline
    made = {}

    class Kept(phase):
        def __init__(self, name, **attrs):
            super().__init__(name, **attrs)
            made.setdefault(name, []).append(self)

    monkeypatch.setattr(batcher, "phase", Kept)
    monkeypatch.setattr(pipeline, "phase", Kept)
    set_flag("telemetry_sample_rate", 1.0)
    ok = _serve(depth=depth, lookups=1)
    assert ok == [True]
    events = get_trace_buffer().events()

    def stage_us(name):
        hits = [e for e in events if e["name"] == name]
        assert len(hits) == 1, (name, len(hits))
        assert "trace" in hits[0]["args"]
        return hits[0]["dur"]

    def one(name):
        assert len(made[name]) == 1, (name, len(made[name]))
        return made[name][0]

    def us(t0, t1):         # as emit_span rounds a stage
        return int((t1 - t0) * 1e3 * 1e3)

    form = one("serve.batcher.form")
    assert stage_us("serve.batch_form") == us(form.t0, form.t1)
    if depth:
        launch = one("serve.batcher.dispatch")
        sync = one("serve.collector.collect")
        # dispatch starts where forming ended, ends where the launch did
        assert stage_us("serve.dispatch") == us(form.t1, launch.t1)
        assert stage_us("serve.device") == us(launch.t1, sync.t0)
        assert stage_us("serve.collect") == us(sync.t0, sync.t1)
    # the thread's phases are not the ring's to keep
    assert not [e for e in events if e["name"].startswith(
        ("serve.batcher.", "serve.collector.", "serve.conn."))]


def test_phases_sit_on_their_own_threads_lines_under_the_profiler(
        mv_env, tmp_path):
    import jax
    from jax.profiler import ProfileData
    table = _table()
    svc = ServingService()
    svc.register_runner(table.serving_runner(), buckets=(8,), max_batch=4,
                        max_wait_ms=1.0, pipeline_depth=2)
    cli = ServingClient(*svc.address)
    try:
        cli.lookup(np.arange(4, dtype=np.int32), deadline_ms=20_000)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            for _ in range(4):
                cli.lookup(np.arange(4, dtype=np.int32), deadline_ms=20_000)
        finally:
            jax.profiler.stop_trace()
    finally:
        cli.close()
        svc.close()
    path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    lines = []          # per host thread: the serve.* names on its line
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names = {e.name for e in line.events
                         if e.name.startswith("serve.")}
                if names:
                    lines.append(names)

    def line_of(name):
        hits = [names for names in lines if name in names]
        assert len(hits) == 1, (name, lines)
        return hits[0]

    batcher = line_of("serve.batcher.form")
    collector = line_of("serve.collector.collect")
    conn = line_of("serve.conn.submit")
    assert {"serve.batcher.form", "serve.batcher.dispatch"} <= batcher
    assert {"serve.collector.collect", "serve.collector.deliver"} \
        <= collector
    for a, b in ((batcher, collector), (batcher, conn), (collector, conn)):
        assert not (a & b), (a, b)
    assert all(n.split(".")[1] == "batcher" for n in batcher)
    assert all(n.split(".")[1] == "collector" for n in collector)
    assert all(n.split(".")[1] == "conn" for n in conn)


TABLE_PHASES = ("table.get_rows.dispatch", "table.get_rows.sync",
                "table.add_rows.dispatch", "table.add_rows.sync")


@pytest.mark.parametrize("name", TABLE_PHASES)
def test_table_call_phase_is_observed_once_a_call(mv_env, name):
    table = mv.create_table(mv.MatrixTableOption(num_row=32, num_col=4))
    rows = np.asarray([1, 5, 9], np.int32)
    before = _count(name)
    table.add_rows(rows, np.ones((3, 4), np.float32))
    got = table.get_rows(rows)
    np.testing.assert_array_equal(got, np.ones((3, 4), np.float32))
    assert _count(name) - before == 1
    # the monitor stays the one whole-call timer; its phases are inside it
    whole = get_registry().histogram(
        "WORKER_TABLE_SYNC_GET" if "get_rows" in name
        else "WORKER_TABLE_SYNC_ADD")
    assert whole.count == 1
    parts = sum(_sum_ms(n) for n in TABLE_PHASES
                if n.split(".")[1] == name.split(".")[1])
    assert parts <= whole.sum


@pytest.mark.parametrize("name", ["table.host_init", "table.device_put",
                                  "zoo.start", "startup.backend"])
def test_start_up_phase_is_observed(mv_env, name):
    from multiverso_tpu.core.options import MatrixTableOption
    assert _count("zoo.start") == 1
    before = _count(name)
    mv.create_table(MatrixTableOption(num_row=16, num_col=4,
                                      updater="adagrad"))
    if name == "zoo.start":
        assert _count(name) == before       # bring-up happens once
    elif name == "startup.backend":
        # the mesh is built on first use, under its span, and once (the
        # rest of the timeline's names: tests/test_startup_timeline.py)
        assert (before, _count(name)) == (0, 1)
        mv.create_table(MatrixTableOption(num_row=16, num_col=4))
        assert _count(name) == 1
    else:
        # the table and its one state leaf (adagrad's g2)
        assert _count(name) - before == 2


def _dlrm():
    """A three-field PS-mode DLRM and its impression stream."""
    from multiverso_tpu.models.dlrm import (DLRMConfig, DLRMModel,
                                            ImpressionStream, StreamConfig)
    cfg = DLRMConfig(fields=3, vocab=64, embed_dim=8, dense_dim=4,
                     bottom_mlp=(8,), top_mlp=(8,))
    stream = ImpressionStream(StreamConfig(
        fields=cfg.fields, vocab=cfg.vocab, dense_dim=cfg.dense_dim,
        zipf=1.3, seed=1, drift_every=0))
    return DLRMModel(cfg, mode="ps"), stream


DLRM_PHASES = ("recsys.pull", "recsys.compute", "recsys.compute.dispatch",
               "recsys.compute.sync", "recsys.push", "recsys.finish")


@pytest.mark.parametrize("name", DLRM_PHASES)
def test_dlrm_step_phase_is_observed(mv_env, name):
    model, stream = _dlrm()
    b = stream.batch(16)
    model.step(b.ids, b.dense, b.labels)            # compiles
    before = _count(name)
    gets, adds = (_count("table.get_rows.sync"),
                  _count("table.add_rows.sync"))
    steps = 5
    for _ in range(steps):
        b = stream.batch(16)
        model.step(b.ids, b.dense, b.labels)
    assert _count(name) - before == steps
    # 3 fields: a step is ONE grouped pull and ONE grouped push through the
    # table phases (tables/table_group.py), whatever the number of fields
    assert _count("table.get_rows.sync") - gets == steps
    assert _count("table.add_rows.sync") - adds == steps


def test_no_part_of_a_dlrm_step_is_outside_a_program_span(mv_env):
    model, stream = _dlrm()
    batches = [stream.batch(16) for _ in range(12)]
    for b in batches[:2]:
        model.step(b.ids, b.dense, b.labels)        # compiles
    top = ("recsys.pull", "recsys.compute", "recsys.push", "recsys.finish")
    inside0 = sum(_sum_ms(n) for n in top)
    wall = 0.0
    for b in batches[2:]:
        t = time.perf_counter()
        model.step(b.ids, b.dense, b.labels)
        wall += (time.perf_counter() - t) * 1e3
    inside = sum(_sum_ms(n) for n in top) - inside0
    assert inside <= wall
    assert (wall - inside) / wall < 0.05, (wall, inside)
    # and the nested phases tile recsys.compute
    nested = _sum_ms("recsys.compute.dispatch") + _sum_ms("recsys.compute.sync")
    assert nested <= _sum_ms("recsys.compute")
    assert nested / _sum_ms("recsys.compute") > 0.9


def test_sketch_fold_fires_once_per_flush_pending_on_the_recorder(mv_env):
    from multiverso_tpu.telemetry.sketch import get_sketch_hub, record_keys
    hub = get_sketch_hub()
    n = hub.FLUSH_PENDING
    folds = get_registry().counter("telemetry.sketch.folds_on_caller")
    keys = np.arange(7, dtype=np.int64)
    for _ in range(n - 1):
        record_keys("test.surface", keys, 7 * 4)
    assert _count("telemetry.sketch_fold") == 0 and folds.value == 0
    record_keys("test.surface", keys, 7 * 4)
    assert _count("telemetry.sketch_fold") == 1 and folds.value == 1
    event = [e for e in get_trace_buffer().events()
             if e["name"] == "telemetry.sketch_fold"][0]
    assert event["args"]["records"] == n and event["args"]["keys"] == 7 * n
    assert event["tid"] == threading.get_ident() % (1 << 31)

    # another thread's records fold on THAT thread, after its own 256
    seen = []

    def other():
        for _ in range(2 * n + 3):
            record_keys("test.surface", keys, 7 * 4)
        seen.append(threading.get_ident() % (1 << 31))

    t = threading.Thread(target=other)
    t.start()
    t.join(timeout=60)
    assert _count("telemetry.sketch_fold") == 3 and folds.value == 3
    tids = [e["tid"] for e in get_trace_buffer().events()
            if e["name"] == "telemetry.sketch_fold"]
    assert tids[1:] == [seen[0], seen[0]]


@pytest.mark.parametrize("mesh", ["one_chip", "dp_x_tp"])
def test_block_programs_are_named_block_step(mv_env, mesh):
    """``w2v_block_device_ms`` reads the trace's ``jit_block_step`` program
    events: the name is part of the yardstick."""
    import jax
    import jax.numpy as jnp
    from multiverso_tpu.models.word2vec.model import (
        build_device_block_step, build_sharded_block_step)
    V, D, S, L = 64, 16, 4, 8
    if mesh == "one_chip":
        step = build_device_block_step(window=2, negative=3, chunk=16,
                                       adagrad=True)
    else:
        devices = np.asarray(jax.devices()[:4]).reshape(2, 2)
        step = build_sharded_block_step(
            jax.sharding.Mesh(devices, ("data", "model")), window=2,
            negative=3, chunk=16, adagrad=True)
    assert step.__name__ == "block_step"
    tables = [jnp.zeros((V, D), jnp.float32)] * 4
    lowered = step.lower(
        *tables, jnp.zeros(997, jnp.int32), jnp.ones(V, jnp.float32),
        jnp.zeros((S, L), jnp.int32), jnp.full((S,), L, jnp.int32),
        jax.random.PRNGKey(0), jnp.float32(0.05))
    assert "module @jit_block_step" in lowered.as_text()


def test_w2v_device_block_is_the_one_timer_of_its_region(mv_env):
    """The two monitors beside the span are gone; the mode is the span's
    attribute."""
    from multiverso_tpu.models.word2vec import (Dictionary, Word2Vec,
                                                Word2VecConfig)
    rng = np.random.default_rng(0)
    d = Dictionary(min_count=1)
    d.counts = [10] * 40
    d.words = [f"w{i}" for i in range(40)]
    cfg = Word2VecConfig(embedding_size=8, window=2, negative=2, sample=0,
                         epochs=1, batch_size=64, block_sentences=4,
                         pad_sentence_length=16, device_pipeline=True,
                         dispatch_mode="in_graph")
    w2v = Word2Vec(cfg, d)
    sents = [rng.integers(0, 40, size=12).astype(np.int32) for _ in range(8)]
    w2v.train(sentences=sents)
    assert _count("w2v.device_block") == 2
    names = set(get_registry().snapshot(buckets=False)["histograms"])
    assert not [n for n in names if n.startswith(("W2V_DEVICE_BLOCK",
                                                   "W2V_DISPATCH_"))]
    modes = {e["args"].get("mode") for e in get_trace_buffer().events()
             if e["name"] == "w2v.device_block"}
    assert modes == {"in_graph"}


def test_span_handle_and_histogram_survive_a_registry_reset():
    """``span()`` holds its histogram by name; a reset registry hands out a
    new one, and the handle carries the region's two clock readings."""
    from multiverso_tpu.telemetry import reset_telemetry
    with span("phase.probe", n=1) as s:
        s.attrs["m"] = 2
    assert 0.0 < s.t0 <= s.t1
    assert _count("phase.probe") == 1
    event = get_trace_buffer().events()[-1]
    assert event["name"] == "phase.probe"
    assert event["args"]["n"] == 1 and event["args"]["m"] == 2
    assert event["dur"] == int((s.t1 - s.t0) * 1e6)
    reset_telemetry()
    assert _count("phase.probe") == 0
    with span("phase.probe"):
        pass
    assert _count("phase.probe") == 1


def test_a_phase_reaches_the_ring_only_under_a_sampled_context():
    """A thread's steady cycle must not flood the ring: with no context a
    ``phase`` is its histogram (and annotation) alone, where a ``span``
    records its event, built, at once."""
    buf = get_trace_buffer()
    buf.clear()
    with phase("phase.cycle"):
        pass
    assert _count("phase.cycle") == 1 and len(buf) == 0
    with span("phase.region"):
        pass
    assert [type(e) for e in buf._events] == [dict]
    with activate(new_root(sampled=False)):
        with phase("phase.cycle"):
            pass
    assert _count("phase.cycle") == 2 and len(buf) == 1
    root = new_root(sampled=True)
    with activate(root):
        with phase("phase.cycle", n=3) as p:
            pass
    assert _count("phase.cycle") == 3
    event = buf.events()[-1]
    assert event["name"] == "phase.cycle" and event["args"]["n"] == 3
    assert event["args"]["trace"] == root.trace_hex
    assert event["dur"] == int((p.t1 - p.t0) * 1e6)


@pytest.mark.parametrize("read", ["count", "sum", "percentile", "raw_counts",
                                  "snapshot"])
def test_every_histogram_reader_sees_what_observe_holds(read):
    """``Histogram.observe`` is one append; whoever reads buckets first."""
    from multiverso_tpu.telemetry.metrics import Histogram
    h = Histogram("held.probe")
    for v in (1.0, 2.0, 4.0):
        h.observe(v)
    assert len(h._pending) == 3 and h._count == 0
    got = {"count": lambda: h.count, "sum": lambda: h.sum / 7.0 * 3,
           "percentile": lambda: 3 if h.percentile(1.0) == 4.0 else 0,
           "raw_counts": lambda: h.raw_counts()[0],
           "snapshot": lambda: h.snapshot()["count"]}[read]()
    assert got == 3 and not h._pending and h._count == 3
    assert h.snapshot()["max_ms"] == 4.0 and h.snapshot()["min_ms"] == 1.0


def test_histogram_holds_a_bounded_number_and_loses_none_across_threads():
    from multiverso_tpu.telemetry.metrics import Histogram
    h = Histogram("held.bound")
    for _ in range(Histogram.PENDING_MAX - 1):
        h.observe(0.5)
    assert h._count == 0
    h.observe(0.5)                      # the 256th append buckets them
    assert h._count == Histogram.PENDING_MAX and not h._pending

    def feed():
        for _ in range(5000):
            h.observe(0.25)

    workers = [threading.Thread(target=feed) for _ in range(4)]
    for w in workers:
        w.start()
    while any(w.is_alive() for w in workers):
        h.percentile(0.5)               # readers fold beside the writers
    for w in workers:
        w.join()
    assert h.count == Histogram.PENDING_MAX + 4 * 5000
    assert len(h._pending) == 0
    assert h.sum == pytest.approx(0.5 * Histogram.PENDING_MAX + 0.25 * 20000)
