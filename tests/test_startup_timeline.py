"""The start-up timeline (ISSUE 50, ``telemetry/startup.py``): the seconds
from the process's start to its first completed unit, partitioned into self
times on the spans that are there, and one ``compile.program`` record for
each program jax compiles or fetches. The names asserted here are what
``benchmark/readers/startup_part.py`` and docs/OBSERVABILITY.md read."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import multiverso_tpu as mv
from multiverso_tpu.telemetry import (get_trace_buffer, metrics_snapshot,
                                      reset_telemetry, span, startup)
from multiverso_tpu.telemetry.metrics import get_registry


@pytest.fixture(autouse=True)
def this_thread_imported_the_package(monkeypatch):
    """The partition is the importing thread's; a test runner may have
    imported the package on another thread than the one it runs tests on."""
    monkeypatch.setattr(startup._tl, "main_tid", startup._tid())


def _counter(name: str) -> int:
    return int(get_registry().counter(name).value)


def _count(name: str) -> int:
    return int(get_registry().histogram(f"span.{name}").count)


def _staged_start_up():
    """A build with a table inside it, a span of no part's, the host
    application's own seconds, then one unit and its wait. Returns the
    report and what each stage took by this thread's own clock."""
    took, t = {}, [time.time()]

    def stage(name, seconds):
        time.sleep(seconds)
        now = time.time()
        took[name] = took.get(name, 0.0) + now - t[0]
        t[0] = now

    with span("x.build"):
        stage("build", 0.02)
        with span("table.host_init"):
            stage("tables", 0.03)
        stage("build", 0.01)
    with span("zoo.start"):
        stage("other_program", 0.01)
    stage("outside_program", 0.02)
    with span("w2v.device_block"):
        stage("first_unit", 0.02)
    stage("first_unit", 0.01)           # the unit's wait, after its span
    startup.mark_ready(("w2v.device_block",))
    return startup.report(), took


def test_the_parts_sum_to_ready_s():
    rep, _ = _staged_start_up()
    assert rep["ready"] and rep["process_start_from"] in ("kernel", "import")
    assert set(rep["parts"]) == set(startup.PARTS) | {"outside_program"}
    assert abs(sum(rep["parts"].values()) - rep["ready_s"]) < 1e-6
    assert all(v >= -1e-9 for v in rep["parts"].values()), rep["parts"]
    assert get_registry().gauge("startup.ready_s").last == \
        pytest.approx(rep["ready_s"])
    # the import was stamped, and lies inside the process's life
    assert 0 < rep["parts"]["import"] < rep["ready_s"]
    assert rep["parts"]["before_program"] >= 0


def test_a_nested_spans_seconds_are_booked_once():
    rep, took = _staged_start_up()
    parts = rep["parts"]
    # the build less its table; the unit from its first span's start to
    # ready; each stage where this thread's clock put it (a span's entry
    # and exit are the slack)
    for part in ("tables", "build", "other_program", "first_unit"):
        assert parts[part] == pytest.approx(took[part], abs=3e-3), part
    assert parts["outside_program"] >= took["outside_program"] - 3e-3
    assert parts["compile"] == parts["backend"] == 0


@pytest.fixture
def compile_cache(tmp_path):
    """jax's persistent cache on, in a directory of the test's own, taking
    every program (tier-1 runs with it off: conftest)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    names = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    old = {n: getattr(jax.config, n) for n in names}
    for n, v in zip(names, (True, str(tmp_path), 0.0, -1)):
        jax.config.update(n, v)
    cc.reset_cache()
    yield
    for n, v in old.items():
        jax.config.update(n, v)
    cc.reset_cache()


def test_a_compile_is_one_record_a_miss_then_a_hit(compile_cache):
    def startup_probe(x):
        return jnp.tanh(x) @ x.T

    def mine():
        return [r for r in startup.report()["compiles"]
                if "startup_probe" in r["program"]]

    x = jnp.ones((8, 8))
    jax.block_until_ready(x)
    misses, hits = (_counter("compile.cache_misses"),
                    _counter("compile.cache_hits"))
    with span("probe.build"):
        jax.jit(startup_probe)(x).block_until_ready()
    (rec,) = mine()
    assert rec["cache"] == "miss" and rec["backend_s"] > 0
    assert rec["fetch_s"] == 0 and rec["trace_s"] > 0 and rec["lower_s"] > 0
    assert rec["t0"] < rec["t1"] and rec["tid"] == startup._tid()
    assert rec["under"] == "probe.build"
    assert (_counter("compile.cache_misses"),
            _counter("compile.cache_hits")) == (misses + 1, hits)
    jax.clear_caches()
    jax.jit(startup_probe)(x).block_until_ready()
    first, second = mine()
    assert first == rec
    assert second["cache"] == "hit" and second["fetch_s"] > 0
    assert second["backend_s"] == 0 and second["under"] is None
    assert (_counter("compile.cache_misses"),
            _counter("compile.cache_hits")) == (misses + 1, hits + 1)
    assert _counter("compile.programs") >= 2
    assert _count("compile.backend") >= 1 and _count("compile.fetch") == 1
    assert _count("compile.trace") >= 2 and _count("compile.lower") >= 2
    events = [e for e in get_trace_buffer().events()
              if e["name"] == "compile.program"
              and "startup_probe" in e["args"]["program"]]
    assert [e["args"]["cache"] for e in events] == ["miss", "hit"]


def test_a_compile_under_a_span_is_a_leaf_of_the_partition():
    with span("x.build"):
        t = time.time()
        jax.jit(lambda x: jnp.cos(x) + 1)(jnp.ones(7)).block_until_ready()
        whole = time.time() - t
    startup.mark_ready()
    rep = startup.report()
    assert rep["programs"] >= 1 and rep["cache_misses"] == 0   # cache off
    assert {r["cache"] for r in rep["compiles"]} == {"off"}
    secs = sum(r["trace_s"] + r["lower_s"] + r["backend_s"]
               for r in rep["compiles"])
    assert rep["parts"]["compile"] == pytest.approx(secs, abs=2e-3)
    assert rep["parts"]["compile"] + rep["parts"]["build"] == \
        pytest.approx(whole, abs=2e-2)


def test_a_compile_after_ready_is_counted_with_its_enclosing_span():
    startup.mark_ready()
    assert _counter("compile.after_ready") == 0
    with span("lm.step"):
        with span("lm.compute"):
            jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(5)).block_until_ready()
    after = startup.report()["after_ready"]
    assert after["count"] == _counter("compile.after_ready") >= 1
    assert after["last"][-1]["under"] == "lm.compute"
    assert startup.report()["programs"] == 0        # none before ready
    for _ in range(40):                             # the last sixteen stay
        jax.jit(lambda x: x * 2)(jnp.ones(3))
    after = startup.report()["after_ready"]
    assert len(after["last"]) == 16 and after["count"] >= 40


def test_compiles_on_another_thread_are_beside_the_parts():
    def work():
        jax.jit(lambda x: jnp.exp(x) - 2)(jnp.ones(9)).block_until_ready()

    thread = threading.Thread(target=work)
    thread.start()
    thread.join(timeout=60)
    assert not thread.is_alive()
    startup.mark_ready()
    rep = startup.report()
    assert rep["compile_other_threads_s"] > 0
    assert rep["parts"]["compile"] == 0
    assert abs(sum(rep["parts"].values()) - rep["ready_s"]) < 1e-6


def test_mark_ready_is_idempotent_and_the_timeline_stops_growing():
    assert not startup.ready and startup.report()["ready"] is False
    assert "parts" not in startup.report()
    with span("w2v.device_block"):
        pass
    startup.mark_ready(("w2v.device_block",))
    first = startup.report()
    held = len(startup._tl.events)
    for _ in range(1000):
        with span("w2v.device_block"):
            pass
    startup.mark_ready(("lm.pull",))
    assert startup.ready and len(startup._tl.events) == held
    assert startup._tl.unit == ("w2v.device_block",)
    again = startup.report()
    assert again["ready_s"] == first["ready_s"]
    assert again["parts"] == first["parts"]


def test_reset_clears_the_timeline_and_keeps_the_listeners():
    from jax._src import monitoring
    jax.jit(lambda x: x + 5)(jnp.ones(2))
    startup.mark_ready()
    assert startup.report()["programs"] >= 1
    t_process = startup._tl.t_process
    reset_telemetry()
    assert not startup.ready
    rep = startup.report()
    assert rep["ready"] is False and rep["programs"] == 0
    assert rep["after_ready"] == {"count": 0, "last": []}
    assert startup._tl.t_process == t_process       # a fact of the process
    for listeners, mine in (
            (monitoring.get_event_listeners(), startup._on_event),
            (monitoring.get_event_duration_listeners(), startup._on_duration),
            (monitoring.get_event_time_span_listeners(),
             startup._on_time_span)):
        assert listeners.count(mine) == 1
    jax.jit(lambda x: x + 6)(jnp.ones(2))
    assert startup.report()["programs"] >= 1        # and they still record


def test_the_import_is_two_events_built_from_stamps(monkeypatch):
    tl = startup._tl
    for attr in ("t_import0", "t_import1", "backend_ready_at_import",
                 "t_process", "process_from"):
        monkeypatch.setattr(tl, attr, getattr(tl, attr))
    assert tl.t_process <= tl.t_import0 < tl.t_import1
    startup.imported(time.time() - 0.25, True)
    pre, imp = [e for e in get_trace_buffer().events()
                if e["name"].startswith("startup.")]
    assert (pre["name"], imp["name"]) == ("startup.pre_import",
                                          "startup.import")
    assert pre["args"]["from"] == tl.process_from
    assert pre["args"]["backend_ready"] is True
    assert pre["ts"] + pre["dur"] == pytest.approx(imp["ts"], abs=2)
    assert imp["dur"] == pytest.approx(0.25e6, rel=0.1)
    assert _count("startup.pre_import") == _count("startup.import") == 1


def test_transfers_landed_is_a_point_on_the_process_clock(mv_env):
    from multiverso_tpu.core.options import MatrixTableOption
    table = mv.create_table(MatrixTableOption(num_row=64, num_col=8,
                                              updater="adagrad"))
    jax.block_until_ready(table.store.data)
    deadline = time.time() + 30
    while startup.report()["transfers_pending"] and time.time() < deadline:
        time.sleep(0.02)
    startup.mark_ready()
    rep = startup.report()
    assert rep["transfers_pending"] == 0
    assert 0 < rep["transfers_landed_s"] <= rep["ready_s"]
    # once ready a store's arrays are not watched
    mv.create_table(MatrixTableOption(num_row=8, num_col=8))
    assert startup.report()["transfers_pending"] == 0


def test_the_backend_is_asked_for_under_its_span(mv_env):
    assert _count("startup.backend") == 0       # built on first use
    mv.create_table(mv.MatrixTableOption(num_row=8, num_col=4))
    mv.create_table(mv.MatrixTableOption(num_row=8, num_col=4))
    assert _count("startup.backend") == 1


def _w2v():
    from multiverso_tpu.models.word2vec import (Dictionary, Word2Vec,
                                                Word2VecConfig)
    d = Dictionary(min_count=1)
    d.counts = [10] * 40
    d.words = [f"w{i}" for i in range(40)]
    cfg = Word2VecConfig(embedding_size=8, window=2, negative=2, sample=0,
                         epochs=1, batch_size=64, block_sentences=4,
                         pad_sentence_length=16, device_pipeline=True,
                         dispatch_mode="in_graph")
    return Word2Vec(cfg, d)


def _dlrm():
    from multiverso_tpu.models.dlrm import DLRMConfig, DLRMModel
    return DLRMModel(DLRMConfig(fields=3, vocab=64, embed_dim=8, dense_dim=4,
                                bottom_mlp=(8,), top_mlp=(8,)), mode="ps")


def _lm():
    from multiverso_tpu.models.hybrid_lm import HybridLM, HybridLMConfig
    return HybridLM(HybridLMConfig(row_bucket=16, attn_block=8, moe_block=4,
                                   loss_block=16), mode="local")


@pytest.mark.parametrize("name,build", [("w2v.build", _w2v),
                                        ("recsys.build", _dlrm),
                                        ("lm.build", _lm)])
def test_a_models_build_is_one_span_with_its_tables_inside(mv_env, name,
                                                           build):
    build()
    assert _count(name) == 1
    (ev,) = [e for e in get_trace_buffer().events() if e["name"] == name]
    inside = [e for e in get_trace_buffer().events()
              if e["name"] in ("table.host_init", "table.device_put")
              and ev["ts"] <= e["ts"]
              and e["ts"] + e["dur"] <= ev["ts"] + ev["dur"] + 1000]
    assert bool(inside) == (name != "lm.build")     # the twin has no table
    startup.mark_ready()
    parts = startup.report()["parts"]
    assert parts["build"] > 0
    assert parts["build"] <= ev["dur"] / 1e6 + 1e-3


def test_the_first_train_call_is_w2vs_first_unit(mv_env):
    w2v = _w2v()
    assert not startup.ready
    rng = np.random.default_rng(0)
    w2v.train(sentences=[rng.integers(0, 40, size=12).astype(np.int32)
                         for _ in range(8)])
    assert startup.ready and startup._tl.unit == ("w2v.device_block",
                                                  "w2v.group")
    rep = startup.report()
    assert rep["parts"]["first_unit"] > 0
    under = {r["program"]: r["under"] for r in rep["compiles"]}
    assert under["jit(block_step)"] == "w2v.device_block"
    assert abs(sum(rep["parts"].values()) - rep["ready_s"]) < 1e-6


def test_the_first_hybrid_step_is_dlrms_first_unit(mv_env):
    from multiverso_tpu.models.dlrm import ImpressionStream, StreamConfig
    model = _dlrm()
    stream = ImpressionStream(StreamConfig(
        fields=3, vocab=64, dense_dim=4, zipf=1.3, seed=1, drift_every=0))
    b = stream.batch(16)
    model.step(b.ids, b.dense, b.labels)
    assert startup.ready and startup._tl.unit == (
        "recsys.pull", "recsys.compute", "recsys.push")
    rep = startup.report()
    assert rep["parts"]["first_unit"] > 0 and rep["parts"]["build"] > 0
    programs = {r["program"]: r["under"] for r in rep["compiles"]}
    assert programs["jit(delta_step)"] == "recsys.compute.dispatch"
    b = stream.batch(16)
    model.step(b.ids, b.dense, b.labels)            # a steady step
    assert startup.report()["after_ready"]["count"] == 0


def test_warmups_return_is_servings_first_unit(mv_env):
    from multiverso_tpu.serving import ServingService
    table = mv.create_table(mv.MatrixTableOption(num_row=64, num_col=8))
    svc = ServingService()
    try:
        svc.register_runner(table.serving_runner(), buckets=(8,),
                            max_batch=4, pipeline_depth=1)
        assert _count("serve.register_runner") == 1 and not startup.ready
        assert svc.warmup() == 1
    finally:
        svc.close()
    assert _count("serve.warmup") == 1
    assert startup.ready and startup._tl.unit == ("serve.warmup",)
    parts = startup.report()["parts"]
    assert parts["first_unit"] > 0 and parts["other_program"] > 0


def test_the_snapshot_carries_the_timeline():
    jax.jit(lambda x: x - 1)(jnp.ones(4))
    startup.mark_ready()
    snap = metrics_snapshot(buckets=False)
    assert snap["startup"]["ready"] and "parts" in snap["startup"]
    assert snap["gauges"]["startup.ready_s"]["last"] == \
        pytest.approx(snap["startup"]["ready_s"])
    assert snap["counters"]["compile.programs"]["value"] >= 1
    assert "span.compile.backend" in snap["histograms"]
    import json
    json.dumps(snap["startup"])                     # the exporter writes it
