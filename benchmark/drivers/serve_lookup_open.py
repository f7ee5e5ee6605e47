"""Driver ``serve_lookup_open``: row lookups against the live input table of a
resident word2vec model, over the socket, at a fixed open-loop rate.

The server is this process: ``ServingService`` + ``table.serving_runner()``
with the service's defaults but for the bucket ladder. The load comes from
generator processes of their own (``loadgen.py``), started and warmed in
set-up; the window tells them when it starts and waits for them. A request
belongs to the window it was due in; its latency runs from when it was due to
when its reply reached the client; a shed, failed or unanswered request is a
failed one and sits at the top of the distribution. The judged latency is the
median over all requests; the tails (p95, p99) are per-layer metrics: on a
one-chip machine that shares its host's cores they follow the host's pauses.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

import seeded
import traffic_gen
from harness import BENCH_DIR, load_module

SPANS = ("serve.admit_wait", "serve.batch_form", "serve.device",
         "serve.reply", "serve.request")
OK = 1


def setup(ctx):
    t = ctx.traffic
    t0 = time.perf_counter()
    from multiverso_tpu.serving import ServingService
    service = ServingService()
    state = {"w2v": None, "service": service, "gens": None, "tmp": None}
    try:
        # the generators load and draw their schedules while the model is
        # built; they connect only once the runner is registered
        spawn_generators(state, ctx, t["rate"], ctx.seconds, ctx.seed)
        w2v_driver = load_module("drivers", "train_w2v", ctx.bench_dir)
        w2v, _ = w2v_driver.build_model(ctx)    # all four tables resident
        w2v_driver.seed_tables(w2v, ctx)
        state["w2v"] = w2v
        state["build_s"] = time.perf_counter() - t0
        tw = time.perf_counter()
        service.register_runner(w2v.input_table.serving_runner(),
                                buckets=tuple(t["buckets"]))
        service.warmup()                        # one executable per bucket
        state["warm_s"] = time.perf_counter() - tw
        warm_generators(state)
    except BaseException:
        close(state)
        raise
    return state


def sample_requests(seed: int, sizes: np.ndarray, k: int) -> list:
    """A seeded sample of the window's requests, the largest among them."""
    rng = traffic_gen.rng_for(seed, 7)
    pick = set(rng.choice(len(sizes), size=min(k, len(sizes)),
                          replace=False).tolist())
    pick.add(int(np.argmax(sizes)))
    return sorted(pick)


def start_generators(state, ctx, rate: float, seconds: float,
                     seed: int) -> None:
    spawn_generators(state, ctx, rate, seconds, seed)
    warm_generators(state)


def _expect(proc, word: str) -> None:
    line = proc.stdout.readline()
    if line.strip() != word:
        raise RuntimeError(f"generator said {line!r}, not {word!r}")


def warm_generators(state) -> None:
    """Once the runner is registered: let every generator connect and send
    one request of each bucket's size."""
    for proc, _ in state["gens"]:
        _expect(proc, "loaded")
        proc.stdin.write("warm\n")
        proc.stdin.flush()
    for proc, _ in state["gens"]:
        _expect(proc, "ready")


def spawn_generators(state, ctx, rate: float, seconds: float,
                     seed: int) -> None:
    """Start the generator processes; each loads and draws its schedule."""
    t, c = ctx.traffic, ctx.config
    _, sizes = traffic_gen.open_loop_schedule(seed, rate, seconds,
                                              t["keys_lo"], t["keys_hi"])
    state["sample"] = sample_requests(seed, sizes, t["sample"])
    state["tmp"] = tempfile.mkdtemp(prefix="bench_loadgen_")
    host, port = state["service"].address
    import multiverso_tpu
    program = os.path.dirname(os.path.dirname(
        os.path.abspath(multiverso_tpu.__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [program] + [p for p in [os.environ.get("PYTHONPATH")] if p]))
    gens = []
    for g in range(t["generators"]):
        args = {"host": host, "port": port, "seed": int(seed), "rate": rate,
                "seconds": seconds, "keys_lo": t["keys_lo"],
                "keys_hi": t["keys_hi"], "zipf": t["zipf"],
                "rows": c["vocab"], "gen_index": g,
                "n_gens": t["generators"], "deadline_ms": t["deadline_ms"],
                "straggler_s": t["straggler_s"], "sample": state["sample"],
                "warm_sizes": t["buckets"],
                "sample_rate": 1.0 if ctx.trace else None,
                "out": os.path.join(state["tmp"], f"gen{g}.npz")}
        path = os.path.join(state["tmp"], f"gen{g}.json")
        with open(path, "w") as f:
            json.dump(args, f)
        gens.append((subprocess.Popen(
            [sys.executable, os.path.join(BENCH_DIR, "loadgen.py"), path],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env,
            text=True), args["out"]))
    state["gens"] = gens
    state["window"] = {"rate": rate, "seconds": seconds, "seed": seed,
                       "sizes": sizes}


def stop_generators(state) -> None:
    for proc, _ in state.get("gens") or []:
        if proc.poll() is None:
            proc.kill()
        proc.wait()
        for pipe in (proc.stdin, proc.stdout):
            if pipe is not None:
                pipe.close()
    state["gens"] = None
    if state.get("tmp"):
        shutil.rmtree(state["tmp"], ignore_errors=True)
        state["tmp"] = None


def run_window(state, ctx) -> dict:
    """Tell the generators when the window starts, wait for them, and reduce
    what they recorded."""
    import jax
    from harness import span_delta, span_totals
    t, win = ctx.traffic, state["window"]
    seconds = win["seconds"]
    spans0 = span_totals(SPANS)
    server0 = _server_latency_counts()
    t0 = time.monotonic() + 0.25
    for proc, _ in state["gens"]:
        proc.stdin.write(f"{t0!r}\n")
        proc.stdin.flush()
    limit = seconds + t["straggler_s"] + 60
    with jax.profiler.TraceAnnotation("bench.serve_wait"):
        for proc, _ in state["gens"]:
            if proc.wait(timeout=limit) != 0:
                raise RuntimeError(f"generator exited {proc.returncode}")
    spans = span_delta(spans0, span_totals(SPANS))
    server = _server_latency(server0, _server_latency_counts())
    parts = [np.load(out) for _, out in state["gens"]]
    cat = {k: np.concatenate([p[k] for p in parts])
           for k in ("index", "due", "sent", "handed", "done", "status",
                     "size")}
    replies = {int(k[6:]): p[k] for p in parts for k in p.files
               if k.startswith("reply_")}
    order = np.argsort(cat["index"])
    cat = {k: v[order] for k, v in cat.items()}
    ok = cat["status"] == OK
    n = len(ok)
    latency_ms = np.sort(np.where(ok, (cat["done"] - cat["due"]) * 1e3,
                                  np.inf))

    def tail(q: float) -> float:
        """The q-quantile over ALL requests, failed ones at the top; where
        the failed reach down to it, it is the whole wait."""
        value = latency_ms[int(np.ceil(q * n)) - 1]
        return float(value if np.isfinite(value)
                     else (seconds + t["straggler_s"]) * 1e3)

    late_ms = np.sort((cat["sent"] - cat["due"])[np.isfinite(cat["sent"])]
                      * 1e3)
    by_due = np.where(ok, (cat["done"] - cat["due"]) * 1e3, np.inf)
    thirds = [float(np.sort(by_due[(cat["due"] >= a * seconds / 3)
                                   & (cat["due"] < (a + 1) * seconds / 3)]
                            )[-max(1, n // 300)]) for a in (0, 2)]
    rows_ok = int(cat["size"][ok].sum())
    state["replies"], state["results"] = replies, cat
    return {
        "attempted": n, "failed": int(n - ok.sum()),
        "metrics": {"serve_p50_ms": tail(0.50),
                    "serve_items_per_s": rows_ok / seconds},
        "spans": spans,
        "counters": {
            "rows_gathered": rows_ok, "requests": n,
            "shed": int((cat["status"] == 2).sum()),
            "unanswered": int((cat["status"] == 0).sum()),
            "serve_p90_ms": tail(0.90), "serve_p95_ms": tail(0.95),
            "serve_p99_ms": tail(0.99), "serve_p995_ms": tail(0.995),
            "gen_late_p99_ms": float(
                late_ms[int(np.ceil(0.99 * len(late_ms))) - 1]),
            "p99_first_third_ms": thirds[0], "p99_last_third_ms": thirds[1],
            "send_call_max_ms": float(np.nanmax(cat["handed"] - cat["sent"])
                                      * 1e3),
            "server_total_p99_ms": server[0], "server_total_max_ms": server[1],
            "setup_compile_s": state["warm_s"], "build_s": state["build_s"]},
    }


def _server_latency_counts():
    """The service's own ``serve.latency.total`` histogram (request read to
    reply written, every request): (count, bucket counts, max)."""
    from multiverso_tpu.telemetry.metrics import get_registry
    h = get_registry().histogram("serve.latency.total")
    count, counts = h.raw_counts()
    return count, counts, h.snapshot()["max_ms"]


def _server_latency(before, after) -> tuple:
    """(p99, max so far) of the server-side latency over the window, from
    the histogram's bucket deltas: how much of the tail is the server's."""
    from multiverso_tpu.telemetry.metrics import Histogram
    delta = [a - b for a, b in zip(after[1], before[1])]
    total = after[0] - before[0]
    return (float(Histogram.percentile_from_counts(delta, total, 0.99)),
            float(after[2]))


def check(state, ctx) -> None:
    """Nothing before the window: this cell's answers are compared one by
    one once the window has closed (``verify``)."""


def measure(state, ctx) -> dict:
    return run_window(state, ctx)


def expected_rows(ctx, seed: int, request: int, size: int) -> np.ndarray:
    """The reference's answer: the request's keys, made again from the seed,
    and those rows of the seeded input table, in request order."""
    c, t = ctx.config, ctx.traffic
    keys = traffic_gen.zipf_keys(seed, request, size, t["zipf"], c["vocab"],
                                 traffic_gen.key_permutation(c["vocab"]))
    init = c["init"][0]
    return seeded.rows_np(ctx.seed, 0, keys, c["embedding_size"],
                          init["scale"], init["kind"])


def verify(state, ctx) -> None:
    """Every reply of the seeded sample equals the table's rows bit for bit,
    in request order; every request is accounted for; none failed."""
    win, cat = state["window"], state["results"]
    wrong = 0
    for i in state["sample"]:
        got = state["replies"].get(i)
        want = expected_rows(ctx, win["seed"], i, int(win["sizes"][i]))
        if got is None or got.dtype != want.dtype \
                or not np.array_equal(got, want):
            wrong += 1
    m = ctx.measured
    ctx.checks.add("sample_replies_wrong", wrong, 0, "eq")
    ctx.checks.add("sample_replies_compared", len(state["sample"]),
                   min(ctx.traffic["sample"], m["attempted"]), "min")
    ctx.checks.add("requests_accounted", len(cat["index"]),
                   int(round(win["rate"] * win["seconds"])), "eq")
    ctx.checks.add("requests_failed", m["failed"],
                   ctx.limit("requests_failed"), "max")


def close(state) -> None:
    import multiverso_tpu as mv
    stop_generators(state)
    state["service"].close()
    if state.get("w2v") is not None:
        mv.shutdown()
