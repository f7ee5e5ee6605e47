"""A part of the program's own start-up timeline
(``multiverso_tpu.telemetry.startup.report()``): the seconds from the
process's start to its first completed unit that the program booked under
``part`` (several joined by ``+`` are summed), or one of the numbers beside
the parts (``ready_s``, ``cache_misses``). None where the program has no
timeline (a tree from before it) or never got ready.

The first call of a run prints the timeline: one ``startup`` line with every
part, then one ``startup.compile`` line a program compiled or fetched before
ready (``program cache trace_s lower_s backend_s|fetch_s under``), so that a
chip run's log says which program missed the cache."""


def _print(rep) -> None:
    beside = ("compile_other_threads_s", "transfers_landed_s",
              "transfers_pending", "programs", "cache_hits", "cache_misses",
              "process_start_from", "backend_ready_at_import")
    fields = [("ready_s", rep["ready_s"]), *rep["parts"].items(),
              *((k, rep.get(k)) for k in beside),
              ("after_ready", rep["after_ready"]["count"])]
    print("startup " + " ".join(
        f"{k}={v:.3f}" if isinstance(v, float) else f"{k}={v}"
        for k, v in fields), flush=True)
    for r in rep["compiles"]:
        last = "fetch_s" if r["cache"] == "hit" else "backend_s"
        print(f"startup.compile {r['program']} {r['cache']} "
              f"trace_s={r['trace_s']:.3f} lower_s={r['lower_s']:.3f} "
              f"{last}={r[last]:.3f} under={r['under']}", flush=True)
    for r in rep["after_ready"]["last"]:
        print(f"startup.after_ready {r['program']} {r['cache']} "
              f"under={r['under']}", flush=True)


def read(ctx, part):
    try:
        from multiverso_tpu.telemetry import startup
    except ImportError:
        return None
    rep = startup.report()
    parts = rep.get("parts")
    if parts is None:
        return None
    if not getattr(ctx, "startup_printed", False):
        ctx.startup_printed = True
        _print(rep)
    return sum(float(parts[name] if name in parts else rep[name])
               for name in part.split("+"))
