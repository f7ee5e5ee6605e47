#!/usr/bin/env python3
"""Where a traced window's time went, by the program's phases: ``python3
benchmark/tools/phase_split.py --workload <cell> --seed <n> [--seconds <s>]``
runs the cell once through the harness's own set-up and traced window and
prints (a) the window's own numbers, (b) every host annotation with its
count, total, mean and longest occurrence, the share of the window under it
and the share of device 0's idle time under it (``readers/idle_under``), (c)
the idle time split by the INNERMOST annotation over each piece (the
shortest one covering it, so a table phase wins over the ``recsys.pull``
around it and the parts sum to the whole), and (d) for a serving cell the
stalls: runs of slow requests by when they were due, beside every
occurrence of a phase longer than ``--long-ms``, on one clock. The numbers
PERF.md's breakdowns quote come from here; the judged run is ``run.py``."""
import argparse
import json
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(_HERE)))


def innermost_split(trace, window, device, idle_under) -> dict:
    """Idle seconds by the innermost annotation over each piece of each
    gap; ``unattributed`` where none is. Every name takes part, the
    benchmark's own too (they hold what the program's spans leave)."""
    gaps = idle_under.idle_gaps(trace, window, device)
    anns = [(a, b, n) for a, b, n in trace.annotations
            if b > window[0] and a < window[1]]
    out: dict = {}
    for g0, g1 in gaps:
        over = [(a, b, n) for a, b, n in anns if b > g0 and a < g1]
        edges = sorted({g0, g1} | {t for a, b, _ in over for t in (a, b)
                                   if g0 < t < g1})
        for lo, hi in zip(edges, edges[1:]):
            cover = [(b - a, n) for a, b, n in over if a <= lo and b >= hi]
            name = min(cover)[1] if cover else "unattributed"
            out[name] = out.get(name, 0.0) + (hi - lo) / 1e9
    return out


def main(argv=None, require_chip: bool = True, **where) -> int:
    import harness
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--long-ms", type=float, default=20.0)
    args = ap.parse_args(argv)
    ctx, driver = harness.open_cell(args.workload, args.seed, 30.0, True,
                                    require_chip, **where)
    ctx.seconds = args.seconds or float(
        ctx.traffic.get("trace_seconds", ctx.seconds))
    idle_under = harness.load_module("readers", "idle_under", ctx.bench_dir)
    stat = harness.load_module("readers", "annotation_stat", ctx.bench_dir)
    state = driver.setup(ctx)
    try:
        driver.check(state, ctx)
        with harness.traced_window(ctx):
            ctx.measured = driver.measure(state, ctx)
        driver.verify(state, ctx)
        results = state.get("results") if isinstance(state, dict) else None
    finally:
        driver.close(state)
    tr, win = ctx.trace_data, ctx.trace_window
    m = ctx.measured
    print("window " + json.dumps({
        "workload": args.workload, "seed": args.seed,
        "window_s": ctx.window_s, "attempted": m["attempted"],
        "failed": m["failed"], "correct": ctx.checks.ok,
        "metrics": m.get("metrics"),
        "counters": {k: v for k, v in m.get("counters", {}).items()
                     if isinstance(v, (int, float))}}), flush=True)
    if tr is None or win is None:
        return 1
    dev = tr.devices()[0] if tr.devices() else None
    win_s = (win[1] - win[0]) / 1e9
    if dev is not None:
        _, idle_s, _ = idle_under.idle_seconds_under(tr, win, dev,
                                                     lambda n: False)
        print(f"device {dev}: idle {idle_s:.6f} s of {win_s:.6f} s",
              flush=True)
    names = sorted({n for a, b, n in tr.annotations
                    if b > win[0] and a < win[1]})
    print(f"window under any annotation of the program: "
          f"{stat.read(ctx, '^(?!bench[.])', 'window_share') or 0.0:.3f} %; "
          f"first one starts "
          f"{(min([a for a, _, n in tr.annotations if not n.startswith('bench.')], default=win[0]) - win[0]) / 1e6:.1f}"
          f" ms into the window", flush=True)
    print("annotation count total_ms mean_ms max_ms window_share "
          "idle_under_share")
    for n in names:
        whole = [(b - a) / 1e6 for a, b, x in tr.annotations
                 if x == n and a >= win[0] and b <= win[1]]
        pat = "^" + n.replace(".", r"\.") + "$"
        share = stat.read(ctx, pat, "window_share")
        under = idle_under.read(ctx, pat, device=dev) \
            if dev is not None else None
        print(f"{n} {len(whole)} {sum(whole):.3f} "
              f"{sum(whole) / max(len(whole), 1):.4f} "
              f"{max(whole, default=0.0):.3f} {share:.3f} "
              f"{'-' if under is None else format(under, '.3f')}",
              flush=True)
    if dev is not None:
        split = innermost_split(tr, win, dev, idle_under)
        whole = sum(split.values())
        print("idle time by the innermost annotation (s, % of idle):")
        for n, s in sorted(split.items(), key=lambda kv: -kv[1]):
            print(f"  {n} {s:.6f} {100.0 * s / whole:.3f}", flush=True)
    # serving: stalls beside long phases, seconds from the window's start
    wait = tr.annotation_window("bench.serve_wait")
    if results is not None and wait is not None:
        import numpy as np
        # the generators start 0.25 s after bench.serve_wait opens
        origin = wait[0] + 0.25e9
        lat = (results["done"] - results["due"]) * 1e3
        slow = np.flatnonzero(~(lat < args.long_ms))
        runs, start = [], None
        for i in slow:
            if start is None or results["due"][i] - results["due"][last] > 0.1:
                if start is not None:
                    runs.append((start, last))
                start = i
            last = i
        if start is not None:
            runs.append((start, last))
        print(f"stalls (requests over {args.long_ms} ms, in runs): "
              f"{len(runs)} runs, {len(slow)} requests of {len(lat)}")
        for a, b in runs:
            print(f"  due {results['due'][a]:.4f}..{results['due'][b]:.4f} s "
                  f"requests {int(results['index'][a])}.."
                  f"{int(results['index'][b])} "
                  f"worst {float(np.max(lat[a:b + 1])):.2f} ms", flush=True)
        print(f"phases longer than {args.long_ms} ms (but the waits):")
        for a, b, n in tr.annotations:
            if (b - a) / 1e6 >= args.long_ms and b > win[0] and a < win[1] \
                    and n.split(".")[-1] not in ("idle", "wait") \
                    and not n.startswith("bench."):
                print(f"  {n} at {(a - origin) / 1e9:.4f} s for "
                      f"{(b - a) / 1e6:.2f} ms", flush=True)
        folds = [(a, b) for a, b, n in tr.annotations
                 if n == "telemetry.sketch_fold"]
        print("sketch folds: " + json.dumps(
            [[round((a - origin) / 1e9, 4), round((b - a) / 1e6, 3)]
             for a, b in folds]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
