#!/usr/bin/env python
"""Telemetry report/diff/merge CLI over ``-telemetry_dir`` output.

Reads the snapshot + trace files a run wrote into its telemetry directory
(``metrics-<pid>-<seq>.json`` / ``trace-<pid>.json``, schema in
docs/OBSERVABILITY.md) and renders a metric catalog per process:
histogram percentiles, gauge extrema, counters.

Usage:

    # catalog of one run
    python scripts/telemetry_report.py /tmp/t

    # diff two runs (e.g. dispatch_mode=pipelined_host vs in_graph)
    python scripts/telemetry_report.py /tmp/t_new --baseline /tmp/t_old

    # merge per-rank Chrome traces into one Perfetto-loadable file
    python scripts/telemetry_report.py /tmp/t --merge-trace /tmp/merged.json

    # stitch distributed request traces across processes: only spans
    # carrying a trace context, grouped by trace id, with cross-process
    # flow arrows (client -> router -> replica)
    python scripts/telemetry_report.py /tmp/t --stitch /tmp/stitched.json
    python scripts/telemetry_report.py /tmp/t --stitch /tmp/one.json \\
        --trace-id 00c0ffee...   # a single request's end-to-end timeline

    # read the flight recorder's crash/wedge artifacts: reason, thread
    # stacks, watchdog ages, active alerts, log tail
    python scripts/telemetry_report.py /tmp/t --postmortem

    # data-plane hot keys: per-surface traffic-sketch tables (keys,
    # bytes, top-1/top-K share, the heavy hitters with error bounds)
    # merged across the run's processes
    python scripts/telemetry_report.py /tmp/t --hotkeys

    # cross-process flamegraph: merged sampling-profiler aggregates
    # (folded stacks + per-plane CPU attribution) from the snapshots
    python scripts/telemetry_report.py /tmp/t --profile

    # critical-path attribution: per-trace phase ledgers over the
    # stitched spans — phase shares, conservation rate, residual, the
    # slowest requests' ledgers verbatim
    python scripts/telemetry_report.py /tmp/t --critical-path

No jax import: usable on any host, including ones without a TPU.
"""

import argparse
import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# Final snapshots further apart than this are treated as belonging to
# different runs of a reused -telemetry_dir (ranks of one run stop within
# seconds of each other; separate runs are minutes-to-days apart).
RUN_SPLIT_SECONDS = 300.0


def latest_snapshots(telemetry_dir):
    """Final (highest-seq) snapshot per pid of the NEWEST run.

    Nothing cleans a reused ``-telemetry_dir``, so the directory may hold
    snapshots from several runs (distinct pids). Blending them would
    count-weight percentiles across unrelated runs with no warning;
    instead keep only pids whose final snapshot time is within
    ``RUN_SPLIT_SECONDS`` of the newest one, and say what was dropped."""
    best = {}
    for path in glob.glob(os.path.join(telemetry_dir, "metrics-*.json")):
        base = os.path.basename(path)[len("metrics-"):-len(".json")]
        try:
            pid, seq = (int(x) for x in base.split("-"))
        except ValueError:
            continue
        if pid not in best or seq > best[pid][0]:
            best[pid] = (seq, path)
    out = []
    for pid, (_, path) in sorted(best.items()):
        try:
            with open(path) as f:
                out.append(json.load(f))
        except (OSError, ValueError) as e:
            print(f"warning: unreadable snapshot {path}: {e}",
                  file=sys.stderr)
    times = [s.get("time_unix", 0.0) for s in out]
    if times:
        newest = max(times)
        stale = [s for s, t in zip(out, times)
                 if newest - t > RUN_SPLIT_SECONDS]
        if stale:
            print(f"warning: {telemetry_dir} holds snapshots from "
                  f"{len(stale)} older process(es) (> {RUN_SPLIT_SECONDS:.0f}s "
                  f"before the newest run); ignoring pids "
                  f"{sorted(s.get('pid') for s in stale)}", file=sys.stderr)
            out = [s for s, t in zip(out, times)
                   if newest - t <= RUN_SPLIT_SECONDS]
    return out


def combine(snapshots):
    """One name->summary view across processes: histogram counts sum and
    percentiles combine count-weighted (approximation — documented as
    such); gauges take the max over processes; counters sum."""
    hists, gauges, counters = {}, {}, {}
    for snap in snapshots:
        for name, h in snap.get("histograms", {}).items():
            agg = hists.setdefault(name, {"count": 0, "sum_ms": 0.0,
                                          "max_ms": 0.0, "_wp": [0.0] * 3})
            n = h.get("count", 0)
            agg["count"] += n
            agg["sum_ms"] += h.get("sum_ms", 0.0)
            agg["max_ms"] = max(agg["max_ms"], h.get("max_ms", 0.0))
            for i, q in enumerate(("p50", "p95", "p99")):
                agg["_wp"][i] += h.get(q, 0.0) * n
        for name, g in snap.get("gauges", {}).items():
            agg = gauges.setdefault(name, {"last": 0.0, "max": 0.0,
                                           "samples": 0})
            agg["last"] = max(agg["last"], g.get("last", 0.0))
            agg["max"] = max(agg["max"], g.get("max", 0.0))
            agg["samples"] += g.get("samples", 0)
        for name, c in snap.get("counters", {}).items():
            counters[name] = counters.get(name, 0) + c.get("value", 0)
    for agg in hists.values():
        n = max(agg["count"], 1)
        agg["p50"], agg["p95"], agg["p99"] = (w / n for w in agg.pop("_wp"))
    return hists, gauges, counters


def print_catalog(telemetry_dir, snapshots):
    print(f"== {telemetry_dir}: {len(snapshots)} process(es)")
    hists, gauges, counters = combine(snapshots)
    if hists:
        print(f"{'histogram':40s} {'count':>8s} {'p50ms':>10s} "
              f"{'p95ms':>10s} {'p99ms':>10s} {'maxms':>10s}")
        for name in sorted(hists):
            h = hists[name]
            print(f"{name:40s} {h['count']:8d} {h['p50']:10.3f} "
                  f"{h['p95']:10.3f} {h['p99']:10.3f} {h['max_ms']:10.3f}")
    if gauges:
        print(f"\n{'gauge':40s} {'last':>10s} {'max':>10s} {'samples':>8s}")
        for name in sorted(gauges):
            g = gauges[name]
            print(f"{name:40s} {g['last']:10.1f} {g['max']:10.1f} "
                  f"{g['samples']:8d}")
    if counters:
        print(f"\n{'counter':40s} {'value':>10s}")
        for name in sorted(counters):
            print(f"{name:40s} {counters[name]:10d}")


def print_diff(new_dir, base_dir):
    new_h, _, _ = combine(latest_snapshots(new_dir))
    old_h, _, _ = combine(latest_snapshots(base_dir))
    names = sorted(set(new_h) | set(old_h))
    print(f"== diff {new_dir} vs {base_dir} (histogram p95, ms)")
    print(f"{'histogram':40s} {'base':>10s} {'new':>10s} {'delta%':>8s}")
    for name in names:
        old = old_h.get(name, {}).get("p95")
        new = new_h.get(name, {}).get("p95")
        if old is None or new is None:
            tag = "new" if old is None else "gone"
            print(f"{name:40s} {'-' if old is None else f'{old:.3f}':>10s} "
                  f"{'-' if new is None else f'{new:.3f}':>10s} "
                  f"{tag:>8s}")
            continue
        if not old:
            # Zero baseline: any nonzero new value is an appearance, not
            # a 0% change; mirror the "new"/"gone" tagging above.
            tag = "new" if new else "="
            print(f"{name:40s} {old:10.3f} {new:10.3f} {tag:>8s}")
            continue
        delta = (new - old) / old * 100.0
        print(f"{name:40s} {old:10.3f} {new:10.3f} {delta:+7.1f}%")


def print_postmortems(telemetry_dir, full=False):
    """Validate + summarize every ``postmortem-<pid>.json`` under the
    directory (the wedge-watchdog / fatal-signal dumps,
    ``telemetry/flight.py``). Returns the number of VALID dumps found."""
    from multiverso_tpu.telemetry import validate_postmortem
    paths = sorted(glob.glob(os.path.join(telemetry_dir,
                                          "postmortem-*.json")))
    if not paths:
        print(f"no postmortem-*.json under {telemetry_dir}")
        return 0
    valid = 0
    for path in paths:
        print(f"== {path}")
        try:
            with open(path) as f:
                pm = json.load(f)
            validate_postmortem(pm)
        except (OSError, ValueError) as e:
            print(f"  INVALID: {e}", file=sys.stderr)
            continue
        valid += 1
        reason = pm["reason"]
        detail = " ".join(f"{k}={v}" for k, v in sorted(reason.items())
                          if k != "kind")
        print(f"  pid {pm['pid']} rank {pm['rank']}  "
              f"reason: {reason['kind']} {detail}")
        tripped = [n for n, w in sorted(pm["watchdogs"].items())
                   if w.get("tripped")]
        print(f"  threads: {len(pm['threads'])}  watchdogs: "
              f"{len(pm['watchdogs'])} ({len(tripped)} tripped"
              + (f": {', '.join(tripped)}" if tripped else "") + ")")
        for alert in pm.get("alerts", []):
            print(f"  alert firing: {alert.get('name')} "
                  f"(value {alert.get('value')})")
        logs = pm.get("flight", {}).get("logs", [])
        for line in logs[-(len(logs) if full else 5):]:
            print(f"  log| {line}")
        if full:
            for t in pm["threads"]:
                print(f"  -- thread {t['name']} "
                      f"(daemon={t.get('daemon')})")
                for frame in t.get("stack", []):
                    for ln in frame.splitlines():
                        print(f"     {ln}")
    return valid


def print_hotkeys(telemetry_dir, snapshots, topn=10):
    """Per-surface hot-key tables from the snapshots' ``sketches``
    sections (telemetry/sketch.py), merged across processes: counts of
    the same key SUM (each process saw a disjoint slice of the stream —
    the Space-Saving merge rule), totals sum, shares re-derive from the
    merged numbers. Returns the number of surfaces printed."""
    surfaces = {}
    for snap in snapshots:
        for name, s in snap.get("sketches", {}).get("surfaces",
                                                    {}).items():
            agg = surfaces.setdefault(name, {"keys": 0, "bytes": 0,
                                             "topk": {}})
            agg["keys"] += int(s.get("keys", 0))
            agg["bytes"] += int(s.get("bytes", 0))
            for key, count, err in s.get("topk", []):
                cur = agg["topk"].get(int(key), (0, 0))
                agg["topk"][int(key)] = (cur[0] + int(count),
                                         cur[1] + int(err))
    if not surfaces:
        print(f"no sketches section in any snapshot under "
              f"{telemetry_dir} (was -telemetry_sketch off, or no "
              f"data-plane traffic?)")
        return 0
    for name in sorted(surfaces):
        agg = surfaces[name]
        total = max(agg["keys"], 1)
        top = sorted(agg["topk"].items(), key=lambda kv: -kv[1][0])[:topn]
        top1 = top[0][1][0] if top else 0
        topk_sum = sum(c for _, (c, _) in top)
        print(f"== {name}: {agg['keys']} keys, {agg['bytes']} bytes, "
              f"top1 {100 * top1 / total:.1f}%, "
              f"top{len(top)} {100 * topk_sum / total:.1f}%")
        print(f"   {'key':>12s} {'count':>10s} {'max_err':>8s} "
              f"{'share%':>7s}")
        for key, (count, err) in top:
            print(f"   {key:12d} {count:10d} {err:8d} "
                  f"{100 * count / total:7.2f}")
    return len(surfaces)


def print_profile(telemetry_dir, snapshots, top=20):
    """Merged sampling-profiler view across the run's processes: plane
    CPU attribution + the hottest folded stacks (paste into a
    flamegraph tool as-is). Returns the number of merged profiles."""
    from multiverso_tpu.telemetry import merge_profiles
    states = [s["profile"] for s in snapshots if s.get("profile")]
    if not states:
        print(f"no profile section in any snapshot under {telemetry_dir} "
              f"(was -telemetry_profile off?)")
        return 0
    merged = merge_profiles(states)
    wall = max(merged.get("wall_s", 0.0), 1e-9)
    print(f"== profile: {len(states)} process(es), "
          f"{merged['samples']} samples over {merged['wall_s']:.1f}s wall")
    planes = merged.get("planes") or {}
    if planes:
        total_cpu = sum(d.get("cpu_s", 0.0) for d in planes.values())
        print(f"{'plane':12s} {'samples':>8s} {'cpu_s':>9s} "
              f"{'cpu%wall':>9s} {'share%':>7s}")
        for name in sorted(planes, key=lambda p: -planes[p]["cpu_s"]):
            d = planes[name]
            print(f"{name:12s} {d['samples']:8d} {d['cpu_s']:9.3f} "
                  f"{100 * d['cpu_s'] / wall:9.1f} "
                  f"{100 * d['cpu_s'] / max(total_cpu, 1e-9):7.1f}")
    stacks = sorted((merged.get("stacks") or {}).items(),
                    key=lambda kv: -kv[1])[:top]
    if stacks:
        print(f"\ntop {len(stacks)} folded stacks (count stack):")
        for stack, count in stacks:
            print(f"{count:6d} {stack}")
    return len(states)


def print_critical_path(telemetry_dir, slow_k=3):
    """Phase-ledger attribution over the run's stitched spans
    (telemetry/critical_path.py): aggregate phase shares, the
    conservation rate, the mean residual, and the slowest requests'
    per-trace ledgers. Returns the number of decomposed traces."""
    from multiverso_tpu.telemetry import (analyze_critical_paths,
                                          stitch_traces)
    paths = glob.glob(os.path.join(telemetry_dir, "trace-*.json"))
    if not paths:
        print(f"no trace-*.json under {telemetry_dir}", file=sys.stderr)
        return 0
    stitched = stitch_traces(paths)
    spans = [e for e in stitched["traceEvents"] if e.get("ph") == "X"]
    cp = analyze_critical_paths(spans, slow_k=slow_k, publish=False)
    print(f"== critical path: {cp['n_traces']} trace(s), "
          f"{cp['n_decomposed']} decomposed, conservation "
          f"{100 * cp['conserved_frac']:.1f}% within "
          f"{100 * cp['tolerance']:.0f}% tolerance")
    ua = cp["unattributed"]
    print(f"   residual: mean {ua['mean_ms']:.3f} ms "
          f"({100 * ua['mean_frac']:.1f}% of e2e), bridged transit "
          f"{cp['bridged_mean_ms']:.3f} ms/trace")
    e2e = cp.get("e2e_ms") or {}
    if e2e:
        print(f"   e2e ms: p50 {e2e.get('p50', 0.0):.3f}  "
              f"p95 {e2e.get('p95', 0.0):.3f}  "
              f"p99 {e2e.get('p99', 0.0):.3f}")
    if cp["phases"]:
        print(f"\n{'phase':12s} {'total_ms':>12s} {'share%':>7s}")
        for name, d in sorted(cp["phases"].items(),
                              key=lambda kv: -kv[1]["total_ms"]):
            print(f"{name:12s} {d['total_ms']:12.3f} "
                  f"{100 * d['share']:7.1f}")
    for d in cp.get("slowest", []):
        cells = " ".join(
            f"{k}={v:.2f}" for k, v in
            sorted(d["phases"].items(), key=lambda kv: -kv[1]))
        flag = "" if d["conserved"] else "  [NOT CONSERVED]"
        print(f"\nslow {d['trace'][:16]}…  e2e {d['e2e_ms']:.3f} ms  "
              f"residual {d['unattributed_ms']:.3f} ms{flag}\n   {cells}")
    return cp["n_decomposed"]


def main():
    p = argparse.ArgumentParser()
    p.add_argument("telemetry_dir", help="run's -telemetry_dir")
    p.add_argument("--baseline", default="",
                   help="another run's telemetry dir to diff against")
    p.add_argument("--merge-trace", default="",
                   help="write one merged Chrome trace for all ranks here")
    p.add_argument("--stitch", default="",
                   help="write one STITCHED Chrome trace here: only spans "
                   "carrying a distributed trace context, keyed by trace "
                   "id, with cross-process flow events on every "
                   "parent->child hop")
    p.add_argument("--trace-id", default="",
                   help="with --stitch: keep only this trace id (hex)")
    p.add_argument("--postmortem", action="store_true",
                   help="validate + summarize postmortem-*.json dumps "
                   "(wedge watchdog / fatal signal artifacts) and exit")
    p.add_argument("--hotkeys", action="store_true",
                   help="print per-surface data-plane hot-key tables "
                   "from the snapshots' traffic-sketch sections "
                   "(merged across processes) and exit")
    p.add_argument("--profile", action="store_true",
                   help="print the merged sampling-profiler view "
                   "(plane CPU attribution + hottest folded stacks) "
                   "from the snapshots' profile sections and exit")
    p.add_argument("--critical-path", action="store_true",
                   help="stitch the run's traces and print the "
                   "phase-ledger attribution: phase shares, "
                   "conservation rate, residual, slowest ledgers; exits")
    p.add_argument("--full", action="store_true",
                   help="with --postmortem: print every thread stack "
                   "and the whole log tail")
    args = p.parse_args()

    if args.postmortem:
        return 0 if print_postmortems(args.telemetry_dir,
                                      full=args.full) > 0 else 1

    if args.hotkeys:
        snapshots = latest_snapshots(args.telemetry_dir)
        if not snapshots:
            print(f"no metrics-*.json under {args.telemetry_dir}",
                  file=sys.stderr)
            return 1
        return 0 if print_hotkeys(args.telemetry_dir, snapshots) > 0 \
            else 1

    if args.profile:
        snapshots = latest_snapshots(args.telemetry_dir)
        if not snapshots:
            print(f"no metrics-*.json under {args.telemetry_dir}",
                  file=sys.stderr)
            return 1
        return 0 if print_profile(args.telemetry_dir, snapshots) > 0 \
            else 1

    if args.critical_path:
        return 0 if print_critical_path(args.telemetry_dir) > 0 else 1

    if args.merge_trace:
        from multiverso_tpu.telemetry import merge_traces
        paths = glob.glob(os.path.join(args.telemetry_dir, "trace-*.json"))
        if not paths:
            print(f"no trace-*.json under {args.telemetry_dir}",
                  file=sys.stderr)
            return 1
        merged = merge_traces(paths, out_path=args.merge_trace)
        print(f"merged {len(paths)} trace(s), "
              f"{len(merged['traceEvents'])} events -> {args.merge_trace}")

    if args.stitch:
        from multiverso_tpu.telemetry import stitch_traces, trace_index
        paths = glob.glob(os.path.join(args.telemetry_dir, "trace-*.json"))
        if not paths:
            print(f"no trace-*.json under {args.telemetry_dir}",
                  file=sys.stderr)
            return 1
        stitched = stitch_traces(paths, trace_id=args.trace_id or None,
                                 out_path=args.stitch)
        spans = [e for e in stitched["traceEvents"] if e.get("ph") == "X"]
        idx = trace_index(spans)
        print(f"stitched {len(paths)} file(s): {len(idx)} trace(s), "
              f"{len(spans)} spans -> {args.stitch}")
        # Top traces by total duration: the "where did the slow request
        # spend its time" entry point without opening Perfetto.
        by_dur = sorted(idx.items(), key=lambda kv: -kv[1]["dur_us"])[:10]
        for tid, info in by_dur:
            print(f"  {tid[:16]}…  {info['dur_us'] / 1e3:9.3f} ms  "
                  f"{info['n_spans']:3d} spans  "
                  f"{len(info['pids'])} process(es)  "
                  f"root={info['root_name']}"
                  + ("" if info["parented_ok"] else "  [orphaned spans]"))

    snapshots = latest_snapshots(args.telemetry_dir)
    if not snapshots:
        print(f"no metrics-*.json under {args.telemetry_dir}",
              file=sys.stderr)
        return 1
    print_catalog(args.telemetry_dir, snapshots)
    if args.baseline:
        print()
        print_diff(args.telemetry_dir, args.baseline)
    return 0


if __name__ == "__main__":
    sys.exit(main())
