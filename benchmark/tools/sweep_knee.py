#!/usr/bin/env python3
"""Find the knee of an open-loop lookup mix by ONE sweep on the chip, in one
process: ``python3 benchmark/tools/sweep_knee.py --workload w2v_lookup
--start 200 --steps 9 --seconds 10``. The server is set up once; each step
offers 1.25x the last step's rate for ``--seconds`` from fresh generator
processes. A rate is sustained when nothing was shed, 99% of the offered
requests completed, the generators ran under 1 ms late at the 99th
percentile, and the p99 of the window's last third is within 1.5x of its
first third's. The knee is the highest sustained rate; the cell's rate is four
fifths of it, rounded down to two figures."""
import argparse
import json
import math
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(_HERE)))


def two_figures_down(x: float) -> float:
    mag = 10 ** (math.floor(math.log10(x)) - 1)
    return math.floor(x / mag) * mag


def main(argv=None, require_chip: bool = True) -> int:
    import harness
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", default="w2v_lookup")
    ap.add_argument("--start", type=float, default=200.0)
    ap.add_argument("--steps", type=int, default=9)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=20260927)
    args = ap.parse_args(argv)
    ctx, driver = harness.open_cell(args.workload, args.seed, args.seconds,
                                    False, require_chip)
    ctx.traffic["rate"] = args.start
    state = driver.setup(ctx)
    knee = None
    try:
        rate = args.start
        for step in range(args.steps):
            if step:
                driver.stop_generators(state)
                driver.start_generators(state, ctx, rate, args.seconds,
                                        args.seed + step)
            m = driver.run_window(state, ctx)
            c = m["counters"]
            sustained = (c["shed"] == 0
                         and m["failed"] <= 0.01 * m["attempted"]
                         and c["gen_late_p99_ms"] < 1.0
                         and c["p99_last_third_ms"]
                         <= 1.5 * c["p99_first_third_ms"])
            print(json.dumps({
                "rate": rate, "sustained": bool(sustained),
                "attempted": m["attempted"], "failed": m["failed"],
                "shed": c["shed"], "unanswered": c["unanswered"],
                "p50_ms": m["metrics"]["serve_p50_ms"],
                "p99_ms": c["serve_p99_ms"],
                "items_per_s": m["metrics"]["serve_items_per_s"],
                "gen_late_p99_ms": c["gen_late_p99_ms"],
                "p99_first_third_ms": c["p99_first_third_ms"],
                "p99_last_third_ms": c["p99_last_third_ms"]}), flush=True)
            if sustained:
                knee = rate
            rate *= 1.25
    finally:
        driver.close(state)
    if knee is None:
        print("no rate of the sweep was sustained: start lower")
        return 1
    print(json.dumps({"knee": knee, "four_fifths": two_figures_down(0.8 * knee),
                      "0.65": two_figures_down(0.65 * knee)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
