#!/usr/bin/env python3
"""Readings a limit is set from: ``python3 benchmark/tools/limits.py
--workload <cell> --seeds 1,2,3,...`` builds the cell's system once and, for
each seed, reads every number its check compares, for the sound program and
for the control (the reference computed in the next lower precision, put in
the program's place). One process, one set-up; run it on the chip at the
cell's own size. Training cells only: the lookup cell's comparison is exact.
"""
import argparse
import json
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(_HERE)))


def main(argv=None, require_chip: bool = True) -> int:
    import harness
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=4)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    ctx0, driver = harness.open_cell(args.workload, seeds[0], 1.0, False,
                                     require_chip)
    rows = driver.limit_readings(
        lambda seed: harness.Context(ctx0.cell, ctx0.config, ctx0.traffic,
                                     seed, 1.0, False, ctx0.device),
        seeds, args.control_seeds)
    for row in rows:
        print(json.dumps(row), flush=True)
    names = sorted({k for r in rows for k in r["gaps"]})
    for name in names:
        sound = [r["gaps"][name] for r in rows if r["side"] == "sound"
                 and name in r["gaps"]]
        control = [r["gaps"][name] for r in rows if r["side"] == "control"
                   and name in r["gaps"]]
        print(f"{name}: sound max {max(sound):.3e} over {len(sound)} seeds; "
              f"control min {min(control):.3e} over {len(control)} seeds"
              if control else f"{name}: sound max {max(sound):.3e}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
