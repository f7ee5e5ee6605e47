#!/usr/bin/env python3
"""``python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: run one cell of BENCHMARK.json once on the chips of this
machine and print one JSON object as the last line of standard output
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, and
``breakdown`` in a traced run). Exits non-zero and prints no result when jax
finds no TPU or fewer chips than the cell asks for."""
import time

T_PROCESS = time.perf_counter()

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)
sys.path.insert(0, os.path.dirname(_HERE))


def main(argv=None, require_chip: bool = True, **where) -> int:
    """``where`` (``root``, ``bench_dir``) points a rehearsal at a copy of the
    benchmark's files; the command itself never passes it."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    import harness
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace),
                                  require_chip=require_chip,
                                  t_process=T_PROCESS, **where)
    except harness.BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        return 2
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
