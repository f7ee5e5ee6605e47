#!/usr/bin/env python3
"""CPU rehearsal of one cell at tiny sizes: ``python3
benchmark/tests/rehearse.py <tmpdir> --workload ... --seed ... --seconds ...
--trace ...``. It skips the harness's look for a chip and nothing else: the
same run.py, harness, drivers, readers and references run."""
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)
sys.path.insert(0, os.path.dirname(_HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(_HERE)))


def main() -> int:
    import run
    import tiny
    tmp, argv = sys.argv[1], sys.argv[2:]
    root = os.path.join(tmp, "root")
    if not os.path.isdir(root):
        tiny.make_root(root)
    return run.main(argv, require_chip=False, root=root,
                    bench_dir=os.path.join(root, "benchmark"))


if __name__ == "__main__":
    sys.exit(main())
