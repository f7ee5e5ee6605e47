#!/usr/bin/env python
"""Parameter-server table tour: every table type, sync/async, checkpointing.

Run:  python examples/ps_tables_demo.py
(on the devices jax finds; ``JAX_PLATFORMS=cpu`` for a CPU run)
"""

import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))



def main() -> int:
    import multiverso_tpu as mv
    from multiverso_tpu.core import checkpoint as ckpt
    from multiverso_tpu.parallel.async_engine import (AsyncTableEngine,
                                                      WorkerPool)

    mv.init([])
    try:
        print(f"runtime: {mv.num_servers()} server shards, "
              f"{mv.num_workers()} workers")

        # 1-D array table with the AdaGrad updater
        arr = mv.create_table(mv.ArrayTableOption(size=1000,
                                                  updater="adagrad"))
        arr.add(np.ones(1000, dtype=np.float32),
                mv.AddOption(learning_rate=0.1, rho=0.1))
        print("array[0:4] after one adagrad add:", arr.get()[:4])

        # row-sharded matrix, row-granular ops
        mat = mv.create_table(mv.MatrixTableOption(num_row=10_000,
                                                   num_col=64))
        rows = [5, 9_999]
        mat.add_rows(rows, np.ones((2, 64), dtype=np.float32))
        print("matrix rows touched:", mat.get_rows(rows)[:, 0])

        # async ASGD through the native staging buffer
        eng = AsyncTableEngine(arr, flush_pending=128)
        WorkerPool(8).run(
            lambda wid: [eng.add_async(np.full(1000, 0.001,
                                               dtype=np.float32))
                         for _ in range(100)])
        print("after 800 async adds, array[0] =", eng.get()[0])

        # KV table
        kv = mv.create_table(mv.KVTableOption())
        kv.add([42, 7], [1.0, 2.0])
        print("kv[42], kv[7] =", kv.get([42, 7]))

        # checkpoint / resume
        workdir = tempfile.mkdtemp(prefix="mv_ckpt_")
        path = ckpt.save_all(workdir, step=1)
        arr.add(np.full(1000, 100.0, dtype=np.float32))
        ckpt.load_all(path)
        print("after save -> clobber -> restore, array[0] =", arr.get()[0])

        # allreduce (model-average mode's aggregate)
        print("aggregate(ones) =", mv.aggregate(np.ones(4))[:2],
              f"(world size {mv.size()})")
        return 0
    finally:
        mv.shutdown()


if __name__ == "__main__":
    sys.exit(main())
