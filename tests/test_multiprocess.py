"""Real multi-process distributed tests: two jax processes on one host over
the coordination service (the reference tier-2 ladder: ``mpirun -np N`` on
one box, SURVEY.md §4).

Each subprocess runs ``mv.init`` with -coordinator/-world_size/-rank flags
(the RegisterNode analog), checks rank/size/barrier, and validates that
``mv.aggregate`` sums contributions across processes — the
``Test/test_allreduce.cpp:11-20`` invariant at world size 2.
"""

import os
import socket
import subprocess
import sys

import pytest

_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
import numpy as np
import multiverso_tpu as mv

coordinator, rank = sys.argv[1], int(sys.argv[2])
mv.init([f"-coordinator={coordinator}", "-world_size=2", f"-rank={rank}"])
assert mv.rank() == rank, (mv.rank(), rank)
assert mv.size() == 2
mv.barrier()
out = mv.aggregate(np.full(8, float(rank + 1), dtype=np.float32))
# 1.0 + 2.0 from the two ranks
np.testing.assert_allclose(out, np.full(8, 3.0))
# 2-D model-average shape through the same psum path
mat = mv.aggregate(np.full((4, 3), float(rank + 1), dtype=np.float32))
np.testing.assert_allclose(mat, np.full((4, 3), 3.0))
mv.barrier()
mv.shutdown()
print(f"RANK{rank}_OK")
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.slow
def test_two_process_aggregate(tmp_path):
    port = _free_port()
    coordinator = f"127.0.0.1:{port}"
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)   # single CPU device per process
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    procs = [subprocess.Popen(
        [sys.executable, str(script), coordinator, str(r)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env)
        for r in range(2)]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=180)
        except subprocess.TimeoutExpired:
            p.kill()
            pytest.fail("multiprocess worker timed out")
        outs.append((p.returncode, out, err))
    for r, (rc, out, err) in enumerate(outs):
        assert rc == 0, f"rank {r} failed:\n{err[-2000:]}"
        assert f"RANK{r}_OK" in out
