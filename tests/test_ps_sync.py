"""Cross-process BSP on the DCN PS path (VERDICT r2 next-round #2).

Port of the reference sync tests at world > 1: ``Test/unittests/
test_sync.cpp:9-44`` (every worker's i-th Get sees identical parameters)
and ``Test/test_array_table.cpp:14-42`` (the self-checking invariant
``data == delta * (i+1) * num_workers`` after round i under -sync=true).

Tier 1: two PSServices in ONE process, 2 logical ranks x 2 local worker
threads = 4 BSP workers, all ops clock-gated through the services' single
dispatcher threads (LocalForward disabled in sync mode). Tier 2 (slow):
the same invariant with 2 REAL processes x 2 threads.
"""

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import multiverso_tpu as mv
from multiverso_tpu.core.options import AddOption, GetOption
from multiverso_tpu.parallel.ps_service import (DistributedArrayTable,
                                                PSService)


@pytest.fixture
def sync_two_rank_world():
    """-sync=true world: 2 ranks x 2 local workers in one process."""
    mv.init(["-sync=true"], num_local_workers=2)
    svc0 = PSService()
    svc1 = PSService()
    peers = [svc0.address, svc1.address]
    yield svc0, svc1, peers
    svc0.close()
    svc1.close()
    mv.shutdown()


def _worker_loop(table, local_wid, rounds, size, views, errors):
    delta = np.ones(size, dtype=np.float32)
    try:
        for i in range(rounds):
            table.add(delta, AddOption(worker_id=local_wid))
            got = table.get(GetOption(worker_id=local_wid))
            views.append((i, got.copy()))
    except Exception as e:  # noqa: BLE001 - surfaced by the main thread
        errors.append(e)


def test_sync_identical_views_2rank_2thread(sync_two_rank_world):
    """Every worker's i-th Get is identical — and equal to the closed form
    delta * (i+1) * num_workers (ref test_array_table.cpp:14-42)."""
    svc0, svc1, peers = sync_two_rank_world
    size, rounds = 32, 5
    t0 = DistributedArrayTable(1, size, svc0, peers, rank=0)
    t1 = DistributedArrayTable(1, size, svc1, peers, rank=1)
    assert t0._bsp and t1._bsp

    views = {k: [] for k in range(4)}
    errors = []
    threads = [
        threading.Thread(target=_worker_loop,
                         args=(table, lw, rounds, size,
                               views[r * 2 + lw], errors))
        for r, table in ((0, t0), (1, t1)) for lw in (0, 1)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=120)
        assert not th.is_alive(), "BSP worker wedged"
    assert not errors, errors

    for w, seq in views.items():
        assert len(seq) == rounds
        for i, got in seq:
            np.testing.assert_allclose(
                got, np.full(size, (i + 1) * 4.0),
                err_msg=f"worker {w} round {i}")


def test_sync_finish_train_releases_stragglers(sync_two_rank_world):
    """A worker that stops participating retires via Server_Finish_Train
    (clock -> infinity, ref src/server.cpp:190-213); the others' gates
    then exclude it and training drains deterministically."""
    svc0, svc1, peers = sync_two_rank_world
    size = 16
    t0 = DistributedArrayTable(2, size, svc0, peers, rank=0)
    t1 = DistributedArrayTable(2, size, svc1, peers, rank=1)

    short_rounds, long_rounds = 2, 4
    views = {k: [] for k in range(4)}
    errors = []

    def short_worker():     # rank 0, local worker 0: quits early
        _worker_loop(t0, 0, short_rounds, size, views[0], errors)
        t0.finish_train(0)

    threads = [threading.Thread(target=short_worker)] + [
        threading.Thread(target=_worker_loop,
                         args=(table, lw, long_rounds, size,
                               views[r * 2 + lw], errors))
        for r, table, lw in ((0, t0, 1), (1, t1, 0), (1, t1, 1))]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=120)
        assert not th.is_alive(), "BSP worker wedged after finish_train"
    assert not errors, errors

    # closed form: 3 live workers + the retiree's min(i+1, short) adds
    for w in (1, 2, 3):
        for i, got in views[w]:
            expect = 3.0 * (i + 1) + min(i + 1, short_rounds)
            np.testing.assert_allclose(got, np.full(size, expect),
                                       err_msg=f"worker {w} round {i}")


def test_async_mode_unaffected(mv_env):
    """Without -sync the gate must not exist: LocalForward stays on and no
    clock state is allocated."""
    svc0, svc1 = PSService(), PSService()
    peers = [svc0.address, svc1.address]
    t0 = DistributedArrayTable(3, 10, svc0, peers, rank=0)
    DistributedArrayTable(3, 10, svc1, peers, rank=1)
    assert not t0._bsp
    assert not svc0._sync and not svc1._sync
    t0.add(np.ones(10, dtype=np.float32))
    np.testing.assert_allclose(t0.get(), np.ones(10))
    svc0.close(); svc1.close()


def test_per_worker_updater_state_spans_dcn_world(mv_env):
    """AdaGrad per-worker accumulators must be sized by the DCN worker
    universe (world x local), not zoo.num_workers() — separate JAX runtimes
    report process_count()==1, so remote ranks' stamped worker ids would
    index out of bounds and their G^2 updates silently drop (review r3)."""
    svc0, svc1 = PSService(), PSService()
    peers = [svc0.address, svc1.address]
    t0 = DistributedArrayTable(4, 8, svc0, peers, rank=0, updater="adagrad")
    t1 = DistributedArrayTable(4, 8, svc1, peers, rank=1, updater="adagrad")
    assert t0.local_store.num_workers == 2
    # each rank's adds must land in DISTINCT accumulator slots
    t0.add(np.ones(8, dtype=np.float32), AddOption(worker_id=0))
    t1.add(np.ones(8, dtype=np.float32), AddOption(worker_id=0))
    g2 = np.asarray(t0.local_store.state["g2"])
    assert g2.shape[0] == 2
    shard = t0.offsets[1] - t0.offsets[0]    # real rows; rest is padding
    assert (g2[0][:shard] > 0).all() and (g2[1][:shard] > 0).all()
    svc0.close(); svc1.close()


_SYNC_WORKER = r"""
import os, sys, threading, time
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
import numpy as np
import multiverso_tpu as mv
from multiverso_tpu.core.options import AddOption, GetOption

rank = int(sys.argv[1]); rendezvous = sys.argv[2]
mv.init(["-sync=true"], num_local_workers=2)
addr = mv.net_bind()
with open(os.path.join(rendezvous, f"addr{rank}"), "w") as f:
    f.write(f"{addr[0]}:{addr[1]}")
other = os.path.join(rendezvous, f"addr{1 - rank}")
for _ in range(600):
    if os.path.exists(other):
        break
    time.sleep(0.05)
host, port = open(other).read().split(":")
peers = [None, None]
peers[rank] = addr
peers[1 - rank] = (host, int(port))
mv.net_connect(peers)
table = mv.create_distributed_array_table(1, 32, rank=rank)
assert table._bsp, "sync flag did not arm BSP"

ROUNDS = 5
delta = np.ones(32, dtype=np.float32)
failures = []

def loop(lw):
    try:
        for i in range(ROUNDS):
            table.add(delta, AddOption(worker_id=lw))
            got = table.get(GetOption(worker_id=lw))
            if not np.allclose(got, (i + 1) * 4.0):
                failures.append((lw, i, got[0]))
                return
    except Exception as e:
        failures.append((lw, "exc", repr(e)))

threads = [threading.Thread(target=loop, args=(lw,)) for lw in (0, 1)]
for t in threads: t.start()
for t in threads: t.join(timeout=120)
assert not failures, failures
print(f"SYNC_RANK{rank}_OK")
with open(os.path.join(rendezvous, f"done{rank}"), "w") as f:
    f.write("ok")
peer_done = os.path.join(rendezvous, f"done{1 - rank}")
for _ in range(600):
    if os.path.exists(peer_done):
        break
    time.sleep(0.05)
mv.shutdown()
"""


@pytest.mark.slow
def test_two_process_two_thread_sync(tmp_path):
    """The VERDICT-prescribed shape: 2 processes x 2 threads, -sync=true,
    every worker's i-th Get equals delta * (i+1) * 4."""
    script = tmp_path / "syncworker.py"
    script.write_text(_SYNC_WORKER)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    procs = [subprocess.Popen(
        [sys.executable, str(script), str(r), str(tmp_path)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env)
        for r in range(2)]
    for r, p in enumerate(procs):
        try:
            out, err = p.communicate(timeout=180)
        except subprocess.TimeoutExpired:
            p.kill()
            pytest.fail("sync worker timed out")
        assert p.returncode == 0, f"rank {r} failed:\n{err[-2000:]}"
        assert f"SYNC_RANK{r}_OK" in out


def test_bsp_fuzz_identical_views_with_jitter(sync_two_rank_world):
    """Fuzz the clock-gated dispatch: 2 ranks x 2 local workers with
    random per-round deltas and random timing jitter. The BSP invariant
    must hold regardless of interleaving: every worker's i-th Get is
    IDENTICAL across all four workers, and equals the sum of all
    workers' rounds 0..i of deltas."""
    import random

    svc0, svc1, peers = sync_two_rank_world
    size, rounds = 16, 6
    t0 = DistributedArrayTable(40, size, svc0, peers, rank=0)
    t1 = DistributedArrayTable(40, size, svc1, peers, rank=1)

    # delta[w][i]: deterministic per (worker, round) so the closed form
    # is computable; values differ per worker/round.
    def delta(w, i):
        return np.full(size, float((w + 1) * 100 + i), dtype=np.float32)

    views = {w: [] for w in range(4)}
    errors = []

    def worker(table, lw, gid, seed):
        rng = random.Random(seed)
        try:
            for i in range(rounds):
                time.sleep(rng.random() * 0.02)
                table.add(delta(gid, i), AddOption(worker_id=lw))
                time.sleep(rng.random() * 0.02)
                views[gid].append(table.get(GetOption(worker_id=lw)))
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=worker,
                                args=(tbl, lw, r * 2 + lw, 31 + r * 2 + lw))
               for r, tbl in ((0, t0), (1, t1)) for lw in (0, 1)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=180)
        assert not th.is_alive(), "BSP fuzz worker wedged"
    assert not errors, errors

    for i in range(rounds):
        expect = np.zeros(size, dtype=np.float32)
        for w in range(4):
            for j in range(i + 1):
                expect += delta(w, j)
        for w in range(4):
            np.testing.assert_allclose(
                views[w][i], expect,
                err_msg=f"worker {w} round {i} diverged")
