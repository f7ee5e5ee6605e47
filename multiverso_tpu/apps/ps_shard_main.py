"""Durable PS shard CLI: one recoverable parameter-server seat.

The operator-facing shape of the ISSUE-15 durability spine: a process
that owns one rank's shard of a distributed table, journals every
accepted add to a write-ahead delta log, periodically checkpoints (and
truncates the log), and — the point — RECOVERS on restart: attach WAL ->
restore the newest shard checkpoint -> replay the log tail -> only then
announce to the membership directory, so a killed seat comes back with
state bitwise-equal to one that never died (docs/DURABILITY.md).

    # seat 1 of a 2-process world, journaled + periodically checkpointed
    python -m multiverso_tpu.apps.ps_shard_main -rank=1 \\
        -ps_peers=10.0.0.1:55555,10.0.0.2:0 -ps_table_size=100000 \\
        -wal=true -wal_dir=/data/wal -checkpoint_dir=/data/ckpt \\
        -ps_checkpoint_every_s=30 -ps_addr_file=/tmp/seat1.addr

    # kill -9 it; rerun the same command: it recovers and re-registers.

``serve_bench --recovery-drill`` drives exactly this loop (SIGKILL under
load, supervisor respawn, recovered-bytes parity) and records it in
BENCH_SERVE_FLEET15.json.
"""

from __future__ import annotations

import os
import sys
import time
from typing import List

from multiverso_tpu.apps._runner import run_app
from multiverso_tpu.utils.configure import (define_double, define_int,
                                            define_string, get_flag)
from multiverso_tpu.utils.log import check, log

define_string("ps_peers", "", "comma host:port list, one per rank (this "
              "rank's own entry is replaced by its bound address)")
define_int("ps_table_id", 900, "distributed table id to serve")
define_int("ps_table_size", 10000, "distributed array table length")
define_string("ps_addr_file", "", "write this seat's bound host:port "
              "here once it is ANNOUNCED (recovery complete)")
define_double("ps_checkpoint_every_s", 0.0, "checkpoint this rank's "
              "shard (and truncate the WAL) every N seconds; 0 = never")
define_string("checkpoint_dir", "", "shard checkpoint directory "
              "(restored on start when a shard file exists)")


def _shard_uri(ckpt_dir: str, rank: int) -> str:
    return f"file://{os.path.join(ckpt_dir, f'ps_shard{rank}.npz')}"


def _body(remaining: List[str]) -> int:
    import numpy as np  # noqa: F401 - jax bootstrap ordering

    from multiverso_tpu.core import checkpoint as ckpt
    from multiverso_tpu.parallel.ps_service import (DistributedArrayTable,
                                                    DistributedMatrixTable,
                                                    PSService)
    from multiverso_tpu.utils.configure import flag_or

    del remaining
    rank = int(get_flag("rank"))
    peers_raw = str(get_flag("ps_peers"))
    check(bool(peers_raw), "-ps_peers=host:port,... is required")
    peers = []
    for part in peers_raw.split(","):
        host, _, port = part.strip().rpartition(":")
        peers.append((host, int(port)))
    check(0 <= rank < len(peers), f"-rank={rank} outside the peer list")

    svc = PSService()
    if bool(flag_or("wal", False)):
        wal_dir = str(get_flag("wal_dir"))
        check(bool(wal_dir), "-wal=true requires -wal_dir=DIR")
        svc.attach_wal(os.path.join(wal_dir, f"rank{rank}"),
                       flush_interval_ms=float(get_flag("wal_flush_ms")),
                       sync_acks=bool(get_flag("wal_sync_acks")))
        fsync_delay_ms = float(flag_or("wal_fsync_delay_ms", 0.0))
        if fsync_delay_ms > 0:
            # Chaos drill's slow-disk seat: every commit fsync stretches
            # by this much, so sync acks slow but stay durable.
            from multiverso_tpu.core import wal as wal_mod
            wal_mod.set_fsync_delay(fsync_delay_ms / 1e3)
            log.info("ps_shard: CHAOS slow disk armed (%.0fms/fsync)",
                     fsync_delay_ms)
    peers[rank] = svc.address
    # Recovery protocol (docs/DURABILITY.md): the table registers its
    # shard but does NOT announce until state is restored — an early
    # announce lets a peer's retried add land on the fresh shard and be
    # overwritten by the restore (the acked-write loss the elastic fuzz
    # pinned).
    kind = str(flag_or("ps_table_kind", "array"))
    check(kind in ("array", "matrix"),
          f"-ps_table_kind={kind} (want array|matrix)")
    if kind == "matrix":
        # Sparse row-sharded seat: the ISSUE-16 drill extends the WAL
        # parity witness to DistributedMatrixTable shards.
        table = DistributedMatrixTable(int(get_flag("ps_table_id")),
                                       int(get_flag("ps_table_size")),
                                       int(flag_or("ps_table_cols", 8)),
                                       svc, peers, rank=rank,
                                       announce=False)
    else:
        table = DistributedArrayTable(int(get_flag("ps_table_id")),
                                      int(get_flag("ps_table_size")),
                                      svc, peers, rank=rank,
                                      announce=False)
    ckpt_dir = str(get_flag("checkpoint_dir"))
    uri = _shard_uri(ckpt_dir, rank) if ckpt_dir else ""
    from multiverso_tpu.utils.stream import exists
    if uri and exists(uri):
        ckpt.load_table(table, uri)
        log.info("ps_shard: restored shard from %s", uri)
    if svc.wal_active:
        report = svc.replay_wal()
        log.info("ps_shard: WAL replay %s", report)
    svc.enable_directory(rank, peers)

    addr_file = str(get_flag("ps_addr_file"))
    if addr_file:
        with open(addr_file + ".tmp", "w") as f:
            f.write(f"{svc.address[0]}:{svc.address[1]}")
        os.replace(addr_file + ".tmp", addr_file)
    log.info("ps_shard: rank %d serving at %s:%d (wal=%s)",
             rank, svc.address[0], svc.address[1], svc.wal_active)

    every = float(get_flag("ps_checkpoint_every_s"))
    duration = float(flag_or("serve_duration", 0.0))
    deadline = time.monotonic() + duration if duration > 0 else None
    next_ckpt = time.monotonic() + every if every > 0 and uri else None
    try:
        while deadline is None or time.monotonic() < deadline:
            # Constant cadence on purpose: this is the checkpoint
            # ticker's clock, not a convergence wait.
            time.sleep(0.1)  # graftlint: disable=poll-loop-no-backoff
            if next_ckpt is not None and time.monotonic() >= next_ckpt:
                # Snapshot is dispatcher-atomic (ps_service); the stream
                # write is atomic-rename (utils/stream); the rotate+prune
                # afterwards is pure space reclamation.
                ckpt.save_table(table, uri)
                svc.wal_checkpoint()
                next_ckpt = time.monotonic() + every
    except KeyboardInterrupt:
        log.info("ps_shard: interrupted, shutting down")
    finally:
        table.close()
        svc.close()
    return 0


def main(argv=None) -> int:
    return run_app(_body, list(argv if argv is not None else sys.argv[1:]))


if __name__ == "__main__":
    sys.exit(main())
