"""Supervised multi-shard PS topology: one supervisor, N durable seats.

The ISSUE-16 tentpole. PR 15 proved the single-seat story — one WAL'd
``ps_shard_main`` process, SIGKILLed and respawned through the recovery
path (checkpoint restore -> WAL replay -> announce LAST) with exact
acked-write parity. :class:`PSShardFleet` generalizes it to a fleet: one
supervisor owns N shard seats of ONE distributed table (ranks 1..N; the
owning process holds rank 0 and the client seat), each journaled and
periodically checkpointed, each respawned through the same recovery
path when it dies.

Re-routing on shard loss is the PS plane's analog of the serving
router: the membership DIRECTORY is replicated on every seat
(``PSService.enable_directory``), a restarting seat registers its new
address with every live peer before serving, and the client's retry
loop (``DistributedTableBase._retry_request``) parks with jittered
exponential backoff against the directory until the replacement
announces — then resumes into the exactly-once reply cache, so a retry
spanning the outage dedups instead of double-applying. Zero acked loss
end-to-end: ``-wal_sync_acks`` makes every acked add durable, recovery
replays the tail, and the dedup cache absorbs the retransmits.

Membership truth is the seat's addr file, written ONLY after recovery
completes — the same protocol the PR-15 drill pinned — so the
supervisor (and the chaos drill's convergence check) see a seat exactly
when clients can reach it. A SIGKILLed seat leaves a stale addr file
behind; the view cross-checks process liveness so a corpse with a
fresh-looking announce still reads as down.

Used by ``fleet_main -fleet_role=ps_fleet`` (operator topology),
``serve_bench --chaos-drill`` (the kill-any-subset drill), and the
fleet smoke tests. The owning process must have the multiverso runtime
initialized (``mv.init``) before :meth:`PSShardFleet.start` builds the
client seat — and, on a TPU host, must not have touched a jax device
yet: every seat holds a shard on its own chip (utils/chips.py), the
owner takes chip 0 for the rank-0 seat, and the seats are spawned before
the owner's backend comes up.
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import tempfile
import time
from typing import Dict, List, Optional, Tuple

from multiverso_tpu.utils.chips import child_env, take_chip
from multiverso_tpu.utils.log import check, log

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class _SeatMembershipView:
    """Fleet-view adapter over the fleet's addr files + process handles.

    Generalizes the bench's single-seat file view: a seat is a member
    iff its addr file exists (announce = recovery complete) AND its
    process is alive — existence alone would let a SIGKILLed seat's
    stale announce mask the death from the supervisor forever."""

    def __init__(self, fleet: "PSShardFleet"):
        self._fleet = fleet

    def stats(self) -> Dict:
        return self._fleet.membership_stats()

    def drain(self, member_id: str, timeout_s: float = 30.0) -> bool:
        return False            # fixed-size shard fleet: never scaled down


class PSShardFleet:
    """One supervisor over N durable WAL'd PS shard seats.

    ``start()`` spawns ranks 1..N (``apps/ps_shard_main.py``), waits for
    every announce, builds the rank-0 client table in THIS process, and
    arms a :class:`ReplicaSupervisor` (member ids ``ps-1..ps-N``) whose
    ``spawn_fn`` re-runs the seat through the recovery path with the
    CURRENT addresses of its siblings. ``table`` is then a live client
    seat that survives any subset of shard deaths (park-and-retry
    through the replicated directory)."""

    def __init__(self, shards: int = 4, *, first_chip: int = 0,
                 table_id: int = 912,
                 table_size: int = 256, table_kind: str = "array",
                 table_cols: int = 8, workdir: Optional[str] = None,
                 sync_acks: bool = True, wal_flush_ms: float = 25.0,
                 checkpoint_every_s: float = 1.0,
                 serve_duration: float = 600.0,
                 supervise: bool = True, join_grace_s: float = 60.0,
                 poll_s: float = 0.1, cooldown_s: float = 0.5,
                 extra_seat_args: Optional[Dict[int, List[str]]] = None):
        check(shards >= 1, "a PS fleet needs at least one shard")
        check(table_kind in ("array", "matrix"),
              f"table_kind={table_kind!r} (want array|matrix)")
        self.shards = int(shards)
        #: on a TPU host the owner runs on chip ``first_chip`` and seat
        #: ``rank`` on chip ``first_chip + rank`` (a caller that already
        #: gave chips to other children starts the fleet after them).
        self.first_chip = int(first_chip)
        self.table_id = int(table_id)
        self.table_size = int(table_size)
        self.table_kind = table_kind
        self.table_cols = int(table_cols)
        self.workdir = workdir or tempfile.mkdtemp(prefix="ps_fleet_")
        self.sync_acks = bool(sync_acks)
        self.wal_flush_ms = float(wal_flush_ms)
        self.checkpoint_every_s = float(checkpoint_every_s)
        self.serve_duration = float(serve_duration)
        self._supervise = bool(supervise)
        self._join_grace_s = float(join_grace_s)
        self._poll_s = float(poll_s)
        self._cooldown_s = float(cooldown_s)
        #: per-rank extra CLI args, applied on every (re)spawn — the
        #: chaos drill marks its seeded slow-disk seats here
        #: (e.g. ``{2: ["-wal_fsync_delay_ms=40"]}``).
        self.extra_seat_args = dict(extra_seat_args or {})
        self._svc = None
        self.table = None
        self.peers: List[Tuple[str, int]] = []
        self._handles: Dict[int, subprocess.Popen] = {}
        self._sup = None
        os.makedirs(os.path.join(self.workdir, "wal"), exist_ok=True)
        os.makedirs(os.path.join(self.workdir, "ckpt"), exist_ok=True)

    # -- seat plumbing -------------------------------------------------------
    def addr_file(self, rank: int) -> str:
        return os.path.join(self.workdir, f"seat{rank}.addr")

    def _read_addr(self, rank: int) -> Optional[Tuple[str, int]]:
        try:
            host, port = open(self.addr_file(rank)).read().split(":")
            return (host, int(port))
        except (OSError, ValueError):
            return None

    def _seat_peers(self, rank: int) -> str:
        """The -ps_peers list for seat ``rank``: parent (rank 0) + every
        sibling's CURRENT address. A sibling not yet announced gets a
        placeholder — its directory registration retries in the
        background and self-corrects the moment the sibling registers
        its real address (enable_directory's retry loop)."""
        entries = [f"{self.peers[0][0]}:{self.peers[0][1]}"]
        for r in range(1, self.shards + 1):
            addr = None if r == rank else self._read_addr(r)
            entries.append(f"{addr[0]}:{addr[1]}" if addr
                           else "127.0.0.1:1")
        return ",".join(entries)

    def spawn_seat(self, rank: int) -> subprocess.Popen:
        """(Re)spawn one shard seat through the recovery path. Removes
        the stale announce first — a replacement must not count as
        recovered until ITS restore+replay completes."""
        check(1 <= rank <= self.shards, f"rank {rank} outside the fleet")
        try:
            os.remove(self.addr_file(rank))
        except OSError:
            pass
        cmd = [sys.executable, "-m",
               "multiverso_tpu.apps.ps_shard_main",
               f"-rank={rank}", f"-ps_peers={self._seat_peers(rank)}",
               f"-ps_table_id={self.table_id}",
               f"-ps_table_size={self.table_size}",
               f"-ps_table_kind={self.table_kind}",
               f"-ps_table_cols={self.table_cols}",
               "-wal=true", f"-wal_dir={self.workdir}/wal",
               f"-wal_flush_ms={self.wal_flush_ms}",
               f"-wal_sync_acks={'true' if self.sync_acks else 'false'}",
               f"-checkpoint_dir={self.workdir}/ckpt",
               f"-ps_checkpoint_every_s={self.checkpoint_every_s}",
               f"-ps_addr_file={self.addr_file(rank)}",
               f"-serve_duration={self.serve_duration}",
               "-telemetry_alerts=false", "-telemetry_flight=false",
               *self.extra_seat_args.get(rank, [])]
        proc = subprocess.Popen(
            cmd, cwd=_REPO,
            env=child_env(self.first_chip + rank, self._holders(),
                          self._what()))
        self._handles[rank] = proc
        return proc

    def _what(self) -> str:
        return f"ps fleet (owner + {self.shards} shard seats)"

    def _holders(self) -> int:
        return self.first_chip + self.shards + 1

    def seat_alive(self, rank: int) -> bool:
        h = self._handles.get(rank)
        return h is not None and h.poll() is None

    def seat_announced(self, rank: int) -> bool:
        return os.path.exists(self.addr_file(rank))

    def membership_stats(self) -> Dict:
        rows = {f"ps-{r}": {"alerts": []}
                for r in range(1, self.shards + 1)
                if self.seat_announced(r) and self.seat_alive(r)}
        return {"replicas": rows, "router_alerts": []}

    # -- lifecycle -----------------------------------------------------------
    def start(self, bringup_timeout_s: float = 240.0) -> "PSShardFleet":
        from multiverso_tpu.fleet.supervisor import ReplicaSupervisor
        from multiverso_tpu.parallel.ps_service import (
            DistributedArrayTable, DistributedMatrixTable, PSService)

        check(self._svc is None, "fleet already started")
        take_chip(self.first_chip, self._holders(), self._what())
        self._svc = PSService()
        self.peers = [self._svc.address] \
            + [("127.0.0.1", 1)] * self.shards
        for r in range(1, self.shards + 1):
            self.spawn_seat(r)
        deadline = time.monotonic() + bringup_timeout_s
        for r in range(1, self.shards + 1):
            delay = 0.01
            while not self.seat_announced(r):
                check(self.seat_alive(r),
                      f"ps shard {r} exited during bring-up")
                check(time.monotonic() < deadline,
                      f"ps shard {r} never announced")
                # bring-up convergence wait: serving hasn't started
                # graftlint: disable=unattributed-wait
                time.sleep(delay)
                delay = min(delay * 2.0, 0.25)
            self.peers[r] = self._read_addr(r)
        if self.table_kind == "matrix":
            self.table = DistributedMatrixTable(
                self.table_id, self.table_size, self.table_cols,
                self._svc, self.peers, rank=0)
        else:
            self.table = DistributedArrayTable(
                self.table_id, self.table_size, self._svc, self.peers,
                rank=0)
        if self._supervise:
            self._sup = ReplicaSupervisor(
                _SeatMembershipView(self), self.spawn_seat,
                member_prefix="ps-", min_replicas=self.shards,
                max_replicas=self.shards, cooldown_s=self._cooldown_s,
                poll_s=self._poll_s, join_grace_s=self._join_grace_s)
            for r in range(1, self.shards + 1):
                self._sup.adopt(r, self._handles[r])
            self._sup.start()
        log.info("ps fleet up: %d shard(s) of table %d under %s",
                 self.shards, self.table_id, self.workdir)
        return self

    def kill(self, rank: int, sig: int = signal.SIGKILL) -> None:
        """Deliver ``sig`` to one seat (the chaos engine's kill/pause
        primitive). The stale announce is LEFT on disk on purpose — a
        real crash doesn't tidy up; the membership view cross-checks
        process liveness instead."""
        h = self._handles.get(rank)
        check(h is not None, f"no seat handle for rank {rank}")
        h.send_signal(sig)

    def wait_converged(self, timeout_s: float = 240.0) -> bool:
        """Block until EVERY seat is announced + alive (full membership
        — the chaos drill's per-round convergence gate)."""
        deadline = time.monotonic() + timeout_s
        delay = 0.01
        while time.monotonic() < deadline:
            if len(self.membership_stats()["replicas"]) == self.shards:
                return True
            # membership convergence gate (chaos drill), control plane
            # graftlint: disable=unattributed-wait
            time.sleep(delay)
            delay = min(delay * 2.0, 0.25)
        return False

    def status(self) -> Dict:
        out = {"shards": self.shards,
               "live": sorted(r for r in range(1, self.shards + 1)
                              if self.seat_alive(r)),
               "announced": sorted(r for r in range(1, self.shards + 1)
                                   if self.seat_announced(r))}
        if self._sup is not None:
            sup = self._sup.status()
            out["supervisor"] = {k: sup[k] for k in
                                 ("respawns", "scale_ups", "scale_downs")}
            out["events"] = sup["events"]
        return out

    def close(self) -> None:
        if self._sup is not None:
            self._sup.stop()
            for rank, h in self._sup.slots().items():
                if isinstance(h, subprocess.Popen):
                    self._handles[rank] = h
            self._sup = None
        for h in self._handles.values():
            if h.poll() is None:
                try:
                    h.send_signal(signal.SIGCONT)   # a paused seat must
                except OSError:                     # see the terminate
                    pass
                h.terminate()
        for h in self._handles.values():
            try:
                # teardown join on child exit, after serving stopped
                # graftlint: disable=unattributed-wait
                h.wait(timeout=15)
            except Exception:  # noqa: BLE001 - last resort on teardown
                h.kill()
        self._handles.clear()
        if self.table is not None:
            self.table.close()
            self.table = None
        if self._svc is not None:
            self._svc.close()
            self._svc = None
