"""Zoo — the runtime singleton: lifecycle, roles, registry, barrier.

Parity with the reference Zoo (``include/multiverso/zoo.h:19-85``,
``src/zoo.cpp``): it owns startup/shutdown ordering, node roles, table
registration, rank/size/worker/server id queries, and the global barrier.

TPU-native re-design: there are no actor threads or an explicit Controller —
JAX's single-controller/multi-controller runtime replaces node registration
(``jax.distributed.initialize`` is the RegisterNode/Controller analog,
ref ``src/controller.cpp:38-80``), a device Mesh replaces the server set, and
the barrier maps to a cross-process sync. Roles are kept for API/semantics
parity (``-ps_role``, ref ``src/zoo.cpp:23-35``; ``-ma`` skips the table
service, ref ``src/zoo.cpp:49``).
"""

from __future__ import annotations

import threading
from typing import Any, List, Optional

import jax

from multiverso_tpu.parallel import mesh as mesh_lib
from multiverso_tpu.telemetry import span
from multiverso_tpu.utils import configure
from multiverso_tpu.utils.log import log, check
from multiverso_tpu.utils.locks import make_lock


class Role:
    """Bitmask roles (ref include/multiverso/node.h:6-27)."""
    NONE = 0
    WORKER = 1
    SERVER = 2
    ALL = 3

    _BY_NAME = {"none": NONE, "worker": WORKER, "server": SERVER,
                "default": ALL, "all": ALL}

    @classmethod
    def parse(cls, name: str) -> int:
        try:
            return cls._BY_NAME[name.lower()]
        except KeyError:
            raise ValueError(f"unknown ps_role '{name}'") from None

    @staticmethod
    def is_worker(role: int) -> bool:
        return bool(role & Role.WORKER)

    @staticmethod
    def is_server(role: int) -> bool:
        return bool(role & Role.SERVER)


class Node:
    """Membership record (ref include/multiverso/node.h:14-27)."""

    def __init__(self, rank: int, role: int, worker_id: int = -1,
                 server_id: int = -1):
        self.rank = rank
        self.role = role
        self.worker_id = worker_id
        self.server_id = server_id


class Zoo:
    _instance: Optional["Zoo"] = None
    _lock = make_lock("core.zoo")

    def __init__(self) -> None:
        self.started = False
        self._mesh: Optional[jax.sharding.Mesh] = None
        self._devices: Optional[List[jax.Device]] = None
        self._multi_process = False
        self.role: int = Role.ALL
        self.ma_mode: bool = False
        self.sync_mode: bool = False
        self.tables: List[Any] = []
        self._barrier_count = 0
        self._num_local_workers = 1
        self._local_mesh: Optional[jax.sharding.Mesh] = None
        # Explicit net bind/connect state (MV_NetBind/MV_NetConnect parity)
        self.ps_service: Optional[Any] = None
        self.ps_peers: List[Any] = []

    # -- singleton ---------------------------------------------------------
    @classmethod
    def get(cls) -> "Zoo":
        with cls._lock:
            if cls._instance is None:
                cls._instance = Zoo()
            return cls._instance

    @classmethod
    def _reset_for_tests(cls) -> None:
        with cls._lock:
            cls._instance = None

    # -- lifecycle (ref src/zoo.cpp:41-80) ---------------------------------
    def start(self, argv: Optional[List[str]] = None,
              devices: Optional[List[jax.Device]] = None,
              num_local_workers: int = 1) -> List[str]:
        check(not self.started, "Zoo already started")
        with span("zoo.start"):
            return self._start(argv, devices, num_local_workers)

    def _start(self, argv, devices, num_local_workers) -> List[str]:
        remaining = configure.parse_cmd_flags(argv)
        self.role = Role.parse(configure.get_flag("ps_role"))
        self.ma_mode = configure.get_flag("ma")
        self.sync_mode = configure.get_flag("sync")
        self._num_local_workers = max(1, int(num_local_workers))
        # Machine-file mode (the reference's ZMQ deployment,
        # zmq_net.h:25-61): derive rank/world from this host's position in
        # the file; rank 0's entry hosts the coordination service.
        machine_file = configure.get_flag("machine_file")
        if machine_file and not configure.get_flag("coordinator"):
            from multiverso_tpu.utils.net_util import rank_from_machine_file

            my_rank, world, peers = rank_from_machine_file(machine_file)
            configure.set_flag("rank", my_rank)
            configure.set_flag("world_size", world)
            # The machine-file ports are the PS service ports; the
            # coordination service must not squat on rank 0's PS port (the
            # peers will net_bind/net_connect against those entries), so it
            # binds one port above.
            configure.set_flag("coordinator",
                               f"{peers[0][0]}:{peers[0][1] + 1}")
        # Multi-controller bring-up: the RegisterNode/Controller handshake
        # (ref src/controller.cpp:38-80) maps to jax.distributed's
        # coordination service — rank 0 hosts it, everyone registers.
        coordinator = configure.get_flag("coordinator")
        self._multi_process = (bool(coordinator)
                               or jax.distributed.is_initialized())
        if coordinator:
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=configure.get_flag("world_size"),
                process_id=configure.get_flag("rank"))
        self._devices = list(devices) if devices is not None else None
        self.started = True
        log.debug("Zoo started: sync=%s ma=%s", self.sync_mode, self.ma_mode)
        return remaining

    @property
    def mesh(self) -> Optional[jax.sharding.Mesh]:
        """The server set (in ma mode too: aggregate uses it), built on
        FIRST USE rather than in :meth:`start`. Building it initializes the
        jax backend, and a chip belongs to one process: a launcher role
        that only parses flags and spawns children (``spawn_ranks``, the
        fleet router/local/drain roles) must never get here, so the
        children it starts can take the chips."""
        if self._mesh is None and self.started:
            # The backend's bring-up where the program is first to ask
            # (near 0 where the host application already had).
            with span("startup.backend"):
                self._mesh = mesh_lib.build_mesh(devices=self._devices)
                mesh_lib.log_backend(self._mesh.devices.flat)
        return self._mesh

    def stop(self, finalize_net: bool = True) -> None:
        del finalize_net
        if not self.started:
            return
        self.barrier()
        for table in self.tables:
            close = getattr(table, "close", None)
            if close:
                close()
        self.tables.clear()
        from multiverso_tpu.core.actor import stop_all_actors
        stop_all_actors()
        if self.ps_service is not None:
            self.ps_service.close()
            self.ps_service = None
        self.ps_peers = []
        self._mesh = None
        self._local_mesh = None
        self.started = False

    # -- identity (ref include/multiverso/zoo.h:38-50) ---------------------
    def rank(self) -> int:
        # One process unless jax.distributed was brought up in start();
        # asking jax would initialize a backend (see :attr:`mesh`).
        return jax.process_index() if self._multi_process else 0

    def size(self) -> int:
        return jax.process_count() if self._multi_process else 1

    def num_workers(self) -> int:
        """Total logical workers: processes x local worker threads."""
        return self.size() * self._num_local_workers

    def num_servers(self) -> int:
        mesh = self.mesh
        if mesh is None or mesh_lib.SERVER_AXIS not in mesh.shape:
            return 1
        return mesh.shape[mesh_lib.SERVER_AXIS]

    def worker_id(self) -> int:
        return self.rank() * self._num_local_workers if Role.is_worker(self.role) else -1

    def server_id(self) -> int:
        return self.rank() if Role.is_server(self.role) else -1

    @property
    def num_local_workers(self) -> int:
        return self._num_local_workers

    @property
    def local_mesh(self) -> jax.sharding.Mesh:
        """Mesh over THIS process's devices only. The DCN PS tables shard
        across processes via the TCP service, so their per-process stores
        must never sit on a process-spanning mesh — a store op would
        otherwise compile to a global collective that hangs unless every
        rank calls it in lockstep. In a single-process world this is
        ``self.mesh``."""
        if self.size() == 1:
            return self.mesh
        if self._local_mesh is None:
            self._local_mesh = mesh_lib.build_mesh(
                devices=jax.local_devices(), spec="")
        return self._local_mesh

    # -- barrier (ref src/zoo.cpp:164-176) ---------------------------------
    def barrier(self) -> None:
        check(self.started, "Zoo not started")
        self._barrier_count += 1
        if self.size() > 1:
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices(f"mv_barrier_{self._barrier_count}")

    # -- table registry (ref src/zoo.cpp:178-186) --------------------------
    def register_table(self, table: Any) -> int:
        table_id = len(self.tables)
        self.tables.append(table)
        return table_id
