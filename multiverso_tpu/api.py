"""Public API — the ``MV_*`` surface.

Parity with ``include/multiverso/multiverso.h:9-65``: init/shutdown/barrier,
rank/size/worker/server queries, flag override, table creation (the
``table_factory`` dispatch, ref ``include/multiverso/table_factory.h:16-26``),
and allreduce aggregate. TPU-native: ``init`` wraps ``jax.distributed``
bring-up; explicit ``net_bind``/``net_connect`` (the reference's
``MV_NetBind``/``MV_NetConnect``, src/multiverso.cpp:58-68) expose the host
PS service for externally-orchestrated clusters.
"""

from __future__ import annotations

from typing import Any, List, Optional

import jax

from multiverso_tpu.core.options import (ArrayTableOption, KVTableOption,
                                         MatrixTableOption, TableOption)
from multiverso_tpu.core.zoo import Zoo
from multiverso_tpu.parallel import collectives
from multiverso_tpu.utils import configure
from multiverso_tpu.utils.log import check


def init(argv: Optional[List[str]] = None, sync: Optional[bool] = None,
         num_local_workers: int = 1,
         devices: Optional[List[jax.Device]] = None) -> List[str]:
    """``MV_Init`` analog (ref src/multiverso.cpp:11-16).

    Parses ``-key=value`` flags out of argv (returning the rest), then starts
    the runtime. ``sync=True`` selects BSP semantics (ref ``-sync`` flag).
    ``num_local_workers`` configures in-process async worker slots (the
    analog of running several worker ranks on one host).
    """
    if sync is not None:
        configure.set_flag("sync", bool(sync))
    return Zoo.get().start(argv, devices=devices,
                           num_local_workers=num_local_workers)


def shutdown(finalize_net: bool = True) -> None:
    """``MV_ShutDown`` analog."""
    Zoo.get().stop(finalize_net)
    Zoo._reset_for_tests()


def barrier() -> None:
    """``MV_Barrier`` analog."""
    Zoo.get().barrier()


def rank() -> int:
    return Zoo.get().rank()


def size() -> int:
    return Zoo.get().size()


def num_workers() -> int:
    return Zoo.get().num_workers()


def num_servers() -> int:
    return Zoo.get().num_servers()


def worker_id() -> int:
    return Zoo.get().worker_id()


def server_id() -> int:
    return Zoo.get().server_id()


def is_master_worker() -> bool:
    """Rank-0 check (binding parity: ``binding/python/multiverso/api.py:66-75``)."""
    return worker_id() == 0


def set_flag(name: str, value: Any) -> None:
    """``MV_SetFlag`` analog."""
    configure.set_flag(name, value)


def get_flag(name: str) -> Any:
    return configure.get_flag(name)


def create_table(option: TableOption):
    """``MV_CreateTable`` + table_factory dispatch
    (ref include/multiverso/multiverso.h:35-41)."""
    from multiverso_tpu.tables.array_table import ArrayTable
    from multiverso_tpu.tables.kv_table import KVTable
    from multiverso_tpu.tables.matrix_table import MatrixTable
    from multiverso_tpu.tables.sparse_matrix_table import SparseMatrixTable

    zoo = Zoo.get()
    check(zoo.started, "call mv.init() first")
    check(not zoo.ma_mode,
          "table service is disabled in model-average (-ma) mode "
          "(ref src/zoo.cpp:49)")
    if isinstance(option, ArrayTableOption):
        table = ArrayTable(option)
    elif isinstance(option, MatrixTableOption):
        table = (SparseMatrixTable(option) if option.is_sparse
                 else MatrixTable(option))
    elif isinstance(option, KVTableOption):
        if option.device:
            from multiverso_tpu.tables.device_kv_table import DeviceKVTable
            table = DeviceKVTable(option, value_dim=option.value_dim)
        else:
            table = KVTable(option)
    else:
        raise TypeError(f"unknown table option {type(option).__name__}")
    barrier()  # ref multiverso.h:40: creation is followed by a barrier
    return table


def create_table_group(tables):
    """A :class:`~multiverso_tpu.tables.table_group.TableGroup` over
    already-created matrix tables: row get/add for all of them in one
    launch and one copy each way (docs/DESIGN.md "Grouped row
    operations")."""
    from multiverso_tpu.tables.table_group import TableGroup
    return TableGroup(tables)


def aggregate(data):
    """``MV_Aggregate`` analog: allreduce-SUM across processes."""
    return collectives.aggregate(data)


def net_bind(host: str = "127.0.0.1", port: int = 0):
    """``MV_NetBind`` analog (ref src/multiverso.cpp:58-62): start this
    process's PS service listener; returns (host, port)."""
    from multiverso_tpu.parallel.ps_service import PSService

    zoo = Zoo.get()
    check(zoo.started, "call mv.init() first")
    check(zoo.ps_service is None, "service already bound")
    zoo.ps_service = PSService(host, port)
    # Durability (-wal; docs/DURABILITY.md): arm the write-ahead delta
    # log before any table registers. Per-rank subdirectory so N ranks
    # sharing -wal_dir never interleave segments.
    from multiverso_tpu.utils.configure import flag_or
    if bool(flag_or("wal", False)):
        wal_dir = str(flag_or("wal_dir", ""))
        check(bool(wal_dir), "-wal=true requires -wal_dir=DIR")
        import os as _os
        zoo.ps_service.attach_wal(
            _os.path.join(wal_dir, f"rank{int(flag_or('rank', 0))}"),
            flush_interval_ms=float(flag_or("wal_flush_ms", 25.0)),
            sync_acks=bool(flag_or("wal_sync_acks", False)))
    return zoo.ps_service.address


def net_connect(peers) -> None:
    """``MV_NetConnect`` analog (ref src/multiverso.cpp:64-68): record the
    full peer list ((host, port) per rank, this process's own entry
    included) used by distributed tables."""
    zoo = Zoo.get()
    check(zoo.started, "call mv.init() first")
    zoo.ps_peers = [tuple(p) for p in peers]


def create_distributed_array_table(table_id: int, size: int, rank: int,
                                   dtype=None, updater: str = "default"):
    """Distributed (process-sharded) array table over the bound service +
    connected peers."""
    import numpy as _np

    from multiverso_tpu.parallel.ps_service import DistributedArrayTable

    zoo = Zoo.get()
    check(zoo.ps_service is not None, "call mv.net_bind() first")
    check(len(zoo.ps_peers) > 0, "call mv.net_connect() first")
    table = DistributedArrayTable(table_id, size, zoo.ps_service,
                                  list(zoo.ps_peers), rank,
                                  dtype=dtype or _np.float32,
                                  updater=updater)
    zoo.register_table(table)   # so shutdown closes its peer connections
    return table


def create_distributed_matrix_table(table_id: int, num_row: int,
                                    num_col: int, rank: int, dtype=None,
                                    updater: str = "default"):
    """Distributed (row-sharded across processes) matrix table over the
    bound service + connected peers (ref ``matrix_table.cpp:24-45`` row
    sharding, served here by the DCN PS service)."""
    import numpy as _np

    from multiverso_tpu.parallel.ps_service import DistributedMatrixTable

    zoo = Zoo.get()
    check(zoo.ps_service is not None, "call mv.net_bind() first")
    check(len(zoo.ps_peers) > 0, "call mv.net_connect() first")
    table = DistributedMatrixTable(table_id, num_row, num_col,
                                   zoo.ps_service, list(zoo.ps_peers), rank,
                                   dtype=dtype or _np.float32,
                                   updater=updater)
    zoo.register_table(table)   # so shutdown closes its peer connections
    return table


def create_distributed_kv_table(table_id: int, rank: int, dtype=None):
    """Distributed (hash-partitioned across processes) key->value table
    over the bound service + connected peers (ref
    ``include/multiverso/table/kv_table.h:42-66`` — key % num_servers
    routing, += merge server-side)."""
    import numpy as _np

    from multiverso_tpu.parallel.ps_service import DistributedKVTable

    zoo = Zoo.get()
    check(zoo.ps_service is not None, "call mv.net_bind() first")
    check(len(zoo.ps_peers) > 0, "call mv.net_connect() first")
    table = DistributedKVTable(table_id, zoo.ps_service,
                               list(zoo.ps_peers), rank,
                               dtype=dtype or _np.int64)
    zoo.register_table(table)
    return table


def create_distributed_sparse_matrix_table(table_id: int, num_row: int,
                                           num_col: int, rank: int,
                                           dtype=None,
                                           updater: str = "default"):
    """Distributed row-sharded matrix with SERVER-SIDE per-worker
    staleness: incremental whole-table Gets ship only rows touched since
    this worker's last pull (ref ``src/table/sparse_matrix_table.cpp:
    184-258``)."""
    import numpy as _np

    from multiverso_tpu.parallel.ps_service import \
        DistributedSparseMatrixTable

    zoo = Zoo.get()
    check(zoo.ps_service is not None, "call mv.net_bind() first")
    check(len(zoo.ps_peers) > 0, "call mv.net_connect() first")
    table = DistributedSparseMatrixTable(table_id, num_row, num_col,
                                         zoo.ps_service,
                                         list(zoo.ps_peers), rank,
                                         dtype=dtype or _np.float32,
                                         updater=updater)
    zoo.register_table(table)
    return table


def finish_train(worker_id: Optional[int] = None) -> None:
    """``Zoo::FinishTrain`` analog (ref src/zoo.cpp:152-161): release this
    worker from every table's BSP clocks so stragglers can drain to
    shutdown."""
    zoo = Zoo.get()
    wid = worker_id if worker_id is not None else zoo.worker_id()
    if wid < 0:
        return   # this process hosts no worker; nothing to release
    for table in zoo.tables:
        ft = getattr(table, "finish_train", None)
        if ft is not None:
            ft(wid)
