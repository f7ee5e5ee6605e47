"""2-D dense MatrixTable, row-sharded over the server axis.

Reference: ``include/multiverso/table/matrix_table.h``,
``src/table/matrix_table.cpp`` — row-granular API (whole table via sentinel
-1, single row, row-id vector), worker-side row routing
(``matrix_table.cpp:235-313``: row r -> server r / num_row_each), server-side
per-row updates at ``(key - row_offset) * num_col``
(``matrix_table.cpp:387-417``), optional uniform random init
(``matrix_table.cpp:372-384``).

TPU-native: storage is a [rows, cols] ``jax.Array`` row-sharded across device
shards. Row Get = ``jnp.take`` (dynamic row gather over ICI); row Add = one
jitted scatter-updater kernel. Whole-table ops are the dense path. Row routing
survives as a ``partition`` parity helper for the host async engine.
"""

from __future__ import annotations

import time
from typing import Dict, Optional, Sequence

import jax
import numpy as np

from multiverso_tpu.core.options import AddOption, GetOption, MatrixTableOption
from multiverso_tpu.core.table import ServerStore, WorkerTable
from multiverso_tpu.core.updater import get_updater
from multiverso_tpu.core.zoo import Zoo
from multiverso_tpu.parallel import comm_policy as cp
from multiverso_tpu.telemetry import phase
from multiverso_tpu.utils.dashboard import monitor
from multiverso_tpu.utils.log import check


def initial_rows(option: MatrixTableOption) -> Optional[np.ndarray]:
    """What a table of ``option`` starts from: its uniform ``random_init``
    draw (ref ``matrix_table.cpp:372-384``), or None for zeros. The ONE
    draw: a :class:`~multiverso_tpu.tables.table_group.LocalTableGroup`
    member of the same option starts with the same bytes."""
    if not option.random_init:
        return None
    rng = np.random.default_rng(option.seed)
    return rng.uniform(option.init_low, option.init_high,
                       size=(option.num_row, option.num_col)
                       ).astype(option.dtype)


class MatrixTable(WorkerTable):
    def __init__(self, option: MatrixTableOption):
        zoo = Zoo.get()
        check(zoo.started, "call mv.init() before creating tables")
        updater = get_updater(option.dtype, option.updater)
        name = option.name or f"matrix_{len(zoo.tables)}"
        store = ServerStore(name, (option.num_row, option.num_col),
                            option.dtype, updater, zoo.mesh,
                            zoo.num_workers(), shard_axis=0,
                            init_array=initial_rows(option))
        super().__init__(store)
        self.num_row = option.num_row
        self.num_col = option.num_col
        # Reference row routing: num_row_each = num_row / num_servers
        # (matrix_table.cpp:24-45); degenerate num_row < num_servers handled
        # by clamping to 1 (matrix_table.cpp:347-369).
        self.num_servers = store.num_servers
        self.num_row_each = max(1, self.num_row // self.num_servers)
        # Per-table communication policy (docs/DESIGN.md "CommPolicy"):
        # None resolves to ps without probing; "auto" runs the decision
        # table (embedding-shaped row counts read as sparse access);
        # concrete values are pre-resolved. Client row ops record
        # comm.ps.* regardless — they ARE the ps plane.
        self.comm = cp.policy_for_option(
            option.comm_policy, (self.num_row, self.num_col),
            self.store.dtype,
            sparse=(option.is_sparse
                    or self.num_row >= cp.SPARSE_ROWS_MIN),
            mesh=zoo.mesh, table=name)
        self.comm_policy = self.comm.policy

    # -- whole-table ops (sentinel key -1 in the reference) ----------------
    def get_async(self, option: Optional[GetOption] = None) -> int:
        with self._bsp_get(option):
            arr = self.store.read()
        return self._register(lambda: np.asarray(arr))

    def get(self, option: Optional[GetOption] = None) -> np.ndarray:
        with monitor("WORKER_TABLE_SYNC_GET"):
            return self.wait(self.get_async(option))

    def raw(self) -> jax.Array:
        return self.store.read()

    def add_async(self, delta, option: Optional[AddOption] = None) -> int:
        delta = np.asarray(delta, dtype=self.store.dtype)
        check(delta.shape == (self.num_row, self.num_col),
              f"delta shape {delta.shape} != {(self.num_row, self.num_col)}")
        with self._bsp_add(option) as opt:
            self.store.apply_dense(delta, opt)
        return self._register_add()

    def add(self, delta, option: Optional[AddOption] = None) -> None:
        with monitor("WORKER_TABLE_SYNC_ADD"):
            self.wait(self.add_async(delta, option))

    # -- row ops (ref matrix_table.h:25-75) --------------------------------
    def get_rows_async(self, row_ids,
                       option: Optional[GetOption] = None) -> int:
        row_ids = np.asarray(row_ids, dtype=np.int32)
        t0 = time.perf_counter()
        with self._bsp_get(option):
            arr = self.store.read_rows(row_ids)
        self.comm.record_client_op(
            len(row_ids) * self.num_col * self.store.dtype.itemsize,
            (time.perf_counter() - t0) * 1e3)
        return self._register(lambda: np.asarray(arr))

    def get_rows(self, row_ids, option: Optional[GetOption] = None
                 ) -> np.ndarray:
        # The monitor is the whole call; the phases are its parts:
        # dispatch (gate, ids to the device, launch) and sync (the device
        # wait + the device-to-host copy).
        with monitor("WORKER_TABLE_SYNC_GET"):
            with phase("table.get_rows.dispatch"):
                msg_id = self.get_rows_async(row_ids, option)
            with phase("table.get_rows.sync"):
                return self.wait(msg_id)

    def get_row(self, row_id: int) -> np.ndarray:
        return self.get_rows([row_id])[0]

    def add_rows_async(self, row_ids, deltas,
                       option: Optional[AddOption] = None) -> int:
        row_ids = np.asarray(row_ids, dtype=np.int32)
        deltas = np.asarray(deltas, dtype=self.store.dtype)
        check(deltas.shape == (len(row_ids), self.num_col),
              f"row delta shape {deltas.shape} != "
              f"{(len(row_ids), self.num_col)}")
        t0 = time.perf_counter()
        with self._bsp_add(option) as opt:
            self.store.apply_rows(row_ids, deltas, opt)
        self.comm.record_client_op(deltas.nbytes,
                                   (time.perf_counter() - t0) * 1e3)
        return self._register_add()

    def add_rows(self, row_ids, deltas,
                 option: Optional[AddOption] = None) -> None:
        # dispatch: host casts and checks (0.011 ms of its 1.2 on the v5e
        # host, PERF.md: no phase of their own), gate, launch; sync: wait
        with monitor("WORKER_TABLE_SYNC_ADD"):
            with phase("table.add_rows.dispatch"):
                msg_id = self.add_rows_async(row_ids, deltas, option)
            with phase("table.add_rows.sync"):
                self.wait(msg_id)

    def add_row(self, row_id: int, delta,
                option: Optional[AddOption] = None) -> None:
        self.add_rows([row_id], np.asarray(delta)[None, :], option)

    # -- comm-policy publish (docs/DESIGN.md "CommPolicy") -----------------
    def publish(self, values) -> None:
        """Whole-replica publish: overwrite the stored params with a
        worker replica at a sync point — how allreduce/model-average
        tables reconcile with the PS surface (one dense write instead of
        per-step delta pushes). Counted under the table's own plane."""
        values = np.asarray(values, dtype=self.store.dtype)
        t0 = time.perf_counter()
        self.store.write_dense(values)
        self.comm.record_publish(values.nbytes,
                                 (time.perf_counter() - t0) * 1e3)

    # -- serving hook (multiverso_tpu/serving; docs/SERVING.md) ------------
    def serving_runner(self, cache=None):
        """A :class:`~multiverso_tpu.serving.SparseLookupRunner` over this
        table's LIVE store. Reads dispatch under the store's donation
        guard, so served values are bitwise-equal to :meth:`get_rows` of
        the same rows; in sync mode the batch is stamped with the BSP add
        clock it was served at. ``cache`` (a
        :class:`~multiverso_tpu.serving.HotRowCache`) answers fully-hot
        lookups host-side within its staleness bound — SYNC mode only:
        without the BSP clock there is no version to age entries by, so
        an async-mode live table ignores the cache rather than mask
        training writes forever."""
        from multiverso_tpu.serving.runners import SparseLookupRunner
        clock_fn = self._sync.clock if self._sync is not None else None
        return SparseLookupRunner(self.store, clock_fn=clock_fn,
                                  cache=cache)

    # -- parity helper (ref matrix_table.cpp:235-313) ----------------------
    def partition(self, row_ids: Sequence[int]
                  ) -> Dict[int, np.ndarray]:
        """Route each row id to its server: ``min(r // num_row_each, n-1)``."""
        out: Dict[int, list] = {}
        for r in row_ids:
            sid = min(int(r) // self.num_row_each, self.num_servers - 1)
            out.setdefault(sid, []).append(int(r))
        return {sid: np.asarray(rows, dtype=np.int32)
                for sid, rows in out.items()}
