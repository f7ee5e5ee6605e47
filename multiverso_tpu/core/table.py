"""Core table machinery: device-resident server store + worker handle.

Reference semantics being reproduced
(``include/multiverso/table_interface.h:24-75``, ``src/table.cpp``):

* ``WorkerTable``: sync ``Get/Add`` wrap async ops; ``GetAsync/AddAsync``
  allocate a message id + Waiter; ``Wait(id)`` blocks until every touched
  server shard replied.
* ``ServerTable``: sharded storage; every Add runs the pluggable Updater;
  Get reads current values; ``Store/Load`` serialize for checkpointing.

TPU-native re-design (SURVEY.md §7): the server store is a **sharded
``jax.Array`` living in HBM** (``NamedSharding`` over the mesh's "server"
axis) — the shard boundary that the reference expresses with per-server
processes is expressed here with device shards. ``Add`` dispatches ONE jitted
donated update kernel (the updater); XLA inserts the ICI collectives the
layout requires. ``AddAsync`` is therefore nearly free: JAX's async dispatch
*is* the reference's request pipeline, and ``Wait`` maps to
``block_until_ready`` — the Waiter/notify machinery collapses into the XLA
stream. The worker-side Partition (``src/table/array_table.cpp:69-86``) is
kept as an explicit helper because the async host engine and the parity tests
need it.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from multiverso_tpu.core.options import AddOption, GetOption
from multiverso_tpu.core.updater import Updater, combine_duplicate_rows
from multiverso_tpu.parallel import mesh as mesh_lib
from multiverso_tpu.telemetry import gauge, span, startup
from multiverso_tpu.utils.configure import get_flag
from multiverso_tpu.utils.log import check
from multiverso_tpu.utils.locks import make_lock, set_lock_order

# XLA's CPU collectives deadlock under concurrent dispatch: a sharded
# store kernel expands to one participant per virtual device, all of which
# must reach a rendezvous — but the host executor pool can be smaller than
# the device count, so two in-flight runs interleave participants and each
# waits forever for threads the other is holding (observed on a 2-core
# host with the test env's 8 virtual devices: AllGather participants of
# run A and run B parked at the same rendezvous). Multi-device CPU stores
# therefore serialize dispatch AND execution process-wide; accelerators
# keep fully async dispatch (the device stream already orders runs).
# Scope: this guards store-vs-store only. Worker-side shard_maps
# (collectives.py / sequence.py / pipeline.py) dispatched concurrently
# with a store kernel on the same multi-device CPU mesh could in
# principle wedge the same way; widening this into a lock around every
# CPU collective dispatch is deferred until such an interleaving is
# actually observed (worker collectives in tests run on the main thread
# between store ops, and CPU meshes exist only in tests).
_CPU_COLLECTIVE_LOCK = make_lock("core.cpu_collective")


def _physical_bytes(arr: jax.Array) -> int:
    """HBM actually held by ``arr`` across the mesh: per-device shard bytes
    x device count — so replication (a leaf NOT sharded over some mesh
    axis) counts once per replica, which is exactly the cost the
    cross-replica state sharding exists to eliminate. Host-side shape
    arithmetic only (no device sync)."""
    shard = arr.sharding.shard_shape(arr.shape)
    return (int(np.prod(shard, dtype=np.int64)) * np.dtype(arr.dtype).itemsize
            * len(arr.sharding.device_set))


def pallas_rows_eligible(shape: Tuple[int, ...], dtype: Any,
                         one_shard: bool) -> bool:
    """Whether the row kernels of ``ops/pallas_rows.py`` can serve a table
    at all: 2-D float32 with EXACTLY one 128-lane tile of columns, on one
    shard. Measured grounds: Mosaic packs 2-byte types two rows per
    sublane in HBM ((8,128)(2,1) tiling), so the kernels' single-row DMA
    slices fail to compile on real chips for bf16; column counts that are
    not a multiple of the lane tile fail alike (the reference's 1M x 50
    matrix, v5e, PR 21); and so does every WIDER multiple (256 .. 2,688:
    "Slice shape along dimension 0 must be aligned to tiling (8), but is
    1", compiled for the v5e, PR 29): only at 128 columns is the
    (8,128)-tiled table row-major, one row one contiguous slice. (The way
    round for wider rows, not taken by these kernels yet: the same table
    seen as ``[rows, columns / 128, 128]`` gives whole-row DMA slices at any
    multiple of 128 columns, one ``[columns / 128, 128]`` plane a row;
    ``ops/pallas_rows.add_unique_rows`` takes the LMs' expert layer's
    accumulators that way, compiled for the v5e at 2,048 and 2,688 columns,
    PR 41.)
    Multi-shard stays XLA: the row kernels would need per-shard offset
    remapping under shard_map, and XLA's sharded scatter already overlaps
    the collective with the update."""
    return (len(shape) == 2 and np.dtype(dtype) == np.dtype(np.float32)
            and shape[1] == 128 and one_shard)


def fused_rows_selected(updater: Updater, shape: Tuple[int, ...], dtype: Any,
                        one_shard: bool, state_sharded: bool) -> bool:
    """Whether a table's stateful row UPDATE runs as the fused Pallas
    gather-update-scatter kernel. Chosen from what the table shows, by no
    option: where the kernel applies (``pallas_rows_eligible``, an updater
    whose class says the kernel may run its ``rows_math``, state the kernel
    can own whole rows of) it walks the live prefix of the folded ids only
    and keeps a step's row DMAs in flight together, 2.6 against XLA's 9.9
    ms for 26 tables of 262,144 x 128 at 2,048 Zipf ids each (PERF.md 6,
    PR 29). ``Updater.fused_rows`` is read from the instance's OWN class:
    a subclass may override the math the claim was made for."""
    return (pallas_rows_eligible(shape, dtype, one_shard)
            and not state_sharded
            and vars(type(updater)).get("fused_rows", False))


def build_row_update(updater: Updater, fused: bool,
                     interpret: bool = False) -> Callable:
    """The un-jitted row update ``(data, state, row_ids, delta, *opt) ->
    (data, state)`` of a stateful-or-not updater on the XLA plane, or
    (``fused``) as the Pallas kernel: the same duplicate folding (stateful
    set-semantics must combine, not accumulate), then ONE fused
    gather-update-scatter dispatch over data + every state leaf."""
    if not fused:
        def rows(data, state, row_ids, delta, *opt):
            return updater.update_rows(data, state, row_ids, delta, opt)
        return rows
    from multiverso_tpu.ops.pallas_rows import fused_stateful_rows

    def fused_rows(data, state, row_ids, delta, *opt):
        ids, totals = combine_duplicate_rows(
            row_ids, delta.astype(data.dtype), data.shape[0])
        return fused_stateful_rows(data, state, ids, totals, opt, updater,
                                   interpret=interpret)
    return fused_rows


class ServerStore:
    """Device-resident sharded storage for one table + its updater state.

    The analog of one *row* of the reference's per-server ``store_`` vector
    (``src/server.cpp:23-58``) — except a single store object spans all
    shards, because XLA owns cross-shard placement.
    """

    def __init__(self, name: str, shape: Tuple[int, ...], dtype: Any,
                 updater: Updater, mesh: jax.sharding.Mesh,
                 num_workers: int, shard_axis: int = 0,
                 init_array: Optional[np.ndarray] = None,
                 state_sharding: Optional[str] = None):
        self.name = name
        self.logical_shape = tuple(int(s) for s in shape)
        self.dtype = np.dtype(dtype)
        self.updater = updater
        self.mesh = mesh
        self.shard_axis = shard_axis
        self.num_workers = num_workers
        num_servers = mesh.shape.get(mesh_lib.SERVER_AXIS, 1)
        self.num_servers = num_servers

        padded = list(self.logical_shape)
        padded[shard_axis] = mesh_lib.pad_to_multiple(padded[shard_axis],
                                                      num_servers)
        self.padded_shape = tuple(padded)
        self._pad = self.padded_shape[shard_axis] - self.logical_shape[shard_axis]

        self.sharding = mesh_lib.table_sharding(mesh, len(padded), shard_axis)
        # Start-up phases, read from their span.<name> histograms (they
        # run before any traced window): the table made on the host, and
        # the CALL that starts its transfer (asynchronous: nothing here
        # waits for it to land).
        with span("table.host_init", table=name):
            host = np.zeros(self.padded_shape, dtype=self.dtype)
            if init_array is not None:
                check(tuple(init_array.shape) == self.logical_shape,
                      f"init shape {init_array.shape} != "
                      f"{self.logical_shape}")
                host[tuple(slice(0, s) for s in self.logical_shape)] = \
                    init_array
        with span("table.device_put", table=name):
            self.data = jax.device_put(host, self.sharding)

        # Updater state: shard each leaf along the same logical axis, shifted
        # by any leading worker axis (AdaGrad's [num_workers, ...] g2).
        # Cross-replica state sharding (arXiv 2004.13336; docs/DESIGN.md
        # "Sharded updater state"): on a mesh with a replica ("worker")
        # axis the data stays replicated across it (row lookups/serving
        # read it without collectives) but P(server) state leaves would be
        # replicated too — pure waste, since the update math is
        # elementwise. Sharding each leaf's row axis over (server, worker)
        # instead holds 1/k of the state per replica; the update step
        # slices the delta onto the state shard and all-gathers only the
        # updated data rows, and because no cross-element reduction exists
        # in any updater the params stay BITWISE-equal to the unsharded
        # layout (tested, pow-2 axes).
        mode = (state_sharding if state_sharding is not None
                else get_flag("state_sharding"))
        check(mode in ("auto", "on", "off"),
              f"state_sharding must be auto|on|off, got {mode!r}")
        replicas = mesh.shape.get(mesh_lib.WORKER_AXIS, 1)
        self.state_replicas = replicas
        want_sharded = mode != "off" and replicas > 1
        with span("table.host_init", table=name, leaves="state"):
            state_host = updater.init_state(self.padded_shape, self.dtype,
                                            num_workers)
        check(not (mode == "on" and replicas < 2 and state_host),
              f"state_sharding=on: table '{name}' carries updater state "
              "but the mesh has no replica ('worker') axis to shard it "
              "over — add one (e.g. -mesh_shape=server:N,worker:K) or "
              "use auto/off")
        self.state = {}
        self.state_sharded = False
        for key, leaf in state_host.items():
            leaf_axis = self._leaf_axis(leaf.ndim)
            axes: Any = mesh_lib.SERVER_AXIS
            if want_sharded and \
                    leaf.shape[leaf_axis] % (num_servers * replicas) == 0:
                axes = (mesh_lib.SERVER_AXIS, mesh_lib.WORKER_AXIS)
                self.state_sharded = True
            else:
                check(not (want_sharded and mode == "on"),
                      f"state_sharding=on: leaf '{key}' of table '{name}' "
                      f"(axis {leaf_axis} extent {leaf.shape[leaf_axis]}) "
                      f"does not divide server x replica = "
                      f"{num_servers * replicas}")
            leaf_sharding = mesh_lib.table_sharding(mesh, leaf.ndim,
                                                    leaf_axis,
                                                    mesh_axis=axes)
            with span("table.device_put", table=name, leaf=key):
                self.state[key] = jax.device_put(leaf, leaf_sharding)
        # When the transfers those calls started had LANDED is the start-up
        # timeline's to stamp, from its own thread: nothing here waits.
        startup.watch_transfers([self.data, *self.state.values()])

        self._row_plane = "fused_stateful" if fused_rows_selected(
            updater, self.padded_shape, self.dtype, num_servers == 1,
            self.state_sharded) else "xla"
        self._build_kernels()
        self._lock = make_lock("core.store")
        devices = list(self.sharding.device_set)
        self._serial_exec = (len(devices) > 1
                             and devices[0].platform == "cpu")
        # Memory accounting (docs/OBSERVABILITY.md): host-computed at
        # init/load/publish — never on the hot path. `name` is a
        # model-declared table name: bounded by construction.
        # graftlint: disable=unbounded-metric-name
        self._g_data_bytes = gauge(f"ps.data_bytes.{name}")
        # graftlint: disable=unbounded-metric-name
        self._g_state_bytes = gauge(f"ps.state_bytes.{name}")
        self._publish_memory_gauges()

    @property
    def row_plane(self) -> str:
        """Which data plane serves this store's row UPDATES:
        ``"fused_stateful"`` (the Pallas kernel of
        ``ops/pallas_rows.fused_stateful_rows``) where
        :func:`fused_rows_selected` holds of what the store shows,
        ``"xla"`` elsewhere. Row READS are XLA's gather on both."""
        return self._row_plane

    @contextlib.contextmanager
    def _dispatch_scope(self):
        """Store-kernel dispatch guard. On multi-device CPU this takes the
        process-wide collective lock (outer) around the store lock, and the
        caller must finish execution before leaving (see _CPU_COLLECTIVE_LOCK
        above); elsewhere it is just the store lock."""
        if self._serial_exec:
            with _CPU_COLLECTIVE_LOCK, self._lock:
                yield
        else:
            with self._lock:
                yield

    def _finish(self, out):
        """Block on ``out`` (any pytree) when this store serializes
        execution (multi-device CPU); pass it through untouched on
        accelerators. Callers must pass EVERY output of the dispatched
        executable: XLA's thunk-based CPU runtime readies outputs
        per-defining-thunk, so blocking on a subset can release the
        collective lock while sibling-output thunks still occupy the
        rendezvous."""
        if self._serial_exec:
            jax.block_until_ready(out)
        return out

    # -- jitted kernels ----------------------------------------------------
    def _build_kernels(self) -> None:
        updater = self.updater
        pad = self._pad
        axis = self.shard_axis
        ndim = len(self.padded_shape)
        # Pin kernel outputs to the live layouts so (a) donation reuses
        # the input buffers (mismatched layouts silently fall back to
        # copies) and (b) sharded state stays sharded: GSPMD slices the
        # replicated delta onto each state shard (the reduce-scatter leg
        # of 2004.13336 — a plain dynamic-slice here because the store
        # receives the already-merged delta) and all-gathers only the
        # updated data rows back to the replicated param layout.
        state_shardings = {k: v.sharding for k, v in self.state.items()}
        pin_layouts = len(self.sharding.device_set) > 1

        def _pin(data, state):
            if not pin_layouts:
                return data, state
            data = jax.lax.with_sharding_constraint(data, self.sharding)
            state = {k: jax.lax.with_sharding_constraint(
                v, state_shardings[k]) for k, v in state.items()}
            return data, state

        # Dense plane under sharded state: run the updater MATH in the
        # unsharded (server-only) state layout and reshard the results.
        # Elementwise math is layout-invariant in exact arithmetic, but
        # XLA:CPU's codegen is not — fusing the same chain over
        # differently-partitioned operands contracts mul/sub into fma (and
        # div/sqrt into rsqrt) differently, measured as ~tens-of-ulp drift
        # on the adagrad/dcasgd dense path (the PR-10 allreduce rounding
        # story again). Gathering state to the off-mode layout makes the
        # math HLO identical in both modes — bitwise parity by structure —
        # at the cost of a TRANSIENT full-size state working set on dense
        # updates only; the row plane (the capacity-critical embedding hot
        # path) computes on gathered row blocks, which are layout-invariant
        # already, and stays shard-local end to end.
        math_shardings = {
            k: mesh_lib.table_sharding(self.mesh, self.state[k].ndim,
                                       self._leaf_axis(self.state[k].ndim))
            for k in self.state}
        gather_for_dense = self.state_sharded

        def dense(data, state, delta, *opt):
            if pad:
                pads = [(0, 0)] * ndim
                pads[axis] = (0, pad)
                delta = jnp.pad(delta, pads)
            if gather_for_dense:
                state = {k: jax.lax.with_sharding_constraint(
                    v, math_shardings[k]) for k, v in state.items()}
                new_data, new_state = updater.update_dense(data, state,
                                                           delta, opt)
                # Pin the math RESULTS to the unsharded layout too before
                # resharding for storage: without this, GSPMD propagates
                # the sharded storage layout backwards through shared
                # subexpressions (adagrad's g2_w feeds both the step and
                # the stored accumulator) and the math region partitions
                # differently from the off mode after all.
                new_state = {k: jax.lax.with_sharding_constraint(
                    v, math_shardings[k]) for k, v in new_state.items()}
                return _pin(new_data, new_state)
            return _pin(*updater.update_dense(data, state, delta, opt))

        def access(data):
            if pad:
                index = [slice(None)] * ndim
                index[axis] = slice(0, self.logical_shape[axis])
                return data[tuple(index)]
            return data

        fused = self._row_plane == "fused_stateful"
        interpret = False
        if fused:
            from multiverso_tpu.ops import pallas_interpret
            interpret = pallas_interpret(self.sharding.device_set)
        update = build_row_update(updater, fused, interpret)

        def rows(data, state, row_ids, delta, *opt):
            return _pin(*update(data, state, row_ids, delta, *opt))

        def access_rows(data, row_ids):
            return jnp.take(data, row_ids, axis=axis, mode="clip")
        self._access_rows = jax.jit(access_rows)
        self._dense_update = jax.jit(dense, donate_argnums=(0, 1))
        self._row_update = jax.jit(rows, donate_argnums=(0, 1))
        self._access = jax.jit(access)
        # The un-jitted row functions of this store's row plane: a
        # TableGroup (tables/table_group.py) traces them into its one
        # program over all members, so the row math has one definition.
        self.row_update_fn = rows
        self.access_rows_fn = access_rows

    # -- server ops (ref ServerTable::ProcessAdd/ProcessGet) ---------------
    # Every dispatch happens under the store lock: the update kernels DONATE
    # the parameter buffer, so a concurrent reader must never capture a
    # reference that a writer is about to invalidate. The lock is held only
    # for the (async) dispatch, never for device execution.
    def apply_dense(self, delta: jax.Array, opt: AddOption) -> None:
        with self._dispatch_scope():
            self.data, self.state = self._dense_update(
                self.data, self.state, delta, *opt.scalars())
            self._finish((self.data, self.state))

    def apply_rows(self, row_ids: jax.Array, delta: jax.Array,
                   opt: AddOption) -> None:
        with self._dispatch_scope():
            self.data, self.state = self._row_update(
                self.data, self.state, row_ids, delta, *opt.scalars())
            self._finish((self.data, self.state))

    def read(self) -> jax.Array:
        """Logical (unpadded) view of the whole table (fresh buffer)."""
        with self._dispatch_scope():
            return self._finish(self._access(self.data))

    def read_rows(self, row_ids: jax.Array) -> jax.Array:
        with self._dispatch_scope():
            return self._finish(self._access_rows(self.data, row_ids))

    def read_rows_with(self, gather_fn: Callable, row_ids) -> jax.Array:
        """Dispatch a CALLER-OWNED jitted gather against the live buffer
        under the store's dispatch guard. The serving plane uses this for
        bucket-shaped batched lookups: the caller keeps its own jit (so
        its executable-per-bucket accounting is exact and isolated from
        training-path shapes) while the store lock guarantees the gather
        never captures a parameter buffer an updater is about to donate
        away — the same snapshot contract as :meth:`read_rows`."""
        with self._dispatch_scope():
            return self._finish(gather_fn(self.data, row_ids))

    def block(self) -> None:
        """Wait until all previously dispatched updates have executed."""
        jax.block_until_ready(self.read())

    def write_dense(self, values) -> None:
        """Overwrite the logical table contents — the whole-replica
        publish the comm-policy planes need (an allreduce/model-average
        worker replaces the stored params at a sync point; the Add API
        deliberately only ships deltas). Pads to the physical shape and
        lays the buffer out with the store's sharding. Concurrent readers
        keep the references they already hold (the same swap discipline
        as :meth:`load_state`); the store lock orders the swap against
        in-flight updater dispatches."""
        values = np.asarray(values, dtype=self.dtype)
        check(tuple(values.shape) == self.logical_shape,
              f"publish shape {values.shape} != {self.logical_shape}")
        if self._pad:
            host = np.zeros(self.padded_shape, dtype=self.dtype)
            host[tuple(slice(0, s) for s in self.logical_shape)] = values
        else:
            host = values
        with self._dispatch_scope():
            self.data = jax.device_put(host, self.sharding)

    # -- memory accounting (docs/OBSERVABILITY.md ps.*_bytes) --------------
    def data_bytes(self) -> int:
        """Physical parameter bytes held across the mesh (replication
        counted per copy)."""
        return _physical_bytes(self.data)

    def state_bytes(self) -> int:
        """Physical updater-state bytes held across the mesh — the number
        the cross-replica sharding shrinks by ~(k-1)/k."""
        return sum(_physical_bytes(leaf) for leaf in self.state.values())

    def _publish_memory_gauges(self) -> None:
        self._g_data_bytes.set(self.data_bytes())
        self._g_state_bytes.set(self.state_bytes())

    # -- checkpointing (ref table_interface.h:61-75) -----------------------
    def _leaf_axis(self, leaf_ndim: int) -> int:
        """A state leaf's shard axis: the table's, shifted by any leading
        worker axis (AdaGrad's [num_workers, ...] g2)."""
        return self.shard_axis + (leaf_ndim - len(self.padded_shape))

    def store_state(self) -> Dict[str, np.ndarray]:
        """Payloads carry LOGICAL extents (shard-axis padding stripped from
        data and state alike): physical padding depends on the mesh the
        writer ran on, and a checkpoint must restore onto a mesh with a
        different server/replica count (load re-pads + re-shards)."""
        out = {"data": np.asarray(self.read())}
        logical = self.logical_shape[self.shard_axis]
        for key, leaf in self.state.items():
            arr = np.asarray(leaf)
            sl = [slice(None)] * arr.ndim
            sl[self._leaf_axis(arr.ndim)] = slice(0, logical)
            out[f"state/{key}"] = arr[tuple(sl)]
        return out

    def load_state(self, payload: Dict[str, np.ndarray]) -> None:
        data = np.asarray(payload["data"])
        check(tuple(data.shape) == self.logical_shape,
              f"checkpoint data shape {tuple(data.shape)} incompatible "
              f"with table '{self.name}' {self.logical_shape}")
        host = np.zeros(self.padded_shape, dtype=self.dtype)
        host[tuple(slice(0, s) for s in self.logical_shape)] = data
        self.data = jax.device_put(host, self.sharding)
        logical = self.logical_shape[self.shard_axis]
        for key in list(self.state):
            saved = payload.get(f"state/{key}")
            if saved is None:
                continue
            leaf = self.state[key]
            saved = np.asarray(saved)
            ax = self._leaf_axis(leaf.ndim)
            # Accept logical-extent saves (current format) and legacy
            # padded saves (shard-axis extent >= logical; the pad region
            # was zeros by construction). Every OTHER dim must match
            # exactly — a different worker count or column width is a
            # genuinely incompatible checkpoint and must fail loudly, not
            # silently truncate.
            check(saved.ndim == leaf.ndim
                  and all(saved.shape[i] == leaf.shape[i]
                          for i in range(leaf.ndim) if i != ax)
                  and saved.shape[ax] >= logical,
                  f"checkpoint state leaf '{key}' shape "
                  f"{tuple(saved.shape)} incompatible with live leaf "
                  f"{tuple(leaf.shape)} of table '{self.name}' "
                  f"(logical shard-axis extent {logical})")
            sl = [slice(None)] * leaf.ndim
            sl[ax] = slice(0, logical)
            # Checkpoint backends may widen extension dtypes (bf16) to
            # f32 for serialization; restore the live leaf's dtype. The
            # device_put with the LIVE sharding is what reshards a
            # checkpoint written under a different replica count.
            host_leaf = np.zeros(leaf.shape, dtype=np.dtype(leaf.dtype))
            host_leaf[tuple(sl)] = saved[tuple(sl)].astype(leaf.dtype)
            self.state[key] = jax.device_put(host_leaf, leaf.sharding)
        self._publish_memory_gauges()


class WorkerTable:
    """Client-side handle: sync wraps async, per-request waiters.

    Ref ``src/table.cpp:27-111``. ``wait`` blocks on the dispatched XLA
    computation; the reference's counted ``Waiter`` (one notify per touched
    server) is subsumed by a single sharded computation touching all shards.
    """

    # Bound on unwaited async requests kept resolvable. Fire-and-forget
    # adds don't need an entry at all (see _register_add); gets beyond the
    # cap are evicted oldest-first — an abandoned get was never going to be
    # fetched (the reference frees waiters on reply; ours resolve lazily).
    MAX_PENDING = 1 << 16

    def __init__(self, store: ServerStore):
        self.store = store
        self._msg_id = 0
        self._pending: "collections.OrderedDict[int, Callable[[], Any]]" = \
            collections.OrderedDict()
        self._lock = make_lock("core.worker_table")
        from multiverso_tpu.core.zoo import Zoo
        zoo = Zoo.get()
        self.table_id = zoo.register_table(self)
        # The store locks form an ordered family keyed by table id: a
        # TableGroup holds several at once, in that order.
        set_lock_order(store._lock, self.table_id)
        # BSP gating (SyncServer semantics) when multiple workers share the
        # host-driven path (ref src/server.cpp:68-222). Sized by LOCAL
        # workers only: this store is per-process state, and remote
        # workers' clocks would never tick here (VERDICT r2 weak #3 — the
        # global sizing deadlocked every multi-process sync run after round
        # 1). Cross-process BSP lives where the cross-process state lives:
        # the clock-gated DCN tables (DistributedTableBase) or the
        # collective add_synced path.
        self._sync = None
        if zoo.sync_mode and zoo.num_local_workers > 1:
            from multiverso_tpu.core.sync_coordinator import SyncCoordinator
            self._sync = SyncCoordinator(zoo.num_local_workers,
                                         name=getattr(self, "name", ""))
        # SSP staleness-adaptive DC-ASGD (docs/DESIGN.md): feed measured
        # clock lag into the add options of staleness-aware updaters.
        self._staleness_adaptive = bool(get_flag("staleness_adaptive"))

    # -- BSP gates (no-ops in async mode / single-worker worlds). Context
    # managers so a raise during application releases the in-flight slot
    # (abort) instead of wedging every future get. --------------------------
    def _local_wid(self, wid: int) -> int:
        """Global worker id -> this process's local index (ids are assigned
        contiguously per process: rank * num_local + k)."""
        return wid % self._sync.num_workers

    @contextlib.contextmanager
    def _bsp_add(self, option: Optional[AddOption]):
        """Gate + stamp: yields the AddOption the caller must dispatch
        with. Under ``-staleness_adaptive`` with a staleness-aware updater
        (DC-ASGD family), the yielded option carries this worker's
        MEASURED add-clock lag (sampled after the gate admits the add, so
        it reflects the committed updates the worker's view is actually
        missing); otherwise the option passes through untouched."""
        opt = option or AddOption()
        if self._sync is None:
            yield opt
            return
        wid = self._local_wid(opt.worker_id)
        self._sync.acquire_add(wid)
        if (self._staleness_adaptive and opt.staleness < 0
                and getattr(self.store.updater, "staleness_aware", False)):
            opt = dataclasses.replace(opt,
                                      staleness=self._sync.lag(wid))
        try:
            yield opt
        except BaseException:
            self._sync.abort_add(wid)
            raise
        self._sync.commit_add(wid)

    @contextlib.contextmanager
    def _bsp_get(self, option: Optional[GetOption]):
        if self._sync is None:
            yield
            return
        wid = self._local_wid(option.worker_id if option else 0)
        self._sync.acquire_get(wid)
        yield
        self._sync.commit_get(wid)

    def finish_train(self, worker_id: int) -> None:
        """``Zoo::FinishTrain`` analog (ref src/zoo.cpp:152-161): release a
        finished worker from the BSP clocks so stragglers can drain."""
        if self._sync is not None:
            self._sync.finish_train(self._local_wid(worker_id))

    # -- cross-process BSP -------------------------------------------------
    def add_synced(self, delta, option: Optional[AddOption] = None) -> None:
        """BSP across PROCESSES: allreduce the delta over all JAX processes,
        then every process applies the identical merged delta to its
        replica — the collective form of the SyncServer guarantee (every
        worker's i-th view identical). All processes must call this the
        same number of times (it is a collective)."""
        from multiverso_tpu.parallel import collectives

        merged = collectives.aggregate(
            np.asarray(delta, dtype=self.store.dtype))
        self.add(merged, option)

    # -- waiter bookkeeping ------------------------------------------------
    def _register(self, resolve: Callable[[], Any]) -> int:
        with self._lock:
            self._msg_id += 1
            msg_id = self._msg_id
            self._pending[msg_id] = resolve
            while len(self._pending) > self.MAX_PENDING:
                self._pending.popitem(last=False)
        return msg_id

    def _register_add(self) -> int:
        """Adds need no stored state: waiting for ANY add means waiting for
        the store's update stream — so fire-and-forget add_async doesn't
        grow the pending map."""
        with self._lock:
            self._msg_id += 1
            return self._msg_id

    def wait(self, msg_id: int) -> Any:
        with self._lock:
            resolve = self._pending.pop(msg_id, None)
        if resolve is None:
            # Not a registered get: either an add handle (resolve = drain
            # the update stream) or an evicted/unknown id.
            check(0 < msg_id <= self._msg_id, f"unknown msg_id {msg_id}")
            return self.store.block()
        return resolve()

    @property
    def name(self) -> str:
        return self.store.name

    def close(self) -> None:
        with self._lock:
            self._pending.clear()


def default_add_option() -> AddOption:
    return AddOption()


def default_get_option() -> GetOption:
    return GetOption()
