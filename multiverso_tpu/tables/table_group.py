"""Grouped row operations over several :class:`MatrixTable`\\ s.

A model with one embedding table per categorical field (DLRM: 26) pulls
and pushes a row batch of EVERY table in each step. Through the per-table
client that is one launch, one wait and one copy per table and direction:
52 serial device round trips a step, each around a fraction of a
millisecond of device work (PERF.md 5). A :class:`TableGroup` serves the
same calls for all its members at once:

* ``get_rows`` — ONE host-to-device copy of the ids, ONE jitted program
  over the tuple of the members' live ``store.data`` (each member's own
  row gather), ONE device-to-host copy of the rows, already laid out as
  ``[B, n_tables, D]`` where the widths agree;
* ``add_rows`` — ONE host-to-device copy of the deltas and ONE jitted
  program over ``(datas, states, ids, deltas, option scalars)`` with every
  member's data and state donated, traced from the SAME per-store row
  function the per-table program is jitted from
  (``ServerStore.row_update_fn``). The call returns after the device has
  executed the update, waiting on an output of that very program: no
  program is launched for the sake of waiting and no table is copied.

* ``get_rows_device`` — the same pull with the rows LEFT on the device
  (the gather program's output as it is), and ``add_rows`` takes device
  arrays as they are: a step that pulls, computes and pushes on the
  tables' own device moves only the ids over the host link.

The program is over a TUPLE of arrays, so members may differ in rows and
width. Nothing is cached across calls: every call reads ``store.data`` /
``store.state`` afresh under the stores' locks and writes the new buffers
back, because checkpoints, publishes and benchmarks swap them.

A :class:`LocalTableGroup` is the twin without tables: the same calls and
the identical programs (the two builders below) over arrays the group owns.
A model holds one kind or the other and asks neither which it is.
"""

from __future__ import annotations

import contextlib
import time
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from multiverso_tpu.core.options import (AddOption, GetOption,
                                         MatrixTableOption)
from multiverso_tpu.core.table import (_CPU_COLLECTIVE_LOCK, build_row_update,
                                       fused_rows_selected)
from multiverso_tpu.core.updater import get_updater
from multiverso_tpu.ops import pallas_interpret
from multiverso_tpu.tables.matrix_table import MatrixTable, initial_rows
from multiverso_tpu.telemetry import counter, phase, register_program
from multiverso_tpu.utils.dashboard import monitor
from multiverso_tpu.utils.log import check

__all__ = ["TableGroup", "LocalTableGroup", "build_group_access",
           "build_group_update", "group_scalars"]

#: Per-member id counts of a call whose ids arrive as one flat
#: concatenation; ``None`` is the ``[B, n_tables]`` matrix layout.
Lengths = Optional[Tuple[int, ...]]


def _split(flat, sizes: Sequence[int]) -> list:
    """Consecutive static slices of a flat (host or traced) array."""
    offs = np.concatenate([[0], np.cumsum(sizes)])
    return [flat[offs[i]:offs[i + 1]] for i in range(len(sizes))]


def _blocks(flat, lengths: Sequence[int], widths: Sequence[int]) -> list:
    """The members' ``[len_i, D_i]`` blocks of their raveled
    concatenation."""
    return [part.reshape(n, w) for part, n, w in zip(
        _split(flat, [n * w for n, w in zip(lengths, widths)]),
        lengths, widths)]


def _member_ids(ids: jax.Array, lengths: Lengths) -> List[jax.Array]:
    if lengths is None:
        return [ids[:, i] for i in range(ids.shape[1])]
    return _split(ids, lengths)


def build_group_access(access_fns: Sequence[Callable]) -> Callable:
    """``(datas, ids, lengths=None, blocks=False) -> rows``: every member's
    row gather in one program. Matrix layout (``ids`` ``[B, n]``, equal
    widths) returns ``[B, n, D]``; flat layout (``ids`` the members' id
    vectors concatenated, ``lengths`` static) returns the members' row
    blocks raveled and concatenated (ONE array for one copy to the host)
    or, with ``blocks``, as they are (a tuple, for a caller that leaves
    them on the device)."""
    def group_access_rows(datas, ids, lengths=None, blocks=False):
        rows = [fn(data, member) for fn, data, member in
                zip(access_fns, datas, _member_ids(ids, lengths))]
        if lengths is None:
            return jnp.stack(rows, axis=1)
        if blocks:
            return tuple(rows)
        return jnp.concatenate([r.reshape(-1) for r in rows])

    return jax.jit(group_access_rows, static_argnames=("lengths", "blocks"))


def build_group_update(update_fns: Sequence[Callable]) -> Callable:
    """``(datas, states, ids, deltas, *group_scalars, lengths=None) ->
    (datas, states, done)``: every member's row update in one program that
    donates all data and state. ``update_fns[i]`` has the per-store
    signature ``(data, state, row_ids, delta, *opt)``. In the flat layout
    ``deltas`` is the members' blocks raveled and concatenated, or a tuple
    of the blocks themselves. ``done`` reads one
    element of each UPDATED table: the output a caller waits on (it is
    never donated, so no later update can delete it under the waiter)."""
    def group_rows(datas, states, ids, deltas, worker_id, momentum, lr, rho,
                   lam, staleness, lengths=None):
        member_ids = _member_ids(ids, lengths)
        if lengths is None:
            member_deltas = [deltas[:, i] for i in range(deltas.shape[1])]
        elif isinstance(deltas, tuple):
            member_deltas = deltas
        else:
            member_deltas = _blocks(deltas, lengths,
                                    [d.shape[1] for d in datas])
        new_datas, new_states = [], []
        for i, fn in enumerate(update_fns):
            data, state = fn(datas[i], states[i], member_ids[i],
                             member_deltas[i], worker_id, momentum, lr, rho,
                             lam, staleness[i])
            new_datas.append(data)
            new_states.append(state)
        done = jnp.stack([d[0, 0] for d in new_datas])
        return tuple(new_datas), tuple(new_states), done

    return jax.jit(group_rows, donate_argnums=(0, 1),
                   static_argnames="lengths")


def group_scalars(options: Sequence[AddOption]) -> tuple:
    """The grouped update's option arguments: the members share every
    scalar but the staleness stamp (a BSP gate may measure a different
    lag per table), which travels as one ``[n]`` vector."""
    return options[0].scalars()[:5] + (
        np.asarray([o.staleness for o in options], np.float32),)


class _RowGroup:
    """The host side of a grouped call, which both kinds of group share:
    ids and deltas as the programs take them."""

    def __init__(self, widths: Sequence[int], dtype):
        self.widths = tuple(widths)
        self.dtype = np.dtype(dtype)
        self._stackable = len(set(self.widths)) == 1

    def _layout(self, ids) -> Tuple[np.ndarray, Lengths]:
        """``ids`` as the program takes them: the ``[B, n]`` matrix where
        the widths agree, else the members' vectors concatenated."""
        n = len(self.widths)
        if isinstance(ids, np.ndarray) and ids.ndim == 2:
            check(ids.shape[1] == n,
                  f"id matrix has {ids.shape[1]} columns for {n} tables")
            if self._stackable:
                return np.asarray(ids, np.int32), None
            ids = list(ids.T)
        check(len(ids) == n, f"{len(ids)} id vectors for {n} tables")
        vectors = [np.asarray(v, np.int32).reshape(-1) for v in ids]
        return np.concatenate(vectors), tuple(len(v) for v in vectors)

    def _on_device(self, delta) -> bool:
        return isinstance(delta, jax.Array) and delta.dtype == self.dtype

    def _delta_layout(self, ids, deltas):
        """``(ids, lengths, deltas, bytes a member, on the device?)`` of an
        ``add_rows`` call. Device arrays of the group's dtype go to the
        program as they are (flat layout: a tuple of blocks); anything else
        is a host array (flat layout: ONE, the blocks raveled and
        concatenated). Shapes are checked before anything is donated."""
        ids, lengths = self._layout(ids)
        n = len(self.widths)
        if lengths is None:
            device = self._on_device(deltas)
            if not device:
                deltas = np.asarray(deltas, self.dtype)
            check(deltas.shape == ids.shape + self.widths[:1],
                  f"row delta shape {deltas.shape} != "
                  f"{ids.shape + self.widths[:1]}")
            return ids, lengths, deltas, [deltas.nbytes // n] * n, device
        device = all(self._on_device(d) for d in deltas)
        blocks = list(deltas) if device else [
            np.asarray(d, self.dtype) for d in deltas]
        want = list(zip(lengths, self.widths))
        check([b.shape for b in blocks] == want,
              f"row delta shapes {[b.shape for b in blocks]} != {want}")
        deltas = tuple(blocks) if device else np.concatenate(
            [b.reshape(-1) for b in blocks])
        return ids, lengths, deltas, [b.nbytes for b in blocks], device


class TableGroup(_RowGroup):
    """Row get/add for a list of :class:`MatrixTable`\\ s in one launch and
    one copy each way, or no copy where the caller's rows stay on the
    device (``get_rows_device``, device deltas). Columns of a call follow
    the order the tables were given in; locks and BSP gates are taken in
    ascending table id.

    Covered, decided from what the members show: plain ``MatrixTable``\\ s
    (a ``SparseMatrixTable`` keeps per-worker staleness bitmaps the group
    does not maintain) on one mesh with equal data shardings and one dtype.
    A member on the Pallas row plane contributes its own row functions to
    the program, like any other.
    """

    def __init__(self, tables: Sequence[MatrixTable]):
        self.tables = list(tables)
        check(len(self.tables) > 0, "a table group needs a member")
        for t in self.tables:
            check(type(t) is MatrixTable,
                  f"table group member '{getattr(t, 'name', t)}' is a "
                  f"{type(t).__name__}: only plain MatrixTables group")
        check(len({t.table_id for t in self.tables}) == len(self.tables),
              "a table may be in a group once")
        first = self.tables[0].store
        for t in self.tables[1:]:
            s = t.store
            check(s.sharding == first.sharding and s.dtype == first.dtype,
                  f"table group member '{s.name}' ({s.sharding}, {s.dtype})"
                  f" is not laid out as '{first.name}' ({first.sharding}, "
                  f"{first.dtype}): group tables of one mesh, sharding "
                  "and dtype")
        self._stores = [t.store for t in self.tables]
        self._by_id = sorted(self.tables, key=lambda t: t.table_id)
        self._serial_exec = first._serial_exec
        super().__init__([t.num_col for t in self.tables], first.dtype)
        self._access = build_group_access(
            [s.access_rows_fn for s in self._stores])
        self._update = build_group_update(
            [s.row_update_fn for s in self._stores])
        self._update_planes = sorted({s.row_plane for s in self._stores})

    # -- the donation discipline of ServerStore, for every member ----------
    @contextlib.contextmanager
    def _dispatch_scope(self):
        """Every member's store lock, in table-id order, for the dispatch
        only; on a multi-device CPU mesh the process-wide collective lock
        ONCE around them (it is not re-entrant)."""
        with contextlib.ExitStack() as stack:
            if self._serial_exec:
                stack.enter_context(_CPU_COLLECTIVE_LOCK)
            for t in self._by_id:
                stack.enter_context(t.store._lock)
            yield

    def _finish(self, out):
        """Multi-device CPU: block on EVERY output before the locks go
        (``ServerStore._finish``)."""
        if self._serial_exec:
            jax.block_until_ready(out)
        return out

    def _record(self, nbytes: Sequence[int], ms: float) -> None:
        """One grouped call in the counters: ``comm.ps.*`` per member with
        its own bytes (the call's latency once), and how often the
        mechanism engaged."""
        for i, t in enumerate(self.tables):
            t.comm.record_client_op(nbytes[i], ms if i == 0 else None)
        counter("table.group.calls").inc()
        counter("table.group.member_ops").inc(len(self.tables))

    # -- row ops -------------------------------------------------------------
    def lives_with(self, array: jax.Array) -> bool:
        """Whether the members live on ONE device and ``array`` on the same:
        a program over ``array`` then takes rows pulled by
        :meth:`get_rows_device` as they are. (Rows of a group that spans a
        mesh carry the mesh's sharding, and a multi-device CPU program
        belongs under the collective lock: such a caller pulls to the
        host.)"""
        devices = self._stores[0].sharding.device_set
        return len(devices) == 1 and devices == array.devices()

    def _pull(self, ids, option: Optional[GetOption], device: bool):
        """The launch of a pull: ``(the gather program's output, lengths)``.
        The output is a fresh buffer, never an alias of a ``store.data``:
        no later donated update can change or delete it (the contract of
        ``ServerStore.read_rows_with``)."""
        with phase("table.get_rows.dispatch"):
            ids, lengths = self._layout(ids)
            t0 = time.perf_counter()
            with contextlib.ExitStack() as gates:
                for t in self._by_id:
                    gates.enter_context(t._bsp_get(option))
                with self._dispatch_scope():
                    datas = tuple(s.data for s in self._stores)
                    static = {"lengths": lengths,
                              "blocks": device and lengths is not None}
                    register_program(self._access, (datas, ids), static)
                    out = self._finish(self._access(datas, ids, **static))
            counts = lengths or (len(ids),) * len(self.tables)
            self._record([n * w * self.dtype.itemsize
                          for n, w in zip(counts, self.widths)],
                         (time.perf_counter() - t0) * 1e3)
        return out, lengths

    def get_rows(self, ids, option: Optional[GetOption] = None):
        """Rows of every member, on the host: ``ids`` is ``[B, n_tables]``
        (returns ``[B, n_tables, D]`` where the widths agree) or one id
        vector per table (returns one ``[len_i, D_i]`` array per table)."""
        with monitor("WORKER_TABLE_SYNC_GET"):
            out, lengths = self._pull(ids, option, device=False)
            with phase("table.get_rows.sync"):
                host = np.asarray(out)
        return host if lengths is None else _blocks(host, lengths,
                                                    self.widths)

    def get_rows_device(self, ids, option: Optional[GetOption] = None):
        """:meth:`get_rows` with the rows left where they are: the gather
        program's output, a ``jax.Array`` (or one per table) that no copy
        and no wait has touched. A snapshot like the host form's: a push
        after the pull does not show in it. The rows are COMMITTED to their
        device, and so is every output of a program over them; a caller's
        fresh leaves (``jnp.asarray``) are not, and jit compiles a program
        once for each: commit such leaves first (``jax.device_put`` to
        the rows' device: the same buffers) and the program compiles once."""
        with monitor("WORKER_TABLE_SYNC_GET"):
            out, lengths = self._pull(ids, option, device=True)
            # Nothing to wait for: the phase brackets the hand-over, so the
            # readers of the host form's copy find it (and read ~0).
            with phase("table.get_rows.sync"):
                counter("table.group.device_pulls").inc()
        return out if lengths is None else list(out)

    def add_rows(self, ids, deltas,
                 option: Optional[AddOption] = None) -> None:
        """Apply every member's row deltas through its updater; returns
        once the device has executed the update. ``deltas`` is
        ``[B, n_tables, D]`` beside an id matrix, else one
        ``[len_i, D_i]`` array per table. Device arrays of the group's
        dtype go to the program as they are; anything else is copied up
        from the host."""
        with monitor("WORKER_TABLE_SYNC_ADD"):
            with phase("table.add_rows.dispatch"):
                ids, lengths, deltas, nbytes, device = self._delta_layout(
                    ids, deltas)
                t0 = time.perf_counter()
                with contextlib.ExitStack() as gates:
                    opts = {t.table_id: gates.enter_context(
                        t._bsp_add(option)) for t in self._by_id}
                    scalars = group_scalars(
                        [opts[t.table_id] for t in self.tables])
                    with self._dispatch_scope():
                        args = (tuple(s.data for s in self._stores),
                                tuple(s.state for s in self._stores),
                                ids, deltas, *scalars)
                        register_program(self._update, args,
                                         {"lengths": lengths})
                        datas, states, done = self._update(
                            *args, lengths=lengths)
                        for s, data, state in zip(self._stores, datas,
                                                  states):
                            s.data, s.state = data, state
                        self._finish((datas, states, done))
                self._record(nbytes, (time.perf_counter() - t0) * 1e3)
                if device:
                    counter("table.group.device_pushes").inc()
                for plane in self._update_planes:
                    # One of two names (ServerStore.row_plane).
                    # graftlint: disable=unbounded-metric-name
                    counter(f"table.rows.plane.{plane}").inc()
            with phase("table.add_rows.sync"):
                jax.block_until_ready(done)


class LocalTableGroup(_RowGroup):
    """The twin of a :class:`TableGroup` without tables: the calls a model
    makes of a group, over arrays the group owns on the default device, one
    per ``MatrixTableOption``. A member starts from its option's own draw
    (``initial_rows``) and its updater's ``init_state``, and the programs
    are the two builders over the row functions a one-device store hands
    its group, on the row plane such a store picks
    (``core/table.fused_rows_selected``): what a ``TableGroup`` of
    same-option tables holds and runs, to the bit. No
    ``mv.init``, locks, gates, monitors or ``comm.ps.*`` counters: one
    worker, one thread."""

    def __init__(self, options: Sequence[MatrixTableOption]):
        options = list(options)
        check(len(options) > 0, "a table group needs a member")
        super().__init__([o.num_col for o in options], options[0].dtype)
        updaters = [get_updater(o.dtype, o.updater) for o in options]
        shapes = [(o.num_row, o.num_col) for o in options]
        self._datas = tuple(
            jnp.asarray(initial_rows(o)) if o.random_init
            else jnp.zeros(shape, self.dtype)
            for o, shape in zip(options, shapes))
        self._states = tuple(u.init_state(shape, self.dtype, 1)
                             for u, shape in zip(updaters, shapes))

        def take(data, ids):
            return jnp.take(data, ids, axis=0, mode="clip")

        interpret = pallas_interpret(self._datas[0].devices())
        self._access = build_group_access([take] * len(options))
        self._update = build_group_update([
            build_row_update(u, fused_rows_selected(
                u, shape, self.dtype, True, False), interpret)
            for u, shape in zip(updaters, shapes)])

    def lives_with(self, array: jax.Array) -> bool:
        return self._datas[0].devices() == array.devices()

    def get_rows_device(self, ids):
        ids, lengths = self._layout(ids)
        out = self._access(self._datas, ids, lengths=lengths,
                           blocks=lengths is not None)
        return out if lengths is None else list(out)

    def get_rows(self, ids):
        return jax.device_get(self.get_rows_device(ids))

    def add_rows(self, ids, deltas, option: AddOption) -> None:
        ids, lengths, deltas, _, _ = self._delta_layout(ids, deltas)
        self._datas, self._states, _ = self._update(
            self._datas, self._states, ids, deltas,
            *group_scalars([option] * len(self.widths)), lengths=lengths)

    def local_rows(self, member: int = 0) -> np.ndarray:
        """Whole-table snapshot of one member (parity tests)."""
        return np.asarray(self._datas[member])
