"""The hybrid parameter-server step, written once (docs/DESIGN.md "Hybrid
step").

A model whose embedding rows live behind a table group (``tables/
table_group.py``: a ``TableGroup`` on the PS plane, a ``LocalTableGroup`` as
the twin) and whose dense leaves are device-resident trains by one cycle:
pull the touched rows, run the delta program, merge the dense deltas over the
data-parallel axis WHERE THERE IS ONE
(:func:`~multiverso_tpu.parallel.comm_policy.build_dense_sync`: one donated
program over the whole tree; no program and no launch without an axis), apply them in a separate donated program, push the row deltas. Between
"ids known" and "row deltas pushed" the host makes one device call a phase,
and none for a phase that has nothing to do. The split in two programs is
``AllreduceModel``'s (models/logreg/model.py): the delta program is NOT
donated, so the parameters outlive it for the apply, and it returns
``lr * barrier(g)`` as an OUTPUT, which pins the rounding point (XLA:CPU
cannot contract the scale into the subtract as an fma). Both kinds of group
run the same programs, so PS against twin is bitwise.
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from multiverso_tpu.parallel import comm_policy as cp
from multiverso_tpu.telemetry import (counter, register_program, span,
                                      startup)

__all__ = ["HybridStep"]


class HybridStep:
    """One model's step, built once from what the model states.

    ``delta_fn(dense[0], rows, *batch) -> (dense deltas, row deltas, *aux)``
    and ``apply_fn(*dense, merged deltas, *apply_args) -> new dense`` are the
    model's own NAMED functions (the profiler shows ``jit_<name>``), jitted
    here, the first with ``delta_options`` as its compiler's options where
    the model gives any. ``dense`` names the model's attributes the apply
    replaces, the differentiated tree first: read at every call (checkpoints
    and benchmarks swap them). ``pull(ids, device)`` returns ``group``'s rows
    as the delta program takes them; ``push(ids, row deltas)`` closes over the
    model's ``_push_rows``, looked up when called (a control patches it).
    Whether the dense deltas are merged is decided here, once, from the mesh:
    ``dense_sync`` is the tree's one merge program under a ``dp_mesh`` whose
    ``dp_axis`` is larger than 1, and None otherwise (counters ``hybrid.
    dense_merge.programs`` / ``.elided``, one or the other a step). Spans:
    ``<prefix>.pull``, ``.compute`` (``.dispatch``, ``.sync``), ``.push``.
    """

    def __init__(self, model, delta_fn: Callable, apply_fn: Callable,
                 dense: Sequence[str], group, pull: Callable, push: Callable,
                 prefix: str, grad_bytes: int, apply_args: tuple = (),
                 dp_mesh=None, dp_axis=None, delta_options=None):
        self.delta = jax.jit(  # graftlint: disable=missing-donation
            delta_fn, compiler_options=delta_options)
        self.apply = jax.jit(apply_fn, donate_argnums=tuple(range(len(dense))))
        # Launched once a step between the delta and apply programs, the
        # merged tree taking the unmerged one's buffers; None where the
        # mesh has no axis to reduce over, and then nothing is launched.
        self.dense_sync = cp.build_dense_sync(
            dp_mesh, dp_axis, donate=True) if cp.reduce_axis_size(
                dp_mesh, dp_axis) > 1 else None
        # Where a merge leaves the dense leaves: on every device of its mesh.
        self._replicated = None if self.dense_sync is None else \
            NamedSharding(dp_mesh, PartitionSpec())
        self._model, self._dense, self._prefix = model, tuple(dense), prefix
        self._pull, self._push = pull, push
        self._apply_args, self._grad_bytes = tuple(apply_args), grad_bytes
        # Decided once, from where the arrays live: rows of a group on the
        # dense leaves' one device stay there from pull to push; a group
        # spread over a mesh hands the step host rows.
        self._on_device = group.lives_with(
            jax.tree_util.tree_leaves(getattr(model, dense[0]))[0])

    def __call__(self, ids, *batch, **attrs) -> list:
        """From "ids known" to "row deltas pushed"; returns the delta
        program's auxiliary outputs, still on the device."""
        prefix = self._prefix
        with span(prefix + ".pull", **attrs):
            rows = self._pull(ids, self._on_device)
        with span(prefix + ".compute", **attrs):
            with span(prefix + ".compute.dispatch"):
                dense = self._place(
                    [getattr(self._model, name) for name in self._dense],
                    rows)
                # The batch's host arrays go up inside the launching phase,
                # in ONE transfer, uncommitted as ``jnp.asarray`` left them:
                # a committed batch is another program text (its arguments'
                # shardings are spelled out) and another compile-cache key.
                args = (dense[0], rows, *jax.device_put(batch))
                register_program(self.delta, args)
                deltas, row_deltas, *aux = self.delta(*args)
                del args
                # The program holds its input: without this name the pulled
                # rows go when it ends, not when the step does.
                del rows
                if self.dense_sync is None:
                    counter("hybrid.dense_merge.elided").inc()
                else:
                    # One launch for the tree, the merged deltas in the
                    # unmerged ones' buffers (donated): no leaf held twice.
                    deltas = self.dense_sync(deltas)
                    counter("hybrid.dense_merge.programs").inc()
                register_program(
                    self.apply, (*dense, deltas, *self._apply_args))
                out = self.apply(*dense, deltas, *self._apply_args)
                self._keep(out if len(self._dense) > 1 else (out,))
                del deltas
                cp.record(cp.ALLREDUCE, self._grad_bytes)
            with span(prefix + ".compute.sync"):
                # A group over a mesh takes the row deltas on the host: the
                # copy is this phase's wait. On the device nothing waits
                # here: the push's program consumes them in stream order,
                # its launch overlaps the delta program, and ``add_rows``
                # returns only when the update has executed (the step's one
                # wait; 0.7 ms of an 10.4 ms DLRM step, PERF.md 6, PR 38).
                if not self._on_device:
                    row_deltas = np.asarray(row_deltas)
        with span(prefix + ".push", **attrs):
            self._push(ids, row_deltas)
        if not startup.ready:
            startup.mark_ready((prefix + ".pull", prefix + ".compute",
                                prefix + ".push"))
        return aux

    def _place(self, dense, rows) -> list:
        """The dense leaves where the step's committed arrays live. Fresh
        leaves (init, a checkpoint, a benchmark's seed) under a merge's mesh
        or beside committed rows are committed there first (beside the rows
        the same buffers), or the step's programs compile once for them and
        again for their own committed outputs. A merge takes host rows or
        the twin's: a PS group's device rows are committed to one device,
        off the mesh, and refused."""
        leaf = jax.tree_util.tree_leaves(dense[0])[0]
        home = self._replicated
        if self._on_device and rows.committed:
            if home is None:
                home = next(iter(rows.devices()))
            elif rows.devices() != home.device_set:
                # Never ran (the per-leaf merge failed inside jax on it too).
                raise ValueError(
                    "a dense merge over a dp_mesh takes host rows or the "
                    f"twin's: this group's rows are committed to "
                    f"{rows.devices()}, off the mesh (a PS group on one "
                    "device)")
        if home is not None and not leaf.committed:
            dense = jax.device_put(dense, home)
            self._keep(dense)
        return dense

    def _keep(self, dense) -> None:
        for name, value in zip(self._dense, dense):
            setattr(self._model, name, value)
