"""The hybrid parameter-server step, written once (docs/DESIGN.md "Hybrid
step").

A model whose embedding rows live behind a table group (``tables/
table_group.py``: a ``TableGroup`` on the PS plane, a ``LocalTableGroup`` as
the twin) and whose dense leaves are device-resident trains by one cycle:
pull the touched rows, run the delta program, merge the dense deltas leaf by
leaf (:func:`~multiverso_tpu.parallel.comm_policy.build_dense_sync`), apply
them in a separate donated program, push the row deltas. The split in two
programs is ``AllreduceModel``'s (models/logreg/model.py): the delta program
is NOT donated, so the parameters outlive it for the apply, and it returns
``lr * barrier(g)`` as an OUTPUT, which pins the rounding point (XLA:CPU
cannot contract the scale into the subtract as an fma). Both kinds of group
run the same programs, so PS against twin is bitwise.
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from multiverso_tpu.parallel import comm_policy as cp
from multiverso_tpu.telemetry import register_program, span

__all__ = ["HybridStep"]


class HybridStep:
    """One model's step, built once from what the model states.

    ``delta_fn(dense[0], rows, *batch) -> (dense deltas, row deltas, *aux)``
    and ``apply_fn(*dense, merged deltas, *apply_args) -> new dense`` are the
    model's own NAMED functions (the profiler shows ``jit_<name>``), jitted
    here. ``dense`` names the model's attributes the apply replaces, the
    differentiated tree first: read at every call, because checkpoints and
    benchmarks swap them. ``pull(ids, device)`` returns ``group``'s rows as
    the delta program takes them; ``push(ids, row deltas)`` is the model's
    closure over ITS ``_push_rows``, looked up when called (the benchmark's
    control patches that method on the class). Spans: ``<prefix>.pull``,
    ``.compute`` (``.dispatch`` and ``.sync`` inside it), ``.push``.
    """

    def __init__(self, model, delta_fn: Callable, apply_fn: Callable,
                 dense: Sequence[str], group, pull: Callable, push: Callable,
                 prefix: str, grad_bytes: int, apply_args: tuple = (),
                 dp_mesh=None, dp_axis=None):
        self.delta = jax.jit(delta_fn)  # graftlint: disable=missing-donation
        self.apply = jax.jit(apply_fn,
                             donate_argnums=tuple(range(len(dense))))
        # Dispatched once a leaf between the delta and apply programs; the
        # merged leaf takes the unmerged one's buffer.
        self.dense_sync = cp.build_dense_sync(dp_mesh, dp_axis, donate=True)
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
                dense = [getattr(self._model, name) for name in self._dense]
                if (self._on_device and rows.committed and not
                        jax.tree_util.tree_leaves(dense[0])[0].committed):
                    # Fresh leaves (init, a checkpoint, a benchmark's seed)
                    # beside committed rows: committed too (the same
                    # buffers), or the step's programs compile once for
                    # them and again for their own committed outputs.
                    dense = jax.device_put(dense, next(iter(rows.devices())))
                    self._keep(dense)
                # The batch's host arrays go up inside the launching phase.
                args = (dense[0], rows,
                        *jax.tree_util.tree_map(jnp.asarray, batch))
                register_program(self.delta, args)
                deltas, row_deltas, *aux = self.delta(*args)
                del args
                # The program holds its input: without this name the pulled
                # rows go when it ends, not when the step does.
                del rows
                # Leaf by leaf, each merge handed the only reference to its
                # unmerged delta (donated): no leaf is ever held twice.
                leaves, treedef = jax.tree_util.tree_flatten(deltas)
                del deltas
                merged = []
                while leaves:
                    merged.append(self.dense_sync(leaves.pop(0)))
                merged = treedef.unflatten(merged)
                register_program(
                    self.apply, (*dense, merged, *self._apply_args))
                out = self.apply(*dense, merged, *self._apply_args)
                self._keep(out if len(self._dense) > 1 else (out,))
                del merged
                cp.record(cp.ALLREDUCE, self._grad_bytes)
            with span(prefix + ".compute.sync"):
                # The phase ends when the row deltas exist: on the device,
                # or copied to the host for a group over a mesh.
                row_deltas = jax.block_until_ready(row_deltas) \
                    if self._on_device else np.asarray(row_deltas)
        with span(prefix + ".push", **attrs):
            self._push(ids, row_deltas)
        return aux

    def _keep(self, dense) -> None:
        for name, value in zip(self._dense, dense):
            setattr(self._model, name, value)
