"""Server-side pluggable updaters as pure jitted kernels.

Parity with the reference updater framework
(``include/multiverso/updater/updater.h:113-140``,
``src/updater/updater.cpp:45-57``): a factory keyed on the ``updater_type``
flag producing one of {default add, sgd, momentum_sgd, adagrad}; integer
tables always use the plain adder (``src/updater/updater.cpp:40-43``).

TPU-native design: an updater is a pair of *pure functions* over
``(data, state, delta, option-scalars)`` — one for dense whole-shard updates,
one for row-scatter updates — jitted once per table with buffer donation so
parameter arrays update in place in HBM. The reference's OpenMP hot loop
(``src/updater/updater.cpp:22-29``) becomes an XLA-fused elementwise kernel on
the VPU; row updates lower to scatter-add.

Per-worker AdaGrad accumulators (``adagrad_updater.h:17-20``) are kept as a
``[num_workers, ...]`` leading-axis state array indexed by the dynamic
``worker_id`` scalar — no recompilation per worker.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Tuple

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np

from multiverso_tpu.utils.configure import get_flag


@functools.lru_cache(maxsize=1)
def _strict_rows_math() -> bool:
    """XLA:CPU only: run row-block updater math one materialized primitive
    at a time. The CPU backend's LLVM codegen contracts mul+add chains to
    fma PER FUSION GROUP — the same math fused into a scatter kernel, an
    interpret-mode Pallas body, or a standalone region rounds differently
    per element (vector body vs scalar tail even diverge within one
    array). Materializing every intermediate pins each primitive to its
    strict IEEE result, making the XLA and Pallas row planes bitwise-equal
    BY VALUE (both match eager arithmetic). Real accelerator backends keep
    the fully fused math — this is a CPU-codegen determinism valve, not a
    semantics change."""
    return jax.default_backend() == "cpu"


def _eval_jaxpr_contraction_proof(jaxpr, consts, guard, *args):
    """Evaluate a jaxpr routing every float result through a division by
    a RUNTIME-opaque 1.0 (``select(guard, 1, 2)`` with an always-true
    runtime guard). ``x / 1.0`` is an exact IEEE identity, and it defeats
    the two XLA:CPU codegen behaviors that break cross-plane bitwise
    parity of identical math:

    * LLVM contracts ``fadd(fmul(a, b), c)`` to fma inside one fused
      loop — with the divide between them the add's operand is no longer
      a multiply;
    * XLA's fusion pass DUPLICATES cheap producers into every consumer
      fusion, and each copy may contract differently — so one jaxpr var
      can yield two different values (measured: a momentum ``smooth``
      fed both the state scatter and the data subtract with a 1-ulp
      split). Divides are "expensive" instructions XLA refuses to
      duplicate, so every consumer reads the same materialized bytes.

    ``optimization_barrier`` does NOT work for any of this — the
    pipeline elides it before fusion (verified: barrier count 0 in the
    optimized HLO)."""
    env: Dict[Any, Any] = {}
    one = jnp.where(guard, np.float32(1.0), np.float32(2.0))

    def read(v):
        return v.val if isinstance(v, jax.extend.core.Literal) else env[v]

    for var, val in zip(jaxpr.constvars, consts):
        env[var] = val
    for var, val in zip(jaxpr.invars, args):
        env[var] = val
    for eqn in jaxpr.eqns:
        outs = eqn.primitive.bind(*[read(v) for v in eqn.invars],
                                  **eqn.params)
        if not eqn.primitive.multiple_results:
            outs = [outs]
        for var, val in zip(eqn.outvars, outs):
            if jnp.issubdtype(val.dtype, jnp.floating):
                val = val / one.astype(val.dtype)
            env[var] = val
    return [read(v) for v in jaxpr.outvars]


def exact_elementwise(fn: Callable) -> Callable:
    """Wrap ``fn`` so its floating-point math rounds strictly per
    primitive (see :func:`_strict_rows_math`); pass-through off-CPU.
    ``guard`` must be a RUNTIME scalar bool that is always true (e.g.
    ``worker_id >= 0``) — the compiler must not be able to fold it."""
    def wrapped(guard, *args):
        if not _strict_rows_math():
            return fn(*args)
        flat, in_tree = jax.tree_util.tree_flatten(args)
        out_tree_box = []

        def flat_fn(*leaves):
            out = fn(*jax.tree_util.tree_unflatten(in_tree, leaves))
            out_flat, out_tree = jax.tree_util.tree_flatten(out)
            out_tree_box.append(out_tree)
            return out_flat
        closed = jax.make_jaxpr(flat_fn)(*flat)
        outs = _eval_jaxpr_contraction_proof(closed.jaxpr, closed.consts,
                                             guard, *flat)
        return jax.tree_util.tree_unflatten(out_tree_box[0], outs)
    return wrapped

# state pytree: dict[str, jax.Array] (possibly empty)
State = Dict[str, jax.Array]
# scalars: (worker_id, momentum, learning_rate, rho, lambda_, staleness)
Scalars = Tuple[Any, ...]


def _opt_staleness(opt: Scalars):
    """Measured clock lag, or -1 when the caller predates the 6th scalar
    (older wire peers / direct test callers pass 5-tuples)."""
    return opt[5] if len(opt) > 5 else np.float32(-1.0)


def combine_duplicate_rows(rows: jax.Array, delta: jax.Array, num_rows: int
                           ) -> Tuple[jax.Array, jax.Array]:
    """Fold duplicate row ids into one total per id, compacted to the front.

    Stateful updaters gather-compute-set; a ``.at[rows].set`` with duplicate
    ids is last-write-wins, which would drop all but one duplicate's state
    contribution (the reference's sequential per-element loop accumulates,
    ``src/updater/updater.cpp:22-29``). Shape-stable under jit: sort by id
    and segment-sum each run in that order.

    Returns ``(ids, totals)``, both the inputs' shapes, and this contract
    (pinned by ``tests/test_updater_rows.py``; the fused row kernel,
    ``ops/pallas_rows.fused_stateful_rows``, skips every group of lanes
    behind the live prefix and writes its lanes unordered on the strength
    of it):

    * ``ids`` ascend STRICTLY, so every id is there once;
    * the first ``n_unique`` are the distinct in-range ids of ``rows``
      (``np.unique``), and ``totals[s]`` is the sum of the deltas of
      ``ids[s]``;
    * every later id is ``>= num_rows`` (``mode="drop"`` writes discard it)
      and its total is zero.

    Ids outside ``[0, num_rows)`` are dropped with their deltas (their one
    total sits in the tail, where nothing is written).
    """
    n = rows.shape[0]
    if n == 0:   # static shape: empty add is a no-op
        return rows, delta
    # Out-of-range ids become ONE run behind every live id, so the live
    # segments stay the first segments.
    rows = jnp.where((rows < 0) | (rows >= num_rows), num_rows, rows)
    order = jnp.argsort(rows)
    r = jnp.take(rows, order)
    d = jnp.take(delta, order, axis=0)
    is_start = jnp.concatenate([jnp.ones((1,), bool), r[1:] != r[:-1]])
    live = is_start & (r < num_rows)
    seg = jnp.cumsum(is_start) - 1
    totals = jax.ops.segment_sum(d, seg, num_segments=n)
    # Segment s IS the s-th smallest distinct id: sorting the run starts to
    # the front lines them up with their totals. Every other lane gets an
    # out-of-range id of its own (its position), ascending as well.
    ids = jnp.sort(jnp.where(live, r, num_rows + jnp.arange(n, dtype=r.dtype)))
    return ids, totals


class Updater:
    """Base: plain accumulate — ``data += delta`` (ref updater.cpp:19-29).

    Class contract consumed by the store / kernel layers:

    * ``per_worker_state`` — state-leaf names carrying a leading
      ``[num_workers]`` axis (indexed by the ``worker_id`` scalar);
    * ``staleness_aware`` — True when ``opt``'s staleness scalar changes
      the math (DC-ASGD family), so callers know when to measure it;
    * ``rows_math(d_rows, state_rows, delta, opt)`` — the PER-ROW update
      math on already-gathered row blocks, shared verbatim between the
      XLA scatter path (:meth:`update_rows` via ``_rows_update_via_math``)
      and the fused Pallas gather-update-scatter kernel
      (:mod:`multiverso_tpu.ops.pallas_rows`) — one implementation is the
      structural bitwise-parity guarantee between the two planes;
    * ``fused_rows`` — True on a class whose ``rows_math`` that kernel may
      run (``core/table.fused_rows_selected``). It is a claim about THIS
      class's math, read from the class's own namespace: a subclass, which
      may override the math, does not inherit it and says so again itself
      where it holds. The DC-ASGD family does not claim it: its per-worker
      whole-row backup writes dominate, the fused kernel is the wrong
      trade.
    """

    name = "default"
    per_worker_state: Tuple[str, ...] = ()
    staleness_aware = False
    fused_rows = False

    def init_state(self, shape: Tuple[int, ...], dtype: Any,
                   num_workers: int) -> State:
        del shape, dtype, num_workers
        return {}

    def update_dense(self, data: jax.Array, state: State, delta: jax.Array,
                     opt: Scalars) -> Tuple[jax.Array, State]:
        del opt
        return data + delta, state

    def update_rows(self, data: jax.Array, state: State, rows: jax.Array,
                    delta: jax.Array, opt: Scalars) -> Tuple[jax.Array, State]:
        del opt
        return data.at[rows].add(delta, mode="drop"), state

    # -- shared row-block machinery (stateful subclasses) -------------------
    def rows_math(self, d_rows: jax.Array, state_rows: State,
                  delta: jax.Array, opt: Scalars
                  ) -> Tuple[jax.Array, State]:
        raise NotImplementedError(f"{self.name} has no row-block math")

    def gather_row_blocks(self, data, state, rows, delta, wid):
        """Fold ``rows`` (:func:`combine_duplicate_rows`) and gather the
        touched rows of data and of every state leaf: ``(ids, totals,
        data rows, state rows)``. Lanes past the live prefix read the last
        row (``mode="clip"``); :meth:`scatter_row_blocks` drops them."""
        ids, totals = combine_duplicate_rows(rows, delta, data.shape[0])
        d_rows = jnp.take(data, ids, axis=0, mode="clip")
        st_rows: State = {}
        for key, leaf in state.items():
            src = leaf[wid] if key in self.per_worker_state else leaf
            st_rows[key] = jnp.take(src, ids, axis=0, mode="clip")
        return ids, totals, d_rows, st_rows

    def scatter_row_blocks(self, data, state, ids, wid, new_d, new_st):
        """Write updated row blocks back at ``ids`` as
        :func:`combine_duplicate_rows` returns them: ``mode="drop"``
        discards the out-of-range tail. The ids are sorted and unique, and
        the writes do NOT say so: on the v5e ``indices_are_sorted=True``
        takes XLA's scatter of 2,048 rows into a 134 MB table from 0.145
        to 0.407 ms and ``unique_indices=True`` moves nothing (PERF.md 6,
        PR 29)."""
        out_state: State = {}
        for key, leaf in state.items():
            at = (leaf.at[wid, ids] if key in self.per_worker_state
                  else leaf.at[ids])
            out_state[key] = at.set(new_st[key], mode="drop")
        return data.at[ids].set(new_d, mode="drop"), out_state

    def _rows_update_via_math(self, data, state, rows, delta, opt):
        """:meth:`gather_row_blocks`, :meth:`rows_math` on the blocks,
        :meth:`scatter_row_blocks`. ``data.at[r].set(d_rows - step)`` is
        bitwise-identical to the historical ``data.at[r].add(-step)``
        (IEEE: a - b == a + (-b)); the gather makes the data rows
        available to the shared math, which is what lets the Pallas
        kernel run the exact same function."""
        wid = opt[0]
        ids, totals, d_rows, st_rows = self.gather_row_blocks(
            data, state, rows, delta, wid)
        # exact_elementwise: on XLA:CPU the math rounds strictly per
        # primitive so this plane and the fused Pallas kernel agree
        # bitwise (see _strict_rows_math); accelerators keep the fully
        # fused math. worker_id >= 0 is the runtime-true guard.
        new_d, new_st = exact_elementwise(self.rows_math)(
            wid >= 0, d_rows, st_rows, totals, opt)
        return self.scatter_row_blocks(data, state, ids, wid, new_d, new_st)


class SGDUpdater(Updater):
    """``data -= delta``; client pre-scales by lr (ref sgd_updater.h:8-27)."""

    name = "sgd"

    def update_dense(self, data, state, delta, opt):
        del opt
        return data - delta, state

    def update_rows(self, data, state, rows, delta, opt):
        del opt
        return data.at[rows].add(-delta, mode="drop"), state


class MomentumUpdater(Updater):
    """``smooth = m*smooth + (1-m)*delta; data -= smooth``
    (ref momentum_updater.h:9-31)."""

    name = "momentum_sgd"
    fused_rows = True

    def init_state(self, shape, dtype, num_workers):
        del num_workers
        return {"smooth": jnp.zeros(shape, dtype=dtype)}

    def update_dense(self, data, state, delta, opt):
        m = opt[1].astype(data.dtype)
        smooth = m * state["smooth"] + (1 - m) * delta
        return data - smooth, {"smooth": smooth}

    def rows_math(self, d_rows, state_rows, delta, opt):
        m = opt[1].astype(d_rows.dtype)
        smooth_rows = m * state_rows["smooth"] + (1 - m) * delta
        return d_rows - smooth_rows, {"smooth": smooth_rows}

    def update_rows(self, data, state, rows, delta, opt):
        return self._rows_update_via_math(data, state, rows, delta, opt)


class AdaGradUpdater(Updater):
    """Per-worker historic squared-gradient accumulators
    (ref adagrad_updater.h:17-41): ``G[w] += (delta/lr)^2;
    data -= rho / sqrt(G[w] + eps) * delta / lr``.

    Clients pre-scale deltas by lr, so the raw gradient is ``delta/lr`` —
    the reference normalizes the accumulator by ``learning_rate`` twice
    (adagrad_updater.h:29-33) so G accumulates squared *gradients*, not
    squared pre-scaled deltas. (The reference's own Update then subtracts a
    stale accumulator copy — a bug we do not reproduce; we keep the clearly
    intended G += grad^2 semantics.) lr==0 is guarded to a no-op scale."""

    name = "adagrad"
    fused_rows = True
    eps = 1e-6
    per_worker_state = ("g2",)

    def init_state(self, shape, dtype, num_workers):
        return {"g2": jnp.zeros((max(num_workers, 1),) + tuple(shape),
                                dtype=jnp.float32)}

    @staticmethod
    def _grad(d32, lr):
        lr_safe = jnp.where(lr > 0, lr, 1.0).astype(jnp.float32)
        return d32 / lr_safe

    def update_dense(self, data, state, delta, opt):
        worker_id, _, lr, rho = opt[0], opt[1], opt[2], opt[3]
        g = self._grad(delta.astype(jnp.float32), lr)
        g2_w = state["g2"][worker_id] + jnp.square(g)
        g2 = state["g2"].at[worker_id].set(g2_w)
        step = rho / jnp.sqrt(g2_w + self.eps) * g
        return data - step.astype(data.dtype), {"g2": g2}

    def rows_math(self, d_rows, state_rows, delta, opt):
        lr, rho = opt[2], opt[3]
        g = self._grad(delta.astype(jnp.float32), lr)
        g2_rows = state_rows["g2"] + jnp.square(g)
        step = rho / jnp.sqrt(g2_rows + self.eps) * g
        return d_rows - step.astype(d_rows.dtype), {"g2": g2_rows}

    def update_rows(self, data, state, rows, delta, opt):
        return self._rows_update_via_math(data, state, rows, delta, opt)


class DCASGDUpdater(Updater):
    """Delay-compensated ASGD (the reference's ``ENABLE_DCASGD`` capability,
    ``src/updater/updater.cpp:7-10,51-54`` — flag present, source absent in
    that snapshot; implemented here from the DC-ASGD formulation the flag
    names): the server keeps a per-worker backup of the parameters at pull
    time and compensates gradient staleness with a first-order term,
    ``data -= lr * (g + lambda * g*g * (data - backup[w]))``, then refreshes
    the worker's backup.

    SSP staleness-adaptive scaling (``-staleness_adaptive``): when the
    caller measured this worker's clock lag tau (``opt`` staleness scalar
    >= 0), the variance-control strength becomes ``lambda * tau`` — the
    compensation term approximates a Taylor correction over the staleness
    window, so its weight should track how stale the gradient actually is
    (tau = 0: the view is current, no compensation; tau = 1 reproduces the
    fixed-lambda behavior; deeper lag compensates harder). Unmeasured
    (negative, the default) keeps the fixed lambda bitwise."""

    name = "dcasgd"
    per_worker_state = ("backup",)
    staleness_aware = True

    @staticmethod
    def _lam_eff(lam, opt):
        stale = jnp.asarray(_opt_staleness(opt), jnp.float32)
        return lam * jnp.where(stale >= 0.0, stale, 1.0)

    def init_state(self, shape, dtype, num_workers):
        return {"backup": jnp.zeros((max(num_workers, 1),) + tuple(shape),
                                    dtype=jnp.float32)}

    def update_dense(self, data, state, delta, opt):
        worker_id, lr = opt[0], opt[2]
        lam = self._lam_eff(opt[4], opt)
        g = delta.astype(jnp.float32)
        d32 = data.astype(jnp.float32)
        backup_w = state["backup"][worker_id]
        step = lr * (g + lam * g * g * (d32 - backup_w))
        new_data = d32 - step
        backup = state["backup"].at[worker_id].set(new_data)
        return new_data.astype(data.dtype), {"backup": backup}

    def rows_math(self, d_rows, state_rows, delta, opt):
        lr = opt[2]
        lam = self._lam_eff(opt[4], opt)
        g = delta.astype(jnp.float32)
        d32 = d_rows.astype(jnp.float32)
        step = lr * (g + lam * g * g * (d32 - state_rows["backup"]))
        new_rows = d32 - step
        return new_rows.astype(d_rows.dtype), {"backup": new_rows}

    def update_rows(self, data, state, rows, delta, opt):
        return self._rows_update_via_math(data, state, rows, delta, opt)


class DCASGDAUpdater(DCASGDUpdater):
    """Adaptive-lambda DC-ASGD (the reference factory's ``dcasgda``,
    ``src/updater/updater.cpp:53`` — named, source absent; implemented from
    the DC-ASGD formulation's adaptive variant): the compensation strength
    tracks the gradient's second moment, ``m = eps_m*m + (1-eps_m)*g*g``,
    and the effective lambda is ``lam / sqrt(m + eps)`` elementwise — large
    recent gradients shrink the compensation, so early noisy steps are not
    over-corrected while stale late steps still are."""

    name = "dcasgda"
    eps_m = 0.95
    eps = 1e-7

    def init_state(self, shape, dtype, num_workers):
        st = super().init_state(shape, dtype, num_workers)
        st["m"] = jnp.zeros(tuple(shape), dtype=jnp.float32)
        return st

    def update_dense(self, data, state, delta, opt):
        worker_id, lr = opt[0], opt[2]
        lam = self._lam_eff(opt[4], opt)
        g = delta.astype(jnp.float32)
        d32 = data.astype(jnp.float32)
        m = self.eps_m * state["m"] + (1.0 - self.eps_m) * g * g
        lam_eff = lam / jnp.sqrt(m + self.eps)
        backup_w = state["backup"][worker_id]
        step = lr * (g + lam_eff * g * g * (d32 - backup_w))
        new_data = d32 - step
        backup = state["backup"].at[worker_id].set(new_data)
        return new_data.astype(data.dtype), {"backup": backup, "m": m}

    def rows_math(self, d_rows, state_rows, delta, opt):
        lr = opt[2]
        lam = self._lam_eff(opt[4], opt)
        g = delta.astype(jnp.float32)
        m_rows = self.eps_m * state_rows["m"] + (1.0 - self.eps_m) * g * g
        lam_eff = lam / jnp.sqrt(m_rows + self.eps)
        d32 = d_rows.astype(jnp.float32)
        step = lr * (g + lam_eff * g * g * (d32 - state_rows["backup"]))
        new_rows = d32 - step
        return (new_rows.astype(d_rows.dtype),
                {"backup": new_rows, "m": m_rows})


class FTRLUpdater(Updater):
    """FTRL-proximal with server-resident {z, n} state.

    Parity with the LR app's FTRL entry table
    (``Applications/LogisticRegression/src/util/ftrl_sparse_table.h:12-88``:
    each weight carries {z, n}). Option mapping: ``learning_rate`` -> alpha,
    ``rho`` -> beta, ``lambda_`` -> l1, ``momentum`` -> l2. Delta is the raw
    gradient; weights are recomputed closed-form on every update.
    """

    name = "ftrl"
    fused_rows = True

    def init_state(self, shape, dtype, num_workers):
        del num_workers
        return {"z": jnp.zeros(shape, dtype=jnp.float32),
                "n": jnp.zeros(shape, dtype=jnp.float32)}

    @staticmethod
    def _step(w, z, n, g, opt):
        l2, alpha, beta, l1 = opt[1], opt[2], opt[3], opt[4]
        g32 = g.astype(jnp.float32)
        n_new = n + jnp.square(g32)
        sigma = (jnp.sqrt(n_new) - jnp.sqrt(n)) / alpha
        z_new = z + g32 - sigma * w.astype(jnp.float32)
        w_new = jnp.where(
            jnp.abs(z_new) > l1,
            -(z_new - jnp.sign(z_new) * l1) /
            ((beta + jnp.sqrt(n_new)) / alpha + l2),
            0.0)
        return w_new.astype(w.dtype), z_new, n_new

    def update_dense(self, data, state, delta, opt):
        w, z, n = self._step(data, state["z"], state["n"], delta, opt)
        return w, {"z": z, "n": n}

    def rows_math(self, d_rows, state_rows, delta, opt):
        w_new, z_new, n_new = self._step(d_rows, state_rows["z"],
                                         state_rows["n"], delta, opt)
        return w_new, {"z": z_new, "n": n_new}

    def update_rows(self, data, state, rows, delta, opt):
        return self._rows_update_via_math(data, state, rows, delta, opt)


_REGISTRY: Dict[str, Callable[[], Updater]] = {
    "default": Updater,
    "sgd": SGDUpdater,
    "momentum_sgd": MomentumUpdater,
    "adagrad": AdaGradUpdater,
    "ftrl": FTRLUpdater,
    "dcasgd": DCASGDUpdater,
    "dcasgda": DCASGDAUpdater,
}


def register_updater(name: str, factory: Callable[[], Updater]) -> None:
    _REGISTRY[name] = factory


def get_updater(dtype: Any, updater_type: str | None = None) -> Updater:
    """Factory (ref src/updater/updater.cpp:45-57).

    Integer tables always get the plain adder (ref updater.cpp:40-43).
    """
    if np.issubdtype(np.dtype(dtype), np.integer):
        return Updater()
    if updater_type is None:
        updater_type = get_flag("updater_type")
    factory = _REGISTRY.get(updater_type)
    if factory is None:
        factory = Updater
    return factory()
