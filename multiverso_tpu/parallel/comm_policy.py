"""Per-table communication policy: PS push/pull vs in-graph collectives.

The reference shipped an ``AllreduceEngine`` and a model-average ("ma")
training mode NEXT TO the parameter-server path (PAPER.md layer 3,
``src/multiverso.cpp:53-56`` / ``-ma`` in ``src/zoo.cpp:24``), but nothing
selected between them per table. MXNET-MPI (PAPERS.md 1801.03855) showed
the winning shape is *hybrid*: keep the PS task model and embed collectives
inside it, so each tensor rides the plane that is cheapest for its shape.
The TPU-concurrency study (PAPERS.md 2011.03641) supplies the roofline
framing: a PS round trip pays host staging + dispatch latency per op, an
in-graph ICI psum pays ~bytes/bandwidth — so small dense tables want the
collective and sparse/HBM-scale tables want row push/pull.

Three policies, selected **per table** at construction:

* ``ps`` — push/pull through the table clients (row gather/scatter against
  the sharded :class:`~multiverso_tpu.core.table.ServerStore`; the only
  plane that supports row-granular sparse access).
* ``allreduce`` — gradients reduced IN-GRAPH (``jax.lax.psum`` over a mesh
  axis) inside the jitted, donated training step; the PS table remains the
  publish/checkpoint surface, written at sync points instead of per step.
* ``model_average`` — the reference's "ma" mode: workers train local
  replicas and periodically average them via the collective plane
  (:func:`model_average_arrays` -> ``collectives.aggregate``).

``auto`` applies :func:`resolve_comm_policy`'s decision table (the same
move as PR 2's ``resolve_dispatch_mode``): explicit override wins; sparse
or HBM-scale tables -> ``ps``; small dense tables -> whichever plane a
cached one-shot measured probe (:func:`measured_policy_latency_ms`) says
is faster for the table's byte size. ``model_average`` is never chosen by
AUTO — it changes training semantics (staleness window = the averaging
period), so it is an explicit opt-in.

Telemetry (docs/OBSERVABILITY.md): ``comm.<policy>.bytes`` counters and
``comm.<policy>.latency_ms`` histograms per plane, ``comm.policy.resolve.
<policy>`` decision counters, ``comm.policy.ps_fallback`` for client row
ops against a non-ps table.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from multiverso_tpu.telemetry import counter, histogram
from multiverso_tpu.utils.log import check, log

PS = "ps"
ALLREDUCE = "allreduce"
MODEL_AVERAGE = "model_average"
AUTO = "auto"
COMM_POLICIES = (PS, ALLREDUCE, MODEL_AVERAGE)

# Decision-table thresholds. A table larger than ALLREDUCE_BYTES_MAX is
# "HBM-scale": densifying its gradient for a psum would move the whole
# table's bytes every step where the PS row plane moves only touched rows.
ALLREDUCE_BYTES_MAX = 16 << 20
# Row-granular tables at/above this row count are treated as sparse-access
# (embedding-shaped): per-step touched rows << total rows, so the dense
# collective loses by construction and the probe is skipped.
SPARSE_ROWS_MIN = 4096

# -- cached one-shot probe ---------------------------------------------------
# Keyed by log2 byte bucket (+ backend/mesh signature): one measurement per
# size class per process, so AUTO costs at most a few ms once.
_PROBE_CACHE: Dict[Tuple[int, str], Dict[str, float]] = {}
_PROBE_LOCK = threading.Lock()

# Bounded decision log: the bench record embeds this as the decision-table
# evidence (scripts/comm_bench.py).
_DECISIONS: List[Dict[str, Any]] = []
_DECISIONS_MAX = 256


def _mesh_signature(mesh, world: int) -> str:
    base = jax.devices()[0].platform + f"/w{world}"
    if mesh is None:
        return base
    return (base + ":" +
            ",".join(f"{k}={v}" for k, v in mesh.shape.items()))


def measured_policy_latency_ms(nbytes: int, mesh=None, world: int = 1,
                               iters: int = 5) -> Dict[str, float]:
    """Measured per-op latency of both planes for a buffer of ``nbytes``.

    ``ps``: the client round trip shape — host->device upload of a delta,
    one donated jitted dense add (the server apply), and the pull's
    device->host readback.  ``allreduce``: the in-graph merge as the
    policy would actually execute it for ``world`` contributors — a psum
    over a ``world``-wide mesh axis when there is more than one
    contributor AND a multi-device mesh to reduce over, else the
    degenerate single-contributor case: one donated dispatch with no host
    transfer at all (which is the whole point of the plane).

    Cached per log2-byte bucket per process (one-shot); both legs time the
    median of ``iters`` runs after a compile warm-up.
    """
    n = max(int(nbytes) // 4, 1)
    key = (max(n, 1).bit_length(), _mesh_signature(mesh, world))
    with _PROBE_LOCK:
        hit = _PROBE_CACHE.get(key)
    if hit is not None:
        return hit

    from multiverso_tpu.parallel.mesh import SERVER_AXIS
    from jax.sharding import PartitionSpec as P

    data = jnp.zeros((n,), jnp.float32)
    delta_host = np.ones((n,), np.float32)

    add = jax.jit(lambda d, x: d + x, donate_argnums=0)
    data = add(data, delta_host)        # compile outside the timing
    ps_times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        data = add(data, jnp.asarray(delta_host))
        # The probe MEASURES the PS round trip; the per-iteration host
        # readback is the quantity being sampled.
        np.asarray(data)  # graftlint: disable=block-until-ready-in-loop
        ps_times.append((time.perf_counter() - t0) * 1e3)

    axis = SERVER_AXIS
    n_axis = mesh.shape.get(axis, 1) if mesh is not None else 1
    if world > 1 and mesh is not None and n_axis > 1:
        # A real k-wide collective of these bytes on this backend (the
        # mesh's server axis stands in for the worker reduction axis —
        # the probe measures transport latency, not placement).
        def _psum(v):
            return jax.lax.psum(v, axis) / n_axis

        fn = jax.jit(jax.shard_map(_psum, mesh=mesh, in_specs=P(),
                                   out_specs=P(), check_vma=False),
                     donate_argnums=0)
    else:
        fn = jax.jit(lambda v: v + 0.0, donate_argnums=0)
    buf = jax.block_until_ready(fn(jnp.zeros((n,), jnp.float32)))
    ar_times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        buf = fn(buf)
        # Same deal: the sync IS the measured round trip.
        jax.block_until_ready(buf)  # graftlint: disable=block-until-ready-in-loop
        ar_times.append((time.perf_counter() - t0) * 1e3)

    out = {PS: float(np.median(ps_times)),
           ALLREDUCE: float(np.median(ar_times)),
           "nbytes": int(nbytes), "world": int(world)}
    with _PROBE_LOCK:
        _PROBE_CACHE[key] = out
    return out


def _log_decision(table: str, policy: str, reason: str,
                  probe: Optional[Dict[str, float]] = None) -> None:
    # `policy` is the three-member CommPolicy enum: bounded.
    # graftlint: disable=unbounded-metric-name
    counter(f"comm.policy.resolve.{policy}").inc()
    entry = {"table": table, "policy": policy, "reason": reason}
    if probe is not None:
        entry["probe_ms"] = {PS: probe[PS], ALLREDUCE: probe[ALLREDUCE]}
    if len(_DECISIONS) < _DECISIONS_MAX:
        _DECISIONS.append(entry)
    log.info("comm policy[%s]: %s (%s)", table or "?", policy, reason)


def resolve_comm_policy(shape: Sequence[int], dtype: Any, *,
                        sparse: bool = False,
                        explicit: Optional[str] = None,
                        mesh=None, world: int = 0, probe: bool = True,
                        table: str = "") -> str:
    """AUTO decision table (the ``resolve_dispatch_mode`` move, per table):

    1. an explicit policy (anything but None/""/"auto") wins, validated;
    2. ``sparse`` (row-granular access / embedding-shaped) -> ``ps`` —
       the collective plane would densify the whole table per step;
    3. table bytes > ``ALLREDUCE_BYTES_MAX`` (HBM-scale) -> ``ps``;
    4. otherwise small dense: the cached measured probe picks whichever
       of {ps round trip, in-graph merge at this ``world`` width} is
       faster for this byte size (``probe=False`` skips the measurement
       and takes ``allreduce``, the expected winner for every
       small-dense shape we measured).

    ``world`` is the number of contributors the allreduce would actually
    reduce over (data-parallel workers sharing the table); 0 means "this
    process count".
    """
    if explicit not in (None, "", AUTO):
        check(explicit in COMM_POLICIES,
              f"comm_policy must be one of {COMM_POLICIES} or '{AUTO}'; "
              f"got {explicit!r}")
        _log_decision(table, explicit, "explicit override")
        return explicit
    nbytes = int(np.prod([int(s) for s in shape]) *
                 np.dtype(dtype).itemsize) if len(tuple(shape)) else 0
    if sparse:
        _log_decision(table, PS, "sparse row-granular access")
        return PS
    if nbytes > ALLREDUCE_BYTES_MAX:
        _log_decision(table, PS,
                      f"hbm-scale ({nbytes} B > {ALLREDUCE_BYTES_MAX} B)")
        return PS
    if not probe:
        _log_decision(table, ALLREDUCE, "small dense (unprobed)")
        return ALLREDUCE
    world = world or max(jax.process_count(), 1)
    lat = measured_policy_latency_ms(nbytes, mesh, world=world)
    policy = PS if lat[PS] < lat[ALLREDUCE] else ALLREDUCE
    _log_decision(table, policy,
                  f"probe {lat[PS]:.3f}ms ps vs {lat[ALLREDUCE]:.3f}ms "
                  f"allreduce @ {nbytes} B, world {world}", probe=lat)
    return policy


def decision_evidence() -> Dict[str, Any]:
    """The decision-table evidence block bench records embed: every
    resolution this process made (bounded) plus the probe cache."""
    with _PROBE_LOCK:
        cache = {f"2^{k[0]}B@{k[1]}": dict(v)
                 for k, v in _PROBE_CACHE.items()}
    return {"decisions": list(_DECISIONS), "probe_cache": cache}


def reset_decisions() -> None:
    """Test isolation: clear the decision log (probe cache survives —
    it is a physical measurement, not state under test)."""
    del _DECISIONS[:]


# -- per-plane telemetry -----------------------------------------------------
def record(plane: str, nbytes: int, ms: Optional[float] = None) -> None:
    """Count one communication op on ``plane`` (bytes moved + optional
    latency). Factories are looked up per call so telemetry resets between
    tests never detach the counters."""
    # `plane` is the three-member policy enum: bounded.
    # graftlint: disable=unbounded-metric-name
    counter(f"comm.{plane}.bytes").inc(int(nbytes))
    # graftlint: disable=unbounded-metric-name
    counter(f"comm.{plane}.ops").inc()
    if ms is not None:
        # graftlint: disable=unbounded-metric-name
        histogram(f"comm.{plane}.latency_ms").observe(float(ms))


class CommPolicy:
    """Per-table policy record: the resolved plane plus the routed-op
    telemetry hooks the table clients call."""

    __slots__ = ("policy", "table")

    def __init__(self, policy: str, table: str = ""):
        check(policy in COMM_POLICIES,
              f"comm policy must be one of {COMM_POLICIES}; got {policy!r}")
        self.policy = policy
        self.table = table

    def record_client_op(self, nbytes: int,
                         ms: Optional[float] = None) -> None:
        """A push/pull through the table client API — always the PS plane
        physically; on a non-ps table it is additionally counted as a
        fallback (the model bypassed its own policy)."""
        record(PS, nbytes, ms)
        if self.policy != PS:
            counter("comm.policy.ps_fallback").inc()

    def record_publish(self, nbytes: int,
                       ms: Optional[float] = None) -> None:
        """A whole-replica publish at a sync point (allreduce /
        model-average tables write the store this way)."""
        record(self.policy, nbytes, ms)


def policy_for_option(explicit: Optional[str], shape: Sequence[int],
                      dtype: Any, *, sparse: bool = False, mesh=None,
                      table: str = "") -> CommPolicy:
    """The one table-constructor entry point for the three policy
    sources: ``None`` -> ps (free, no probe, no log noise); a concrete
    policy -> taken as pre-resolved (models resolve BEFORE construction
    so the decision logs once, with its real reason); anything else
    (``"auto"``) -> the decision table."""
    if explicit is None:
        return CommPolicy(PS, table=table)
    if explicit in COMM_POLICIES:
        return CommPolicy(explicit, table=table)
    return CommPolicy(resolve_comm_policy(shape, dtype, sparse=sparse,
                                          explicit=explicit, mesh=mesh,
                                          table=table), table=table)


# -- plane helpers -----------------------------------------------------------
def reduce_axis_size(mesh, axis: Optional[str] = None) -> int:
    """The one rule for "is there an axis to reduce over": the size of
    ``axis`` (the server axis by default) in ``mesh``, 1 without a mesh.
    At 1 a merge has nothing to merge."""
    from multiverso_tpu.parallel.mesh import SERVER_AXIS

    return 1 if mesh is None else mesh.shape.get(axis or SERVER_AXIS, 1)


def build_dense_sync(mesh, axis: Optional[str] = None,
                     donate: bool = False):
    """One jitted in-graph allreduce dispatch for a replicated dense operand,
    ONE array or a whole tree of them: every leaf ``psum`` over ``axis``
    normalized by the axis size, so the value is preserved (exactly, for
    power-of-two axis sizes) while the dispatch exercises a real ICI/mesh
    collective. In a one-process world every contribution is identical and
    the op is an identity-preserving barrier; data-parallel callers feed
    per-worker partials through the same function. word2vec's block step
    hands it one scalar a block; the hybrid step its whole tree of dense
    deltas, one launch for all of them.

    Build ONCE per model (compiles one executable). Without an axis to
    reduce over (:func:`reduce_axis_size` 1) the dispatch is a jitted
    identity: a caller that can do without the launch asks the rule first
    and builds nothing (the hybrid step). With ``donate`` the result takes
    its operand's buffers (the caller gives its only reference up): a merge
    then allocates nothing, so no leaf is held twice while the delta
    program's temporaries are alive (``peak_hbm_gb`` read 12.09 to 12.37 GB
    by the run without it at 180 MB leaves, PERF.md 6, PR 32).
    """
    from multiverso_tpu.parallel.mesh import SERVER_AXIS
    from jax.sharding import PartitionSpec as P

    axis = axis or SERVER_AXIS
    n_axis = reduce_axis_size(mesh, axis)
    donated = (0,) if donate else ()
    if n_axis <= 1:
        return jax.jit(lambda x: x + 0.0, donate_argnums=donated)

    def _sync(tree):
        return jax.tree_util.tree_map(
            lambda v: jax.lax.psum(v, axis) / n_axis, tree)

    return jax.jit(jax.shard_map(_sync, mesh=mesh, in_specs=P(),
                                 out_specs=P(), check_vma=False),
                   donate_argnums=donated)


def model_average_arrays(arrays: Sequence[np.ndarray]) -> List[np.ndarray]:
    """The reference "ma" merge: elementwise mean of each array across all
    JAX processes via :func:`collectives.aggregate` (a true allreduce over
    the process-spanning mesh; the identity in a one-process world, where
    the mean of one replica is itself — bitwise). Counted per array under
    ``comm.model_average.*``."""
    from multiverso_tpu.parallel import collectives

    world = max(jax.process_count(), 1)
    out: List[np.ndarray] = []
    for a in arrays:
        a = np.asarray(a)
        t0 = time.perf_counter()
        merged = collectives.aggregate(a)
        if world > 1:
            merged = (merged / world).astype(a.dtype)
        record(MODEL_AVERAGE, a.nbytes,
               (time.perf_counter() - t0) * 1e3)
        out.append(merged)
    return out
