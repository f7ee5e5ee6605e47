"""LogReg models: local and parameter-server modes.

Parity with ``Applications/LogisticRegression/src/model/``:

* ``Model`` (local): weights on device, one jitted minibatch step.
* ``PSModel`` (``ps_model.cpp``): weights in an :class:`ArrayTable`; each
  minibatch computes the gradient against the worker's local copy and pushes
  a **client-side lr-scaled delta** (ref ``updater/updater.cpp:12-60``);
  the model is pulled every ``sync_frequency`` minibatches
  (``ps_model.cpp:172-182``), optionally **pipelined** with a double-buffered
  async Get so the pull overlaps compute (``ps_model.cpp:236-271``).

TPU-native: the minibatch step is one jitted function — X @ W on the MXU,
regularizer fused by XLA. FTRL mode pushes raw gradients; the server-side
FTRL updater owns {z, n} and recomputes weights (the reference's FTRL table).
"""

from __future__ import annotations

import dataclasses
from typing import ClassVar, Optional

import jax
import jax.numpy as jnp
import numpy as np

import multiverso_tpu as mv
from multiverso_tpu.core.options import AddOption, ArrayTableOption
from multiverso_tpu.models.logreg.objective import get_objective
from multiverso_tpu.utils.dashboard import monitor


@dataclasses.dataclass
class LogRegConfig:
    """Key=value config (ref LR ``configure.h:9-115`` surface)."""
    objective: str = "sigmoid"          # linear|sigmoid|softmax|ftrl
    num_feature: int = 0
    num_class: int = 1
    learning_rate: float = 0.1
    minibatch_size: int = 20
    epochs: int = 1
    sync_frequency: int = 1
    pipeline: bool = False
    use_ps: bool = True
    # Weight-table communication policy (parallel/comm_policy.py):
    # "" -> ps (the PSModel path, unchanged default); "auto" -> the
    # decision table (small dense weights -> allreduce wherever the
    # probe says the in-graph plane wins); ps|allreduce|model_average
    # explicit. FTRL is pinned to ps (server-side {z, n} state).
    comm_policy: str = ""
    regular: str = "none"               # none|l1|l2
    regular_coef: float = 0.0
    bias: bool = True
    input_format: str = "libsvm"
    # FTRL hyperparams (mapped onto AddOption fields)
    ftrl_alpha: float = 0.1
    ftrl_beta: float = 1.0
    ftrl_l1: float = 1.0
    ftrl_l2: float = 1.0
    # IO surface carried in the config file (ref configure.h:53-79)
    train_file: str = ""
    test_file: str = ""
    output_file: str = ""
    init_model_file: str = ""
    output_model_file: str = ""

    # Reference key names (configure.h:19-96) -> our field names.
    KEY_ALIASES: ClassVar[dict] = {
        "input_size": "num_feature",
        "output_size": "num_class",
        "train_epoch": "epochs",
        "objective_type": "objective",
        "regular_type": "regular",
        "alpha": "ftrl_alpha",
        "beta": "ftrl_beta",
        "lambda1": "ftrl_l1",
        "lambda2": "ftrl_l2",
    }
    VALUE_ALIASES: ClassVar[dict] = {
        "objective": {"default": "linear"},
        "regular": {"default": "none", "L1": "l1", "L2": "l2"},
    }

    @property
    def width(self) -> int:
        return self.num_feature + (1 if self.bias else 0)

    @classmethod
    def from_file(cls, path: str) -> "LogRegConfig":
        """Parse the reference's ``key=value`` config-file format, accepting
        both our field names and the reference's key spellings."""
        cfg = cls()
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line or line.startswith("#") or "=" not in line:
                    continue
                key, _, val = line.partition("=")
                key = cls.KEY_ALIASES.get(key.strip(), key.strip())
                val = val.strip()
                val = cls.VALUE_ALIASES.get(key, {}).get(val, val)
                if hasattr(cfg, key):
                    field_type = type(getattr(cfg, key))
                    if field_type is bool:
                        setattr(cfg, key, val.lower() in ("true", "1"))
                    else:
                        setattr(cfg, key, field_type(val))
        return cfg


def _raw_step(cfg: LogRegConfig):
    """Unjitted ``(weights, X, y) -> (loss, grad)`` — the one objective
    math both the PS path and the in-graph comm-policy steps share, so
    policy parity is structural (same ops, same order)."""
    loss_grad, _ = get_objective(cfg.objective)
    coef = cfg.regular_coef
    regular = cfg.regular

    def step(weights, X, y):
        loss, grad = loss_grad(weights, X, y)
        if regular == "l2" and coef:
            grad = grad + coef * weights
        elif regular == "l1" and coef:
            grad = grad + coef * jnp.sign(weights)
        return loss, grad

    return step


def _make_step(cfg: LogRegConfig):
    # grad has exactly the weights' shape/dtype: donating lets XLA write
    # it into the uploaded weights buffer instead of allocating a second
    # [width, num_class] array per minibatch (PSModel uploads fresh
    # weights every call; LocalModel traces through this jit inside its
    # own donating sgd jit, where the inner annotation is a no-op).
    return jax.jit(_raw_step(cfg), donate_argnums=(0,))


class LocalModel:
    """Non-PS mode: weights stay on device, fully fused step."""

    def __init__(self, cfg: LogRegConfig):
        self.cfg = cfg
        self.weights = jnp.zeros((cfg.width, cfg.num_class),
                                 dtype=jnp.float32)
        step = _make_step(cfg)
        lr = cfg.learning_rate

        def sgd(weights, X, y):
            loss, grad = step(weights, X, y)
            return weights - lr * grad, loss

        self._sgd = jax.jit(sgd, donate_argnums=0)

    def update(self, X: np.ndarray, y: np.ndarray):
        """Returns the loss as a device scalar (no host sync)."""
        self.weights, loss = self._sgd(self.weights, jnp.asarray(X),
                                       jnp.asarray(y))
        return loss

    def get_weights(self) -> np.ndarray:
        return np.asarray(self.weights)

    def set_weights(self, w: np.ndarray) -> None:
        self.weights = jnp.asarray(
            np.asarray(w, dtype=np.float32).reshape(self.cfg.width,
                                                    self.cfg.num_class))


class PSModel:
    """PS mode: weights live in a sharded ArrayTable (or any injected
    table with the same get/add surface — e.g. a DistributedArrayTable for
    multi-process deployments, the reference's 24-machine LR shape)."""

    def __init__(self, cfg: LogRegConfig, table=None):
        self.cfg = cfg
        is_ftrl = cfg.objective == "ftrl"
        updater = "ftrl" if is_ftrl else "sgd"
        self.table = table if table is not None else mv.create_table(
            ArrayTableOption(
                size=cfg.width * cfg.num_class, updater=updater,
                name="logreg_weights"))
        self.is_ftrl = is_ftrl
        self._step = _make_step(cfg)
        self.local_weights = np.zeros((cfg.width, cfg.num_class),
                                      dtype=np.float32)
        self._minibatches_since_sync = 0
        self._pending_get: Optional[int] = None
        self._dirty = False     # True once this instance has pushed grads
        # Real worker id: in sync mode the BSP vector clocks are per-worker,
        # so every worker's adds must tick its OWN clock (worker_id=0 for
        # everyone would wedge the get gate at world>1).
        wid = max(mv.worker_id(), 0)
        if is_ftrl:
            self._add_option = AddOption(
                worker_id=wid, learning_rate=cfg.ftrl_alpha,
                rho=cfg.ftrl_beta, lambda_=cfg.ftrl_l1,
                momentum=cfg.ftrl_l2)
        else:
            self._add_option = AddOption(worker_id=wid,
                                         learning_rate=cfg.learning_rate)

    def update(self, X: np.ndarray, y: np.ndarray):
        """Returns the loss as a device scalar (no host sync)."""
        loss, grad = self._step(jnp.asarray(self.local_weights),
                                jnp.asarray(X), jnp.asarray(y))
        grad = np.asarray(grad)
        if self.is_ftrl:
            delta = grad          # raw gradient; server FTRL owns the step
        else:
            delta = self.cfg.learning_rate * grad  # client-side lr scaling
        with monitor("LOGREG_PUSH"):
            self.table.add_async(delta.reshape(-1), self._add_option)
        self._dirty = True
        self._minibatches_since_sync += 1
        if self._needs_sync():
            self._pull()
        return loss

    def _needs_sync(self) -> bool:
        # ref ps_model.cpp:172-182
        return self._minibatches_since_sync >= self.cfg.sync_frequency

    def _pull(self) -> None:
        cfg = self.cfg
        with monitor("LOGREG_PULL"):
            if cfg.pipeline:
                # Double buffer (ref ps_model.cpp:236-271): wait on the get
                # issued LAST sync, then immediately issue the next.
                if self._pending_get is not None:
                    data = self.table.wait(self._pending_get)
                    self.local_weights = data.reshape(cfg.width,
                                                      cfg.num_class)
                self._pending_get = self.table.get_async()
            else:
                self.local_weights = self.table.get().reshape(
                    cfg.width, cfg.num_class)
        self._minibatches_since_sync = 0

    def sync(self) -> None:
        """Blocking pull — epoch boundaries / before test
        (ref ps_model.cpp:206-233)."""
        if self._pending_get is not None:
            self.table.wait(self._pending_get)
            self._pending_get = None
        self.local_weights = self.table.get().reshape(
            self.cfg.width, self.cfg.num_class)
        self._minibatches_since_sync = 0

    def get_weights(self) -> np.ndarray:
        return self.local_weights

    def set_weights(self, w: np.ndarray) -> None:
        """Warm start (ref ``init_model_file``) on a FRESH (zero) table via
        the reference binding's master-init trick
        (``binding/python/multiverso/tables.py:38-68``): the master worker
        adds the init value, every other worker adds zeros — one symmetric
        add per worker, so it is BSP-safe and concurrent warm-starts cannot
        double-apply. FTRL keeps server-side {z,n} state that a raw weight
        file cannot reconstruct, so warm start is rejected there."""
        from multiverso_tpu.utils.log import check, log
        if self.is_ftrl:
            log.error("init_model_file ignored: ftrl server state cannot be "
                      "reconstructed from a weight vector")
            return
        check(not self._dirty,
              "warm start requires a fresh (zero) PS table — construct a "
              "new LogReg with init_model_file instead of calling "
              "load_model on a trained one")
        # _dirty only tracks THIS instance; an injected/shared table may
        # have been trained elsewhere. Ask the server (one init-time pull;
        # symmetric across workers, so BSP-safe).
        check(not np.any(self.table.get()),
              "warm start requires a fresh (zero) PS table — the shared "
              "table already holds trained weights")
        w = np.asarray(w, dtype=np.float32).reshape(self.cfg.width,
                                                    self.cfg.num_class)
        # sgd updater applies data -= delta, so the master pushes -w.
        delta = -w if mv.is_master_worker() else np.zeros_like(w)
        self.table.add(delta.reshape(-1), self._add_option)
        self.local_weights = w.copy()


class AllreduceModel:
    """``comm_policy=allreduce``: weights stay device-resident and the
    gradient is merged IN-GRAPH inside one jitted, donated step — no PS
    round trip per minibatch. With a data-parallel mesh axis the merge is
    a real ``jax.lax.psum`` of per-shard gradients (the MXNET-MPI hybrid:
    collectives embedded in the PS task model, PAPERS.md 1801.03855);
    with a single contributor it degenerates to the fused local update.
    The PS table remains the checkpoint/serving surface: :meth:`sync`
    publishes the replica once, instead of pushing a delta every
    minibatch (``table.publish`` counts under ``comm.allreduce.*``)."""

    def __init__(self, cfg: LogRegConfig, table=None, dp_mesh=None,
                 dp_axis: Optional[str] = None):
        from multiverso_tpu.parallel import comm_policy as cp
        from multiverso_tpu.utils.log import check
        from jax.sharding import PartitionSpec as P

        check(cfg.objective != "ftrl",
              "ftrl keeps server-side {z, n} state — comm_policy=allreduce "
              "cannot reconstruct it; use ps")
        self.cfg = cfg
        self.table = table if table is not None else mv.create_table(
            ArrayTableOption(size=cfg.width * cfg.num_class, updater="sgd",
                             name="logreg_weights",
                             comm_policy="allreduce"))
        raw = _raw_step(cfg)
        lr = cfg.learning_rate
        n_axis = (dp_mesh.shape.get(dp_axis, 1)
                  if dp_mesh is not None and dp_axis else 1)
        barrier = jax.lax.optimization_barrier

        # Bitwise parity with the PS path needs its exact rounding
        # points: there grad is a jit OUTPUT, lr*grad rounds as its own
        # op, and the server subtract is its own kernel. One fused
        # program drifts an ulp per step — the HLO simplifier folds
        # grad's /batch into *lr (the barrier pins that), and XLA:CPU's
        # LLVM backend then contracts mul+sub into an fma BELOW the HLO
        # barrier. So the delta program and the donated subtract stay
        # two dispatches: both device-side and async-chained (zero host
        # round trips — the plane's whole point), with no mul feeding a
        # sub inside either kernel.
        if n_axis > 1:
            axis = dp_axis

            def delta_step(w, X, y):
                loss, grad = raw(w, X, y)
                # Per-shard batch means -> global mean: the in-graph
                # allreduce this policy exists for.
                grad = jax.lax.psum(grad, axis) / n_axis
                loss = jax.lax.psum(loss, axis) / n_axis
                return lr * barrier(grad), loss

            fn = jax.shard_map(delta_step, mesh=dp_mesh,
                               in_specs=(P(), P(axis), P(axis)),
                               out_specs=(P(), P()), check_vma=False)
            # No donation by design: w must SURVIVE this program for the
            # separate donated apply kernel (the bitwise-parity split
            # above); grad/loss don't alias any input shape worth reusing.
            self._delta = jax.jit(fn)  # graftlint: disable=missing-donation
        else:
            def delta_step(w, X, y):
                loss, grad = raw(w, X, y)
                return lr * barrier(grad), loss

            # Same deliberate non-donation as the dp branch above.
            self._delta = jax.jit(delta_step)  # graftlint: disable=missing-donation
        self._apply = jax.jit(lambda w, d: w - d, donate_argnums=0)
        self._n_axis = n_axis
        self._grad_bytes = cfg.width * cfg.num_class * 4
        self._cp = cp
        self.weights = jnp.asarray(
            np.asarray(self.table.raw()).reshape(cfg.width, cfg.num_class))

    def update(self, X: np.ndarray, y: np.ndarray):
        """Returns the loss as a device scalar (no host sync)."""
        delta, loss = self._delta(self.weights, jnp.asarray(X),
                                  jnp.asarray(y))
        self.weights = self._apply(self.weights, delta)
        self._cp.record(self._cp.ALLREDUCE, self._grad_bytes)
        return loss

    def sync(self) -> None:
        """Publish the device replica to the PS table (epoch boundaries /
        before test) — ONE dense write where PSModel pushed a delta per
        minibatch."""
        self.table.publish(np.asarray(self.weights).reshape(-1))

    def get_weights(self) -> np.ndarray:
        return np.asarray(self.weights)

    def set_weights(self, w: np.ndarray) -> None:
        self.weights = jnp.asarray(
            np.asarray(w, dtype=np.float32).reshape(self.cfg.width,
                                                    self.cfg.num_class))
        self.sync()


class ModelAverageModel(LocalModel):
    """``comm_policy=model_average`` — the reference's "ma" mode for LR
    (``-ma``, src/zoo.cpp:24): each worker trains a local replica with the
    fully fused donated step; :meth:`sync` averages replicas across
    processes over the collective plane
    (:func:`~multiverso_tpu.parallel.comm_policy.model_average_arrays`)
    and publishes the merged weights to the PS table. Convergence trades a
    staleness window (the averaging period) for zero per-step
    communication — loss-trajectory parity with PS, not bitwise parity."""

    def __init__(self, cfg: LogRegConfig, table=None):
        from multiverso_tpu.parallel import comm_policy as cp
        super().__init__(cfg)
        self.table = table if table is not None else mv.create_table(
            ArrayTableOption(size=cfg.width * cfg.num_class, updater="sgd",
                             name="logreg_weights",
                             comm_policy="model_average"))
        self._cp = cp

    def sync(self) -> None:
        merged = self._cp.model_average_arrays(
            [np.asarray(self.weights)])[0]
        self.weights = jnp.asarray(merged)
        self.table.publish(merged.reshape(-1))


def resolve_logreg_comm_policy(cfg: LogRegConfig) -> str:
    """Per-table policy for the LR weight table (docs/DESIGN.md decision
    table). Default ""/ps keeps the PSModel path without probing; "auto"
    resolves on the weight shape (dense, usually small -> allreduce where
    the probe agrees); FTRL is pinned to ps."""
    from multiverso_tpu.core.zoo import Zoo
    from multiverso_tpu.parallel import comm_policy as cp
    from multiverso_tpu.utils.log import check

    explicit = (cfg.comm_policy or "").strip().lower()
    if cfg.objective == "ftrl":
        check(explicit in ("", "ps", "auto"),
              "ftrl keeps server-side {z, n} updater state — its "
              f"comm_policy must stay ps (got '{explicit}')")
        return cp.PS
    if explicit in ("", cp.PS):
        return cp.PS
    zoo = Zoo._instance
    mesh = zoo.mesh if zoo is not None and zoo.started else None
    return cp.resolve_comm_policy(
        (cfg.width, cfg.num_class), np.float32, sparse=False,
        explicit=None if explicit == "auto" else explicit, mesh=mesh,
        table="logreg_weights")


def make_model(cfg: LogRegConfig):
    if not cfg.use_ps:
        return LocalModel(cfg)
    from multiverso_tpu.parallel import comm_policy as cp
    policy = resolve_logreg_comm_policy(cfg)
    if policy == cp.ALLREDUCE:
        return AllreduceModel(cfg)
    if policy == cp.MODEL_AVERAGE:
        return ModelAverageModel(cfg)
    return PSModel(cfg)
