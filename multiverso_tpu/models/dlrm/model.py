"""DLRM-shaped click-through model over the PS embedding plane.

The parameter-server sweet spot the reference Multiverso was built for
(PAPER.md: sparse row-granular access IS the PS case), assembled from
the planes this stack already grew:

* **Embedding tables** — one PS-backed :class:`MatrixTable` per
  categorical field (``comm_policy='ps'``), updated by the server-side
  ``adagrad`` updater whose per-worker ``g2`` state shards under
  ``-state_sharding``. Clients push lr-prescaled row deltas
  (``AddOption.learning_rate`` reconstructs the raw gradient server-side
  — the PSModel contract from models/logreg). A step pulls and pushes
  ALL fields through one table group (tables/table_group.py): one launch
  for the pull (rows arrive as ``[B, fields, D]``), one donated update
  for the push (docs/RECSYS.md).
* **Dense bottom/top MLP** — device-resident, trained by the hybrid step
  (:class:`~multiverso_tpu.parallel.hybrid_step.HybridStep`, which owns
  the pull-compute-merge-apply-push cycle and where the rows live
  meanwhile). This module states the delta function, the apply function
  and the tables.
* **The local twin** (``mode='local'``) is the same model over a
  :class:`~multiverso_tpu.tables.table_group.LocalTableGroup` of the
  same table options: the same programs over the same initial bytes, so
  PS-vs-local parity is bitwise (tests/test_dlrm.py pins it).

Model shape (DLRM): bottom MLP embeds the dense features into the
embedding space, the interaction layer takes all pairwise dot products
of the (bottom output + per-field embedding) vectors, and the top MLP
maps [bottom output ++ interactions] to one click logit.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import multiverso_tpu as mv
from multiverso_tpu.core.options import AddOption, MatrixTableOption
from multiverso_tpu.parallel.hybrid_step import HybridStep
from multiverso_tpu.tables.table_group import LocalTableGroup
from multiverso_tpu.telemetry import span
from multiverso_tpu.utils.log import check

__all__ = ["DLRMConfig", "DLRMModel", "SnapshotScorer", "dense_param_count",
           "flatten_dense", "unflatten_dense", "init_dense_params",
           "make_forward"]


@dataclasses.dataclass
class DLRMConfig:
    """Model + optimizer shape. ``vocab`` rows per field table; the
    stream config's (fields, vocab, dense_dim) must match."""
    fields: int = 4
    vocab: int = 2048
    embed_dim: int = 16
    dense_dim: int = 8
    bottom_mlp: Tuple[int, ...] = (32,)
    top_mlp: Tuple[int, ...] = (32,)
    #: Client-side delta prescale for embedding pushes AND the dense
    #: plane's SGD step (the PSModel lr contract: server reconstructs
    #: grad = delta / lr).
    learning_rate: float = 0.05
    #: Server-side adagrad step scale (AddOption.rho): the effective
    #: embedding step is ``rho / sqrt(G + eps) * grad``.
    adagrad_step: float = 0.05
    seed: int = 0
    table_prefix: str = "dlrm_emb"
    #: Embedding-table policy. The PS plane is the point of this model;
    #: "auto" would resolve there anyway for embedding-shaped tables.
    comm_policy: str = "ps"

    @property
    def interaction_dim(self) -> int:
        # Pairwise dots among (bottom output + fields) vectors, i < j.
        n = self.fields + 1
        return (n * (n - 1)) // 2

    @property
    def top_in_dim(self) -> int:
        return self.embed_dim + self.interaction_dim

    def layer_dims(self) -> List[Tuple[int, int]]:
        """(in, out) of every dense layer, bottom then top."""
        dims = []
        prev = self.dense_dim
        for h in tuple(self.bottom_mlp) + (self.embed_dim,):
            dims.append((prev, h))
            prev = h
        prev = self.top_in_dim
        for h in tuple(self.top_mlp) + (1,):
            dims.append((prev, h))
            prev = h
        return dims

    @property
    def dense_table_name(self) -> str:
        return f"{self.table_prefix}_dense"

    def table_name(self, field: int) -> str:
        return f"{self.table_prefix}{field}"


def dense_param_count(cfg: DLRMConfig) -> int:
    return sum(i * o + o for i, o in cfg.layer_dims())


def init_dense_params(cfg: DLRMConfig) -> List[Tuple[jax.Array, jax.Array]]:
    """Deterministic He-style init — same seed, same bytes, which is what
    lets the PS model and its local twin start bitwise-identical."""
    rng = np.random.default_rng(cfg.seed)
    params = []
    for fan_in, fan_out in cfg.layer_dims():
        W = (rng.standard_normal((fan_in, fan_out))
             * np.sqrt(2.0 / max(1, fan_in))).astype(np.float32)
        b = np.zeros(fan_out, dtype=np.float32)
        params.append((jnp.asarray(W), jnp.asarray(b)))
    return params


def flatten_dense(params) -> np.ndarray:
    """Pack the MLP params into one row vector — the payload the
    ``{prefix}_dense`` publish table (and therefore every checkpoint /
    serving snapshot) carries."""
    return np.concatenate([np.asarray(leaf).reshape(-1)
                           for W, b in params for leaf in (W, b)])


def unflatten_dense(cfg: DLRMConfig, vec) -> List[Tuple[jax.Array, jax.Array]]:
    vec = np.asarray(vec, dtype=np.float32).reshape(-1)
    if vec.size != dense_param_count(cfg):
        raise ValueError(f"dense vector has {vec.size} params, config "
                         f"needs {dense_param_count(cfg)}")
    params, off = [], 0
    for fan_in, fan_out in cfg.layer_dims():
        W = vec[off:off + fan_in * fan_out].reshape(fan_in, fan_out)
        off += fan_in * fan_out
        b = vec[off:off + fan_out]
        off += fan_out
        params.append((jnp.asarray(W), jnp.asarray(b)))
    return params


def make_forward(cfg: DLRMConfig):
    """Unjitted ``(params, emb[B,F,D], dense_x[B,dd]) -> logits[B]`` —
    the one forward both the train step and every serving lane share, so
    lane parity is structural (same ops, same order)."""
    n_bottom = len(cfg.bottom_mlp) + 1
    iu = np.triu_indices(cfg.fields + 1, k=1)

    def forward(params, emb, dense_x):
        h = dense_x
        for W, b in params[:n_bottom]:
            h = jax.nn.relu(h @ W + b)
        z = jnp.concatenate([h[:, None, :], emb], axis=1)   # [B, F+1, D]
        prods = jnp.einsum("bij,bkj->bik", z, z)            # [B, F+1, F+1]
        inter = prods[:, iu[0], iu[1]]                      # [B, F(F+1)/2]
        t = jnp.concatenate([h, inter], axis=1)
        for W, b in params[n_bottom:-1]:
            t = jax.nn.relu(t @ W + b)
        W, b = params[-1]
        return (t @ W + b)[:, 0]

    return forward


def _make_loss(cfg: DLRMConfig):
    forward = make_forward(cfg)

    def loss_fn(params, emb, dense_x, y):
        logits = forward(params, emb, dense_x)
        # Numerically stable BCE-with-logits.
        loss = jnp.mean(jnp.maximum(logits, 0.0) - logits * y
                        + jnp.log1p(jnp.exp(-jnp.abs(logits))))
        return loss, jax.nn.sigmoid(logits)

    return loss_fn


class DLRMModel:
    """The train-side model. ``mode='ps'`` keeps embeddings in PS tables
    (requires ``mv.init``); ``mode='local'`` is the single-worker
    reference twin — same dense programs, embeddings in host-owned
    device arrays updated through the server's own adagrad row math.
    """

    def __init__(self, cfg: DLRMConfig, mode: str = "ps", dp_mesh=None,
                 dp_axis: Optional[str] = None):
        with span("recsys.build", mode=mode):
            self._build(cfg, mode, dp_mesh, dp_axis)

    def _build(self, cfg: DLRMConfig, mode: str, dp_mesh, dp_axis) -> None:
        check(mode in ("ps", "local"), f"bad DLRM mode {mode!r}")
        self.cfg, self.mode = cfg, mode
        self.dense_params = init_dense_params(cfg)
        lr = cfg.learning_rate
        loss_fn = _make_loss(cfg)
        barrier = jax.lax.optimization_barrier

        def delta_step(params, emb, dense_x, y):
            (loss, scores), (gp, gemb) = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True)(params, emb,
                                                       dense_x, y)
            deltas = jax.tree_util.tree_map(lambda g: lr * barrier(g), gp)
            return deltas, lr * barrier(gemb), loss, scores

        self.steps = 0
        self._add_option = AddOption(
            worker_id=max(mv.worker_id(), 0) if mode == "ps" else 0,
            learning_rate=lr, rho=cfg.adagrad_step)
        options = [MatrixTableOption(
            num_row=cfg.vocab, num_col=cfg.embed_dim,
            random_init=True, seed=cfg.seed + 101 + f,
            updater="adagrad", name=cfg.table_name(f),
            comm_policy=cfg.comm_policy or "ps")
            for f in range(cfg.fields)]
        # One group over the field tables: a step pulls and pushes every
        # field in one launch each way.
        if mode == "ps":
            self.tables = [mv.create_table(o) for o in options]
            self.group = mv.create_table_group(self.tables)
            # Dense params ride the allreduce plane's publish surface so
            # checkpoints (and serving snapshots) carry the whole model.
            self.dense_table = mv.create_table(MatrixTableOption(
                num_row=1, num_col=dense_param_count(cfg),
                updater="sgd", name=cfg.dense_table_name,
                comm_policy="allreduce"))
            self.sync()
        else:
            self.group = LocalTableGroup(options)
        self._hybrid = HybridStep(
            self, delta_step,
            lambda p, d: jax.tree_util.tree_map(lambda w, g: w - g, p, d),
            dense=("dense_params",), group=self.group, pull=self._pull,
            push=lambda ids, delta: self._push_rows(None, ids, delta),
            prefix="recsys", grad_bytes=dense_param_count(cfg) * 4,
            dp_mesh=dp_mesh, dp_axis=dp_axis)

    # -- embedding plane ---------------------------------------------------
    def _push_rows(self, field: None, ids: np.ndarray, delta) -> None:
        """Every field's row deltas (``ids`` [B, fields], ``delta``
        [B, fields, D], on the host or on the device) in one donated
        update. ``field`` is always None: the name and the signature are
        what the benchmark's dropped-push control patches
        (benchmark/tests/test_controls.py). Duplicate ids within the batch
        are exact: the updater's combine_duplicate_rows sums co-keyed
        deltas before the row math, identically on both planes."""
        if field is not None:
            raise ValueError("a step pushes every field at once")
        self.group.add_rows(np.asarray(ids, np.int32), delta,
                            self._add_option)

    def _pull(self, ids: np.ndarray, device: bool):
        """[B, fields, embed_dim] rows for one batch's id matrix, one launch
        for all fields: on the device as the gather program left them, or
        (one copy) on the host."""
        pull = self.group.get_rows_device if device else self.group.get_rows
        return pull(np.asarray(ids, np.int32))

    def gather_emb(self, ids: np.ndarray) -> np.ndarray:
        """[B, fields, embed_dim] rows for one batch's id matrix, on the
        host (serving lanes, checks)."""
        return self._pull(ids, device=False)

    # -- training ----------------------------------------------------------
    def step(self, ids: np.ndarray, dense_x: np.ndarray,
             labels: np.ndarray) -> Tuple[float, np.ndarray]:
        """One minibatch: pull touched rows, run the hybrid step, push
        the fields' row deltas. Returns (loss, predicted scores) — the
        scores feed the streaming train AUC for free."""
        loss, scores = self._hybrid(ids, dense_x, labels,
                                    fields=self.cfg.fields, batch=len(labels))
        with span("recsys.finish"):
            self.steps += 1
            # Both results come down in one copy.
            loss, scores = jax.device_get((loss, scores))
            return float(loss), scores

    # -- inference ---------------------------------------------------------
    def predict(self, ids: np.ndarray, dense_x: np.ndarray) -> np.ndarray:
        """Fresh-table scores (the staleness-0 lane)."""
        return self.scores(self.gather_emb(ids), dense_x)

    def scores(self, emb: np.ndarray, dense_x: np.ndarray) -> np.ndarray:
        """Scores from pre-gathered rows — the serving lanes feed rows
        from whatever plane (live runner, frozen replica) they own."""
        _, _, _, scores = self._hybrid.delta(
            self.dense_params, jnp.asarray(emb), jnp.asarray(dense_x),
            jnp.zeros(len(dense_x), jnp.float32))
        return np.asarray(scores)

    # -- checkpoint / publish surface --------------------------------------
    def sync(self) -> None:
        """Publish the dense replica to its PS table (ps mode) — the
        checkpoint/serving reconcile point, one dense write (the
        AllreduceModel contract)."""
        if self.mode == "ps":
            self.dense_table.publish(
                flatten_dense(self.dense_params)[None, :])

    def local_rows(self, field: int) -> np.ndarray:
        """Whole-table snapshot of one local-twin field (parity tests)."""
        return self.group.local_rows(field)


class SnapshotScorer:
    """Frozen-lane scorer: dense params + embedding gather both come
    from one serving snapshot (a :class:`CheckpointReplica`'s tables),
    so a lane's predictions are wholly as-of its publish step — dense
    and sparse halves can never mix generations."""

    def __init__(self, cfg: DLRMConfig, dense_vec, row_lookup,
                 forward=None):
        """``row_lookup(field, ids) -> [n, embed_dim]`` rows. Pass a
        prebuilt jitted ``forward`` when constructing scorers per batch
        (the freshness tracker does) so the jit cache is shared."""
        self.cfg = cfg
        self._params = unflatten_dense(cfg, dense_vec)
        self._lookup = row_lookup
        self._forward = forward if forward is not None \
            else jax.jit(make_forward(cfg))

    def scores(self, ids: np.ndarray, dense_x: np.ndarray) -> np.ndarray:
        emb = np.stack([self._lookup(f, ids[:, f])
                        for f in range(self.cfg.fields)], axis=1)
        logits = self._forward(self._params, jnp.asarray(emb),
                               jnp.asarray(dense_x))
        return np.asarray(jax.nn.sigmoid(logits))
