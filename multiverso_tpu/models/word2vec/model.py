"""Word2vec (distributed WordEmbedding) — the flagship workload.

Parity with ``Applications/WordEmbedding/src/`` (SURVEY.md §2.6): CBOW and
skip-gram, negative sampling and hierarchical softmax, the five parameter
tables (input/output embedding matrices, two AdaGrad accumulator matrices,
word-count KV table — ref ``communicator.cpp:17-32``), block-pipelined
training with a words/sec metric, linear lr decay, and batched rank-0
embedding export (ref ``distributed_wordembedding.cpp:263-306``).

TPU-native design (the whole point): the reference's hot loop is per-sample
dot products over ``embedding_size`` (``wordembedding.cpp:57-135``) pushed
through per-row table RPCs. Here one **fused jitted step** gathers all rows
for a [B]-pair batch from the vocab-row-sharded embedding tables (TP of the
vocab axis over ICI), computes every dot product as batched einsums on the
MXU, applies AdaGrad/SGD, and scatter-adds updates back into HBM — the
"Get-update-Add round trip fused into a single compiled step" that SURVEY.md
§7 names as the perf requirement. Tables remain first-class: the step reads
and writes the same ``ServerStore`` arrays the PS Get/Add API serves, so
parity semantics (checkpointing, row gets) coexist with fused speed.
"""

from __future__ import annotations

import contextvars
import dataclasses
import functools
import time
from typing import Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import multiverso_tpu as mv
from multiverso_tpu.core.options import KVTableOption, MatrixTableOption
from multiverso_tpu.models.word2vec.data import (BatchGenerator, BlockStream,
                                                 CbowBatch, SkipGramBatch,
                                                 read_corpus)
from multiverso_tpu.models.word2vec.dictionary import (Dictionary,
                                                       HuffmanEncoder,
                                                       Sampler)
from multiverso_tpu.telemetry import (counter, gauge, register_program,
                                      span, startup)
from multiverso_tpu.utils.dashboard import monitor
from multiverso_tpu.utils.log import check, log

_EPS = 1e-7
_WORDCOUNT_KEY = 0


@dataclasses.dataclass
class Word2VecConfig:
    embedding_size: int = 100
    window: int = 5
    negative: int = 5
    min_count: int = 5
    sample: float = 1e-3
    batch_size: int = 1024
    learning_rate: float = 0.05
    epochs: int = 1
    sg: bool = True                 # skip-gram vs CBOW
    hs: bool = False                # hierarchical softmax vs negative sampling
    optimizer: str = "adagrad"      # adagrad | sgd
    block_words: int = 100_000
    pipeline: bool = True
    # Distributed mode: double-buffered param prefetch — issue block N+1's
    # table pulls BEFORE computing block N, overlapping the PS round trip
    # with device compute (the reference's is_pipeline GetAsync swap,
    # ps_model.cpp:236-271 / distributed_wordembedding.cpp:203-212).
    # Pulled views are >= one block stale (the documented pipeline trade);
    # async dense tables only (BSP and sparse keep strict ordering).
    param_prefetch: bool = False
    scan_group: int = 32            # minibatches per jitted scan dispatch
    # Embedding storage dtype: "float32" or "bfloat16" (math stays f32;
    # bf16 halves HBM bytes per gather/scatter — the dominant cost).
    param_dtype: str = "float32"
    # Device pipeline (all four variants): pair-gen/windowing/subsample/
    # negatives/Huffman gathers on device; host uploads raw token ids only.
    device_pipeline: bool = False
    # Compact valid pairs to the front of the device pair stream and skip
    # all-padding chunks (~2x fewer chunk steps at typical subsample rates).
    compact_pairs: bool = True
    # How the fused chunk loop executes (sg-ns, single device):
    #   "in_graph"       — one jitted block program, ONE launch per
    #                      block; the chunk loop is a lax.fori_loop. On
    #                      tables the row kernel serves (float32, 128
    #                      columns, AdaGrad, on one device or in row ranges
    #                      over one mesh axis: _PlacedStep.row_kernel) a
    #                      chunk's row updates are Pallas kernels over
    #                      sorted ids; elsewhere XLA's scatter-adds, which
    #                      write a row of a big table in 71-86 ns and are
    #                      then all of a chunk (PERF.md 5-6, PR 31);
    #   "pipelined_host" — per-chunk host dispatches with a depth-N
    #                      in-flight window (dispatch_depth): donated table
    #                      carries chain through the queue and the host
    #                      never blocks per chunk, so launch latency
    #                      overlaps device compute;
    #   None / "auto"    — resolve_dispatch_mode's decision table.
    dispatch_mode: Optional[str] = None
    # In-flight dispatch window for pipelined_host (chunks dispatched ahead
    # of device completion before the host waits on the oldest).
    dispatch_depth: int = 8
    block_sentences: int = 512      # sentences per device block
    pad_sentence_length: int = 512  # fixed sentence pad (longer ones split)
    # dp x tp mesh for the device pipeline: sentences sharded over
    # mesh_data devices, vocab rows over mesh_model. 1 x 1 = single-device
    # step (tables still row-sharded by the store's own mesh).
    mesh_data: int = 1
    mesh_model: int = 1
    max_code_length: int = 40
    seed: int = 0
    delta_scale: Optional[float] = None   # 1/num_workers push scaling
    # Per-table communication policy (parallel/comm_policy.py;
    # docs/DESIGN.md "CommPolicy"):
    #   None        — legacy fused plane, no resolution (zero overhead);
    #   "auto"/"hybrid" — per-table decision table: the sparse embedding/
    #                 accumulator tables stay on the (fused) PS plane,
    #                 small dense tables (the word-count) merge through
    #                 one in-graph collective per block;
    #   "ps"        — force EVERY table through the client push/pull
    #                 plane (pull-train-push per block — the reference's
    #                 communicator loop; the pure-PS bench baseline);
    #   "model_average" — replicas train fused, reconciled per epoch via
    #                 the collective plane (the reference's "ma" mode).
    comm_policy: Optional[str] = None
    # Per-table override map {table name -> policy}, e.g.
    # {"w2v_wordcount": "ps"} pins the word-count table back onto the
    # kv plane under an otherwise-auto resolution.
    comm_policy_overrides: Optional[dict] = None


def _row_gather_negatives(neg_table, key, shape):
    """Draw ``prod(shape)`` unigram negatives as ROW gathers.

    TPU scalar gathers are ~7ns/element (a 13M-element block draw costs
    ~93ms measured on v5e); row gathers of 128-wide tiles are ~24x faster.
    The sampler table is SHUFFLED at build time so any 128 consecutive
    entries are an iid unigram^0.75 sample — drawing a random row and
    consuming its entries is then statistically equivalent to 128
    independent element draws (without-replacement within one row of a
    2^20-entry table: negligible). Replaces the reference's per-sample
    ``sampler.cpp`` draws."""
    total = 1
    for s in shape:
        total *= s
    assert neg_table.ndim == 1, "pass the 1-D SHUFFLED sampler table"
    width = min(128, neg_table.shape[0])
    rows_tbl = neg_table.shape[0] // width
    table2d = neg_table[:rows_tbl * width].reshape(rows_tbl, width)
    rows_needed = -(-total // width)
    ridx = jax.random.randint(key, (rows_needed,), 0, rows_tbl)
    flat = jnp.take(table2d, ridx, axis=0).reshape(-1)
    return flat[:total].reshape(shape)


def _pair_arrays(sents, lengths, keep_prob, k_keep, k_win, window):
    """Masked offset-shift pairing (shared by the block step and the
    chunked pair_gen — the two paths must stay bitwise identical)."""
    S, L = sents.shape
    pos = jnp.arange(L)[None, :]
    valid = (pos < lengths[:, None])
    keep = jax.random.uniform(k_keep, (S, L)) < keep_prob[sents]
    valid = valid & keep
    wpos = jax.random.randint(k_win, (S, L), 1, window + 1)
    centers, contexts, pmask = [], [], []
    for d in range(1, window + 1):
        c = sents[:, :-d].reshape(-1)
        o = sents[:, d:].reshape(-1)
        m = ((wpos[:, :-d] >= d) & valid[:, :-d] &
             valid[:, d:]).reshape(-1)
        centers += [c, o]
        contexts += [o, c]
        pmask += [m, m]
    return (jnp.concatenate(centers), jnp.concatenate(contexts),
            jnp.concatenate(pmask))


def _compact_stream(centers, contexts, pmask, chunk):
    """Stable-partition valid pairs to the front; [n, chunk] views +
    true pair count."""
    (centers, contexts), _, n_pairs, n = _compact_examples(
        pmask, chunk, [centers, contexts], [])
    return centers, contexts, n_pairs, n


def _compact_examples(pmask, chunk, arrays1d, arrays2d):
    """Stable-partition valid examples to the front across parallel
    streams — 1-D ([P] -> [n, chunk]) and 2-D ([P, C] -> [n, chunk, C])
    payloads share one cumsum/destination map."""
    P = pmask.shape[0]
    total = P + (-P) % chunk
    n = total // chunk
    n_ex = pmask.sum().astype(jnp.int32)
    dest = jnp.cumsum(pmask.astype(jnp.int32)) - 1
    dest = jnp.where(pmask, dest, total)
    out1 = [jnp.zeros(total, a.dtype).at[dest].set(a, mode="drop")
            .reshape(n, chunk) for a in arrays1d]
    out2 = [jnp.zeros((total, a.shape[1]), a.dtype)
            .at[dest].set(a, mode="drop").reshape(n, chunk, a.shape[1])
            for a in arrays2d]
    return out1, out2, n_ex, n


def _cbow_arrays(sents, lengths, keep_prob, k_keep, k_win, window):
    """In-graph CBOW example construction: every kept token position is an
    example whose context is the surrounding (randomly shrunk) window —
    the device analog of the reference's CBOW loop
    (``wordembedding.cpp:120-135``): contexts within the center's
    effective window contribute; subsampled/pad tokens drop out of both
    roles. Returns centers [S*L], contexts [S*L, 2W], cmask (f32), and
    the example mask."""
    S, L = sents.shape
    pos = jnp.arange(L)[None, :]
    valid = pos < lengths[:, None]
    keep = jax.random.uniform(k_keep, (S, L)) < keep_prob[sents]
    tok_valid = valid & keep
    wpos = jax.random.randint(k_win, (S, L), 1, window + 1)
    ctx_cols, m_cols = [], []
    for d in range(1, window + 1):
        # Traced, not a host loop: the only caller is block_step, which
        # _PlacedStep jits (the lint follows jax.jit(...) lexically only).
        # graftlint: disable=host-jnp-in-loop
        pad_i = jnp.zeros((S, d), sents.dtype)
        pad_b = jnp.zeros((S, d), bool)  # graftlint: disable=host-jnp-in-loop
        right = jnp.concatenate([sents[:, d:], pad_i], axis=1)
        rmask = jnp.concatenate([tok_valid[:, d:], pad_b], axis=1) \
            & (wpos >= d)
        left = jnp.concatenate([pad_i, sents[:, :-d]], axis=1)
        lmask = jnp.concatenate([pad_b, tok_valid[:, :-d]], axis=1) \
            & (wpos >= d)
        ctx_cols += [right.reshape(-1), left.reshape(-1)]
        m_cols += [rmask.reshape(-1), lmask.reshape(-1)]
    contexts = jnp.stack(ctx_cols, axis=1)          # [S*L, 2W]
    cmask = jnp.stack(m_cols, axis=1)               # [S*L, 2W]
    ex_mask = tok_valid.reshape(-1) & cmask.any(axis=1)
    return (sents.reshape(-1), contexts, cmask.astype(jnp.float32),
            ex_mask)


# ---------------------------------------------------------------------------
# Fused jitted steps. All take/return the (padded) table arrays.
# ---------------------------------------------------------------------------
# Ids a sort takes at once on the fused plane: XLA's TPU sort compiles in
# 0.5 s at 8,192 ids, 3 s at 16,384 and 13-16 s from 32,768 on (compiled
# for the v5e, PR 31), and every start-up pays it.
_SORT_SLAB = 8192


def row_kernel_selected(w, adagrad: bool, one_shard: bool) -> bool:
    """Whether a table's AdaGrad row update runs as the Pallas row kernel
    (``ops/pallas_rows.adagrad_fold_rows``): ``ServerStore``'s rule
    (``core/table.pallas_rows_eligible``: 2-D float32, exactly 128 columns,
    one shard), AdaGrad on, and no fewer rows than a kernel step's lanes
    (it waits for that many rows' DMA against a slice of the table). A
    trace shows shape and dtype but not the placement, so the rule is
    applied to the arrays a program is CALLED with (:class:`_PlacedStep`),
    once for the whole program."""
    from multiverso_tpu.core.table import pallas_rows_eligible
    from multiverso_tpu.ops.pallas_rows import _FOLD_GROUP_ROWS
    return (adagrad and pallas_rows_eligible(w.shape, w.dtype, one_shard)
            and w.shape[0] >= _FOLD_GROUP_ROWS)


@dataclasses.dataclass(frozen=True)
class _ShardedRows:
    """The row kernel a SHARD: tables whose rows lie in equal ranges over
    ``axis`` of ``mesh`` (``P(axis, None)``; replicated over its other
    axes), each shard a table the row kernel serves."""
    mesh: jax.sharding.Mesh
    axis: str
    interpret: bool


# The plane the row updates of the program being traced run on: None for
# XLA's scatter-adds; the row kernel over tables on one device, the value
# its ``interpret`` (``ops.pallas_interpret``); or the row kernel a shard of
# tables over one mesh axis (``_ShardedRows``). A program sets it around
# its own body (``_on_row_kernel``), so the raw steps keep the signature
# they have always had and ``_apply_update`` reads it where it traces.
_ROW_KERNEL = contextvars.ContextVar("w2v_row_kernel", default=None)


def _on_row_kernel(fn, plane):
    """``fn`` with its row updates on the Pallas plane ``plane`` wherever
    it is traced (under ``fn``'s own name, which names the jitted program);
    ``fn`` itself for ``None``, XLA's lines."""
    if plane is None:
        return fn

    @functools.wraps(fn)
    def placed(*args):
        token = _ROW_KERNEL.set(plane)
        try:
            return fn(*args)
        finally:
            _ROW_KERNEL.reset(token)
    return placed


def _row_shard_axis(sharding, shape) -> Optional[str]:
    """The ONE mesh axis the rows of a ``[rows, columns]`` table are divided
    over in equal ranges, columns whole: ``NamedSharding`` of spec ``(axis,
    None)``, rows divisible by the axis' size. Else ``None``."""
    if not isinstance(sharding, jax.sharding.NamedSharding) \
            or len(shape) != 2:
        return None
    rows, cols = (tuple(sharding.spec) + (None, None))[:2]
    if not isinstance(rows, str) or cols is not None \
            or shape[0] % sharding.mesh.shape[rows]:
        return None
    return rows


def _sorted_in_slabs(rows, grad, num_rows: int, slab: int):
    """``(ids, back, grads)`` for the row kernel: the ids ascending within
    slabs of ``slab``, an id out of range turned into ``num_rows`` (the
    kernel's dropped lane, behind its slab's live ones); for each position
    the number of positions directly before it that hold its id; and the
    gradients permuted alike."""
    n = rows.shape[0]
    pad = (-n) % slab
    rows = jnp.where((rows < 0) | (rows >= num_rows), num_rows,
                     rows).astype(jnp.int32)
    if pad:
        rows = jnp.concatenate([rows, jnp.full((pad,), num_rows, jnp.int32)])
    lane = jnp.arange(slab, dtype=jnp.int32)
    ids, order = jax.lax.map(
        lambda r: jax.lax.sort((r, lane), num_keys=1),
        rows.reshape(-1, slab))
    order = order + (jnp.arange(ids.shape[0], dtype=jnp.int32)
                     * slab)[:, None]
    # A pad lane's position is past the gradients: it reads the last row
    # (clipped) into a lane that is never written.
    grads = jnp.take(grad.astype(jnp.float32), order.reshape(-1), axis=0,
                     mode="clip")
    # Runs are read off the whole stream: where a slab's last id is the
    # next slab's first, the run goes on (to the kernel a run is equal
    # neighbours, wherever the slabs' seams fall).
    ids = ids.reshape(-1)
    pos = jnp.arange(ids.shape[0], dtype=jnp.int32)
    starts = jnp.concatenate([jnp.ones((1,), bool), ids[1:] != ids[:-1]])
    back = pos - jax.lax.cummax(jnp.where(starts, pos, 0))
    return ids, back, grads


def _fused_adagrad_update(w, g2, rows, grad, lr, interpret: bool):
    """The AdaGrad row update on the Pallas plane: sort, then the row
    kernel folds the duplicates and walks the touched rows. One slab: the
    whole update in one pass. More: two additive passes, exact whatever the
    order (``ops/pallas_rows.py``)."""
    from multiverso_tpu.ops.pallas_rows import adagrad_fold_rows
    one_slab = rows.shape[0] <= _SORT_SLAB
    ids, back, grads = _sorted_in_slabs(
        rows, grad, w.shape[0], rows.shape[0] if one_slab else _SORT_SLAB)
    for phase in (("both",) if one_slab else ("accumulate", "step")):
        w, g2 = adagrad_fold_rows(w, g2, ids, back, grads, lr, phase,
                                  interpret)
    return w, g2


def _sharded_adagrad_update(w, g2, rows, grad, lr, plane: _ShardedRows):
    """``_fused_adagrad_update`` a shard, under ``shard_map`` over the axis
    the rows are divided over: a shard takes the whole (replicated) id
    stream moved to its own row range, so another shard's id falls out of
    range and becomes the sentinel the slab sort puts behind the live ones
    (a kernel step that starts with one costs no DMA). A shard writes only
    its rows and replicas over the other axes compute alike: no collective,
    and the kernels' aliasing reaches the shards of the donated tables."""
    from jax.sharding import PartitionSpec as P
    shard_rows = w.shape[0] // plane.mesh.shape[plane.axis]

    def shard(w, g2, rows, grad, lr):
        lo = jax.lax.axis_index(plane.axis) * shard_rows
        return _fused_adagrad_update(w, g2, rows - lo, grad, lr,
                                     plane.interpret)

    table = P(plane.axis, None)
    # check_vma off: a pallas_call's internals mix varying and unvarying
    # values under shard_map (parallel/sequence.py does the same).
    return jax.shard_map(
        shard, mesh=plane.mesh, in_specs=(table, table, P(), P(), P()),
        out_specs=(table, table), check_vma=False)(w, g2, rows, grad, lr)


def _apply_update(w, g2, rows, grad, lr, adagrad: bool):
    """Apply an embedding update (+AdaGrad) for possibly-duplicated rows:
    for a row with gradients g_1..g_k, ``G += sum(g_i^2)`` then ``w -= lr
    sum(g_i) / sqrt(G + 1e-6)``; ids out of range are dropped. Gradients
    arrive f32; the step is cast to the storage dtype (bf16 tables keep f32
    math). In a program whose tables the row kernel serves
    (``_ROW_KERNEL``, set from ``_PlacedStep.row_kernel``) the kernel runs,
    over the table or over each of its shards; else XLA's scatter-adds, one
    write an id."""
    plane = _ROW_KERNEL.get()
    if isinstance(plane, _ShardedRows):
        return _sharded_adagrad_update(w, g2, rows, grad, lr, plane)
    if plane is not None:
        return _fused_adagrad_update(w, g2, rows, grad, lr, plane)
    if adagrad:
        g2 = g2.at[rows].add(jnp.square(grad).astype(g2.dtype), mode="drop")
        denom = jnp.sqrt(jnp.take(g2, rows, axis=0, mode="clip")
                         .astype(jnp.float32) + 1e-6)
        step = (-lr * grad / denom).astype(w.dtype)
    else:
        step = (-lr * grad).astype(w.dtype)
    w = w.at[rows].add(step, mode="drop")
    return w, g2


def _ns_grads(u, v_pos, v_neg, mask):
    """Shared negative-sampling math (f32). u:[B,D] v_pos:[B,D]
    v_neg:[B,K,D]."""
    u = u.astype(jnp.float32)
    v_pos = v_pos.astype(jnp.float32)
    v_neg = v_neg.astype(jnp.float32)
    s_pos = jax.nn.sigmoid(jnp.sum(u * v_pos, axis=-1))          # [B]
    s_neg = jax.nn.sigmoid(jnp.einsum("bd,bkd->bk", u, v_neg))   # [B,K]
    loss = -(mask * jnp.log(s_pos + _EPS)).sum() \
           - (mask[:, None] * jnp.log(1.0 - s_neg + _EPS)).sum()
    g_pos = (s_pos - 1.0) * mask                                 # [B]
    g_neg = s_neg * mask[:, None]                                # [B,K]
    grad_u = g_pos[:, None] * v_pos + jnp.einsum("bk,bkd->bd", g_neg, v_neg)
    grad_vpos = g_pos[:, None] * u                               # [B,D]
    grad_vneg = g_neg[..., None] * u[:, None, :]                 # [B,K,D]
    return loss, grad_u, grad_vpos, grad_vneg


def _hs_grads(u, v_nodes, codes, lmask):
    """Hierarchical-softmax math (f32). u:[B,D] v_nodes:[B,L,D]
    codes:[B,L]."""
    u = u.astype(jnp.float32)
    v_nodes = v_nodes.astype(jnp.float32)
    score = jnp.einsum("bd,bld->bl", u, v_nodes)                 # [B,L]
    target = 1.0 - codes
    sign = 2.0 * target - 1.0
    loss = -(lmask * jnp.log(jax.nn.sigmoid(sign * score) + _EPS)).sum()
    g = (jax.nn.sigmoid(score) - target) * lmask                 # [B,L]
    grad_u = jnp.einsum("bl,bld->bd", g, v_nodes)
    grad_v = g[..., None] * u[:, None, :]                        # [B,L,D]
    return loss, grad_u, grad_v


def raw_sg_ns_step(adagrad: bool):
    """Unjitted skip-gram/negative-sampling step — callers apply their own
    jit/shardings (the multi-chip dry run shards vocab rows over a model
    axis and the batch over a data axis). Like its three siblings it
    returns ``(w_in, w_out, g_in, g_out, loss)``; which plane its row
    updates run on is the enclosing program's to say (``_apply_update``)."""
    def step(w_in, w_out, g_in, g_out, centers, contexts, negatives, mask,
             lr):
        with jax.named_scope("w2v_gather"):
            u = jnp.take(w_in, centers, axis=0, mode="clip")
            v_pos = jnp.take(w_out, contexts, axis=0, mode="clip")
            v_neg = jnp.take(w_out, negatives, axis=0, mode="clip")
        with jax.named_scope("w2v_grads"):
            loss, grad_u, grad_vpos, grad_vneg = _ns_grads(u, v_pos, v_neg,
                                                           mask)
        with jax.named_scope("w2v_rows"), jax.named_scope("w2v_rows_in"):
            w_in, g_in = _apply_update(w_in, g_in, centers, grad_u, lr,
                                       adagrad)
        with jax.named_scope("w2v_rows"), jax.named_scope("w2v_rows_out"):
            B, K, D = grad_vneg.shape
            rows = jnp.concatenate([contexts, negatives.reshape(B * K)])
            grads = jnp.concatenate([grad_vpos,
                                     grad_vneg.reshape(B * K, D)])
            w_out, g_out = _apply_update(w_out, g_out, rows, grads, lr,
                                         adagrad)
        return w_in, w_out, g_in, g_out, loss

    return step


class _PlacedStep:
    """A jitted word2vec program that adapts to where its tables live.

    A trace cannot see the placement; the caller's arrays can. So the rule
    is applied to the four tables a call brings (``row_kernel``) and the
    program is jitted once a plane, ``jax.jit(_on_row_kernel(fn, plane),
    donating the tables)``. Tables the row kernel serves (float32, 128
    columns, AdaGrad on: ``row_kernel_selected``) on one device, committed
    there or not, run the kernel (interpreted off the TPU); such tables in
    equal row ranges over ONE mesh axis run it a shard
    (``_sharded_adagrad_update``); anything else (rows over two axes or not
    divisible, host arrays, another width or dtype, AdaGrad off) runs
    ``fn`` as it stands. A program that lays its tables out itself (the
    ``in_shardings`` among its ``jit_options``) is judged by that layout,
    whatever the arrays come in. Each call counts
    ``w2v.rows.plane.<fused|xla>``, the plane its row updates ran on."""

    def __init__(self, fn, adagrad: bool, **jit_options):
        self._fn, self._adagrad = fn, adagrad
        self._jit_options = jit_options
        self._programs = {}
        self.__name__ = fn.__name__

    def row_kernel(self, tables):
        """``_ROW_KERNEL``'s value for a call with these four tables."""
        from multiverso_tpu.ops import pallas_interpret
        layout = self._jit_options.get("in_shardings")
        placed = (list(layout[:4]) if layout else
                  [getattr(t, "sharding", None) for t in tables])
        if any(p is None for p in placed):
            return None
        devices = set().union(*(p.device_set for p in placed))
        plane = pallas_interpret(devices)
        shards = [t.shape for t in tables]
        if len(devices) > 1:
            axes = {_row_shard_axis(p, t.shape)
                    for p, t in zip(placed, tables)}
            if len(axes) > 1 or None in axes \
                    or len({p.mesh for p in placed}) > 1:
                return None
            mesh, (axis,) = placed[0].mesh, axes
            shards = [(rows // mesh.shape[axis], cols)
                      for rows, cols in shards]
            plane = _ShardedRows(mesh, axis, plane)
        if not all(row_kernel_selected(
                jax.ShapeDtypeStruct(shard, t.dtype), self._adagrad, True)
                for shard, t in zip(shards[:2], tables)):
            return None
        return plane

    def program(self, *args):
        """``(jitted program, its plane)`` for these arguments (the four
        tables first)."""
        plane = self.row_kernel(args[:4])
        if plane not in self._programs:
            self._programs[plane] = jax.jit(
                _on_row_kernel(self._fn, plane), donate_argnums=(0, 1, 2, 3),
                **self._jit_options)
        return self._programs[plane], plane

    def lower(self, *args, **kwargs):
        return self.program(*args)[0].lower(*args, **kwargs)

    def __call__(self, *args, **kwargs):
        program, plane = self.program(*args)
        counter("w2v.rows.plane.xla" if plane is None
                else "w2v.rows.plane.fused").inc()
        if not kwargs:      # traced keywords are no part of a registration
            register_program(program, args)
        return program(*args, **kwargs)


def build_sg_ns_step(adagrad: bool):
    return _PlacedStep(raw_sg_ns_step(adagrad), adagrad)


def raw_sg_hs_step(adagrad: bool):
    def step(w_in, w_out, g_in, g_out, centers, points, codes, lmask, lr):
        with jax.named_scope("w2v_gather"):
            u = jnp.take(w_in, centers, axis=0, mode="clip")
            v = jnp.take(w_out, points, axis=0, mode="clip")
        with jax.named_scope("w2v_grads"):
            loss, grad_u, grad_v = _hs_grads(u, v, codes, lmask)
        with jax.named_scope("w2v_rows"), jax.named_scope("w2v_rows_in"):
            w_in, g_in = _apply_update(w_in, g_in, centers, grad_u, lr,
                                       adagrad)
        with jax.named_scope("w2v_rows"), jax.named_scope("w2v_rows_out"):
            B, L, D = grad_v.shape
            w_out, g_out = _apply_update(
                w_out, g_out, points.reshape(B * L),
                grad_v.reshape(B * L, D), lr, adagrad)
        return w_in, w_out, g_in, g_out, loss

    return step


def raw_cbow_ns_step(adagrad: bool):
    def step(w_in, w_out, g_in, g_out, centers, contexts, cmask, negatives,
             mask, lr):
        with jax.named_scope("w2v_gather"):
            ctx = jnp.take(w_in, contexts, axis=0,
                           mode="clip").astype(jnp.float32)     # [B,C,D]
            counts = jnp.maximum(cmask.sum(axis=-1, keepdims=True), 1.0)
            u = (ctx * cmask[..., None]).sum(axis=1) / counts   # [B,D]
            v_pos = jnp.take(w_out, centers, axis=0, mode="clip")
            v_neg = jnp.take(w_out, negatives, axis=0, mode="clip")
        with jax.named_scope("w2v_grads"):
            loss, grad_u, grad_vpos, grad_vneg = _ns_grads(u, v_pos, v_neg,
                                                           mask)
        B, C = contexts.shape
        D = grad_u.shape[-1]
        with jax.named_scope("w2v_rows"), jax.named_scope("w2v_rows_in"):
            # distribute grad_u to each contributing context row
            gctx = (grad_u[:, None, :] * cmask[..., None]
                    / counts[..., None])
            w_in, g_in = _apply_update(w_in, g_in, contexts.reshape(B * C),
                                       gctx.reshape(B * C, D), lr, adagrad)
        with jax.named_scope("w2v_rows"), jax.named_scope("w2v_rows_out"):
            K = negatives.shape[1]
            rows = jnp.concatenate([centers, negatives.reshape(B * K)])
            grads = jnp.concatenate([grad_vpos,
                                     grad_vneg.reshape(B * K, D)])
            w_out, g_out = _apply_update(w_out, g_out, rows, grads, lr,
                                         adagrad)
        return w_in, w_out, g_in, g_out, loss

    return step


def raw_cbow_hs_step(adagrad: bool):
    def step(w_in, w_out, g_in, g_out, centers, contexts, cmask, points,
             codes, lmask, lr):
        with jax.named_scope("w2v_gather"):
            ctx = jnp.take(w_in, contexts, axis=0,
                           mode="clip").astype(jnp.float32)
            counts = jnp.maximum(cmask.sum(axis=-1, keepdims=True), 1.0)
            u = (ctx * cmask[..., None]).sum(axis=1) / counts
            v = jnp.take(w_out, points, axis=0, mode="clip")
        with jax.named_scope("w2v_grads"):
            loss, grad_u, grad_v = _hs_grads(u, v, codes, lmask)
        B, C = contexts.shape
        D = grad_u.shape[-1]
        with jax.named_scope("w2v_rows"), jax.named_scope("w2v_rows_in"):
            gctx = (grad_u[:, None, :] * cmask[..., None]
                    / counts[..., None])
            w_in, g_in = _apply_update(w_in, g_in, contexts.reshape(B * C),
                                       gctx.reshape(B * C, D), lr, adagrad)
        with jax.named_scope("w2v_rows"), jax.named_scope("w2v_rows_out"):
            L = points.shape[1]
            w_out, g_out = _apply_update(
                w_out, g_out, points.reshape(B * L),
                grad_v.reshape(B * L, D), lr, adagrad)
        return w_in, w_out, g_in, g_out, loss

    return step


def raw_step_factory(sg: bool, hs: bool):
    """The variant's raw-step maker, ``maker(adagrad)``."""
    return {(True, False): raw_sg_ns_step, (True, True): raw_sg_hs_step,
            (False, False): raw_cbow_ns_step,
            (False, True): raw_cbow_hs_step}[(bool(sg), bool(hs))]


def _make_block_fn(window: int, negative: int, chunk: int,
                   adagrad: bool, compact: bool, sg: bool = True,
                   hs: bool = False, huffman=None, constrain=None):
    """Unjitted whole-block step — factored out so the sharded builder can
    apply dp x tp shardings. ALL FOUR variants (sg/cbow x ns/hs).

    The host uploads only raw token ids ([S, L] padded sentences + lengths)
    — everything the reference does on the worker CPU (subsampling, dynamic
    window pair/window extraction, unigram negative sampling, Huffman path
    lookup, ``wordembedding.cpp:120-135`` / ``sampler.cpp``) happens inside
    one jitted program: masked offset-shift construction (static shapes),
    PRNG-driven subsample/window/negative draws, in-graph gathers of the
    Huffman point/code tables for HS, then a loop over fixed-size chunks
    through the fused update. Host->device traffic per block drops from
    ~40 bytes/pair to 4 bytes/word.

    ``compact=True`` additionally scatter-compacts the valid examples to
    the front of the stream (cumsum positions + masked scatter — cheap
    int32 traffic) and runs a dynamic-trip-count ``fori_loop`` over only
    the chunks that hold real work. The fixed window-d shift construction
    leaves ~half the slots masked (subsampled words, shrunk windows,
    sentence pads); without compaction every one of those slots still pays
    its gather/einsum/scatter. With it the per-block compute is
    proportional to true examples — the TPU answer to the reference's
    exact dynamic-window loop (``wordembedding.cpp:120-135``).
    """
    raw = raw_step_factory(sg, hs)(adagrad)
    if hs:
        check(huffman is not None, "HS device pipeline needs the encoder")
        # Device-resident Huffman path tables; [V, Lc] gathers happen
        # in-graph per chunk (closure constants: uploaded once, reused by
        # every dispatch).
        hp = jnp.asarray(huffman.points.astype(np.int32))
        hc = jnp.asarray(huffman.codes.astype(np.float32))
        hl = jnp.asarray(huffman.lengths.astype(np.int32))
        l_lane = jnp.arange(hp.shape[1])

    def _hs_args(target, m):
        """points/codes/length-mask for a chunk of target word ids."""
        pts = jnp.take(hp, target, axis=0, mode="clip")
        cds = jnp.take(hc, target, axis=0, mode="clip")
        lm = ((l_lane[None, :] <
               jnp.take(hl, target, mode="clip")[:, None])
              .astype(jnp.float32) * m[:, None])
        return pts, cds, lm

    def run_chunk(tables, slices, m, neg, lr):
        """Dispatch one chunk's streams into the variant's raw step."""
        if sg and not hs:
            c, o = slices
            return raw(*tables, c, o, neg, m, lr)
        if sg and hs:
            c, o = slices
            return raw(*tables, c, *_hs_args(o, m), lr)
        if not sg and not hs:
            c, ctx, cm = slices
            return raw(*tables, c, ctx, cm, neg, m, lr)
        c, ctx, cm = slices
        return raw(*tables, c, ctx, cm, *_hs_args(c, m), lr)

    def block_step(w_in, w_out, g_in, g_out, neg_table, keep_prob, sents,
                   lengths, key, lr):
        with jax.named_scope("w2v_pairs"):
            k_keep, k_win, k_neg = jax.random.split(key, 3)
            if sg:
                centers, contexts, pmask = _pair_arrays(
                    sents, lengths, keep_prob, k_keep, k_win, window)
                arrays1d, arrays2d = [centers, contexts], []
            else:
                centers, contexts, cmask, pmask = _cbow_arrays(
                    sents, lengths, keep_prob, k_keep, k_win, window)
                arrays1d, arrays2d = [centers], [contexts, cmask]
            if constrain is not None:
                # Under dp x tp GSPMD, XLA reshards the concatenated pair
                # streams (slices of the data-sharded sentence block) with a
                # partial-sum representation that double-counts every element
                # across the model axis (observed on jax 0.4.37 CPU: the
                # resharded stream comes back exactly 2x the true token ids).
                # Pinning the streams to an explicit layout right after
                # construction keeps the partitioner out of that path.
                arrays1d = [constrain(a) for a in arrays1d]
                arrays2d = [constrain(a) for a in arrays2d]
                pmask = constrain(pmask)
                if sg:
                    centers, contexts = arrays1d
                else:
                    (centers,), (contexts, cmask) = arrays1d, arrays2d
            P = pmask.shape[0]
            pad = (-P) % chunk
            n = (P + pad) // chunk

            if compact:
                out1, out2, n_pairs, n = _compact_examples(
                    pmask, chunk, arrays1d, arrays2d)
                streams = out1 + out2
            else:
                n_pairs = pmask.sum()
                streams = [jnp.pad(a, (0, pad)).reshape(n, chunk)
                           for a in arrays1d]
                streams += [jnp.pad(a, ((0, pad), (0, 0)))
                            .reshape(n, chunk, a.shape[1]) for a in arrays2d]
            negatives = (None if hs else
                         _row_gather_negatives(neg_table, k_neg,
                                               (n, chunk, negative)))

        if compact:
            # After compaction the first n_pairs slots are exactly the
            # valid examples, so only ceil(n_pairs/chunk) chunks carry
            # work.
            n_live = (n_pairs.astype(jnp.int32) + chunk - 1) // chunk
            lane = jnp.arange(chunk)

            def body(i, carry):
                *tables, loss = carry
                slices = tuple(
                    jax.lax.dynamic_index_in_dim(s, i, keepdims=False)
                    for s in streams)
                neg = (None if hs else jax.lax.dynamic_index_in_dim(
                    negatives, i, keepdims=False))
                m = ((i * chunk + lane) <
                     n_pairs.astype(jnp.int32)).astype(jnp.float32)
                out = run_chunk(tuple(tables), slices, m, neg, lr)
                return (*out[:4], loss + out[4])

            carry = jax.lax.fori_loop(
                0, n_live, body,
                (w_in, w_out, g_in, g_out, jnp.float32(0.0)))
            return (*carry, n_pairs)

        mask = jnp.pad(pmask, (0, pad)).reshape(n, chunk) \
                  .astype(jnp.float32)
        xs = (*streams, mask) if hs else (*streams, mask, negatives)

        def body(carry, xs_i):
            if hs:
                *slices, m = xs_i
                neg = None
            else:
                *slices, m, neg = xs_i
            *tables, acc = carry
            out = run_chunk(tuple(tables), tuple(slices), m, neg, lr)
            # Accumulate the loss IN the carry (sequential adds in chunk
            # order) exactly like the compact fori_loop path — a post-hoc
            # losses.sum() reduces in a different association order and
            # drifts from the compact path by an ulp, breaking the
            # bitwise compact/uncompact contract.
            return (*out[:4], acc + out[4]), None

        carry, _ = jax.lax.scan(
            body, (w_in, w_out, g_in, g_out, jnp.float32(0.0)), xs)
        return (*carry, n_pairs)

    return block_step


def build_device_block_step(window: int, negative: int, chunk: int,
                            adagrad: bool, compact: bool = True,
                            sg: bool = True, hs: bool = False,
                            huffman=None):
    """Whole-block training step with ON-DEVICE pair generation — all four
    variants (sg/cbow x ns/hs).

    The host uploads only raw token ids; pairing/windowing, subsampling,
    compaction, negative sampling or Huffman path gathers, and the chunk
    training loop all run in one jitted program (details in
    :func:`_make_block_fn`'s body), built for where the tables it is
    called with live (:class:`_PlacedStep`)."""
    return _PlacedStep(_make_block_fn(window, negative, chunk, adagrad,
                                      compact, sg=sg, hs=hs, huffman=huffman),
                       adagrad)


def build_sharded_block_step(mesh, window: int, negative: int, chunk: int,
                             adagrad: bool, compact: bool = True,
                             sg: bool = True, hs: bool = False,
                             huffman=None):
    """The SAME block step jitted over a (data x model) mesh — the dp x tp
    execution the reference reaches with row-sharded tables across servers
    plus data-parallel workers (SURVEY.md §2.4):

    * embedding + accumulator tables: vocab rows sharded over ``model``,
      replicated over ``data`` (``P("model", None)``) — the gathers become
      XLA collectives over the mesh;
    * the sentence block: sharded over ``data`` (each data shard generates
      pairs from its own sentences);
    * negative table / keep probabilities / RNG key / lr: replicated.

    Semantics are identical to the single-device step (same keys -> same
    pairs, negatives and update order), so losses match the unsharded run.
    The pair streams are pinned REPLICATED right after they are generated
    (``constrain``): ``data`` divides pair generation only, every chip runs
    every chunk over all its pairs. A :class:`_PlacedStep` like the
    one-device program, judged by the layout it gives its tables: where a
    ``model`` shard is a table the row kernel serves, each shard runs the
    kernel over the ids of its own row range (ISSUE 33); else the row
    updates are XLA's sharded scatters.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    table = NamedSharding(mesh, P("model", None))
    data2 = NamedSharding(mesh, P("data", None))
    data1 = NamedSharding(mesh, P("data"))
    repl = NamedSharding(mesh, P())

    def _repl(x):
        return jax.lax.with_sharding_constraint(x, repl)

    fn = _make_block_fn(window, negative, chunk, adagrad, compact,
                        sg=sg, hs=hs, huffman=huffman, constrain=_repl)
    return _PlacedStep(
        fn, adagrad,
        in_shardings=(table, table, table, table, repl, repl, data2, data1,
                      repl, repl),
        out_shardings=(table, table, table, table, repl, repl))


# Dispatch-latency threshold for dispatch-mode AUTO on XLA's row plane:
# below this, host launches are cheap enough that per-chunk dispatch beats
# the in-graph loop's scatters (jax 0.4.37: standalone chunk 0.05-0.12ms
# vs 2.2-2.6ms in-loop; on jax 0.9 XLA writes a row in 71 ns alone and 86
# in the loop, and pipelined_host read 1.6x in_graph at V=4M, PERF.md 6,
# PR 23-29). The v5e host measures 0.5-1.07ms, a coin flip against this
# threshold, so the benchmark pins in_graph. Tables on the row kernel
# (PR 31) do not come here (resolve_dispatch_mode, rule 2): a Mosaic call
# costs in a loop body what it costs alone.
CHUNK_DISPATCH_LATENCY_MS = 1.0


def measured_dispatch_latency_ms(n: int = 7) -> float:
    """Median latency of a trivial jitted dispatch + sync — the signal
    that decides dispatch-mode AUTO."""
    f = jax.jit(lambda a: a + 1.0)
    x = jnp.zeros(8, jnp.float32)
    f(x).block_until_ready()       # compile outside the timing
    times = []
    for _ in range(n):
        t0 = time.perf_counter()
        # The probe MEASURES the dispatch+sync round trip; the per-
        # iteration wait is the quantity being sampled.
        f(x).block_until_ready()  # graftlint: disable=block-until-ready-in-loop
        times.append((time.perf_counter() - t0) * 1e3)
    return float(np.median(times))


DISPATCH_MODES = ("in_graph", "pipelined_host")


def resolve_dispatch_mode(cfg: "Word2VecConfig",
                          row_kernel: bool = False) -> str:
    """Dispatch-mode decision.

    Explicit ``dispatch_mode`` wins; AUTO applies the decision table
    (docs/MIGRATION.md):

    1. variant is not sg-ns, or a dp x tp mesh is configured -> in_graph
       (the fused block step is the only implementation of those paths);
    2. the tables' row updates run the row kernel (``row_kernel``, which
       the caller reads off its live tables: ``_PlacedStep.row_kernel``)
       -> in_graph: on the kernel pipelined_host read 9% slower on the
       v5e (347,000 against 381,000 samples/s at V=4M) and holds 0.12 GB
       more (PERF.md 6, PR 31);
    3. measured launch latency < CHUNK_DISPATCH_LATENCY_MS ->
       pipelined_host (the depth-N window hides cheap launches; XLA's
       scatters run faster standalone than in the loop);
    4. otherwise (high launch latency) -> in_graph.
    """
    mode = cfg.dispatch_mode
    if mode not in (None, "auto"):
        check(mode in DISPATCH_MODES,
              f"dispatch_mode must be one of {DISPATCH_MODES} or 'auto'; "
              f"got {mode!r}")
        return mode
    eligible = (cfg.sg and not cfg.hs
                and cfg.mesh_data * cfg.mesh_model == 1)
    if not eligible or row_kernel:
        return "in_graph"
    lat = measured_dispatch_latency_ms()
    mode = ("pipelined_host" if lat < CHUNK_DISPATCH_LATENCY_MS
            else "in_graph")
    log.info("w2v dispatch auto: launch latency %.3fms -> %s", lat, mode)
    return mode


W2V_COMM_MODES = ("fused", "hybrid", "ps", "model_average")


def resolve_w2v_comm(cfg: "Word2VecConfig", V: int, D: int,
                     out_rows: int, mesh=None):
    """Per-table CommPolicy resolution for the five word2vec tables
    (docs/DESIGN.md decision table). Returns ``(mode, policies)`` where
    ``mode`` is the training-loop plane and ``policies`` maps table name
    -> policy string (passed into the table options, so each table's
    ``comm_policy`` attribute reflects the decision).

    * ``None`` -> ("fused", {}): today's fused in-store plane untouched,
      no probe, no resolution cost.
    * ``auto``/``hybrid`` -> per-table: the four embedding/accumulator
      tables are sparse row-granular -> ps (served by the fused in-store
      dispatch); the word-count table is small dense -> whatever the
      measured probe picks (allreduce on every box we measured). Explicit
      ``comm_policy_overrides`` entries win per table.
    * ``ps`` / ``model_average`` -> every table pinned to that plane.
    * ``allreduce`` is rejected with the reason: word2vec's tables are
      sparse row-granular — densifying a [V, D] gradient per step is the
      exact case the decision table exists to prevent. Use auto/hybrid
      (dense quantities go allreduce, embeddings stay ps).
    """
    from multiverso_tpu.parallel import comm_policy as cp

    mode = (cfg.comm_policy or "").strip().lower()
    check(mode in ("", "auto", "hybrid", "ps", "model_average"),
          "word2vec comm_policy must be auto|hybrid|ps|model_average; "
          f"got {cfg.comm_policy!r}"
          + (" (allreduce applies per-TABLE to small dense tables — "
             "word2vec's embedding tables are sparse; use auto/hybrid)"
             if mode == "allreduce" else ""))
    if not mode:
        return "fused", {}
    overrides = dict(cfg.comm_policy_overrides or {})
    names_sparse = ["w2v_input", "w2v_output", "w2v_adagrad_in",
                    "w2v_adagrad_out"]
    shapes = {"w2v_input": (V, D), "w2v_output": (out_rows, D),
              "w2v_adagrad_in": (V, D), "w2v_adagrad_out": (out_rows, D),
              "w2v_wordcount": (1,)}
    policies = {}
    if mode in ("ps", "model_average"):
        want = cp.PS if mode == "ps" else cp.MODEL_AVERAGE
        for name in names_sparse + ["w2v_wordcount"]:
            policies[name] = cp.resolve_comm_policy(
                shapes[name], np.float32, sparse=name in names_sparse,
                explicit=overrides.get(name, want), mesh=mesh, table=name)
        return mode, policies
    # auto/hybrid: the decision table proper.
    for name in names_sparse:
        policies[name] = cp.resolve_comm_policy(
            shapes[name], np.dtype(cfg.param_dtype), sparse=True,
            explicit=overrides.get(name), mesh=mesh, table=name)
    policies["w2v_wordcount"] = cp.resolve_comm_policy(
        (1,), np.int64, sparse=False,
        explicit=overrides.get("w2v_wordcount"), mesh=mesh,
        table="w2v_wordcount")
    return "hybrid", policies


class _DispatchQueue:
    """Depth-N in-flight dispatch window for pipelined_host.

    ``push`` enqueues a per-chunk completion marker (the chunk's loss
    array); once more than ``depth`` markers are in flight the host waits
    on the OLDEST one — so up to ``depth`` launches overlap device compute
    and the wait itself is overlapped by the younger queued chunks. This
    bounds the dispatch queue (no launch storms / unbounded buffer chains
    when launches are slow) without the per-chunk ``block_until_ready``
    round trip that made per-chunk dispatch lose 10x at high launch
    latency."""

    def __init__(self, depth: int):
        from collections import deque
        self._depth = max(int(depth), 1)
        self._fifo = deque()
        # Window-occupancy gauge: how much of the depth-N budget the host
        # actually keeps in flight (a persistently full window means the
        # device is the bottleneck; an empty one, the host).
        self._g_inflight = gauge("w2v.dispatch_inflight")

    def push(self, marker) -> None:
        self._fifo.append(marker)
        while len(self._fifo) > self._depth:
            # The bounded backpressure wait IS the mechanism here: block
            # on the oldest marker only once >depth launches are in
            # flight, overlapped by the younger queued chunks.
            # graftlint: disable=block-until-ready-in-loop
            jax.block_until_ready(self._fifo.popleft())
        self._g_inflight.set(len(self._fifo))

    def drain(self) -> None:
        # One batched wait for everything still in flight — a per-marker
        # wait loop would re-sync serially once per queued chunk.
        jax.block_until_ready(list(self._fifo))
        self._fifo.clear()
        self._g_inflight.set(0)


def build_chunked_pipeline(window: int, negative: int, chunk: int,
                           adagrad: bool):
    """Device pair-gen + HOST-dispatched per-chunk training steps.

    Profiling on v5e (jax 0.4.37) showed the identical sg-ns update running
    ~0.05-0.12ms as a standalone jitted dispatch but 2.2-2.6ms inside
    ``lax.scan`` / ``while_loop``; on jax 0.9 the gap is 71 against 86 ns a
    row written (PERF.md 6, PR 29). Here the block loop moves to the
    host: ``pair_gen`` runs once per block on device (pairing, compaction,
    row-gathered negatives — everything stays in HBM), then the host
    dispatches one jitted ``chunk_step`` per live chunk (async dispatch
    pipelines them; tables are donated through the chain). The live-chunk
    count is ESTIMATED host-side from the expected subsample/window keep
    rates (no device sync — a scalar D2H round trip stalls the dispatch
    queue); a final ``tail_step`` fori-loops from the estimate to
    the true ``n_pairs`` on device, so training is EXACT regardless of the
    estimate (the estimate only balances dispatch count vs tail work).
    """
    raw = raw_sg_ns_step(adagrad)

    @jax.jit
    def pair_gen(neg_table, keep_prob, sents, lengths, key):
        k_keep, k_win, k_neg = jax.random.split(key, 3)
        centers, contexts, pmask = _pair_arrays(sents, lengths, keep_prob,
                                                k_keep, k_win, window)
        centers, contexts, n_pairs, n = _compact_stream(
            centers, contexts, pmask, chunk)
        negatives = _row_gather_negatives(neg_table, k_neg,
                                          (n, chunk, negative))
        return centers, contexts, negatives, n_pairs

    lane = jnp.arange(chunk)

    def _chunk_body(tables, centers2d, contexts2d, negatives2d, n_pairs, i,
                    lr):
        c = jax.lax.dynamic_index_in_dim(centers2d, i, keepdims=False)
        o = jax.lax.dynamic_index_in_dim(contexts2d, i, keepdims=False)
        neg = jax.lax.dynamic_index_in_dim(negatives2d, i, keepdims=False)
        m = ((i * chunk + lane) < n_pairs).astype(jnp.float32)
        return raw(*tables, c, o, neg, m, lr)

    def chunk_step(w_in, w_out, g_in, g_out, centers2d, contexts2d,
                   negatives2d, n_pairs, i, lr):
        return _chunk_body((w_in, w_out, g_in, g_out), centers2d,
                           contexts2d, negatives2d, n_pairs, i, lr)

    def tail_step(w_in, w_out, g_in, g_out, centers2d, contexts2d,
                  negatives2d, n_pairs, lr, start):
        # ``start`` is a traced operand (NOT static): the estimate varies
        # per block and a static arg would recompile per distinct value;
        # the loop lowers to while_loop either way.
        n_live = (n_pairs + chunk - 1) // chunk

        def body(i, carry):
            *tables, loss = carry
            out = _chunk_body(tuple(tables), centers2d, contexts2d,
                              negatives2d, n_pairs, i, lr)
            return (*out[:4], loss + out[4])

        return jax.lax.fori_loop(
            start, jnp.maximum(n_live, start), body,
            (w_in, w_out, g_in, g_out, jnp.float32(0.0)))

    return (pair_gen, _PlacedStep(chunk_step, adagrad),
            _PlacedStep(tail_step, adagrad))


def expected_live_chunks(keep_prob: np.ndarray, mat: np.ndarray,
                         lens: np.ndarray, window: int, chunk: int,
                         n_static: int) -> int:
    """Host-side estimate of ceil(n_pairs/chunk) — E[pairs] from the keep
    probabilities of the block's actual words plus a dispersion margin
    (each word's keep draw influences up to 2*window pairs). Dispatching a
    few masked extra chunks costs ~0.1ms each; undershoot is caught by the
    exact device tail."""
    kp = keep_prob[mat]
    kp = kp * (np.arange(mat.shape[1])[None, :] < lens[:, None])
    e_pairs = 0.0
    for d in range(1, window + 1):
        e_pairs += (2.0 * (window - d + 1) / window *
                    float(np.sum(kp[:, :-d] * kp[:, d:])))
    margin = 4.0 * np.sqrt(max(2 * window * e_pairs, 1.0)) + chunk
    return min(int(np.ceil((e_pairs + margin) / chunk)), n_static)


def build_scan_step(raw_step, adagrad: bool):
    """Wrap a raw step (``raw_*_step(adagrad)``) into a jitted ``lax.scan``
    over a GROUP of batches, on the row plane of the tables it is called
    with (:class:`_PlacedStep`).

    The batch args arrive stacked with a leading [N] group axis; one dispatch
    trains N minibatches. This is the TPU-idiomatic answer to the
    reference's per-request dispatch: host round trips amortize N-fold, and
    the embedding tables stay resident in HBM for the whole group
    (SURVEY.md §7 hard part (e): fuse Get-update-Add round trips into single
    compiled steps).
    """
    def scan_step(w_in, w_out, g_in, g_out, *batch_args_and_lr):
        *batch_args, lr = batch_args_and_lr

        def body(carry, xs):
            out = raw_step(*carry, *xs, lr)
            return out[:4], out[4]

        carry, losses = jax.lax.scan(
            body, (w_in, w_out, g_in, g_out), tuple(batch_args))
        return (*carry, losses.sum())

    return _PlacedStep(scan_step, adagrad)


class Word2Vec:
    """The word2vec trainer over its five tables (module docstring).

    A word's row. The tables are ``[V, D]`` arrays addressed by ROW, and
    the device programs take row ids. Off a mesh a word's row is its
    dictionary id. On a dp x tp mesh whose ``model`` axis has ``n > 1``
    shards the tables are ``P("model", None)``, n row RANGES, and
    dictionary ids are frequency ranks: by range, shard 0 would own nearly
    every id a chunk touches and the step would go at its pace (PERF.md 6,
    PR 33, PR 36). So the MODEL deals its words round-robin into the
    ranges, :meth:`rows_of`: ranks 0, n, 2n, .. fall in shard 0, ranks 1,
    n + 1, .. in shard 1, and every shard owns 1/n of every part of the
    Zipf curve. It is a relabelling at the model's edge, decided by the
    number of row shards alone (no option): what the block program takes
    BY word or holding words is relabelled once at construction (the
    keep-probabilities, the negative table's values, the rows of the
    Huffman tables handed to the builder), each block's sentence matrix as
    it is built; the program then draws the same pairs and negatives OF
    THE SAME WORDS and updates their rows. What leaves the model by word
    (:meth:`embeddings`, :meth:`save`, the queries on them) comes back
    through ``rows_of``; the tables' own accessors (``get_rows``, a
    checkpoint) stay by row, so a table-level checkpoint of a mesh-trained
    model belongs to its ``mesh_model`` (docs/DURABILITY.md).
    ``train()``'s ``stats["row_layout"]`` names the layout."""

    def __init__(self, cfg: Word2VecConfig, dictionary: Dictionary):
        with span("w2v.build"):
            self._build(cfg, dictionary)

    def _build(self, cfg: Word2VecConfig, dictionary: Dictionary) -> None:
        check(len(dictionary) >= 2, "vocabulary too small")
        self.cfg = cfg
        self.dict = dictionary
        V, D = len(dictionary), cfg.embedding_size

        # The five reference tables (communicator.cpp:17-32): input embed,
        # output embed, two adagrad accumulators, word-count KV. Embeddings
        # may store bf16 (param_dtype); accumulators stay f32.
        pdtype = np.dtype(cfg.param_dtype)
        out_rows = (V - 1) if cfg.hs else V   # inner nodes for HS
        # Per-table CommPolicy resolution BEFORE creation, so each table
        # carries its resolved policy attribute (docs/DESIGN.md).
        from multiverso_tpu.core.zoo import Zoo as _Zoo
        self.comm_mode, comm = resolve_w2v_comm(
            cfg, V, D, max(out_rows, 1), mesh=_Zoo.get().mesh)
        self.comm_policies = comm
        self.input_table = mv.create_table(MatrixTableOption(
            V, D, dtype=pdtype, random_init=True, init_low=-0.5 / D,
            init_high=0.5 / D, seed=cfg.seed, name="w2v_input",
            updater="default", comm_policy=comm.get("w2v_input")))
        self.output_table = mv.create_table(MatrixTableOption(
            max(out_rows, 1), D, dtype=pdtype, name="w2v_output",
            updater="default", comm_policy=comm.get("w2v_output")))
        self.adagrad_in = mv.create_table(MatrixTableOption(
            V, D, name="w2v_adagrad_in", updater="default",
            comm_policy=comm.get("w2v_adagrad_in")))
        self.adagrad_out = mv.create_table(MatrixTableOption(
            max(out_rows, 1), D, name="w2v_adagrad_out",
            updater="default", comm_policy=comm.get("w2v_adagrad_out")))
        self.wordcount_table = mv.create_table(
            KVTableOption(value_dtype=np.int64, name="w2v_wordcount",
                          comm_policy=comm.get("w2v_wordcount")))
        # Hybrid mode: one in-graph collective per block merges the dense
        # quantities (word counts — the lr schedule's cross-worker sync)
        # while the sparse tables stay on the fused PS plane. Built once;
        # dispatched per block; never host-synced inside the loop.
        self._dense_sync = None
        self._comm_synced = None
        if (self.comm_mode == "hybrid" and
                comm.get("w2v_wordcount") == "allreduce"):
            from multiverso_tpu.parallel import comm_policy as _cp
            self._dense_sync = _cp.build_dense_sync(_Zoo.get().mesh)

        self.huffman = (HuffmanEncoder(dictionary.counts,
                                       cfg.max_code_length)
                        if cfg.hs else None)
        self.generator = BatchGenerator(
            dictionary, batch_size=cfg.batch_size, window=cfg.window,
            negative=cfg.negative, sample=cfg.sample, sg=cfg.sg,
            seed=cfg.seed)

        adagrad = cfg.optimizer == "adagrad"
        self._adagrad = adagrad
        check(cfg.mesh_data * cfg.mesh_model == 1 or cfg.device_pipeline,
              "mesh_data/mesh_model need device_pipeline=True (the host "
              "batch path has no sharded step)")
        # The row ranges the dp x tp block step shards the tables into
        # (rows_of). The pure client plane trains by dictionary id through
        # the table API and never runs that step: by range.
        self._row_shards = 1 if self.comm_mode == "ps" else cfg.mesh_model
        self._scan_step = build_scan_step(
            raw_step_factory(cfg.sg, cfg.hs)(adagrad), adagrad)

        if cfg.device_pipeline:
            sampler = self.generator.sampler
            # Shuffled so 128-wide rows are iid draws (row-gather sampling).
            perm = np.random.default_rng(cfg.seed + 17).permutation(
                len(sampler.table))
            # The program draws the same POSITIONS of the table, so the
            # same words; its values and what it reads by word are rows.
            self._neg_table = jnp.asarray(self.rows_of(sampler.table[perm]))
            keep_host = self._by_row(Sampler.keep_probability(
                dictionary.counts, cfg.sample).astype(np.float32))
            self._keep_prob_host = keep_host
            self._keep_prob = jnp.asarray(keep_host)
            huffman = self.huffman
            if huffman is not None and self._row_shards > 1:
                # The paths' ROWS follow their words; their values index
                # inner nodes, another id space, and stay.
                import copy
                huffman = copy.copy(huffman)
                for name in ("points", "codes", "lengths"):
                    setattr(huffman, name,
                            self._by_row(getattr(huffman, name)))
            self._block_step = build_device_block_step(
                cfg.window, cfg.negative, cfg.batch_size, adagrad,
                compact=cfg.compact_pairs, sg=cfg.sg, hs=cfg.hs,
                huffman=huffman)
            self._dispatch_mode = resolve_dispatch_mode(
                cfg, self._block_step.row_kernel([t.store.data for t in (
                    self.input_table, self.output_table, self.adagrad_in,
                    self.adagrad_out)]) is not None)
            if self._dispatch_mode != "in_graph":
                check(cfg.sg and not cfg.hs,
                      f"dispatch_mode={self._dispatch_mode} (per-chunk "
                      "host dispatch) is the sg-ns perf "
                      "experiment path; the fused device block step "
                      "covers all four variants")
                (self._pair_gen, self._chunk_step,
                 self._tail_step) = build_chunked_pipeline(
                    cfg.window, cfg.negative, cfg.batch_size, adagrad)
            self._sharded_mesh = None
            if cfg.mesh_data * cfg.mesh_model > 1:
                check(self._dispatch_mode == "in_graph",
                      "pipelined_host and a dp x tp mesh are mutually "
                      "exclusive: per-chunk host dispatch would "
                      "serialize the sharded step; pick one")
                from jax.sharding import Mesh
                n = cfg.mesh_data * cfg.mesh_model
                # The dp x tp mesh regroups the SAME devices the tables
                # already live on (the runtime's server mesh), never a
                # second view of jax.devices().
                devs = list(_Zoo.get().mesh.devices.flat)
                check(len(devs) >= n,
                      f"mesh {cfg.mesh_data}x{cfg.mesh_model} needs {n} "
                      f"devices, have {len(devs)}")
                check(cfg.block_sentences % cfg.mesh_data == 0,
                      "block_sentences must divide over mesh_data")
                self._sharded_mesh = Mesh(
                    np.asarray(devs[:n]).reshape(cfg.mesh_data,
                                                 cfg.mesh_model),
                    ("data", "model"))
                self._block_step = build_sharded_block_step(
                    self._sharded_mesh, cfg.window, cfg.negative,
                    cfg.batch_size, adagrad, compact=cfg.compact_pairs,
                    sg=cfg.sg, hs=cfg.hs, huffman=huffman)
            self._key = jax.random.PRNGKey(cfg.seed)

        self.total_words = dictionary.total_count * max(cfg.epochs, 1)
        self.trained_words = 0
        self.words_per_sec = 0.0
        scale = cfg.delta_scale
        if scale is None:
            scale = 1.0
        self._push_scale = scale

    # -- a word's row (class docstring) ------------------------------------
    def rows_of(self, word_ids) -> np.ndarray:
        """Table rows of dictionary ids. Off a mesh, and on one whose
        ``model`` axis is not divided, the ids themselves. Over ``n`` row
        shards word ``w`` is the ``w // n``-th of the words dealt to shard
        ``w % n``, whose rows follow the earlier shards': at ``V % n == 0``
        row ``(w % n) * (V // n) + w // n``. A bijection on ``range(V)``
        for any ``V`` (the first ``V % n`` shards take one word more);
        the pad id 0 stays 0."""
        ids = np.asarray(word_ids)
        n = self._row_shards
        if n == 1:
            return ids
        per, longer = divmod(len(self.dict), n)
        shard = ids % n
        return shard * per + np.minimum(shard, longer) + ids // n

    def _by_row(self, by_word: np.ndarray) -> np.ndarray:
        """An array indexed by dictionary id, laid out by row:
        ``out[rows_of(w)] = by_word[w]``."""
        if self._row_shards == 1:
            return by_word
        out = np.empty_like(by_word)
        out[self.rows_of(np.arange(len(by_word)))] = by_word
        return out

    @property
    def row_layout(self) -> str:
        """``"range"`` (a word's row is its id) or ``"interleaved/<n>"``."""
        n = self._row_shards
        return "range" if n == 1 else f"interleaved/{n}"

    # -- comm-policy hooks (docs/DESIGN.md "CommPolicy") -------------------
    def _hybrid_sync(self, words: int) -> None:
        """Hybrid mode's dense-plane merge: one in-graph collective per
        block carries the block's word count, accumulated DEVICE-SIDE
        into ``_comm_synced`` — the global trained-word count the lr
        schedule needs agreed across workers, read back exactly once per
        train() (``stats["synced_words"]``; a per-block read would
        re-serialize the loop on a host sync, the exact tax the plane
        exists to avoid). In a one-process world the psum (over
        identical replicated contributions, normalized) is an
        identity-preserving merge; data-parallel hybrids feed real
        per-worker partials through the same function."""
        if self._dense_sync is None:
            return
        from multiverso_tpu.parallel import comm_policy as cp
        synced = self._dense_sync(np.asarray([words], np.float32))
        self._comm_synced = (synced if self._comm_synced is None
                             else self._comm_synced + synced)
        cp.record(cp.ALLREDUCE, 4)

    def _synced_words(self) -> Optional[float]:
        """One end-of-train host read of the device-side merged word
        count (None outside hybrid mode)."""
        if self._comm_synced is None:
            return None
        return float(np.asarray(self._comm_synced)[0])

    def _model_average_epoch(self) -> None:
        """The reference "ma" epoch merge: average every table replica
        across processes over the collective plane and publish the
        result back through the PS surface (identity in one process —
        bitwise — so fused and model_average runs agree exactly there;
        multi-process runs trade one epoch of staleness for zero
        per-block pushes)."""
        from multiverso_tpu.parallel import comm_policy as cp
        tables = [self.input_table, self.output_table]
        if self._adagrad:
            tables += [self.adagrad_in, self.adagrad_out]
        merged = cp.model_average_arrays(
            [np.asarray(t.store.read()) for t in tables])
        for t, m in zip(tables, merged):
            t.store.write_dense(m)

    # -- lr schedule (ref distributed_wordembedding.cpp:92-134) ------------
    def _current_lr(self) -> float:
        if self._adagrad:
            return self.cfg.learning_rate
        frac = min(self.trained_words / max(self.total_words, 1), 1.0)
        return max(self.cfg.learning_rate * (1.0 - frac),
                   self.cfg.learning_rate * 1e-4)

    # -- batch -> step-arg tuple (order matches the raw step signatures) ---
    def _batch_args(self, batch) -> Tuple[np.ndarray, ...]:
        if isinstance(batch, SkipGramBatch):
            if self.cfg.hs:
                points = self.huffman.points[batch.contexts]
                codes = self.huffman.codes[batch.contexts]
                lmask = ((np.arange(self.cfg.max_code_length)[None, :] <
                          self.huffman.lengths[batch.contexts][:, None])
                         .astype(np.float32) * batch.mask[:, None])
                return (batch.centers, points, codes, lmask)
            return (batch.centers, batch.contexts, batch.negatives,
                    batch.mask)
        if self.cfg.hs:
            points = self.huffman.points[batch.centers]
            codes = self.huffman.codes[batch.centers]
            lmask = ((np.arange(self.cfg.max_code_length)[None, :] <
                      self.huffman.lengths[batch.centers][:, None])
                     .astype(np.float32) * batch.mask[:, None])
            return (batch.centers, batch.contexts, batch.context_mask,
                    points, codes, lmask)
        return (batch.centers, batch.contexts, batch.context_mask,
                batch.negatives, batch.mask)

    # -- group producer: stacked [N, ...] scan inputs ----------------------
    def _group_iter(self, sentences):
        """Yields (stacked_args, words, pairs) — one jitted dispatch each.
        Runs on the prefetch thread in pipeline mode, so host-side batch
        assembly overlaps device execution (the reference's omp prefetch
        pipeline, distributed_wordembedding.cpp:203-212)."""
        N = max(1, self.cfg.scan_group)
        pending_args: List[Tuple[np.ndarray, ...]] = []
        pending_words = 0
        pending_pairs = 0

        def emit():
            nonlocal pending_args, pending_words, pending_pairs
            args = pending_args
            if len(args) < N:   # pad with zero (masked-out) batches
                zero = tuple(np.zeros_like(a) for a in args[0])
                args = args + [zero] * (N - len(args))
            stacked = tuple(np.stack([a[i] for a in args])
                            for i in range(len(args[0])))
            out = (stacked, pending_words, pending_pairs)
            pending_args, pending_words, pending_pairs = [], 0, 0
            return out

        for block in BlockStream(sentences, self.cfg.block_words,
                                 prefetch=False):
            pending_words += sum(len(s) for s in block)
            for batch in self.generator.batches(block):
                pending_args.append(self._batch_args(batch))
                pending_pairs += batch.n_words
                if len(pending_args) == N:
                    yield emit()
        if pending_args:
            yield emit()

    def _run_group(self, stacked_args) -> jax.Array:
        st_in = self.input_table.store
        st_out = self.output_table.store
        st_gin = self.adagrad_in.store
        st_gout = self.adagrad_out.store
        lr = np.float32(self._current_lr() * self._push_scale)
        (st_in.data, st_out.data, st_gin.data, st_gout.data,
         loss) = self._scan_step(st_in.data, st_out.data, st_gin.data,
                                 st_gout.data, *stacked_args, lr)
        return loss

    # -- training loop (ref TrainNeuralNetwork :147-237) -------------------
    def train(self, sentences: Optional[Iterable[Sequence[int]]] = None,
              corpus_path: Optional[str] = None,
              epochs: Optional[int] = None) -> dict:
        stats = self._train(sentences, corpus_path, epochs)
        if not startup.ready:
            startup.mark_ready(("w2v.device_block", "w2v.group"))
        return stats

    def _train(self, sentences, corpus_path, epochs) -> dict:
        from multiverso_tpu.utils.async_buffer import ASyncBuffer

        epochs = epochs if epochs is not None else self.cfg.epochs
        check(sentences is not None or corpus_path is not None,
              "need sentences or corpus_path")
        if self.comm_mode == "ps":
            # Pure client plane: pull-train-push per block through the
            # table API (commplane.PSPlaneTrainer) — the comparison
            # baseline the hybrid mode exists to beat.
            from multiverso_tpu.models.word2vec.commplane import \
                PSPlaneTrainer
            return {**PSPlaneTrainer(self).train(sentences, corpus_path,
                                                 epochs),
                    "row_layout": self.row_layout}
        if self.cfg.device_pipeline:
            return self._train_device(sentences, corpus_path, epochs)
        t0 = time.perf_counter()
        losses: List[jax.Array] = []
        total_pairs = 0
        for _ in range(epochs):
            if corpus_path is not None:
                sents: Iterable = (self.dict.encode(s)
                                   for s in read_corpus(corpus_path))
            else:
                sents = iter(sentences)
            groups = self._group_iter(sents)
            if self.cfg.pipeline:
                it = groups
                buf: ASyncBuffer = ASyncBuffer(lambda: next(it, None))
                def drain():
                    while True:
                        item = buf.get()
                        if item is None:
                            return
                        yield item
                source: Iterable = drain()
            else:
                buf = None
                source = groups
            try:
                for stacked, words, pairs in source:
                    with span("w2v.group"), monitor("W2V_GROUP"):
                        losses.append(self._run_group(stacked))
                    total_pairs += pairs
                    self.trained_words += words
                    if words:
                        # word-count table drives the lr schedule across
                        # workers (ref distributed_wordembedding.cpp:92-134)
                        self.wordcount_table.add([_WORDCOUNT_KEY], [words])
                        self._hybrid_sync(words)
            finally:
                if buf is not None:
                    buf.close()
            if self.comm_mode == "model_average":
                self._model_average_epoch()
        jax.block_until_ready(self.input_table.store.data)
        elapsed = time.perf_counter() - t0
        self.words_per_sec = self.trained_words / max(elapsed, 1e-9)
        mean_loss = (float(np.mean([float(l) for l in losses[-50:]]))
                     if losses else 0.0)
        log.info("word2vec: %d words, %d pairs, %.0f words/sec, loss=%.4f",
                 self.trained_words, total_pairs, self.words_per_sec,
                 mean_loss)
        return {"words": self.trained_words, "pairs": total_pairs,
                "words_per_sec": self.words_per_sec, "loss": mean_loss,
                "seconds": elapsed, "comm_mode": self.comm_mode,
                "synced_words": self._synced_words(),
                "row_layout": self.row_layout}

    # -- device-pipeline training loop -------------------------------------
    def _sentence_blocks(self, sentences):
        """[S, L] int32 sentence matrix + lengths per block; long sentences
        split at the pad length, short blocks zero-padded."""
        S, L = self.cfg.block_sentences, self.cfg.pad_sentence_length
        mat = np.zeros((S, L), dtype=np.int32)
        lens = np.zeros(S, dtype=np.int32)
        row = 0
        words = 0
        for sent in sentences:
            sent = np.asarray(sent, dtype=np.int32)
            for i in range(0, max(len(sent), 1), L):
                piece = sent[i:i + L]
                if len(piece) == 0:
                    continue
                mat[row, :len(piece)] = piece
                lens[row] = len(piece)
                words += len(piece)
                row += 1
                if row == S:
                    yield mat, lens, words
                    mat = np.zeros((S, L), dtype=np.int32)
                    lens = np.zeros(S, dtype=np.int32)
                    row, words = 0, 0
        if row:
            yield mat, lens, words

    def _train_device(self, sentences, corpus_path, epochs) -> dict:
        from multiverso_tpu.utils.async_buffer import ASyncBuffer

        t0 = time.perf_counter()
        losses: List[jax.Array] = []
        pair_counts: List[jax.Array] = []
        st_in = self.input_table.store
        st_out = self.output_table.store
        st_gin = self.adagrad_in.store
        st_gout = self.adagrad_out.store
        sharded = getattr(self, "_sharded_mesh", None) is not None
        interleaved = self._row_shards > 1
        if sharded:
            # Re-lay the tables onto the dp x tp mesh once; the step's
            # donated outputs keep that sharding for every later block.
            from jax.sharding import NamedSharding, PartitionSpec as P
            tsh = NamedSharding(self._sharded_mesh, P("model", None))
            for st in (st_in, st_out, st_gin, st_gout):
                st.data = jax.device_put(st.data, tsh)
            # Replicated operands get laid out once too — otherwise every
            # block dispatch reshards the ~4MB negative table to the mesh.
            repl = NamedSharding(self._sharded_mesh, P())
            self._neg_table = jax.device_put(self._neg_table, repl)
            self._keep_prob = jax.device_put(self._keep_prob, repl)
        for _ in range(epochs):
            if corpus_path is not None:
                sents: Iterable = (self.dict.encode(s)
                                   for s in read_corpus(corpus_path))
            else:
                sents = iter(sentences)
            blocks = self._sentence_blocks(sents)
            if interleaved:
                # The program takes ROW ids (on the prefetch thread in
                # pipeline mode: one numpy map a block).
                blocks = ((self.rows_of(mat), lens, words)
                          for mat, lens, words in blocks)
            if self.cfg.pipeline:
                it = blocks
                buf: ASyncBuffer = ASyncBuffer(lambda: next(it, None))
                def drain():
                    while True:
                        item = buf.get()
                        if item is None:
                            return
                        yield item
                source: Iterable = drain()
            else:
                buf = None
                source = blocks
            mode = self._dispatch_mode if not sharded else "in_graph"
            W, chunk = self.cfg.window, self.cfg.batch_size
            inflight = _DispatchQueue(self.cfg.dispatch_depth)
            # On a multi-device CPU mesh every launch is finished before
            # the next (``ServerStore._finish``, decided by the store):
            # several such programs in flight starve XLA:CPU's rendezvous
            # (core/table.py), and pipelined_host would keep
            # ``dispatch_depth`` of them there. Elsewhere: the identity.
            finish = st_in._finish
            try:
                for mat, lens, words in source:
                    # The one timer of a block's host-side dispatch; the
                    # mode rides as its attribute. (The block's DEVICE
                    # time is the jit_block_step program's, in the trace.)
                    with span("w2v.device_block", mode=mode):
                        self._key, sub = jax.random.split(self._key)
                        lr = np.float32(self._current_lr() *
                                        self._push_scale)
                        if mode == "pipelined_host":
                            (centers2d, contexts2d, negs,
                             n_pairs) = self._pair_gen(
                                self._neg_table, self._keep_prob, mat,
                                lens, sub)
                            n_static = centers2d.shape[0]
                            est = expected_live_chunks(
                                self._keep_prob_host, mat, lens, W, chunk,
                                n_static)
                            lr_dev = jnp.asarray(lr)
                            tables = (st_in.data, st_out.data, st_gin.data,
                                      st_gout.data)
                            block_loss = []
                            for i in range(est):
                                out = finish(self._chunk_step(
                                    *tables, centers2d, contexts2d, negs,
                                    n_pairs, np.int32(i), lr_dev))
                                tables = out[:4]
                                block_loss.append(out[4])
                                # Depth-N backpressure: waits (overlapped)
                                # only once >depth chunks are in flight.
                                inflight.push(out[4])
                            out = finish(self._tail_step(
                                *tables, centers2d, contexts2d, negs,
                                n_pairs, lr_dev, np.int32(est)))
                            (st_in.data, st_out.data, st_gin.data,
                             st_gout.data) = out[:4]
                            block_loss.append(out[4])
                            inflight.push(out[4])
                            losses.append(jnp.sum(jnp.stack(block_loss)))
                            pair_counts.append(n_pairs)
                        else:
                            (st_in.data, st_out.data, st_gin.data,
                             st_gout.data, loss, pairs) = finish(
                                self._block_step(
                                    st_in.data, st_out.data, st_gin.data,
                                    st_gout.data, self._neg_table,
                                    self._keep_prob, mat, lens, sub, lr))
                            losses.append(loss)
                            pair_counts.append(pairs)
                    if interleaved:
                        counter("w2v.rows.layout.interleaved").inc()
                    self.trained_words += words
                    self.wordcount_table.add([_WORDCOUNT_KEY], [words])
                    self._hybrid_sync(words)
            finally:
                inflight.drain()
                if buf is not None:
                    buf.close()
            if self.comm_mode == "model_average":
                self._model_average_epoch()
        jax.block_until_ready(st_in.data)
        elapsed = time.perf_counter() - t0
        self.words_per_sec = self.trained_words / max(elapsed, 1e-9)
        total_pairs = int(sum(int(p) for p in pair_counts))
        mean_loss = (float(np.mean([float(l) for l in losses[-50:]]))
                     if losses else 0.0)
        log.info("word2vec[device]: %d words, %d pairs, %.0f words/sec, "
                 "loss=%.4f", self.trained_words, total_pairs,
                 self.words_per_sec, mean_loss)
        return {"words": self.trained_words, "pairs": total_pairs,
                "words_per_sec": self.words_per_sec, "loss": mean_loss,
                "seconds": elapsed, "comm_mode": self.comm_mode,
                "dispatch_mode": ("in_graph" if sharded
                                  else self._dispatch_mode),
                "synced_words": self._synced_words(),
                "row_layout": self.row_layout}

    # -- embeddings out ----------------------------------------------------
    def embeddings(self) -> np.ndarray:
        """The input embeddings ``[V, D]`` in WORD order, whatever the
        rows' layout."""
        table = self.input_table.get()
        if self._row_shards == 1:
            return table
        return table[self.rows_of(np.arange(len(self.dict)))]

    def save(self, path: str, batch_rows: int = 100_000) -> None:
        """Rank-0 batched text export (ref :263-306 saves in 100K-row
        batches). Goes through the URI stream layer, so ``gs://`` targets
        work exactly as they do for checkpoints (plain paths are
        ``file://``)."""
        if not mv.is_master_worker():
            return
        from multiverso_tpu.utils.stream import open_stream
        with open_stream(path, "w") as f:
            f.write(f"{len(self.dict)} {self.cfg.embedding_size}\n"
                    .encode())
            for start in range(0, len(self.dict), batch_rows):
                rows = list(range(start,
                                  min(start + batch_rows, len(self.dict))))
                # astype: bf16 scalars don't support the 'f' format code
                emb = self.input_table.get_rows(
                    self.rows_of(rows)).astype(np.float32)
                chunk = []
                for r, vec in zip(rows, emb):
                    vec_s = " ".join(f"{x:.6f}" for x in vec)
                    chunk.append(f"{self.dict.words[r]} {vec_s}\n")
                f.write("".join(chunk).encode())

    def analogy(self, a: str, b: str, c: str, topk: int = 5
                ) -> List[Tuple[str, float]]:
        """a : b :: c : ?  via vector arithmetic (b - a + c), inputs
        excluded — the standard word2vec evaluation query."""
        ids = [self.dict.word2id.get(w) for w in (a, b, c)]
        if any(i is None for i in ids):
            return []
        emb = self.embeddings().astype(np.float32)
        emb = emb / (np.linalg.norm(emb, axis=1, keepdims=True) + 1e-12)
        query = emb[ids[1]] - emb[ids[0]] + emb[ids[2]]
        query = query / (np.linalg.norm(query) + 1e-12)
        sims = emb @ query
        out: List[Tuple[str, float]] = []
        for i in np.argsort(-sims):
            if i in ids:
                continue
            out.append((self.dict.words[i], float(sims[i])))
            if len(out) == topk:
                break
        return out

    def most_similar(self, word: str, topk: int = 5) -> List[Tuple[str, float]]:
        wid = self.dict.word2id.get(word)
        if wid is None:
            return []
        emb = self.embeddings()
        norms = np.linalg.norm(emb, axis=1) + 1e-12
        sims = emb @ emb[wid] / (norms * norms[wid])
        order = np.argsort(-sims)
        out = []
        for i in order:
            if i != wid:
                out.append((self.dict.words[i], float(sims[i])))
            if len(out) == topk:
                break
        return out
