"""Pallas TPU kernels for the table hot ops: the row UPDATES that read,
change and write back whole rows in one launch.

These are the framework's per-row data-plane primitives — the role the
OpenMP updater loop plays in the reference (``src/updater/updater.cpp:22-29``)
— written as Mosaic kernels so row traffic streams HBM->VMEM via manual
per-row DMA with scalar-prefetched indices.

Mosaic constrains mapped block shapes to (8k, 128k) tiles, so arbitrary
single rows cannot be block-mapped; instead the table stays unmapped
(``pl.ANY`` -> HBM) and each grid step DMAs a group of rows addressed by
the prefetched id array, all in flight together, in place via
``input_output_aliases`` (the table buffer is donated). Three kernels:

* ``fused_stateful_rows`` — a store's stateful row update (momentum,
  AdaGrad, FTRL), chosen by ``core/table.fused_rows_selected``;
* ``adagrad_fold_rows`` — word2vec's AdaGrad row update over sorted ids,
  duplicates folded inside the kernel;
* ``add_unique_rows`` — the LMs' expert layer's accumulators, rows of any
  multiple of 128 columns.

Row READS and the stateless updaters' scatter-add are XLA's: the DMA gather
and the sorted-run scatter-add that lived here until PR 43 lost to it on the
chip 5x and 4x; the tiled table sweep won at one small table (ROADMAP A10).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# ---------------------------------------------------------------------------
# fused stateful gather-update-scatter (ROADMAP perf #2 / ISSUE 12)
# ---------------------------------------------------------------------------
# The stateful sparse hot path (momentum/adagrad/ftrl) reads touched rows
# of the table AND every updater-state leaf, applies the updater math, and
# writes both back. As XLA ops that is a chain of gathers, elementwise
# math, and scatters over full-size HBM temporaries; here it is ONE grid
# kernel: each grid step DMAs a sublane-tile group of data+state rows
# (addresses from the scalar-prefetched id array), runs the updater's
# shared ``rows_math`` on the VMEM row blocks, and DMAs both back, with
# every buffer donated via ``input_output_aliases``.
#
# Caller contract (core/table.py builds this inside the store's jitted
# ``pallas_rows_update``): ids come from ``combine_duplicate_rows`` — every
# live id UNIQUE and ascending in a prefix, its run total beside it, and
# every other lane a sentinel: any id ``>= num_rows`` (the fold's are each
# there once; the padding below repeats ``num_rows``). Sentinel
# lanes clamp their load address (matching the XLA path's ``mode="clip"``
# gathers) and skip write-back entirely (the XLA ``mode="drop"``
# scatters), so no ordering hazards exist between lanes or grid steps and
# the grid needs no run folding. Bitwise parity with the
# XLA path is STRUCTURAL: both planes execute the same ``rows_math``
# function on identical row blocks.


# Rows a grid step of the fused kernel: more rows in flight a step hide
# more of a row DMA's latency (26 tables of dlrm_train's shape: 4.1 ms at
# 8 rows, 3.6 at 16, 3.4 at 32 and at 64; PERF.md 6, PR 29). A multiple of
# the sublane tile; at the one width Mosaic compiles (128 columns) a
# step's blocks are 64 KB.
_FUSED_GROUP_ROWS = 32


def _make_fused_kernel(group: int, state_keys, per_worker, rows_math,
                       row_dtype):
    n_state = len(state_keys)
    n_io = 1 + n_state          # table + state leaves (aliased in/out)

    def _kernel(ids_ref, meta_ref, opts_ref, delta_ref, *refs):
        # refs: [aliased inputs]*n_io, [outputs]*n_io, drows, srows*, sems
        outs = refs[n_io:2 * n_io]
        table_ref, st_refs = outs[0], outs[1:]
        drows = refs[2 * n_io]
        srows = refs[2 * n_io + 1: 2 * n_io + 1 + n_state]
        sems = refs[2 * n_io + 1 + n_state]
        g = pl.program_id(0)
        base = g * group
        wid = meta_ref[0]
        num_rows = meta_ref[1]

        def _row_copies(k, load):
            """Lane k's row DMAs, data row + each state leaf's row: into
            VMEM (``load``; a sentinel id clamped like mode='clip') or
            back to HBM."""
            rid = ids_ref[base + k]
            if load:
                rid = jnp.minimum(rid, num_rows - 1)
            ends = [(table_ref.at[rid], drows.at[k], sems.at[0, k])]
            for j in range(n_state):
                hbm = (st_refs[j].at[wid, rid] if per_worker[j]
                       else st_refs[j].at[rid])
                ends.append((hbm, srows[j].at[k], sems.at[1 + j, k]))
            return [pltpu.make_async_copy(*((h, v) if load else (v, h)), sem)
                    for h, v, sem in ends]

        def _lanes(act, load):
            """``act`` (start | wait) every lane's row DMAs; on the way
            back to HBM the live lanes' only (sentinel = dropped duplicate
            run position or padding). A loop, not an unrolled body (which
            runs a fifth faster): the kernel stays small, and so do the
            seconds Mosaic takes over it for EACH table of a group (26
            tables: 3 s against 17, PERF.md 6, PR 29), which every
            start-up pays."""
            def body(k, carry):
                def go():
                    for c in _row_copies(k, load):
                        getattr(c, act)()
                if load:
                    go()
                else:
                    pl.when(ids_ref[base + k] < num_rows)(go)
                return carry
            jax.lax.fori_loop(0, group, body, 0)

        # The ids ascend, so a group whose FIRST id is a sentinel holds
        # nothing else: the whole tail behind the live prefix costs a grid
        # step each and no DMA.
        @pl.when(ids_ref[base] < num_rows)
        def _():
            _lanes("start", True)
            _lanes("wait", True)

            opt = (wid, opts_ref[0], opts_ref[1], opts_ref[2], opts_ref[3],
                   opts_ref[4])
            st_rows = {key: srows[j][:] for j, key in enumerate(state_keys)}
            # exact_elementwise: identical strict-IEEE rounding as the XLA
            # plane on CPU interpret runs (pass-through on real chips).
            # wid >= 0 is the runtime-true guard it needs.
            from multiverso_tpu.core.updater import exact_elementwise
            new_d, new_st = exact_elementwise(rows_math)(
                wid >= 0, drows[:], st_rows, delta_ref[:], opt)
            drows[:] = new_d.astype(row_dtype)
            for j, key in enumerate(state_keys):
                srows[j][:] = new_st[key]

            # Ids are unique, so lanes never collide: every write is in
            # flight before the first is awaited.
            _lanes("start", False)
            _lanes("wait", False)
    return _kernel


def fused_stateful_rows(table: jax.Array, state: dict, ids: jax.Array,
                        deltas: jax.Array, opt, updater,
                        interpret: bool = False):
    """One donated gather-update-scatter dispatch for a stateful updater.

    ``ids``/``deltas`` must already be duplicate-combined
    (:func:`multiverso_tpu.core.updater.combine_duplicate_rows`): unique
    ASCENDING ids, duplicates folded, every dropped lane an id
    ``>= table.shape[0]`` behind the live ones.
    Returns ``(new_table, new_state)`` with every buffer aliased in place.
    Trace this inside a donating jit (the store's ``_row_update``).
    """
    num_rows, d = table.shape
    state_keys = sorted(state)
    if not state_keys:
        raise ValueError("fused_stateful_rows needs at least one state "
                         "leaf; stateless updaters take XLA's scatter-add")
    group = _FUSED_GROUP_ROWS
    per_worker = [k in updater.per_worker_state for k in state_keys]
    n = ids.shape[0]
    if n == 0:
        return table, dict(state)
    # Pad with the SENTINEL id (num_rows), not a repeated real id: these
    # are set-semantics updates, so a pad lane aimed at a real row would
    # recompute that row from the pre-update state and clobber the real
    # lane's write.
    pad = (-n) % group
    if pad:
        ids = jnp.concatenate(
            [ids, jnp.full((pad,), num_rows, ids.dtype)])
        deltas = jnp.concatenate(
            [deltas, jnp.zeros((pad,) + deltas.shape[1:], deltas.dtype)])
    n_padded = n + pad
    floats = list(opt[1:5]) + [opt[5] if len(opt) > 5 else -1.0]
    meta = jnp.stack([jnp.asarray(opt[0], jnp.int32),
                      jnp.asarray(num_rows, jnp.int32)])
    opts = jnp.stack([jnp.asarray(f, jnp.float32) for f in floats])
    leaves = [state[k] for k in state_keys]
    n_state = len(leaves)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,      # ids, meta[wid, num_rows], opt floats
        grid=(n_padded // group,),
        in_specs=[pl.BlockSpec((group, d), lambda g, *refs: (g, 0))] +
                 [pl.BlockSpec(memory_space=pl.ANY)] * (1 + n_state),
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * (1 + n_state),
        scratch_shapes=[pltpu.VMEM((group, d), table.dtype)] +
                       [pltpu.VMEM((group, d), leaf.dtype)
                        for leaf in leaves] +
                       [pltpu.SemaphoreType.DMA((1 + n_state, group))],
    )
    outs = pl.pallas_call(
        _make_fused_kernel(group, state_keys, per_worker,
                           updater.rows_math, table.dtype),
        out_shape=[jax.ShapeDtypeStruct(table.shape, table.dtype)] +
                  [jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)
                   for leaf in leaves],
        grid_spec=grid_spec,
        # inputs: ids(0) meta(1) opts(2) deltas(3) table(4) leaves(5..)
        input_output_aliases={4 + i: i for i in range(1 + n_state)},
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        interpret=interpret,
    )(ids.astype(jnp.int32), meta, opts,
      deltas.astype(jnp.float32), table, *leaves)
    new_table = outs[0]
    new_state = {key: outs[1 + j] for j, key in enumerate(state_keys)}
    return new_table, new_state


# ---------------------------------------------------------------------------
# word2vec's AdaGrad row update over sorted ids, folded inside the kernel
# ---------------------------------------------------------------------------
# ``models/word2vec/model._apply_update`` on the Pallas plane. Its row math
# is not ``AdaGradUpdater.rows_math`` (which squares a folded total): for a
# row that occurs with gradients g_1..g_k it is G' = G + sum(g_i^2), then
# w' = w - lr sum(g_i) / sqrt(G' + 1e-6), so it needs BOTH run sums. A
# chunk brings 49,152 ids; a fold ahead of the kernel
# (``combine_duplicate_rows``) with ``fused_stateful_rows`` behind it read
# no faster than XLA's scatters at that size (PERF.md 5, PR 31). So the
# caller only sorts (out-of-range ids turned into ``num_rows``; the
# gradients permuted alike) and the kernel folds: a grid step takes 32
# positions, sums each run of equal ids down its lanes by a segmented scan
# on whole VMEM blocks (the open run's sum rides from step to step in a
# scratch row), and only the LAST lane of a run applies the update and
# writes its rows back. A step's row DMAs are all in flight together, and
# every write has landed before the next step reads.
#
# Three phases share the kernel. ``both`` is the whole update in one pass
# and needs every id's occurrences in ONE run (a full sort). The update is
# also the sum of two ADDITIVE passes, ``accumulate`` (G += sum g^2) and,
# once G is final, ``step`` (w -= lr sum(g) / sqrt(G + 1e-6)): each is exact
# whatever the order of the ids, a run is merely what it can fold, so they
# take a stream that is sorted in slabs (XLA's TPU sort compiles in 0.5 s at
# 8,192 ids and in 15 s at 49,152).

_FOLD_GROUP_ROWS = 32
ADAGRAD_PHASES = ("both", "accumulate", "step")


def _make_adagrad_fold_kernel(group: int, num_rows: int, phase: str):
    read_w = phase != "accumulate"
    write_w, write_g = phase != "accumulate", phase != "step"

    def _kernel(ids_ref, ends_ref, live_ref, lr_ref, grad_ref, back_ref,
                w_hbm, g_hbm, *refs):
        # refs: the aliased outputs (w and/or g2), wrows, grows, carry,
        # sems. An output IS its input's buffer: read through it.
        n_out = write_w + write_g
        outs = list(refs[:n_out])
        w_ref = outs.pop(0) if write_w else w_hbm
        g_ref = outs.pop(0) if write_g else g_hbm
        wrows, grows, carry, sems = refs[n_out:]
        step = pl.program_id(0)
        base = step * group

        def _tables(load):
            pairs = [(g_ref, grows, 0)] if (load or write_g) else []
            if (load and read_w) or (not load and write_w):
                pairs.append((w_ref, wrows, 1))
            return pairs

        def _start(load):
            """Start the lanes' row DMAs, one semaphore a table. Into VMEM
            (``load``): every lane's, with no branch (a lane that writes
            nothing reads its clamped row for nothing, which costs less
            than asking). Back to HBM: of the lanes that END a live run,
            which ``ends`` flags. Unrolled: the scalar core's loop over
            lanes, not the DMAs, is what a step's time is made of."""
            def body(k, c):
                def go():
                    rid = ids_ref[base + k]
                    if load:
                        rid = jnp.minimum(rid, num_rows - 1)
                    for hbm, vmem, j in _tables(load):
                        ends = ((hbm.at[rid], vmem.at[k]) if load
                                else (vmem.at[k], hbm.at[rid]))
                        pltpu.make_async_copy(*ends, sems.at[j]).start()
                if load:
                    go()
                else:
                    pl.when(ends_ref[base + k] != 0)(go)
                return c
            jax.lax.fori_loop(0, group, body, 0, unroll=True)

        def _wait(load):
            """Wait for what ``_start`` started: a DMA semaphore counts
            bytes, so ONE wait a table takes all 32 reads, and the writes'
            number (``live``, the step's flagged lanes) is waited for by
            its binary digits."""
            def wait_rows(n):
                for hbm, vmem, j in _tables(load):
                    pair = (hbm.at[pl.ds(0, n)], vmem.at[pl.ds(0, n)])
                    pltpu.make_async_copy(*(pair if load else pair[::-1]),
                                          sems.at[j]).wait()
            if load:
                return wait_rows(group)
            n = group
            while n:
                pl.when((live_ref[step] & n) != 0)(
                    functools.partial(wait_rows, n))
                n //= 2

        # Sorted ids: a step whose FIRST id is the sentinel holds nothing
        # else (in a slab-sorted stream: nothing else of its slab, whose
        # length the group divides), and costs a grid step and no DMA.
        @pl.when(ids_ref[base] < num_rows)
        def _():
            _start(True)
            # The fold, while the reads fly: a segmented inclusive scan
            # down the step's 32 lanes in five doubling strides, on whole
            # blocks. ``back`` says how many positions before a lane lie in
            # its run; within the step that is at most the lane's own
            # index, and a run that began earlier takes the step before's
            # last lane, which rides in ``carry``.
            back = back_ref[:]
            lane = jax.lax.broadcasted_iota(jnp.int32, back.shape, 0)
            within = jnp.minimum(back, lane)
            d = grad_ref[:]
            sums = []
            for j, term in ((0, d), (1, d * d)):
                if not (write_w, write_g)[j]:
                    sums.append(None)
                    continue
                stride = 1
                while stride < group:
                    term = term + jnp.where(
                        within >= stride, pltpu.roll(term, stride, 0), 0.0)
                    stride *= 2
                term = term + jnp.where(back > lane, carry[j:j + 1, :], 0.0)
                carry[j:j + 1, :] = term[group - 1:group, :]
                sums.append(term)
            _wait(True)
            g_new = grows[:] + sums[1] if write_g else grows[:]
            if write_w:
                wrows[:] = (wrows[:] - lr_ref[0] * sums[0]
                            / jnp.sqrt(g_new + 1e-6))
            if write_g:
                grows[:] = g_new
            _start(False)
            _wait(False)
    return _kernel


def adagrad_fold_rows(w: jax.Array, g2: jax.Array, sorted_ids: jax.Array,
                      run_back: jax.Array, sorted_grads: jax.Array, lr,
                      phase: str = "both", interpret: bool = False
                      ) -> Tuple[jax.Array, jax.Array]:
    """word2vec's AdaGrad row update, ``G += sum(g^2)`` then ``w -= lr
    sum(g) / sqrt(G + 1e-6)`` per touched row, over float32 ``[V, 128]``
    table and accumulator; what a phase writes is aliased in place.

    ``phase="both"``: the whole update; ``sorted_ids`` ascend (duplicates
    allowed: the kernel folds them). ``"accumulate"`` then ``"step"``: the
    same update as two additive passes over ids sorted in slabs of a
    multiple of 32. An id that is to be dropped is ``>= V``, behind every
    live one of its slab. ``run_back[p]`` is the number of positions
    directly before ``p`` that hold ``p``'s id (0 at a run's first).
    ``sorted_grads`` are the float32 gradients in the ids' order. Returns
    ``(w, g2)``."""
    if phase not in ADAGRAD_PHASES:
        raise ValueError(f"phase must be one of {ADAGRAD_PHASES}; "
                         f"got {phase!r}")
    num_rows, d = w.shape
    group = _FOLD_GROUP_ROWS
    n = sorted_ids.shape[0]
    if n == 0:
        return w, g2
    pad = (-n) % group
    ids = sorted_ids.astype(jnp.int32)
    if pad:
        ids = jnp.concatenate([ids, jnp.full((pad,), num_rows, jnp.int32)])
        run_back = jnp.concatenate([run_back, jnp.zeros((pad,), jnp.int32)])
        sorted_grads = jnp.concatenate(
            [sorted_grads, jnp.zeros((pad, d), sorted_grads.dtype)])
    # a lane writes where its live run ends: the next id differs
    ends = ((ids != jnp.concatenate([ids[1:], ids[-1:] + 1]))
            & (ids < num_rows)).astype(jnp.int32)
    live = ends.reshape(-1, group).sum(axis=1)      # a step's written lanes
    written = [i for i, on in enumerate((phase != "accumulate",
                                         phase != "step")) if on]
    tables = (w, g2)
    block = pl.BlockSpec((group, d), lambda g, *refs: (g, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,      # ids, ends, live, lr
        grid=((n + pad) // group,),
        in_specs=[block, block,
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * len(written),
        scratch_shapes=[pltpu.VMEM((group, d), jnp.float32),
                        pltpu.VMEM((group, d), jnp.float32),
                        pltpu.VMEM((8, d), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    outs = pl.pallas_call(
        _make_adagrad_fold_kernel(group, num_rows, phase),
        out_shape=[jax.ShapeDtypeStruct(tables[i].shape, tables[i].dtype)
                   for i in written],
        grid_spec=grid_spec,
        # inputs: ids(0) ends(1) live(2) lr(3) grads(4) back(5) w(6) g2(7)
        input_output_aliases={6 + i: o for o, i in enumerate(written)},
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        interpret=interpret,
    )(ids, ends, live, jnp.asarray(lr, jnp.float32).reshape(1),
      sorted_grads.astype(jnp.float32),
      # one value a lane, laid along the lanes' own axis: a full-width plane
      jnp.broadcast_to(run_back.astype(jnp.int32)[:, None], (n + pad, d)),
      w, g2)
    new = list(tables)
    for o, i in enumerate(written):
        new[i] = outs[o]
    return tuple(new)


# ---------------------------------------------------------------------------
# add a block of UNIQUE rows into a wide accumulator (the LMs' expert layer)
# ---------------------------------------------------------------------------
# ``acc[ids[i]] += vals[i]`` where no live id occurs twice: what the block
# loop of ``parallel/expert.py`` does with a block's result rows (a token
# chooses an expert once, and a block is one expert's), and what XLA's TPU
# scatter-add takes 244 ns a row of 2,048 floats for (PERF.md 5, PR 40).
# Mosaic refuses a one-row DMA slice of a float32 array wider than 128
# columns (``pallas_rows_eligible``), so the accumulator is handed over as
# ``[rows, cols / 128, 128]``: a row is then a whole ``[cols / 128, 128]``
# plane, contiguous in the tiled layout, and ``acc.at[id]`` a slice the DMA
# engine takes at any multiple of 128 columns (compiled for the v5e at 2,048
# and 2,688: tests/test_hybrid_lm_tpu_compile.py; the expert layer hands it
# whole (8, 128) tiles a row, 2,688 columns as 24 planes). A grid step takes
# a group
# of rows: their planes are read into one of three VMEM slots, added to the
# step's values, and written back; the NEXT step's reads are started before
# this step's are awaited and a step's writes are awaited two steps later
# (unique ids: no read can meet a write of the same row), so the DMA
# engines' latency hides behind the scalar core's issuing of the next rows.
# An id ``>= rows`` starts no DMA either way, which is ``mode="drop"``. No
# ``has_side_effects``: the result IS the output, and a rematerialised
# forward whose result nobody reads stays removable.

_ADD_GROUP_ROWS = 64
_ADD_SLOTS = 3
_ADD_UNROLL = 8


def _make_add_unique_kernel(group: int, num_rows: int, steps: int):
    slots, unroll = _ADD_SLOTS, min(_ADD_UNROLL, group)

    def _kernel(ids_ref, live_ref, vals_ref, acc_in, acc_ref, rows, sems):
        del acc_in              # aliased with acc_ref (the output)
        step = pl.program_id(0)

        def _start(s, load):
            """Start the row DMAs of step ``s``'s live lanes: into its VMEM
            slot (``load``) or back to HBM."""
            slot, base = s % slots, s * group

            def lane(k):
                rid = ids_ref[base + k]

                @pl.when(rid < num_rows)
                def _():
                    ends = (acc_ref.at[rid], rows.at[slot, k])
                    pltpu.make_async_copy(
                        *(ends if load else ends[::-1]),
                        sems.at[1 if load else 0, slot]).start()

            # Mosaic unrolls a loop wholly or not at all: eight lanes a
            # trip keep the kernel small and the scalar core off the loop's
            # own bookkeeping.
            def body(i, c):
                for j in range(unroll):
                    lane(i * unroll + j)
                return c
            jax.lax.fori_loop(0, group // unroll, body, 0)

        def _wait(s, load):
            """Wait for what ``_start(s, load)`` started: a DMA semaphore
            counts bytes, so the step's live lanes (``live``) are waited
            for by their number's binary digits."""
            slot, n = s % slots, group
            while n:
                def wait_rows(n=n):
                    ends = (acc_ref.at[pl.ds(0, n)],
                            rows.at[slot, pl.ds(0, n)])
                    pltpu.make_async_copy(
                        *(ends if load else ends[::-1]),
                        sems.at[1 if load else 0, slot]).wait()
                pl.when((live_ref[s] & n) != 0)(wait_rows)
                n //= 2

        pl.when(step == 0)(lambda: _start(step, True))
        pl.when(step >= 2)(lambda: _wait(step - 2, False))
        pl.when(step + 1 < steps)(lambda: _start(step + 1, True))
        _wait(step, True)
        slot = step % slots
        rows[slot] = rows[slot] + vals_ref[...]
        _start(step, False)

        @pl.when(step == steps - 1)
        def _():
            if steps > 1:
                _wait(step - 1, False)
            _wait(step, False)
    return _kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def add_unique_rows(acc: jax.Array, ids: jax.Array, vals: jax.Array,
                    interpret: bool = False) -> jax.Array:
    """``acc[ids[i]] += vals[i]`` for ids of which no live one occurs twice,
    in any order: ``acc`` float32 ``[rows, planes, 128]`` (a ``[rows, planes
    * 128]`` accumulator seen a 128-lane plane at a time), updated in place
    (aliased: trace it where ``acc`` is a loop's carry or donated); ``ids``
    ``[n]``, an id ``>= rows`` dropped as ``.at[ids].add(mode="drop")``
    drops it; ``vals`` ``[n, planes, 128]``. Bitwise the XLA scatter-add's
    result: every live row is read, added to once and written."""
    num_rows, planes, lanes = acc.shape
    n = ids.shape[0]
    if n == 0:
        return acc
    # a power of two (the waits count by binary digits) no larger than the
    # accumulator, whose leading rows the wait's descriptor names
    group = min(_ADD_GROUP_ROWS, 1 << (num_rows.bit_length() - 1))
    pad = (-n) % group
    ids = ids.astype(jnp.int32)
    if pad:
        ids = jnp.concatenate([ids, jnp.full((pad,), num_rows, jnp.int32)])
        vals = jnp.concatenate(
            [vals, jnp.zeros((pad, planes, lanes), vals.dtype)])
    steps = (n + pad) // group
    live = jnp.sum((ids < num_rows).reshape(steps, group), axis=1,
                   dtype=jnp.int32)
    if interpret:
        # Interpreted, the kernel's add is an XLA:CPU op, and LLVM contracts
        # it with a multiply that made ``vals`` into one fma (one rounding
        # where XLA's scatter-add and the chip make two). A division by a
        # one the compiler cannot see through keeps them apart
        # (``core/updater._eval_jaxpr_contraction_proof``; a barrier does
        # not).
        vals = vals / jnp.where(live[0] >= 0, 1.0, 2.0).astype(vals.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,      # ids, live
        grid=(steps,),
        in_specs=[pl.BlockSpec((group, planes, lanes),
                               lambda g, *refs: (g, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((_ADD_SLOTS, group, planes, lanes),
                                   acc.dtype),
                        pltpu.SemaphoreType.DMA((2, _ADD_SLOTS))],
    )
    return pl.pallas_call(
        _make_add_unique_kernel(group, num_rows, steps),
        out_shape=jax.ShapeDtypeStruct(acc.shape, acc.dtype),
        grid_spec=grid_spec,
        input_output_aliases={3: 0},    # ids(0) live(1) vals(2) acc(3)
        interpret=interpret,
    )(ids, live, vals.astype(acc.dtype), acc)
