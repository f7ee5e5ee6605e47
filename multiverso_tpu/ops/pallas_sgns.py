"""Pallas grid-resident skip-gram/negative-sampling chunk loop.

The round-2 profiling finding (docs/BENCHMARK.md §3) is that the XLA sg-ns
update is memory-bound-fast as a STANDALONE dispatch (0.05-0.12 ms per
8192-pair chunk) but ~20x slower inside ``lax.scan``/``while_loop`` — XLA
de-optimizes the gather/scatter hot path in loop bodies, and unrolling does
not recover it. The host-dispatched workaround (``chunk_dispatch``) escapes
the loop but pays one host->device launch per chunk, which loses 10x at
high launch latency.

This kernel is the third execution: the chunk loop becomes a **sequential
Pallas grid**. Mosaic grids are a hardware loop over block fetches — there
is no XLA loop body for the de-optimization to apply to — and the whole
block (every chunk) costs ONE launch, so launch latency stops mattering
entirely. Layout:

* the four tables (w_in, w_out and their AdaGrad accumulators) are
  block-mapped whole with a constant index map, so Mosaic fetches them into
  VMEM once, keeps them **resident across every grid step**, and flushes
  them back to HBM once at the end — the grid-resident carry that
  ``lax.scan`` cannot express;
* ``input_output_aliases`` donates the table buffers (same contract as
  ``pallas_rows.scatter_add_sorted_rows``);
* the compacted chunk streams from ``pair_gen`` ([n, chunk] centers and
  contexts, [n, chunk, K] negatives) are block-mapped per grid step, so
  Mosaic double-buffers the (small, int32) stream DMAs under compute;
* the true pair count rides scalar prefetch and masks the tail chunk —
  numerics are EXACT regardless of how many dead (all-padding) chunks the
  static grid contains, mirroring the in-graph path's mask.

The per-chunk math is ``raw_sg_ns_step`` itself — imported lazily from the
model (the model imports this module, so a top-level import would cycle).
Reusing the exact step function is what makes the mode swap safe: the same
primitive sequence in the same order gives bitwise-identical table state
(tests/test_pallas_sgns.py, tests/test_word2vec.py three-way test).

VMEM is the constraint: whole-table residency needs all four tables (plus
Mosaic's input copies) in VMEM, i.e. small-to-medium vocabularies (~2K
words at D=128 under a 14 MB budget). For >VMEM vocabs the follow-up is a
row-DMA variant that keeps the tables in HBM (``pl.ANY``) and streams only
the touched rows per chunk through ``pallas_rows``' per-row DMA machinery;
the sorted-run scatter fold there must be restructured to sequential
row-value folds before it can match XLA's duplicate-accumulation order
bitwise, so it lands only with on-chip numbers.

STATUS (v5e, jax 0.9.0, PR 21): the Pallas TPU lowering REFUSES this
kernel, for a structural reason. The body is ``raw_sg_ns_step`` verbatim,
i.e. row gathers (``jnp.take(table[V, D], ids[chunk])``) and scatter-adds
(``.at[rows].add``) over whole VMEM tables; Mosaic's gather covers only the
same-shape 2-D ``take_along_axis`` form, so lowering stops at the first
``jnp.take`` with ``ValueError: Shape mismatch in input, indices and
output`` (the scatter-adds would be next). The block shapes and VMEM limit
were repaired here so that the refusal is the real one and not a layout
complaint. The kernel is therefore out of every automatic selection
(``resolve_dispatch_mode`` never returns ``pallas_grid``); asking for it by
name on a TPU fails with the compiler's message. Off a TPU it runs under
the Pallas interpreter, which tier-1 uses to pin its numerics against the
in-graph loop. Making it real means writing the gather/scatter as per-row
DMA (``ops/pallas_rows.py``'s machinery) — a rewrite, not a repair.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The [chunk, K] negatives block pads K to 128 lanes in VMEM (4 MB at chunk
# 8192, double-buffered) on top of the four resident tables: ask Mosaic for
# more than its 16 MB default scoped limit.
VMEM_LIMIT_BYTES = 64 << 20


def _make_sgns_grid_kernel(raw_step, chunk: int):
    def kernel(n_pairs_ref, centers_ref, contexts_ref, negs_ref, lr_ref,
               w_in_in, w_out_in, g_in_in, g_out_in,
               w_in, w_out, g_in, g_out, loss_ref):
        g = pl.program_id(0)

        # First grid step: seed the resident output blocks from the donated
        # tables (out blocks are write-before-read on first visit; constant
        # index maps keep them in VMEM for every later step).
        @pl.when(g == 0)
        def _():
            w_in[:] = w_in_in[:]
            w_out[:] = w_out_in[:]
            g_in[:] = g_in_in[:]
            g_out[:] = g_out_in[:]
            loss_ref[0, 0] = jnp.float32(0.0)

        # Tail/dead-chunk mask — same int math as the in-graph fori body
        # (1-D iota is rejected by Mosaic, hence broadcasted_iota).
        lane = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)[:, 0]
        m = ((g * chunk + lane) < n_pairs_ref[0]).astype(jnp.float32)
        out = raw_step(w_in[:], w_out[:], g_in[:], g_out[:],
                       centers_ref[0, 0, :], contexts_ref[0, 0, :],
                       negs_ref[0, :, :], m, lr_ref[0, 0])
        w_in[:] = out[0]
        w_out[:] = out[1]
        g_in[:] = out[2]
        g_out[:] = out[3]
        loss_ref[0, 0] = loss_ref[0, 0] + out[4]

    return kernel


def build_sgns_grid_step(chunk: int, negative: int, adagrad: bool,
                         interpret: bool = False):
    """Jitted whole-block sg-ns trainer: one launch runs every chunk as a
    sequential Pallas grid with VMEM-resident tables.

    Signature matches the chunked pipeline's operands::

        step(w_in, w_out, g_in, g_out, centers2d, contexts2d, negatives3d,
             n_pairs, lr) -> (w_in, w_out, g_in, g_out, loss)

    where the streams are ``pair_gen`` outputs ([n, chunk] / [n, chunk, K])
    and ``n_pairs`` is the true pair count (tail masking). Tables are
    donated through ``input_output_aliases``.
    """
    # Lazy import: the model module imports this one at top level.
    from multiverso_tpu.models.word2vec.model import raw_sg_ns_step
    raw = raw_sg_ns_step(adagrad)
    kernel = _make_sgns_grid_kernel(raw, chunk)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def step(w_in, w_out, g_in, g_out, centers2d, contexts2d, negatives3d,
             n_pairs, lr):
        n = centers2d.shape[0]
        v_in, d = w_in.shape
        v_out = w_out.shape[0]
        const = lambda g, np_ref: (0, 0)  # noqa: E731 - resident blocks
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n,),
            # The [n, chunk] id streams go in as [n, 1, chunk]: a
            # (1, chunk) block of a 2-D array has a second-minor dim of 1,
            # which Mosaic's (8, 128) block rule refuses; as the last two
            # dims of a 3-D block it equals the array's own.
            in_specs=[
                pl.BlockSpec((1, 1, chunk), lambda g, np_ref: (g, 0, 0)),
                pl.BlockSpec((1, 1, chunk), lambda g, np_ref: (g, 0, 0)),
                pl.BlockSpec((1, chunk, negative),
                             lambda g, np_ref: (g, 0, 0)),
                pl.BlockSpec((1, 1), const, memory_space=pltpu.SMEM),
                pl.BlockSpec((v_in, d), const),
                pl.BlockSpec((v_out, d), const),
                pl.BlockSpec((v_in, d), const),
                pl.BlockSpec((v_out, d), const),
            ],
            out_specs=[
                pl.BlockSpec((v_in, d), const),
                pl.BlockSpec((v_out, d), const),
                pl.BlockSpec((v_in, d), const),
                pl.BlockSpec((v_out, d), const),
                pl.BlockSpec((1, 1), const, memory_space=pltpu.SMEM),
            ],
        )
        outs = pl.pallas_call(
            kernel,
            out_shape=[
                jax.ShapeDtypeStruct(w_in.shape, w_in.dtype),
                jax.ShapeDtypeStruct(w_out.shape, w_out.dtype),
                jax.ShapeDtypeStruct(g_in.shape, g_in.dtype),
                jax.ShapeDtypeStruct(g_out.shape, g_out.dtype),
                jax.ShapeDtypeStruct((1, 1), jnp.float32),
            ],
            grid_spec=grid_spec,
            # inputs: n_pairs(sp), centers, contexts, negs, lr, then tables
            input_output_aliases={5: 0, 6: 1, 7: 2, 8: 3},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),   # sequential carry
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            interpret=interpret,
        )(jnp.reshape(n_pairs, (1,)).astype(jnp.int32),
          centers2d[:, None, :], contexts2d[:, None, :], negatives3d,
          jnp.reshape(jnp.asarray(lr, jnp.float32), (1, 1)),
          w_in, w_out, g_in, g_out)
        return (*outs[:4], outs[4][0, 0])

    return step
