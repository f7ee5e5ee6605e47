#!/usr/bin/env python
"""Attribution experiment for the in-graph chunk-loop de-optimization.

Round-2 finding (docs/BENCHMARK.md §3): the identical sg-ns update runs
0.05-0.12ms as a standalone jitted dispatch but 2.2-2.6ms inside
``lax.scan``/``fori_loop`` on TPU. This script isolates WHERE the loop
overhead lives by timing the same chunk workload under six formulations:

  A standalone      — host-dispatched donated chunk steps (no loop)
  B fori-full       — fori_loop, full step (gather+compute+scatter)
  C fori-gather     — fori_loop, gather+compute only (no table scatter)
  D fori-scatter    — fori_loop, scatter-only (precomputed grads)
  E fori-small      — full step but tables shrunk to the touched-row
                      sub-table (carry bytes ~100x smaller)
  F fori-sub        — full tables, but the loop carries a SUB-TABLE of
                      gathered rows and one final scatter applies the
                      delta (the candidate fix: if the loop copies its
                      carry per iteration, cost drops with carry size)

If B-C >> D: the gather side de-optimizes. If B-D >> C: the scatter does.
If E/F track A: the cost scales with CARRY SIZE -> per-iteration copies
of the carried tables are the mechanism and the sub-table restructure is
the fix. Run ON the chip:

    python scripts/perf_attrib.py [--vocab 50000] [--dim 128]

``--dry-run`` shrinks every shape to seconds-on-CPU and runs all legs —
the tier-1 smoke that keeps this harness from bit-rotting (with
``JAX_PLATFORMS=cpu``; the platform comes from the environment).
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--vocab", type=int, default=50_000)
    p.add_argument("--dim", type=int, default=128)
    p.add_argument("--chunk", type=int, default=8192)
    p.add_argument("--negative", type=int, default=5)
    p.add_argument("--chunks", type=int, default=16)
    p.add_argument("--iters", type=int, default=5)
    p.add_argument("--dry-run", action="store_true",
                   help="tiny shapes, 1 iter: CI "
                        "smoke so the harness cannot bit-rot off-chip")
    p.add_argument("--telemetry-dir", default="",
                   help="write telemetry snapshots + Chrome trace here "
                        "(each leg becomes a span; snapshots carry the "
                        "span.perf_attrib.* latency histograms)")
    args = p.parse_args()
    if args.dry_run:
        args.vocab, args.dim, args.chunk = 512, 32, 64
        args.negative, args.chunks, args.iters = 2, 2, 1

    import jax
    import jax.numpy as jnp

    from multiverso_tpu.models.word2vec.model import raw_sg_ns_step
    from multiverso_tpu.telemetry import span, start_exporter, stop_exporter

    if args.telemetry_dir:
        start_exporter(args.telemetry_dir, interval=5.0)
        # A leg that dies (TPU OOM, compile error) must still flush the
        # partial spans — that run is exactly the one worth inspecting.
        # stop_exporter is idempotent, so the explicit calls below remain.
        import atexit
        atexit.register(stop_exporter)

    V, D, C, K, N = (args.vocab, args.dim, args.chunk, args.negative,
                     args.chunks)
    print(f"backend: {jax.devices()[0].platform} "
          f"V={V} D={D} chunk={C} K={K} chunks={N}")
    rng = np.random.default_rng(0)
    raw = raw_sg_ns_step(adagrad=True)

    def tables():
        return (jnp.asarray(rng.normal(size=(V, D)).astype(np.float32)),
                jnp.zeros((V, D), jnp.float32),
                jnp.zeros((V, D), jnp.float32),
                jnp.zeros((V, D), jnp.float32))

    centers = jnp.asarray(rng.integers(0, V, (N, C)).astype(np.int32))
    contexts = jnp.asarray(rng.integers(0, V, (N, C)).astype(np.int32))
    negs = jnp.asarray(rng.integers(0, V, (N, C, K)).astype(np.int32))
    mask = jnp.ones((N, C), jnp.float32)
    lr = jnp.float32(0.025)

    def timeit(name, fn, *operands, per_chunk: float = 1.0):
        out = fn(*operands)             # compile
        jax.block_until_ready(out)
        best = float("inf")
        for _ in range(args.iters):
            ops = tables() + operands[4:]   # fresh tables (donation)
            with span(f"perf_attrib.{name}", leg=name):
                t0 = time.perf_counter()
                out = fn(*ops)
                jax.block_until_ready(out)
                best = min(best, time.perf_counter() - t0)
        ms = best * 1e3 / per_chunk
        print(f"{name:14s} {ms:8.3f} ms/chunk")
        return ms

    # A: standalone host-dispatched chain -----------------------------------
    step = jax.jit(raw, donate_argnums=(0, 1, 2, 3))
    w = tables()
    out = step(*w, centers[0], contexts[0], negs[0], mask[0], lr)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(args.iters):
        w = tables()
        with span("perf_attrib.A standalone", leg="A standalone"):
            t0 = time.perf_counter()
            for i in range(N):
                w = step(*w, centers[i], contexts[i], negs[i], mask[i],
                         lr)[:4]
            jax.block_until_ready(w)
            best = min(best, time.perf_counter() - t0)
    print(f"{'A standalone':14s} {best * 1e3 / N:8.3f} ms/chunk")

    # B: fori_loop full ------------------------------------------------------
    def loop_full(w_in, w_out, g_in, g_out, cs, os_, ns, ms):
        def body(i, carry):
            out = raw(*carry[:4], cs[i], os_[i], ns[i], ms[i], lr)
            return (*out[:4], carry[4] + out[4])
        return jax.lax.fori_loop(
            0, N, body, (w_in, w_out, g_in, g_out, jnp.float32(0)))

    timeit("B fori-full", jax.jit(loop_full, donate_argnums=(0, 1, 2, 3)),
           *tables(), centers, contexts, negs, mask, per_chunk=N)

    # C: fori_loop gather+compute only (tables carried untouched) ------------
    def loop_gather(w_in, w_out, g_in, g_out, cs, os_, ns, ms):
        def body(i, carry):
            *tbl, acc = carry
            u = jnp.take(tbl[0], cs[i], axis=0, mode="clip")
            vp = jnp.take(tbl[1], os_[i], axis=0, mode="clip")
            vn = jnp.take(tbl[1], ns[i], axis=0, mode="clip")
            s = jax.nn.sigmoid(jnp.sum(u * vp, -1)) \
                + jax.nn.sigmoid(jnp.einsum("bd,bkd->bk", u, vn)).sum(-1)
            return (*tbl, acc + (s * ms[i]).sum())
        return jax.lax.fori_loop(
            0, N, body, (w_in, w_out, g_in, g_out, jnp.float32(0)))

    timeit("C fori-gather", jax.jit(loop_gather,
                                    donate_argnums=(0, 1, 2, 3)),
           *tables(), centers, contexts, negs, mask, per_chunk=N)

    # D: fori_loop scatter-only (grads precomputed outside) ------------------
    grads = jnp.asarray(rng.normal(size=(N, C, D)).astype(np.float32))

    def loop_scatter(w_in, w_out, g_in, g_out, cs, os_, gs):
        def body(i, carry):
            wi, wo = carry
            wi = wi.at[cs[i]].add(gs[i], mode="drop")
            wo = wo.at[os_[i]].add(gs[i], mode="drop")
            return (wi, wo)
        return jax.lax.fori_loop(0, N, body, (w_in, w_out))

    timeit("D fori-scatter",
           jax.jit(lambda a, b, c_, d_, cs, os_, gs:
                   loop_scatter(a, b, c_, d_, cs, os_, gs),
                   donate_argnums=(0, 1)),
           *tables(), centers, contexts, grads, per_chunk=N)

    # E: fori_loop full but tiny tables (carry-size scaling probe) -----------
    V_small = max(C * (2 + K) * 2, 1024)
    if V_small < V:
        sm_rng = np.random.default_rng(1)
        sm = (jnp.asarray(sm_rng.normal(size=(V_small, D))
                          .astype(np.float32)),
              jnp.zeros((V_small, D), jnp.float32),
              jnp.zeros((V_small, D), jnp.float32),
              jnp.zeros((V_small, D), jnp.float32))
        cs2 = centers % V_small
        os2 = contexts % V_small
        ns2 = negs % V_small

        def small_tables():
            return tuple(jnp.array(t) for t in sm)

        def loop_small(w_in, w_out, g_in, g_out):
            def body(i, carry):
                out = raw(*carry[:4], cs2[i], os2[i], ns2[i], mask[i], lr)
                return (*out[:4], carry[4] + out[4])
            return jax.lax.fori_loop(
                0, N, body, (w_in, w_out, g_in, g_out, jnp.float32(0)))

        fn = jax.jit(loop_small, donate_argnums=(0, 1, 2, 3))
        out = fn(*small_tables())
        jax.block_until_ready(out)
        best = float("inf")
        for _ in range(args.iters):
            ops = small_tables()
            t0 = time.perf_counter()
            out = fn(*ops)
            jax.block_until_ready(out)
            best = min(best, time.perf_counter() - t0)
        print(f"{'E fori-small':14s} {best * 1e3 / N:8.3f} ms/chunk "
              f"(V={V_small})")

    # F: sub-table carry + single final scatter ------------------------------
    def loop_subtable(w_in, w_out, g_in, g_out, cs, os_, ns, ms):
        uniq = jnp.unique(jnp.concatenate(
            [cs.ravel(), os_.ravel(), ns.ravel()]),
            size=min(V, N * C * (2 + K)), fill_value=V - 1)
        rm = lambda x: jnp.searchsorted(uniq, x).astype(jnp.int32)  # noqa
        sub = [jnp.take(t, uniq, axis=0) for t in
               (w_in, w_out, g_in, g_out)]
        sub0 = [sub[0], sub[1]]

        def body(i, carry):
            out = raw(*carry[:4], rm(cs[i]), rm(os_[i]), rm(ns[i]), ms[i],
                      lr)
            return (*out[:4], carry[4] + out[4])

        sub0 = sub0 + [sub[2], sub[3]]
        *sub_new, loss = jax.lax.fori_loop(
            0, N, body, (*sub, jnp.float32(0)))
        w_in = w_in.at[uniq].add(sub_new[0] - sub0[0])
        w_out = w_out.at[uniq].add(sub_new[1] - sub0[1])
        g_in = g_in.at[uniq].add(sub_new[2] - sub0[2])
        g_out = g_out.at[uniq].add(sub_new[3] - sub0[3])
        return w_in, w_out, g_in, g_out, loss

    timeit("F fori-sub", jax.jit(loop_subtable,
                                 donate_argnums=(0, 1, 2, 3)),
           *tables(), centers, contexts, negs, mask, per_chunk=N)

    stop_exporter()     # writes the final snapshot + Chrome trace


if __name__ == "__main__":
    main()
