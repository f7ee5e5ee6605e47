#!/usr/bin/env python
"""Sharded-optimizer-state + fused-stateful-kernel bench (ISSUE 12 /
docs/DESIGN.md "Sharded updater state").

Measures, on THIS box:

* ``state_memory`` — per-store updater-state bytes with cross-replica
  state sharding off vs on (gauge-backed: the numbers are read from the
  ``ps.state_bytes.*`` / ``ps.data_bytes.*`` telemetry gauges, not
  recomputed), plus the max table rows admittable at a fixed simulated
  HBM budget per updater — HBM headroom IS table capacity;
* ``stateful_sparse`` — stateful sparse updates/sec through the shipped
  FUSED path (one donated jit dispatch: gather + updater math + scatter
  in one executable) vs an UNFUSED three-dispatch chain (separate jitted
  gather, math, scatter executables — the naive host-driven shape) at a
  dispatch-bound batch and a bandwidth-bound batch, plus the fused
  Pallas gather-update-scatter kernel (parity witness; off a TPU it runs
  under the Pallas interpreter and its timing measures the interpreter,
  not the kernel);
* a small in-process sharded-vs-unsharded parity witness (params
  bitwise) so the record carries the correctness claim next to the
  memory claim.

Writes BENCH_STATE.json on full runs; ``--dry-run`` is the tier-1 smoke
shape (witnesses asserted). Numbers are box-relative.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

# The platform comes from the environment (JAX_PLATFORMS=cpu for a CPU
# drive). Under the CPU platform the CLI asks for an 8-device virtual mesh
# so the replica axis exists on laptops/CI; the flag does nothing on a TPU.
if __name__ == "__main__":
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8")

import numpy as np  # noqa: E402

_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _HERE)

_BUDGET_BYTES = 256 << 20       # simulated per-replica HBM budget
_UPDATERS = ("momentum_sgd", "adagrad", "ftrl", "dcasgd")


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _memory_gauges(name: str) -> dict:
    from multiverso_tpu.telemetry import metrics_snapshot
    gauges = metrics_snapshot(buckets=False).get("gauges", {})
    return {
        "data_bytes": int(gauges[f"ps.data_bytes.{name}"]["last"]),
        "state_bytes": int(gauges[f"ps.state_bytes.{name}"]["last"]),
    }


def _replica_axis_size() -> int:
    import jax
    n = len(jax.devices())
    return min(4, n) if n > 1 else 1


def bench_state_memory(dry: bool) -> dict:
    """Gauge-backed per-store bytes, sharded vs unsharded, per updater."""
    import multiverso_tpu as mv

    rows = 512 if dry else 8192
    cols = 64
    replicas = _replica_axis_size()
    updaters = _UPDATERS[:2] if dry else _UPDATERS
    out = {"replicas": replicas, "rows": rows, "cols": cols,
           "budget_bytes": _BUDGET_BYTES, "per_updater": {}}
    if replicas < 2:
        out["note"] = "single device: no replica axis, sharding inert"
    modes = ("off", "on") if replicas > 1 else ("off",)
    for upd in updaters:
        rec = {}
        for mode in modes:
            mv.init([f"-mesh_shape=server:1,worker:{replicas}"
                     if replicas > 1 else "-mesh_shape=",
                     f"-state_sharding={mode}"])
            try:
                t = mv.create_table(mv.MatrixTableOption(
                    rows, cols, updater=upd, name=f"sb_{upd}"))
                g = _memory_gauges(f"sb_{upd}")
                # Gauges count MESH-TOTAL bytes (replication per copy);
                # the budget is PER REPLICA, so capacity divides by the
                # per-replica share: data (full copy each) and state
                # (replicated or 1/k-sharded) both cost total/replicas
                # per replica.
                per_row = ((g["data_bytes"] + g["state_bytes"])
                           / replicas / rows)
                rec[mode] = {
                    **g,
                    "state_sharded": bool(t.store.state_sharded),
                    "bytes_per_row_per_replica": round(per_row, 2),
                    "max_rows_at_budget": int(_BUDGET_BYTES // per_row),
                }
            finally:
                mv.shutdown()
        if "on" in rec:
            off_b, on_b = rec["off"]["state_bytes"], rec["on"]["state_bytes"]
            rec["state_reduction_pct"] = round(100.0 * (1 - on_b / off_b), 1)
            rec["capacity_gain"] = round(
                rec["on"]["max_rows_at_budget"]
                / max(rec["off"]["max_rows_at_budget"], 1), 3)
        out["per_updater"][upd] = rec
        _log(f"state_memory[{upd}]: {rec}")
    return out


def _unfused_chain(store):
    """The naive three-dispatch stateful row update: separate jitted
    gather, math, and scatter executables over the SAME shared rows_math
    — what the fused path collapses into one donated dispatch."""
    import jax

    upd = store.updater

    # The stages are the updater's own (core/updater.py): the fold's id
    # contract and the write-back's hints live there, not here.
    gather = jax.jit(upd.gather_row_blocks)

    @jax.jit
    def math(d_rows, st_rows, delta, *opt):
        return upd.rows_math(d_rows, st_rows, delta, opt)

    scatter = jax.jit(upd.scatter_row_blocks, donate_argnums=(0, 1))

    def step(rows, delta, opt):
        wid = opt[0]
        r, d, d_rows, st_rows = gather(store.data, store.state, rows,
                                       delta, wid)
        new_d, new_st = math(d_rows, st_rows, d, *opt)
        store.data, store.state = scatter(store.data, store.state, r,
                                          wid, new_d, new_st)
    return step


def bench_stateful_sparse(dry: bool) -> dict:
    """Fused one-dispatch vs unfused three-dispatch stateful row updates
    (+ Pallas fused-kernel parity)."""
    import jax

    import multiverso_tpu as mv
    from multiverso_tpu.core.options import AddOption

    rows_total = 4096 if dry else 65536
    cols = 64
    reps = 20 if dry else 60
    updaters = ("momentum_sgd", "adagrad") if dry \
        else ("momentum_sgd", "adagrad", "ftrl")
    batches = (256,) if dry else (256, 8192)
    out = {"rows": rows_total, "cols": cols, "reps": reps,
           "per_updater": {}}
    opt = AddOption(worker_id=0, momentum=0.9, learning_rate=0.1, rho=0.1)
    rng = np.random.default_rng(0)

    for upd in updaters:
        rec = {}
        for batch in batches:
            ids_sets = [rng.integers(0, rows_total, size=batch)
                        .astype(np.int32) for _ in range(8)]
            deltas = rng.normal(size=(batch, cols)).astype(np.float32)

            def timed(step_fn, store):
                """Best of 3 windows: this box is 1-core and shared, so a
                single window eats scheduler noise asymmetrically."""
                step_fn(ids_sets[0], deltas, opt.scalars())   # compile
                store.block()
                best = 0.0
                for _ in range(3):
                    t0 = time.perf_counter()
                    for i in range(reps):
                        step_fn(ids_sets[i % len(ids_sets)], deltas,
                                opt.scalars())
                    store.block()
                    dt = time.perf_counter() - t0
                    best = max(best, reps * batch * cols / dt)
                return best

            mv.init(["-mesh_shape=", "-state_sharding=auto"],
                    devices=jax.devices()[:1])
            try:
                t_f = mv.create_table(mv.MatrixTableOption(
                    rows_total, cols, updater=upd, name="fb"))

                def fused_step(ids, d, sc, _t=t_f):
                    _t.store.apply_rows(ids, d, opt)
                fused = timed(fused_step, t_f.store)

                t_u = mv.create_table(mv.MatrixTableOption(
                    rows_total, cols, updater=upd, name="ub"))
                chain = _unfused_chain(t_u.store)

                def unfused_step(ids, d, sc):
                    import jax.numpy as jnp
                    chain(jnp.asarray(ids), jnp.asarray(d), sc)
                unfused = timed(unfused_step, t_u.store)
            finally:
                mv.shutdown()
            rec[f"batch_{batch}"] = {
                "fused_updates_per_sec": round(fused),
                "unfused_updates_per_sec": round(unfused),
                "fused_over_unfused": round(fused / max(unfused, 1e-9), 3),
            }
            _log(f"stateful_sparse[{upd} b{batch}]: fused {fused:.3g} vs "
                 f"unfused {unfused:.3g} updates/sec "
                 f"({fused / max(unfused, 1e-9):.2f}x)")
        out["per_updater"][upd] = rec

    # Pallas fused kernel: parity witness + timing. The kernel needs
    # 128-lane rows (core/table.py eligibility) and a single shard. Off a
    # TPU it runs interpreted and the time measures the interpreter.
    from multiverso_tpu.ops import pallas_interpret
    pcols = 128
    mv.init(["-mesh_shape=", "-state_sharding=auto"],
            devices=jax.devices()[:1])
    try:
        import jax.numpy as jnp
        from multiverso_tpu.core.table import build_row_update
        t_p = mv.create_table(mv.MatrixTableOption(512, pcols,
                                                   updater="adagrad",
                                                   name="pp"))
        # the store picks the kernel by itself (fused_rows_selected), so
        # the reference is the XLA row update of its updater, over copies
        assert t_p.store.row_plane == "fused_stateful"
        xla_update = jax.jit(build_row_update(t_p.store.updater, False),
                             donate_argnums=(0, 1))
        xla = (jnp.array(t_p.store.data),
               jax.tree_util.tree_map(jnp.array, t_p.store.state))
        interpreted = pallas_interpret(t_p.store.sharding.device_set)
        ids = rng.integers(0, 512, size=128).astype(np.int32)
        d = rng.normal(size=(128, pcols)).astype(np.float32)
        for _ in range(3):
            xla = xla_update(*xla, jnp.asarray(ids), jnp.asarray(d),
                             *opt.scalars())
            t_p.add_rows(ids, d, opt)
        # Bitwise under the interpreter (both planes round strictly per
        # primitive, core/updater.exact_elementwise); on a TPU the fused
        # math may differ in the last bit, so the witness is closeness.
        same = np.array_equal if interpreted else \
            (lambda a, b: np.allclose(a, b, rtol=1e-5, atol=1e-6))
        parity = bool(
            same(np.asarray(xla[0]), t_p.get())
            and all(same(np.asarray(xla[1][k]),
                         np.asarray(t_p.store.state[k]))
                    for k in xla[1]))
        t0 = time.perf_counter()
        for _ in range(5):
            t_p.add_rows(ids, d, opt)
        t_p.store.block()
        interp_dt = (time.perf_counter() - t0) / 5
        out["pallas_fused"] = {
            "bitwise_vs_xla": parity,
            "interpreted": interpreted,
            "ms_per_dispatch": round(interp_dt * 1e3, 2),
        }
        _log(f"pallas_fused: parity={parity} interpreted={interpreted} "
             f"{interp_dt * 1e3:.1f} ms/dispatch")
    finally:
        mv.shutdown()
    return out


def bench_sharded_parity_witness(dry: bool) -> dict:
    """Small in-process witness: sharded-state params bitwise-equal to
    unsharded over a short mixed add schedule (the full matrix lives in
    tests/test_state_sharding.py)."""
    import multiverso_tpu as mv

    replicas = _replica_axis_size()
    if replicas < 2:
        return {"skipped": "single device"}
    del dry
    results = {}
    for mode in ("off", "on"):
        mv.init([f"-mesh_shape=server:1,worker:{replicas}",
                 f"-state_sharding={mode}"])
        try:
            t = mv.create_table(mv.MatrixTableOption(
                64, 16, updater="adagrad", name="pw"))
            rng = np.random.default_rng(11)
            opt = mv.AddOption(learning_rate=0.1, rho=0.1)
            for _ in range(4):
                ids = rng.integers(0, 64, size=16).astype(np.int32)
                t.add_rows(ids, rng.normal(size=(16, 16))
                           .astype(np.float32), opt)
                t.add(rng.normal(size=(64, 16)).astype(np.float32), opt)
            results[mode] = (t.get().copy(), t.store.state_bytes())
        finally:
            mv.shutdown()
    bitwise = bool(np.array_equal(results["off"][0], results["on"][0]))
    return {"replicas": replicas, "params_bitwise": bitwise,
            "state_bytes_off": results["off"][1],
            "state_bytes_on": results["on"][1]}


def check_witnesses(mem: dict, sparse: dict, parity: dict) -> dict:
    """Tier-1 witnesses: the memory claim, the dispatch-fusion claim and
    the correctness claims are all measured, in one block."""
    ada = mem["per_updater"].get("adagrad", {})
    replicas = mem.get("replicas", 1)
    # The >= 1.3x dispatch-fusion claim is made for the momentum/adagrad
    # fused kernels at the dispatch-bound batch. FTRL rides along as
    # recorded data only: its row math (sqrt/sign/where chain) is
    # compute-bound, so collapsing three dispatches into one moves it
    # little on this box — the record says so rather than hiding it.
    ratios = [sparse["per_updater"][u]["batch_256"]["fused_over_unfused"]
              for u in ("momentum_sgd", "adagrad")
              if u in sparse["per_updater"]]
    return {
        "adagrad_state_reduction_ge_40pct":
            replicas < 2 or ada.get("state_reduction_pct", 0) >= 40.0,
        "sharded_capacity_gain_gt_1":
            replicas < 2 or ada.get("capacity_gain", 0) > 1.0,
        "sharded_params_bitwise":
            parity.get("params_bitwise", True),
        "fused_over_unfused_ge_1_3":
            bool(ratios) and min(ratios) >= 1.3,
        "pallas_fused_bitwise_vs_xla":
            sparse.get("pallas_fused", {}).get("bitwise_vs_xla", False),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dry-run", action="store_true",
                    help="tiny shapes; tier-1 smoke (witnesses asserted)")
    ap.add_argument("--out", default=None,
                    help="record path (default BENCH_STATE.json at the "
                    "repo root on full runs; dry runs only write when "
                    "--out is given)")
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    _log(f"backend: {dev.platform} x {len(jax.devices())}")

    mem = bench_state_memory(args.dry_run)
    sparse = bench_stateful_sparse(args.dry_run)
    parity = bench_sharded_parity_witness(args.dry_run)
    witnesses = check_witnesses(mem, sparse, parity)

    try:
        rev = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             capture_output=True, text=True,
                             cwd=_HERE).stdout.strip() or "?"
    except OSError:     # no git here (the chip tool's copy is not a repo)
        rev = "?"
    record = {
        "metric": "state_sharding_bench", "schema": 1,
        "dry_run": bool(args.dry_run),
        "platform": dev.platform, "cpu_cores": os.cpu_count(),
        "date": time.strftime("%Y-%m-%d %H:%M UTC", time.gmtime()),
        "git": rev,
        "state_memory": mem, "stateful_sparse": sparse,
        "sharded_parity": parity, "witnesses": witnesses,
    }
    out_path = args.out
    if out_path is None and not args.dry_run:
        out_path = os.path.join(_HERE, "BENCH_STATE.json")
    if out_path:
        with open(out_path, "w") as f:
            json.dump(record, f, indent=1)
        _log(f"record written: {out_path}")
    print(json.dumps(record))
    gating = dict(witnesses)
    if args.dry_run:
        # The dispatch-fusion ratio is a timing claim: full runs gate the
        # committed record on it, but a smoke on a loaded CI box must not
        # fail tier-1 over a wall-clock dip (parity/memory witnesses are
        # deterministic and always gate).
        gating.pop("fused_over_unfused_ge_1_3", None)
    if not all(gating.values()):
        _log(f"WITNESS FAILURE: {witnesses}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
