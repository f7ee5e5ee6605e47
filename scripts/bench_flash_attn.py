#!/usr/bin/env python
"""On-chip timing: ring attention's local block step, XLA vs the Pallas
flash kernel (ops/pallas_attention.py) — the adoption decision for the
``-flash_attention`` flag (same two-tier protocol as the scatter
kernels: correctness proven in interpret mode by
tests/test_pallas_attention.py; this script produces the chip numbers).

Run ON the chip:  python scripts/bench_flash_attn.py
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> None:
    import jax
    import jax.numpy as jnp

    from multiverso_tpu.ops import pallas_interpret
    from multiverso_tpu.ops.pallas_attention import flash_block_attn
    from multiverso_tpu.parallel.sequence import _block_attn

    backend = jax.devices()[0].platform
    interpret = pallas_interpret(jax.devices())
    print(f"backend: {backend} (interpret={interpret})")
    rng = np.random.default_rng(0)
    # Ring-step shapes: per-device S/n blocks at long-context scale.
    # Interpret mode (CPU smoke) runs one tiny shape — the interpreter
    # executes grid steps in Python, so chip shapes would take minutes.
    shapes = ((1, 8, 2048, 128), (1, 8, 4096, 128), (2, 16, 2048, 64)) \
        if not interpret else ((1, 2, 256, 64),)
    iters = 20 if not interpret else 2
    for (B, H, S, D) in shapes:
        q = jnp.asarray(rng.normal(size=(B, H, S, D)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(B, H, S, D)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(B, H, S, D)).astype(np.float32))
        scale = float(1.0 / np.sqrt(D))

        # One jit per benchmarked shape is the point: each (B,H,S,D) needs
        # its own executable and compile time is excluded from the timing.
        xla = jax.jit(lambda a, b, c: _block_attn(a, b, c, scale))  # graftlint: disable=retrace-hazard
        jax.block_until_ready(xla(q, k, v))
        t0 = time.perf_counter()
        for _ in range(iters):
            out = xla(q, k, v)
        jax.block_until_ready(out)
        xla_ms = (time.perf_counter() - t0) / iters * 1e3

        jax.block_until_ready(
            flash_block_attn(q, k, v, scale=scale, interpret=interpret))
        t0 = time.perf_counter()
        for _ in range(iters):
            out = flash_block_attn(q, k, v, scale=scale,
                                   interpret=interpret)
        jax.block_until_ready(out)
        fl_ms = (time.perf_counter() - t0) / iters * 1e3

        print(f"B{B} H{H} S{S} D{D}: XLA {xla_ms:.3f} ms "
              f"vs flash {fl_ms:.3f} ms ({xla_ms / max(fl_ms, 1e-9):.2f}x)")


if __name__ == "__main__":
    main()
