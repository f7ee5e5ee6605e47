"""Pallas grid-resident sg-ns chunk loop: interpret-mode numerics on CPU.

The kernel's contract (ISSUE 2 tentpole) is that swapping the chunk-loop
execution NEVER changes training semantics: the sequential grid with
VMEM-resident tables must reproduce the jitted in-graph ``fori_loop`` and
the host-dispatched chunk chain bitwise. These tests pin that at the
kernel level; the end-to-end three-way test lives in test_word2vec.py."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from multiverso_tpu.ops.pallas_sgns import build_sgns_grid_step


def _tables(V, D, dtype=jnp.float32, seed=1):
    w = jnp.asarray(np.random.default_rng(seed)
                    .normal(size=(V, D)).astype(np.float32)).astype(dtype)
    return [w, jnp.zeros((V, D), dtype),
            jnp.zeros((V, D), jnp.float32), jnp.zeros((V, D), jnp.float32)]


def _streams(V, C, K, N, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.integers(0, V, (N, C)).astype(np.int32)),
            jnp.asarray(rng.integers(0, V, (N, C)).astype(np.int32)),
            jnp.asarray(rng.integers(0, V, (N, C, K)).astype(np.int32)))


def _fori_reference(adagrad, V, C, K, N, streams, n_pairs, lr, dtype):
    """The in-graph formulation the kernel must match bitwise."""
    from multiverso_tpu.models.word2vec.model import raw_sg_ns_step
    raw = raw_sg_ns_step(adagrad)
    centers, contexts, negs = streams

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def loop(w_in, w_out, g_in, g_out):
        lane = jnp.arange(C)

        def body(i, carry):
            *t, loss = carry
            m = ((i * C + lane) < n_pairs).astype(jnp.float32)
            out = raw(*t, centers[i], contexts[i], negs[i], m, lr)
            return (*out[:4], loss + out[4])

        return jax.lax.fori_loop(
            0, N, body, (w_in, w_out, g_in, g_out, jnp.float32(0)))

    return loop(*_tables(V, 16, dtype))


@pytest.mark.parametrize("adagrad", [True, False])
def test_grid_step_matches_fori_bitwise(adagrad):
    """Full chunks + a partially masked tail: bitwise-identical tables and
    an identical loss against the jitted in-graph loop."""
    V, D, C, K, N = 64, 16, 8, 3, 4
    streams = _streams(V, C, K, N)
    n_pairs = jnp.int32(N * C - 5)
    lr = jnp.float32(0.05)
    ref = _fori_reference(adagrad, V, C, K, N, streams, n_pairs, lr,
                          jnp.float32)
    step = build_sgns_grid_step(chunk=C, negative=K, adagrad=adagrad,
                                interpret=True)
    got = step(*_tables(V, D), *streams, n_pairs, lr)
    for r, g in zip(ref[:4], got[:4]):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(g))
    np.testing.assert_allclose(float(got[4]), float(ref[4]), rtol=1e-6)
    assert np.isfinite(float(got[4]))


def test_grid_step_dead_chunks_are_noops():
    """n_pairs masking: chunks past the live count must leave the tables
    bitwise untouched (the static grid may contain all-padding chunks that
    the in-graph dynamic-trip loop never runs)."""
    V, D, C, K, N = 32, 16, 8, 2, 3
    streams = _streams(V, C, K, N, seed=2)
    lr = jnp.float32(0.1)
    step = build_sgns_grid_step(chunk=C, negative=K, adagrad=True,
                                interpret=True)
    live = step(*_tables(V, D), *streams, jnp.int32(C), lr)       # 1 chunk
    # Same single live chunk, but the grid sweeps two extra dead chunks.
    dead = step(*_tables(V, D), *streams[:2], streams[2],
                jnp.int32(C), lr)
    for a, b in zip(live[:4], dead[:4]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # Zero live pairs: the whole sweep is a numerical no-op.
    base = _tables(V, D)
    out = step(*[jnp.array(t) for t in base], *streams, jnp.int32(0), lr)
    for a, b in zip(base, out[:4]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(out[4]) == 0.0


def test_grid_step_bfloat16_tables():
    """bf16 embedding storage (f32 accumulators/math) through the kernel
    matches the in-graph loop bitwise."""
    V, D, C, K, N = 48, 16, 8, 2, 3
    streams = _streams(V, C, K, N, seed=3)
    n_pairs = jnp.int32(N * C)
    lr = jnp.float32(0.05)
    ref = _fori_reference(True, V, C, K, N, streams, n_pairs, lr,
                          jnp.bfloat16)
    step = build_sgns_grid_step(chunk=C, negative=K, adagrad=True,
                                interpret=True)
    got = step(*_tables(V, D, jnp.bfloat16), *streams, n_pairs, lr)
    assert got[0].dtype == jnp.bfloat16
    for r, g in zip(ref[:4], got[:4]):
        np.testing.assert_array_equal(
            np.asarray(r).view(np.uint16) if r.dtype == jnp.bfloat16
            else np.asarray(r),
            np.asarray(g).view(np.uint16) if g.dtype == jnp.bfloat16
            else np.asarray(g))
