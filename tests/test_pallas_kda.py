"""The delta rule's kernels (``ops/pallas_kda.py``) under the Pallas
interpreter at the smallest shapes they take (heads of 128, chunks of 64 and
32): the output and all five gradients against the ``jax.numpy`` body of
``kda.kda_chunked`` and against the recurrence run token by token, a gate at
the published bound on every step, the kernel's inverse against the body's,
and the rule that chooses them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from multiverso_tpu.models.hybrid_lm import kda
from multiverso_tpu.ops import pallas_kda
from multiverso_tpu.ops.pallas_kda import kda_kernel_selected

WIDTH = 128
BOUND = -5.0            # kda_lower_bound, as published
LENGTHS = {"one_chunk": 64, "padded_tail": 150}
NAMES = ("q", "k", "v", "g", "beta")


def inputs(length: int, heads: int, bsz: int = 1, width: int = WIDTH,
           at_bound: bool = False):
    """``at_bound``: the gate's logits so large that every step decays by
    the bound."""
    rng = np.random.default_rng(length + heads)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape, dtype=np.float32))

    q = kda.l2_normalised(normal(bsz, length, heads, width)) * width ** -0.5
    k = kda.l2_normalised(normal(bsz, length, heads, width))
    g = BOUND * jax.nn.sigmoid(2.0 * normal(bsz, length, heads, width)
                               + 30.0 * at_bound)
    return (q, k, normal(bsz, length, heads, width), g,
            jax.nn.sigmoid(normal(bsz, length, heads))), \
        normal(bsz, length, heads, width)


def recurrence(q, k, v, g, beta):
    """``S_t = (I - b_t k_t k_t^T) Diag(exp g_t) S_{t-1} + b_t k_t v_t^T``,
    ``o_t = S_t^T q_t``, a token at a time."""
    def token(s, now):
        q_t, k_t, v_t, g_t, b_t = now               # [B, H, D], .., [B, H]
        s = jnp.exp(g_t)[..., None] * s
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t))
        s = s + k_t[..., None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    start = jnp.zeros(q.shape[:1] + q.shape[2:] + v.shape[-1:], q.dtype)
    return jnp.moveaxis(jax.lax.scan(token, start, tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))[1], 0, 1)


def close(got, want, tol, what=""):
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 0, what
    assert float(jnp.max(jnp.abs(got - want))) <= tol * scale, what


def kernel(*t, chunk=64):
    return kda.kda_chunked(*t, chunk, interpret=True)


def body(*t, chunk=64):
    return kda.kda_chunked(*t, chunk)


def grads(fn, t, w):
    return jax.jit(jax.grad(lambda *t: jnp.sum(fn(*t) * w),
                            argnums=(0, 1, 2, 3, 4)))(*t)


@pytest.mark.parametrize("heads", [1, 2, 4])     # four: one grid step's
@pytest.mark.parametrize("length", sorted(LENGTHS))
def test_forward_matches_the_body_and_the_recurrence(length, heads):
    t, _ = inputs(LENGTHS[length], heads)
    assert "pallas_call" in str(jax.make_jaxpr(kernel)(*t))
    o = jax.jit(kernel)(*t)
    assert o.shape == t[2].shape and bool(jnp.all(jnp.isfinite(o)))
    # the running sum inside a chunk is taken in another order
    close(o, jax.jit(body)(*t), 2e-5, "body")
    close(o, jax.jit(recurrence)(*t), 5e-5, "recurrence")


@pytest.mark.parametrize("heads", [1, 4])
@pytest.mark.parametrize("length", sorted(LENGTHS))
def test_gradients_match_the_body_and_the_recurrence(length, heads):
    t, w = inputs(LENGTHS[length], heads)
    got, of_body, of_recurrence = (grads(fn, t, w)
                                   for fn in (kernel, body, recurrence))
    for name, g, want_b, want_r in zip(NAMES, got, of_body, of_recurrence):
        assert g.shape == want_b.shape, name
        # float32 sums in another order: a fault reads about 1
        close(g, want_b, 1e-4, name + " against the body")
        close(g, want_r, 2e-4, name + " against the recurrence")


def test_a_chunk_of_two_sub_chunks():
    t, w = inputs(80, 1)
    small = {"chunk": 32}
    close(jax.jit(lambda *t: kernel(*t, **small))(*t),
          jax.jit(recurrence)(*t), 5e-5)
    for name, g, want in zip(NAMES, grads(lambda *t: kernel(*t, **small), t,
                                          w), grads(recurrence, t, w)):
        close(g, want, 2e-4, name)


def test_a_factored_decay_would_overflow_where_the_kernel_is_exact():
    """Every step at the bound: a chunk's running sum reaches -320, and
    ``exp(320)`` is no float32; the kernel's differences from the middle of a
    sub-chunk stay inside, and the gate's gradient is not flushed."""
    with np.errstate(over="ignore"):
        assert not np.isfinite(np.exp(np.float32(-BOUND * 64)))
    t, w = inputs(128, 1, at_bound=True)
    assert float(jnp.max(t[3])) < BOUND + 1e-3
    o = jax.jit(kernel)(*t)
    assert bool(jnp.all(jnp.isfinite(o)))
    close(o, jax.jit(recurrence)(*t), 5e-5)
    # a state that forgets e^-5 a step: a token's output is its own write's
    q, k, v, _, beta = t
    own = (beta * jnp.sum(q * k, axis=-1))[..., None] * v
    close(o, own, 0.05, "own token")
    got, want = grads(kernel, t, w), grads(recurrence, t, w)
    for name, g, r in zip(NAMES, got, want):
        assert bool(jnp.all(jnp.isfinite(g))), name
        close(g, r, 2e-4, name)
    assert float(jnp.max(jnp.abs(got[3]))) > 0


def test_sequences_of_a_batch_do_not_mix():
    t, _ = inputs(150, 2, bsz=2)
    o = jax.jit(kernel)(*t)
    for i in range(2):
        alone = jax.jit(kernel)(*(u[i:i + 1] for u in t))
        np.testing.assert_array_equal(o[i:i + 1], alone)


def test_a_shape_the_rule_refuses_takes_the_body_bit_for_bit():
    t, w = inputs(100, 2, width=64)
    assert "pallas_call" not in str(jax.make_jaxpr(kernel)(*t))
    np.testing.assert_array_equal(jax.jit(kernel)(*t), jax.jit(body)(*t))
    for got, want in zip(grads(kernel, t, w), grads(body, t, w)):
        np.testing.assert_array_equal(got, want)
    # and with no device known, the kernels' own shapes too
    t, _ = inputs(100, 1)
    assert "pallas_call" not in str(jax.make_jaxpr(body)(*t))


@pytest.mark.parametrize("chunk", [32, 64, 128])
def test_the_kernels_inverse_is_the_bodys(chunk):
    rng = np.random.default_rng(chunk)
    # beta k_i . k_j of unit keys: well under one
    n = jnp.tril(jnp.asarray(rng.uniform(-0.3, 0.3, (chunk, chunk)),
                             jnp.float32), -1)
    at, to = pallas_kda._square(chunk)
    got = jax.jit(pallas_kda.unit_lower_inverse)(n, at, to)
    want = kda._unit_lower_inverse(n)
    close(got, want, 1e-5)
    close((jnp.eye(chunk) + n) @ got, jnp.eye(chunk), 1e-4)
    assert float(jnp.max(jnp.abs(jnp.triu(got, 1)))) == 0.0


@pytest.mark.parametrize("chunk,d_k,d_v,heads,dtype,taken", [
    (64, 128, 128, 8, np.float32, True),        # ling3_train's head groups
    (64, 128, 128, 1, np.float32, True),
    (32, 128, 128, 4, np.float32, True),
    (128, 128, 128, 4, np.float32, True),
    (64, 128, 128, 8, jnp.bfloat16, False),
    (64, 64, 64, 8, np.float32, False),         # half a lane tile
    (64, 256, 128, 8, np.float32, False),       # keys over two tiles
    (64, 128, 256, 8, np.float32, False),
    (16, 128, 128, 8, np.float32, False),       # one sub-chunk a chunk
    (256, 128, 128, 8, np.float32, False),      # a plane over a lane tile
    (32, 16, 16, 4, np.float32, False),         # the CPU tests' tiny models
])
def test_the_rule(chunk, d_k, d_v, heads, dtype, taken):
    assert kda_kernel_selected(chunk, d_k, d_v, heads, dtype) is taken
    assert kda_kernel_selected(chunk, d_k, d_v, heads, np.float32,
                               dtype) is taken
