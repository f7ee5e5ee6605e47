"""The scan kernels (``ops/pallas_ssd.py``) under the Pallas interpreter at
the smallest shapes they take (chunk 128, state 128): the output and all
five gradients against the ``jax.numpy`` body of ``mamba2.ssd_chunked`` and
against the recurrence run token by token, in Mamba-2's grouping (heads
that share a group's ``B`` and ``C``, two to a lane tile, steps that depend
on the input, the ``D x`` skip) and in Lightning's (a group a head, a lane
tile a head, steps of one and no ``dt``, no skip, the cell's steepest
decay); and the rule that chooses them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from multiverso_tpu.models.hybrid_lm.mamba2 import ssd_chunked
from multiverso_tpu.ops.pallas_ssd import scan_kernel_selected

CHUNK = 128
#: Lightning's fastest held head in the cell: h = 2 of 32 at a layer factor
#: of one decays by 2 ** -0.5 a position.
STEEPEST = 2.0 ** -0.5
GROUPINGS = {
    # groups, heads a group, head width, steps
    "mamba2": (2, 2, 64, "input"),
    "lightning": (2, 1, 128, "ones"),
}
LENGTHS = {"one_chunk": 128, "several": 384, "padded_tail": 300}


def inputs(grouping: str, length: int, state: int = 128, bsz: int = 1):
    groups, per_group, width, steps = GROUPINGS[grouping]
    heads = groups * per_group
    rng = np.random.default_rng(length + heads)

    def normal(*shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape, dtype=np.float32)
                           * np.float32(scale))

    x = normal(bsz, length, heads, width)
    b, c = (normal(bsz, length, groups, state, scale=0.3) for _ in range(2))
    if steps == "ones":
        dt, skip = None, None
        a = -jnp.asarray([STEEPEST, 0.02][:heads], jnp.float32)
    else:
        dt = jnp.asarray(rng.uniform(0.001, 0.1, (bsz, length, heads)),
                         jnp.float32)
        a = -jnp.asarray(rng.uniform(1.0, 16.0, heads), jnp.float32)
        skip = normal(heads)
    return (x, dt, a, b, c, skip), normal(bsz, length, heads, width)


def recurrence(x, dt, a, b, c, skip):
    """``h_t = exp(dt_t a) h_{t-1} + dt_t x_t B_t^T``, ``y_t = h_t C_t + D
    x_t``, a token at a time."""
    per_group = x.shape[2] // b.shape[2]
    dt = jnp.ones(x.shape[:3], x.dtype) if dt is None else dt
    b, c = (jnp.repeat(t, per_group, axis=2) for t in (b, c))

    def token(h, now):
        x_t, dt_t, b_t, c_t = now                   # [B, H, P], [B, H], ..
        h = jnp.exp(dt_t * a)[..., None, None] * h \
            + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :]
        return h, jnp.sum(h * c_t[..., None, :], axis=-1)

    start = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:], x.dtype)
    _, y = jax.lax.scan(token, start, tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c)))
    y = jnp.moveaxis(y, 0, 1)
    return y if skip is None else y + x * skip[:, None]


def close(got, want, tol, what=""):
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 0, what
    assert float(jnp.max(jnp.abs(got - want))) <= tol * scale, what


def kernel(*t):
    return ssd_chunked(*t[:5], CHUNK, interpret=True, skip=t[5])


def body(*t):
    return ssd_chunked(*t[:5], CHUNK, skip=t[5])


@pytest.mark.parametrize("length", sorted(LENGTHS))
@pytest.mark.parametrize("grouping", sorted(GROUPINGS))
def test_forward_matches_the_body_and_the_recurrence(grouping, length):
    t, _ = inputs(grouping, LENGTHS[length])
    y = jax.jit(kernel)(*t)
    assert y.shape == t[0].shape and bool(jnp.all(jnp.isfinite(y)))
    # the running sum inside a chunk is taken in another order
    close(y, jax.jit(body)(*t), 1e-5, "body")
    close(y, jax.jit(recurrence)(*t), 2e-5, "recurrence")


@pytest.mark.parametrize("length", sorted(LENGTHS))
@pytest.mark.parametrize("grouping", sorted(GROUPINGS))
def test_gradients_match_the_body_and_the_recurrence(grouping, length):
    t, w = inputs(grouping, LENGTHS[length])

    def grads(fn):
        return jax.jit(jax.grad(lambda *t: jnp.sum(fn(*t) * w),
                                argnums=(0, 1, 2, 3, 4, 5)))(*t)

    got, of_body, of_recurrence = grads(kernel), grads(body), \
        grads(recurrence)
    for name, g, want_b, want_r in zip(("x", "dt", "a", "b", "c", "skip"),
                                       got, of_body, of_recurrence):
        if g is None:                   # Lightning: no dt, no skip
            assert want_b is None and grouping == "lightning", name
            continue
        assert g.shape == want_b.shape, name
        # float32 sums in another order: a fault reads about 1
        close(g, want_b, 1e-4, name + " against the body")
        close(g, want_r, 1e-4, name + " against the recurrence")


def test_a_factored_decay_would_overflow_where_the_kernel_is_exact():
    """Over a chunk the steepest head's running sum reaches -90.5:
    ``exp(90.5)`` is no float32, the masked difference never leaves [0, 1]."""
    with np.errstate(over="ignore"):
        assert not np.isfinite(np.exp(np.float32(STEEPEST * CHUNK)))
    t, _ = inputs("lightning", 256)
    y = jax.jit(kernel)(*t)
    assert bool(jnp.all(jnp.isfinite(y)))
    # the steep head forgets: its output is its own token's almost alone
    x, _, _, b, c, _ = t
    own = jnp.sum(b[0, :, 0] * c[0, :, 0], axis=-1)[:, None] * x[0, :, 0]
    close(y[0, :, 0], own, 0.8, "steepest head")


def test_sequences_of_a_batch_do_not_mix():
    t, _ = inputs("mamba2", 256, bsz=2)
    y = jax.jit(kernel)(*t)
    for i in range(2):
        alone = jax.jit(kernel)(*(u[i:i + 1] if u.ndim > 2 else u for u in t))
        np.testing.assert_array_equal(y[i:i + 1], alone)


def test_a_shape_the_rule_refuses_takes_the_body_bit_for_bit():
    t, w = inputs("mamba2", 200, state=16)
    assert "pallas_call" not in str(jax.make_jaxpr(kernel)(*t))
    assert "pallas_call" in str(jax.make_jaxpr(kernel)(
        *inputs("mamba2", 200)[0]))
    np.testing.assert_array_equal(jax.jit(kernel)(*t), jax.jit(body)(*t))
    grad = [jax.jit(jax.grad(lambda *t: jnp.sum(fn(*t) * w),
                             argnums=(0, 1, 2, 3, 4, 5)))(*t)
            for fn in (kernel, body)]
    for got, want in zip(*grad):
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("chunk,state,heads,width,dtype,taken", [
    (128, 128, 8, 64, np.float32, True),        # nemotron_train's Mamba-2
    (128, 128, 1, 128, np.float32, True),       # sala_train's Lightning
    (256, 128, 2, 256, np.float32, True),
    (128, 128, 8, 64, jnp.bfloat16, False),
    (64, 128, 8, 64, np.float32, False),
    (128, 16, 8, 64, np.float32, False),
    (128, 128, 1, 64, np.float32, False),       # half a lane tile
    (128, 128, 4, 96, np.float32, False),       # a head across a tile's edge
    (8, 16, 2, 16, np.float32, False),          # the CPU tests' tiny models
])
def test_the_rule(chunk, state, heads, width, dtype, taken):
    assert scan_kernel_selected(chunk, state, heads, width, dtype) is taken
