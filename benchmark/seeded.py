"""Weights and table rows from ``--seed``: a counter-based hash with a jax twin
(whole tables, made on the device in one jitted call) and a NumPy twin (any
rows, made on the host), bit for bit the same.

Element ``i = row * cols + col`` of stream ``s`` under seed ``k`` is
``fmix32(i * GOLD + key(k, s))`` (murmur3's finalizer), reduced to a multiple
of 2**-24 in [0, 1). ``centered`` values are ``(u - 0.5) * scale`` and
``positive`` ones ``u * scale``: one rounding each, so XLA and NumPy agree to
the bit and the plain references can make the rows they need without holding
a table, and without taking anything the program made.
"""
from __future__ import annotations

import numpy as np

_GOLD = 0x9E3779B1
_M1, _M2 = 0x85EBCA6B, 0xC2B2AE35


def _fmix32_py(x: int) -> int:
    x &= 0xFFFFFFFF
    x ^= x >> 16
    x = (x * _M1) & 0xFFFFFFFF
    x ^= x >> 13
    x = (x * _M2) & 0xFFFFFFFF
    x ^= x >> 16
    return x


def stream_key(seed: int, stream: int) -> int:
    """32-bit key of one stream (a table, a layer's weights) under a seed of
    any size: both halves of the seed and the stream number are mixed in."""
    seed = int(seed)
    lo, hi = seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF
    return _fmix32_py(lo ^ _fmix32_py(hi ^ _fmix32_py(int(stream) + 1)))


def _fmix32(x, xp):
    x = x ^ (x >> xp.uint32(16))
    x = x * xp.uint32(_M1)
    x = x ^ (x >> xp.uint32(13))
    x = x * xp.uint32(_M2)
    return x ^ (x >> xp.uint32(16))


def _unit(index_u32, key: int, xp):
    h = _fmix32(index_u32 * xp.uint32(_GOLD) + xp.uint32(key), xp)
    return (h >> xp.uint32(8)).astype(xp.float32) * xp.float32(2.0 ** -24)


def _shape_values(unit, scale: float, kind: str, xp):
    if kind == "centered":
        return (unit - xp.float32(0.5)) * xp.float32(scale)
    if kind == "positive":
        return unit * xp.float32(scale)
    raise ValueError(f"unknown kind {kind!r}")


def rows_np(seed: int, stream: int, rows, cols: int, scale: float,
            kind: str = "centered") -> np.ndarray:
    """float32 [len(rows), cols]: those rows of the seeded table, on the host."""
    rows = np.asarray(rows).astype(np.uint32)
    idx = rows[:, None] * np.uint32(cols) + np.arange(cols, dtype=np.uint32)
    with np.errstate(over="ignore"):
        unit = _unit(idx, stream_key(seed, stream), np)
    return _shape_values(unit, scale, kind, np)


_TABLE_FNS: dict = {}


def table_jax(seed: int, stream: int, shape, scale: float,
              kind: str = "centered", sharding=None, live_rows=None):
    """The whole seeded table as one jitted on-device call. ``shape`` may be
    the store's padded shape: rows from ``live_rows`` on are zero, as the
    program pads them. The key is an argument of the jitted function, so one
    compiled program serves every seed."""
    import jax
    import jax.numpy as jnp

    rows, cols = int(shape[0]), int(shape[1])
    if rows * cols >= 2 ** 32:
        raise ValueError("table too large for a 32-bit element counter")
    live = rows if live_rows is None else min(int(live_rows), rows)
    sig = (rows, cols, float(scale), kind, live, sharding)
    if sig not in _TABLE_FNS:
        def make(key):
            r = jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 0)
            c = jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 1)
            h = _fmix32((r * jnp.uint32(cols) + c) * jnp.uint32(_GOLD) + key,
                        jnp)
            unit = (h >> jnp.uint32(8)).astype(jnp.float32) \
                * jnp.float32(2.0 ** -24)
            vals = _shape_values(unit, scale, kind, jnp)
            if live < rows:
                vals = jnp.where(r < jnp.uint32(live), vals, 0.0)
            return vals
        _TABLE_FNS[sig] = jax.jit(make, out_shardings=sharding)
    return _TABLE_FNS[sig](np.uint32(stream_key(seed, stream)))


def reseed_store(store, seed: int, stream: int, scale: float, kind: str,
                 live_rows: int) -> None:
    """Replace a ``ServerStore``'s table with the seeded one, in its current
    shape and sharding. The old buffer is waited for (the store makes it on
    the host and its transfer may still be on its way: a table dropped early
    keeps its memory and the link busy well into the window) and freed first,
    so that seeding never holds two copies of a table and the peak stays the
    program's."""
    import jax
    old = store.data
    shape, sharding = old.shape, old.sharding
    jax.block_until_ready(old)
    store.data = None
    old.delete()
    store.data = table_jax(seed, stream, shape, scale, kind, sharding,
                           live_rows)
