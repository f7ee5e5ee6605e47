"""Model runners behind the serving batcher.

Two workloads, one contract (:class:`ServingRunner`): the batcher hands a
bucket-padded ``(max_batch, bucket)`` payload matrix + per-row lengths, the
runner returns a batch-leading result array and slices per-request rows
out of it. Every runner compiles EXACTLY one executable per bucket — the
batch dimension is fixed, the bucket ladder fixes the payload dimension,
and parameters travel as jit ARGUMENTS (never closures) so a replica
hot-swap can rebind weights without retracing.

* :class:`SparseLookupRunner` — embedding/parameter row lookup straight
  from a LIVE :class:`~multiverso_tpu.core.table.ServerStore` shard. Reads
  dispatch under the store's donation guard, so a batch is one consistent
  snapshot of the table and the values are bitwise-equal to a direct
  ``table.get`` of the same rows at the same clock (the serving plane
  never sees a torn update).
* :class:`ReplicaLookupRunner` — the same lookup against a FROZEN
  checkpoint replica (``serving/replica.py``): zero contention with
  training, hot-swapped between batches.
* :class:`AttentionLMRunner` — greedy decode for ``models/attention_lm``
  checkpoints with a PREALLOCATED per-bucket KV-cache: prefill writes the
  prompt's K/V once, the decode loop runs as one ``lax.scan`` attending
  into the cache, and the cache buffers are donated back to themselves
  call-over-call (no per-request allocation).
"""

from __future__ import annotations

import functools
import threading
from typing import Callable, Dict, Optional, Protocol, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from multiverso_tpu.core.table import ServerStore
from multiverso_tpu.serving.cache import HotRowCache
from multiverso_tpu.serving.paged import PagePool, page_plan, pages_of
from multiverso_tpu.serving.quant import (decode_rows, encode_rows,
                                          storage_dtype)
from multiverso_tpu.telemetry.sketch import record_keys
from multiverso_tpu.utils.log import check
from multiverso_tpu.utils.locks import make_lock

class ServingRunner(Protocol):
    """What the batcher needs from a model runner."""

    name: str
    payload_dtype: np.dtype
    pad_id: int

    def run(self, batch: np.ndarray, lengths: np.ndarray) -> np.ndarray:
        """``batch`` is ``(max_batch, bucket)`` padded payloads, ``lengths``
        the real payload length per row (0 = padding row). Returns an
        array whose leading dim is ``max_batch``."""
        ...

    def slice_result(self, out: np.ndarray, i: int, length: int):
        """Extract request ``i``'s reply from the batch result."""
        ...

    def jit_cache_size(self) -> int:
        """Compiled-executable count — the no-retrace contract's witness
        (== number of distinct buckets exercised)."""
        ...

    # Optional two-phase contract (serving/pipeline.py): ``dispatch``
    # launches the device work WITHOUT syncing and returns an opaque
    # handle; ``collect(handle)`` blocks and returns what ``run`` would
    # have. Runners that implement both ride the depth-N dispatch
    # pipeline; ``run`` stays as dispatch+collect for warmup and the
    # serialized fallback. ``try_cached(payload)`` (optional) may answer
    # a request host-side at admission (hot-row cache) — None means
    # "take the device path".


def _batch_keys(batch: np.ndarray, lengths: np.ndarray) -> np.ndarray:
    """The REAL keys of a padded batch (pad rows/columns excluded) — what
    the traffic sketch must see: pad id 0 is a legitimate row id, so the
    stream is cut by lengths, never by value."""
    parts = [batch[i, :int(n)] for i, n in enumerate(lengths) if n]
    if not parts:
        return np.empty(0, dtype=batch.dtype)
    return np.concatenate(parts) if len(parts) > 1 else parts[0]


def _make_gather():
    """A fresh jitted gather per runner. The closure matters: jax's jit
    cache is keyed by the underlying function object, so a shared
    module-level fn would pool every runner's executables into one cache
    and break the per-runner one-executable-per-bucket accounting."""
    def gather(data, ids):
        # mode="clip" mirrors ServerStore's access_rows kernel exactly: a
        # pad id of 0 gathers row 0, which the per-request slice discards.
        return jnp.take(data, ids, axis=0, mode="clip")
    return jax.jit(gather)


def _make_dequant_gather():
    """Gather with the storage decode FUSED in (quantized replica
    tables): int8 rows dequantize against their per-row absmax scale,
    bf16 upcasts, and the full-precision copy only ever exists at the
    gathered-batch size — never table size."""
    def gather(data, scale, ids):
        rows = jnp.take(data, ids, axis=0, mode="clip") \
            .astype(jnp.float32)
        if scale is not None:
            rows = rows * jnp.take(scale, ids, axis=0, mode="clip")
        return rows
    return jax.jit(gather)


class SparseLookupRunner:
    """Row lookup served from a live ServerStore shard.

    ``row_offset`` maps GLOBAL row ids to this shard's local rows (the
    same offset arithmetic the DCN tables route by); ``clock_fn`` (e.g.
    ``sync_coordinator.clock``) stamps each batch with the snapshot
    version it was served at."""

    name = "lookup"
    payload_dtype = np.int32
    pad_id = 0

    def __init__(self, store: ServerStore, row_offset: int = 0,
                 clock_fn: Optional[Callable[[], Tuple[float, float]]]
                 = None, cache: Optional[HotRowCache] = None):
        check(len(store.padded_shape) == 2,
              "SparseLookupRunner serves 2-D row tables")
        self.store = store
        self.row_offset = int(row_offset)
        self._clock_fn = clock_fn
        self.cache = cache
        self._gather = _make_gather()
        self.last_clock: float = -1.0

    def current_clock(self) -> float:
        """The live BSP clock (host read, no device work) — what stamps
        cache entries and decides cache freshness."""
        if self._clock_fn is None:
            return -1.0
        return float(self._clock_fn()[0])

    def try_cached(self, payload: np.ndarray) -> Optional[np.ndarray]:
        """Host-side answer for a fully-hot request (every key cached
        within the staleness bound); None sends it down the device path.

        A LIVE table without a clock (async mode, no SyncCoordinator)
        never serves from cache: with no version to age entries by,
        training writes would be masked forever — the staleness bound
        is only meaningful against the BSP clock."""
        if self.cache is None or payload.size == 0 \
                or self._clock_fn is None:
            return None
        return self.cache.get_rows(payload, self.current_clock())

    # -- two-phase dispatch (serving/pipeline.py contract) -----------------
    def dispatch(self, batch: np.ndarray, lengths: np.ndarray):
        # Stamp BEFORE the gather: the snapshot the guarded gather
        # captures is at-or-after this clock, so a cache entry is never
        # stamped NEWER than its data (reading after would let a tick
        # landing mid-dispatch relabel clock-c rows as c+1, and a
        # staleness-0 hit would then serve stale bytes as fresh). The
        # conservative stamp only costs an early refetch.
        clock = self.current_clock()
        # Device-path half of the key stream (cache hits record at the
        # cache): hot-key sketch, docs/OBSERVABILITY.md "Data-plane load".
        keys = _batch_keys(batch, lengths)
        record_keys("serve.lookup", keys,
                    keys.size * int(self.store.padded_shape[1]) * 4)
        flat = (batch.astype(np.int64) - self.row_offset).reshape(-1)
        # Negative ids (pad rows under a nonzero offset) clip to row 0.
        flat = np.maximum(flat, 0).astype(np.int32)
        values = self.store.read_rows_with(self._gather, flat)
        return values, clock, batch, lengths.copy()

    def collect(self, handle) -> np.ndarray:
        values, clock, batch, lengths = handle
        values = np.asarray(values)         # the device sync
        out = values.reshape(batch.shape[0], batch.shape[1], -1)
        # FIFO collection order (pipeline contract) keeps last_clock
        # monotone with delivery order.
        self.last_clock = clock
        # Populate only under a clock: clockless live entries could
        # never be aged out (see try_cached) so caching them is waste.
        if self.cache is not None and self._clock_fn is not None:
            for i in range(len(lengths)):
                n = int(lengths[i])
                if n:
                    self.cache.put_rows(batch[i, :n], out[i, :n], clock)
        return out

    def run(self, batch: np.ndarray, lengths: np.ndarray) -> np.ndarray:
        return self.collect(self.dispatch(batch, lengths))

    def slice_result(self, out: np.ndarray, i: int, length: int):
        return out[i, :length]

    def clock(self) -> float:
        return self.last_clock

    def jit_cache_size(self) -> int:
        return int(self._gather._cache_size())


class ReplicaLookupRunner:
    """Row lookup from a frozen checkpoint replica (``replica.py``).

    Captures one replica snapshot per batch, so a hot-swap between
    batches is atomic from the client's point of view and NEVER blocks:
    readers of the old snapshot finish against the old arrays."""

    name = "replica_lookup"
    payload_dtype = np.int32
    pad_id = 0

    def __init__(self, replica, table: str,
                 cache: Optional[HotRowCache] = None):
        self.replica = replica
        self.table = table
        self.cache = cache
        self._gather = _make_gather()
        self._dq_gather = _make_dequant_gather()
        self.last_clock: float = -1.0

    def current_clock(self) -> float:
        """The replica's checkpoint step: advancing on hot-swap, so a
        swap invalidates older cache entries by arithmetic."""
        return float(self.replica.snapshot().step)

    def try_cached(self, payload: np.ndarray) -> Optional[np.ndarray]:
        if self.cache is None or payload.size == 0:
            return None
        return self.cache.get_rows(payload, self.current_clock())

    # -- two-phase dispatch (serving/pipeline.py contract) -----------------
    def dispatch(self, batch: np.ndarray, lengths: np.ndarray):
        snap = self.replica.snapshot()
        data, scale = snap.storage(self.table)
        keys = _batch_keys(batch, lengths)
        record_keys("serve.lookup", keys,
                    keys.size * int(data.shape[1]) * 4)
        flat = np.clip(batch.reshape(-1), 0, data.shape[0] - 1)
        if scale is None and data.dtype == jnp.float32:
            # f32 storage: EXACTLY the pre-quantization gather (the
            # bitwise-parity contract with direct table rows).
            values = self._gather(data, flat.astype(np.int32))
        else:
            values = self._dq_gather(data, scale, flat.astype(np.int32))
        return values, float(snap.step), batch, lengths.copy()

    def collect(self, handle) -> np.ndarray:
        values, step, batch, lengths = handle
        values = np.asarray(values)         # the device sync
        out = values.reshape(batch.shape[0], batch.shape[1], -1)
        self.last_clock = step
        if self.cache is not None:
            for i in range(len(lengths)):
                n = int(lengths[i])
                if n:
                    self.cache.put_rows(batch[i, :n], out[i, :n], step)
        return out

    def run(self, batch: np.ndarray, lengths: np.ndarray) -> np.ndarray:
        return self.collect(self.dispatch(batch, lengths))

    def slice_result(self, out: np.ndarray, i: int, length: int):
        return out[i, :length]

    def clock(self) -> float:
        return self.last_clock

    def jit_cache_size(self) -> int:
        return int(self._gather._cache_size())


# ---------------------------------------------------------------------------
# Greedy decode with a preallocated KV-cache.
# ---------------------------------------------------------------------------
class AttentionLMRunner:
    """Greedy decode for an ``attention_lm`` checkpoint.

    One jitted ``decode`` per prompt bucket: prefill the prompt (plain
    causal attention — the serving replica is single-host, ring attention
    is a training concern), write K/V into the preallocated cache, then a
    ``lax.scan`` of single-token steps attending into the cache. The
    cache buffers are jit-donated and threaded back into ``self._caches``
    after every call, so steady-state serving allocates nothing."""

    name = "attention_lm"
    payload_dtype = np.int32
    pad_id = 0

    def __init__(self, params: Dict[str, np.ndarray], cfg,
                 max_new: int = 16, max_batch: int = 8,
                 paged: bool = False, kv_dtype: str = "f32",
                 page: int = 16, pool_pages: Optional[int] = None):
        check(cfg.moe_experts == 0 and cfg.pipeline_stages == 0,
              "serving decode supports the flat dense attention_lm layout")
        self.cfg = cfg
        self.max_new = int(max_new)
        self.max_batch = int(max_batch)
        self.paged = bool(paged)
        self.kv_dtype = storage_dtype(kv_dtype)
        self.page = int(page)
        self.pool_pages = pool_pages
        check(self.kv_dtype == "f32" or self.paged,
              "quantized KV storage requires the paged cache")
        self._params = jax.tree.map(jnp.asarray, params)
        self._params_lock = make_lock("serve.runner.params")
        self._params_version = 0
        # bucket -> preallocated (ck, cv): [L, B, H, bucket+max_new, dh]
        self._caches: Dict[int, Tuple[jax.Array, jax.Array]] = {}
        self._decode = jax.jit(self._decode_fn, donate_argnums=(3, 4))
        # Paged drain mode: one shared pool, one executable per bucket.
        self._pool: Optional[PagePool] = None
        self._decode_paged: Dict[int, object] = {}

    def swap_params(self, params: Dict[str, np.ndarray]) -> None:
        """Hot-swap weights (replica handoff). Same pytree structure and
        shapes -> no retrace; the next batch serves the new checkpoint."""
        new = jax.tree.map(jnp.asarray, params)
        with self._params_lock:
            self._params = new
            self._params_version += 1

    def params_ref(self):
        """The current weight pytree under the swap lock — what the
        continuous-batching engine binds per dispatch (a hot-swap lands
        at the next step boundary, never mid-step)."""
        with self._params_lock:
            return self._params

    def params_versioned(self):
        """``(params, version)`` atomically under the swap lock. The
        MONOTONIC version is the prefix store's weights token — object
        identity (``id``) is unsound there: CPython reuses a freed
        dict's address, so after two swaps a stale entry could validate
        against new weights."""
        with self._params_lock:
            return self._params, self._params_version

    def _cache_for(self, bucket: int) -> Tuple[jax.Array, jax.Array]:
        cached = self._caches.get(bucket)
        if cached is not None:
            return cached
        cfg = self.cfg
        shape = (cfg.layers, self.max_batch, cfg.heads,
                 bucket + self.max_new, cfg.dim // cfg.heads)
        return (jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32))

    def _decode_fn(self, params, tokens, lengths, ck, cv):
        """tokens [B, S] right-padded, lengths [B] -> ([B, max_new] greedy
        tokens, ck, cv). Positions: prompt occupies 0..len-1; generated
        token t sits at len+t."""
        from multiverso_tpu.models.attention_lm import _ln, _posenc

        cfg = self.cfg
        B, S = tokens.shape
        H, D = cfg.heads, cfg.dim
        dh = D // H
        N = self.max_new
        scale = jnp.float32(1.0) / jnp.sqrt(jnp.float32(dh))
        lengths = jnp.maximum(lengths, 1)        # pad rows: harmless row 0
        pe = _posenc(S + N, D)

        def heads_of(t, s):
            return t.reshape(B, s, H, dh).transpose(0, 2, 1, 3)

        # -- prefill: full causal pass over the padded prompt --------------
        x = jnp.take(params["embed"], tokens, axis=0) + pe[None, :S]
        causal = jnp.tril(jnp.ones((S, S), bool))[None, None]
        for i in range(cfg.layers):
            h = _ln(x)
            q, k, v = jnp.split(h @ params[f"qkv_{i}"], 3, axis=-1)
            q, k, v = heads_of(q, S), heads_of(k, S), heads_of(v, S)
            ck = jax.lax.dynamic_update_slice(ck, k[None], (i, 0, 0, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, v[None], (i, 0, 0, 0, 0))
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
            probs = jax.nn.softmax(
                jnp.where(causal, scores, -jnp.inf), axis=-1)
            o = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
            x = x + o.transpose(0, 2, 1, 3).reshape(B, S, D) \
                @ params[f"attn_out_{i}"]
            h = _ln(x)
            x = x + jax.nn.gelu(h @ params[f"mlp_in_{i}"]) \
                @ params[f"mlp_out_{i}"]
        logits = _ln(x) @ params["out"]                        # [B, S, V]
        barange = jnp.arange(B)
        first = jnp.argmax(logits[barange, lengths - 1], axis=-1)
        first = first.astype(jnp.int32)                        # [B]

        # -- decode: one cached-attention step per new token ----------------
        # Cache SLOT for generated token t is S+t (past the prompt region,
        # same slot for every row); its POSITION (rotary-free posenc index)
        # is lengths+t per row. Keeping slot and position decoupled means a
        # short prompt's pad slots (len..S) — which prefill filled with
        # pad-token K/V — are never attended: valid keys are exactly
        # ``slot < len`` (the real prompt) or ``S <= slot <= S+t``.
        key_slot = jnp.arange(S + N)[None, :]                  # [1, S+N]

        def step(carry, t):
            tok, ck, cv = carry
            pos = lengths + t                                  # [B]
            x = jnp.take(params["embed"], tok, axis=0) + pe[pos]
            mask = (key_slot < lengths[:, None]) | \
                ((key_slot >= S) & (key_slot <= S + t))        # [B, S+N]
            for i in range(cfg.layers):
                h = _ln(x)
                q, k, v = jnp.split(h @ params[f"qkv_{i}"], 3, axis=-1)
                q = q.reshape(B, H, dh)
                k = k.reshape(B, H, dh)
                v = v.reshape(B, H, dh)
                ck = jax.lax.dynamic_update_slice(
                    ck, k[None, :, :, None], (i, 0, 0, S + t, 0))
                cv = jax.lax.dynamic_update_slice(
                    cv, v[None, :, :, None], (i, 0, 0, S + t, 0))
                scores = jnp.einsum("bhd,bhkd->bhk", q, ck[i]) * scale
                probs = jax.nn.softmax(
                    jnp.where(mask[:, None], scores, -jnp.inf), axis=-1)
                o = jnp.einsum("bhk,bhkd->bhd", probs, cv[i])
                x = x + o.reshape(B, D) @ params[f"attn_out_{i}"]
                h = _ln(x)
                x = x + jax.nn.gelu(h @ params[f"mlp_in_{i}"]) \
                    @ params[f"mlp_out_{i}"]
            logits = _ln(x) @ params["out"]                    # [B, V]
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (nxt, ck, cv), nxt

        (_, ck, cv), rest = jax.lax.scan(
            step, (first, ck, cv), jnp.arange(N - 1)) if N > 1 else \
            ((first, ck, cv), jnp.zeros((0, B), jnp.int32))
        out = jnp.concatenate([first[None], rest], axis=0).T   # [B, N]
        return out, ck, cv

    # -- paged drain decode (docs/SERVING.md "Decode memory hierarchy") -----
    # Same math as _decode_fn; the KV cache indexing goes through a
    # per-row page table into the shared pool, so a batch holds pages
    # for its ACTUAL context lengths instead of max-shape per bucket —
    # and the pool is shared across buckets, so exercising a new bucket
    # no longer pins a fresh full-size cache forever.
    def _decode_paged_fn(self, bucket, params, tokens, lengths, ptab,
                         kp, vp, ks, vs):
        from multiverso_tpu.models.attention_lm import _ln, _posenc

        cfg = self.cfg
        B, S = tokens.shape
        H, D = cfg.heads, cfg.dim
        dh = D // H
        N = self.max_new
        P = self.page
        G = ptab.shape[1]
        n_pp = pages_of(S, P)
        pad_s = n_pp * P - S
        scale = jnp.float32(1.0) / jnp.sqrt(jnp.float32(dh))
        lengths = jnp.maximum(lengths, 1)
        pe = _posenc(S + N, D)
        harange = jnp.arange(H)
        pages_flat = ptab[:, :n_pp].reshape(-1)

        def heads_of(t, s):
            return t.reshape(B, s, H, dh).transpose(0, 2, 1, 3)

        def paginate(t):
            """[B, H, S, dh] -> [B*n_pp, H, P, dh] page-major scatter
            form (positions past S pad with zeros — the straddle page's
            untouched gen region)."""
            w = jnp.pad(t, ((0, 0), (0, 0), (0, pad_s), (0, 0)))
            w = w.transpose(0, 2, 1, 3).reshape(B, n_pp, P, H, dh)
            return w.transpose(0, 1, 3, 2, 4).reshape(B * n_pp, H, P, dh)

        def gather(pool_i, scale_i):
            """[NP, H, P, dh] pages -> [B, H, G*P, dh] logical keys."""
            g = jnp.take(pool_i, ptab, axis=0, mode="clip")
            g = g.transpose(0, 2, 1, 3, 4).reshape(B, H, G * P, dh)
            s = jnp.take(scale_i, ptab, axis=0, mode="clip")
            s = s.transpose(0, 2, 1, 3, 4).reshape(B, H, G * P, 1)
            return decode_rows(g, s, self.kv_dtype)

        # -- prefill: full causal pass over the padded prompt --------------
        x = jnp.take(params["embed"], tokens, axis=0) + pe[None, :S]
        causal = jnp.tril(jnp.ones((S, S), bool))[None, None]
        for i in range(cfg.layers):
            h = _ln(x)
            q, k, v = jnp.split(h @ params[f"qkv_{i}"], 3, axis=-1)
            q, k, v = heads_of(q, S), heads_of(k, S), heads_of(v, S)
            kq, ksc = encode_rows(paginate(k), self.kv_dtype)
            vq, vsc = encode_rows(paginate(v), self.kv_dtype)
            kp = kp.at[pages_flat, i].set(kq)
            vp = vp.at[pages_flat, i].set(vq)
            ks = ks.at[pages_flat, i].set(ksc)
            vs = vs.at[pages_flat, i].set(vsc)
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
            probs = jax.nn.softmax(
                jnp.where(causal, scores, -jnp.inf), axis=-1)
            o = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
            x = x + o.transpose(0, 2, 1, 3).reshape(B, S, D) \
                @ params[f"attn_out_{i}"]
            h = _ln(x)
            x = x + jax.nn.gelu(h @ params[f"mlp_in_{i}"]) \
                @ params[f"mlp_out_{i}"]
        logits = _ln(x) @ params["out"]                        # [B, S, V]
        barange = jnp.arange(B)
        first = jnp.argmax(logits[barange, lengths - 1], axis=-1)
        first = first.astype(jnp.int32)                        # [B]

        # -- decode: one cached-attention step per new token ----------------
        key_slot = jnp.arange(G * P)[None, :]                  # [1, G*P]

        def step(carry, t):
            tok, kp, vp, ks, vs = carry
            pos = lengths + t                                  # [B]
            x = jnp.take(params["embed"], tok, axis=0) + pe[pos]
            mask = (key_slot < lengths[:, None]) | \
                ((key_slot >= S) & (key_slot <= S + t))        # [B, G*P]
            gphys = jnp.take(ptab, (S + t) // P, axis=1)       # [B]
            goff = (S + t) % P
            for i in range(cfg.layers):
                h = _ln(x)
                q, k, v = jnp.split(h @ params[f"qkv_{i}"], 3, axis=-1)
                q = q.reshape(B, H, dh)
                k = k.reshape(B, H, dh)
                v = v.reshape(B, H, dh)
                kq, ksc = encode_rows(k, self.kv_dtype)
                vq, vsc = encode_rows(v, self.kv_dtype)
                kp = kp.at[gphys[:, None], i, harange[None, :],
                           goff].set(kq)
                vp = vp.at[gphys[:, None], i, harange[None, :],
                           goff].set(vq)
                ks = ks.at[gphys[:, None], i, harange[None, :],
                           goff].set(ksc)
                vs = vs.at[gphys[:, None], i, harange[None, :],
                           goff].set(vsc)
                kf = gather(kp[:, i], ks[:, i])
                vf = gather(vp[:, i], vs[:, i])
                scores = jnp.einsum("bhd,bhkd->bhk", q, kf) * scale
                probs = jax.nn.softmax(
                    jnp.where(mask[:, None], scores, -jnp.inf), axis=-1)
                o = jnp.einsum("bhk,bhkd->bhd", probs, vf)
                x = x + o.reshape(B, D) @ params[f"attn_out_{i}"]
                h = _ln(x)
                x = x + jax.nn.gelu(h @ params[f"mlp_in_{i}"]) \
                    @ params[f"mlp_out_{i}"]
            logits = _ln(x) @ params["out"]                    # [B, V]
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (nxt, kp, vp, ks, vs), nxt

        (_, kp, vp, ks, vs), rest = jax.lax.scan(
            step, (first, kp, vp, ks, vs), jnp.arange(N - 1)) \
            if N > 1 else ((first, kp, vp, ks, vs),
                           jnp.zeros((0, B), jnp.int32))
        out = jnp.concatenate([first[None], rest], axis=0).T   # [B, N]
        return out, kp, vp, ks, vs

    def _decode_paged_for(self, bucket: int):
        fn = self._decode_paged.get(bucket)
        if fn is None:
            fn = jax.jit(functools.partial(self._decode_paged_fn, bucket),
                         donate_argnums=(4, 5, 6, 7))
            self._decode_paged[bucket] = fn
        return fn

    def _pool_for(self, need: int) -> PagePool:
        cfg = self.cfg
        if self._pool is None:
            # An explicit -serve_kv_pages budget is honored EXACTLY
            # (growth is the logged correctness valve); auto sizes for
            # two in-flight batches of the first-seen shape.
            capacity = int(self.pool_pages) if self.pool_pages \
                else max(2 * need, 1)
            self._pool = PagePool(capacity, cfg.layers, cfg.heads,
                                  self.page, cfg.dim // cfg.heads,
                                  self.kv_dtype)
        return self._pool

    def _dispatch_paged(self, batch: np.ndarray, lengths: np.ndarray):
        bucket = batch.shape[1]
        N, P = self.max_new, self.page
        plans = [page_plan(int(n), bucket, N, P) for n in lengths]
        G = pages_of(bucket + N, P)
        need = sum(p.n_backed for p in plans)
        pool = self._pool_for(need)
        pages = pool.alloc(need)
        if pages is None:
            # The drain path has no admission queue to lean on — a batch
            # that cannot fit GROWS the pool (bounded by the dispatch
            # pipeline depth) instead of deadlocking or shedding.
            pool.grow(pool.capacity + need)
            pages = pool.alloc(need)
            check(pages is not None, "page pool exhausted after growth")
        ptab = np.zeros((batch.shape[0], G), dtype=np.int32)
        it = iter(pages)
        for b, plan in enumerate(plans):
            for logical in (*plan.shared, *plan.private):
                ptab[b, logical] = next(it)
        with self._params_lock:
            params = self._params
        try:
            kp, vp, ks, vs = pool.arrays()
            out, kp, vp, ks, vs = self._decode_paged_for(bucket)(
                params, jnp.asarray(batch), jnp.asarray(lengths),
                jnp.asarray(ptab), kp, vp, ks, vs)
            pool.update(kp, vp, ks, vs)
        except Exception:
            pool.decref(pages)      # a failed launch must not leak pages
            raise
        return out, pages

    # -- two-phase dispatch (serving/pipeline.py contract) -----------------
    def dispatch(self, batch: np.ndarray, lengths: np.ndarray):
        """Launch the decode WITHOUT syncing. Back-to-back dispatches of
        the same bucket serialize on the donated KV-cache chain (batch
        k+1's prefill consumes the arrays batch k returns) — jax orders
        them; the pipeline only overlaps host work with device work."""
        if self.paged:
            return self._dispatch_paged(batch, lengths)
        bucket = batch.shape[1]
        ck, cv = self._cache_for(bucket)
        with self._params_lock:
            params = self._params
        out, ck, cv = self._decode(params, jnp.asarray(batch),
                                   jnp.asarray(lengths), ck, cv)
        self._caches[bucket] = (ck, cv)
        return out

    def collect(self, handle) -> np.ndarray:
        if self.paged:
            out, pages = handle
            values = np.asarray(out)        # the device sync
            self._pool.decref(pages)        # pages free once the batch
            return values                   # is off the device
        return np.asarray(handle)           # the device sync

    def run(self, batch: np.ndarray, lengths: np.ndarray) -> np.ndarray:
        return self.collect(self.dispatch(batch, lengths))

    def slice_result(self, out: np.ndarray, i: int, length: int):
        del length                     # every request gets max_new tokens
        return out[i]

    def clock(self) -> float:
        return -1.0

    def jit_cache_size(self) -> int:
        if self.paged:
            return sum(int(fn._cache_size())
                       for fn in self._decode_paged.values())
        return int(self._decode._cache_size())
