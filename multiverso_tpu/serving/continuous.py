"""Iteration-level continuous batching for attention-LM decode.

The drain-first path (:class:`~multiverso_tpu.serving.runners.
AttentionLMRunner` behind the plain :class:`DynamicBatcher`) coalesces
prompts into a batch, then runs prefill + the FULL ``max_new``-step decode
as one dispatch: a request arriving one step after a batch launched waits
out the whole bucket before its own decode begins. That is the decisive
serving inefficiency the Gemma-on-TPU comparison names (PAPERS.md, arXiv
2605.25645): decode batches should admit at *iteration* granularity.

This module decodes step-by-step from the host instead: one jitted
``prefill`` (a single prompt into one KV-cache slot) and one jitted
``step`` (one cached-attention token step for ALL slots at once, with a
per-slot step counter). New requests claim free KV-cache slots at step
boundaries and ride along with whatever is mid-decode; a finished slot
frees at the next boundary. Because every slot's computation depends only
on its own row — its own cache rows, its own mask ``key_slot < len`` or
``bucket <= key_slot <= bucket + t_slot``, its own position ``len +
t_slot`` (slot/position decoupling, exactly the drain path's layout) —
a late joiner's tokens are BIT-IDENTICAL to decoding it alone through
the drain path (``tests/test_serving_continuous.py`` asserts it).

The host-stepped loop is the same trade PR 2 made for training: a
de-optimized in-graph loop (here: ``lax.scan`` that forces bucket-drain
batching) loses to host dispatch once the launch is cheap, and the
per-step dispatches pipeline through jax's async queue (each step donates
the caches forward, so steady state allocates nothing and the chain
serializes on data flow, not host syncs — the only sync is one
row-read per COMPLETED request).

PAGED mode (``paged=True`` / ``-serve_paged_kv``) is the decode memory
hierarchy (docs/SERVING.md "Decode memory hierarchy"): instead of one
preallocated max-shape cache per bucket engine, every engine draws
fixed-size KV pages from ONE shared :class:`~multiverso_tpu.serving.
paged.PagePool` through per-slot page tables. HBM held scales with
actual context lengths (pad pages are unbacked), pages free at step
boundaries under the existing cv discipline, pool exhaustion QUEUES the
request at admission (never crashes), and with f32 storage the decoded
tokens stay BITWISE-identical to the drain path — the page gather
appends only exactly-masked keys, whose softmax weight is exactly zero.
A :class:`~multiverso_tpu.serving.prefix.PrefixStore` (``prefix_entries
> 0``) then lets requests sharing a prompt share prefill output and
prompt pages outright (copy-on-extend for the straddle page), probed at
step-boundary admission the way ``HotRowCache.try_cached`` is probed at
submit. Quantized page storage (``kv_dtype`` bf16/int8) rides the same
kernels with encode-on-write/decode-on-read fused in.

Telemetry: ``serve.continuous.active`` gauge (occupied slots),
``serve.continuous.joins`` / ``serve.continuous.steps`` counters, plus
``serve.kv.*`` (pool) and ``serve.prefix.*`` (sharing) families
(docs/OBSERVABILITY.md catalog).
"""

from __future__ import annotations

import collections
import functools
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from multiverso_tpu.serving.batcher import (DynamicBatcher, ServeRequest,
                                            ShedError)
from multiverso_tpu.serving.paged import (GARBAGE_PAGE, PagePlan, PagePool,
                                          default_pool_pages, page_plan,
                                          pages_of)
from multiverso_tpu.serving.prefix import PrefixStore
from multiverso_tpu.serving.quant import (decode_rows, encode_rows,
                                          storage_dtype)
from multiverso_tpu.telemetry import child_of, counter, emit_span, gauge
from multiverso_tpu.utils.log import check, log


def _snapshot(host: np.ndarray):
    """Device copy of a host array the worker keeps writing in place (slot
    lengths, step counters, page tables). jax may alias a host buffer
    (CPU) or read it after the dispatch returns (async host-to-device
    copy), so handing it the live array lets a later ``eng.t[i] += 1``
    change a step already in flight. The copy is never written again."""
    import jax.numpy as jnp

    return jnp.asarray(host.copy())


class _SlotEngine:
    """Per-bucket decode state: B cache slots sharing one KV-cache of
    shape ``[layers, B, heads, bucket+max_new, dh]`` plus the device-side
    carry (current token per slot, token output buffer) and the
    host-side slot table (which request owns which slot, its prompt
    length and step counter)."""

    __slots__ = ("bucket", "ck", "cv", "out", "tok", "lengths", "t",
                 "reqs", "t_join")

    def __init__(self, bucket: int, max_batch: int, max_new: int,
                 cache_shape):
        import jax.numpy as jnp

        self.bucket = bucket
        self.ck = jnp.zeros(cache_shape, jnp.float32)
        self.cv = jnp.zeros(cache_shape, jnp.float32)
        self.out = jnp.zeros((max_batch, max_new), jnp.int32)
        self.tok = jnp.zeros((max_batch,), jnp.int32)
        self.lengths = np.ones(max_batch, dtype=np.int32)
        self.t = np.zeros(max_batch, dtype=np.int32)
        self.reqs: List[Optional[ServeRequest]] = [None] * max_batch
        self.t_join = [0.0] * max_batch

    def free_slot(self) -> int:
        for i, r in enumerate(self.reqs):
            if r is None:
                return i
        return -1

    def n_active(self) -> int:
        return sum(1 for r in self.reqs if r is not None)


class _PagedEngine:
    """Per-bucket decode state, paged flavor: no cache of its own — a
    per-slot PAGE TABLE (host int32 + a device mirror refreshed when
    dirty) maps this engine's logical cache positions into the shared
    pool. ``slot_pages[s]`` is every physical page slot ``s`` holds a
    reference on (freed at delivery); idle slots' rows point at the
    garbage page so their confined-garbage step writes land nowhere."""

    __slots__ = ("bucket", "n_logical", "out", "tok", "lengths", "t",
                 "reqs", "t_join", "ptab", "ptab_dev", "ptab_dirty",
                 "slot_pages", "plans", "pending_publish")

    def __init__(self, bucket: int, max_batch: int, max_new: int,
                 page: int):
        import jax.numpy as jnp

        self.bucket = bucket
        self.n_logical = pages_of(bucket + max_new, page)
        self.out = jnp.zeros((max_batch, max_new), jnp.int32)
        self.tok = jnp.zeros((max_batch,), jnp.int32)
        self.lengths = np.ones(max_batch, dtype=np.int32)
        self.t = np.zeros(max_batch, dtype=np.int32)
        self.reqs: List[Optional[ServeRequest]] = [None] * max_batch
        self.t_join = [0.0] * max_batch
        self.ptab = np.zeros((max_batch, self.n_logical), dtype=np.int32)
        self.ptab_dev = None
        self.ptab_dirty = True
        self.slot_pages: List[List[int]] = [[] for _ in range(max_batch)]
        self.plans: List[Optional[PagePlan]] = [None] * max_batch
        # Deferred prefix publish (payload, shared, straddle, params
        # token): resolved at DELIVERY, when the slot's first token is
        # host-resident anyway — publishing at join would cost a scalar
        # readback (a device sync) per novel prompt.
        self.pending_publish: List[Optional[tuple]] = [None] * max_batch

    def free_slot(self) -> int:
        for i, r in enumerate(self.reqs):
            if r is None:
                return i
        return -1

    def n_active(self) -> int:
        return sum(1 for r in self.reqs if r is not None)

    def device_ptab(self):
        if self.ptab_dirty or self.ptab_dev is None:
            self.ptab_dev = _snapshot(self.ptab)
            self.ptab_dirty = False
        return self.ptab_dev


class _PagedClaim:
    """Pages + prefix pin reserved for one queued request at claim time
    (under the batcher cv). Released on every shed path, consumed by
    the join."""

    __slots__ = ("plan", "entry", "pages")

    def __init__(self, plan, entry, pages):
        self.plan = plan
        self.entry = entry
        self.pages = pages


class ContinuousBatcher(DynamicBatcher):
    """Drop-in batcher for :class:`AttentionLMRunner` decode with
    iteration-level admission.

    Reuses the :class:`DynamicBatcher` surface whole — deadline-aware
    admission, cancel tokens, quiesce barrier, close semantics — and
    replaces the worker loop: instead of gather->run->deliver it claims
    free KV-cache slots for queued requests, prefills them, and advances
    every engine one decode step per iteration. ``max_wait_ms`` is
    irrelevant here (admission happens at every step boundary; nothing
    ever waits for company) and is pinned to 0.

    Paged-mode knobs: ``paged`` switches the engines onto the shared
    page pool; ``kv_dtype`` ('f32'|'bf16'|'int8') picks the page storage
    codec; ``page`` the page size in token positions; ``pool_pages``
    the pool capacity (None = auto: full backing for every bucket
    engine — set LOWER to enforce an HBM budget, exhaustion queues);
    ``prefix_entries`` enables the prefix store (requires ``paged``)."""

    def __init__(self, runner, buckets: Sequence[int],
                 max_batch: int = 8, max_queue: int = 64,
                 paged: bool = False, kv_dtype: str = "f32",
                 page: int = 16, pool_pages: Optional[int] = None,
                 prefix_entries: int = 0):
        import jax

        cfg = runner.cfg
        check(cfg.moe_experts == 0 and cfg.pipeline_stages == 0,
              "continuous decode supports the flat dense attention_lm "
              "layout")
        self.runner_ref = runner
        self.cfg = cfg
        self.max_new = int(runner.max_new)
        self.paged = bool(paged)
        self.kv_dtype = storage_dtype(kv_dtype)
        self.page = int(page)
        check(self.page >= 1, "page size must be >= 1")
        check(self.kv_dtype == "f32" or self.paged,
              "quantized KV storage (-serve_kv_dtype) requires the paged "
              "cache (-serve_paged_kv)")
        check(prefix_entries == 0 or self.paged,
              "the prefix cache shares KV pages and requires the paged "
              "cache (-serve_paged_kv)")
        # Engines + slot accounting exist BEFORE super().__init__ starts
        # the worker thread (which immediately enters our _loop).
        self._engines: Dict[int, object] = {}
        self._active: "collections.Counter" = collections.Counter()
        self._g_active = gauge("serve.continuous.active")
        self._c_joins = counter("serve.continuous.joins")
        self._c_steps = counter("serve.continuous.steps")
        self._c_batched_reads = counter("serve.continuous.batched_reads")
        self._c_pool_exhausted = counter("serve.kv.pool_exhausted")
        self.pool: Optional[PagePool] = None
        self.prefix: Optional[PrefixStore] = None
        if self.paged:
            n_pages = int(pool_pages) if pool_pages else \
                default_pool_pages(buckets, max_batch, self.max_new,
                                   self.page)
            self.pool = PagePool(n_pages, cfg.layers, cfg.heads,
                                 self.page, cfg.dim // cfg.heads,
                                 self.kv_dtype)
            if prefix_entries > 0:
                self.prefix = PrefixStore(self.pool, prefix_entries)
            # One executable per bucket, keyed by the static bucket arg.
            self._prefill_paged: Dict[int, object] = {}
            self._step_paged: Dict[int, object] = {}
            self._copy_page = jax.jit(self._copy_page_fn,
                                      donate_argnums=(2, 3, 4, 5))
        self._prefill = jax.jit(self._prefill_fn,
                                donate_argnums=(4, 5, 6, 7))
        self._step = jax.jit(self._step_fn, donate_argnums=(3, 4, 5, 6))
        super().__init__(runner, buckets, max_batch=max_batch,
                         max_wait_ms=0.0, max_queue=max_queue,
                         pipeline_depth=0)

    # -- jitted kernels ------------------------------------------------------
    # The math is the drain path's (_decode_fn) verbatim per row: same
    # _ln/_posenc, same einsum strings, same mask formula, same
    # slot/position decoupling. Only the batching topology differs — one
    # prompt per prefill, a per-slot step counter vector in step.
    def _prefill_fn(self, params, tokens, length, slot, ck, cv, out, tok):
        """tokens [1, S] right-padded, length [1], slot scalar -> writes
        the prompt's K/V into cache row ``slot``, the first greedy token
        into ``out[slot, 0]`` and ``tok[slot]``."""
        import jax
        import jax.numpy as jnp

        from multiverso_tpu.models.attention_lm import _ln, _posenc

        cfg = self.cfg
        S = tokens.shape[1]
        H, D = cfg.heads, cfg.dim
        dh = D // H
        scale = jnp.float32(1.0) / jnp.sqrt(jnp.float32(dh))
        length = jnp.maximum(length, 1)
        pe = _posenc(S + self.max_new, D)

        x = jnp.take(params["embed"], tokens, axis=0) + pe[None, :S]
        causal = jnp.tril(jnp.ones((S, S), bool))[None, None]
        for i in range(cfg.layers):
            h = _ln(x)
            q, k, v = jnp.split(h @ params[f"qkv_{i}"], 3, axis=-1)
            q = q.reshape(1, S, H, dh).transpose(0, 2, 1, 3)
            k = k.reshape(1, S, H, dh).transpose(0, 2, 1, 3)
            v = v.reshape(1, S, H, dh).transpose(0, 2, 1, 3)
            ck = jax.lax.dynamic_update_slice(ck, k[None],
                                              (i, slot, 0, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, v[None],
                                              (i, slot, 0, 0, 0))
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
            probs = jax.nn.softmax(
                jnp.where(causal, scores, -jnp.inf), axis=-1)
            o = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
            x = x + o.transpose(0, 2, 1, 3).reshape(1, S, D) \
                @ params[f"attn_out_{i}"]
            h = _ln(x)
            x = x + jax.nn.gelu(h @ params[f"mlp_in_{i}"]) \
                @ params[f"mlp_out_{i}"]
        logits = _ln(x) @ params["out"]                       # [1, S, V]
        first = jnp.argmax(logits[0, length[0] - 1], axis=-1) \
            .astype(jnp.int32)                                # scalar
        out = jax.lax.dynamic_update_slice(out, first[None, None],
                                           (slot, 0))
        tok = jax.lax.dynamic_update_slice(tok, first[None], (slot,))
        return ck, cv, out, tok

    def _step_fn(self, params, lengths, t, ck, cv, out, tok):
        """One cached-attention step for EVERY slot at once; ``t`` is the
        per-slot step counter (generated token ``t`` is on deck: its K/V
        lands in cache slot ``S+t_row``, its position is ``len_row +
        t_row``, and the emitted token writes ``out[row, t_row+1]``).
        Idle slots compute garbage confined to their own rows — their
        next prefill overwrites everything a future occupant can see."""
        import jax.numpy as jnp
        from jax import nn as jnn

        from multiverso_tpu.models.attention_lm import _ln, _posenc

        cfg = self.cfg
        B = tok.shape[0]
        H, D = cfg.heads, cfg.dim
        dh = D // H
        S = ck.shape[3] - self.max_new
        N = self.max_new
        scale = jnp.float32(1.0) / jnp.sqrt(jnp.float32(dh))
        pe = _posenc(S + N, D)
        barange = jnp.arange(B)
        harange = jnp.arange(H)
        key_slot = jnp.arange(S + N)[None, :]                  # [1, S+N]

        pos = lengths + t                                      # [B]
        x = jnp.take(params["embed"], tok, axis=0) + pe[pos]
        mask = (key_slot < lengths[:, None]) | \
            ((key_slot >= S) & (key_slot <= (S + t)[:, None]))  # [B, S+N]
        for i in range(cfg.layers):
            h = _ln(x)
            q, k, v = jnp.split(h @ params[f"qkv_{i}"], 3, axis=-1)
            q = q.reshape(B, H, dh)
            k = k.reshape(B, H, dh)
            v = v.reshape(B, H, dh)
            ck = ck.at[i, barange[:, None], harange[None, :],
                       (S + t)[:, None]].set(k)
            cv = cv.at[i, barange[:, None], harange[None, :],
                       (S + t)[:, None]].set(v)
            scores = jnp.einsum("bhd,bhkd->bhk", q, ck[i]) * scale
            probs = jnn.softmax(
                jnp.where(mask[:, None], scores, -jnp.inf), axis=-1)
            o = jnp.einsum("bhk,bhkd->bhd", probs, cv[i])
            x = x + o.reshape(B, D) @ params[f"attn_out_{i}"]
            h = _ln(x)
            x = x + jnn.gelu(h @ params[f"mlp_in_{i}"]) \
                @ params[f"mlp_out_{i}"]
        logits = _ln(x) @ params["out"]                        # [B, V]
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out = out.at[barange, jnp.clip(t + 1, 0, N - 1)].set(nxt)
        return ck, cv, out, nxt

    # -- paged kernels -------------------------------------------------------
    # Same math; the cache indexing goes through the page table. The
    # gathered key axis is n_logical*page >= S+N positions — the tail
    # past S+N (page-alignment pad) is ALWAYS masked, and exactly-masked
    # keys carry softmax weight exactly 0.0, which is what keeps paged
    # f32 bitwise-equal to the preallocated path.
    def _prefill_paged_fn(self, bucket, params, tokens, length, slot,
                          pages, kp, vp, ks, vs, out, tok):
        """One prompt into its pages: ``pages`` [ceil(bucket/page)] are
        the slot's physical ids for the prompt-region logical pages
        (garbage page 0 for unbacked pad pages — their writes are never
        attended)."""
        import jax
        import jax.numpy as jnp

        from multiverso_tpu.models.attention_lm import _ln, _posenc

        cfg = self.cfg
        S = bucket
        H, D = cfg.heads, cfg.dim
        dh = D // H
        P = self.page
        n_pp = pages.shape[0]
        pad_s = n_pp * P - S
        scale = jnp.float32(1.0) / jnp.sqrt(jnp.float32(dh))
        length = jnp.maximum(length, 1)
        pe = _posenc(S + self.max_new, D)

        def paginate(h_s_d):
            """[H, S, dh] -> [n_pp, H, P, dh] (page-major scatter form).
            Positions past S pad with zeros — they land in the straddle
            page's GEN region, which a fresh slot has not started."""
            w = jnp.pad(h_s_d, ((0, 0), (0, pad_s), (0, 0)))
            return w.reshape(H, n_pp, P, dh).transpose(1, 0, 2, 3)

        x = jnp.take(params["embed"], tokens, axis=0) + pe[None, :S]
        causal = jnp.tril(jnp.ones((S, S), bool))[None, None]
        for i in range(cfg.layers):
            h = _ln(x)
            q, k, v = jnp.split(h @ params[f"qkv_{i}"], 3, axis=-1)
            q = q.reshape(1, S, H, dh).transpose(0, 2, 1, 3)
            k = k.reshape(1, S, H, dh).transpose(0, 2, 1, 3)
            v = v.reshape(1, S, H, dh).transpose(0, 2, 1, 3)
            kq, ksc = encode_rows(paginate(k[0]), self.kv_dtype)
            vq, vsc = encode_rows(paginate(v[0]), self.kv_dtype)
            kp = kp.at[pages, i].set(kq)
            vp = vp.at[pages, i].set(vq)
            ks = ks.at[pages, i].set(ksc)
            vs = vs.at[pages, i].set(vsc)
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
            probs = jax.nn.softmax(
                jnp.where(causal, scores, -jnp.inf), axis=-1)
            o = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
            x = x + o.transpose(0, 2, 1, 3).reshape(1, S, D) \
                @ params[f"attn_out_{i}"]
            h = _ln(x)
            x = x + jax.nn.gelu(h @ params[f"mlp_in_{i}"]) \
                @ params[f"mlp_out_{i}"]
        logits = _ln(x) @ params["out"]                       # [1, S, V]
        first = jnp.argmax(logits[0, length[0] - 1], axis=-1) \
            .astype(jnp.int32)
        out = out.at[slot, 0].set(first)
        tok = tok.at[slot].set(first)
        return kp, vp, ks, vs, out, tok

    def _step_paged_fn(self, bucket, params, lengths, t, ptab, kp, vp,
                       ks, vs, out, tok):
        """The per-slot-counter step over paged storage: scatter the new
        token's K/V into each slot's CURRENT gen page (idle slots'
        tables point at the garbage page), gather every slot's pages
        back into logical order, decode-on-read, attend."""
        import jax.numpy as jnp
        from jax import nn as jnn

        from multiverso_tpu.models.attention_lm import _ln, _posenc

        cfg = self.cfg
        B = tok.shape[0]
        H, D = cfg.heads, cfg.dim
        dh = D // H
        S, N, P = bucket, self.max_new, self.page
        G = ptab.shape[1]
        scale = jnp.float32(1.0) / jnp.sqrt(jnp.float32(dh))
        pe = _posenc(S + N, D)
        barange = jnp.arange(B)
        harange = jnp.arange(H)
        key_slot = jnp.arange(G * P)[None, :]                  # [1, G*P]

        pos = lengths + t                                      # [B]
        x = jnp.take(params["embed"], tok, axis=0) + pe[pos]
        mask = (key_slot < lengths[:, None]) | \
            ((key_slot >= S) & (key_slot <= (S + t)[:, None]))  # [B, G*P]
        gphys = jnp.take_along_axis(
            ptab, ((S + t) // P)[:, None], axis=1)[:, 0]       # [B]
        goff = (S + t) % P                                     # [B]

        def gather(pool_i, scale_i):
            """[NP, H, P, dh] pages -> [B, H, G*P, dh] logical keys."""
            g = jnp.take(pool_i, ptab, axis=0, mode="clip")
            g = g.transpose(0, 2, 1, 3, 4).reshape(B, H, G * P, dh)
            s = jnp.take(scale_i, ptab, axis=0, mode="clip")
            s = s.transpose(0, 2, 1, 3, 4).reshape(B, H, G * P, 1)
            return decode_rows(g, s, self.kv_dtype)

        for i in range(cfg.layers):
            h = _ln(x)
            q, k, v = jnp.split(h @ params[f"qkv_{i}"], 3, axis=-1)
            q = q.reshape(B, H, dh)
            k = k.reshape(B, H, dh)
            v = v.reshape(B, H, dh)
            kq, ksc = encode_rows(k, self.kv_dtype)
            vq, vsc = encode_rows(v, self.kv_dtype)
            kp = kp.at[gphys[:, None], i, harange[None, :],
                       goff[:, None]].set(kq)
            vp = vp.at[gphys[:, None], i, harange[None, :],
                       goff[:, None]].set(vq)
            ks = ks.at[gphys[:, None], i, harange[None, :],
                       goff[:, None]].set(ksc)
            vs = vs.at[gphys[:, None], i, harange[None, :],
                       goff[:, None]].set(vsc)
            kf = gather(kp[:, i], ks[:, i])
            vf = gather(vp[:, i], vs[:, i])
            scores = jnp.einsum("bhd,bhkd->bhk", q, kf) * scale
            probs = jnn.softmax(
                jnp.where(mask[:, None], scores, -jnp.inf), axis=-1)
            o = jnp.einsum("bhk,bhkd->bhd", probs, vf)
            x = x + o.reshape(B, D) @ params[f"attn_out_{i}"]
            h = _ln(x)
            x = x + jnn.gelu(h @ params[f"mlp_in_{i}"]) \
                @ params[f"mlp_out_{i}"]
        logits = _ln(x) @ params["out"]                        # [B, V]
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out = out.at[barange, jnp.clip(t + 1, 0, N - 1)].set(nxt)
        return kp, vp, ks, vs, out, nxt

    def _copy_page_fn(self, src, dst, kp, vp, ks, vs):
        """Copy-on-extend: clone one physical page (prefix sharer's
        straddle). Sequenced with every other pool op by data flow —
        the donated pool arrays thread through the worker's dispatches
        in program order."""
        kp = kp.at[dst].set(kp[src])
        vp = vp.at[dst].set(vp[src])
        ks = ks.at[dst].set(ks[src])
        vs = vs.at[dst].set(vs[src])
        return kp, vp, ks, vs

    def _prefill_paged_for(self, bucket: int):
        import jax

        fn = self._prefill_paged.get(bucket)
        if fn is None:
            fn = jax.jit(functools.partial(self._prefill_paged_fn, bucket),
                         donate_argnums=(5, 6, 7, 8, 9, 10))
            self._prefill_paged[bucket] = fn
        return fn

    def _step_paged_for(self, bucket: int):
        import jax

        fn = self._step_paged.get(bucket)
        if fn is None:
            fn = jax.jit(functools.partial(self._step_paged_fn, bucket),
                         donate_argnums=(4, 5, 6, 7, 8, 9))
            self._step_paged[bucket] = fn
        return fn

    # -- engine management ---------------------------------------------------
    def _engine_for(self, bucket: int):
        eng = self._engines.get(bucket)
        if eng is None:
            cfg = self.cfg
            if self.paged:
                eng = _PagedEngine(bucket, self.max_batch, self.max_new,
                                   self.page)
            else:
                shape = (cfg.layers, self.max_batch, cfg.heads,
                         bucket + self.max_new, cfg.dim // cfg.heads)
                eng = _SlotEngine(bucket, self.max_batch, self.max_new,
                                  shape)
            self._engines[bucket] = eng
        return eng

    def warmup(self) -> int:
        """Compile prefill + step for every ladder bucket (the service
        warmup hook — first real request never pays a trace). Paged
        warmup writes the garbage page only (no allocation)."""
        import jax.numpy as jnp

        params = self.runner_ref.params_ref()
        one = jnp.ones((1,), jnp.int32)
        slot0 = jnp.int32(0)
        warmed = 0
        for bucket in self.ladder.buckets:
            eng = self._engine_for(bucket)
            # One prompt buffer per bucket — warmup runs once at
            # bring-up, and the shape is the thing being compiled.
            # graftlint: disable=host-jnp-in-loop
            zeros = jnp.zeros((1, bucket), jnp.int32)
            if self.paged:
                # Same once-at-bring-up trade as the prompt buffer above.
                # graftlint: disable=host-jnp-in-loop
                pages0 = jnp.zeros((pages_of(bucket, self.page),),
                                   jnp.int32)
                kp, vp, ks, vs = self.pool.arrays()
                kp, vp, ks, vs, eng.out, eng.tok = \
                    self._prefill_paged_for(bucket)(
                        params, zeros, one, slot0, pages0, kp, vp, ks,
                        vs, eng.out, eng.tok)
                kp, vp, ks, vs, eng.out, eng.tok = \
                    self._step_paged_for(bucket)(
                        params, _snapshot(eng.lengths),
                        _snapshot(eng.t), eng.device_ptab(), kp, vp,
                        ks, vs, eng.out, eng.tok)
                self.pool.update(kp, vp, ks, vs)
            else:
                eng.ck, eng.cv, eng.out, eng.tok = self._prefill(
                    params, zeros, one, slot0, eng.ck, eng.cv, eng.out,
                    eng.tok)
                eng.ck, eng.cv, eng.out, eng.tok = self._step(
                    params, _snapshot(eng.lengths), _snapshot(eng.t),
                    eng.ck, eng.cv, eng.out, eng.tok)
            warmed += 2
        return warmed

    def jit_cache_size(self) -> int:
        """Prefill executables == buckets exercised (step compiles in
        lockstep; the unit test asserts the two caches agree)."""
        if self.paged:
            return sum(int(fn._cache_size())
                       for fn in self._prefill_paged.values())
        return int(self._prefill._cache_size())

    def _step_cache_size(self) -> int:
        if self.paged:
            return sum(int(fn._cache_size())
                       for fn in self._step_paged.values())
        return int(self._step._cache_size())

    # -- the iteration loop --------------------------------------------------
    def _loop(self) -> None:  # overrides DynamicBatcher._loop
        from multiverso_tpu.telemetry import watchdog_scope
        with watchdog_scope("serve-continuous", timeout_s=60.0) as wd:
            self._wd = wd
            self._run_decode_loop(wd)

    def _run_decode_loop(self, wd) -> None:
        while True:
            wd.beat()
            with self._cv:
                while self._running and not self._queue \
                        and not self._n_active_locked():
                    self._cv.wait(0.05)
                    wd.beat()       # idle is progress, not a wedge
                if not self._running and not self._queue \
                        and not self._n_active_locked():
                    return
                claims = self._claim_locked()
                if claims or self._n_active_locked():
                    self._busy = True
                elif self._queue:
                    # Pool-stalled: queued work, nothing claimable,
                    # nothing decoding. Wait for a submit/cancel/close
                    # instead of spinning the claim loop hot (page
                    # frees happen on THIS thread, so nothing is missed
                    # by sleeping here).
                    self._cv.wait(0.05)
                self._g_depth.set(len(self._queue))
            self._admit_claims(claims)
            # Deliver BEFORE stepping: a slot that completed on the
            # previous step — or straight out of prefill when max_new==1
            # — must hand its tokens over before another step can write
            # into its out row (stepping a complete slot would overwrite
            # token out[slot, clip(t+1)] with an extra greedy token).
            self._deliver_finished()
            self._step_engines()
            self._deliver_finished()
            with self._cv:
                if not self._n_active_locked() and not self._queue:
                    self._busy = False

    def _n_active_locked(self) -> int:
        return sum(self._active.values())

    def _claim_locked(self) -> List[ServeRequest]:
        """FIFO claim of queued requests into free slots, per bucket —
        the step-boundary admission. Requests whose bucket is full stay
        queued in order (a later small-bucket request may still claim).
        Paged mode ALSO reserves the request's physical pages here
        (prefix pin + page allocation, under the cv): a request the pool
        cannot serve stays queued — and blocks later claims for this
        round, so a stream of small requests cannot starve a large one
        — until delivery frees pages at a step boundary."""
        claims: List[ServeRequest] = []
        remaining: List[ServeRequest] = []
        claimed: "collections.Counter" = collections.Counter()
        pool_blocked = False
        for req in self._queue:
            b = self.ladder.pick(req.payload.shape[0])
            if self._active[b] + claimed[b] >= self.max_batch:
                remaining.append(req)
                continue
            if self.paged \
                    and getattr(req, "_paged_claim", None) is None:
                plan = page_plan(req.payload.shape[0], b, self.max_new,
                                 self.page)
                if plan.n_backed > self.pool.capacity:
                    # Never fits: no amount of freeing serves this
                    # request — shed it NOW (outside the cv, via the
                    # claims list) instead of queueing it forever.
                    req._paged_doomed = True
                    claims.append(req)
                    continue
                if pool_blocked or not self._reserve_paged(req, b, plan):
                    if not pool_blocked:
                        pool_blocked = True
                        self._c_pool_exhausted.inc()
                    remaining.append(req)
                    continue
            claimed[b] += 1
            claims.append(req)
        self._queue.clear()
        self._queue.extend(remaining)
        for b, n in claimed.items():
            self._active[b] += n
        return claims

    def _params_token(self) -> int:
        """The prefix store's weights token: the runner's MONOTONIC
        swap version. Object identity would be unsound — CPython reuses
        a freed dict's address, so after two hot-swaps a stale entry
        could validate against new weights."""
        fn = getattr(self.runner_ref, "params_versioned", None)
        if fn is None:          # foreign runner: identity is best-effort
            return id(self.runner_ref.params_ref())
        return int(fn()[1])

    def _reserve_paged(self, req: ServeRequest, bucket: int,
                       plan: PagePlan) -> bool:
        """Pin the prefix entry (when the store knows this prompt) and
        allocate the private/backed pages the slot will own. A dry pool
        first RECLAIMS prefix-store retention (cache bytes must yield
        to live admissions — retained pages could otherwise starve the
        pool forever, since store eviction only runs on publish and a
        publish needs a completed request). False = genuinely
        exhausted; the request keeps its queue position."""
        entry = None
        if self.prefix is not None:
            entry = self.prefix.probe(req.payload, bucket,
                                      self._params_token())
        need = len(plan.private) if entry is not None \
            else len(plan.shared) + len(plan.private)
        pages = self.pool.alloc(need)
        if pages is None and self.prefix is not None:
            if self.prefix.reclaim(need - self.pool.free_pages()) > 0:
                pages = self.pool.alloc(need)
        if pages is None:
            if entry is not None:
                self.prefix.release(entry)
            return False
        req._paged_claim = _PagedClaim(plan, entry, pages)
        return True

    def _release_claim(self, req: ServeRequest) -> None:
        """Give back a reserved claim that will never reach a slot."""
        claim = getattr(req, "_paged_claim", None)
        if claim is None:
            return
        req._paged_claim = None
        if claim.entry is not None:
            self.prefix.release(claim.entry)
        if claim.pages:
            self.pool.decref(claim.pages)

    def _unclaim(self, bucket: int) -> None:
        with self._cv:
            self._active[bucket] -= 1

    def _admit_claims(self, claims: List[ServeRequest]) -> None:
        now = time.monotonic()
        for req in claims:
            if getattr(req, "_paged_doomed", False):
                # Needs more pages than the pool will EVER hold: an
                # admission-time config mismatch, shed with the reason.
                self._c_shed_oversize.inc()
                self._safe_done(req, ShedError(
                    "oversize",
                    "request needs more KV pages than the pool holds "
                    "(raise -serve_kv_pages or shrink the bucket "
                    "ladder)"))
                continue
            bucket = self.ladder.pick(req.payload.shape[0])
            if req.cancelled:
                self._c_cancelled.inc()
                self._unclaim(bucket)
                self._release_claim(req)
                self._safe_done(req, ShedError("cancelled",
                                               "hedged loser cancelled"))
            elif req.deadline < now:
                self._c_shed_deadline.inc()
                self._unclaim(bucket)
                self._release_claim(req)
                self._safe_done(req, ShedError("deadline",
                                               "expired while queued"))
            else:
                self._h_admit.observe((now - req.t_submit) * 1e3)
                if req.ctx is not None and req.ctx.sampled:
                    # Phase ledger: queue = enqueue -> claimed at a step
                    # boundary (the continuous analog of batch gather).
                    t_enq = req.t_enqueue or req.t_submit
                    emit_span("serve.admit_wait", child_of(req.ctx),
                              t_enq, (now - t_enq) * 1e3)
                self._join(req, bucket)

    def _join(self, req: ServeRequest, bucket: int) -> None:
        """Prefill one prompt into a free KV-cache slot — the join is a
        device dispatch like any step, so it lands exactly at a step
        boundary of everything already decoding in this engine. Paged
        joins wire the slot's page table first; a prefix hit skips the
        prefill dispatch entirely (the shared pages already hold the
        prompt's K/V and the entry holds the first greedy token)."""
        eng = self._engine_for(bucket)
        slot = eng.free_slot()
        try:
            check(slot >= 0, "claim accounting out of slots")
            n = req.payload.shape[0]
            if self.paged:
                self._join_paged(req, eng, slot, bucket, n)
            else:
                self._join_prealloc(req, eng, slot, bucket, n)
        except Exception as e:  # noqa: BLE001 - a poisoned prompt sheds
            log.error("continuous decode: prefill failed: %s", e)  # alone
            self._unclaim(bucket)
            self._release_claim(req)
            self._safe_done(req, ShedError("closed", f"runner error: {e}"))
            return
        eng.reqs[slot] = req
        eng.lengths[slot] = max(n, 1)
        eng.t[slot] = 0
        eng.t_join[slot] = time.monotonic()
        self._c_joins.inc()
        self._c_requests.inc()
        self._g_active.set(self._total_active())
        self._g_inflight.set(self._total_active())

    def _join_prealloc(self, req: ServeRequest, eng: _SlotEngine,
                       slot: int, bucket: int, n: int) -> None:
        import jax.numpy as jnp

        tokens = np.zeros((1, bucket), dtype=np.int32)
        tokens[0, :n] = req.payload
        params = self.runner_ref.params_ref()
        eng.ck, eng.cv, eng.out, eng.tok = self._prefill(
            params, jnp.asarray(tokens),
            jnp.asarray([max(n, 1)], np.int32), jnp.int32(slot),
            eng.ck, eng.cv, eng.out, eng.tok)

    def _join_paged(self, req: ServeRequest, eng: _PagedEngine,
                    slot: int, bucket: int, n: int) -> None:
        import jax.numpy as jnp

        claim: Optional[_PagedClaim] = getattr(req, "_paged_claim", None)
        check(claim is not None, "paged join without a page claim")
        # The claim stays ON the request until the slot owns everything:
        # a failure anywhere below propagates to _join's handler, whose
        # _release_claim gives the pinned entry + pages back exactly
        # once. Only the final line transfers ownership to the slot.
        plan, entry, pages = claim.plan, claim.entry, claim.pages
        row = np.zeros(eng.n_logical, dtype=np.int32)
        versioned = getattr(self.runner_ref, "params_versioned", None)
        if versioned is not None:
            params, params_token = versioned()
        else:
            params = self.runner_ref.params_ref()
            params_token = id(params)
        if entry is not None:
            # Prefix hit: alias the shared prompt pages, own the private
            # gen pages; the straddle page (prompt tail + gen head)
            # copies-on-extend when it carries real prompt tokens.
            for logical, phys in zip(plan.shared, entry.shared_pages):
                row[logical] = phys
            for logical, phys in zip(plan.private, pages):
                row[logical] = phys
            if plan.straddle_has_prompt:
                check(entry.straddle_page is not None,
                      "prefix entry lost its straddle page")
                dst = pages[plan.private.index(plan.straddle)]
                kp, vp, ks, vs = self.pool.arrays()
                self.pool.update(*self._copy_page(
                    jnp.int32(entry.straddle_page), jnp.int32(dst),
                    kp, vp, ks, vs))
            eng.out = eng.out.at[slot, 0].set(entry.first_token)
            eng.tok = eng.tok.at[slot].set(entry.first_token)
            eng.slot_pages[slot] = list(entry.pages()) + list(pages)
            self.prefix.consume(entry)
        else:
            shared = pages[:len(plan.shared)]
            private = pages[len(plan.shared):]
            for logical, phys in zip(plan.shared, shared):
                row[logical] = phys
            for logical, phys in zip(plan.private, private):
                row[logical] = phys
            tokens = np.zeros((1, bucket), dtype=np.int32)
            tokens[0, :n] = req.payload
            prompt_pages = jnp.asarray(row[:plan.n_prompt])
            kp, vp, ks, vs = self.pool.arrays()
            kp, vp, ks, vs, eng.out, eng.tok = \
                self._prefill_paged_for(bucket)(
                    params, jnp.asarray(tokens),
                    jnp.asarray([max(n, 1)], np.int32), jnp.int32(slot),
                    prompt_pages, kp, vp, ks, vs, eng.out, eng.tok)
            self.pool.update(kp, vp, ks, vs)
            eng.slot_pages[slot] = list(pages)
            if self.prefix is not None:
                straddle_phys = None
                if plan.straddle_has_prompt:
                    straddle_phys = private[
                        plan.private.index(plan.straddle)]
                eng.pending_publish[slot] = (
                    np.array(req.payload, np.int32, copy=True), shared,
                    straddle_phys, params_token)
        eng.ptab[slot] = row
        eng.ptab_dirty = True
        eng.plans[slot] = plan
        req._paged_claim = None         # the slot owns the pages now

    def _total_active(self) -> int:
        return sum(e.n_active() for e in self._engines.values())

    def _step_engines(self) -> None:
        params = None
        for eng in self._engines.values():
            if eng.n_active() == 0:
                continue
            if params is None:
                params = self.runner_ref.params_ref()
            try:
                if self.paged:
                    kp, vp, ks, vs = self.pool.arrays()
                    kp, vp, ks, vs, eng.out, eng.tok = \
                        self._step_paged_for(eng.bucket)(
                            params, _snapshot(eng.lengths),
                            _snapshot(eng.t), eng.device_ptab(), kp,
                            vp, ks, vs, eng.out, eng.tok)
                    self.pool.update(kp, vp, ks, vs)
                else:
                    eng.ck, eng.cv, eng.out, eng.tok = self._step(
                        params, _snapshot(eng.lengths),
                        _snapshot(eng.t), eng.ck, eng.cv, eng.out,
                        eng.tok)
            except Exception as e:  # noqa: BLE001 - shed this engine's
                log.error("continuous decode: step failed: %s", e)  # slots
                self._fail_engine(eng, e)
                continue
            self._c_steps.inc()
            for i, r in enumerate(eng.reqs):
                if r is not None:
                    eng.t[i] += 1

    def _publish_pending(self, eng, slot: int, row) -> None:
        """Deferred prefix publish at delivery: the first token is
        host-resident in the delivered row, and the store increfs the
        prompt pages BEFORE the slot's decref below — the entry can
        never hold freed pages."""
        pending = eng.pending_publish[slot]
        eng.pending_publish[slot] = None
        if pending is None or self.prefix is None \
                or not isinstance(row, np.ndarray):
            return
        payload, shared, straddle_phys, params_token = pending
        try:
            self.prefix.publish(payload, eng.bucket, int(row[0]), shared,
                                straddle_phys, params_token)
        except Exception as e:  # noqa: BLE001 - a publish failure loses
            log.error("prefix publish failed: %s", e)  # only reuse

    def _free_slot_pages(self, eng, slot: int) -> None:
        """Return a paged slot's page references and point its table row
        at the garbage page (an idle slot's confined-garbage step writes
        must never land in a page someone else now owns)."""
        if not self.paged:
            return
        eng.pending_publish[slot] = None
        pages = eng.slot_pages[slot]
        eng.slot_pages[slot] = []
        eng.plans[slot] = None
        eng.ptab[slot, :] = GARBAGE_PAGE
        eng.ptab_dirty = True
        if pages:
            self.pool.decref(pages)

    def _fail_engine(self, eng, err: Exception) -> None:
        for i, r in enumerate(eng.reqs):
            if r is None:
                continue
            eng.reqs[i] = None
            eng.lengths[i] = 1
            eng.t[i] = 0
            self._free_slot_pages(eng, i)
            self._unclaim(eng.bucket)
            self._safe_done(r, ShedError("closed", f"runner error: {err}"))
        self._g_active.set(self._total_active())
        self._g_inflight.set(self._total_active())

    def _deliver_finished(self) -> None:
        """Slots with all ``max_new`` tokens emitted deliver and free at
        this step boundary — in paged mode their pages return to the
        pool HERE, under the same worker/cv discipline every other slot
        mutation rides. Completions that land at the SAME boundary —
        the common case when ``max_new`` is small and requests joined
        together — are read back as ONE device sync (a single gathered
        [k, max_new] transfer) instead of one sync per request; the
        per-slot fallback path contains a failed batched read without
        losing the error-per-slot semantics."""
        import jax.numpy as jnp
        from multiverso_tpu.telemetry.critical_path import get_reservoir

        now = time.monotonic()
        reservoir = get_reservoir("serve")
        for eng in self._engines.values():
            done = [i for i, r in enumerate(eng.reqs)
                    if r is not None and eng.t[i] >= self.max_new - 1]
            if not done:
                continue
            rows = {}
            if len(done) > 1:
                try:
                    block = np.asarray(jnp.take(
                        eng.out, jnp.asarray(np.asarray(done, np.int32)),
                        axis=0))
                    rows = {i: block[k] for k, i in enumerate(done)}
                    self._c_batched_reads.inc()
                except Exception as e:  # noqa: BLE001 - fall back per-slot
                    log.error("continuous decode: batched readback "
                              "failed: %s", e)
            for i in done:
                r = eng.reqs[i]
                row = rows.get(i)
                if row is None:
                    try:
                        row = np.asarray(eng.out[i])
                    except Exception as e:  # noqa: BLE001 - contain
                        log.error("continuous decode: readback failed: "
                                  "%s", e)
                        row = ShedError("closed", f"runner error: {e}")
                eng.reqs[i] = None
                eng.lengths[i] = 1
                eng.t[i] = 0
                if self.paged:
                    self._publish_pending(eng, i, row)
                self._free_slot_pages(eng, i)
                self._unclaim(eng.bucket)
                if r.ctx is not None and r.ctx.sampled:
                    emit_span("serve.device", child_of(r.ctx),
                              eng.t_join[i], (now - eng.t_join[i]) * 1e3,
                              bucket=eng.bucket, continuous=1)
                self._c_batches.inc()
                self._h_device.observe((now - eng.t_join[i]) * 1e3)
                self._safe_done(r, row)
                total_ms = (now - r.t_submit) * 1e3
                if reservoir.would_admit(total_ms):
                    t_enq = r.t_enqueue or r.t_submit
                    reservoir.offer(
                        total_ms,
                        {"admission": (t_enq - r.t_submit) * 1e3,
                         "queue": (eng.t_join[i] - t_enq) * 1e3,
                         "device": (now - eng.t_join[i]) * 1e3},
                        trace=r.ctx.trace_hex if r.ctx is not None else "",
                        bucket=eng.bucket, continuous=1)
        self._g_active.set(self._total_active())
        self._g_inflight.set(self._total_active())

    def _safe_done(self, req: ServeRequest, result: object) -> None:
        # Instance override (DynamicBatcher's is a staticmethod): every
        # delivery path funnels here, so a reserved-but-never-joined
        # claim can never leak its pinned pages.
        self._release_claim(req)
        DynamicBatcher._safe_done(req, result)
