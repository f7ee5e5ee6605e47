"""The general generators: every traffic mix is a JSON file of parameters that
one of these reads. Same seed, same inputs; and every seed gives the same
amount of work (the same sizes, in another order), so that seeds do not
change what a run costs."""
from __future__ import annotations

import functools

import numpy as np

_PERM_SEED = 0xC0FFEE


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream)])


# -- word2vec: a Zipf corpus ---------------------------------------------------
def zipf_rank_cdf(vocab: int) -> np.ndarray:
    """CDF of word frequency ~ 1/rank (text8-shaped ranks)."""
    p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64)
    cdf = np.cumsum(p)
    return cdf / cdf[-1]


def zipf_counts(vocab: int, total_words: int) -> np.ndarray:
    """Word counts of a corpus of ``total_words`` under the same law (the
    arithmetic of ``Dictionary.synthetic_zipf``)."""
    p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64)
    p /= p.sum()
    return np.maximum((p * total_words).astype(np.int64), 1)


def corpus_blocks(seed: int, cdf: np.ndarray, blocks: int, sentences: int,
                  words: int) -> np.ndarray:
    """int32 [blocks, sentences, words] word ids: one vectorised inverse-CDF
    draw (``bench.py`` walks a V-entry table once per sentence)."""
    u = rng_for(seed, 1).random((blocks, sentences, words))
    ids = np.searchsorted(cdf, u, side="right")
    return np.minimum(ids, len(cdf) - 1).astype(np.int32)


# -- DLRM: impressions (the arithmetic of models/dlrm/stream.py) ---------------------
def zipf_ids(rng, alpha: float, n: int, vocab: int) -> np.ndarray:
    if alpha > 1.0:
        return ((rng.zipf(alpha, n) - 1) % vocab).astype(np.int32)
    return rng.integers(0, vocab, size=n, dtype=np.int32)


def impression_batches(seed: int, batches: int, batch: int, fields: int,
                       vocab: int, dense_dim: int, zipf: float,
                       drift_every: int, drift_scale: float,
                       affinity_scale: float, click_bias: float) -> list:
    """``batches`` x (ids [batch, fields] int32, dense [batch, dense_dim]
    float32, labels [batch] float32): Zipf ids, labels from a logistic click
    model over per-id affinities that random-walk every ``drift_every``
    impressions."""
    rng = rng_for(seed, 2)
    theta = (affinity_scale / np.sqrt(max(1, fields))
             * rng.standard_normal((fields, vocab), dtype=np.float32))
    w_dense = rng.standard_normal(dense_dim) / np.sqrt(max(1, dense_dim))
    out, since = [], 0
    for _ in range(batches):
        ids = np.stack([zipf_ids(rng, zipf, batch, vocab)
                        for _ in range(fields)], axis=1)
        dense = rng.standard_normal((batch, dense_dim)).astype(np.float32)
        logit = (click_bias + theta[np.arange(fields), ids].sum(axis=1)
                 + dense @ w_dense)
        p = 1.0 / (1.0 + np.exp(-logit))
        labels = (rng.random(batch) < p).astype(np.float32)
        out.append((ids.astype(np.int32), dense, labels))
        since += batch
        while drift_every > 0 and since >= drift_every:
            since -= drift_every
            theta += drift_scale * rng.standard_normal(theta.shape,
                                                       dtype=np.float32)
    return out


# -- lookup serving: an open loop -----------------------------------------------
def loguniform_sizes(n: int, lo: int, hi: int) -> np.ndarray:
    """The fixed multiset of request sizes: the n quantiles of the
    log-uniform law on [lo, hi], so every seed offers the same rows."""
    q = (np.arange(n) + 0.5) / n
    return np.clip(np.round(np.exp(np.log(lo) + q * (np.log(hi) - np.log(lo)))
                            ), lo, hi).astype(np.int64)


def open_loop_schedule(seed: int, rate: float, seconds: float, keys_lo: int,
                       keys_hi: int):
    """(due [n] seconds from the window's start, sorted; sizes [n]): exactly
    ``round(rate * seconds)`` requests at sorted uniform times (a Poisson
    process given its count); the seed draws the times and shuffles the fixed
    sizes."""
    n = int(round(rate * seconds))
    rng = rng_for(seed, 3)
    due = np.sort(rng.random(n)) * seconds
    sizes = loguniform_sizes(n, keys_lo, keys_hi)
    rng.shuffle(sizes)
    return due, sizes


def zipf_keys(seed: int, request: int, size: int, alpha: float, rows: int,
              perm: np.ndarray) -> np.ndarray:
    """Keys of one request: Zipf(alpha) ranks through a fixed permutation
    (``scripts/serve_bench.py::_key_sampler``), drawn from (seed, request) so
    that any process can make any request's keys."""
    rng = np.random.default_rng([int(seed), 4, int(request)])
    ranks = (rng.zipf(alpha, size) - 1) % rows
    return perm[ranks]


@functools.lru_cache(maxsize=2)
def key_permutation(rows: int) -> np.ndarray:
    """The fixed rank -> row permutation (read-only: it is shared)."""
    perm = np.random.default_rng(_PERM_SEED).permutation(rows) \
        .astype(np.int32)
    perm.setflags(write=False)
    return perm
