"""Plain reference for skip-gram with negative sampling under AdaGrad, as
``w2v-sgns-4m`` states it: float32 NumPy, no program code, no tables.

One step on a batch of (center, context, K negatives, mask) at rate ``lr``:

    u = w_in[c];  vp = w_out[o];  vn = w_out[neg]
    sp = sigmoid(u.vp);  sn = sigmoid(u.vn)
    loss = -sum(mask * log(sp + 1e-7)) - sum(mask * log(1 - sn + 1e-7))
    du = (sp - 1) mask vp + sum_k sn mask vn;  dvp = (sp - 1) mask u;
    dvn = sn mask u
    per table: g2[rows] += grad**2 (duplicates summed first), then
               w[rows]  -= lr * grad / sqrt(g2[rows] + 1e-6) (summed too)

It works on the rows a run touches only, which ``seeded.rows_np`` makes from
the seed; ``storage`` rounds what is stored after every update, which is how
the lower-precision control (bfloat16 tables) is computed.
"""
from __future__ import annotations

import numpy as np

LOSS_EPS = 1e-7
ADAGRAD_EPS = 1e-6


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x, dtype=np.float32))


def _store(x, storage):
    return x.astype(storage).astype(np.float32) if storage else x


class Rows:
    """The touched rows of one (parameter, accumulator) pair of tables,
    compacted: ``ids`` are the table's row numbers, sorted and unique."""

    def __init__(self, ids, w, g2, storage=None):
        self.ids = np.asarray(ids)
        self.w = _store(np.asarray(w, np.float32), storage)
        self.g2 = np.asarray(g2, np.float32).copy()
        self.storage = storage

    def local(self, rows):
        pos = np.searchsorted(self.ids, rows)
        if not np.array_equal(self.ids[pos], rows):
            raise ValueError("a row outside the touched set")
        return pos

    def update(self, rows, grad, lr):
        pos = self.local(rows)
        np.add.at(self.g2, pos, np.square(grad, dtype=np.float32))
        denom = np.sqrt(self.g2[pos] + np.float32(ADAGRAD_EPS),
                        dtype=np.float32)
        step = (-np.float32(lr) * grad / denom).astype(np.float32)
        step = _store(step, self.storage)
        np.add.at(self.w, pos, step)
        self.w = _store(self.w, self.storage)


def step(w_in: Rows, w_out: Rows, centers, contexts, negatives, mask, lr):
    """One sg-ns + AdaGrad step in place; returns the batch's summed loss."""
    mask = np.asarray(mask, np.float32)
    u = w_in.w[w_in.local(centers)]
    vp = w_out.w[w_out.local(contexts)]
    B, K = negatives.shape
    vn = w_out.w[w_out.local(negatives.reshape(-1))].reshape(B, K, -1)
    sp = _sigmoid(np.sum(u * vp, axis=-1, dtype=np.float32))
    sn = _sigmoid(np.einsum("bd,bkd->bk", u, vn, dtype=np.float32))
    loss = (-(mask * np.log(sp + np.float32(LOSS_EPS))).sum(dtype=np.float64)
            - (mask[:, None] * np.log(1.0 - sn + np.float32(LOSS_EPS))
               ).sum(dtype=np.float64))
    gp = ((sp - 1.0) * mask).astype(np.float32)
    gn = (sn * mask[:, None]).astype(np.float32)
    du = gp[:, None] * vp + np.einsum("bk,bkd->bd", gn, vn, dtype=np.float32)
    dvp = gp[:, None] * u
    dvn = gn[..., None] * u[:, None, :]
    w_in.update(centers, du.astype(np.float32), lr)
    w_out.update(np.concatenate([contexts, negatives.reshape(-1)]),
                 np.concatenate([dvp, dvn.reshape(B * K, -1)]
                                ).astype(np.float32), lr)
    return float(loss)


def expected_pairs(keep_prob, sentences, window: int) -> float:
    """Expected number of (center, context) pairs a block yields: word i
    pairs with word i+d, both kept by subsampling, when the dynamic window
    drawn uniformly on 1..window reaches d, from either side."""
    kp = np.asarray(keep_prob, np.float64)[np.asarray(sentences)]
    out = 0.0
    for d in range(1, window + 1):
        out += (2.0 * (window - d + 1) / window
                * float(np.sum(kp[:, :-d] * kp[:, d:])))
    return out


def keep_probability(counts, sample: float):
    """word2vec's subsampling: P(keep) = min(1, sqrt(t/f) + t/f)."""
    counts = np.asarray(counts, np.float64)
    ratio = sample / np.maximum(counts / counts.sum(), 1e-12)
    return np.minimum(1.0, np.sqrt(ratio) + ratio)
