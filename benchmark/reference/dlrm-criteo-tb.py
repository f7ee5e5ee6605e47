"""Plain reference for DLRM (Naumov et al., arXiv:1906.00091) as
``dlrm-criteo-tb`` states it: straightforward ``jax.numpy`` in float32 with
matrix products at ``highest`` precision, no program code, no tables.

    h = bottom MLP (ReLU after every layer) of the dense features
    z = [h, e_1 .. e_F]                      the F embedding rows of a sample
    t = [h, (z_i . z_j) for i < j]           dot interaction
    logit = top MLP(t) (ReLU but for the last layer);  loss = mean BCE(logit, y)

One training step: dense parameters take plain SGD at ``lr``; each field's
touched rows take server-side AdaGrad, the gradients of a batch's duplicate
ids summed first:  G[r] += g_r**2;  row[r] -= rho * g_r / sqrt(G[r] + 1e-6).

``compute`` is the type the arithmetic runs in and ``storage`` rounds what is
stored: bfloat16 for both is the lower-precision control.
"""
from __future__ import annotations

import numpy as np

ADAGRAD_EPS = 1e-6


def layer_dims(dense_dim, bottom, embed_dim, fields, top):
    dims, prev = [], dense_dim
    for h in tuple(bottom) + (embed_dim,):
        dims.append((prev, h))
        prev = h
    n = fields + 1
    prev = embed_dim + n * (n - 1) // 2
    for h in tuple(top) + (1,):
        dims.append((prev, h))
        prev = h
    return dims


def make_loss(n_bottom: int, fields: int):
    import jax
    import jax.numpy as jnp
    iu = np.triu_indices(fields + 1, k=1)

    def loss_fn(params, emb, dense_x, y):
        h = dense_x
        for W, b in params[:n_bottom]:
            h = jax.nn.relu(h @ W + b)
        z = jnp.concatenate([h[:, None, :], emb], axis=1)
        prods = jnp.einsum("bij,bkj->bik", z, z)
        t = jnp.concatenate([h, prods[:, iu[0], iu[1]]], axis=1)
        for W, b in params[n_bottom:-1]:
            t = jax.nn.relu(t @ W + b)
        W, b = params[-1]
        logit = (t @ W + b)[:, 0].astype(jnp.float32)
        return jnp.mean(jnp.maximum(logit, 0.0) - logit * y
                        + jnp.log1p(jnp.exp(-jnp.abs(logit))))

    return loss_fn


class Model:
    """Dense parameters and, per field, the touched embedding rows."""

    def __init__(self, params, field_ids, field_rows, n_bottom, lr, rho,
                 compute="float32", storage=None):
        import jax
        self.n_bottom, self.lr, self.rho = n_bottom, lr, rho
        self.compute, self.storage = compute, storage
        self.params = [(self._st(W), self._st(b)) for W, b in params]
        self.ids = [np.asarray(i) for i in field_ids]       # sorted unique
        self.rows = [self._st(r) for r in field_rows]
        self.g2 = [np.zeros(r.shape, np.float32) for r in field_rows]
        fields = len(self.ids)
        self._grad = jax.jit(jax.value_and_grad(
            make_loss(n_bottom, fields), argnums=(0, 1)))

    def _st(self, x):
        x = np.asarray(x, np.float32)
        return x.astype(self.storage).astype(np.float32) if self.storage \
            else x

    def step(self, ids, dense_x, labels) -> float:
        import jax
        import jax.numpy as jnp
        pos = [np.searchsorted(self.ids[f], ids[:, f])
               for f in range(len(self.ids))]
        emb = np.stack([self.rows[f][pos[f]] for f in range(len(pos))],
                       axis=1)
        ct = jnp.dtype(self.compute)
        with jax.default_matmul_precision("highest"):
            loss, (gp, gemb) = self._grad(
                [(jnp.asarray(W, ct), jnp.asarray(b, ct))
                 for W, b in self.params],
                jnp.asarray(emb, ct), jnp.asarray(dense_x, ct),
                jnp.asarray(labels, jnp.float32))
        gemb = np.asarray(gemb.astype(jnp.float32))
        self.params = [
            (self._st(W - self.lr * np.asarray(gW.astype(jnp.float32))),
             self._st(b - self.lr * np.asarray(gb.astype(jnp.float32))))
            for (W, b), (gW, gb) in zip(self.params, gp)]
        for f, p in enumerate(pos):
            g = np.zeros_like(self.rows[f])
            np.add.at(g, p, gemb[:, f, :])
            touched = np.unique(p)
            self.g2[f][touched] += np.square(g[touched])
            self.rows[f][touched] -= (
                self.rho * g[touched]
                / np.sqrt(self.g2[f][touched] + np.float32(ADAGRAD_EPS)))
            self.rows[f] = self._st(self.rows[f])
        return float(loss)
