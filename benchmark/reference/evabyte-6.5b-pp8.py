"""Plain reference for one pipeline stage of EvaByte 6.5B (``evabyte``) as
``evabyte-6.5b-pp8`` states it: straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, no program code, no tables, no
kernels, no custom gradient.

A layer is two pre-norm residual blocks (RMSNorm with scale ``1 + w``, eps
1e-5): ``u <- u + EVA(RMSNorm(u))``, then ``u <- u + FFN(RMSNorm(u))``. The
functions below take the blocks one at a time, by letter: a model of ``n``
layers is the pattern ``VD VD ..``. Then a final RMSNorm and ``logits_t =
RMSNorm(u_t) W_head`` in R^(8 x 320): prediction head ``h`` predicts byte
``t + 1 + h``; the loss is the mean of ``-log softmax(logits_t[h])[x_(t+1+h)]``
over the eight heads and the positions whose target exists.

``V``  EVA (Zheng et al., "Efficient Attention via Control Variates", ICLR
       2023) as EvaByte's ``eva.py`` / ``eva_prep_kv`` / ``eva_agg`` fix it: no
       sampling, aligned non-overlapping windows, summaries only across
       windows. One head, ``s = 128 ** -0.5``, window ``W`` = 2,048, chunk ``C``
       = 16. (1) ``q, k, v = n W_q, n W_k, n W_v`` (no bias), 32 heads of 128;
       ``q`` and ``k`` turned by RoPE over all 128 dimensions, ``theta``
       100,000, ``x cos + rotate_half(x) sin`` (dimension ``i`` with ``i +
       64``), positions from the sequence's start. (2) Per head two learned
       vectors ``phi`` (``adaptive_phi``) and ``mu`` (``adaptive_mu_k``); for
       chunk ``c`` over positions ``j in [cC, (c+1)C)``, the sequence padded up
       to a whole chunk with masked positions: ``p_j = softmax_j(s phi . k_j)``
       over the chunk's unmasked positions, ``k~_c = sum_j p_j k_j + mu``,
       ``v~_c = sum_j p_j v_j`` (from the TURNED keys). (3) Query ``t`` of
       window ``w = t // W`` sees the keys ``j`` with ``wW <= j <= t`` and the
       summaries ``c`` with ``c // (W / C) < w`` (every chunk of every earlier
       window, none of its own), in ONE softmax: ``o_t = (sum_j exp(s q_t .
       k_j) v_j + sum_c exp(s q_t . k~_c) v~_c) / Z_t``. (4) ``y =
       concat_heads(o) W_o``. A window at a time: the scores ``[32, 2048,
       <= 2944]`` are formed outright.
``D``  ``y = (silu(x W_gate) * (x W_up)) W_down``, width 11,008.

Departures from the published code and sizes the catalog's row does not give,
each also in the configuration's ``assumed``: where ``phi`` and ``mu`` enter
(the pooling logits from ``phi`` alone, no ``-|k|^2 / 2`` term; ``mu`` added to
the pooled key) and the equal weight of the eight heads are the published
code's form as ISSUE 32 recalls it, with no network to check; float32
parameters and residual adds (``fp32_skip_add`` and ``fp32_logits`` are then
this precision itself; published bfloat16 with ``mixedp_attn``); attention runs
across document boundaries inside a packed sequence and positions count from
the sequence's start (no reset); AdaGrad, not the published optimizer.

One training step (``AdaGradUpdater`` as it documents itself): ``G += g^2; w -=
rho * g / sqrt(G + 1e-6)`` on every parameter; the embedding rows of a batch's
repeated ids take their summed gradient.

Targets. The callers hand over ``targets`` [B, S], each position's NEXT id
(the last position's wraps around), and ``mask`` [B, S], 0 at the last
position: what a one-target model trains on. The eight targets a position are
made here from them (:func:`multibyte_targets`), not taken from the program.

``compute`` is the type the arithmetic runs in (``bfloat16`` is the
lower-precision control; storage is rounded by the caller). Every softmax
(pooling, attention, loss) stays float32 whatever ``compute`` is.
"""
from __future__ import annotations

import functools

import numpy as np

ADAGRAD_EPS = 1e-6
EVA, DENSE = "V", "D"
TOKEN_BLOCK = 4096


def _jax():
    import jax
    import jax.numpy as jnp
    return jax, jnp


# -- pieces -------------------------------------------------------------------
def rmsnorm(x, w, eps):
    """Scale ``1 + w`` (``norm_add_unit_offset``)."""
    _, jnp = _jax()
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + w)


def rope(x, theta):
    """``x`` [S, heads, D]: ``x cos + rotate_half(x) sin``, the angle of
    position ``t`` and pair ``(i, i + D/2)`` being ``t * theta^(-2i/D)``,
    computed in float32."""
    _, jnp = _jax()
    half = x.shape[-1] // 2
    inv_freq = jnp.asarray(
        theta ** (-np.arange(half, dtype=np.float64) / half), jnp.float32)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    angle = jnp.concatenate([angle, angle], axis=-1)[:, None, :]
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * jnp.cos(angle).astype(x.dtype) \
        + rotated * jnp.sin(angle).astype(x.dtype)


def chunk_summaries(k, v, phi, mu, chunk, scale):
    """``k``, ``v`` [S, heads, D] -> (``k~``, ``v~``) [ceil(S / chunk), heads,
    D]; positions padded up to a whole chunk are masked out of the pooling
    softmax."""
    jax, jnp = _jax()
    length = k.shape[0]
    pad = (-length) % chunk

    def chunks(x):
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
        return x.reshape((-1, chunk) + x.shape[1:])

    kc, vc = chunks(k), chunks(v)
    real = (jnp.arange(length + pad) < length).reshape(-1, chunk)
    logits = jnp.einsum("nchd,hd->nch", kc, phi).astype(jnp.float32) * scale
    logits = jnp.where(real[:, :, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=1).astype(k.dtype)
    return (jnp.einsum("nch,nchd->nhd", p, kc) + mu,
            jnp.einsum("nch,nchd->nhd", p, vc))


def eva_attention(p, n, s):
    """One sequence: ``n`` [S, hidden] -> [S, hidden]; a window at a time,
    full softmax rows over [earlier windows' summaries | the window's own
    keys]."""
    jax, jnp = _jax()
    heads, window, chunk = (s["num_attention_heads"], s["window_size"],
                            s["chunk_size"])
    length = n.shape[0]
    dim = p["wq"].shape[1] // heads
    scale = dim ** -0.5
    q = rope((n @ p["wq"]).reshape(length, heads, dim), s["rope_theta"])
    k = rope((n @ p["wk"]).reshape(length, heads, dim), s["rope_theta"])
    v = (n @ p["wv"]).reshape(length, heads, dim)
    k_sum, v_sum = chunk_summaries(k, v, p["adaptive_phi"],
                                   p["adaptive_mu_k"], chunk, scale)

    @jax.checkpoint
    def one_window(qw, kw, vw, ks, vs):
        size = qw.shape[0]
        keys, values = jnp.concatenate([ks, kw]), jnp.concatenate([vs, vw])
        scores = jnp.einsum("thd,shd->hts", qw, keys).astype(jnp.float32) \
            * scale
        # every summary handed in is of an earlier window; own keys causal
        seen = jnp.concatenate(
            [jnp.ones((size, ks.shape[0]), bool),
             jnp.arange(size)[:, None] >= jnp.arange(size)[None, :]], axis=1)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", probs.astype(values.dtype), values)

    out = []
    for w in range(-(-length // window)):
        lo, hi = w * window, min(length, (w + 1) * window)
        before = w * (window // chunk)
        out.append(one_window(q[lo:hi], k[lo:hi], v[lo:hi], k_sum[:before],
                              v_sum[:before]))
    return jnp.concatenate(out).reshape(length, heads * dim) @ p["wo"]


def gated_ffn(n, gate, up, down):
    jax, _ = _jax()
    return (jax.nn.silu(n @ gate) * (n @ up)) @ down


def layer(kind, p, u, s, held=(), shared=True):
    """One block: ``u`` [B, S, hidden] -> (``u + mixer(RMSNorm(u))``, None,
    0.0): the second and third are what an expert block of another
    configuration would count and weigh (the drivers' common shape)."""
    jax, jnp = _jax()
    n = rmsnorm(u, p["norm"], s["norm_eps"])
    if kind == EVA:         # a sequence at a time
        return u + jnp.stack([eva_attention(p, seq, s) for seq in n]), \
            None, 0.0
    flat = n.reshape(-1, n.shape[-1])       # a block of tokens at a time
    blocks = max(1, len(flat) // TOKEN_BLOCK)
    if len(flat) % blocks:
        blocks = 1
    y = jax.lax.map(jax.checkpoint(lambda nb: gated_ffn(
        nb, p["ffn_gate"], p["ffn_up"], p["ffn_down"])),
        flat.reshape(blocks, -1, flat.shape[-1]))
    return u + y.reshape(u.shape), None, 0.0


def multibyte_targets(targets, mask, heads):
    """From each position's next id (``targets`` [B, S], wrapping at the end)
    the ids ``1 + h`` ahead, [B, S, heads], and which of them exist."""
    targets = np.asarray(targets)
    length = targets.shape[1]
    tokens = np.roll(targets, 1, axis=1)    # position t's own id
    ahead = np.zeros(targets.shape + (heads,), np.int32)
    exists = np.zeros(targets.shape + (heads,), np.float32)
    for h in range(heads):
        n = max(length - 1 - h, 0)
        ahead[:, :n, h] = tokens[:, 1 + h:1 + h + n]
        exists[:, :n, h] = 1.0
    exists[..., 0] = np.asarray(mask)
    return ahead, exists


def loss_from_hidden(final_norm, head, u, ahead, exists, s, block=2048):
    """(mean ``-log softmax(logits_t[h])[x_(t+1+h)]`` over the heads and the
    positions whose target exists, each head's own mean [heads]); ``u`` [T,
    hidden], ``ahead`` / ``exists`` [T, heads]; the logits a block of tokens
    at a time, in float32."""
    jax, jnp = _jax()
    t, heads = ahead.shape
    blk = min(block, t)
    pad = (-t) % blk
    if pad:
        u = jnp.concatenate([u, jnp.zeros((pad, u.shape[1]), u.dtype)])
        ahead = jnp.concatenate([ahead, jnp.zeros((pad, heads), ahead.dtype)])
        exists = jnp.concatenate([exists,
                                  jnp.zeros((pad, heads), exists.dtype)])

    @jax.checkpoint
    def tokens(xs):
        ub, ab, eb = xs
        logits = (rmsnorm(ub, final_norm, s["norm_eps"]) @ head).astype(
            jnp.float32).reshape(blk, heads, -1)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, ab[..., None], axis=-1)[..., 0]
        return jnp.sum(-picked * eb, axis=0)

    per_head = jnp.sum(jax.lax.map(tokens, (
        u.reshape(-1, blk, u.shape[1]), ahead.reshape(-1, blk, heads),
        exists.reshape(-1, blk, heads))), axis=0)
    return (jnp.sum(per_head) / jnp.maximum(jnp.sum(exists), 1.0),
            per_head / jnp.maximum(jnp.sum(exists, axis=0), 1.0))


def _cast(tree, compute):
    jax, jnp = _jax()
    return jax.tree_util.tree_map(lambda x: x.astype(compute), tree)


def model_loss(params, rows, where, ahead, exists, s, compute="float32"):
    """The whole model at once (small sizes): ``rows[where]`` is the embedded
    input. Returns (loss, each head's loss)."""
    params, rows = _cast(params, compute), rows.astype(compute)
    u = rows[where]
    for kind, p in zip(s["pattern"], params["layers"]):
        u = layer(kind, p, u, s)[0]
    heads = ahead.shape[-1]
    return loss_from_hidden(params["final_norm"], params["head"],
                            u.reshape(-1, u.shape[-1]),
                            ahead.reshape(-1, heads),
                            exists.reshape(-1, heads), s)


def _sizes_key(s):
    return tuple(sorted(s.items()))


@functools.lru_cache(maxsize=None)
def _whole_program(sizes, compute):
    jax, jnp = _jax()
    s = dict(sizes)

    def run(params, rows, where, ahead, exists):
        return jax.value_and_grad(model_loss, argnums=(0, 1), has_aux=True)(
            params, rows, where, ahead, exists, s, compute)

    return jax.jit(run)


def value_and_grads(params, rows, where, targets, mask, s, held=(),
                    compute="float32"):
    """(loss, each head's loss, 0.0, gradients of the parameters, of the
    rows) by ``jax.grad`` of :func:`model_loss`, at ``highest``."""
    jax, jnp = _jax()
    ahead, exists = multibyte_targets(targets, mask, s["num_pred_heads"])
    with jax.default_matmul_precision("highest"):
        (loss, per_head), (gp, grows) = _whole_program(
            _sizes_key(s), compute)(params, rows, where, ahead, exists)
    return loss, per_head, 0.0, _cast(gp, "float32"), grows.astype("float32")


# -- the same gradients a block at a time, so that the full size fits ---------
@functools.lru_cache(maxsize=None)
def _layer_programs(kind, sizes, compute):
    jax, jnp = _jax()
    s = dict(sizes)

    def fwd(p, u):
        return layer(kind, _cast(p, compute), u, s)[0]

    def bwd(p, u, g):
        return jax.vjp(fwd, p, u)[1](g)

    return jax.jit(fwd), jax.jit(bwd)


@functools.lru_cache(maxsize=None)
def _loss_program(sizes, compute):
    jax, jnp = _jax()
    s = dict(sizes)

    def top(final_norm, head, u, ahead, exists):
        return loss_from_hidden(final_norm.astype(compute),
                                head.astype(compute), u, ahead, exists, s)

    return jax.jit(jax.value_and_grad(top, argnums=(0, 1, 2), has_aux=True))


def grads_by_layer(get_layer, get_top, rows, where, targets, mask, s, held,
                   on_grad, compute="float32", inputs_on_host=False):
    """:func:`value_and_grads` with one block's weights and gradients alive at
    a time: ``get_layer(i)`` gives block ``i``'s parameters, ``get_top()``
    ``(final_norm, head)``, and ``on_grad(where, grads)`` takes block ``i``'s
    gradients (``where`` = i) or the top's (``where`` = "top") as they come.
    The chain rule written out: the forward keeps every block's input, the
    backward pulls the gradient back through one block after the other
    (``jax.vjp``); with ``inputs_on_host`` the kept inputs wait on the host.
    Returns (loss, each head's loss, 0.0, gradient of the rows): the shape
    the expert models' references return, their assignment counts and balance
    term being here the heads' losses and nothing."""
    jax, jnp = _jax()
    sizes = _sizes_key(s)
    ahead, exists = multibyte_targets(targets, mask, s["num_pred_heads"])
    heads = ahead.shape[-1]
    with jax.default_matmul_precision("highest"):
        u = rows.astype(compute)[where]
        inputs = []
        for i, kind in enumerate(s["pattern"]):
            inputs.append(np.asarray(u) if inputs_on_host else u)
            u = _layer_programs(kind, sizes, compute)[0](get_layer(i), u)
        shape = u.shape
        (loss, per_head), top_grads = _loss_program(sizes, compute)(
            *get_top(), u.reshape(-1, shape[-1]), ahead.reshape(-1, heads),
            exists.reshape(-1, heads))
        g = top_grads[2].reshape(shape)
        on_grad("top", _cast(top_grads[:2], "float32"))
        del u, top_grads        # nothing of the top outlives its turn
        for i in reversed(range(len(s["pattern"]))):
            bwd = _layer_programs(s["pattern"][i], sizes, compute)[1]
            gp, g = bwd(get_layer(i), jnp.asarray(inputs.pop()), g)
            on_grad(i, _cast(gp, "float32"))
        grows = jnp.zeros(rows.shape, jnp.float32).at[where.reshape(-1)].add(
            g.reshape(-1, shape[-1]).astype(jnp.float32))
    return loss, per_head, 0.0, grows


# -- the optimizer step -------------------------------------------------------
def adagrad(w, g2, g, rho):
    """``G += g^2; w -= rho * g / sqrt(G + eps)``; returns (w, G)."""
    _, jnp = _jax()
    g2 = g2 + g * g
    return w - rho * g / jnp.sqrt(g2 + ADAGRAD_EPS), g2


def pattern_of(config: dict) -> str:
    """Two letters a layer: ``V`` then ``D``."""
    return (EVA + DENSE) * config["num_hidden_layers"]


def sizes_of(config: dict) -> dict:
    """The sizes the functions above read, from a configuration file's keys."""
    keys = ("num_attention_heads", "window_size", "chunk_size", "rope_theta",
            "num_pred_heads")
    return dict({k: config[k] for k in keys}, pattern=pattern_of(config),
                norm_eps=config["rms_norm_eps"])
