"""Plain reference for one pipeline stage of MiniCPM-SALA 9B (``minicpm_sala``)
as ``minicpm-sala-9b-pp8`` states it: straightforward ``jax.numpy`` in float32
under ``jax.default_matmul_precision("highest")``, no program code, no tables, no
kernels, no custom gradient, no scan.

``d`` = 128, ``s = d ** -0.5``, ``r = scale_depth / sqrt(32)`` over the PUBLISHED
depth, RMSNorm scales by ``w`` with eps 1e-6, no bias anywhere. ``u_0 = scale_emb
* E[x]``. A layer is two pre-norm residual blocks, ``u <- u + r * mixer(
RMSNorm(u))`` then ``u <- u + r * FFN(RMSNorm(u))``; the functions below take
the blocks one at a time, by letter: the published ``mixer_types`` read as ``S``
(``minicpm4``) or ``N`` (``lightning-attn``), each followed by ``D``. Then
``logits = (RMSNorm(u) / (hidden_size / dim_model_base)) W_head`` and the mean
cross-entropy of the next token over the positions that have one.

``N``  Lightning attention. ``q, k, v = n W_q, n W_k, n W_v`` in 32 heads of 128;
       ``q`` and ``k`` take an RMSNorm over a head's 128 (one weight vector each,
       shared by the heads) and are turned by RoPE over the whole head, ``x cos
       + rotate_half(x) sin``, theta 10,000, positions from the sequence's start.
       Head ``h`` (1..32) of published layer ``l`` (0..31) decays by ``s_h = 2 **
       (-8 h / 32) * (1 - l / 31 + 1e-5)``, the heads held here being those the
       file's ``held_lightning_heads`` numbers: ``o_t = sum_{j <= t} exp(-s_h
       (t - j)) * s * (q_t . k_j) * v_j``, the sum written out over all ``j``,
       a block of queries at a time. ``y = RMSNorm(concat_heads(o))`` over all 4,096,
       ``y <- y * sigmoid(n W_g)``, output ``y W_o``.
``S``  InfLLM-v2. ``q`` in 32 heads, ``k, v`` in 2 of 128, head ``h`` with key-value
       head ``h // 16``; the same norms on ``q`` and ``k``; NO positions. Up to
       ``dense_len`` positions: causal softmax attention, scale ``s``. Longer:
       :func:`select_blocks` says, per query and key-value head and with no
       gradient, which 64-key blocks are attended; one softmax over the keys
       ``j <= t`` of those blocks. ``o <- o * sigmoid(n W_g)``, output ``o W_o``.
``D``  ``y = (silu(x W_gate) * (x W_up)) W_down``, width 16,384.

Sizes the catalog's row does not give and departures from the published code,
each also in the configuration's ``assumed``:
- sparse sizes: kernel 32, stride 16, block 64, window 2,048, 1 initial block,
  ``dense_len`` 8,192 (MiniCPM4's published ``sparse_config``), top-64 (the row's
  ``described_as``); pooled keys are plain means of 32 keys every 16, a block's
  score the max over the 5 pooled keys that reach into it (max-pool 5, stride 4,
  padding 1: InfLLM-v2's form as ISSUE 37 recalls it);
- the 64 chosen blocks are counted BESIDE the forced ones (the other reading
  counts them within);
- decay slopes and their layer factor: Lightning Attention's published
  ``build_slope_tensor`` form; the factor uses the layer's PUBLISHED index;
- no activation on ``q, k, v`` beyond the QK norm; the output norm is over all
  4,096; each gate is ``sigmoid(n W_g)`` on the mixer's output before ``W_o``;
- ``mup_denominator`` carried and not interpreted;
- init uniform of std 0.02 from ``--seed`` (``init_std`` is not published),
  out-projections not divided further, norms one;
- AdaGrad for the published optimizer, prescale 0.05, ``rho`` 2e-5;
- float32 parameters and residual adds; no reset at document boundaries;
  16,384 tokens a sequence (memory).

One training step (``AdaGradUpdater`` as it documents itself): ``G += g^2; w -=
rho * g / sqrt(G + 1e-6)`` on every parameter; the embedding rows of a batch's
repeated ids take their summed gradient.

``compute`` is the type the arithmetic runs in (``bfloat16`` is the
lower-precision control; storage is rounded by the caller). Every softmax, the
decay and the logits stay float32 whatever ``compute`` is.
"""
from __future__ import annotations

import functools

import numpy as np

ADAGRAD_EPS = 1e-6
SPARSE, LIGHTNING, DENSE = "S", "N", "D"
LETTER = {"minicpm4": SPARSE, "lightning-attn": LIGHTNING}
TOKEN_BLOCK = 2048
QUERY_BLOCK = 256


def _jax():
    import jax
    import jax.numpy as jnp
    return jax, jnp


# -- pieces -------------------------------------------------------------------
def rmsnorm(x, w, eps):
    _, jnp = _jax()
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


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


def decay_slopes(held, heads: int, layer: int, layers: int) -> np.ndarray:
    """``s_h`` of the heads ``held`` (each ``h`` one of 1..heads, the
    PUBLISHED count) in published layer ``layer`` of ``layers``."""
    h = np.asarray(held, dtype=np.float64)
    return (2.0 ** (-8.0 * h / heads)
            * (1.0 - layer / (layers - 1) + 1e-5)).astype(np.float32)


def _query_blocks(fn, length, *per_query):
    """``fn(positions [QB], *blocks)`` over blocks of ``QUERY_BLOCK`` queries,
    each rematerialised in the backward pass; the results side by side."""
    jax, jnp = _jax()
    qb = min(QUERY_BLOCK, length)
    pad = (-length) % qb

    def blocks(x):
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
        return x.reshape((-1, qb) + x.shape[1:])

    out = jax.lax.map(
        lambda xs: jax.checkpoint(fn)(*xs),
        (jnp.arange(length + pad).reshape(-1, qb),
         *(blocks(x) for x in per_query)))
    return jax.tree_util.tree_map(
        lambda o: o.reshape((-1,) + o.shape[2:])[:length], out)


def lightning_attention(p, n, s, slopes):
    """One sequence: ``n`` [S, hidden] -> [S, hidden]; every query's sum over
    all the keys at or before it, written out."""
    jax, jnp = _jax()
    heads = s["lightning_nh"]
    length = n.shape[0]
    dim = p["wq"].shape[1] // heads
    scale = dim ** -0.5
    q = rope(rmsnorm((n @ p["wq"]).reshape(length, heads, dim), p["q_norm"],
                     s["norm_eps"]), s["rope_theta"])
    k = rope(rmsnorm((n @ p["wk"]).reshape(length, heads, dim), p["k_norm"],
                     s["norm_eps"]), s["rope_theta"])
    v = (n @ p["wv"]).reshape(length, heads, dim)
    j = jnp.arange(length)

    def queries(t, qt):
        ago = (t[:, None] - j[None, :]).astype(jnp.float32)
        weight = jnp.exp(jnp.where(
            ago >= 0, -slopes[:, None, None] * ago, -jnp.inf))
        scores = jnp.einsum("thd,jhd->htj", qt, k) * scale
        return jnp.einsum("htj,jhd->thd", scores * weight.astype(qt.dtype), v)

    o = _query_blocks(queries, length, q).reshape(length, heads * dim)
    y = rmsnorm(o, p["o_norm"], s["norm_eps"]) * jax.nn.sigmoid(n @ p["wg"])
    return y @ p["wo"]


def select_blocks(q, k, s):
    """What each query attends, past ``dense_len``: ``q`` [S, H, D], ``k`` [S,
    K, D] -> [K, S, ceil(S / block)] bool, for query ``t`` and key-value head
    ``g`` (its heads ``h`` with ``h // (H / K) == g``). No gradient.

    Pooled key ``m``: the mean of ``k_j``, ``j`` in ``[stride m, stride m +
    kernel)``, for every ``m`` whose positions all exist; ``t`` sees ``m`` iff
    ``stride m + kernel - 1 <= t``. ``p^h = softmax_m(s q_t^h . kc_m)`` over the
    seen ``m`` (float32); ``a[m] = sum_h p^h[m]``. Block ``b`` is keys ``[block
    b, block (b + 1))``; its score is the largest ``a[m]`` over the seen ``m`` in
    ``{4b - 1, .., 4b + 3}`` (those whose positions reach into the block).
    FORCED: blocks ``< init_blocks`` and every block holding a position of
    ``[t - window + 1, t]``. CHOSEN: the ``topk`` highest-scoring blocks among
    those neither forced nor reaching past ``t - window`` (all if fewer; ties to
    the lower ``b``). Attended: forced or chosen."""
    jax, jnp = _jax()
    q, k = jax.lax.stop_gradient(q), jax.lax.stop_gradient(k)
    length, heads, dim = q.shape
    kv = k.shape[1]
    kernel, stride, block, window, init, topk = (
        s["kernel_size"], s["kernel_stride"], s["block_size"],
        s["window_size"], s["init_blocks"], s["topk"])
    pooled = (length - kernel) // stride + 1
    nblocks = -(-length // block)
    members = stride * np.arange(pooled)[:, None] + np.arange(kernel)[None, :]
    kc = jnp.mean(k[members], axis=1)                       # [pooled, K, D]
    m_last = jnp.asarray(members[:, -1])
    b = np.arange(nblocks)
    per, before = block // stride, kernel // stride - 1
    reach = per * b[:, None] + np.arange(-before, per)[None, :]  # [nb, 5]
    exists = jnp.asarray((reach >= 0) & (reach < pooled))
    reach = jnp.asarray(np.clip(reach, 0, pooled - 1))
    first, last = jnp.asarray(block * b), jnp.asarray(block * b + block - 1)

    def queries(t, qt):
        seen = m_last[None, :] <= t[:, None]                    # [QB, pooled]
        logits = jnp.einsum("tkgd,mkd->kgtm", qt.reshape(
            len(t), kv, heads // kv, dim), kc).astype(jnp.float32) \
            * dim ** -0.5
        logits = jnp.where(seen, logits, -jnp.inf)
        top = jnp.max(logits, axis=-1, keepdims=True)
        e = jnp.exp(logits - jnp.where(jnp.isfinite(top), top, 0.0))
        total = jnp.sum(e, axis=-1, keepdims=True)
        a = jnp.sum(jnp.where(total > 0, e / total, 0.0), axis=1)  # [K,QB,m]
        score = jnp.max(jnp.where(exists & seen[:, reach], a[:, :, reach],
                                  -jnp.inf), axis=-1)           # [K, QB, nb]
        recent = t[:, None] - window + 1
        forced = (first[None, :] <= t[:, None]) & (
            (b[None, :] < init) | (last[None, :] >= recent))
        free = (last[None, :] < recent) & (b[None, :] >= init)
        score = jnp.where(free, score, -jnp.inf)
        order = jnp.argsort(-score, axis=-1, stable=True)
        rank = jnp.argsort(order, axis=-1, stable=True)
        chosen = (rank < topk) & free
        return jnp.moveaxis(forced | chosen, 0, 1)              # [QB, K, nb]

    return jnp.moveaxis(_query_blocks(queries, length, q), 0, 1)


def sparse_attention(p, n, s):
    """One sequence: ``n`` [S, hidden] -> ([S, hidden], what was attended [K,
    S, blocks] bool, or None up to ``dense_len`` positions)."""
    jax, jnp = _jax()
    heads, kv = s["num_attention_heads"], s["num_key_value_heads"]
    length = n.shape[0]
    dim = p["wq"].shape[1] // heads
    q = rmsnorm((n @ p["wq"]).reshape(length, heads, dim), p["q_norm"],
                s["norm_eps"])
    k = rmsnorm((n @ p["wk"]).reshape(length, kv, dim), p["k_norm"],
                s["norm_eps"])
    v = (n @ p["wv"]).reshape(length, kv, dim)
    j = jnp.arange(length)
    sparse = length > s["dense_len"]
    attended = select_blocks(q, k, s) if sparse else None

    def queries(t, qt, *mine):
        seen = j[None, None, :] <= t[None, :, None]             # [1, QB, S]
        if mine:    # [QB, K, blocks] -> key by key
            seen = seen & jnp.moveaxis(jnp.repeat(
                mine[0], s["block_size"], axis=-1)[..., :length], 0, 1)
        qg = qt.reshape(len(t), kv, heads // kv, dim)
        scores = jnp.einsum("tkgd,jkd->kgtj", qg, k).astype(jnp.float32) \
            * dim ** -0.5
        probs = jax.nn.softmax(jnp.where(seen[:, None], scores, -jnp.inf),
                               axis=-1)
        return jnp.einsum("kgtj,jkd->tkgd", probs.astype(v.dtype), v)

    o = _query_blocks(queries, length, q, *(
        (jnp.moveaxis(attended, 0, 1),) if sparse else ()))
    o = o.reshape(length, heads * dim) * jax.nn.sigmoid(n @ p["wg"])
    return o @ p["wo"], attended


def gated_ffn(n, gate, up, down):
    jax, _ = _jax()
    return (jax.nn.silu(n @ gate) * (n @ up)) @ down


def block(kind, p, u, s, slopes=None):
    """One block: ``u`` [B, S, hidden] -> (``u + r * mixer(RMSNorm(u))``, what a
    sparse block's queries attended [B, K, S, blocks] or None). ``slopes``: a
    Lightning block's decay a head."""
    jax, jnp = _jax()
    r = s["residual_scale"]
    n = rmsnorm(u, p["norm"], s["norm_eps"])
    if kind == SPARSE:          # a sequence at a time
        outs = [sparse_attention(p, seq, s) for seq in n]
        attended = None if outs[0][1] is None \
            else jnp.stack([a for _, a in outs])
        return u + r * jnp.stack([y for y, _ in outs]), attended
    if kind == LIGHTNING:
        return u + r * jnp.stack([lightning_attention(p, seq, s, slopes)
                                  for seq in n]), None
    flat = n.reshape(-1, n.shape[-1])       # a block of tokens at a time
    blocks = max(1, len(flat) // TOKEN_BLOCK)
    if len(flat) % blocks:
        blocks = 1
    y = jax.lax.map(jax.checkpoint(lambda nb: gated_ffn(
        nb, p["ffn_gate"], p["ffn_up"], p["ffn_down"])),
        flat.reshape(blocks, -1, flat.shape[-1]))
    return u + r * y.reshape(u.shape), None


def loss_from_hidden(final_norm, head, u, targets, mask, s, block=2048):
    """Mean ``-log softmax((RMSNorm(u) / divisor) W_head)[target]`` over the
    unmasked positions; ``u`` [T, hidden]; the logits a block of tokens at a
    time, in float32."""
    jax, jnp = _jax()
    t = u.shape[0]
    blk = min(block, t)
    pad = (-t) % blk
    if pad:
        u = jnp.concatenate([u, jnp.zeros((pad, u.shape[1]), u.dtype)])
        targets = jnp.concatenate([targets, jnp.zeros(pad, targets.dtype)])
        mask = jnp.concatenate([mask, jnp.zeros(pad, mask.dtype)])

    @jax.checkpoint
    def tokens(xs):
        ub, tb, mb = xs
        n = rmsnorm(ub, final_norm, s["norm_eps"]) / s["logit_divisor"]
        logp = jax.nn.log_softmax((n @ head).astype(jnp.float32), axis=-1)
        return jnp.sum(-jnp.take_along_axis(logp, tb[:, None], axis=-1)[:, 0]
                       * mb)

    total = jnp.sum(jax.lax.map(tokens, (
        u.reshape(-1, blk, u.shape[1]), targets.reshape(-1, blk),
        mask.reshape(-1, blk))))
    return total / jnp.maximum(jnp.sum(mask), 1.0)


def _cast(tree, compute):
    jax, jnp = _jax()
    return jax.tree_util.tree_map(lambda x: x.astype(compute), tree)


def slopes_of(s, i):
    """Block ``i``'s decay, or None: the layer of a block is the number of
    mixers before it."""
    if s["pattern"][i] != LIGHTNING:
        return None
    layer = sum(kind != DENSE for kind in s["pattern"][:i])
    return decay_slopes(s["lightning_heads"], s["lightning_published_nh"],
                        layer, s["published_layers"])


def model_loss(params, rows, where, targets, mask, s, compute="float32"):
    """The whole model at once (small sizes): ``rows[where]`` is the embedded
    input. Returns (loss, what each sparse block attended)."""
    params, rows = _cast(params, compute), rows.astype(compute)
    u = s["scale_emb"] * rows[where]
    attended = []
    for i, (kind, p) in enumerate(zip(s["pattern"], params["layers"])):
        u, a = block(kind, p, u, s, slopes_of(s, i))
        if kind == SPARSE:
            attended.append(a)
    return loss_from_hidden(
        params["final_norm"], params["head"], u.reshape(-1, u.shape[-1]),
        targets.reshape(-1), mask.reshape(-1), s), attended


def _sizes_key(s):
    return tuple(sorted(s.items()))


@functools.lru_cache(maxsize=None)
def _whole_program(sizes, compute):
    jax, jnp = _jax()
    s = dict(sizes)

    def run(params, rows, where, targets, mask):
        return jax.value_and_grad(model_loss, argnums=(0, 1), has_aux=True)(
            params, rows, where, targets, mask, s, compute)

    return jax.jit(run)


def value_and_grads(params, rows, where, targets, mask, s,
                    compute="float32"):
    """(loss, what each sparse block attended, gradients of the parameters,
    of the rows) by ``jax.grad`` of :func:`model_loss`, at ``highest``."""
    jax, jnp = _jax()
    with jax.default_matmul_precision("highest"):
        (loss, attended), (gp, grows) = _whole_program(
            _sizes_key(s), compute)(params, rows, where, jnp.asarray(targets),
                                    jnp.asarray(mask))
    return loss, attended, _cast(gp, "float32"), grows.astype("float32")


# -- the same gradients a block at a time, so that the full size fits ---------
@functools.lru_cache(maxsize=None)
def _block_programs(kind, sizes, compute):
    jax, jnp = _jax()
    s = dict(sizes)

    def fwd(p, u, slopes):
        return block(kind, _cast(p, compute), u, s, slopes)

    def bwd(p, u, slopes, g):
        return jax.vjp(lambda p, u: fwd(p, u, slopes)[0], p, u)[1](g)

    return jax.jit(fwd), jax.jit(bwd)


@functools.lru_cache(maxsize=None)
def _loss_program(sizes, compute):
    jax, jnp = _jax()
    s = dict(sizes)

    def top(final_norm, head, u, targets, mask):
        return loss_from_hidden(final_norm.astype(compute),
                                head.astype(compute), u, targets, mask, s)

    return jax.jit(jax.value_and_grad(top, argnums=(0, 1, 2)))


def grads_by_block(get_block, get_top, rows, where, targets, mask, s, on_grad,
                   compute="float32", inputs_on_host=False):
    """:func:`value_and_grads` with one block's weights and gradients alive at
    a time: ``get_block(i)`` gives block ``i``'s parameters, ``get_top()``
    ``(final_norm, head)``, and ``on_grad(where, grads)`` takes block ``i``'s
    gradients (``where`` = i) or the top's (``where`` = "top") as they come.
    The chain rule written out: the forward keeps every block's input, the
    backward pulls the gradient back through one block after the other
    (``jax.vjp``); with ``inputs_on_host`` the kept inputs wait on the host.
    Returns (loss, what each sparse block attended (on the host), gradient of
    the rows)."""
    jax, jnp = _jax()
    sizes = _sizes_key(s)
    pattern = s["pattern"]
    with jax.default_matmul_precision("highest"):
        u = (s["scale_emb"] * rows.astype(compute))[where]
        inputs, attended = [], []
        for i, kind in enumerate(pattern):
            inputs.append(np.asarray(u) if inputs_on_host else u)
            u, a = _block_programs(kind, sizes, compute)[0](
                get_block(i), u, slopes_of(s, i))
            if kind == SPARSE:
                attended.append(None if a is None else np.asarray(a))
        shape = u.shape
        loss, top_grads = _loss_program(sizes, compute)(
            *get_top(), u.reshape(-1, shape[-1]),
            jnp.asarray(targets).reshape(-1), jnp.asarray(mask).reshape(-1))
        g = top_grads[2].reshape(shape)
        on_grad("top", _cast(top_grads[:2], "float32"))
        del u, top_grads        # nothing of the top outlives its turn
        for i in reversed(range(len(pattern))):
            bwd = _block_programs(pattern[i], sizes, compute)[1]
            gp, g = bwd(get_block(i), jnp.asarray(inputs.pop()),
                        slopes_of(s, i), g)
            on_grad(i, _cast(gp, "float32"))
        grows = jnp.zeros(rows.shape, jnp.float32).at[where.reshape(-1)].add(
            s["scale_emb"] * g.reshape(-1, shape[-1]).astype(jnp.float32))
    return loss, attended, grows


# -- the optimizer step -------------------------------------------------------
def adagrad(w, g2, g, rho):
    """``G += g^2; w -= rho * g / sqrt(G + eps)``; returns (w, G)."""
    _, jnp = _jax()
    g2 = g2 + g * g
    return w - rho * g / jnp.sqrt(g2 + ADAGRAD_EPS), g2


def pattern_of(config: dict) -> str:
    """Two letters a layer: the mixer ``mixer_types`` names, then ``D``."""
    return "".join(LETTER[name] + DENSE for name in
                   config["mixer_types"][:config["num_hidden_layers"]])


def sizes_of(config: dict) -> dict:
    """The sizes the functions above read, from a configuration file's keys."""
    keys = ("num_attention_heads", "num_key_value_heads", "lightning_nh",
            "rope_theta", "scale_emb")
    published = config.get("published", config)
    layers = published["num_hidden_layers"]
    sparse = ("kernel_size", "kernel_stride", "block_size", "window_size",
              "init_blocks", "topk", "dense_len")
    return dict({k: config[k] for k in keys},
                **{k: config["sparse_config"][k] for k in sparse},
                pattern=pattern_of(config), norm_eps=config["rms_norm_eps"],
                published_layers=layers,
                lightning_published_nh=published["lightning_nh"],
                lightning_heads=tuple(config.get("held_lightning_heads")
                                      or range(1, config["lightning_nh"] + 1)),
                residual_scale=config["scale_depth"] / layers ** 0.5,
                logit_divisor=config["hidden_size"]
                / config["dim_model_base"])
