"""Plain reference for one chip's share of NVIDIA-Nemotron-3-Nano-30B-A3B
(``nemotron_h``) as ``nemotron3-nano-30b-a3b-ep16`` states it: straightforward
``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``,
no program code, no tables, no kernels.

Every layer is ``u <- u + mixer(RMSNorm_w(u))`` (eps 1e-5), the mixer by the
layer's letter; then a final RMSNorm and ``logits = u W_head``; the loss is the
mean next-token cross-entropy over the unmasked positions.

``M``  ``[z | xBC | dt] = n W_in``; ``xBC <- silu(causal depthwise conv1d(xBC,
       k) + b)``, split into ``x [S,H,P]``, ``B, C [S,G,N]``; ``dt <-
       softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head ``h_t =
       exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``, ``y_t = h_t C_t + D x_t``;
       ``y <- GroupRMSNorm_w(y * silu(z))``; ``out = y W_out``.
       ``ssm="recurrence"`` steps through the tokens as written;
       ``ssm="cumulative"`` is the textbook closed form ``y_t = sum_{s<=t}
       exp(cum_t - cum_s) (C_t . B_s) dt_s x_s`` with ``cum = cumsum(dt A)``, in
       blocks of queries so that a sequence of 8,192 fits (the recurrence's
       backward would keep 8,192 states).
``*``  ``q, k, v = n W_q, n W_k, n W_v``; causal softmax at ``head_dim**-0.5``,
       query head ``h`` reads key-value head ``h // (heads / kv_heads)``; full
       softmax rows, in blocks of queries; ``out = o W_o``.
``E``  ``s = sigmoid(n W_r)``; the ``k`` largest of ``s + b`` chosen; ``w =
       s[chosen] / (sum + 1e-20) * scaling``; ``out = sum_{e held} w_e
       relu(n W_up,e)^2 W_down,e + relu(n W_up,s)^2 W_down,s``: a loop over the
       HELD experts, each over every token under its gate (0 where not chosen).

Departures from the published description, each also in the configuration's
``assumed``: no rotary or other positions (the published code applies none);
``b`` fixed; no load-balancing term; state and attention run across document
boundaries inside a sequence; what the experts NOT held would add is left out
(``held`` is an argument: every share of the experts can be computed, and the
shares add up to the whole layer, ``shared=False`` on all but one).

One training step (``AdaGradUpdater`` as it documents itself): ``G += g^2; w -=
rho * g / sqrt(G + 1e-6)`` on every parameter; the embedding rows of a batch's
repeated ids take their summed gradient.

``compute`` is the type the arithmetic runs in (``bfloat16`` is the
lower-precision control; storage is rounded by the caller).
"""
from __future__ import annotations

import functools

import numpy as np

ADAGRAD_EPS = 1e-6
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
EXPERT_TOKEN_BLOCK = 4096


def _jax():
    import jax
    import jax.numpy as jnp
    return jax, jnp


# -- pieces -------------------------------------------------------------------
def rmsnorm(x, w, eps):
    _, jnp = _jax()
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def causal_conv(x, w, b):
    """``x`` [S, C], ``w`` [C, K]: ``out[t] = sum_j w[:, j] x[t-(K-1)+j] + b``."""
    _, jnp = _jax()
    k, s = w.shape[1], x.shape[0]
    xp = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return sum(xp[j:j + s] * w[:, j] for j in range(k)) + b


def ssm_recurrence(x, dt, a, b, c):
    """The recurrence as written, token by token: ``x`` [S,H,P], ``dt`` [S,H],
    ``a`` [H], ``b``/``c`` [S,G,N] (a group serves H/G heads)."""
    jax, jnp = _jax()
    h, p = x.shape[1:]
    g, n = b.shape[1:]
    rep = h // g

    def token(state, t):
        xt, dtt, bt, ct = t
        bt, ct = jnp.repeat(bt, rep, axis=0), jnp.repeat(ct, rep, axis=0)
        state = jnp.exp(dtt * a).astype(x.dtype)[:, None, None] * state \
            + (dtt.astype(x.dtype)[:, None] * xt)[:, :, None] \
            * bt[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, ct)

    _, y = jax.lax.scan(token, jnp.zeros((h, p, n), x.dtype), (x, dt, b, c))
    return y


def ssm_cumulative(x, dt, a, b, c, block=64):
    """The same outputs from the closed form, a block of queries at a time."""
    jax, jnp = _jax()
    s, h, p = x.shape
    g, n = b.shape[1:]
    rep = h // g
    blk = min(block, s)
    pad = (-s) % blk
    if pad:
        x, dt, b, c = (jnp.concatenate(
            [t, jnp.zeros((pad,) + t.shape[1:], t.dtype)]) for t in
            (x, dt, b, c))
    cum = jnp.cumsum(dt * a, axis=0)                        # [S, H], float32
    xdt = (x * dt.astype(x.dtype)[:, :, None]).reshape(-1, g, rep, p)
    keys = jnp.arange(s + pad)

    @jax.checkpoint
    def queries(i):
        cq = jax.lax.dynamic_slice_in_dim(c, i * blk, blk)
        cumq = jax.lax.dynamic_slice_in_dim(cum, i * blk, blk)
        rows = i * blk + jnp.arange(blk)
        cb = jnp.einsum("tgn,sgn->gts", cq, b)              # [G, blk, S]
        diff = cumq.T[:, :, None] - cum.T[:, None, :]       # [H, blk, S]
        decay = jnp.exp(jnp.where(rows[:, None] >= keys[None, :], diff,
                                  -jnp.inf)).astype(x.dtype)
        w = decay.reshape(g, rep, blk, -1) * cb[:, None]
        return jnp.einsum("grts,sgrp->tgrp", w, xdt)

    y = jax.lax.map(queries, jnp.arange((s + pad) // blk))
    return y.reshape(s + pad, h, p)[:s]


def mamba_mixer(p, n, s, ssm):
    """One sequence: ``n`` [S, hidden]."""
    jax, jnp = _jax()
    h, hp = s["mamba_num_heads"], s["mamba_head_dim"]
    g, st = s["n_groups"], s["ssm_state_size"]
    d_inner, conv_dim = h * hp, h * hp + 2 * g * st
    zxbcdt = n @ p["in_proj"]
    z, xbc, dt = (zxbcdt[:, :d_inner], zxbcdt[:, d_inner:d_inner + conv_dim],
                  zxbcdt[:, d_inner + conv_dim:])
    xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], p["conv_b"]))
    x = xbc[:, :d_inner].reshape(-1, h, hp)
    b = xbc[:, d_inner:d_inner + g * st].reshape(-1, g, st)
    c = xbc[:, d_inner + g * st:].reshape(-1, g, st)
    # the step sizes and decay rates stay float32 whatever ``compute`` is, as
    # the published kernels keep them: a running sum of thousands of them in
    # bfloat16 is off by more than exp() can take
    dt = jax.nn.softplus((dt + p["dt_bias"]).astype(jnp.float32))
    a = -jnp.exp(p["A_log"].astype(jnp.float32))
    run = ssm_recurrence if ssm == "recurrence" else ssm_cumulative
    y = run(x, dt, a, b, c) + x * p["D"][:, None]
    y = (y.reshape(-1, d_inner) * jax.nn.silu(z)).reshape(-1, g, d_inner // g)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + s["norm_eps"])
    return (y.reshape(-1, d_inner) * p["gnorm"]) @ p["out_proj"]


def attention_mixer(p, n, s, block=128):
    """One sequence: full softmax rows, a block of queries at a time."""
    jax, jnp = _jax()
    heads, kvh, d = (s["num_attention_heads"], s["num_key_value_heads"],
                     s["head_dim"])
    length = n.shape[0]
    q = (n @ p["wq"]).reshape(length, heads, d)
    k = jnp.repeat((n @ p["wk"]).reshape(length, kvh, d), heads // kvh, axis=1)
    v = jnp.repeat((n @ p["wv"]).reshape(length, kvh, d), heads // kvh, axis=1)
    blk = min(block, length)
    pad = (-length) % blk
    if pad:
        q = jnp.concatenate([q, jnp.zeros((pad, heads, d), q.dtype)])
    keys = jnp.arange(length)

    @jax.checkpoint
    def queries(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * blk, blk)
        rows = i * blk + jnp.arange(blk)
        scores = jnp.einsum("thd,shd->hts", qi, k) * (d ** -0.5)
        scores = jnp.where(rows[:, None] >= keys[None, :], scores, -jnp.inf)
        return jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)

    o = jax.lax.map(queries, jnp.arange((length + pad) // blk))
    return o.reshape(length + pad, heads * d)[:length] @ p["wo"]


def route(p, bias, n, s):
    """(chosen [T, k], weights [T, k]): float32 whatever ``compute`` is, as
    the published code keeps its router."""
    jax, jnp = _jax()
    scores = jax.nn.sigmoid(n.astype(jnp.float32)
                            @ p["router"].astype(jnp.float32))
    _, chosen = jax.lax.top_k(scores + bias, s["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if s["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * s["routed_scaling_factor"]


def expert_mixer(p, bias, n, s, held, shared=True):
    """``n`` [T, hidden] -> (this share's output, assignments per held
    expert). ``p["w_up"][i]`` / ``p["w_down"][i]`` are expert ``held[i]``'s."""
    jax, jnp = _jax()
    chosen, w = route(p, bias, n, s)
    out = jnp.zeros_like(n)
    counts = []
    @jax.checkpoint         # an expert's products are not kept for the backward
    def expert(n, up, down):
        return jnp.square(jax.nn.relu(n @ up)) @ down

    for i, e in enumerate(held):
        gate = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)
        counts.append(jnp.sum(chosen == e))
        out = out + gate.astype(n.dtype)[:, None] * expert(
            n, p["w_up"][i], p["w_down"][i])
    if shared:
        out = out + jnp.square(jax.nn.relu(n @ p["s_up"])) @ p["s_down"]
    return out, jnp.stack(counts)


def layer(kind, p, bias, u, s, held, ssm="recurrence", shared=True):
    """``u`` [B, S, hidden] -> (``u + mixer(RMSNorm_w(u))``, counts or None)."""
    jax, jnp = _jax()
    n = rmsnorm(u, p["norm"], s["norm_eps"])
    if kind in (MAMBA, ATTENTION):      # a sequence at a time, none kept
        mixer = (lambda seq: mamba_mixer(p, seq, s, ssm)) if kind == MAMBA \
            else (lambda seq: attention_mixer(p, seq, s))
        return u + jax.lax.map(jax.checkpoint(mixer), n), None
    n = n.reshape(-1, n.shape[-1])
    blocks = max(1, len(n) // EXPERT_TOKEN_BLOCK)
    if len(n) % blocks:
        blocks = 1
    # token by token the layer is local: a block of tokens at a time, so that
    # the [tokens, width] products of a long batch never exist at once
    y, counts = jax.lax.map(
        jax.checkpoint(lambda nb: expert_mixer(p, bias, nb, s, held, shared)),
        n.reshape(blocks, -1, n.shape[-1]))
    return u + y.reshape(u.shape), jnp.sum(counts, axis=0)


def loss_from_hidden(final_norm, head, u, targets, mask, s, block=2048):
    """Mean cross-entropy over the unmasked positions, ``u`` [T, hidden], the
    logits a block of tokens at a time."""
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
        logits = (rmsnorm(ub, final_norm, s["norm_eps"]) @ head).astype(
            jnp.float32)
        picked = jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]
        return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - picked) * mb)

    parts = jax.lax.map(tokens, (u.reshape(-1, blk, u.shape[1]),
                                 targets.reshape(-1, blk),
                                 mask.reshape(-1, blk)))
    return jnp.sum(parts) / jnp.maximum(jnp.sum(mask), 1.0)


def _cast(tree, compute):
    jax, jnp = _jax()
    return jax.tree_util.tree_map(lambda x: x.astype(compute), tree)


def model_loss(params, rows, buffers, where, targets, mask, s, held,
               ssm="recurrence", compute="float32"):
    """The whole model at once (small sizes): ``rows[where]`` is the embedded
    input. Returns (loss, [expert layers, held] assignment counts)."""
    jax, jnp = _jax()
    params, rows = _cast(params, compute), rows.astype(compute)
    u = rows[where]
    counts = []
    for kind, p, bias in zip(s["pattern"], params["layers"], buffers):
        u, c = layer(kind, p, bias, u, s, held, ssm)
        if c is not None:
            counts.append(c)
    loss = loss_from_hidden(params["final_norm"], params["head"],
                            u.reshape(-1, u.shape[-1]), targets.reshape(-1),
                            mask.reshape(-1), s)
    return loss, (jnp.stack(counts) if counts else None)


def _sizes_key(s):
    return tuple(sorted(s.items()))


@functools.lru_cache(maxsize=None)
def _whole_program(sizes, held, ssm, compute):
    jax, jnp = _jax()
    s = dict(sizes)

    def run(params, rows, buffers, where, targets, mask):
        return jax.value_and_grad(model_loss, argnums=(0, 1), has_aux=True)(
            params, rows, buffers, where, targets, mask, s, held, ssm,
            compute)

    return jax.jit(run)


def value_and_grads(params, rows, buffers, where, targets, mask, s, held,
                    ssm="recurrence", compute="float32"):
    """(loss, counts, gradients of the parameters, of the rows) by
    ``jax.grad`` of :func:`model_loss`, at ``highest``."""
    jax, jnp = _jax()
    with jax.default_matmul_precision("highest"):
        (loss, counts), (gp, grows) = _whole_program(
            _sizes_key(s), tuple(held), ssm, compute)(
                params, rows, buffers, where, targets, mask)
    return loss, counts, _cast(gp, "float32"), grows.astype("float32")


# -- the same gradients a layer at a time, so that the full size fits ---------
@functools.lru_cache(maxsize=None)
def _layer_programs(kind, sizes, held, ssm, compute):
    jax, jnp = _jax()
    s = dict(sizes)

    def fwd(p, bias, u):
        return layer(kind, _cast(p, compute), bias, u, s, held, ssm)

    def bwd(p, bias, u, g):
        _, pull = jax.vjp(lambda p, u: fwd(p, bias, u)[0], p, u)
        return pull(g)

    return jax.jit(fwd), jax.jit(bwd)


@functools.lru_cache(maxsize=None)
def _loss_program(sizes, compute):
    jax, jnp = _jax()
    s = dict(sizes)

    def top(final_norm, head, u, targets, mask):
        return loss_from_hidden(final_norm.astype(compute),
                                head.astype(compute), u, targets, mask, s)

    return jax.jit(jax.value_and_grad(top, argnums=(0, 1, 2)))


def grads_by_layer(get_layer, get_top, rows, buffers, where, targets, mask, s,
                   held, on_grad, ssm="recurrence", compute="float32",
                   inputs_on_host=False):
    """:func:`value_and_grads` with one layer's weights and gradients alive at a
    time: ``get_layer(i)`` gives layer ``i``'s parameters, ``get_top()``
    ``(final_norm, head)``, and ``on_grad(where, grads)`` takes layer ``i``'s
    gradients (``where`` = i) or the top's (``where`` = "top") as they come.
    The chain rule written out: the forward keeps every layer's input, the
    backward pulls the gradient back through one layer after the other
    (``jax.vjp``); with ``inputs_on_host`` the kept inputs wait on the host.
    Returns (loss, counts, gradient of the rows)."""
    jax, jnp = _jax()
    sizes, held = _sizes_key(s), tuple(held)
    with jax.default_matmul_precision("highest"):
        u = rows.astype(compute)[where]
        inputs, counts = [], []
        for i, kind in enumerate(s["pattern"]):
            inputs.append(np.asarray(u) if inputs_on_host else u)
            fwd, _ = _layer_programs(kind, sizes, held, ssm, compute)
            u, c = fwd(get_layer(i), buffers[i], u)
            if c is not None:
                counts.append(c)
        shape = u.shape
        loss, top_grads = _loss_program(sizes, compute)(
            *get_top(), u.reshape(-1, shape[-1]), targets.reshape(-1),
            mask.reshape(-1))
        g = top_grads[2].reshape(shape)
        on_grad("top", _cast(top_grads[:2], "float32"))
        del u, top_grads        # nothing of the top outlives its turn
        for i in reversed(range(len(s["pattern"]))):
            _, bwd = _layer_programs(s["pattern"][i], sizes, held, ssm,
                                     compute)
            gp, g = bwd(get_layer(i), buffers[i], jnp.asarray(inputs.pop()),
                        g)
            on_grad(i, _cast(gp, "float32"))
        grows = jnp.zeros(rows.shape, jnp.float32).at[where.reshape(-1)].add(
            g.reshape(-1, shape[-1]).astype(jnp.float32))
    return loss, (jnp.stack(counts) if counts else None), grows


# -- the optimizer step -------------------------------------------------------
def adagrad(w, g2, g, rho):
    """``G += g^2; w -= rho * g / sqrt(G + eps)``; returns (w, G)."""
    _, jnp = _jax()
    g2 = g2 + g * g
    return w - rho * g / jnp.sqrt(g2 + ADAGRAD_EPS), g2


def sizes_of(config: dict) -> dict:
    """The sizes the functions above read, from a configuration file's keys."""
    return {
        "pattern": config["hybrid_override_pattern"][
            :config["num_hidden_layers"]],
        "norm_eps": config["layer_norm_epsilon"],
        "mamba_num_heads": config["mamba_num_heads"],
        "mamba_head_dim": config["mamba_head_dim"],
        "n_groups": config["n_groups"],
        "ssm_state_size": config["ssm_state_size"],
        "num_attention_heads": config["num_attention_heads"],
        "num_key_value_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "num_experts_per_tok": config["num_experts_per_tok"],
        "norm_topk_prob": config["norm_topk_prob"],
        "routed_scaling_factor": config["routed_scaling_factor"],
    }

