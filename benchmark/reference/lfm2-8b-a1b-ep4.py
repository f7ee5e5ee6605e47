"""Plain reference for one chip's share of LFM2-8B-A1B (``lfm2_moe``) as
``lfm2-8b-a1b-ep4`` states it: straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, no program code, no tables, no
kernels.

``u`` is the residual stream; every norm is an RMSNorm with a learned scale
and ``norm_eps``; there is no bias anywhere. A layer is two pre-norm residual
blocks, ``u <- u + mixer(norm(u))`` then ``u <- u + ff(norm(u))``; the
functions below take the blocks one at a time, by letter: published layers
``conv conv full_attention conv ..`` with ``num_dense_layers`` 2 are the
pattern ``CD CD *E CE ..``. Then a final RMSNorm and ``logits = norm(u) E^T``
with ``E`` the embedding table's rows: the SAME array that embedded the input
(``u_0 = E[tokens]``), so the gradient of ``E`` is the sum of both uses. The
loss is the mean next-token cross-entropy over the unmasked positions.

``C``  gated short convolution, ``n`` [S, hidden]: ``[B | C | x] = n W_in``
       (thirds, in that order); ``z = B * x``; ``c_t = sum_{j < L} w[:, j] *
       z_{t-(L-1)+j}`` (depthwise, causal, zero before the sequence's start,
       ``L`` = ``conv_L_cache`` taps, no bias); ``y = C * c``; out ``y W_out``.
       No activation: the two gates are the non-linearity.
``*``  ``q = n W_q`` as ``heads`` of ``D``, ``k = n W_k``, ``v = n W_v`` as
       ``kv`` heads of ``D``; ``q`` and ``k`` normed a head (RMSNorm over the
       ``D``, one learned scale vector for ``q``, one for ``k``); both turned
       by the rotary angle of their position over the WHOLE head, half layout
       (pair ``(i, i + D/2)`` by ``t * theta^(-2i/D)``), no scaling; causal
       ``softmax(q k^T / sqrt(D)) v`` in float32, full rows, a block of
       queries at a time, ``heads / kv`` query heads a key-value head; out
       ``W_o``.
``D``  ``y = (silu(n W_gate) * (n W_up)) W_down``.
``E``  ``s = sigmoid(n W_r)`` over ALL experts, float32; the ``k`` largest of
       ``s + b`` chosen (``b`` the ``expert_bias`` buffer, outside the
       gradient, moved between steps: ``b_e += u sign(mean(c) - c_e)`` from
       the step's assignments ``c`` to every expert); weights ``s[chosen] /
       (sum + 1e-20)`` (``norm_topk_prob``)
       times ``routed_scaling_factor``; ``y = sum_{e held} w_e E_e(n)``, every
       expert a ``D`` of its own width: a loop over the HELD experts, each
       over every token under its gate (0 where not chosen). NO shared expert.

Departures from the published code, each also in the configuration's
``assumed``: float32 parameters (published bfloat16); attention and the
convolution run across document boundaries inside a packed sequence and
positions count from the sequence's start; what the experts NOT held would add
is left out (``held`` is an argument: every share of the experts can be
computed, and the shares add up to the whole block, nothing counted once since
nothing is shared); the selection bias is drawn small from the seed and moved
between steps by :func:`updated_bias` (the rule and its rate are the
configuration's assumption: the published file says only that there is one);
the logits, the loss and the rows are those of
the vocabulary slice; AdaGrad, not the published optimizer.

One training step (``AdaGradUpdater`` as it documents itself): ``G += g^2; w -=
rho * g / sqrt(G + 1e-6)`` on every parameter and on every row of the slice
(the head's use moves rows no token named).

``compute`` is the type the arithmetic runs in (``bfloat16`` is the
lower-precision control; storage is rounded by the caller). The router's
product and sigmoid, the attention's softmax and the logits stay float32
whatever ``compute`` is, as the published code keeps them.
"""
from __future__ import annotations

import functools

import numpy as np

ADAGRAD_EPS = 1e-6
CONV, ATTENTION, DENSE, EXPERTS = "C", "*", "D", "E"
LETTER = {"conv": CONV, "full_attention": ATTENTION}
TOKEN_BLOCK = 4096


def _jax():
    import jax
    import jax.numpy as jnp
    return jax, jnp


# -- pieces -------------------------------------------------------------------
def rmsnorm(x, w, eps):
    _, jnp = _jax()
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """``x`` [S, heads, D]: pair ``(i, i + D/2)`` of position ``t`` turned by
    ``t * theta^(-2i/D)``, the angle and its cos/sin in float32."""
    _, jnp = _jax()
    half = x.shape[-1] // 2
    inv_freq = jnp.asarray(
        theta ** (-2.0 * np.arange(half, dtype=np.float64) / x.shape[-1]),
        jnp.float32)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.cos(angle).astype(x.dtype)[:, None, :]
    sin = jnp.sin(angle).astype(x.dtype)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def short_convolution(p, n, s):
    """One sequence: ``n`` [S, hidden]."""
    _, jnp = _jax()
    d = n.shape[-1]
    bcx = n @ p["in_proj"]
    b, c, x = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    z = b * x
    taps = p["conv_w"].shape[1]
    zp = jnp.concatenate([jnp.zeros((taps - 1, d), z.dtype), z])
    conv = sum(p["conv_w"][:, j] * zp[j:j + n.shape[0]] for j in range(taps))
    return (c * conv) @ p["out_proj"]


def attention(p, n, s, block=128):
    """One sequence: ``n`` [S, hidden]; full softmax rows, a block of queries
    at a time."""
    jax, jnp = _jax()
    heads, kv = s["num_attention_heads"], s["num_key_value_heads"]
    length, d = n.shape[0], s["head_dim"]
    q = rmsnorm((n @ p["wq"]).reshape(length, heads, d), p["q_norm"],
                s["norm_eps"])
    k = rmsnorm((n @ p["wk"]).reshape(length, kv, d), p["k_norm"],
                s["norm_eps"])
    v = (n @ p["wv"]).reshape(length, kv, d)
    q, k = rope(q, s["rope_theta"]), rope(k, s["rope_theta"])
    # query head h reads key-value head h // (heads / kv)
    q = q.reshape(length, kv, heads // kv, d)
    blk = min(block, length)
    pad = (-length) % blk
    if pad:
        q = jnp.concatenate([q, jnp.zeros((pad,) + q.shape[1:], q.dtype)])
    keys = jnp.arange(length)

    @jax.checkpoint
    def queries(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * blk, blk)
        rows = i * blk + jnp.arange(blk)
        scores = jnp.einsum("tkgd,skd->kgts", qi, k) * (float(d) ** -0.5)
        scores = jnp.where(rows[:, None] >= keys[None, :],
                           scores.astype(jnp.float32), -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return jnp.einsum("kgts,skd->tkgd", probs, v)

    o = jax.lax.map(queries, jnp.arange((length + pad) // blk))
    return o.reshape(length + pad, heads * d)[:length] @ p["wo"]


def gated_ffn(n, gate, up, down):
    jax, _ = _jax()
    return (jax.nn.silu(n @ gate) * (n @ up)) @ down


def route(p, bias, n, s):
    """(chosen [T, k], weights [T, k]), float32."""
    jax, jnp = _jax()
    scores = jax.nn.sigmoid(n.astype(jnp.float32)
                            @ p["router"].astype(jnp.float32))
    _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(bias),
                              s["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if s["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * s["routed_scaling_factor"]


def held_experts(p, n, chosen, w, held):
    """This share's output for tokens ``n`` [T, hidden] under their routing.
    ``p["w_*"][i]`` are expert ``held[i]``'s."""
    jax, jnp = _jax()
    out = jnp.zeros_like(n)
    expert = jax.checkpoint(gated_ffn)  # an expert's products are not kept
    for i, e in enumerate(held):
        gate = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)
        out = out + gate.astype(n.dtype)[:, None] * expert(
            n, p["w_gate"][i], p["w_up"][i], p["w_down"][i])
    return out


def _token_blocks(flat):
    blocks = max(1, len(flat) // TOKEN_BLOCK)
    return 1 if len(flat) % blocks else blocks


def expert_block(p, bias, n, s, held):
    """``n`` [B, S, hidden] (normed) -> (this share's output, assignments to
    EVERY expert of the router, [E]). Routing sees every token; the experts'
    products run a block of tokens at a time, so that the [tokens, width]
    products of a long batch never exist at once."""
    jax, jnp = _jax()
    flat = n.reshape(-1, n.shape[-1])
    chosen, w = route(p, bias, flat, s)
    counts = jnp.stack([jnp.sum(chosen == e)
                        for e in range(p["router"].shape[1])])
    blocks = _token_blocks(flat)
    y = jax.lax.map(
        jax.checkpoint(lambda xs: held_experts(p, *xs, held)),
        (flat.reshape(blocks, -1, flat.shape[-1]),
         chosen.reshape(blocks, -1, chosen.shape[-1]),
         w.reshape(blocks, -1, w.shape[-1])))
    return y.reshape(n.shape), counts


def layer(kind, p, bias, u, s, held):
    """One block: ``u`` [B, S, hidden] -> (``u + mixer(RMSNorm_w(u))``, counts
    or None)."""
    jax, jnp = _jax()
    n = rmsnorm(u, p["norm"], s["norm_eps"])
    if kind == CONV:        # a sequence at a time, none kept
        mixer = jax.checkpoint(lambda seq: short_convolution(p, seq, s))
        return u + jax.lax.map(mixer, n), None
    if kind == ATTENTION:
        mixer = jax.checkpoint(lambda seq: attention(p, seq, s))
        return u + jax.lax.map(mixer, n), None
    if kind == DENSE:       # a block of tokens at a time
        flat = n.reshape(-1, n.shape[-1])
        y = jax.lax.map(jax.checkpoint(lambda nb: gated_ffn(
            nb, p["ffn_gate"], p["ffn_up"], p["ffn_down"])),
            flat.reshape(_token_blocks(flat), -1, flat.shape[-1]))
        return u + y.reshape(u.shape), None
    y, counts = expert_block(p, bias, n, s, held)
    return u + y, counts


def loss_from_hidden(final_norm, table, u, targets, mask, s, block=2048):
    """Mean cross-entropy over the unmasked positions, ``u`` [T, hidden],
    logits ``norm(u) table^T`` against the embedding table's rows ``table``
    [V, hidden], in float32, a block of tokens at a time."""
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
        logits = (rmsnorm(ub, final_norm, s["norm_eps"]) @ table.T).astype(
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


def model_loss(params, table, buffers, tokens, targets, mask, s, held,
               compute="float32"):
    """The whole model at once (small sizes): ``table`` [V, hidden] is every
    row of the slice, ``table[tokens]`` the embedded input and ``table`` the
    head. Returns (loss, [expert blocks, E] assignments to every expert of
    the router: column ``e`` of a held expert is what this share computed)."""
    jax, jnp = _jax()
    params, table = _cast(params, compute), table.astype(compute)
    u = table[tokens]
    counts = []
    for kind, p, bias in zip(s["pattern"], params["layers"], buffers):
        u, c = layer(kind, p, bias, u, s, held)
        if c is not None:
            counts.append(c)
    loss = loss_from_hidden(params["final_norm"], table,
                            u.reshape(-1, u.shape[-1]), targets.reshape(-1),
                            mask.reshape(-1), s)
    return loss, (jnp.stack(counts) if counts else None)


def _sizes_key(s):
    return tuple(sorted(s.items()))


@functools.lru_cache(maxsize=None)
def _whole_program(sizes, held, compute):
    jax, jnp = _jax()
    s = dict(sizes)

    def run(params, table, buffers, tokens, targets, mask):
        return jax.value_and_grad(model_loss, argnums=(0, 1), has_aux=True)(
            params, table, buffers, tokens, targets, mask, s, held, compute)

    return jax.jit(run)


def value_and_grads(params, table, buffers, tokens, targets, mask, s, held,
                    compute="float32"):
    """(loss, assignments to every expert, gradients of the parameters, of
    every row of the table) by ``jax.grad`` of :func:`model_loss`, at
    ``highest``."""
    jax, jnp = _jax()
    with jax.default_matmul_precision("highest"):
        (loss, counts), (gp, gtable) = _whole_program(
            _sizes_key(s), tuple(held), compute)(
                params, table, buffers, tokens, targets, mask)
    return loss, counts, _cast(gp, "float32"), gtable.astype("float32")


# -- the same gradients a block at a time, so that the full size fits ---------
@functools.lru_cache(maxsize=None)
def _layer_programs(kind, sizes, held, compute):
    jax, jnp = _jax()
    s = dict(sizes)

    def fwd(p, bias, u):
        return layer(kind, _cast(p, compute), bias, u, s, held)

    def bwd(p, bias, u, g):
        _, pull = jax.vjp(lambda p, u: fwd(p, bias, u)[0], p, u)
        return pull(g)

    return jax.jit(fwd), jax.jit(bwd)


@functools.lru_cache(maxsize=None)
def _loss_program(sizes, compute):
    jax, jnp = _jax()
    s = dict(sizes)

    def top(final_norm, table, u, targets, mask):
        return loss_from_hidden(final_norm.astype(compute),
                                table.astype(compute), u, targets, mask, s)

    return jax.jit(jax.value_and_grad(top, argnums=(0, 1, 2)))


def grads_by_layer(get_layer, get_top, table, buffers, tokens, targets, mask,
                   s, held, on_grad, compute="float32", inputs_on_host=False):
    """:func:`value_and_grads` with one block's weights and gradients alive at
    a time: ``get_layer(i)`` gives block ``i``'s parameters, ``get_top()``
    ``(final_norm,)``, and ``on_grad(where, grads)`` takes block ``i``'s
    gradients (``where`` = i) or the top's (``where`` = "top") as they come.
    The chain rule written out: the forward keeps every block's input, the
    backward pulls the gradient back through one block after the other
    (``jax.vjp``); with ``inputs_on_host`` the kept inputs wait on the host.
    The table's gradient is the head's use plus, row by row, the sum over the
    positions that embedded it. Returns (loss, assignments to every expert,
    gradient of the table)."""
    jax, jnp = _jax()
    sizes, held = _sizes_key(s), tuple(held)
    with jax.default_matmul_precision("highest"):
        table = table.astype(compute)
        u = table[tokens]
        inputs, counts = [], []
        for i, kind in enumerate(s["pattern"]):
            inputs.append(np.asarray(u) if inputs_on_host else u)
            fwd, _ = _layer_programs(kind, sizes, held, compute)
            u, c = fwd(get_layer(i), buffers[i], u)
            if c is not None:
                counts.append(c)
        shape = u.shape
        loss, top_grads = _loss_program(sizes, compute)(
            *get_top(), table, u.reshape(-1, shape[-1]), targets.reshape(-1),
            mask.reshape(-1))
        g = top_grads[2].reshape(shape)
        gtable = top_grads[1].astype(jnp.float32)
        on_grad("top", _cast(top_grads[:1], "float32"))
        del u, top_grads        # nothing of the top outlives its turn
        for i in reversed(range(len(s["pattern"]))):
            _, bwd = _layer_programs(s["pattern"][i], sizes, held, compute)
            gp, g = bwd(get_layer(i), buffers[i], jnp.asarray(inputs.pop()),
                        g)
            on_grad(i, _cast(gp, "float32"))
        gtable = gtable.at[tokens.reshape(-1)].add(
            g.reshape(-1, shape[-1]).astype(jnp.float32))
    return loss, (jnp.stack(counts) if counts else None), gtable


# -- the optimizer step -------------------------------------------------------
def adagrad(w, g2, g, rho):
    """``G += g^2; w -= rho * g / sqrt(G + eps)``; returns (w, G)."""
    _, jnp = _jax()
    g2 = g2 + g * g
    return w - rho * g / jnp.sqrt(g2 + ADAGRAD_EPS), g2


def updated_bias(bias, counts, rate):
    """The selection bias after a step, outside the gradient (balancing
    without an auxiliary loss): ``b_e + rate * sign(mean(c) - c_e)``, ``c``
    [E] the step's assignments to every expert."""
    _, jnp = _jax()
    c = jnp.asarray(counts, jnp.float32)
    return bias + rate * jnp.sign(jnp.mean(c) - c)


def pattern_of(config: dict) -> str:
    """Two letters a layer for the first ``num_hidden_layers`` of
    ``layer_types``: the mixer, then ``D`` in the first ``num_dense_layers``
    layers and ``E`` after."""
    return "".join(
        LETTER[name] + (DENSE if i < config["num_dense_layers"] else EXPERTS)
        for i, name in enumerate(
            config["layer_types"][:config["num_hidden_layers"]]))


def sizes_of(config: dict) -> dict:
    """The sizes the functions above read, from a configuration file's keys."""
    keys = ("num_attention_heads", "num_key_value_heads", "rope_theta",
            "norm_eps", "num_experts_per_tok", "norm_topk_prob",
            "routed_scaling_factor")
    return dict({k: config[k] for k in keys}, pattern=pattern_of(config),
                head_dim=config.get("head_dim") or config["hidden_size"]
                // config["num_attention_heads"])
