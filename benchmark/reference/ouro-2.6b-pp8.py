"""Plain reference for one pipeline stage of Ouro-2.6B (``ouro``, a looped LM)
as ``ouro-2.6b-pp8`` states it: straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, Python loops over passes and
layers (nothing rolled), no program code, no tables, no kernels.

``T`` = ``total_ut_steps``, ``L`` the layers held; every norm is an RMSNorm
with a learned scale and ``rms_norm_eps``; there is no bias in any projection.

Input  ``h_0 = E[x]``, the pulled rows at the positions' ids.
Pass   ``t = 1..T``, ``v = h_{t-1}``, every layer with the SAME leaves in every
       pass: ``a = v + RMSNorm_w2(Attn(RMSNorm_w1(v)))``, ``v = a +
       RMSNorm_w4(W_down (silu(W_gate n) * W_up n))`` with ``n =
       RMSNorm_w3(a)`` (a norm on each mixer's input and on its OUTPUT); then
       ``h_t = RMSNorm_f(v)`` with the ONE final norm: ``h_t`` is the next
       pass's input AND what the head and the gate read (no second norm
       before the head). The functions below take a layer's two halves one at
       a time, by letter (``*`` then ``D``), as the program's pattern does:
       each a block ``u + RMSNorm_post(mixer(RMSNorm_norm(u)))``.
``*``  ``q, k, v = n W_q, n W_k, n W_v`` as ``heads`` heads of ``D`` each (as
       many key-value heads as query heads); ``q`` and ``k`` turned by the
       rotary angle of their position over the WHOLE head, half layout (pair
       ``(i, i + D/2)`` by ``t * theta^(-2i/D)``), no scaling, the same
       positions in every pass; causal ``softmax(q k^T / sqrt(D)) v`` in
       float32, full rows, a block of queries at a time; out ``W_o``. No norm
       a head.
Gate   float32: ``g_t(i) = w_g . h_t(i) + b_g``, ``lambda_t = sigmoid(g_t)``;
       exit distribution ``p_1 = lambda_1``, ``p_t = lambda_t prod_{s<t} (1 -
       lambda_s)``, ``p_T = prod_{s<T} (1 - lambda_s)`` (the last pass takes
       what is left; ``lambda_T`` is unused).
Loss   ``l_t(i) = -log softmax(h_t(i) W_head)[target_i]`` over the whole
       vocabulary; the mean over the unmasked positions of ``sum_t p_t(i)
       l_t(i) - beta H(p(i))``, ``H(p) = -sum_t p_t log(max(p_t, 1e-20))``,
       ``beta`` = ``exit_entropy_weight``. Nothing is stopped: the gradient
       runs through ``p`` into the gate and on into ``h_t``, and through every
       ``l_t`` into all the passes; a leaf's gradient is the sum over its uses.

Departures from the published code, each also in the configuration's
``assumed``: float32 parameters (published bfloat16); attention runs across
document boundaries inside a packed sequence and positions count from the
sequence's start; the layers NOT held (42 of 48) lie on other pipeline stages
and are left out: the stage's layers, final norm, gate and head are the model;
AdaGrad, not the published optimizer; the first-stage objective only.

One training step (``AdaGradUpdater`` as it documents itself): ``G += g^2; w
-= rho * g / sqrt(G + 1e-6)`` on every parameter and every pulled row, ``g``
the gradient summed over the passes.

``compute`` is the type the arithmetic runs in (``bfloat16`` is the
lower-precision control; storage is rounded by the caller). The gate, the
exit distribution, the attention's softmax and the logits stay float32
whatever ``compute`` is.
"""
from __future__ import annotations

import functools

import numpy as np

ADAGRAD_EPS = 1e-6
ATTENTION, DENSE = "*", "D"
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


def attention(p, n, s, block=128):
    """One sequence: ``n`` [S, hidden]; full softmax rows, a block of queries
    at a time."""
    jax, jnp = _jax()
    heads, d = s["num_attention_heads"], s["head_dim"]
    length = n.shape[0]
    q = rope((n @ p["wq"]).reshape(length, heads, d), s["rope_theta"])
    k = rope((n @ p["wk"]).reshape(length, heads, d), s["rope_theta"])
    v = (n @ p["wv"]).reshape(length, heads, d)
    blk = min(block, length)
    pad = (-length) % blk
    if pad:
        q = jnp.concatenate([q, jnp.zeros((pad,) + q.shape[1:], q.dtype)])
    keys = jnp.arange(length)

    @jax.checkpoint
    def queries(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * blk, blk)
        rows = i * blk + jnp.arange(blk)
        scores = jnp.einsum("thd,shd->hts", qi, k) * (float(d) ** -0.5)
        scores = jnp.where(rows[:, None] >= keys[None, :],
                           scores.astype(jnp.float32), -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return jnp.einsum("hts,shd->thd", probs, v)

    o = jax.lax.map(queries, jnp.arange((length + pad) // blk))
    return o.reshape(length + pad, heads * d)[:length] @ p["wo"]


def gated_ffn(n, gate, up, down):
    jax, _ = _jax()
    return (jax.nn.silu(n @ gate) * (n @ up)) @ down


def _token_blocks(flat):
    blocks = max(1, len(flat) // TOKEN_BLOCK)
    return 1 if len(flat) % blocks else blocks


def block(kind, p, u, s):
    """Half a layer: ``u`` [B, S, hidden] -> ``u + RMSNorm_post(mixer(
    RMSNorm_norm(u)))``."""
    jax, jnp = _jax()
    n = rmsnorm(u, p["norm"], s["norm_eps"])
    if kind == ATTENTION:       # a sequence at a time, none kept
        y = jax.lax.map(jax.checkpoint(lambda seq: attention(p, seq, s)), n)
    else:                       # a block of tokens at a time
        flat = n.reshape(-1, n.shape[-1])
        y = jax.lax.map(jax.checkpoint(lambda nb: gated_ffn(
            nb, p["ffn_gate"], p["ffn_up"], p["ffn_down"])),
            flat.reshape(_token_blocks(flat), -1, flat.shape[-1]))
        y = y.reshape(u.shape)
    return u + rmsnorm(y, p["post_norm"], s["norm_eps"])


def exit_distribution(gate_logits):
    """``g`` [T, N] -> ``p`` [T, N], written out pass by pass."""
    jax, jnp = _jax()
    left = jnp.ones_like(gate_logits[0])
    p = []
    for g in gate_logits[:-1]:
        leave = jax.nn.sigmoid(g)
        p.append(leave * left)
        left = left * (1.0 - leave)
    return jnp.stack(p + [left])


def position_losses(h, head, targets, block=2048):
    """``-log softmax(h W_head)[target]`` a position, ``h`` [N, hidden], the
    logits in float32, a block of tokens at a time: [N]."""
    jax, jnp = _jax()
    t = h.shape[0]
    blk = min(block, t)
    pad = (-t) % blk
    if pad:
        h = jnp.concatenate([h, jnp.zeros((pad, h.shape[1]), h.dtype)])
        targets = jnp.concatenate([targets, jnp.zeros(pad, targets.dtype)])

    @jax.checkpoint
    def tokens(xs):
        hb, tb = xs
        logits = (hb @ head).astype(jnp.float32)
        picked = jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked

    return jax.lax.map(tokens, (h.reshape(-1, blk, h.shape[1]),
                                targets.reshape(-1, blk))).reshape(-1)[:t]


def loss_from_states(head, gate_w, gate_b, hs, targets, mask, s):
    """``hs`` T x [N, hidden], every pass's normed state -> (loss, {each
    pass's own mean cross-entropy [T], mean exit mass [T], mean entropy})."""
    _, jnp = _jax()
    count = jnp.maximum(jnp.sum(mask), 1.0)
    gate = jnp.stack([h.astype(jnp.float32) @ gate_w.astype(jnp.float32)
                      for h in hs]) + gate_b.astype(jnp.float32)
    p = exit_distribution(gate)
    losses = jnp.stack([position_losses(h, head, targets) for h in hs])
    entropy = -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-20)), axis=0)
    a_position = jnp.sum(p * losses, axis=0) \
        - s["exit_entropy_weight"] * entropy
    return jnp.sum(a_position * mask) / count, {
        "pass_loss": jnp.sum(losses * mask, axis=1) / count,
        "exit_mass": jnp.sum(p * mask, axis=1) / count,
        "exit_entropy": jnp.sum(entropy * mask) / count}


def _cast(tree, compute):
    jax, _ = _jax()
    return jax.tree_util.tree_map(lambda x: x.astype(compute), tree)


def hidden_states(params, u, s):
    """``u`` [B, S, hidden] -> every pass's ``h_t``, a list of T."""
    hs = []
    for _ in range(s["total_ut_steps"]):
        for kind, p in zip(s["pattern"], params["layers"]):
            u = block(kind, p, u, s)
        u = rmsnorm(u, params["final_norm"], s["norm_eps"])
        hs.append(u)
    return hs


def model_loss(params, rows, where, targets, mask, s, compute="float32"):
    """The whole model at once (small sizes): ``rows`` [n, hidden] the pulled
    rows, ``rows[where]`` the embedded input. Returns (loss, the passes'
    numbers)."""
    _, jnp = _jax()
    gate = params["exit_gate_w"], params["exit_gate_b"]     # stay float32
    params = _cast(params, compute)
    hs = hidden_states(params, rows.astype(compute)[where], s)
    return loss_from_states(
        params["head"], *gate, [h.reshape(-1, rows.shape[-1]) for h in hs],
        targets.reshape(-1), mask.reshape(-1), s)


def _sizes_key(s):
    return tuple(sorted(s.items()))


@functools.lru_cache(maxsize=None)
def _whole_program(sizes, compute):
    jax, _ = _jax()
    s = dict(sizes)

    def run(params, rows, where, targets, mask):
        return jax.value_and_grad(model_loss, argnums=(0, 1), has_aux=True)(
            params, rows, where, targets, mask, s, compute)

    return jax.jit(run)


def value_and_grads(params, rows, where, targets, mask, s,
                    compute="float32"):
    """(loss, the passes' numbers, gradients of the parameters, of the pulled
    rows) by ``jax.grad`` of :func:`model_loss`, at ``highest``."""
    jax, _ = _jax()
    with jax.default_matmul_precision("highest"):
        (loss, passes), (gp, grows) = _whole_program(_sizes_key(s), compute)(
            params, rows, where, targets, mask)
    return loss, passes, _cast(gp, "float32"), grows.astype("float32")


# -- the same gradients a block at a time, so that the full size fits ---------
@functools.lru_cache(maxsize=None)
def _block_programs(kind, sizes, compute):
    jax, _ = _jax()
    s = dict(sizes)

    def fwd(p, u):
        return block(kind, _cast(p, compute), u, s)

    def bwd(p, u, g):
        return jax.vjp(fwd, p, u)[1](g)

    return jax.jit(fwd), jax.jit(bwd)


@functools.lru_cache(maxsize=None)
def _norm_programs(sizes, compute):
    jax, _ = _jax()
    s = dict(sizes)

    def fwd(w, u):
        return rmsnorm(u, w.astype(compute), s["norm_eps"])

    def bwd(w, u, g):
        return jax.vjp(fwd, w, u)[1](g)

    return jax.jit(fwd), jax.jit(bwd)


@functools.lru_cache(maxsize=None)
def _loss_program(sizes, compute, donate=False):
    jax, _ = _jax()
    s = dict(sizes)

    def top(head, gate_w, gate_b, hs, targets, mask):
        return loss_from_states(
            head.astype(compute), gate_w, gate_b,
            [h.reshape(-1, h.shape[-1]) for h in hs], targets, mask, s)

    # ``donate``: the states' buffers are given to their gradients (only
    # where nothing else reads them: the kept inputs wait on the host)
    return jax.jit(jax.value_and_grad(top, argnums=(0, 1, 2, 3),
                                      has_aux=True),
                   donate_argnums=(3,) if donate else ())


def grads_by_layer(get_layer, get_top, rows, where, targets, mask, s, on_grad,
                   compute="float32", inputs_on_host=False):
    """:func:`value_and_grads` with one block's weights and gradients alive on
    the device at a time: ``get_layer(i)`` gives block ``i``'s parameters (it
    is asked once a pass and direction), ``get_top()`` ``(final_norm, head,
    exit_gate_w, exit_gate_b)``, and ``on_grad(where, grads)`` takes block
    ``i``'s gradients (``where`` = i, a dict) or the top's (``where`` = "top",
    a tuple in ``get_top``'s order) once they are WHOLE: the sum over the
    passes, kept on the host while the backward walks the passes from the
    last to the first. The chain rule written out: the forward keeps every
    block run's input and every pass's un-normed output, the backward pulls
    the gradient back through the final norm and one block after the other
    (``jax.vjp``) and, between passes, adds what the loss says of ``h_t``
    itself. With ``inputs_on_host`` the kept inputs wait on the host. Returns
    (loss, the passes' numbers, gradient of the pulled rows)."""
    jax, jnp = _jax()
    sizes, pattern = _sizes_key(s), s["pattern"]
    norm_fwd, norm_bwd = _norm_programs(sizes, compute)

    def keep(x):
        return np.asarray(x) if inputs_on_host else x

    with jax.default_matmul_precision("highest"):
        final_norm, _, gate_w, gate_b = get_top()     # the head: not yet
        u = rows.astype(compute)[where]
        shape = u.shape
        inputs, hs = [], []         # per pass: (block inputs, before the norm)
        for _ in range(s["total_ut_steps"]):
            ins = []
            for i, kind in enumerate(pattern):
                ins.append(keep(u))
                u = _block_programs(kind, sizes, compute)[0](get_layer(i), u)
            inputs.append((ins, keep(u)))
            u = norm_fwd(final_norm, u)
            hs.append(u)
        # waited for, and what it read dropped, before the backward asks for
        # its first block: launched ahead they would be held together
        (loss, passes), (ghead, gw, gb, ghs) = jax.block_until_ready(
            _loss_program(sizes, compute, inputs_on_host)(
                get_top()[1], gate_w, gate_b, tuple(hs),
                targets.reshape(-1), mask.reshape(-1)))
        del hs, u
        sums = {}                   # block -> its gradients so far, host
        gnorm = 0.0
        ghs = list(ghs)
        g = jnp.zeros(shape, ghs[0].dtype)
        for t in reversed(range(s["total_ut_steps"])):
            ins, before_norm = inputs.pop()
            gw_t, g = norm_bwd(final_norm, jnp.asarray(before_norm),
                               g + ghs.pop())
            gnorm = gnorm + gw_t.astype(jnp.float32)
            for i in reversed(range(len(pattern))):
                gp, g = _block_programs(pattern[i], sizes, compute)[1](
                    get_layer(i), jnp.asarray(ins.pop()), g)
                gp = {k: np.array(v, np.float32) for k, v in gp.items()}
                if i in sums:
                    for k in gp:
                        sums[i][k] += gp[k]
                else:
                    sums[i] = gp
                del gp
        on_grad("top", [gnorm, ghead.astype(jnp.float32), gw, gb])
        del ghead
        for i in reversed(range(len(pattern))):
            on_grad(i, {k: jnp.asarray(v) for k, v in sums.pop(i).items()})
        grows = jnp.zeros(rows.shape, jnp.float32).at[where.reshape(-1)].add(
            g.reshape(-1, shape[-1]).astype(jnp.float32))
    return loss, passes, grows


# -- the optimizer step -------------------------------------------------------
def adagrad(w, g2, g, rho):
    """``G += g^2; w -= rho * g / sqrt(G + eps)``; returns (w, G)."""
    _, jnp = _jax()
    g2 = g2 + g * g
    return w - rho * g / jnp.sqrt(g2 + ADAGRAD_EPS), g2


def pattern_of(config: dict) -> str:
    """Two letters a layer for the first ``num_hidden_layers`` of
    ``layer_types``, all ``full_attention``: attention, then the SwiGLU."""
    names = config["layer_types"][:config["num_hidden_layers"]]
    assert set(names) == {"full_attention"}, names
    return (ATTENTION + DENSE) * len(names)


def sizes_of(config: dict) -> dict:
    """The sizes the functions above read, from a configuration file's keys."""
    return {"num_attention_heads": config["num_attention_heads"],
            "head_dim": config["head_dim"],
            "rope_theta": config["rope_theta"],
            "norm_eps": config["rms_norm_eps"],
            "total_ut_steps": config["total_ut_steps"],
            "exit_entropy_weight": config["exit_entropy_weight"],
            "pattern": pattern_of(config)}
