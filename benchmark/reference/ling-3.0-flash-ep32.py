"""Plain reference for one chip's share of Ling-3.0-flash (the language model
of ``Ling-3.0-flash-VL``) as ``ling-3.0-flash-ep32`` states it: straightforward
``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``, no
program code, no tables, no kernels.

``u`` is the residual stream; every norm is an RMSNorm with a learned scale and
``rms_norm_eps``; there is no bias on any projection. A layer is two pre-norm
residual blocks, ``u <- u + mixer(norm(u))`` then ``u <- u + ff(norm(u))``; the
functions below take the blocks one at a time, by letter. Layer ``i`` (0-based)
mixes by latent attention where ``(i + 1) % layer_group_size == 0`` and by Kimi
Delta Attention everywhere else; its feed-forward is dense in the first
``first_k_dense_replace`` layers and the expert block after: six layers are the
pattern ``KD KD KE KE KE LE``. Then a final RMSNorm, ``logits = norm(u) W_head``
and the mean next-token cross-entropy over the unmasked positions.

``K``  Kimi Delta Attention, one sequence ``n`` [S, hidden], ``H`` heads of
       ``D`` keys and ``D`` values. ``q~ = silu(conv(n W_q))``, ``k~`` and
       ``v`` likewise: ``conv`` depthwise, causal, ``short_conv_kernel_size``
       taps a channel, zero before the sequence's start, no bias, written as
       the sum over its taps. ``q = q~ / max(|q~|, 1e-6) * D^-0.5``, ``k = k~ /
       max(|k~|, 1e-6)`` a head. Log decay a CHANNEL (``kda_safe_gate``):
       ``g_t = kda_lower_bound * sigmoid(exp(A_log_h) * (n W_a + dt_bias))``,
       ``a_t = exp(g_t)``; ``b_t = sigmoid(n W_beta)`` one a head. The state
       ``S`` [D, D] a head starts at zero and steps TOKEN BY TOKEN: ``S <-
       Diag(a_t) S``; ``S <- S + b_t k_t (v_t - S^T k_t)^T`` (which is ``(I -
       b_t k_t k_t^T) Diag(a_t) S + b_t k_t v_t^T``); ``o_t = S^T q_t``. A
       ``lax.scan`` over positions inside a checkpointed ``lax.scan`` over
       blocks of positions, so that the gradients fit at 8,192 positions: that
       is blocking, not another algorithm. ``y = (RMSNorm_head(o_t; w[D]) *
       sigmoid(n W_g)_h) W_o``, one gate a head. No rotary turn.
``L``  latent attention without a query latent, as in ``deepseek-v2-lite-ep4``'s
       reference, with plain rotary frequencies (``rope_scaling`` absent): ``q =
       n W_q`` -> heads x ``[nope | rope]``; ``[c | k_r] = n W_kva``; ``c <-
       RMSNorm(c)``; ``[k_nope | v] = c W_kvb`` a head; ``q_r`` and ``k_r`` (ONE
       vector a token) turned at the token's index, pair ``(2i, 2i+1)`` by
       ``index * rope_theta^(-2i/rope)``; ``score = (q_nope . k_nope + q_r . k_r)
       * (nope + rope)^-0.5``; causal softmax in float32, full rows, a block of
       queries at a time; ``y = (sum p v) W_o``.
``D``  ``y = (silu(n W_gate) * (n W_up)) W_down``.
``E``  ``s = sigmoid(n W_r)`` over ALL experts, float32; ``s' = s + b`` (``b``
       the selection bias, outside the gradient, moved between steps: ``b_e +=
       u sign(mean(c) - c_e)`` from the step's assignments ``c`` to every
       expert). Group-limited choice: the experts lie in ``n_group`` contiguous
       groups; a group's score is the sum of its two largest ``s'``; the
       ``topk_group`` groups of largest score are kept (of equal scores the
       lower-numbered group) and every other expert's ``s'`` is ``-inf``; the
       ``k`` largest ``s'`` among the kept are chosen. Weights ``s[chosen] /
       (sum + 1e-20)`` times ``routed_scaling_factor``. ``y = sum_{e held} w_e
       E_e(n) + S(n)``, every expert and the shared one a ``D`` of its own
       width: a loop over the HELD experts, each over every token under its
       gate (0 where not chosen).

Departures from the published code, each also in the configuration's
``assumed``: float32 parameters (published bfloat16); attention, the
convolutions and the delta-rule state run across document boundaries inside a
packed sequence and positions count from the sequence's start; what the experts
NOT held would add is left out (``held`` is an argument: every share of the
experts can be computed, and the shares add up to the whole block,
``shared=False`` on all but one); the selection bias is drawn small from the
seed and moved between steps by :func:`updated_bias`; no vision tower, no
multi-token prediction module; the logits, the loss and the rows are those of
the vocabulary slice; AdaGrad, not the published optimizer.

One training step (``AdaGradUpdater`` as it documents itself): ``G += g^2; w -=
rho * g / sqrt(G + 1e-6)`` on every parameter; the embedding rows of a batch's
repeated ids take their summed gradient.

``compute`` is the type the arithmetic runs in (``bfloat16`` is the
lower-precision control; storage is rounded by the caller). The router's
product and sigmoid, the attention's softmax, the logits, and KDA's log decay
and state stay float32 whatever ``compute`` is, as the published code keeps
them.
"""
from __future__ import annotations

import functools

import numpy as np

ADAGRAD_EPS = 1e-6
L2_EPS = 1e-6
KDA, LATENT, DENSE, EXPERTS = "K", "L", "D", "E"
TOKEN_BLOCK = 4096
SCAN_BLOCK = 64


def _jax():
    import jax
    import jax.numpy as jnp
    return jax, jnp


# -- pieces -------------------------------------------------------------------
def rmsnorm(x, w, eps):
    _, jnp = _jax()
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def l2norm(x):
    _, jnp = _jax()
    return x / jnp.maximum(jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True)),
                           L2_EPS)


def rope(x, theta):
    """``x`` [S, heads, rope]: pair ``(2i, 2i+1)`` of position ``t`` turned by
    ``t * theta^(-2i/rope)``, the angle and its cos/sin in float32."""
    _, jnp = _jax()
    half = x.shape[-1] // 2
    inv_freq = jnp.asarray(
        theta ** (-2.0 * np.arange(half, dtype=np.float64) / x.shape[-1]),
        jnp.float32)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.cos(angle).astype(x.dtype)[:, None, :]
    sin = jnp.sin(angle).astype(x.dtype)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def short_conv(x, taps):
    """``x`` [S, C], ``taps`` [C, K]: ``out_t = sum_j taps[:, j] x_{t-(K-1)+j}``,
    zero before the sequence's start."""
    _, jnp = _jax()
    width = taps.shape[1]
    xp = jnp.concatenate([jnp.zeros((width - 1, x.shape[1]), x.dtype), x])
    return sum(taps[:, j] * xp[j:j + x.shape[0]] for j in range(width))


def delta_rule(q, k, v, g, beta):
    """The recurrence token by token: ``q``, ``k``, ``g`` [S, H, D], ``v``
    [S, H, D], ``beta`` [S, H] -> ``o`` [S, H, D]; the state float32."""
    jax, jnp = _jax()
    length, heads, d = q.shape
    pad = (-length) % SCAN_BLOCK
    f32 = [x.astype(jnp.float32) for x in (q, k, v, g, beta)]
    if pad:     # positions that neither decay nor write
        f32 = [jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
               for x in f32]

    def step(state, x):
        qt, kt, vt, gt, bt = x
        state = jnp.exp(gt)[:, :, None] * state
        seen = jnp.einsum("hkv,hk->hv", state, kt)
        state = state + (bt[:, None] * kt)[:, :, None] * (vt - seen)[:, None]
        return state, jnp.einsum("hkv,hk->hv", state, qt)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(step, state, xs)

    _, o = jax.lax.scan(
        block, jnp.zeros((heads, d, v.shape[-1]), jnp.float32),
        tuple(x.reshape((-1, SCAN_BLOCK) + x.shape[1:]) for x in f32))
    return o.reshape((-1,) + o.shape[2:])[:length].astype(v.dtype)


def kimi_delta_attention(p, n, s):
    """One sequence: ``n`` [S, hidden]."""
    jax, jnp = _jax()
    heads, d = s["num_attention_heads"], s["head_dim"]
    length = n.shape[0]

    def by_heads(w, taps):
        return jax.nn.silu(short_conv(n @ p[w], p[taps])).reshape(
            length, heads, d)

    q = l2norm(by_heads("wq", "conv_q")) * (float(d) ** -0.5)
    k = l2norm(by_heads("wk", "conv_k"))
    v = by_heads("wv", "conv_v")
    rate = jnp.exp(p["A_log"].astype(jnp.float32))[:, None]
    g = s["kda_lower_bound"] * jax.nn.sigmoid(rate * (
        (n @ p["wa"]).astype(jnp.float32).reshape(length, heads, d)
        + p["dt_bias"].astype(jnp.float32).reshape(heads, d)))
    beta = jax.nn.sigmoid(n @ p["wbeta"])
    o = delta_rule(q, k, v, g, beta)
    y = rmsnorm(o, p["o_norm"], s["norm_eps"]) \
        * jax.nn.sigmoid(n @ p["wg"])[:, :, None]
    return y.reshape(length, heads * d) @ p["wo"]


def latent_attention(p, n, s, block=128):
    """One sequence: ``n`` [S, hidden]; full softmax rows, a block of queries
    at a time."""
    jax, jnp = _jax()
    heads, nope, rot, vd, rank = (
        s["num_attention_heads"], s["qk_nope_head_dim"],
        s["qk_rope_head_dim"], s["v_head_dim"], s["kv_lora_rank"])
    length = n.shape[0]
    q = (n @ p["wq"]).reshape(length, heads, nope + rot)
    q_nope, q_rot = q[..., :nope], rope(q[..., nope:], s["rope_theta"])
    kva = n @ p["wkva"]
    latent = rmsnorm(kva[:, :rank], p["kv_norm"], s["norm_eps"])
    k_rot = rope(kva[:, None, rank:], s["rope_theta"])[:, 0]    # [S, rope]
    kv = (latent @ p["wkvb"]).reshape(length, heads, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = float(nope + rot) ** -0.5
    blk = min(block, length)
    pad = (-length) % blk
    if pad:
        q_nope = jnp.concatenate(
            [q_nope, jnp.zeros((pad,) + q_nope.shape[1:], q.dtype)])
        q_rot = jnp.concatenate(
            [q_rot, jnp.zeros((pad,) + q_rot.shape[1:], q.dtype)])
    keys = jnp.arange(length)

    @jax.checkpoint
    def queries(i):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, i * blk, blk)
        qr = jax.lax.dynamic_slice_in_dim(q_rot, i * blk, blk)
        rows = i * blk + jnp.arange(blk)
        scores = (jnp.einsum("thd,shd->hts", qn, k_nope)
                  + jnp.einsum("thd,sd->hts", qr, k_rot)) * scale
        scores = jnp.where(rows[:, None] >= keys[None, :],
                           scores.astype(jnp.float32), -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return jnp.einsum("hts,shd->thd", probs, v)

    o = jax.lax.map(queries, jnp.arange((length + pad) // blk))
    return o.reshape(length + pad, heads * vd)[:length] @ p["wo"]


def gated_ffn(n, gate, up, down):
    jax, _ = _jax()
    return (jax.nn.silu(n @ gate) * (n @ up)) @ down


def kept_groups(biased, n_group, topk_group):
    """[T, E] bool: the experts of each token's ``topk_group`` best groups. A
    group's score is the sum of its two largest entries; group ``g`` is kept
    where fewer than ``topk_group`` groups come before it: those of larger
    score, and those of equal score and lower number."""
    _, jnp = _jax()
    t, e = biased.shape
    groups = biased.reshape(t, n_group, e // n_group)
    score = jnp.sort(groups, axis=-1)[..., -2:].sum(axis=-1)       # [T, G]
    number = jnp.arange(n_group)
    before = (score[:, None, :] > score[:, :, None]) | (
        (score[:, None, :] == score[:, :, None])
        & (number[None, None, :] < number[None, :, None]))
    kept = before.sum(axis=-1) < topk_group                        # [T, G]
    return jnp.repeat(kept, e // n_group, axis=-1)


def route(p, bias, n, s):
    """(chosen [T, k], weights [T, k]), float32."""
    jax, jnp = _jax()
    scores = jax.nn.sigmoid(n.astype(jnp.float32)
                            @ p["router"].astype(jnp.float32))
    biased = scores + jax.lax.stop_gradient(bias)
    if s["n_group"] > 1:
        biased = jnp.where(kept_groups(biased, s["n_group"], s["topk_group"]),
                           biased, -jnp.inf)
    _, chosen = jax.lax.top_k(biased, s["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if s["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * s["routed_scaling_factor"]


def held_experts(p, n, chosen, w, held, shared=True):
    """This share's output for tokens ``n`` [T, hidden] under their routing.
    ``p["w_*"][i]`` are expert ``held[i]``'s."""
    jax, jnp = _jax()
    out = jnp.zeros_like(n)
    expert = jax.checkpoint(gated_ffn)  # an expert's products are not kept
    for i, e in enumerate(held):
        gate = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)
        out = out + gate.astype(n.dtype)[:, None] * expert(
            n, p["w_gate"][i], p["w_up"][i], p["w_down"][i])
    if shared:
        out = out + gated_ffn(n, p["s_gate"], p["s_up"], p["s_down"])
    return out


def _token_blocks(flat):
    blocks = max(1, len(flat) // TOKEN_BLOCK)
    return 1 if len(flat) % blocks else blocks


def expert_block(p, bias, n, s, held, shared=True):
    """``n`` [B, S, hidden] (normed) -> (this share's output, assignments to
    EVERY expert of the router, [E]). Routing sees every token; the experts'
    products run a block of tokens at a time, so that the [tokens, width]
    products of a long batch never exist at once."""
    jax, jnp = _jax()
    flat = n.reshape(-1, n.shape[-1])
    chosen, w = route(p, bias, flat, s)
    counts = jnp.sum(chosen.reshape(-1, 1)
                     == jnp.arange(p["router"].shape[1]), axis=0)
    blocks = _token_blocks(flat)
    y = jax.lax.map(
        jax.checkpoint(lambda xs: held_experts(p, *xs, held, shared)),
        (flat.reshape(blocks, -1, flat.shape[-1]),
         chosen.reshape(blocks, -1, chosen.shape[-1]),
         w.reshape(blocks, -1, w.shape[-1])))
    return y.reshape(n.shape), counts


def layer(kind, p, bias, u, s, held, shared=True):
    """One block: ``u`` [B, S, hidden] -> (``u + mixer(RMSNorm_w(u))``, counts
    or None)."""
    jax, jnp = _jax()
    n = rmsnorm(u, p["norm"], s["norm_eps"])
    if kind == KDA:         # a sequence at a time, none kept
        mixer = jax.checkpoint(lambda seq: kimi_delta_attention(p, seq, s))
        return u + jax.lax.map(mixer, n), None
    if kind == LATENT:
        mixer = jax.checkpoint(lambda seq: latent_attention(p, seq, s))
        return u + jax.lax.map(mixer, n), None
    if kind == DENSE:       # a block of tokens at a time
        flat = n.reshape(-1, n.shape[-1])
        y = jax.lax.map(jax.checkpoint(lambda nb: gated_ffn(
            nb, p["ffn_gate"], p["ffn_up"], p["ffn_down"])),
            flat.reshape(_token_blocks(flat), -1, flat.shape[-1]))
        return u + y.reshape(u.shape), None
    y, counts = expert_block(p, bias, n, s, held, shared)
    return u + y, counts


def loss_from_hidden(final_norm, head, u, targets, mask, s, block=2048):
    """Mean cross-entropy over the unmasked positions, ``u`` [T, hidden], the
    logits in float32, a block of tokens at a time."""
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
               compute="float32"):
    """The whole model at once (small sizes): ``rows[where]`` is the embedded
    input. Returns (loss, [expert blocks, E] assignments to every expert of
    the router: column ``e`` of a held expert is what this share computed)."""
    jax, jnp = _jax()
    params, rows = _cast(params, compute), rows.astype(compute)
    u = rows[where]
    counts = []
    for kind, p, bias in zip(s["pattern"], params["layers"], buffers):
        u, c = layer(kind, p, bias, u, s, held)
        if c is not None:
            counts.append(c)
    loss = loss_from_hidden(params["final_norm"], params["head"],
                            u.reshape(-1, u.shape[-1]), targets.reshape(-1),
                            mask.reshape(-1), s)
    return loss, (jnp.stack(counts) if counts else None)


def _sizes_key(s):
    return tuple(sorted(s.items()))


@functools.lru_cache(maxsize=None)
def _whole_program(sizes, held, compute):
    jax, jnp = _jax()
    s = dict(sizes)

    def run(params, rows, buffers, where, targets, mask):
        return jax.value_and_grad(model_loss, argnums=(0, 1), has_aux=True)(
            params, rows, buffers, where, targets, mask, s, held, compute)

    return jax.jit(run)


def value_and_grads(params, rows, buffers, where, targets, mask, s, held,
                    compute="float32"):
    """(loss, assignments to every expert, gradients of the parameters, of
    the rows) by ``jax.grad`` of :func:`model_loss`, at ``highest``."""
    jax, jnp = _jax()
    with jax.default_matmul_precision("highest"):
        (loss, counts), (gp, grows) = _whole_program(
            _sizes_key(s), tuple(held), compute)(
                params, rows, buffers, where, targets, mask)
    return loss, counts, _cast(gp, "float32"), grows.astype("float32")


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

    def top(final_norm, head, u, targets, mask):
        return loss_from_hidden(final_norm.astype(compute),
                                head.astype(compute), u, targets, mask, s)

    return jax.jit(jax.value_and_grad(top, argnums=(0, 1, 2)))


def grads_by_layer(get_layer, get_top, rows, buffers, where, targets, mask,
                   s, held, on_grad, compute="float32", inputs_on_host=False):
    """:func:`value_and_grads` with one block's weights and gradients alive at
    a time: ``get_layer(i)`` gives block ``i``'s parameters, ``get_top()``
    ``(final_norm, head)``, and ``on_grad(where, grads)`` takes block ``i``'s
    gradients (``where`` = i) or the top's (``where`` = "top") as they come.
    The chain rule written out: the forward keeps every block's input, the
    backward pulls the gradient back through one block after the other
    (``jax.vjp``); with ``inputs_on_host`` the kept inputs wait on the host.
    Returns (loss, assignments to every expert, gradient of the rows)."""
    jax, jnp = _jax()
    sizes, held = _sizes_key(s), tuple(held)
    with jax.default_matmul_precision("highest"):
        u = rows.astype(compute)[where]
        inputs, counts = [], []
        for i, kind in enumerate(s["pattern"]):
            inputs.append(np.asarray(u) if inputs_on_host else u)
            fwd, _ = _layer_programs(kind, sizes, held, compute)
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
            _, bwd = _layer_programs(s["pattern"][i], sizes, held, compute)
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


def updated_bias(bias, counts, rate):
    """The selection bias after a step, outside the gradient (balancing
    without an auxiliary loss): ``b_e + rate * sign(mean(c) - c_e)``, ``c``
    [E] the step's assignments to every expert."""
    _, jnp = _jax()
    c = jnp.asarray(counts, jnp.float32)
    return bias + rate * jnp.sign(jnp.mean(c) - c)


def pattern_of(config: dict) -> str:
    """Two letters a layer: ``L`` where ``(i + 1) % layer_group_size == 0``
    and ``K`` elsewhere, then ``D`` in the first ``first_k_dense_replace``
    layers and ``E`` after."""
    group, dense = config["layer_group_size"], config["first_k_dense_replace"]
    return "".join((LATENT if (i + 1) % group == 0 else KDA)
                   + (DENSE if i < dense else EXPERTS)
                   for i in range(config["num_hidden_layers"]))


def sizes_of(config: dict) -> dict:
    """The sizes the functions above read, from a configuration file's keys."""
    keys = ("num_attention_heads", "head_dim", "kda_lower_bound",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "rope_theta", "num_experts_per_tok", "n_group",
            "topk_group", "norm_topk_prob", "routed_scaling_factor")
    return dict({k: config[k] for k in keys}, pattern=pattern_of(config),
                norm_eps=config["rms_norm_eps"])
