"""Plain reference for one chip's share of DeepSeek-V2-Lite (``deepseek_v2``)
as ``deepseek-v2-lite-ep4`` states it: straightforward ``jax.numpy`` in float32
under ``jax.default_matmul_precision("highest")``, no program code, no tables,
no kernels.

A layer is two pre-norm residual blocks (RMSNorm, eps 1e-6): ``h <- h +
MLA(RMSNorm(h))``, then ``h <- h + FFN(RMSNorm(h))``, the FFN dense in the
first ``first_k_dense_replace`` layers and the expert block after. The
functions below take the blocks one at a time, by letter: a model of ``n``
layers is the pattern ``LD LE LE ..``. Then a final RMSNorm, ``logits = h
W_head``, and the mean next-token cross-entropy over the unmasked positions,
plus every expert block's balance loss.

``L``  latent attention without a query latent. ``q = x W_q`` -> heads x
       ``[nope | rope]``; ``[c | k_r] = x W_kva``; ``c <- RMSNorm(c)``;
       ``[k_nope | v] = c W_kvb`` per head. ``q_r`` and ``k_r`` (ONE vector a
       token, shared by the heads) are turned by RoPE at the token's index in
       its packed sequence: pair ``(2i, 2i+1)`` by ``index * inv_freq_i``,
       ``inv_freq`` YaRN's (:func:`yarn_inv_freq`). ``score = (q_nope . k_nope
       + q_r . k_r) * scale``, ``scale = (nope + rope)^-0.5 * mscale^2``,
       ``mscale = 0.1 * mscale_all_dim * ln(factor) + 1``; causal softmax in
       float32, full rows, a block of queries at a time; ``y = (sum p v) W_o``.
``D``  ``y = (silu(x W_gate) * (x W_up)) W_down``.
``E``  ``p = softmax(x W_r)`` over ALL experts, float32; the ``k`` largest
       (greedy); weights ``p`` at those, not renormalised unless
       ``norm_topk_prob``, times ``routed_scaling_factor``; ``y = sum_{e held}
       w_e E_e(x) + S(x)``, every expert and the shared one a ``D`` of its own
       width: a loop over the HELD experts, each over every token under its
       gate (0 where not chosen). Balance loss (``seq_aux``): per sequence of
       ``S`` tokens ``f_e = E / (k S) * count_e``, ``P_e = mean_t p_te``;
       ``alpha * mean over sequences of sum_e f_e P_e`` over all ``E`` experts;
       the counts carry no gradient.

Departures from the published code, each also in the configuration's
``assumed``: float32 parameters (published bfloat16); the rotated pairs stay
where they are (the published code first permutes ``(2i, 2i+1)`` to the
half-split layout, queries and keys alike, which leaves every score as it is);
attention runs across document boundaries inside a packed sequence and
positions count from the sequence's start; what the experts NOT held would add
is left out (``held`` is an argument: every share of the experts can be
computed, and the shares add up to the whole block, ``shared=False`` on all but
one); AdaGrad, not AdamW.

One training step (``AdaGradUpdater`` as it documents itself): ``G += g^2; w -=
rho * g / sqrt(G + 1e-6)`` on every parameter; the embedding rows of a batch's
repeated ids take their summed gradient.

``compute`` is the type the arithmetic runs in (``bfloat16`` is the
lower-precision control; storage is rounded by the caller). The router's
product and softmax and the attention's softmax stay float32 whatever
``compute`` is, as the published code keeps them.
"""
from __future__ import annotations

import functools
import math

import numpy as np

ADAGRAD_EPS = 1e-6
LATENT, DENSE, EXPERTS = "L", "D", "E"
TOKEN_BLOCK = 4096


def _jax():
    import jax
    import jax.numpy as jnp
    return jax, jnp


# -- pieces -------------------------------------------------------------------
def rmsnorm(x, w, eps):
    _, jnp = _jax()
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim, base, scaling):
    """float64 [dim / 2]: ``f_i = base^(-2i/dim)``; the pair that makes ``n``
    turns over the original context is ``cd(n) = dim ln(original / (2 pi n)) /
    (2 ln base)``; ``ramp_i = clip((i - floor(cd(beta_fast))) / (ceil(cd(
    beta_slow)) - floor(cd(beta_fast))), 0, 1)``; ``inv_freq_i = f_i / factor *
    ramp_i + f_i (1 - ramp_i)``."""
    i = np.arange(dim // 2, dtype=np.float64)
    f = base ** (-2.0 * i / dim)
    if not scaling:
        return f
    original = scaling["original_max_position_embeddings"]

    def cd(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(cd(scaling["beta_fast"])), 0)
    high = min(math.ceil(cd(scaling["beta_slow"])), dim - 1)
    ramp = np.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return f / scaling["factor"] * ramp + f * (1.0 - ramp)


def softmax_scale(s):
    scale = (s["qk_nope_head_dim"] + s["qk_rope_head_dim"]) ** -0.5
    scaling = s["rope_scaling"]
    if scaling and scaling.get("mscale_all_dim", 0):
        scale *= yarn_mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2
    return scale


def rope(x, s):
    """``x`` [S, heads, rope]: pair ``(2i, 2i+1)`` of position ``t`` turned by
    ``t * inv_freq_i``, the angle and its cos/sin in float32."""
    _, jnp = _jax()
    scaling = s["rope_scaling"]
    inv_freq = jnp.asarray(yarn_inv_freq(x.shape[-1], s["rope_theta"],
                                         scaling), jnp.float32)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    on_tables = 1.0 if not scaling else \
        yarn_mscale(scaling["factor"], scaling["mscale"]) \
        / yarn_mscale(scaling["factor"], scaling["mscale_all_dim"])
    cos = (jnp.cos(angle) * on_tables).astype(x.dtype)[:, None, :]
    sin = (jnp.sin(angle) * on_tables).astype(x.dtype)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def latent_attention(p, n, s, block=128):
    """One sequence: ``n`` [S, hidden]; full softmax rows, a block of queries
    at a time."""
    jax, jnp = _jax()
    heads, nope, rot, vd, rank = (
        s["num_attention_heads"], s["qk_nope_head_dim"],
        s["qk_rope_head_dim"], s["v_head_dim"], s["kv_lora_rank"])
    length = n.shape[0]
    q = (n @ p["wq"]).reshape(length, heads, nope + rot)
    q_nope, q_rot = q[..., :nope], rope(q[..., nope:], s)
    kva = n @ p["wkva"]
    latent = rmsnorm(kva[:, :rank], p["kv_norm"], s["norm_eps"])
    k_rot = rope(kva[:, None, rank:], s)[:, 0]              # [S, rope]
    kv = (latent @ p["wkvb"]).reshape(length, heads, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = softmax_scale(s)
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


def route(p, n, s):
    """(chosen [T, k], weights [T, k], probabilities [T, E]), float32."""
    jax, jnp = _jax()
    probs = jax.nn.softmax(n.astype(jnp.float32)
                           @ p["router"].astype(jnp.float32), axis=-1)
    w, chosen = jax.lax.top_k(probs, s["num_experts_per_tok"])
    if s["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * s["routed_scaling_factor"], probs


def balance_loss(probs, chosen, sequences, s):
    """``alpha * mean_b sum_e f_be P_be`` over all experts."""
    jax, jnp = _jax()
    experts = probs.shape[-1]
    k = chosen.shape[-1]
    length = probs.shape[0] // sequences
    counts = jax.nn.one_hot(chosen.reshape(sequences, length * k), experts,
                            dtype=jnp.float32).sum(axis=1)
    f = jax.lax.stop_gradient(counts) * experts / (k * length)
    mean_p = probs.reshape(sequences, length, experts).mean(axis=1)
    return s["aux_loss_alpha"] * jnp.mean(jnp.sum(f * mean_p, axis=-1))


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


def expert_block(p, n, s, held, shared=True):
    """``n`` [B, S, hidden] (normed) -> (this share's output, assignments per
    held expert, balance loss). Routing and the balance loss see every token;
    the experts' products run a block of tokens at a time, so that the
    [tokens, width] products of a long batch never exist at once."""
    jax, jnp = _jax()
    flat = n.reshape(-1, n.shape[-1])
    chosen, w, probs = route(p, flat, s)
    aux = balance_loss(probs, chosen, n.shape[0], s)
    counts = jnp.stack([jnp.sum(chosen == e) for e in held])
    blocks = max(1, len(flat) // TOKEN_BLOCK)
    if len(flat) % blocks:
        blocks = 1
    y = jax.lax.map(
        jax.checkpoint(lambda xs: held_experts(p, *xs, held, shared)),
        (flat.reshape(blocks, -1, flat.shape[-1]),
         chosen.reshape(blocks, -1, chosen.shape[-1]),
         w.reshape(blocks, -1, w.shape[-1])))
    return y.reshape(n.shape), counts, aux


def layer(kind, p, u, s, held, shared=True):
    """One block: ``u`` [B, S, hidden] -> (``u + mixer(RMSNorm_w(u))``, counts
    or None, balance loss or 0)."""
    jax, jnp = _jax()
    n = rmsnorm(u, p["norm"], s["norm_eps"])
    if kind == LATENT:      # a sequence at a time, none kept
        mixer = jax.checkpoint(lambda seq: latent_attention(p, seq, s))
        return u + jax.lax.map(mixer, n), None, 0.0
    if kind == DENSE:       # a block of tokens at a time
        flat = n.reshape(-1, n.shape[-1])
        blocks = max(1, len(flat) // TOKEN_BLOCK)
        if len(flat) % blocks:
            blocks = 1
        y = jax.lax.map(jax.checkpoint(lambda nb: gated_ffn(
            nb, p["ffn_gate"], p["ffn_up"], p["ffn_down"])),
            flat.reshape(blocks, -1, flat.shape[-1]))
        return u + y.reshape(u.shape), None, 0.0
    y, counts, aux = expert_block(p, n, s, held, shared)
    return u + y, counts, aux


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


def model_loss(params, rows, where, targets, mask, s, held,
               compute="float32"):
    """The whole model at once (small sizes): ``rows[where]`` is the embedded
    input. Returns (loss with the balance term, ([expert blocks, held]
    assignment counts, the balance term))."""
    jax, jnp = _jax()
    params, rows = _cast(params, compute), rows.astype(compute)
    u = rows[where]
    counts, aux = [], jnp.float32(0.0)
    for kind, p in zip(s["pattern"], params["layers"]):
        u, c, a = layer(kind, p, u, s, held)
        aux = aux + a
        if c is not None:
            counts.append(c)
    loss = loss_from_hidden(params["final_norm"], params["head"],
                            u.reshape(-1, u.shape[-1]), targets.reshape(-1),
                            mask.reshape(-1), s)
    return loss + aux, (jnp.stack(counts) if counts else None, aux)


def _sizes_key(s):
    return tuple(sorted((k, tuple(sorted(v.items())) if isinstance(v, dict)
                         else v) for k, v in s.items()))


def _sizes_of_key(key):
    return {k: dict(v) if isinstance(v, tuple) else v for k, v in key}


@functools.lru_cache(maxsize=None)
def _whole_program(sizes, held, compute):
    jax, jnp = _jax()
    s = _sizes_of_key(sizes)

    def run(params, rows, where, targets, mask):
        return jax.value_and_grad(model_loss, argnums=(0, 1), has_aux=True)(
            params, rows, where, targets, mask, s, held, compute)

    return jax.jit(run)


def value_and_grads(params, rows, where, targets, mask, s, held,
                    compute="float32"):
    """(loss, counts, balance term, gradients of the parameters, of the rows)
    by ``jax.grad`` of :func:`model_loss`, at ``highest``."""
    jax, jnp = _jax()
    with jax.default_matmul_precision("highest"):
        (loss, (counts, aux)), (gp, grows) = _whole_program(
            _sizes_key(s), tuple(held), compute)(
                params, rows, where, targets, mask)
    return loss, counts, aux, _cast(gp, "float32"), grows.astype("float32")


# -- the same gradients a block at a time, so that the full size fits ---------
@functools.lru_cache(maxsize=None)
def _layer_programs(kind, sizes, held, compute):
    jax, jnp = _jax()
    s = _sizes_of_key(sizes)

    def fwd(p, u):
        return layer(kind, _cast(p, compute), u, s, held)

    def bwd(p, u, g):
        def out_and_aux(p, u):
            out, _, aux = fwd(p, u)
            return out, jnp.float32(aux)

        _, pull = jax.vjp(out_and_aux, p, u)
        return pull((g, jnp.float32(1.0)))

    return jax.jit(fwd), jax.jit(bwd)


@functools.lru_cache(maxsize=None)
def _loss_program(sizes, compute):
    jax, jnp = _jax()
    s = _sizes_of_key(sizes)

    def top(final_norm, head, u, targets, mask):
        return loss_from_hidden(final_norm.astype(compute),
                                head.astype(compute), u, targets, mask, s)

    return jax.jit(jax.value_and_grad(top, argnums=(0, 1, 2)))


def grads_by_layer(get_layer, get_top, rows, where, targets, mask, s, held,
                   on_grad, compute="float32", inputs_on_host=False):
    """:func:`value_and_grads` with one block's weights and gradients alive at
    a time: ``get_layer(i)`` gives block ``i``'s parameters, ``get_top()``
    ``(final_norm, head)``, and ``on_grad(where, grads)`` takes block ``i``'s
    gradients (``where`` = i) or the top's (``where`` = "top") as they come.
    The chain rule written out: the forward keeps every block's input, the
    backward pulls the gradient back through one block after the other
    (``jax.vjp``; an expert block's balance loss enters with cotangent 1);
    with ``inputs_on_host`` the kept inputs wait on the host. Returns (loss
    with the balance term, counts, balance term, gradient of the rows)."""
    jax, jnp = _jax()
    sizes, held = _sizes_key(s), tuple(held)
    with jax.default_matmul_precision("highest"):
        u = rows.astype(compute)[where]
        inputs, counts, aux = [], [], 0.0
        for i, kind in enumerate(s["pattern"]):
            inputs.append(np.asarray(u) if inputs_on_host else u)
            fwd, _ = _layer_programs(kind, sizes, held, compute)
            u, c, a = fwd(get_layer(i), u)
            aux = aux + a
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
            gp, g = bwd(get_layer(i), jnp.asarray(inputs.pop()), g)
            on_grad(i, _cast(gp, "float32"))
        grows = jnp.zeros(rows.shape, jnp.float32).at[where.reshape(-1)].add(
            g.reshape(-1, shape[-1]).astype(jnp.float32))
    return loss + aux, (jnp.stack(counts) if counts else None), aux, grows


# -- the optimizer step -------------------------------------------------------
def adagrad(w, g2, g, rho):
    """``G += g^2; w -= rho * g / sqrt(G + eps)``; returns (w, G)."""
    _, jnp = _jax()
    g2 = g2 + g * g
    return w - rho * g / jnp.sqrt(g2 + ADAGRAD_EPS), g2


def pattern_of(config: dict) -> str:
    """Two letters a layer: ``L`` then ``D`` in the first
    ``first_k_dense_replace`` layers (and where ``moe_layer_freq`` skips),
    ``E`` after."""
    first, freq = config["first_k_dense_replace"], config["moe_layer_freq"]
    return "".join(LATENT + (EXPERTS if i >= first and i % freq == 0
                             else DENSE)
                   for i in range(config["num_hidden_layers"]))


def sizes_of(config: dict) -> dict:
    """The sizes the functions above read, from a configuration file's keys."""
    keys = ("num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "rope_theta", "rope_scaling",
            "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
            "aux_loss_alpha")
    return dict({k: config[k] for k in keys}, pattern=pattern_of(config),
                norm_eps=config["rms_norm_eps"])
