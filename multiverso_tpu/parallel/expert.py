"""Expert parallelism: top-1 routed MoE with expert-sharded weights, and the
held-share expert layer of a published top-k router (sigmoid scores with a
selection bias, or softmax scores; two-matrix ``relu2`` experts or gated
three-matrix ``silu`` ones).

The reference predates MoE entirely; this module supplies the
expert-parallel building block the same way ``parallel/sequence.py``
supplies sequence parallelism: expert weights live sharded over the mesh's
``"expert"`` axis and the dense dispatch/combine einsums let XLA place the
token shuffles (the all-to-all) on ICI.

Design: the classic capacity-bounded dense-dispatch formulation — tokens are
routed top-1, each expert takes at most ``capacity`` tokens (overflow drops,
standard MoE semantics), dispatch/combine are one-hot einsums. Dense
dispatch trades FLOPs for compiler-friendliness: everything is static-shape
einsums the TPU runs well, versus gather/sort plumbing.

:func:`held_topk_moe` is the other formulation, for layers whose experts
outnumber the chips: the layer is TOLD which experts it holds, routes over
all of them, and computes its own experts' terms for every assignment that
lands here — no capacity, nothing dropped. The assignments are sorted by
expert into blocks of one expert each, and a loop over the blocks IN USE
gathers a block's tokens, runs the products against that expert's
weights and adds the weighted result rows into a token-ordered float32
accumulator; the backward is the same loop with the products transposed
(the input's gradient accumulated the same way), so nothing of a block
outlives it. A block's token ids are unique, so where the accumulator has
whole 128-lane planes a row and its caller knows it to live on one device
(``rows_interpret`` not None) the rows land by the DMA read-modify-write
kernel ``ops/pallas_rows.add_unique_rows`` on a ``[tokens, hidden / 128,
128]`` view carried through the loop; anywhere else by XLA's scatter-add,
to the same bits (:func:`_token_accumulator`). What the
absent experts would add is left out: on one chip the layer runs without
its exchange (docs/HYBRID_LM.md). A softmax router can hand back its
sequence-wise balance loss (:func:`sequence_balance_loss`): the router is
whole on every share, so a share computes it exactly.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

EXPERT_AXIS = "expert"


@dataclasses.dataclass
class MoEParams:
    router: jax.Array   # [D, E]
    w1: jax.Array       # [E, D, H]
    w2: jax.Array       # [E, H, D]


def init_moe(key: jax.Array, dim: int, hidden: int, num_experts: int,
             mesh: Optional[Mesh] = None) -> MoEParams:
    k1, k2, k3 = jax.random.split(key, 3)
    scale = dim ** -0.5
    router = jax.random.normal(k1, (dim, num_experts)) * scale
    w1 = jax.random.normal(k2, (num_experts, dim, hidden)) * scale
    w2 = jax.random.normal(k3, (num_experts, hidden, dim)) * scale
    if mesh is not None and EXPERT_AXIS in mesh.shape:
        shard = NamedSharding(mesh, P(EXPERT_AXIS, None, None))
        w1 = jax.device_put(w1, shard)
        w2 = jax.device_put(w2, shard)
        router = jax.device_put(router, NamedSharding(mesh, P()))
    return MoEParams(router, w1, w2)


def top1_moe(params: MoEParams, x: jax.Array,
             capacity_factor: float = 1.25
             ) -> Tuple[jax.Array, jax.Array]:
    """x [B, S, D] -> (y [B, S, D], aux_loss).

    aux_loss is the standard load-balancing term (mean fraction * mean
    router prob per expert, scaled by E)."""
    B, S, D = x.shape
    E = params.router.shape[1]
    T = B * S
    xt = x.reshape(T, D)
    logits = xt @ params.router                       # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)               # [T]
    gate = jnp.max(probs, axis=-1)                    # [T]

    capacity = max(int(capacity_factor * T / E), 1)
    onehot = jax.nn.one_hot(expert, E, dtype=x.dtype)           # [T, E]
    # position of each token within its expert's queue
    pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot            # [T, E]
    keep = (pos < capacity).astype(x.dtype) * onehot
    slot = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                          dtype=x.dtype) * keep[..., None]       # [T,E,C]

    expert_in = jnp.einsum("tec,td->ecd", slot, xt)              # [E,C,D]
    h = jax.nn.gelu(jnp.einsum("ecd,edh->ech", expert_in, params.w1))
    expert_out = jnp.einsum("ech,ehd->ecd", h, params.w2)        # [E,C,D]
    y = jnp.einsum("tec,ecd->td", slot, expert_out) * gate[:, None]

    # load-balancing auxiliary (Shazeer-style)
    frac_tokens = onehot.mean(axis=0)                            # [E]
    frac_probs = probs.mean(axis=0)                              # [E]
    aux = (frac_tokens * frac_probs).sum() * E
    return y.reshape(B, S, D), aux


def reference_top1_moe(params: MoEParams, x: jax.Array,
                       capacity_factor: float = 1.25) -> jax.Array:
    """Per-token loop reference (numpy) for testing."""
    B, S, D = x.shape
    E = params.router.shape[1]
    T = B * S
    xt = np.asarray(x).reshape(T, D)
    logits = xt @ np.asarray(params.router)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    expert = probs.argmax(-1)
    gate = probs.max(-1)
    capacity = max(int(capacity_factor * T / E), 1)
    counts = np.zeros(E, dtype=int)
    out = np.zeros_like(xt)
    w1 = np.asarray(params.w1)
    w2 = np.asarray(params.w2)

    def gelu(v):
        return 0.5 * v * (1 + np.tanh(np.sqrt(2 / np.pi)
                                      * (v + 0.044715 * v ** 3)))

    for t in range(T):
        e = expert[t]
        if counts[e] >= capacity:
            continue                     # dropped token
        counts[e] += 1
        h = gelu(xt[t] @ w1[e])
        out[t] = (h @ w2[e]) * gate[t]
    return out.reshape(B, S, D)


# -- the held share of a sigmoid top-k expert layer ---------------------------
def kept_groups(biased: jax.Array, n_group: int, topk_group: int
                ) -> jax.Array:
    """``biased`` [T, E] scores for choosing -> the same with every expert
    outside the token's ``topk_group`` best groups at ``-inf``: the experts
    lie in ``n_group`` contiguous groups of ``E / n_group``, a group's score
    is the sum of its two largest entries, and the groups of largest score
    are kept (of equal ones the lower-numbered, as ``top_k`` orders them)."""
    t, e = biased.shape
    by_group = biased.reshape(t, n_group, e // n_group)
    group_score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
    _, best = jax.lax.top_k(group_score, topk_group)
    kept = jnp.any(best[..., None] == jnp.arange(n_group), axis=1)
    return jnp.where(kept[..., None], by_group, -jnp.inf).reshape(t, e)


def sigmoid_topk_route(n: jax.Array, router: jax.Array, bias: jax.Array,
                       top_k: int, scaling: float, normalize: bool = True,
                       n_group: int = 1, topk_group: int = 1
                       ) -> Tuple[jax.Array, jax.Array]:
    """``n`` [T, D] -> (experts [T, k], weights [T, k]): scores
    ``sigmoid(n W_r)`` in float32 at ``highest`` (as the published code
    computes them, so that routing does not turn on a product's rounding),
    the ``k`` largest of ``score + bias`` chosen (with ``n_group`` > 1 among
    the experts of the token's ``topk_group`` best groups only:
    :func:`kept_groups`), their own scores normalised over the chosen (``+
    1e-20``) and scaled."""
    scores = jax.nn.sigmoid(jnp.dot(
        n.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    if n_group > 1:
        _, chosen = jax.lax.top_k(
            kept_groups(scores + bias, n_group, topk_group), top_k)
    else:
        _, chosen = jax.lax.top_k(scores + bias, top_k)
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if normalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * scaling


def softmax_topk_route(n: jax.Array, router: jax.Array, top_k: int,
                       scaling: float, normalize: bool = False
                       ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``n`` [T, D] -> (experts [T, k], weights [T, k], probabilities
    [T, E]): ``softmax(n W_r)`` over ALL experts in float32 at ``highest``,
    the ``k`` largest chosen (greedy, no bias), their own probabilities the
    weights: as they are, or normalised over the chosen (``+ 1e-20``);
    then scaled."""
    probs = jax.nn.softmax(jnp.dot(
        n.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    w, chosen = jax.lax.top_k(probs, top_k)
    if normalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * scaling, probs


def sequence_balance_loss(probs: jax.Array, chosen: jax.Array,
                          sequences: int, alpha: float) -> jax.Array:
    """``alpha * mean over sequences of sum_e f_e P_e``: per sequence of
    ``S`` tokens ``f_e = E / (k S) * (assignments to e)`` and ``P_e`` the
    mean probability of ``e``. The counts carry no gradient; it reaches the
    router (and the block's input) through ``P``."""
    t, e = probs.shape
    k = chosen.shape[1]
    s = t // sequences
    counts = jnp.sum(chosen.reshape(sequences, s * k, 1)
                     == jnp.arange(e, dtype=chosen.dtype), axis=1)
    f = counts.astype(probs.dtype) * (e / (k * s))
    mean_p = jnp.mean(probs.reshape(sequences, s, e), axis=1)
    return alpha * jnp.mean(jnp.sum(f * mean_p, axis=-1))


def group_held_assignments(chosen: jax.Array, weights: jax.Array,
                           held: Sequence[int], num_experts: int,
                           block: int):
    """Sort the assignments that land on held experts into blocks of one
    expert each. Returns ``(tokens [L], gates [L], block_expert [L/block],
    blocks_in_use, counts [len(held)])``: slot ``i`` of the layout serves
    token ``tokens[i]`` (``T`` marks an empty slot, gate 0) with weight
    ``gates[i]``; every expert's run is padded to whole blocks; ``L`` is
    the layout's static worst case (every assignment held)."""
    t, k = chosen.shape
    n_held = len(held)
    local_of = np.full(num_experts, n_held, np.int32)
    local_of[np.asarray(held)] = np.arange(n_held, dtype=np.int32)
    local = jnp.asarray(local_of)[chosen.reshape(-1)]
    order = jnp.argsort(local, stable=True)       # held first, by expert
    sorted_local = local[order]
    counts = jnp.sum(local[:, None] == jnp.arange(n_held)[None, :],
                     axis=0, dtype=jnp.int32)
    padded = (counts + block - 1) // block * block
    ends = jnp.cumsum(padded)
    here = jnp.minimum(sorted_local, n_held - 1)
    rank = jnp.arange(t * k, dtype=jnp.int32) \
        - (jnp.cumsum(counts) - counts)[here]
    length = -(-t * k // block) * block + n_held * block
    dest = jnp.where(sorted_local < n_held,
                     (ends - padded)[here] + rank, length)
    tokens = jnp.full((length,), t, jnp.int32).at[dest].set(
        (order // k).astype(jnp.int32), mode="drop")
    gates = jnp.zeros((length,), weights.dtype).at[dest].set(
        weights.reshape(-1)[order], mode="drop")
    starts = jnp.arange(length // block, dtype=jnp.int32) * block
    block_expert = jnp.minimum(
        jnp.searchsorted(ends, starts, side="right"),
        n_held - 1).astype(jnp.int32)
    return tokens, gates, block_expert, ends[-1] // block, counts


def _block_of(tokens, gates, block_expert, b, block):
    idx = jax.lax.dynamic_slice_in_dim(tokens, b * block, block)
    gate = jax.lax.dynamic_slice_in_dim(gates, b * block, block)
    return idx, gate, block_expert[b]


def _rows(x, idx):
    return jnp.take(x, idx, axis=0, mode="fill", fill_value=0)


def token_rows_kernel_selected(hidden: int, dtype) -> bool:
    """Whether a ``[tokens, hidden]`` accumulator of the block loops can be
    the DMA row kernel's (``ops/pallas_rows.add_unique_rows``), as far as
    the array itself says: float32, whole 128-lane planes a row. Its caller
    adds what only it knows: the accumulator on ONE device, no mesh axis
    over its rows (``HybridLM`` reads that off its leaves)."""
    return np.dtype(dtype) == np.dtype(np.float32) and hidden % 128 == 0


def _token_accumulator(like: jax.Array, rows_interpret: Optional[bool]):
    """Where a block's result rows land: ``(zero, add(acc, idx, vals),
    done(acc))`` for a float32 ``[T, hidden]`` sum over the blocks, ``idx``
    a block's UNIQUE token ids (``T`` = an empty slot, dropped), ``vals``
    ``[block, hidden]``. ``rows_interpret`` None, or an accumulator the row
    kernel cannot serve: XLA's scatter-add into ``[T, hidden]``. Otherwise
    the loop carries ``[T, hidden / 128, 128]``, the DMA read-modify-write
    kernel adds a block's rows in place (under the Pallas interpreter where
    ``rows_interpret``), and ``done`` turns the sum back once after the
    loop. Either way every row takes the same additions in the same order:
    the two planes agree to the bit."""
    t, d = like.shape
    if rows_interpret is None or not token_rows_kernel_selected(
            d, like.dtype):
        return (jnp.zeros_like(like),
                lambda acc, idx, vals: acc.at[idx].add(vals, mode="drop"),
                lambda acc: acc)
    from multiverso_tpu.ops.pallas_rows import add_unique_rows
    # Whole (8, 128) tiles a row: the 21 planes of hidden 2,688 are carried
    # as 24, which is what they occupy in HBM anyway, the COLUMNS padded
    # before the view is taken and cut after it is turned back. (Carried as
    # 21, XLA re-lays [T, 21, 128] through a transposed layout and the v5e
    # program's code read 211 MB against 55, 1.9% of nemotron_train's
    # ``peak_hbm_gb``, PERF.md 6, PR 41; and the DMAs moved partial tiles.)
    planes = d // 128
    pad = (-planes) % 8

    def add(acc, idx, vals):
        if pad:
            vals = jnp.pad(vals, ((0, 0), (0, pad * 128)))
        return add_unique_rows(acc, idx, vals.reshape(-1, planes + pad, 128),
                               interpret=rows_interpret)

    return (jnp.zeros((t, planes + pad, 128), like.dtype), add,
            lambda acc: acc.reshape(t, -1)[:, :d])


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def grouped_relu2_experts(n, gates, w_up, w_down, tokens, block_expert,
                          blocks_in_use, block, rows_interpret=None):
    """``sum_e gate_e relu(n W_up,e)^2 W_down,e`` over the grouped
    assignments: ``n`` [T, D], ``w_up`` [E_held, D, F], ``w_down``
    [E_held, F, D] -> [T, D]. ``rows_interpret``: where a block's rows land
    (:func:`_token_accumulator`)."""
    zero, add, done = _token_accumulator(n, rows_interpret)

    def body(b, out):
        idx, gate, e = _block_of(tokens, gates, block_expert, b, block)
        h = jnp.square(jax.nn.relu(_rows(n, idx) @ w_up[e]))
        return add(out, idx, (h @ w_down[e]) * gate[:, None])

    return done(jax.lax.fori_loop(0, blocks_in_use, body, zero))


def _grouped_fwd(n, gates, w_up, w_down, tokens, block_expert, blocks_in_use,
                 block, rows_interpret):
    out = grouped_relu2_experts(n, gates, w_up, w_down, tokens, block_expert,
                                blocks_in_use, block, rows_interpret)
    return out, (n, gates, w_up, w_down, tokens, block_expert, blocks_in_use)


def _grouped_bwd(block, rows_interpret, saved, dout):
    n, gates, w_up, w_down, tokens, block_expert, blocks_in_use = saved
    zero, add, done = _token_accumulator(n, rows_interpret)

    def body(b, carry):
        dn, dgates, dup, ddown = carry
        idx, gate, e = _block_of(tokens, gates, block_expert, b, block)
        x = _rows(n, idx)
        r = jax.nn.relu(x @ w_up[e])
        h = jnp.square(r)
        dy = _rows(dout, idx)
        dgates = jax.lax.dynamic_update_slice_in_dim(
            dgates, jnp.sum(dy * (h @ w_down[e]), axis=-1), b * block, 0)
        dy = dy * gate[:, None]
        da = (dy @ w_down[e].T) * (2.0 * r)
        ddown = ddown.at[e].add(h.T @ dy)
        dup = dup.at[e].add(x.T @ da)
        return add(dn, idx, da @ w_up[e].T), dgates, dup, ddown

    dn, dgates, dup, ddown = jax.lax.fori_loop(
        0, blocks_in_use, body,
        (zero, jnp.zeros_like(gates), jnp.zeros_like(w_up),
         jnp.zeros_like(w_down)))
    no_grad = lambda x: np.zeros(x.shape, jax.dtypes.float0)  # noqa: E731
    return (done(dn), dgates, dup, ddown, no_grad(tokens),
            no_grad(block_expert), no_grad(blocks_in_use))


grouped_relu2_experts.defvjp(_grouped_fwd, _grouped_bwd)


def _silu_parts(a):
    """(silu(a), its derivative)."""
    sig = jax.nn.sigmoid(a)
    return a * sig, sig * (1.0 + a * (1.0 - sig))


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def grouped_gated_experts(n, gates, w_gate, w_up, w_down, tokens,
                          block_expert, blocks_in_use, block,
                          rows_interpret=None):
    """``sum_e gate_e (silu(n W_gate,e) * n W_up,e) W_down,e`` over the
    grouped assignments: ``n`` [T, D], ``w_gate`` / ``w_up`` [E_held, D, F],
    ``w_down`` [E_held, F, D] -> [T, D]. ``rows_interpret``: where a block's
    rows land (:func:`_token_accumulator`)."""
    zero, add, done = _token_accumulator(n, rows_interpret)

    def body(b, out):
        idx, gate, e = _block_of(tokens, gates, block_expert, b, block)
        x = _rows(n, idx)
        h = jax.nn.silu(x @ w_gate[e]) * (x @ w_up[e])
        return add(out, idx, (h @ w_down[e]) * gate[:, None])

    return done(jax.lax.fori_loop(0, blocks_in_use, body, zero))


def _gated_fwd(n, gates, w_gate, w_up, w_down, tokens, block_expert,
               blocks_in_use, block, rows_interpret):
    out = grouped_gated_experts(n, gates, w_gate, w_up, w_down, tokens,
                                block_expert, blocks_in_use, block,
                                rows_interpret)
    return out, (n, gates, w_gate, w_up, w_down, tokens, block_expert,
                 blocks_in_use)


def _gated_bwd(block, rows_interpret, saved, dout):
    n, gates, w_gate, w_up, w_down, tokens, block_expert, blocks_in_use = \
        saved
    zero, add, done = _token_accumulator(n, rows_interpret)

    def body(b, carry):
        dn, dgates, dgate_w, dup, ddown = carry
        idx, gate, e = _block_of(tokens, gates, block_expert, b, block)
        x = _rows(n, idx)
        a, u = x @ w_gate[e], x @ w_up[e]
        act, dact = _silu_parts(a)
        h = act * u
        dy = _rows(dout, idx)
        dgates = jax.lax.dynamic_update_slice_in_dim(
            dgates, jnp.sum(dy * (h @ w_down[e]), axis=-1), b * block, 0)
        dy = dy * gate[:, None]
        dh = dy @ w_down[e].T
        da, du = dh * u * dact, dh * act
        ddown = ddown.at[e].add(h.T @ dy)
        dgate_w = dgate_w.at[e].add(x.T @ da)
        dup = dup.at[e].add(x.T @ du)
        return (add(dn, idx, da @ w_gate[e].T + du @ w_up[e].T), dgates,
                dgate_w, dup, ddown)

    dn, dgates, dgate_w, dup, ddown = jax.lax.fori_loop(
        0, blocks_in_use, body,
        (zero, jnp.zeros_like(gates), jnp.zeros_like(w_gate),
         jnp.zeros_like(w_up), jnp.zeros_like(w_down)))
    no_grad = lambda x: np.zeros(x.shape, jax.dtypes.float0)  # noqa: E731
    return (done(dn), dgates, dgate_w, dup, ddown, no_grad(tokens),
            no_grad(block_expert), no_grad(blocks_in_use))


grouped_gated_experts.defvjp(_gated_fwd, _gated_bwd)


def held_topk_moe(n: jax.Array, router: jax.Array, bias: jax.Array,
                  w_up: jax.Array, w_down: jax.Array, s_up: jax.Array,
                  s_down: jax.Array, held: Sequence[int], top_k: int,
                  scaling: float, normalize: bool = True, block: int = 512,
                  shared: bool = True, scoring: str = "sigmoid",
                  w_gate: Optional[jax.Array] = None,
                  s_gate: Optional[jax.Array] = None,
                  balance: Optional[Tuple[float, int]] = None,
                  count_all: bool = False,
                  rows_interpret: Optional[bool] = None,
                  n_group: int = 1, topk_group: int = 1):
    """One chip's share of a top-k expert layer: ``n`` [T, D] ->
    (y [T, D], assignments per held expert [len(held)]). ``router`` is
    [D, E] over ALL experts, ``w_up`` / ``w_down`` hold the experts
    ``held`` names, in that order. ``shared=False`` leaves the shared
    expert to another share (a deployment computes it once).

    ``scoring``: ``sigmoid`` (the ``k`` largest of ``score + bias``, with
    ``n_group`` > 1 group-limited: :func:`sigmoid_topk_route`) or
    ``softmax`` (greedy, ``bias`` unused). With ``w_gate`` / ``s_gate`` the
    experts are gated, ``(silu(n W_gate) * n W_up) W_down``; without, they
    are ``relu(n W_up)^2 W_down``. ``balance = (alpha, sequences)`` (softmax
    only; ``n`` is ``sequences`` equal runs of tokens) adds a third result,
    the sequence-wise balance loss. ``count_all`` adds, last, the assignments
    to EVERY expert of the router, [E] (the router is whole on every share):
    what a selection bias's update between steps reads. ``rows_interpret``
    says where the block loops' result rows land: None (a caller that does
    not know ``n`` to live on one device) in XLA's scatter-add, else, for a
    float32 ``n`` of whole 128-lane planes, in the DMA row kernel, run under
    the Pallas interpreter where true (:func:`_token_accumulator`)."""
    if scoring == "softmax":
        chosen, weights, probs = softmax_topk_route(n, router, top_k,
                                                    scaling, normalize)
    else:
        with jax.named_scope("lm_route"):
            chosen, weights = sigmoid_topk_route(
                n, router, bias, top_k, scaling, normalize, n_group,
                topk_group)
    tokens, gates, block_expert, in_use, counts = group_held_assignments(
        chosen, weights, held, router.shape[1], block)
    if w_gate is None:
        y = grouped_relu2_experts(n, gates, w_up, w_down, tokens,
                                  block_expert, in_use, block,
                                  rows_interpret)
        if shared:
            y = y + jnp.square(jax.nn.relu(n @ s_up)) @ s_down
    else:
        y = grouped_gated_experts(n, gates, w_gate, w_up, w_down, tokens,
                                  block_expert, in_use, block,
                                  rows_interpret)
        if shared:
            y = y + (jax.nn.silu(n @ s_gate) * (n @ s_up)) @ s_down
    out = (y, counts)
    if balance is not None:
        out += (sequence_balance_loss(probs, chosen, balance[1], balance[0]),)
    if count_all:
        out += (jnp.sum(chosen.reshape(-1, 1) == jnp.arange(router.shape[1]),
                        axis=0, dtype=jnp.int32),)
    return out
