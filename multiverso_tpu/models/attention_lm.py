"""Long-context decoder-only LM trained with causal ring attention.

The reference has no sequence dimension anywhere (SURVEY.md §5); this model
is the framework's demonstration that long-context training is first-class:
the sequence axis is sharded over the mesh's ``"seq"`` axis and attention
runs as the ring program in ``parallel/sequence.py`` (K/V shards rotating
over ICI, streaming-softmax merge, causal masking reconstructed from block
indices), with data parallelism on the ``"data"`` axis. Memory per device is
O(S/n) — the S x S score matrix never materializes, which is what makes
sequence lengths beyond a single chip's HBM trainable.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from multiverso_tpu.ops import pallas_interpret
from multiverso_tpu.parallel.sequence import (ring_attention,
                                              ulysses_attention)
from multiverso_tpu.utils.log import check, log

Params = Dict[str, jax.Array]


@dataclasses.dataclass
class LMConfig:
    vocab: int = 256
    dim: int = 64
    heads: int = 4
    layers: int = 2
    seq: int = 128
    learning_rate: float = 1e-3
    data_parallel: Optional[int] = None   # None -> infer from devices
    seq_parallel: Optional[int] = None
    moe_experts: int = 0                  # >0: MoE MLP (expert parallelism)
    moe_aux_weight: float = 0.01
    # "ring": K/V rotation, O(S/n) memory (default). "ulysses": all-to-all
    # head<->seq layout swap — fewer collective rounds when heads divide
    # the seq axis, at O(S) score memory per device.
    sp_mode: str = "ring"
    remat: bool = False                   # rematerialize each layer block
    # >0: train with the 1F1B layer pipeline over a ("stage", "seq") mesh
    # (PP x SP in one program); layers must divide by it. Batches fed to
    # fit() are split into `pipeline_microbatches` microbatches.
    pipeline_stages: int = 0
    pipeline_microbatches: int = 4
    seed: int = 0


def init_params(cfg: LMConfig, key: jax.Array) -> Params:
    keys = jax.random.split(key, 2 + 4 * cfg.layers)
    scale = cfg.dim ** -0.5
    params: Params = {
        "embed": jax.random.normal(keys[0], (cfg.vocab, cfg.dim)) * scale,
        "out": jax.random.normal(keys[1], (cfg.dim, cfg.vocab)) * scale,
    }
    for i in range(cfg.layers):
        k = keys[2 + 4 * i: 6 + 4 * i]
        params[f"qkv_{i}"] = jax.random.normal(
            k[0], (cfg.dim, 3 * cfg.dim)) * scale
        params[f"attn_out_{i}"] = jax.random.normal(
            k[1], (cfg.dim, cfg.dim)) * scale
        if cfg.moe_experts > 0:
            from multiverso_tpu.parallel.expert import init_moe

            moe = init_moe(k[2], cfg.dim, 4 * cfg.dim, cfg.moe_experts)
            params[f"moe_router_{i}"] = moe.router
            params[f"moe_w1_{i}"] = moe.w1
            params[f"moe_w2_{i}"] = moe.w2
        else:
            params[f"mlp_in_{i}"] = jax.random.normal(
                k[2], (cfg.dim, 4 * cfg.dim)) * scale
            params[f"mlp_out_{i}"] = jax.random.normal(
                k[3], (4 * cfg.dim, cfg.dim)) * scale
    return params


def _ln(x: jax.Array) -> jax.Array:
    mu = x.mean(-1, keepdims=True)
    var = x.var(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-6)


def forward(params: Params, tokens: jax.Array, cfg: LMConfig,
            mesh: Mesh) -> Tuple[jax.Array, jax.Array]:
    """tokens [B, S] -> (logits [B, S, vocab], moe aux loss). Positions
    enter via a fixed sinusoidal table."""
    B, S = tokens.shape
    H, D = cfg.heads, cfg.dim
    dh = D // H
    x = jnp.take(params["embed"], tokens, axis=0) + _posenc(S, D)[None]
    aux_total = jnp.float32(0.0)

    def layer_block(x, i):
        aux = jnp.float32(0.0)
        h = _ln(x)
        qkv = h @ params[f"qkv_{i}"]                       # [B,S,3D]
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(t):
            return t.reshape(B, S, H, dh).transpose(0, 2, 1, 3)

        attn = (ulysses_attention if cfg.sp_mode == "ulysses"
                else ring_attention)
        o = attn(heads(q), heads(k), heads(v), mesh,
                 causal=True)                              # [B,H,S,dh]
        o = o.transpose(0, 2, 1, 3).reshape(B, S, D)
        x = x + o @ params[f"attn_out_{i}"]
        h = _ln(x)
        if cfg.moe_experts > 0:
            from multiverso_tpu.parallel.expert import MoEParams, top1_moe

            moe = MoEParams(params[f"moe_router_{i}"],
                            params[f"moe_w1_{i}"], params[f"moe_w2_{i}"])
            y, aux = top1_moe(moe, h)
            x = x + y
        else:
            x = x + jax.nn.gelu(h @ params[f"mlp_in_{i}"]) \
                @ params[f"mlp_out_{i}"]
        return x, aux

    for i in range(cfg.layers):
        block = (jax.checkpoint(layer_block, static_argnums=(1,))
                 if cfg.remat else layer_block)
        x, aux = block(x, i)
        aux_total = aux_total + aux
    return _ln(x) @ params["out"], aux_total


# ---------------------------------------------------------------------------
# 1F1B pipelined training (PP x SP): transformer blocks sharded over the
# "stage" mesh axis, the sequence over "seq"; one shard_map program runs the
# whole schedule (parallel/pipeline.py::pipeline_train_1f1b).
# ---------------------------------------------------------------------------
def init_pipeline_params(cfg: LMConfig, key: jax.Array) -> Params:
    """Stage-stacked parameters: every per-block tensor gets leading axes
    [stages, blocks_per_stage]; embed/out stay unstacked (embed trains via
    the pipeline's input-stream grads, out is the loss head)."""
    P_, L = cfg.pipeline_stages, cfg.layers
    bps = L // P_
    keys = jax.random.split(key, 2 + 4 * L)
    scale = cfg.dim ** -0.5

    def stack(offset):
        return jnp.stack([
            jnp.stack([jax.random.normal(
                keys[2 + 4 * (s * bps + j) + offset],
                _BLOCK_SHAPES(cfg)[offset]) * scale
                for j in range(bps)])
            for s in range(P_)])

    return {
        "embed": jax.random.normal(keys[0], (cfg.vocab, cfg.dim)) * scale,
        "out": jax.random.normal(keys[1], (cfg.dim, cfg.vocab)) * scale,
        "qkv": stack(0), "attn_out": stack(1),
        "mlp_in": stack(2), "mlp_out": stack(3),
    }


def _BLOCK_SHAPES(cfg: LMConfig):
    return ((cfg.dim, 3 * cfg.dim), (cfg.dim, cfg.dim),
            (cfg.dim, 4 * cfg.dim), (4 * cfg.dim, cfg.dim))


def _pipeline_stage_fn(cfg: LMConfig, sp: int, interpret: bool):
    """One pipeline stage = blocks_per_stage transformer blocks. ``x`` is
    this device's [mb, S/sp, D] sequence block; attention runs the ring
    body over the enclosing shard_map's "seq" axis (``interpret``: that
    mesh's ``ops.pallas_interpret`` verdict, for the flash kernel)."""
    from multiverso_tpu.parallel.sequence import ring_attention_block

    H, D = cfg.heads, cfg.dim
    dh = D // H
    bps = cfg.layers // cfg.pipeline_stages

    def block(bp, x):
        mb, Sb, _ = x.shape
        h = _ln(x)
        qkv = h @ bp["qkv"]
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(t):
            return t.reshape(mb, Sb, H, dh).transpose(0, 2, 1, 3)

        o = ring_attention_block(heads(q), heads(k), heads(v), "seq", sp,
                                 causal=True, interpret=interpret)
        o = o.transpose(0, 2, 1, 3).reshape(mb, Sb, D)
        x = x + o @ bp["attn_out"]
        h = _ln(x)
        return x + jax.nn.gelu(h @ bp["mlp_in"]) @ bp["mlp_out"]

    def stage_fn(stage_params, x):
        for j in range(bps):
            x = block(jax.tree.map(lambda p: p[j], stage_params), x)
        return x

    return stage_fn


def _pipeline_loss_fn(S: int):
    """Sum (not mean) next-token xent over this device's sequence block;
    the global wrap-around position is masked via the seq-axis index."""
    def loss_fn(head, y, tgt):
        logits = _ln(y) @ head["out"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
        Sb = y.shape[1]
        gpos = jax.lax.axis_index("seq") * Sb + jnp.arange(Sb)
        valid = (gpos < S - 1).astype(picked.dtype)[None, :]
        return -(picked * valid).sum()
    return loss_fn


def next_token_loss(params: Params, tokens: jax.Array, cfg: LMConfig,
                    mesh: Mesh) -> jax.Array:
    logits, aux = forward(params, tokens, cfg, mesh)
    logp = jax.nn.log_softmax(logits, axis=-1)
    # predict token[t+1] from position t; wrap-around position masked out
    targets = jnp.roll(tokens, -1, axis=1)
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    S = tokens.shape[1]
    valid = (jnp.arange(S) < S - 1).astype(picked.dtype)[None, :]
    xent = -(picked * valid).sum() / valid.sum() / tokens.shape[0]
    return xent + cfg.moe_aux_weight * aux


def pipeline_params_to_flat(cfg: LMConfig, params: Params) -> Params:
    """Unstack pipeline params into the flat layout :func:`forward` reads —
    used for eval and for pipelined-vs-flat parity tests."""
    bps = cfg.layers // cfg.pipeline_stages
    flat: Params = {"embed": params["embed"], "out": params["out"]}
    for s in range(cfg.pipeline_stages):
        for j in range(bps):
            i = s * bps + j
            flat[f"qkv_{i}"] = params["qkv"][s, j]
            flat[f"attn_out_{i}"] = params["attn_out"][s, j]
            flat[f"mlp_in_{i}"] = params["mlp_in"][s, j]
            flat[f"mlp_out_{i}"] = params["mlp_out"][s, j]
    return flat


def _posenc(S: int, D: int) -> jax.Array:
    pos = jnp.arange(S)[:, None] / (10000.0 ** (jnp.arange(D)[None, :] / D))
    return jnp.where(jnp.arange(D)[None, :] % 2 == 0, jnp.sin(pos),
                     jnp.cos(pos))


class AttentionLM:
    def __init__(self, cfg: LMConfig,
                 devices: Optional[List[jax.Device]] = None):
        import optax

        check(cfg.dim % cfg.heads == 0, "dim must divide by heads")
        devices = list(devices if devices is not None else jax.devices())
        n = len(devices)
        self.cfg = cfg
        self._opt = optax.adam(cfg.learning_rate)
        if cfg.pipeline_stages > 0:
            self._init_pipelined(devices, n)
            return
        sp = cfg.seq_parallel or min(n, 4)
        dp = cfg.data_parallel or (n // sp)
        check(dp * sp <= n, f"mesh {dp}x{sp} exceeds {n} devices")
        check(cfg.seq % sp == 0, "seq must divide by seq_parallel")
        self.mesh = Mesh(
            np.asarray(devices[:dp * sp]).reshape(dp, sp), ("data", "seq"))
        self.params = init_params(cfg, jax.random.PRNGKey(cfg.seed))
        self._opt_state = self._opt.init(self.params)
        self._token_sharding = NamedSharding(self.mesh, P("data", "seq"))

        def train_step(params, opt_state, tokens):
            loss, grads = jax.value_and_grad(next_token_loss)(
                params, tokens, cfg, self.mesh)
            updates, opt_state = self._opt.update(grads, opt_state)
            import optax as _optax
            params = _optax.apply_updates(params, updates)
            return params, opt_state, loss

        self._train_step = jax.jit(train_step, donate_argnums=(0, 1))

    # -- 1F1B pipelined mode (PP x SP) ------------------------------------
    def _init_pipelined(self, devices, n: int) -> None:
        import optax

        from multiverso_tpu.parallel.pipeline import pipeline_train_1f1b

        cfg = self.cfg
        P_ = cfg.pipeline_stages
        check(cfg.layers % P_ == 0, "layers must divide by pipeline_stages")
        check(cfg.moe_experts == 0,
              "pipeline mode does not compose with MoE yet")
        check(cfg.data_parallel in (None, 1),
              "pipeline mode has no data axis (microbatching covers it); "
              "unset data_parallel")
        sp = cfg.seq_parallel or 1
        check(cfg.seq % sp == 0, "seq must divide by seq_parallel")
        check(P_ * sp <= n, f"mesh {P_}x{sp} exceeds {n} devices")
        self.mesh = Mesh(np.asarray(devices[:P_ * sp]).reshape(P_, sp),
                         ("stage", "seq"))
        self.params = init_pipeline_params(cfg, jax.random.PRNGKey(cfg.seed))
        self._opt_state = self._opt.init(self.params)
        self._token_sharding = NamedSharding(
            self.mesh, P(None, None, "seq"))
        stage_fn = _pipeline_stage_fn(
            cfg, sp, pallas_interpret(self.mesh.devices.flat))
        loss_fn = _pipeline_loss_fn(cfg.seq)
        stage_keys = ("qkv", "attn_out", "mlp_in", "mlp_out")

        def train_step(params, opt_state, tokens):     # tokens [M, mb, S]
            M, mb, S = tokens.shape
            stage_params = {k: params[k] for k in stage_keys}
            head = {"out": params["out"]}
            x = jnp.take(params["embed"], tokens, axis=0) \
                + _posenc(S, cfg.dim)[None, None]
            tgts = jnp.roll(tokens, -1, axis=-1)
            loss_sum, sgrads, hgrads, dxs = pipeline_train_1f1b(
                stage_fn, loss_fn, stage_params, x, tgts, self.mesh,
                stream_spec=P(None, None, "seq", None),
                target_spec=P(None, None, "seq"),
                reduce_axes=("seq",), head_params=head,
                return_input_grads=True)
            dembed = jnp.zeros_like(params["embed"]).at[
                tokens.reshape(-1)].add(dxs.reshape(-1, cfg.dim))
            denom = M * mb * (S - 1)         # mean-per-position, as eval
            grads = {"embed": dembed, "out": hgrads["out"], **sgrads}
            grads = jax.tree.map(lambda g: g / denom, grads)
            updates, opt_state = self._opt.update(grads, opt_state)
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss_sum / denom

        self._train_step = jax.jit(train_step, donate_argnums=(0, 1))

    def _microbatch(self, tokens: np.ndarray) -> np.ndarray:
        M = self.cfg.pipeline_microbatches
        B = tokens.shape[0]
        check(B % M == 0,
              f"batch {B} must divide into {M} pipeline microbatches")
        return tokens.reshape(M, B // M, tokens.shape[1])

    def fit(self, batches: Iterable[np.ndarray]) -> List[float]:
        """batches of int tokens [B, S]; returns per-batch losses."""
        from multiverso_tpu.utils.configure import get_flag
        check(not get_flag("flash_attention"),
              "-flash_attention is forward-only (loss/eval): "
              "flash_block_attn has no backward pass — jax's pallas_call "
              "JVP rule rejects its scratch operands with a bare "
              "AssertionError — so train without the flag (ROADMAP A8)")
        losses = []
        for tokens in batches:
            tokens = np.asarray(tokens, dtype=np.int32)
            if self.cfg.pipeline_stages > 0:
                tokens = self._microbatch(tokens)
            tokens = jax.device_put(tokens, self._token_sharding)
            self.params, self._opt_state, loss = self._train_step(
                self.params, self._opt_state, tokens)
            losses.append(loss)
        return [float(l) for l in losses]

    def loss(self, tokens: np.ndarray) -> float:
        if self.cfg.pipeline_stages > 0:
            # eval through the flat forward on a 1x1 (data, seq) mesh
            eval_mesh = Mesh(
                np.asarray(self.mesh.devices.flat[:1]).reshape(1, 1),
                ("data", "seq"))
            # params live sharded on the (stage, seq) mesh; fetch to host so
            # the single-device eval forward doesn't mix meshes
            flat = pipeline_params_to_flat(
                self.cfg, jax.tree.map(np.asarray, self.params))
            flat = jax.tree.map(jnp.asarray, flat)
            return float(next_token_loss(
                flat, jnp.asarray(np.asarray(tokens, dtype=np.int32)),
                self.cfg, eval_mesh))
        tokens = jax.device_put(np.asarray(tokens, dtype=np.int32),
                                self._token_sharding)
        return float(next_token_loss(self.params, tokens, self.cfg,
                                     self.mesh))
