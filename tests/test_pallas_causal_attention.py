"""The causal attention kernels (``ops/pallas_causal_attention.py``) under
the Pallas interpreter against the ``jax.numpy`` walk of ``models/hybrid_lm/
attention.py`` that stays their oracle: value and all three gradients; the
rule that chooses the plane; the counter that says which ran."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from multiverso_tpu.models.hybrid_lm import HybridLM, HybridLMConfig
from multiverso_tpu.models.hybrid_lm import attention
from multiverso_tpu.ops.pallas_causal_attention import \
    attention_kernel_selected
from multiverso_tpu.telemetry import get_registry


def _planes(fn, *arrays):
    """``fn(interpret)(*arrays)`` and its pull of one seeded ``dout`` on the
    ``jax.numpy`` walk and on the kernels: [(out, dq, dk, dv)] x 2."""
    got = []
    for interpret in (None, True):
        out, pull = jax.vjp(fn(interpret), *arrays)
        dout = jax.random.normal(jax.random.PRNGKey(7), out.shape, out.dtype)
        got.append((out,) + pull(dout))
        text = str(jax.make_jaxpr(fn(interpret))(*arrays))
        assert ("pallas_call" in text) == (interpret is True)
    return got


# (sequences, positions, key-value heads, group, key width, value width,
# attention block, span)
@pytest.mark.parametrize("bsz,s,kh,g,d,dv,blk,span", [
    pytest.param(1, 256, 2, 1, 128, 128, 128, None, id="mha"),
    pytest.param(1, 256, 1, 4, 128, 128, 128, None, id="group4"),
    pytest.param(1, 256, 1, 16, 128, 128, 128, None, id="group16"),
    pytest.param(1, 256, 2, 1, 192, 128, 128, None, id="keys192"),
    pytest.param(1, 200, 2, 1, 128, 128, 128, None, id="padded"),
    pytest.param(1, 512, 2, 1, 128, 128, 128, 2, id="windowed"),
    pytest.param(2, 512, 1, 2, 128, 128, 256, None, id="two_rows"),
    pytest.param(1, 384, 1, 1, 128, 128, 128, None, id="square_tiles"),
])
def test_kernels_match_the_walk(bsz, s, kh, g, d, dv, blk, span):
    """``padded`` goes through :func:`causal_gqa`'s padding (200 positions in
    blocks of 128); ``windowed`` gives the walk a lower bound (a window of two
    blocks, no remote keys); ``two_rows`` has two sequences; a query tile is
    two key tiles but in ``square_tiles`` (three blocks: one each)."""
    keys = jax.random.split(jax.random.PRNGKey(s + g + d), 3)
    q = jax.random.normal(keys[0], (bsz, s, kh, g, d), jnp.float32)
    k = jax.random.normal(keys[1], (bsz, s, kh, d), jnp.float32)
    v = jax.random.normal(keys[2], (bsz, s, kh, dv), jnp.float32)

    def fn(interpret):
        if span is None:
            return lambda q, k, v: attention.causal_gqa(
                q, k, v, blk, interpret=interpret)
        return lambda q, k, v: attention._blocked_attention(
            *(attention.in_blocks(t, blk) for t in (q, k, v)), None, None,
            float(d) ** -0.5, blk, span, interpret)

    walk, kernels = _planes(fn, q, k, v)
    for name, a, b in zip(("out", "dq", "dk", "dv"), walk, kernels):
        assert float(jnp.abs(a).max()) > 0, name
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=2e-5,
                                   atol=2e-5 * float(jnp.abs(a).max()),
                                   err_msg=name)


def test_the_rule_refuses_what_the_kernels_do_not_take():
    f32, shape = np.float32, (8192, 512, 16, 1, 128, 128)
    assert attention_kernel_selected(*shape, f32, f32, f32)
    # the four cells' shapes: ouro, dsv2lite (192 / 128), nemotron (a group
    # of 16); lfm2's 64-wide heads fill half a lane tile
    assert attention_kernel_selected(8192, 512, 16, 1, 192, 128, f32)
    assert attention_kernel_selected(8192, 512, 2, 16, 128, 128, f32)
    assert not attention_kernel_selected(8192, 512, 8, 4, 64, 64, f32)
    assert not attention_kernel_selected(*shape, f32, jnp.bfloat16, f32)
    assert not attention_kernel_selected(*shape, f32, remote=(0, 0))
    assert not attention_kernel_selected(*shape, f32, chosen=0)
    # a block of whole lane tiles; two heads a step only without a group,
    # and an even number of them; a head's sequence within VMEM
    assert not attention_kernel_selected(8192, 72, 16, 1, 128, 128, f32)
    assert not attention_kernel_selected(8192, 512, 16, 2, 192, 128, f32)
    assert not attention_kernel_selected(8192, 512, 3, 1, 192, 128, f32)
    assert not attention_kernel_selected(1 << 17, 512, 16, 1, 128, 128, f32)


WIDE = dict(hidden_size=64, vocab_size=64, pattern="*M*", mamba_num_heads=2,
            mamba_head_dim=16, ssm_state_size=16, n_groups=1, conv_kernel=4,
            chunk_size=8, num_attention_heads=2, num_key_value_heads=1,
            head_dim=128, attn_block=128, loss_block=64, row_bucket=16)


@pytest.mark.parametrize("plane", ["fused", "xla", "two_devices"])
def test_step_counts_the_plane_its_attention_blocks_walk_on(plane):
    """``lm.attn.plane.<fused/xla>``, one a step and attention block run: the
    kernels where a head is a whole lane tile and the block whole tiles, on
    the leaves' one device (here under the interpreter); the ``jax.numpy``
    walk at 16-wide heads, and under a mesh axis of two."""
    cfg = HybridLMConfig(**dict(
        WIDE, **({"head_dim": 16} if plane == "xla" else {})))
    mesh = {}
    if plane == "two_devices":
        mesh = dict(dp_mesh=jax.sharding.Mesh(
            np.asarray(jax.devices()[:2]), ("dp",)), dp_axis="dp")
    model = HybridLM(cfg, mode="local", **mesh)
    assert model.mixer_interpret == (True if plane == "fused" else None)
    if plane == "two_devices":
        return
    reg = get_registry()
    names = [f"lm.attn.plane.{p}" for p in ("fused", "xla")]
    before = [reg.counter(n).value for n in names]
    rng = np.random.default_rng(5)
    tokens = rng.integers(0, cfg.vocab_size, (2, 128)).astype(np.int32)
    assert np.isfinite(model.step(tokens))
    moved = dict(zip(("fused", "xla"), (reg.counter(n).value - b
                                        for n, b in zip(names, before))))
    assert moved == {plane: 2, "xla" if plane == "fused" else "fused": 0}
