"""The fused passes round a Mamba-2 block's scan (``ops/pallas_mamba.py``)
under the Pallas interpreter, at sequence tiles of 64 so that a test's
sequence spans several: the convolution + bias + ``silu`` + cut and the gated
group norm, each with all its gradients, against ``mamba2.causal_conv1d`` +
``silu`` + slices and ``mamba2.gated_group_rmsnorm`` reading the same columns
of the same array; that the sequences of a batch never mix; the rule that
chooses them, and what a shape it refuses runs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from multiverso_tpu.models.hybrid_lm import HybridLMConfig
from multiverso_tpu.models.hybrid_lm.mamba2 import (causal_conv1d,
                                                    gated_group_rmsnorm,
                                                    mamba2_mixer,
                                                    passes_kernel_selected)
from multiverso_tpu.ops import pallas_mamba
from multiverso_tpu.ops.pallas_mamba import (conv_silu_split,
                                             gated_group_norm,
                                             mamba_passes_selected)

ROWS = 64
#: whole tiles; a last tile of 8 rows; one tile that ends past the sequence
LENGTHS = {"whole_tiles": 2 * ROWS, "ragged": 3 * ROWS + 8, "short": 40}
#: (d_inner, groups x state): ``u`` is ``z | x | B | C | dt``, 8 heads
WIDTHS = {"one_tile": (128, 128), "x_two_tiles": (256, 128)}
HEADS = 8


@pytest.fixture(autouse=True)
def short_tiles(monkeypatch):
    """Sequence tiles of 64 positions, and no program traced at 512."""
    passes = (pallas_mamba._conv_forward, pallas_mamba._conv_backward,
              pallas_mamba._norm_forward, pallas_mamba._norm_backward)
    for fn in passes:
        fn.clear_cache()
    monkeypatch.setattr(pallas_mamba, "_ROWS", ROWS)
    yield
    for fn in passes:
        fn.clear_cache()


def normal(rng, *shape, scale=1.0):
    return jnp.asarray(rng.standard_normal(shape, dtype=np.float32)
                       * np.float32(scale))


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def projected(rng, length: int, d_inner: int, bc: int, bsz: int = 2):
    """``in_proj``'s output ``z | x | B | C | dt``."""
    return normal(rng, bsz, length, 2 * d_inner + 2 * bc + HEADS)


def conv_reference(u, w, b, d_inner: int, bc: int):
    xbc = jax.nn.silu(causal_conv1d(
        u[..., d_inner:2 * d_inner + 2 * bc], w, b))
    return (xbc[..., :d_inner], xbc[..., d_inner:d_inner + bc],
            xbc[..., d_inner + bc:])


@pytest.mark.parametrize("biased", [True, False], ids=["bias", "no_bias"])
@pytest.mark.parametrize("taps", [3, 4])
@pytest.mark.parametrize("widths", sorted(WIDTHS))
@pytest.mark.parametrize("length", sorted(LENGTHS))
def test_conv_silu_split_is_the_convolution_silu_and_slices(
        length, widths, taps, biased):
    """``x``, ``B``, ``C`` and the gradients of ``in_proj``'s output (zero
    outside the convolved columns), the taps and the bias."""
    d_inner, bc = WIDTHS[widths]
    rng = np.random.default_rng(LENGTHS[length] + d_inner + taps)
    u = projected(rng, LENGTHS[length], d_inner, bc)
    w = normal(rng, d_inner + 2 * bc, taps, scale=0.5)
    b = normal(rng, d_inner + 2 * bc) if biased else None
    want = conv_reference(u, w, b, d_inner, bc)
    cots = [normal(rng, *t.shape) for t in want]

    def fused(u, w, b):
        return conv_silu_split(u, w, b, d_inner, (d_inner, bc, bc), True)

    def loss(fn):
        return lambda *a: sum(jnp.sum(out * cot) for out, cot in zip(
            fn(*a), cots))

    got = fused(u, w, b)
    for name, g, t in zip("xBC", got, want):
        assert g.shape == t.shape
        assert rel(g, t) < 1e-6, name
    over = (0, 1, 2) if biased else (0, 1)
    grads = jax.grad(loss(fused), over)(u, w, b)
    wanted = jax.grad(loss(lambda u, w, b: conv_reference(
        u, w, b, d_inner, bc)), over)(u, w, b)
    for name, g, t in zip(("u", "conv_w", "conv_b"), grads, wanted):
        assert float(jnp.abs(t).max()) > 0
        assert rel(g, t) < 2e-6, name
    du = np.asarray(grads[0])
    assert not du[..., :d_inner].any() and not du[..., -HEADS:].any()


@pytest.mark.parametrize("d_inner,groups", [(256, 2), (512, 1)])
@pytest.mark.parametrize("length", sorted(LENGTHS))
def test_gated_group_norm_is_the_gated_group_rmsnorm(length, d_inner, groups):
    """The normed output and the gradients of ``y``, of ``in_proj``'s output
    (zero outside ``z``) and of the weight; a group of one lane tile, and of
    four."""
    rng = np.random.default_rng(LENGTHS[length] + d_inner)
    u = projected(rng, LENGTHS[length], d_inner, 128)
    y = normal(rng, 2, LENGTHS[length], d_inner)
    w = 1.0 + normal(rng, d_inner, scale=0.3)
    cot = normal(rng, *y.shape)

    def reference(y, u, w):
        return gated_group_rmsnorm(y, u[..., :d_inner], w, groups, 1e-5)

    def fused(y, u, w):
        return gated_group_norm(y, u, w, groups, 1e-5, True)

    assert rel(fused(y, u, w), reference(y, u, w)) < 1e-6
    grads = jax.grad(lambda *a: jnp.sum(fused(*a) * cot), (0, 1, 2))(y, u, w)
    wanted = jax.grad(lambda *a: jnp.sum(reference(*a) * cot), (0, 1, 2))(
        y, u, w)
    for name, g, t in zip(("y", "u", "gnorm"), grads, wanted):
        assert float(jnp.abs(t).max()) > 0
        assert rel(g, t) < 2e-6, name
    assert not np.asarray(grads[1])[..., d_inner:].any()


@pytest.mark.parametrize("length", ["whole_tiles", "ragged"])
def test_the_sequences_of_a_batch_do_not_mix(length):
    """A sequence convolved in a batch is the sequence convolved alone, to
    the bit, forward and backward: the rows before a sequence's first tile
    are zeros, not its neighbour's last."""
    d_inner, bc = WIDTHS["one_tile"]
    rng = np.random.default_rng(3)
    u = projected(rng, LENGTHS[length], d_inner, bc, bsz=3)
    w, b = normal(rng, d_inner + 2 * bc, 4), normal(rng, d_inner + 2 * bc)
    cots = [normal(rng, 3, LENGTHS[length], n) for n in (d_inner, bc, bc)]

    def run(u, cots):
        out, back = jax.vjp(lambda u: conv_silu_split(
            u, w, b, d_inner, (d_inner, bc, bc), True), u)
        return out, back(tuple(cots))[0]

    together, du = run(u, cots)
    for i in range(3):
        alone, du_alone = run(u[i:i + 1], [c[i:i + 1] for c in cots])
        for a, t in zip(alone, together):
            np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(t[i]))
        np.testing.assert_array_equal(np.asarray(du_alone[0]),
                                      np.asarray(du[i]))
    # and the first position sees its own input alone
    first = jax.nn.silu(u[:, 0, d_inner:2 * d_inner] * w[:d_inner, 3]
                        + b[:d_inner])
    assert rel(together[0][:, 0], first) < 1e-6


RULE = {
    # d_inner, groups x state, the norm's group, taps, dtypes -> taken
    "nemotron": ((4096, 1024, 512, 4, np.float32), True),
    "one_tile_each": ((128, 128, 128, 3, np.float32, np.float32), True),
    "eight_taps": ((256, 128, 128, 8), True),
    "bfloat16": ((4096, 1024, 512, 4, np.float32, jnp.bfloat16), False),
    "x_not_whole_tiles": ((192, 128, 64, 4, np.float32), False),
    "b_not_whole_tiles": ((256, 64, 128, 4, np.float32), False),
    "group_not_whole_tiles": ((256, 128, 64, 4, np.float32), False),
    "group_does_not_divide": ((384, 128, 256, 4, np.float32), False),
    "nine_taps": ((256, 128, 128, 9, np.float32), False),
    "tiny": ((32, 16, 32, 4, np.float32), False),
}


@pytest.mark.parametrize("case", sorted(RULE))
def test_the_rule(case):
    shape, taken = RULE[case]
    assert mamba_passes_selected(*shape) is taken


def mixer_case(wide: bool):
    cfg = HybridLMConfig(
        hidden_size=32, vocab_size=64, pattern="M", conv_kernel=4,
        **(dict(mamba_num_heads=4, mamba_head_dim=64, n_groups=2,
                ssm_state_size=64, chunk_size=16) if wide else
           dict(mamba_num_heads=2, mamba_head_dim=16, n_groups=1,
                ssm_state_size=16, chunk_size=8)))
    rng = np.random.default_rng(7)
    d_inner, conv = cfg.d_inner, cfg.conv_dim
    p = {"in_proj": normal(rng, 32, cfg.in_proj_dim, scale=0.2),
         "conv_w": normal(rng, conv, 4, scale=0.5),
         "conv_b": normal(rng, conv, scale=0.3),
         "dt_bias": normal(rng, cfg.mamba_num_heads, scale=0.3),
         "A_log": jnp.log(jnp.asarray(rng.uniform(
             1.0, 8.0, cfg.mamba_num_heads), jnp.float32)),
         "D": normal(rng, cfg.mamba_num_heads),
         "gnorm": 1.0 + normal(rng, d_inner, scale=0.3),
         "out_proj": normal(rng, d_inner, 32, scale=0.1)}
    return cfg, p, normal(rng, 2, 72, 32)


def test_a_shape_the_rule_refuses_runs_todays_functions_to_the_bit():
    """At the tiny widths of every other test the mixer handed an
    ``interpret`` is the mixer handed none: the same program (no kernel in
    it), the same bits."""
    cfg, p, n = mixer_case(wide=False)
    assert not passes_kernel_selected(cfg, np.float32)

    def run(interpret):
        def loss(p, n):
            return jnp.sum(jnp.sin(mamba2_mixer(p, n, cfg, interpret)))
        return jax.value_and_grad(loss, (0, 1))

    assert "pallas_call" not in str(jax.make_jaxpr(run(True))(p, n))
    for a, b in zip(jax.tree_util.tree_leaves(run(True)(p, n)),
                    jax.tree_util.tree_leaves(run(None)(p, n))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_mixer_on_the_fused_passes_is_the_mixer_on_xlas():
    """Widths the rule takes (the scan's chunk of 16 keeps the scan on its
    ``jax.numpy`` body: only the passes differ): the output and every
    gradient."""
    cfg, p, n = mixer_case(wide=True)
    assert passes_kernel_selected(cfg, np.float32)

    def run(interpret):
        def loss(p, n):
            return jnp.sum(jnp.sin(mamba2_mixer(p, n, cfg, interpret)))
        return jax.value_and_grad(loss, (0, 1))

    assert str(jax.make_jaxpr(run(True))(p, n)).count("pallas_call") >= 2
    (loss, (dp, dn)), (want, (wp, wn)) = run(True)(p, n), run(None)(p, n)
    assert abs(float(loss) - float(want)) < 1e-5 * abs(float(want))
    assert rel(dn, wn) < 1e-5
    for name in sorted(wp):
        assert rel(dp[name], wp[name]) < 1e-5, name
