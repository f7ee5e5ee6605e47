"""The fused passes round a KDA block's delta rule (``ops/pallas_kda_passes.
py``) under the Pallas interpreter, at sequence tiles of 64 so that a test's
sequence spans several: ``q | k | v | g`` out of ONE joined projection's
output and the gated output norm, each with all its gradients, against the
``jax.numpy`` lines of ``kda.py`` reading the same columns of the same array;
that a sequence's first rows see zeros and the sequences of a batch never
mix; the L2 norm's ``eps`` branch; the rule that chooses them, and what a
shape it refuses runs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from multiverso_tpu.models.hybrid_lm import HybridLMConfig, kda
from multiverso_tpu.models.hybrid_lm.mamba2 import causal_conv1d
from multiverso_tpu.models.hybrid_lm.norm import rmsnorm
from multiverso_tpu.ops import pallas_kda_passes, pallas_mamba
from multiverso_tpu.ops.pallas_kda_passes import (delta_rule_inputs,
                                                  gated_head_norm,
                                                  kda_passes_selected)

ROWS = 64
#: whole tiles; a last tile of 8 rows; one tile that ends past the sequence
LENGTHS = {"whole_tiles": 2 * ROWS, "ragged": 3 * ROWS + 8, "short": 40}
HEADS = [1, 2, 8]       # a column tile is one head, two, four of the eight
WIDTH = 128
BOUND = -5.0            # kda_lower_bound, as published
EPS = 1e-6
NAMES = ("q", "k", "v", "g")


@pytest.fixture(autouse=True)
def short_tiles(monkeypatch):
    """Sequence tiles of 64 positions, and no program traced at 512."""
    passes = (pallas_kda_passes._inputs_forward,
              pallas_kda_passes._inputs_backward,
              pallas_kda_passes._out_forward,
              pallas_kda_passes._out_backward)
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


def leaves(rng, heads: int, taps: int = 4, width: int = WIDTH):
    """The three parts' taps, ``A_log`` and ``dt_bias`` of ``heads`` heads."""
    return (tuple(normal(rng, heads * width, taps, scale=0.5)
                  for _ in range(3)),
            jnp.log(jnp.asarray(rng.uniform(1.0, 2.0, heads), jnp.float32)),
            normal(rng, heads * width))


def inputs_reference(u, taps, a_log, dt_bias, width: int = WIDTH):
    """``kda._heads_mixer``'s lines, on the joined projection's columns."""
    bsz, s, total = u.shape
    hd = total // 4
    by_heads = (bsz, s, hd // width, width)

    def heads(part):
        return jax.nn.silu(causal_conv1d(
            u[..., part * hd:(part + 1) * hd], taps[part])).reshape(by_heads)

    q = kda.l2_normalised(heads(0)) * width ** -0.5
    k = kda.l2_normalised(heads(1))
    g = kda.kda_gate(u[..., 3 * hd:].reshape(by_heads), a_log,
                     dt_bias.reshape(by_heads[2:]), BOUND)
    return tuple(t.reshape(bsz, s, hd) for t in (q, k, heads(2), g))


def inputs_fused(u, taps, a_log, dt_bias, width: int = WIDTH):
    return delta_rule_inputs(u, taps, a_log, dt_bias, width, BOUND, True)


def weighed(fn, cots):
    return lambda *a: sum(jnp.sum(out * cot) for out, cot in zip(
        fn(*a), cots))


@pytest.mark.parametrize("taps", [3, 4])
@pytest.mark.parametrize("heads", HEADS)
@pytest.mark.parametrize("length", sorted(LENGTHS))
def test_delta_rule_inputs_are_the_mixers_lines(length, heads, taps):
    """``q``, ``k``, ``v``, ``g`` and the gradients of the joined
    projection's output, of each part's taps, of ``A_log`` and ``dt_bias``."""
    rng = np.random.default_rng(LENGTHS[length] + heads + taps)
    u = normal(rng, 2, LENGTHS[length], 4 * heads * WIDTH)
    params = leaves(rng, heads, taps)
    want = inputs_reference(u, *params)
    got = inputs_fused(u, *params)
    for name, g, t in zip(NAMES, got, want):
        assert g.shape == t.shape
        assert rel(g, t) < 1e-6, name
    cots = [normal(rng, *t.shape) for t in want]
    grads = jax.grad(weighed(inputs_fused, cots), (0, 1, 2, 3))(u, *params)
    wanted = jax.grad(weighed(inputs_reference, cots), (0, 1, 2, 3))(
        u, *params)
    flat = jax.tree_util.tree_leaves
    for name, g, t in zip(("u", "conv_q", "conv_k", "conv_v", "A_log",
                           "dt_bias"), flat(grads), flat(wanted)):
        assert g.shape == t.shape and float(jnp.abs(t).max()) > 0
        # a head's rate: ONE number summed over every position and lane
        assert rel(g, t) < (2e-5 if name == "A_log" else 3e-6), name


@pytest.mark.parametrize("width", [128, 256])
@pytest.mark.parametrize("heads", HEADS)
@pytest.mark.parametrize("length", sorted(LENGTHS))
def test_gated_head_norm_is_the_norm_and_the_gate(length, heads, width):
    """The normed and gated output and the gradients of the delta rule's
    output, of the gate's input and of ``o_norm``; a head of one lane tile,
    and of two."""
    rng = np.random.default_rng(LENGTHS[length] + heads + width)
    shape = (2, LENGTHS[length], heads, width)
    o = normal(rng, 2, LENGTHS[length], heads * width)
    gate = normal(rng, *shape[:3])
    w = 1.0 + normal(rng, width, scale=0.3)
    cot = normal(rng, *o.shape)

    def reference(o, gate, w):
        return (rmsnorm(o.reshape(shape), w, EPS)
                * jax.nn.sigmoid(gate)[..., None]).reshape(o.shape)

    def fused(o, gate, w):
        return gated_head_norm(o, gate, w, EPS, True)

    assert rel(fused(o, gate, w), reference(o, gate, w)) < 1e-6
    grads = jax.grad(lambda *a: jnp.sum(fused(*a) * cot), (0, 1, 2))(
        o, gate, w)
    wanted = jax.grad(lambda *a: jnp.sum(reference(*a) * cot), (0, 1, 2))(
        o, gate, w)
    for name, g, t in zip(("o", "gate", "o_norm"), grads, wanted):
        assert g.shape == t.shape and float(jnp.abs(t).max()) > 0
        assert rel(g, t) < 3e-6, name


@pytest.mark.parametrize("length", ["whole_tiles", "ragged"])
def test_the_sequences_of_a_batch_do_not_mix(length):
    """A sequence passed in a batch is the sequence passed alone, to the bit,
    forward and backward: the ``K - 1`` rows before a sequence's first are
    zeros, not its neighbour's last; and the first position's ``v`` is its
    own input through the last tap alone."""
    heads = 2
    rng = np.random.default_rng(3)
    u = normal(rng, 3, LENGTHS[length], 4 * heads * WIDTH)
    params = leaves(rng, heads)
    cots = [normal(rng, 3, LENGTHS[length], heads * WIDTH) for _ in NAMES]

    def run(u, cots):
        out, back = jax.vjp(lambda u: inputs_fused(u, *params), u)
        return out, back(tuple(cots))[0]

    together, du = run(u, cots)
    for i in range(3):
        alone, du_alone = run(u[i:i + 1], [c[i:i + 1] for c in cots])
        for a, t in zip(alone, together):
            np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(t[i]))
        np.testing.assert_array_equal(np.asarray(du_alone[0]),
                                      np.asarray(du[i]))
    hd = heads * WIDTH
    first = jax.nn.silu(u[:, 0, 2 * hd:3 * hd] * params[0][2][:, 3])
    assert rel(together[2][:, 0], first) < 1e-6


@pytest.mark.parametrize("part", ["q", "k"])
def test_a_head_of_zeros_takes_the_norms_eps_branch(part):
    """Rows whose convolved head is all zeros (the head's columns zero for
    ``K`` rows on end): the output is zero there, not NaN, and the gradient
    is the reference's, which passes ``1 / eps`` through."""
    heads, length = 2, LENGTHS["ragged"]
    rng = np.random.default_rng(5)
    u = np.array(normal(rng, 1, length, 4 * heads * WIDTH))
    at = NAMES.index(part) * heads * WIDTH + WIDTH      # the part's 2nd head
    u[:, 60:70, at:at + WIDTH] = 0.0        # across two sequence tiles
    u = jnp.asarray(u)
    params = leaves(rng, heads)
    got = inputs_fused(u, *params)[NAMES.index(part)]
    assert not np.asarray(got)[0, 63:70, WIDTH:].any()
    assert np.asarray(got)[0, 63:70, :WIDTH].all()
    cots = [normal(rng, 1, length, heads * WIDTH, scale=1e-3)
            for _ in NAMES]
    grads = jax.grad(weighed(inputs_fused, cots))(u, *params)
    wanted = jax.grad(weighed(inputs_reference, cots))(u, *params)
    assert np.isfinite(np.asarray(grads)).all()
    assert float(jnp.abs(wanted[0, 63:70, at:at + WIDTH]).max()) > 1.0
    assert rel(grads, wanted) < 3e-6


RULE = {
    # head_dim, heads, taps, dtypes -> taken
    "ling3_group": ((128, 8, 4, np.float32), True),
    "ling3_all_heads": ((128, 32, 4, np.float32, np.float32), True),
    "one_head": ((128, 1, 4, np.float32), True),
    "two_tiles_a_head": ((256, 4, 4, np.float32), True),
    "eight_taps": ((128, 8, 8, np.float32), True),
    "one_tap": ((128, 8, 1, np.float32), True),
    "bfloat16": ((128, 8, 4, np.float32, jnp.bfloat16), False),
    "float16_first": ((128, 8, 4, np.float16, np.float32), False),
    "head_of_64": ((64, 8, 4, np.float32), False),
    "head_of_16": ((16, 4, 4, np.float32), False),
    "head_of_192": ((192, 8, 4, np.float32), False),
    "head_past_a_column_tile": ((1024, 2, 4, np.float32), False),
    "nine_taps": ((128, 8, 9, np.float32), False),
    "no_taps": ((128, 8, 0, np.float32), False),
    "no_heads": ((128, 0, 4, np.float32), False),
}


@pytest.mark.parametrize("case", sorted(RULE))
def test_the_rule(case):
    shape, taken = RULE[case]
    assert kda_passes_selected(*shape) is taken


def mixer_case(wide: bool, groups: int = 1):
    cfg = HybridLMConfig(
        hidden_size=32, vocab_size=64, pattern="K", short_conv_kernel_size=4,
        kda_lower_bound=BOUND, kda_chunk=32, **(
            dict(kda_num_heads=2 * groups, kda_head_dim=128) if wide else
            dict(kda_num_heads=4, kda_head_dim=16)))
    rng = np.random.default_rng(7)
    h, d = cfg.kda_num_heads, cfg.kda_head_dim
    taps, a_log, dt_bias = leaves(rng, h, 4, d)
    p = {"wq": normal(rng, 32, h * d, scale=0.2),
         "wk": normal(rng, 32, h * d, scale=0.2),
         "wv": normal(rng, 32, h * d, scale=0.2),
         "wa": normal(rng, 32, h * d, scale=0.2),
         "wbeta": normal(rng, 32, h, scale=0.2),
         "wg": normal(rng, 32, h, scale=0.2),
         "conv_q": taps[0], "conv_k": taps[1], "conv_v": taps[2],
         "A_log": a_log, "dt_bias": dt_bias,
         "o_norm": 1.0 + normal(rng, d, scale=0.3),
         "wo": normal(rng, h * d, 32, scale=0.1)}
    return cfg, p, normal(rng, 2, 72, 32), normal(rng, 2, 72, 32)


def without_the_passes(monkeypatch):
    monkeypatch.setattr(kda, "kda_passes_selected", lambda *a: False)


@pytest.mark.parametrize("groups", [1, 2], ids=["all_heads", "two_groups"])
def test_a_block_on_the_fused_passes_is_the_block_on_jax_numpys(
        groups, monkeypatch):
    """``kda_mixer`` with its leaves on one device: the passes are the
    kernels' (``pallas_call``s of each pass in its jaxpr, the four joined
    projections ONE product), a group of heads at a time too, and the output
    and every leaf's gradient are those of the ``jax.numpy`` plane (the delta
    rule on ``jax.numpy`` on both sides: ``tests/test_hybrid_lm_ling3.py``
    steps a model with both on their kernels)."""
    cfg, p, n, cot = mixer_case(True, groups)
    monkeypatch.setattr(kda, "kda_kernel_selected", lambda *a: False)
    if groups > 1:      # a group of two heads of the four
        monkeypatch.setattr(kda, "GROUP_ELEMENTS", 72 * 2 * 128)

    def run():
        def loss(p, n):
            return jnp.sum(kda.kda_mixer(p, n, cfg, scan_interpret=True)
                           * cot)
        return (jax.jit(lambda p, n: kda.kda_mixer(
            p, n, cfg, scan_interpret=True))(p, n),
                jax.jit(jax.grad(loss, (0, 1)))(p, n),
                str(jax.make_jaxpr(jax.grad(loss))(p, n)))

    out, grads, text = run()
    for name in ("_inputs_forward", "_inputs_backward", "_out_forward",
                 "_out_backward"):
        assert f"name={name}" in text, name
    without_the_passes(monkeypatch)
    want, wanted, plain = run()
    assert "_inputs_forward" not in plain and "_out_forward" not in plain
    assert rel(out, want) < 1e-5
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), t in zip(flat, jax.tree_util.tree_leaves(wanted)):
        assert float(jnp.abs(t).max()) > 0
        assert rel(g, t) < 2e-5, jax.tree_util.keystr(path)


def todays_lines(p, n, cfg, interpret):
    """``kda._heads_mixer`` as it stood before the passes had a kernel."""
    bsz, s, _ = n.shape
    h, d = cfg.kda_num_heads, cfg.kda_head_dim

    def heads(w, taps):
        return jax.nn.silu(causal_conv1d(n @ p[w], p[taps])).reshape(
            bsz, s, h, d)

    q = kda.l2_normalised(heads("wq", "conv_q")) * d ** -0.5
    k = kda.l2_normalised(heads("wk", "conv_k"))
    v = heads("wv", "conv_v")
    g = kda.kda_gate((n @ p["wa"]).reshape(bsz, s, h, d), p["A_log"],
                     p["dt_bias"].reshape(h, d), cfg.kda_lower_bound)
    beta = jax.nn.sigmoid(n @ p["wbeta"])
    o = kda.kda_chunked(q, k, v, g, beta, cfg.kda_chunk, interpret)
    y = rmsnorm(o, p["o_norm"], cfg.norm_eps) \
        * jax.nn.sigmoid(n @ p["wg"])[..., None]
    return y.reshape(bsz, s, h * d) @ p["wo"]


@pytest.mark.parametrize("why", ["no_placement", "heads_of_16"])
def test_a_call_the_rule_refuses_runs_todays_lines(why, monkeypatch):
    """Without the caller's word that the arrays live on one device, or with
    heads narrower than a lane tile, the mixer gives the bits of the lines it
    had before the passes' kernels, and traces to the jaxpr of a ``kda``
    that knows none, to the letter."""
    cfg, p, n, _ = mixer_case(why == "no_placement")
    interpret = None if why == "no_placement" else True
    np.testing.assert_array_equal(
        np.asarray(jax.jit(lambda p, n: kda.kda_mixer(
            p, n, cfg, scan_interpret=interpret))(p, n)),
        np.asarray(jax.jit(lambda p, n: todays_lines(
            p, n, cfg, interpret))(p, n)))

    def traced():
        return str(jax.make_jaxpr(jax.grad(lambda p, n: jnp.sum(
            kda.kda_mixer(p, n, cfg, scan_interpret=interpret))))(p, n))

    now = traced()
    assert "_inputs_forward" not in now and "_out_forward" not in now

    def no_kernel(*a, **kw):
        raise AssertionError("a fused pass where the rule refuses")

    monkeypatch.setattr(kda, "delta_rule_inputs", no_kernel)
    monkeypatch.setattr(kda, "gated_head_norm", no_kernel)
    without_the_passes(monkeypatch)
    assert traced() == now
