"""word2vec's AdaGrad row update on the Pallas plane (ISSUE 31, ISSUE 33).

``models/word2vec/model._apply_update`` runs, where ``ServerStore``'s rule
allows it (``core/table.pallas_rows_eligible``: float32, exactly 128
columns, one shard; AdaGrad on), as a sort plus the row kernel
``ops/pallas_rows.adagrad_fold_rows``, which folds the duplicates itself;
since ISSUE 33 also a SHARD of tables whose rows lie over one mesh axis,
under ``shard_map`` with the ids moved to the shard's row range.
Held here: the kernel's three phases and the fused update against XLA's
lines; three whole sg-ns steps against the XLA plane under the benchmark
cell's own limit; the four variants through every builder that runs them;
the selection rule; that every program the rule leaves alone lowers to the
text the parent commit lowered it to; a raw-step maker of one argument
still served; the counters; the mesh plane against the one-device kernel
on ids in one shard, in the other, across the boundary and out of range.
CPU: the kernels run under the Pallas interpreter, values and counts only.
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from multiverso_tpu.models.word2vec import model as w2v_model
from multiverso_tpu.models.word2vec.model import (
    _apply_update, _on_row_kernel, _ShardedRows, build_chunked_pipeline,
    build_device_block_step, build_scan_step, build_sg_ns_step,
    build_sharded_block_step, raw_sg_ns_step, raw_step_factory,
    row_kernel_selected)
from multiverso_tpu.ops.pallas_rows import ADAGRAD_PHASES, adagrad_fold_rows
from multiverso_tpu.telemetry import counter

V, D = 300, 128
STEP_ROWS_REL_GAP = 3e-4      # benchmark/traffic/sgns_zipf_b8192.json


def _tables(seed=1, rows=V, cols=D, dtype=np.float32):
    r = np.random.default_rng(seed)
    return [jnp.asarray((r.normal(size=(rows, cols)) * 0.01).astype(dtype)),
            jnp.asarray((r.normal(size=(rows, cols)) * 0.01).astype(dtype)),
            jnp.asarray((r.random((rows, cols)) * 1e-5).astype(np.float32)),
            jnp.asarray((r.random((rows, cols)) * 1e-5).astype(np.float32))]


def _mesh_2x2():
    return Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                ("data", "model"))


def _update(fused: bool):
    """``_apply_update`` jitted on one plane, ``lr`` traced."""
    def fn(w, g2, rows, grad, lr):
        return _apply_update(w, g2, rows, grad, lr, True)
    return jax.jit(_on_row_kernel(fn, True if fused else None))


def _reference(w, g2, rows, grad, lr):
    """The update as ISSUE 31 states it, in float64: per touched row
    ``G += sum(g^2)``, ``w -= lr sum(g) / sqrt(G + 1e-6)``; ids out of
    range dropped."""
    w, g2 = np.asarray(w, np.float64), np.asarray(g2, np.float64)
    for r in np.unique(rows):
        if 0 <= r < w.shape[0]:
            g = np.asarray(grad, np.float64)[rows == r]
            g2[r] += np.square(g).sum(0)
            w[r] -= lr * g.sum(0) / np.sqrt(g2[r] + 1e-6)
    return w, g2


UPDATE_CASES = {
    # name: (ids maker, slab)
    "heavy_duplicates_one_slab": (
        lambda rng: rng.integers(0, 24, 200).astype(np.int32), 8192),
    "heavy_duplicates_slabs_of_64": (
        lambda rng: rng.integers(0, 24, 256).astype(np.int32), 64),
    "a_run_longer_than_two_slabs": (
        lambda rng: np.concatenate([np.full(150, 17), rng.integers(
            0, V, 42)]).astype(np.int32), 64),
    "clipped_and_dropped_ids": (
        lambda rng: np.asarray([V + 7, 3, V, 3, 299, 0, 2 ** 30, 5] * 9,
                               np.int32), 8192),
    "ids_not_a_multiple_of_the_slab": (
        lambda rng: rng.integers(0, V, 150).astype(np.int32), 64),
}


@pytest.mark.parametrize("case", sorted(UPDATE_CASES))
def test_fused_update_is_the_xla_update(case, monkeypatch):
    make, slab = UPDATE_CASES[case]
    monkeypatch.setattr(w2v_model, "_SORT_SLAB", slab)
    rng = np.random.default_rng(31)
    rows = make(rng)
    grad = rng.normal(size=(len(rows), D)).astype(np.float32)
    grad[::5] = 0.0                     # masked pairs carry zero gradients
    w, _, g2, _ = _tables()
    lr = np.float32(0.05)
    got = _update(True)(w, g2, rows, grad, lr)
    want = _update(False)(w, g2, rows, grad, lr)
    ref = _reference(w, g2, rows, grad, lr)
    for g, x, r, start in zip(got, want, ref, (w, g2)):
        g, x, start = np.asarray(g), np.asarray(x), np.asarray(start)
        # a re-ordered float32 sum of at most 150 terms
        np.testing.assert_allclose(g, x, rtol=2e-5, atol=1e-6)
        np.testing.assert_allclose(g, r, rtol=2e-5, atol=1e-6)
        untouched = np.setdiff1d(np.arange(V), rows)
        assert np.array_equal(g[untouched], start[untouched])


def test_masked_pairs_are_no_ops_on_the_fused_plane():
    w, _, g2, _ = _tables()
    rows = np.arange(64, dtype=np.int32) % 9
    out = _update(True)(w, g2, rows, np.zeros((64, D), np.float32),
                        np.float32(0.05))
    assert np.array_equal(np.asarray(out[0]), np.asarray(w))
    assert np.array_equal(np.asarray(out[1]), np.asarray(g2))


def test_k_equal_gradients_sum_their_squares_not_square_their_sum():
    """k equal gradients g on one row: G gains k g^2. Squaring the folded
    total (``AdaGradUpdater.rows_math``) would give k^2 g^2."""
    k, row = 7, 11
    w = jnp.zeros((V, D), jnp.float32)
    g2 = jnp.zeros((V, D), jnp.float32)
    grad = np.full((k, D), 0.5, np.float32)
    got_w, got_g = _update(True)(w, g2, np.full(k, row, np.int32), grad,
                                 np.float32(0.1))
    np.testing.assert_allclose(np.asarray(got_g)[row], k * 0.25, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(got_w)[row], -0.1 * k * 0.5 / np.sqrt(k * 0.25 + 1e-6),
        rtol=1e-6)


@pytest.mark.parametrize("phase", ADAGRAD_PHASES)
def test_a_phase_writes_its_own_table_only(phase):
    rng = np.random.default_rng(3)
    ids = np.sort(rng.integers(0, 40, 96)).astype(np.int32)
    starts = np.r_[True, ids[1:] != ids[:-1]]
    back = (np.arange(96) - np.maximum.accumulate(
        np.where(starts, np.arange(96), 0))).astype(np.int32)
    grad = rng.normal(size=(96, D)).astype(np.float32)
    w, _, g2, _ = _tables()
    new_w, new_g = jax.jit(
        lambda *a: adagrad_fold_rows(*a, phase=phase, interpret=True))(
            w, g2, ids, back, grad, np.float32(0.05))
    assert np.array_equal(np.asarray(new_w), np.asarray(w)) == \
        (phase == "accumulate")
    assert np.array_equal(np.asarray(new_g), np.asarray(g2)) == \
        (phase == "step")
    with pytest.raises(ValueError, match="phase"):
        adagrad_fold_rows(w, g2, ids, back, grad, 0.05, phase="fold")


def _zipf_batches(steps, batch, negatives, rows):
    rng = np.random.default_rng(5)
    out = []
    for _ in range(steps):
        ids = np.minimum(rng.zipf(1.3, (batch, 2 + negatives)) - 1,
                         rows - 1).astype(np.int32)
        mask = (np.arange(batch) < batch - rng.integers(1, batch // 8)
                ).astype(np.float32)
        out.append((ids[:, 0], ids[:, 1], ids[:, 2:], mask))
    return out


def test_three_sg_ns_steps_fused_are_the_xla_steps(monkeypatch):
    """The benchmark's ``step_gaps`` in small: three steps on Zipf batches
    with a masked tail, rows after them against the XLA plane's, error norm
    over the change, under the cell's ``step_rows_rel_gap``; ``w_out``'s
    ids span three slabs, so both kernel forms run."""
    monkeypatch.setattr(w2v_model, "_SORT_SLAB", 96)
    fused_before = counter("w2v.rows.plane.fused").value
    step = build_sg_ns_step(True)
    xla = jax.jit(raw_sg_ns_step(True))
    start = _tables()
    got, want = _tables(), _tables()
    lr = np.float32(0.05)
    for batch in _zipf_batches(3, 96, 5, V):
        out_f = step(*got, *batch, lr)
        out_x = xla(*want, *batch, lr)
        got, want = list(out_f[:4]), list(out_x[:4])
        assert abs(float(out_f[4]) - float(out_x[4])) \
            <= 4e-6 * abs(float(out_x[4]))
    for g, x, s in zip(got, want, start):
        g, x, s = (np.asarray(a, np.float64) for a in (g, x, s))
        assert np.linalg.norm(g - x) / np.linalg.norm(x - s) \
            < STEP_ROWS_REL_GAP
    assert counter("w2v.rows.plane.fused").value - fused_before == 3


def _variant_batches(variant, groups, batch, rows):
    """``groups`` stacked batches of ``variant``'s raw step, Zipf ids."""
    rng = np.random.default_rng(7)
    K, C, L = 3, 4, 5

    def ids(*shape):
        return np.minimum(rng.zipf(1.3, (groups, batch, *shape)) - 1,
                          rows - 1).astype(np.int32)

    def bits(*shape):
        return (rng.random((groups, batch, *shape)) < 0.7).astype(np.float32)
    mask = bits()
    mask[:, :4] = 1.0
    target = ((ids(L), bits(L), bits(L)) if variant.endswith("hs")
              else (ids(K), mask))
    if variant.startswith("sg"):
        return (ids(), *((ids(),) if variant == "sg_ns" else ()), *target)
    return (ids(), ids(C), bits(C), *target)


def _gap_over_change(got, want, start):
    got, want, start = (np.asarray(a, np.float64) for a in (got, want, start))
    return np.linalg.norm(got - want) / np.linalg.norm(want - start)


@pytest.mark.parametrize("variant", ["sg_ns", "sg_hs", "cbow_ns", "cbow_hs"])
def test_a_scan_group_fused_is_the_xla_group(variant, monkeypatch):
    """``build_scan_step`` (the host batch path, ``DistributedWord2Vec``,
    ``commplane``): the kernel inside ``lax.scan``, every variant's ids
    (``[B, C]`` contexts, ``[B, L]`` Huffman points) through it, against
    the same program on host arrays, which take XLA's lines."""
    monkeypatch.setattr(w2v_model, "_SORT_SLAB", 96)
    sg, hs = variant.startswith("sg"), variant.endswith("hs")
    step = build_scan_step(raw_step_factory(sg, hs)(True), True)
    batches = _variant_batches(variant, 2, 48, V)
    lr = np.float32(0.05)
    start = _tables()
    got_plane = step.program(*start, *batches, lr)[1]
    host = [np.asarray(t) for t in start]
    assert got_plane is True and step.program(*host, *batches, lr)[1] is None
    want = step(*host, *batches, lr)
    got = step(*_tables(), *batches, lr)
    assert abs(float(got[4]) - float(want[4])) <= 4e-6 * abs(float(want[4]))
    for g, x, s in zip(got[:4], want[:4], start):
        assert _gap_over_change(g, x, s) < STEP_ROWS_REL_GAP


def test_the_chunked_pipeline_fused_is_the_xla_pipeline(monkeypatch):
    """``pipelined_host``'s programs: two host-dispatched ``chunk_step``s
    and the ``tail_step`` loop over the rest, on one device (the kernel)
    against tables whose rows lie over BOTH axes of a 2 x 2 mesh (XLA's
    lines, and what a program returns stays there); the tail's kernel sits
    in a ``fori_loop`` with a traced start."""
    monkeypatch.setattr(w2v_model, "_SORT_SLAB", 96)
    chunk, K, n = 48, 3, 4
    _, chunk_step, tail_step = build_chunked_pipeline(2, K, chunk, True)
    rng = np.random.default_rng(11)
    ids = np.minimum(rng.zipf(1.3, (n, chunk, 2 + K)) - 1,
                     V - 1).astype(np.int32)
    streams = (ids[..., 0], ids[..., 1], ids[..., 2:],
               np.int32(n * chunk - 17))          # the last chunk part masked
    lr = np.float32(0.05)

    def run(tables):
        loss = 0.0
        for i in range(2):
            *tables, l = chunk_step(*tables, *streams, np.int32(i), lr)
            loss += float(l)
        *tables, l = tail_step(*tables, *streams, lr, np.int32(2))
        return tables, loss + float(l)

    before = {p: counter(f"w2v.rows.plane.{p}").value
              for p in ("fused", "xla")}
    start = _tables()
    over_two = NamedSharding(_mesh_2x2(), P(("data", "model"), None))
    want, want_loss = run([jax.device_put(t, over_two) for t in start])
    got, got_loss = run(_tables())
    assert abs(got_loss - want_loss) <= 4e-6 * abs(want_loss)
    for g, x, s in zip(got, want, start):
        assert _gap_over_change(g, x, s) < STEP_ROWS_REL_GAP
    for p, was in before.items():
        assert counter(f"w2v.rows.plane.{p}").value - was == 3


def test_a_raw_step_maker_of_one_argument_is_served_on_the_fused_plane(
        monkeypatch):
    """The benchmark's fault-planting control replaces ``raw_sg_ns_step``
    with ``maker(adagrad)`` returning five values and runs it on ONE device
    at 128 columns (``benchmark/tests``: no forced device count): every
    builder calls a maker with ``adagrad`` alone, whatever the plane."""
    def frozen_step(adagrad):
        def step(w_in, w_out, g_in, g_out, centers, contexts, negatives,
                 mask, lr):
            return w_in, w_out, g_in, g_out, np.float32(1.0)
        return step
    monkeypatch.setattr(w2v_model, "raw_sg_ns_step", frozen_step)
    tables = [jax.device_put(t, jax.devices()[0]) for t in _tables()]
    want = [np.asarray(t) for t in tables]
    batch = _zipf_batches(1, 32, 3, V)[0]
    step = build_sg_ns_step(True)
    assert step.program(*tables, *batch, np.float32(0.05))[1] is True
    out = step(*tables, *batch, np.float32(0.05))
    assert float(out[4]) == 1.0 and len(out) == 5
    for got, was in zip(out[:4], want):
        assert np.array_equal(np.asarray(got), was)
    block = build_device_block_step(2, 3, 16, True)
    out = block(*out[:4], *_block_args(V, D, jnp.float32)[4:])
    assert len(out) == 6
    _, chunk_step, _ = build_chunked_pipeline(2, 3, 16, True)
    assert len(chunk_step(*out[:4], np.zeros((2, 16), np.int32),
                          np.zeros((2, 16), np.int32),
                          np.zeros((2, 16, 3), np.int32), np.int32(20),
                          np.int32(0), np.float32(0.05))) == 5


RULE_CASES = {
    # name: (dtype, columns, adagrad, shards, selected)
    "float32": (np.float32, 128, True, 1, True),
    "bfloat16": (jnp.bfloat16, 128, True, 1, False),
    "64_columns": (np.float32, 64, True, 1, False),
    "256_columns": (np.float32, 256, True, 1, False),
    "adagrad_off": (np.float32, 128, False, 1, False),
    "a_model_axis_of_2": (np.float32, 128, True, 2, True),
    "a_model_axis_of_2_64_columns": (np.float32, 64, True, 2, False),
}


@pytest.mark.parametrize("case", sorted(RULE_CASES))
def test_the_row_kernel_is_selected_from_shape_dtype_and_placement(case):
    dtype, cols, adagrad, shards, selected = RULE_CASES[case]
    rows = 64
    sharding = NamedSharding(
        Mesh(np.asarray(jax.devices()[:shards]), ("model",)),
        P("model", None))
    tables = [jax.device_put(jnp.zeros((rows, cols), dtype), sharding),
              jax.device_put(jnp.zeros((rows, cols), dtype), sharding),
              jax.device_put(jnp.zeros((rows, cols), jnp.float32), sharding),
              jax.device_put(jnp.zeros((rows, cols), jnp.float32), sharding)]
    # ``ServerStore``'s rule says yes to ONE shard only; a shard of a mesh
    # is handed to it as the table it is (``_PlacedStep.row_kernel``)
    assert row_kernel_selected(tables[0], adagrad, shards == 1) == \
        (selected and shards == 1)
    step = build_sg_ns_step(adagrad)
    batch = (jnp.zeros(32, jnp.int32), jnp.ones(32, jnp.int32),
             jnp.full((32, 3), 2, jnp.int32), jnp.ones(32, jnp.float32))
    program, plane = step.program(*tables, *batch, jnp.float32(0.05))
    # off the TPU a selected kernel is interpreted; None is XLA's lines
    if selected and shards > 1:
        assert plane == _ShardedRows(sharding.mesh, "model", True)
    else:
        assert plane == (True if selected else None)
    text = program.lower(*tables, *batch, jnp.float32(0.05)).as_text()
    # interpreted here, the kernel is a loop over its grid steps; XLA's
    # lines are scatters and no loop
    assert ("stablehlo.while" in text) == selected
    before = {p: counter(f"w2v.rows.plane.{p}").value
              for p in ("fused", "xla")}
    out = step(*tables, *batch, jnp.float32(0.05))
    assert np.isfinite(float(out[4]))
    plane = "fused" if selected else "xla"
    for p, was in before.items():
        assert counter(f"w2v.rows.plane.{p}").value - was == (p == plane)


# -- the kernel a shard: tables over one mesh axis (ISSUE 33) ---------------------
HALF = V // 2       # rows a shard over a ``model`` axis of 2


def _on_the_mesh(tables):
    over_model = NamedSharding(_mesh_2x2(), P("model", None))
    return [jax.device_put(t, over_model) for t in tables]


def _ids_in(lo, hi, shape, seed=9):
    """Zipf-skewed ids (many duplicates) in ``[lo, hi)``."""
    rng = np.random.default_rng(seed)
    return (lo + np.minimum(rng.zipf(1.3, shape) - 1, hi - lo - 1)
            ).astype(np.int32)


def _mesh_batch(case, B=96, K=3):
    """One sg-ns batch whose ids lie where ``case`` says (slabs of 64:
    ``w_out``'s 384 ids span six)."""
    if case == "all_in_shard_0":
        ids = _ids_in(0, HALF, (B, 2 + K))
    elif case == "all_in_shard_1":
        ids = _ids_in(HALF, V, (B, 2 + K))
    elif case == "runs_over_slab_seams_at_the_shard_boundary":
        # w_out's stream: 96 x the last row of shard 0, then 288 x the
        # first of shard 1: both runs cross slab seams, the second slab
        # holds both, and each shard sees the other's run as sentinels
        ids = np.full((B, 2 + K), HALF, np.int32)
        ids[:, 1] = HALF - 1
        ids[:, 0] = np.where(np.arange(B) % 3 == 0, HALF - 1, HALF)
    elif case == "ids_out_of_range":
        ids = _ids_in(0, V, (B, 2 + K))
        ids[::7, 0] = V             # the first id past the last shard
        ids[1::7, 1] = V + 7
        ids[2::7, 2] = 2 ** 30
        ids[3::7, 3] = -3           # dropped by the kernel plane, as one-device
    else:
        raise KeyError(case)
    mask = (np.arange(B) < B - 5).astype(np.float32)
    return ids[:, 0], ids[:, 1], ids[:, 2:], mask


MESH_CASES = ["all_in_shard_0", "all_in_shard_1",
              "runs_over_slab_seams_at_the_shard_boundary",
              "ids_out_of_range"]


def _assert_same_step(got, want, start):
    """Loss and the four tables of two planes that fold in another order:
    a float32 sum re-associated, nothing lost and nothing counted twice
    (either reads of order 1 against the change)."""
    np.testing.assert_allclose(float(got[4]), float(want[4]), rtol=1e-6)
    for g, x, s in zip(got[:4], want[:4], start):
        # an element: a re-ordered float32 sum of up to some hundred terms
        np.testing.assert_allclose(np.asarray(g), np.asarray(x), rtol=2e-5,
                                   atol=1e-6)
        assert _gap_over_change(g, x, s) < 1e-6


@pytest.mark.parametrize("case", MESH_CASES)
def test_the_sg_ns_step_on_a_2x2_mesh_is_the_one_device_kernel_step(
        case, monkeypatch):
    """``build_sg_ns_step`` as the benchmark's check calls it on the live
    sharded tables: the kernel a ``model`` shard (replicas over ``data``
    compute alike), against the kernel over the same tables on one device;
    what it returns stays on the mesh and on the plane."""
    monkeypatch.setattr(w2v_model, "_SORT_SLAB", 64)
    step = build_sg_ns_step(True)
    batch = _mesh_batch(case)
    lr = np.float32(0.05)
    start = _tables()
    sharded = _on_the_mesh(_tables())
    plane = step.program(*sharded, *batch, lr)[1]
    assert plane == _ShardedRows(_mesh_2x2(), "model", True)
    before = {p: counter(f"w2v.rows.plane.{p}").value
              for p in ("fused", "xla")}
    got = step(*sharded, *batch, lr)
    want = step(*_tables(), *batch, lr)
    moved = {p: counter(f"w2v.rows.plane.{p}").value - was
             for p, was in before.items()}
    assert moved == {"fused": 2, "xla": 0}
    _assert_same_step(got, want, start)
    for t in got[:4]:
        assert t.sharding == sharded[0].sharding
    assert step.program(*got[:4], *batch, lr)[1] == plane
    touched = np.unique(np.concatenate([batch[1], batch[2].ravel()]))
    untouched = np.setdiff1d(np.arange(V), touched)
    assert np.array_equal(np.asarray(got[1])[untouched],
                          np.asarray(start[1])[untouched])


def _block_inputs(case, S=8, L=24):
    """Sentences and a negative table whose ids lie where ``case`` says."""
    rng = np.random.default_rng(13)
    if case == "all_in_shard_0":
        lo, hi = 0, HALF
    elif case == "all_in_shard_1":
        lo, hi = HALF, V
    else:
        lo, hi = 0, V
    sents = _ids_in(lo, hi, (S, L), seed=13)
    neg = rng.integers(lo, hi, 1024).astype(np.int32)
    if case == "runs_over_slab_seams_at_the_shard_boundary":
        sents = np.where(rng.random((S, L)) < 0.5, HALF - 1, HALF
                         ).astype(np.int32)
        neg = np.where(np.arange(1024) % 2, HALF - 1, HALF).astype(np.int32)
    elif case == "ids_out_of_range":
        neg[::5] = V + 3            # a negative past the table: dropped
    return (neg, np.ones(V, np.float32), sents, np.full((S,), L, np.int32),
            jax.random.PRNGKey(5), np.float32(0.05))


@pytest.mark.parametrize("case", MESH_CASES)
def test_the_block_step_on_a_2x2_mesh_is_the_one_device_kernel_block(
        case, monkeypatch):
    """``build_sharded_block_step`` (sentences over ``data``, rows over
    ``model``, the streams replicated) at 128 float32 columns against the
    one-device block program on the same inputs: same pairs, loss and
    tables. It lays its tables out itself, so it is judged by that layout
    whatever the arrays come in (here: one device)."""
    monkeypatch.setattr(w2v_model, "_SORT_SLAB", 64)
    kw = dict(window=2, negative=3, chunk=64, adagrad=True)
    inputs = _block_inputs(case)
    start = _tables()
    single = build_device_block_step(**kw)
    want = single(*_tables(), *inputs)
    sharded = build_sharded_block_step(_mesh_2x2(), **kw)
    assert sharded.program(*_tables(), *inputs)[1] == \
        _ShardedRows(_mesh_2x2(), "model", True)
    got = sharded(*_tables(), *inputs)
    assert int(got[5]) == int(want[5]) > 64         # more than one chunk
    _assert_same_step(got, want, start)
    assert got[0].sharding == NamedSharding(_mesh_2x2(), P("model", None))


@pytest.mark.parametrize("variant", ["cbow_ns", "sg_hs"])
def test_a_variants_group_on_the_mesh_is_the_one_device_kernel_group(
        variant, monkeypatch):
    """The other variants' ids (``[B, C]`` contexts, ``[B, L]`` Huffman
    points) through the kernel a shard: ``build_scan_step`` on tables over
    the ``model`` axis against the same tables on one device."""
    monkeypatch.setattr(w2v_model, "_SORT_SLAB", 96)
    sg, hs = variant.startswith("sg"), variant.endswith("hs")
    step = build_scan_step(raw_step_factory(sg, hs)(True), True)
    batches = _variant_batches(variant, 2, 48, V)
    lr = np.float32(0.05)
    sharded = _on_the_mesh(_tables())
    assert isinstance(step.program(*sharded, *batches, lr)[1], _ShardedRows)
    got = step(*sharded, *batches, lr)
    want = step(*_tables(), *batches, lr)
    _assert_same_step(got, want, _tables())


def _placed(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _placement_cases():
    """name -> (adagrad, the four tables, the plane expected)."""
    mesh = _mesh_2x2()
    model = NamedSharding(mesh, P("model", None))
    sharded = _ShardedRows(mesh, "model", True)
    one = jax.sharding.SingleDeviceSharding(jax.devices()[0])

    def four(rows=64, cols=128, dtype=np.float32, sharding=model, last=None):
        ws = [_placed((rows, cols), dtype, sharding)] * 2
        gs = [_placed((rows, cols), np.float32, sharding)] * 2
        if last is not None:
            gs[1] = _placed((rows, cols), np.float32, last)
        return ws + gs
    return {
        "one_device": (True, four(sharding=one), True),
        "one_device_of_a_mesh_of_one": (True, four(sharding=NamedSharding(
            Mesh(np.asarray(jax.devices()[:1]), ("server",)),
            P("server", None))), True),
        "rows_over_model": (True, four(), sharded),
        "rows_over_model_spelled_short": (True, four(
            sharding=NamedSharding(mesh, P("model"))), sharded),
        "rows_over_the_stores_eight": (
            True, four(rows=256, sharding=NamedSharding(
                Mesh(np.asarray(jax.devices()[:8]), ("server",)),
                P("server", None))),
            _ShardedRows(Mesh(np.asarray(jax.devices()[:8]), ("server",)),
                         "server", True)),
        "64_columns": (True, four(cols=64), None),
        "bfloat16": (True, four(dtype=jnp.bfloat16), None),
        "adagrad_off": (False, four(), None),
        "rows_not_divisible": (True, four(rows=63), None),
        "a_shard_shorter_than_a_kernel_step": (True, four(rows=40), None),
        "one_device_shorter_than_a_kernel_step": (
            True, four(rows=20, sharding=one), None),
        "rows_over_two_axes": (True, four(sharding=NamedSharding(
            mesh, P(("data", "model"), None))), None),
        "columns_sharded_too": (True, four(sharding=NamedSharding(
            mesh, P("model", "data"))), None),
        "replicated_over_the_mesh": (True, four(sharding=NamedSharding(
            mesh, P())), None),
        "one_table_over_another_axis": (True, four(last=NamedSharding(
            mesh, P("data", None))), None),
        "host_arrays": (True, [np.zeros((64, 128), np.float32)] * 4, None),
    }


@pytest.mark.parametrize("case", [
    "one_device", "one_device_of_a_mesh_of_one", "rows_over_model",
    "rows_over_model_spelled_short", "rows_over_the_stores_eight",
    "64_columns", "bfloat16", "adagrad_off", "rows_not_divisible",
    "a_shard_shorter_than_a_kernel_step",
    "one_device_shorter_than_a_kernel_step", "rows_over_two_axes",
    "columns_sharded_too", "replicated_over_the_mesh",
    "one_table_over_another_axis", "host_arrays"])
def test_a_placed_step_reads_its_plane_off_the_tables(case):
    """``_PlacedStep.row_kernel``: the one-device kernel, the kernel a
    shard over ONE mesh axis, or XLA's lines; one program a plane."""
    adagrad, tables, plane = _placement_cases()[case]
    step = build_sg_ns_step(adagrad)
    assert step.row_kernel(tables) == plane
    batch = (jnp.zeros(32, jnp.int32), jnp.ones(32, jnp.int32),
             jnp.full((32, 3), 2, jnp.int32), jnp.ones(32, jnp.float32),
             jnp.float32(0.05))
    assert step.program(*tables, *batch)[1] == plane
    assert step.program(*tables, *batch)[0] is step._programs[plane]
    # a program that lays its tables out itself is judged by that layout
    own = build_sharded_block_step(_mesh_2x2(), 2, 3, 16, adagrad)
    rows, cols = tables[0].shape
    assert own.row_kernel(tables) == (
        _ShardedRows(_mesh_2x2(), "model", True)
        if adagrad and cols == 128 and rows % 2 == 0 and rows >= 64
        and tables[0].dtype == np.float32 else None)


def test_the_mesh_block_program_counts_the_kernel_plane_a_block(mv_env):
    """``Word2Vec(mesh_data=2, mesh_model=2)`` at 128 columns: every block
    of ``train`` runs the kernel a shard (``w2v.rows.plane.fused`` one a
    block, ``.xla`` not at all; no counter is written by hand), under the
    program name the benchmark's metric reads."""
    from multiverso_tpu.models.word2vec import (Dictionary, Word2Vec,
                                                Word2VecConfig)
    rng = np.random.default_rng(0)
    d = Dictionary(min_count=1)
    d.counts = [50] * 80            # 40 rows a shard
    d.words = [str(i) for i in range(80)]
    cfg = Word2VecConfig(embedding_size=128, window=2, negative=3, sample=0,
                         batch_size=32, block_sentences=4,
                         pad_sentence_length=16, device_pipeline=True, seed=3,
                         mesh_data=2, mesh_model=2)
    w2v = Word2Vec(cfg, d)
    before = {n: counter(n).value for n in (
        "w2v.rows.plane.fused", "w2v.rows.plane.xla")}
    sents = [rng.integers(0, 80, 16).tolist() for _ in range(8)]
    stats = w2v.train(sentences=sents)
    moved = {n: counter(n).value - was for n, was in before.items()}
    assert moved == {"w2v.rows.plane.fused": 2, "w2v.rows.plane.xla": 0}
    assert stats["pairs"] > 0 and np.isfinite(stats["loss"])
    assert stats["dispatch_mode"] == "in_graph"
    tables = [t.store.data for t in (w2v.input_table, w2v.output_table,
                                     w2v.adagrad_in, w2v.adagrad_out)]
    assert isinstance(w2v._block_step.row_kernel(tables), _ShardedRows)
    assert w2v._block_step.__name__ == "block_step"
    # the check's step on the live tables follows the same rule
    assert build_sg_ns_step(True).row_kernel(tables) == \
        w2v._block_step.row_kernel(tables)


# -- what the rule leaves alone lowers as it did ----------------------------------
# sha256 of ``.lower(...).as_text()`` on the parent commit (2149a1a, jax
# 0.9.0), taken by running ``_untouched_programs`` against that tree. The
# two mesh programs at 128 columns run the kernel a shard since ISSUE 33:
# theirs are re-taken from ITS parent (2beef50) at 64 columns, still XLA's
# lines, and the one-device kernel program's is taken there too.
PARENT_LOWERINGS = {
    "jax": "0.9.0",
    "block_64_columns":
        "aaa6f1b2d9eacb9bae14820fd1abbf52dad3795447c9d48fed82c5ff93dd092e",
    "block_256_columns":
        "c1acb9f66a259172765dbd09aaaddbbdfa1bdea9c6c13999380271ad13d8ed42",
    "block_bfloat16":
        "645c3d24c64a1622fe33dd56f071ec48e6a8e8836705246acafcf607f80dd093",
    "block_adagrad_off":
        "62f2c5eb5b2aac37e6752fc1994c3ad398846a8c9803cd382d79bafbbfe27626",
    "block_over_the_store_mesh":
        "18c2345610bc8565eec1e1fc0b6b6387cb2a1aed07e9ee262de397da960d0f0d",
    "sharded_block_step":
        "20e1f468c430c882b86ab62c779d484d314d202b1e26411c4ae56c73a5711faf",
    "block_one_device_kernel":
        "9020e346a3151d01432fa9f311ef6cd71cb9b231ba78169d1fdf7be7633128c0",
    "sg_ns_step_64_columns":
        "52c9dd081c425619ee6d00ba44a212c35ab0c4d5fb0f07443eb5b7c8a3885359",
    "dlrm_group_rows":
        "5068c53378d5c83a03ec1ebaf493e3dab2e2bec843c6a2640ccebb9bb2ab2d6a",
}


def _block_args(rows, cols, dtype, sharding=None):
    tables = [jnp.zeros((rows, cols), dtype)] * 2 + \
        [jnp.zeros((rows, cols), jnp.float32)] * 2
    if sharding is not None:
        tables = [jax.device_put(t, sharding) for t in tables]
    S, L = 4, 8
    return (*tables, jnp.zeros(997, jnp.int32), jnp.ones(rows, jnp.float32),
            jnp.zeros((S, L), jnp.int32), jnp.full((S,), L, jnp.int32),
            jax.random.PRNGKey(0), jnp.float32(0.05))


@pytest.fixture(scope="module")
def untouched_programs():
    return _untouched_programs()


def _untouched_programs():
    """name -> lowered text of each program ISSUE 31 and ISSUE 33 must leave
    as it was."""
    kw = dict(window=2, negative=3, chunk=16)
    texts = {}
    for name, (cols, dtype, adagrad) in {
            "block_64_columns": (64, jnp.float32, True),
            "block_256_columns": (256, jnp.float32, True),
            "block_bfloat16": (128, jnp.bfloat16, True),
            "block_adagrad_off": (128, jnp.float32, False)}.items():
        step = build_device_block_step(adagrad=adagrad, **kw)
        texts[name] = step.lower(*_block_args(64, cols, dtype)).as_text()
    mesh8 = Mesh(np.asarray(jax.devices()[:8]), ("server",))
    step = build_device_block_step(adagrad=True, **kw)
    texts["block_over_the_store_mesh"] = step.lower(*_block_args(
        64, 64, jnp.float32, NamedSharding(mesh8, P("server", None)))
    ).as_text()
    # w2v_train's program in small: the kernel over tables on one device
    texts["block_one_device_kernel"] = step.lower(
        *_block_args(64, 128, jnp.float32)).as_text()
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                ("data", "model"))
    step = build_sharded_block_step(mesh, adagrad=True, **kw)
    texts["sharded_block_step"] = step.lower(
        *_block_args(64, 64, jnp.float32)).as_text()
    step = build_sg_ns_step(True)
    texts["sg_ns_step_64_columns"] = step.lower(
        *_block_args(64, 64, jnp.float32)[:4], jnp.zeros(32, jnp.int32),
        jnp.ones(32, jnp.int32), jnp.full((32, 3), 2, jnp.int32),
        jnp.ones(32, jnp.float32), jnp.float32(0.05)).as_text()
    # dlrm_train's shape in small: three 128-column AdaGrad tables in one
    # group, the fused row kernel a table (ops/pallas_rows.py is shared)
    from multiverso_tpu.core.options import AddOption, MatrixTableOption
    from multiverso_tpu.tables.table_group import (LocalTableGroup,
                                                   group_scalars)
    group = LocalTableGroup([MatrixTableOption(
        256, 128, updater="adagrad", name=f"t{i}") for i in range(3)])
    ids, lengths, deltas, _, _ = group._delta_layout(
        list(np.zeros((3, 32), np.int32)),
        list(np.zeros((3, 32, 128), np.float32)))
    texts["dlrm_group_rows"] = group._update.lower(
        group._datas, group._states, ids, deltas,
        *group_scalars([AddOption(learning_rate=0.05)] * 3),
        lengths=lengths).as_text()
    return texts


@pytest.mark.parametrize("name", [
    "block_64_columns", "block_256_columns", "block_bfloat16",
    "block_adagrad_off", "block_over_the_store_mesh", "sharded_block_step",
    "block_one_device_kernel", "sg_ns_step_64_columns", "dlrm_group_rows"])
def test_untouched_programs_lower_to_the_parents_text(untouched_programs,
                                                      name):
    if jax.__version__ != PARENT_LOWERINGS["jax"]:
        pytest.skip("the digests were taken under jax "
                    + PARENT_LOWERINGS["jax"])
    text = untouched_programs[name]
    assert "module @jit_" in text
    assert hashlib.sha256(text.encode()).hexdigest() == \
        PARENT_LOWERINGS[name]


def test_the_fused_block_program_keeps_its_name_and_counts_its_plane(
        mv_env, monkeypatch):
    """Two blocks through ``Word2Vec.train`` on tables the rule selects:
    the program is still ``jit_block_step`` (``w2v_block_device_ms`` reads
    it), it returns what the XLA plane's returns, and the plane counter
    moves one a block."""
    import multiverso_tpu as mv
    from multiverso_tpu.models.word2vec import (Dictionary, Word2Vec,
                                                Word2VecConfig)
    mv.shutdown()
    mv.init([], devices=jax.devices()[:1])
    rng = np.random.default_rng(0)
    d = Dictionary(min_count=1)
    d.counts = [50] * 40
    d.words = [str(i) for i in range(40)]
    cfg = Word2VecConfig(embedding_size=128, window=2, negative=3, sample=0,
                         batch_size=32, block_sentences=4,
                         pad_sentence_length=16, device_pipeline=True, seed=3)
    # AUTO, on a host whose launches are cheap: tables on the row kernel
    # still take ``in_graph``, the mode timed on it (tables on XLA's plane
    # would take ``pipelined_host`` here: tests/test_word2vec.py)
    monkeypatch.setattr(w2v_model, "measured_dispatch_latency_ms",
                        lambda: 0.05)
    assert w2v_model.resolve_dispatch_mode(cfg) == "pipelined_host"
    w2v = Word2Vec(cfg, d)
    before = {n: counter(n).value for n in (
        "w2v.rows.plane.fused", "w2v.rows.plane.xla")}
    sents = [rng.integers(0, 40, 16).tolist() for _ in range(8)]
    stats = w2v.train(sentences=sents)
    moved = {n: counter(n).value - was for n, was in before.items()}
    assert moved["w2v.rows.plane.fused"] == 2      # two blocks of 4 sentences
    assert moved["w2v.rows.plane.xla"] == 0
    assert stats["pairs"] > 0 and np.isfinite(stats["loss"])
    assert stats["dispatch_mode"] == "in_graph"
    tables = [t.store.data for t in (w2v.input_table, w2v.output_table,
                                     w2v.adagrad_in, w2v.adagrad_out)]
    lowered = w2v._block_step.lower(
        *tables, w2v._neg_table, w2v._keep_prob, jnp.zeros((4, 16), jnp.int32),
        jnp.full((4,), 16, jnp.int32), jax.random.PRNGKey(0),
        jnp.float32(0.05))
    assert "module @jit_block_step" in lowered.as_text()
    assert len(lowered.out_info) == 6       # four tables, loss, pairs
    assert w2v._block_step.__name__ == "block_step"
