"""Pallas row-op kernels vs numpy references (interpret mode on CPU)."""

import contextlib

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from multiverso_tpu.ops.pallas_rows import (gather_rows, scatter_add_rows,
                                            scatter_add_sorted_rows)


def test_gather_rows():
    rng = np.random.default_rng(0)
    table = rng.normal(size=(64, 128)).astype(np.float32)
    ids = np.array([3, 0, 63, 3, 17], dtype=np.int32)
    out = gather_rows(jnp.asarray(table), jnp.asarray(ids), interpret=True)
    np.testing.assert_allclose(np.asarray(out), table[ids])


def test_scatter_add_sorted_unique():
    table = np.zeros((16, 128), dtype=np.float32)
    ids = np.array([1, 4, 9], dtype=np.int32)
    deltas = np.ones((3, 128), dtype=np.float32)
    out = scatter_add_sorted_rows(jnp.asarray(table), jnp.asarray(ids),
                                  jnp.asarray(deltas), interpret=True)
    expected = table.copy()
    expected[ids] += 1.0
    np.testing.assert_allclose(np.asarray(out), expected)


def test_scatter_add_duplicates_accumulate():
    table = np.ones((8, 128), dtype=np.float32)
    ids = np.array([2, 2, 2, 5], dtype=np.int32)
    deltas = np.stack([np.full(128, float(i + 1), dtype=np.float32)
                       for i in range(4)])
    out = scatter_add_sorted_rows(jnp.asarray(table), jnp.asarray(ids),
                                  jnp.asarray(deltas), interpret=True)
    expected = np.ones((8, 128), dtype=np.float32)
    expected[2] += 1 + 2 + 3
    expected[5] += 4
    np.testing.assert_allclose(np.asarray(out), expected)


def test_scatter_add_run_crossing_group_boundary():
    """Regression: a duplicate-id run longer than GROUP(8) spanning a group
    boundary must not drop the first group's partial sum (advisor round-1
    finding: 16 deltas of 1.0 yielded +8.0; [1]*10+[3]*6 yielded +2.0)."""
    table = np.zeros((8, 128), dtype=np.float32)
    ids = np.full(16, 1, dtype=np.int32)
    deltas = np.ones((16, 128), dtype=np.float32)
    out = scatter_add_sorted_rows(jnp.asarray(table), jnp.asarray(ids),
                                  jnp.asarray(deltas), interpret=True)
    expected = np.zeros((8, 128), dtype=np.float32)
    expected[1] = 16.0
    np.testing.assert_allclose(np.asarray(out), expected)

    ids = np.array([1] * 10 + [3] * 6, dtype=np.int32)
    out = scatter_add_sorted_rows(jnp.zeros((8, 128), dtype=jnp.float32),
                                  jnp.asarray(ids), jnp.asarray(deltas),
                                  interpret=True)
    expected = np.zeros((8, 128), dtype=np.float32)
    expected[1] = 10.0
    expected[3] = 6.0
    np.testing.assert_allclose(np.asarray(out), expected)


def test_scatter_add_long_runs_random():
    """Runs of random lengths (1..20) across several group boundaries."""
    rng = np.random.default_rng(7)
    table = rng.normal(size=(32, 128)).astype(np.float32)
    ids = np.sort(rng.integers(0, 32, size=67)).astype(np.int32)
    deltas = rng.normal(size=(67, 128)).astype(np.float32)
    out = scatter_add_sorted_rows(jnp.asarray(table), jnp.asarray(ids),
                                  jnp.asarray(deltas), interpret=True)
    expected = table.copy()
    np.add.at(expected, ids, deltas)
    np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-4,
                               atol=1e-5)


def test_scatter_add_unsorted_wrapper():
    rng = np.random.default_rng(1)
    table = rng.normal(size=(32, 128)).astype(np.float32)
    ids = np.array([9, 2, 9, 31, 0, 2], dtype=np.int32)
    deltas = rng.normal(size=(6, 128)).astype(np.float32)
    out = scatter_add_rows(jnp.asarray(table), jnp.asarray(ids),
                           jnp.asarray(deltas), interpret=True)
    expected = table.copy()
    np.add.at(expected, ids, deltas)
    np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-5)


def test_pallas_table_path(monkeypatch):
    """MatrixTable with use_pallas=True routes row ops through the Mosaic
    kernels (interpret mode on CPU) with identical semantics. Eligibility
    needs a single shard: restrict the mesh to one device."""
    import multiverso_tpu as mv

    mv.init([], devices=jax.devices()[:1])
    try:
        t = mv.create_table(mv.MatrixTableOption(num_row=64, num_col=128,
                                                 use_pallas=True))
        assert t.store._pallas_rows
        rows = [3, 9, 3, 63]
        deltas = np.stack([np.full(128, float(i + 1), dtype=np.float32)
                           for i in range(4)])
        t.add_rows(rows, deltas)
        expected = np.zeros((64, 128), dtype=np.float32)
        np.add.at(expected, rows, deltas)
        np.testing.assert_allclose(t.get_rows([3, 9, 63]),
                                   expected[[3, 9, 63]], rtol=1e-6)
        np.testing.assert_allclose(t.get(), expected, rtol=1e-6)
    finally:
        mv.shutdown()


def test_pallas_flag_ignored_when_ineligible():
    """Sharded tables (8 devices) silently fall back to the XLA path."""
    import multiverso_tpu as mv

    mv.init([])
    try:
        t = mv.create_table(mv.MatrixTableOption(num_row=64, num_col=128,
                                                 use_pallas=True))
        assert not t.store._pallas_rows   # 8 shards -> ineligible
        t.add_rows([5], np.ones((1, 128), dtype=np.float32))
        np.testing.assert_allclose(t.get_row(5), np.ones(128))
    finally:
        mv.shutdown()


# -- round 2: widened eligibility (bf16 tiles, SGD sign) --------------------
def test_scatter_add_sgd_sign():
    """Interpret-mode note: bf16 kernels pass here but are REJECTED by
    Mosaic on real chips (2-byte HBM tiling packs 2 rows/sublane; 1-row DMA
    slices misalign), so table eligibility stays f32-only."""
    import jax.numpy as jnp
    from multiverso_tpu.ops.pallas_rows import (gather_rows,
                                                group_for_dtype,
                                                scatter_add_rows)

    assert group_for_dtype(np.float32) == 8
    assert group_for_dtype(jnp.bfloat16) == 16

    rng = np.random.default_rng(0)
    for dtype in (np.float32,):
        table = jnp.asarray(rng.normal(size=(64, 128)), dtype=dtype)
        ids = jnp.asarray(np.sort(rng.integers(0, 64, size=40))
                          .astype(np.int32))
        deltas = jnp.asarray(rng.normal(size=(40, 128)), dtype=dtype)
        ref = np.array(table, dtype=np.float32)   # writable copy
        np.add.at(ref, np.asarray(ids), np.asarray(deltas,
                                                   dtype=np.float32))
        got = scatter_add_rows(table, ids, deltas, interpret=True)
        np.testing.assert_allclose(np.asarray(got, dtype=np.float32), ref,
                                   rtol=2e-2, atol=2e-2)
        back = gather_rows(got, ids, interpret=True)
        np.testing.assert_allclose(np.asarray(back, dtype=np.float32),
                                   ref[np.asarray(ids)], rtol=2e-2,
                                   atol=2e-2)
    # SGD sign: data -= delta
    table = jnp.zeros((16, 128), jnp.float32)
    ids = jnp.asarray([2, 2, 5], dtype=jnp.int32)
    deltas = jnp.ones((3, 128), jnp.float32)
    got = scatter_add_rows(table, ids, deltas, interpret=True, sign=-1.0)
    assert np.allclose(np.asarray(got)[2], -2.0)
    assert np.allclose(np.asarray(got)[5], -1.0)


def test_table_pallas_eligibility_widened():
    """SGD tables route through the Pallas row path (single shard,
    sign-flipped scatter); bf16 stays on XLA; stateful updaters named by
    the capability registry (adagrad) get the FUSED gather-update-scatter
    kernel; unregistered stateful updaters (dcasgd) stay on XLA."""
    import multiverso_tpu as mv
    from multiverso_tpu.core.options import AddOption
    from multiverso_tpu.core.table import ServerStore
    from multiverso_tpu.core.updater import get_updater
    from multiverso_tpu.core.zoo import Zoo

    mv.init([], devices=jax.devices()[:1])   # single shard for eligibility
    try:
        mesh = Zoo.get().mesh
        st = ServerStore("p1", (32, 128), np.float32,
                         get_updater(np.float32, "sgd"), mesh, 1,
                         use_pallas_rows=True)
        assert st._pallas_rows and st._pallas_cap == "scatter_sub"
        st_bf = ServerStore("p2", (32, 128), jnp.bfloat16,
                            get_updater(np.dtype(jnp.bfloat16), "default"),
                            mesh, 1, use_pallas_rows=True)
        assert not st_bf._pallas_rows   # bf16: Mosaic 1-row DMA misaligned
        st_ada = ServerStore("p3", (32, 128), np.float32,
                             get_updater(np.float32, "adagrad"), mesh, 1,
                             use_pallas_rows=True)
        assert st_ada._pallas_rows and st_ada._pallas_cap == "fused_stateful"
        st_dc = ServerStore("p4", (32, 128), np.float32,
                            get_updater(np.float32, "dcasgd"), mesh, 1,
                            use_pallas_rows=True)
        assert not st_dc._pallas_rows   # not in the capability registry
        st_50 = ServerStore("p5", (32, 50), np.float32,
                            get_updater(np.float32, "default"), mesh, 1,
                            use_pallas_rows=True)
        assert not st_50._pallas_rows   # 50 cols: Mosaic lane misaligned
        # behavior: sgd table applies data -= delta through the kernel
        ids = jnp.asarray([1, 1, 3], dtype=jnp.int32)
        st.apply_rows(ids, jnp.ones((3, 128), jnp.float32), AddOption())
        out = np.asarray(st.read_rows(jnp.asarray([1, 3],
                                                  dtype=jnp.int32)))
        assert np.allclose(out[0], -2.0) and np.allclose(out[1], -1.0)
    finally:
        mv.shutdown()


def test_tiled_scatter_matches_numpy_random():
    """Tiled table-sweep scatter: random duplicated ids vs np.add.at."""
    from multiverso_tpu.ops.pallas_rows import tiled_scatter_add_rows
    rng = np.random.default_rng(0)
    table = rng.normal(size=(1000, 128)).astype(np.float32)
    ids = rng.integers(0, 1000, size=512).astype(np.int32)
    deltas = rng.normal(size=(512, 128)).astype(np.float32)
    want = table.copy()
    np.add.at(want, ids, deltas)
    got = tiled_scatter_add_rows(jnp.asarray(table), jnp.asarray(ids),
                                 jnp.asarray(deltas), interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_tiled_scatter_nonmultiple_rows_and_tile_edges():
    """Row count not a multiple of the tile + ids clustered at tile
    boundaries (start/end searchsorted correctness)."""
    from multiverso_tpu.ops.pallas_rows import tiled_scatter_add_rows
    rng = np.random.default_rng(1)
    table = rng.normal(size=(777, 128)).astype(np.float32)
    # hit first/last rows of tiles plus heavy duplication
    ids = np.asarray([0, 255, 255, 256, 511, 512, 512, 512, 776, 776],
                     dtype=np.int32)
    deltas = rng.normal(size=(len(ids), 128)).astype(np.float32)
    want = table.copy()
    np.add.at(want, ids, deltas)
    got = tiled_scatter_add_rows(jnp.asarray(table), jnp.asarray(ids),
                                 jnp.asarray(deltas), interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_tiled_scatter_sgd_sign_and_eligibility():
    from multiverso_tpu.ops.pallas_rows import (tiled_scatter_add_rows,
                                                tiled_scatter_eligible)
    rng = np.random.default_rng(2)
    table = np.zeros((300, 8), dtype=np.float32)
    ids = np.asarray([3, 3, 299], dtype=np.int32)
    deltas = np.ones((3, 8), dtype=np.float32)
    got = tiled_scatter_add_rows(jnp.asarray(table), jnp.asarray(ids),
                                 jnp.asarray(deltas), interpret=True,
                                 sign=-1.0)
    want = np.zeros_like(table)
    np.add.at(want, ids, -deltas)
    np.testing.assert_allclose(np.asarray(got), want)
    assert tiled_scatter_eligible(8192, 128, np.float32)
    assert not tiled_scatter_eligible(100_000, 128, np.float32)


# ---------------------------------------------------------------------------
# fused stateful gather-update-scatter (ISSUE 12): interpret-mode BITWISE
# vs the XLA update path — both planes run the updater's shared rows_math,
# so equality here proves the kernel's gather/scatter plumbing.
# ---------------------------------------------------------------------------
@contextlib.contextmanager
def _on_the_xla_plane():
    """Tables made inside keep their row updates on the XLA plane: a
    one-shard 128-column stateful table picks the fused kernel by itself
    (core/table.fused_rows_selected, ISSUE 29), and no option says
    otherwise, so the reference is steered here, in the test."""
    from multiverso_tpu.core import table as table_mod
    chosen = table_mod.fused_rows_selected
    table_mod.fused_rows_selected = lambda *a, **k: False
    try:
        yield
    finally:
        table_mod.fused_rows_selected = chosen


@pytest.mark.parametrize("updater", ["momentum_sgd", "adagrad", "ftrl"])
def test_fused_stateful_bitwise_vs_xla(updater):
    import multiverso_tpu as mv

    mv.init([], devices=jax.devices()[:1])
    try:
        with _on_the_xla_plane():
            t_xla = mv.create_table(mv.MatrixTableOption(33, 128,
                                                         updater=updater,
                                                         name="fx"))
        assert t_xla.store.row_plane == "xla"
        t_pal = mv.create_table(mv.MatrixTableOption(33, 128,
                                                     updater=updater,
                                                     name="fp",
                                                     use_pallas=True))
        assert t_pal.store._pallas_cap == "fused_stateful"
        rng = np.random.default_rng(3)
        opt = mv.AddOption(worker_id=0, momentum=0.9, learning_rate=0.05,
                           rho=0.1, lambda_=0.01)
        for step in range(5):
            n = int(rng.integers(1, 24))
            ids = rng.integers(0, 33, size=n).astype(np.int32)
            d = rng.normal(size=(n, 128)).astype(np.float32)
            t_xla.add_rows(ids, d, opt)
            t_pal.add_rows(ids, d, opt)
        assert np.array_equal(t_xla.get(), t_pal.get()), updater
        for k in t_xla.store.state:
            assert np.array_equal(np.asarray(t_xla.store.state[k]),
                                  np.asarray(t_pal.store.state[k])), \
                (updater, k)
    finally:
        mv.shutdown()


def test_fused_stateful_duplicates_and_empty():
    """Duplicate ids in one add fold (combine semantics, like the XLA
    path); an empty add is a no-op; heavy duplication across group
    boundaries stays exact."""
    import multiverso_tpu as mv

    mv.init([], devices=jax.devices()[:1])
    try:
        with _on_the_xla_plane():
            t_xla = mv.create_table(mv.MatrixTableOption(8, 128,
                                                         updater="adagrad",
                                                         name="dx"))
        assert t_xla.store.row_plane == "xla"
        t_pal = mv.create_table(mv.MatrixTableOption(8, 128,
                                                     updater="adagrad",
                                                     name="dp",
                                                     use_pallas=True))
        assert t_pal.store._pallas_cap == "fused_stateful"
        opt = mv.AddOption(learning_rate=0.1, rho=0.1)
        # 11 ids over 3 rows: duplicates straddle the 8-lane group
        ids = np.array([2, 2, 2, 6, 6, 1, 1, 1, 1, 2, 6], dtype=np.int32)
        d = np.ones((11, 128), dtype=np.float32)
        t_xla.add_rows(ids, d, opt)
        t_pal.add_rows(ids, d, opt)
        t_pal.add_rows([], np.zeros((0, 128), np.float32), opt)  # no-op
        assert np.array_equal(t_xla.get(), t_pal.get())
        assert np.array_equal(np.asarray(t_xla.store.state["g2"]),
                              np.asarray(t_pal.store.state["g2"]))
    finally:
        mv.shutdown()


def test_fused_stateful_per_worker_state_indexing():
    """AdaGrad's [num_workers, ...] g2: the kernel must address worker w's
    accumulator plane, not worker 0's."""
    import multiverso_tpu as mv

    mv.init([], num_local_workers=2, devices=jax.devices()[:1])
    try:
        with _on_the_xla_plane():
            t_xla = mv.create_table(mv.MatrixTableOption(16, 128,
                                                         updater="adagrad",
                                                         name="wx"))
        assert t_xla.store.row_plane == "xla"
        t_pal = mv.create_table(mv.MatrixTableOption(16, 128,
                                                     updater="adagrad",
                                                     name="wp",
                                                     use_pallas=True))
        assert t_pal.store._pallas_cap == "fused_stateful"
        rng = np.random.default_rng(5)
        for step in range(4):
            w = step % 2
            opt = mv.AddOption(worker_id=w, learning_rate=0.1, rho=0.1)
            ids = rng.integers(0, 16, size=6).astype(np.int32)
            d = rng.normal(size=(6, 128)).astype(np.float32)
            t_xla.add_rows(ids, d, opt)
            t_pal.add_rows(ids, d, opt)
        assert np.array_equal(t_xla.get(), t_pal.get())
        g2x = np.asarray(t_xla.store.state["g2"])
        g2p = np.asarray(t_pal.store.state["g2"])
        assert g2x.shape[0] == 2 and np.array_equal(g2x, g2p)
        assert np.abs(g2x[0] - g2x[1]).max() > 0   # both planes really used
    finally:
        mv.shutdown()


# -- a block of unique rows added into a wide accumulator (ISSUE 41) ----------
def _unique_case(rows, cols, ids, seed=0):
    from multiverso_tpu.ops.pallas_rows import add_unique_rows
    rng = np.random.default_rng(seed)
    acc = jnp.asarray(rng.standard_normal((rows, cols), dtype=np.float32))
    ids = jnp.asarray(ids, jnp.int32)
    a = jnp.asarray(rng.standard_normal((len(ids), cols), dtype=np.float32))
    g = jnp.asarray(rng.standard_normal(len(ids), dtype=np.float32))
    planes = (cols // 128, 128)

    # the values come out of a multiply, as a block's gated result does: the
    # kernel's add must not contract with it
    @jax.jit
    def xla(acc, a, g):
        return acc.at[ids].add(a * g[:, None], mode="drop")

    @jax.jit
    def kernel(acc, a, g):
        return add_unique_rows(
            acc.reshape((rows,) + planes), ids,
            (a * g[:, None]).reshape((-1,) + planes),
            interpret=True).reshape(rows, cols)

    return acc, xla(acc, a, g), kernel(acc, a, g)


@pytest.mark.parametrize("cols", [128, 256, 2688])
@pytest.mark.parametrize("order", ["random", "descending"])
def test_add_unique_rows_bitwise_vs_xla_scatter_add(cols, order):
    """Unique ids in any order, the out-of-range ones (the layout's empty
    slots) at the tail and dropped; 150 ids are two whole groups of 64 and
    a padded third, so the in-flight slots are reused."""
    rows = 200
    ids = np.random.default_rng(cols).permutation(rows)[:150]
    if order == "descending":
        ids = np.sort(ids)[::-1].copy()
    ids[-9:] = rows + np.arange(9) % 2       # `rows`, `rows + 1`, ...
    acc, want, got = _unique_case(rows, cols, ids)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
    assert not np.array_equal(np.asarray(acc), np.asarray(got))


@pytest.mark.parametrize("rows,n", [(200, 64), (5, 7), (64, 0)])
def test_add_unique_rows_all_empty_block_changes_nothing(rows, n):
    """Every id out of range (a block of empty slots), an accumulator
    smaller than a group, no ids at all: the accumulator comes back as it
    went in."""
    acc, want, got = _unique_case(rows, 256, np.full(n, rows))
    np.testing.assert_array_equal(np.asarray(acc), np.asarray(got))
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


def test_add_unique_rows_in_a_loop_carry_over_several_blocks():
    """As the expert layer drives it: the accumulator a ``fori_loop``'s
    carry, a block of ids a trip, a token in several blocks (once each)."""
    from multiverso_tpu.ops.pallas_rows import add_unique_rows
    rows, cols, blocks, block = 96, 256, 5, 24
    rng = np.random.default_rng(3)
    ids = np.stack([rng.permutation(rows + 8)[:block] for _ in range(blocks)])
    ids = jnp.asarray(np.minimum(ids, rows), jnp.int32)   # some empty slots
    vals = jnp.asarray(rng.standard_normal((blocks, block, cols),
                                           dtype=np.float32))

    @jax.jit
    def xla(ids, vals):
        return jax.lax.fori_loop(
            0, blocks, lambda b, acc: acc.at[ids[b]].add(vals[b],
                                                         mode="drop"),
            jnp.zeros((rows, cols), jnp.float32))

    @jax.jit
    def kernel(ids, vals):
        return jax.lax.fori_loop(
            0, blocks, lambda b, acc: add_unique_rows(
                acc, ids[b], vals[b].reshape(block, cols // 128, 128),
                interpret=True),
            jnp.zeros((rows, cols // 128, 128), jnp.float32)
        ).reshape(rows, cols)

    np.testing.assert_array_equal(np.asarray(xla(ids, vals)),
                                  np.asarray(kernel(ids, vals)))
