"""The row plane a store picks by itself, its XLA plane against NumPy on one
device, and the Pallas row kernels against the XLA plane (interpret mode on
CPU)."""

import contextlib

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402


# -- which plane serves a store's row updates ---------------------------------
# (updater, columns, dtype, mesh arguments, one device) -> row_plane. The
# store decides from what it shows (core/table.fused_rows_selected); no
# option says otherwise.
def _overriding_subclass():
    from multiverso_tpu.core.updater import AdaGradUpdater

    class Louder(AdaGradUpdater):
        # inherits the name, the state and ``fused_rows = True`` by
        # attribute look-up; the claim was made for the parent's math
        def rows_math(self, d_rows, state_rows, delta, opt):
            return super().rows_math(d_rows, state_rows, 2.0 * delta, opt)
    return Louder()


_ONE = dict(args=[], one=True)
ROW_PLANES = {
    "default_128": ("default", 128, np.float32, _ONE, "xla"),
    "sgd_128": ("sgd", 128, np.float32, _ONE, "xla"),
    "momentum_sgd_128": ("momentum_sgd", 128, np.float32, _ONE,
                         "fused_stateful"),
    "adagrad_128": ("adagrad", 128, np.float32, _ONE, "fused_stateful"),
    "ftrl_128": ("ftrl", 128, np.float32, _ONE, "fused_stateful"),
    # the DC-ASGD family does not claim the kernel (Updater.fused_rows)
    "dcasgd_128": ("dcasgd", 128, np.float32, _ONE, "xla"),
    "dcasgda_128": ("dcasgda", 128, np.float32, _ONE, "xla"),
    # Mosaic's one-row DMA slices: float32 at exactly 128 columns only
    "adagrad_bfloat16": ("adagrad", 128, "bfloat16", _ONE, "xla"),
    "adagrad_50_columns": ("adagrad", 50, np.float32, _ONE, "xla"),
    "adagrad_256_columns": ("adagrad", 256, np.float32, _ONE, "xla"),
    "adagrad_eight_shards": ("adagrad", 128, np.float32,
                             dict(args=[], one=False), "xla"),
    "adagrad_sharded_state": (
        "adagrad", 128, np.float32,
        dict(args=["-mesh_shape=server:1,worker:2", "-state_sharding=on"],
             one=False), "xla"),
    # the guard: a subclass that overrides the math does not inherit the
    # parent's claim
    "adagrad_subclass_other_math": (_overriding_subclass, 128, np.float32,
                                    _ONE, "xla"),
}


@pytest.mark.parametrize("case", sorted(ROW_PLANES))
def test_the_store_picks_its_row_plane_from_what_it_shows(case):
    import multiverso_tpu as mv
    from multiverso_tpu.core.table import ServerStore
    from multiverso_tpu.core.updater import get_updater
    from multiverso_tpu.core.zoo import Zoo

    updater, cols, dtype, place, want = ROW_PLANES[case]
    dtype = np.dtype(dtype)
    mv.init(place["args"], devices=jax.devices()[:1] if place["one"]
            else None)
    try:
        made = updater() if callable(updater) else get_updater(dtype,
                                                               updater)
        store = ServerStore(case, (64, cols), dtype, made, Zoo.get().mesh, 1)
        assert store.state_sharded == (case == "adagrad_sharded_state")
        assert store.row_plane == want
        # whichever plane: an add lands, and a read finds it
        opt = mv.AddOption(learning_rate=0.1, rho=0.1, momentum=0.5,
                           lambda_=0.01)
        ids = jnp.asarray([5, 9, 5], jnp.int32)
        store.apply_rows(ids, jnp.ones((3, cols), jnp.float32), opt)
        got = np.asarray(store.read_rows(jnp.asarray([5, 9, 0], jnp.int32)),
                         np.float32)
        assert np.all(got[:2] != 0) and np.all(got[2] == 0)
    finally:
        mv.shutdown()


# -- the XLA plane of the stateless updaters, one device, 128 columns ---------
# (what the stateless row kernels served behind an option until PR 43)
STATELESS_IDS = {
    "unique": [1, 4, 9],
    "duplicates": [2, 2, 2, 5],
    "one_long_run": [1] * 10 + [3] * 6,
    "unsorted": [9, 2, 9, 31, 0, 2],
    "long_random": np.random.default_rng(7).integers(0, 32, size=67),
}


@pytest.mark.parametrize("updater,sign", [("default", 1.0), ("sgd", -1.0)])
@pytest.mark.parametrize("case", sorted(STATELESS_IDS))
def test_stateless_store_rows_on_one_device_equal_numpy(case, updater,
                                                        sign):
    """``ServerStore.apply_rows`` / ``read_rows``: every occurrence of an id
    adds (``sgd``: subtracts, the client pre-scales by the rate), in any
    order; a read answers in the order asked, repeats and all."""
    import multiverso_tpu as mv
    from multiverso_tpu.core.table import ServerStore
    from multiverso_tpu.core.updater import get_updater
    from multiverso_tpu.core.zoo import Zoo

    rng = np.random.default_rng(3)
    start = rng.normal(size=(32, 128)).astype(np.float32)
    ids = np.asarray(STATELESS_IDS[case], np.int32)
    deltas = rng.normal(size=(len(ids), 128)).astype(np.float32)
    mv.init([], devices=jax.devices()[:1])
    try:
        store = ServerStore("s", (32, 128), np.float32,
                            get_updater(np.float32, updater),
                            Zoo.get().mesh, 1, init_array=start)
        assert store.row_plane == "xla"
        store.apply_rows(jnp.asarray(ids), jnp.asarray(deltas),
                         mv.AddOption())
        want = start.copy()
        np.add.at(want, ids, sign * deltas)
        np.testing.assert_allclose(np.asarray(store.read()), want,
                                   rtol=1e-5, atol=1e-5)
        ask = np.array([3, 0, 31, 3, 17, 2], np.int32)
        np.testing.assert_array_equal(
            np.asarray(store.read_rows(jnp.asarray(ask))),
            np.asarray(store.read())[ask])
    finally:
        mv.shutdown()


def test_the_removed_row_plane_option_is_an_unknown_keyword():
    """PR 43 took ``use_pallas`` away: the store chooses. It fails as any
    unknown keyword does, on the option and on the store."""
    import multiverso_tpu as mv
    from multiverso_tpu.core.table import ServerStore
    with pytest.raises(TypeError):
        mv.MatrixTableOption(num_row=8, num_col=128, use_pallas=True)
    with pytest.raises(TypeError):
        ServerStore("s", (8, 128), np.float32, None, None, 1,
                    use_pallas_rows=True)


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
                                                     name="fp"))
        assert t_pal.store.row_plane == "fused_stateful"
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
                                                     name="dp"))
        assert t_pal.store.row_plane == "fused_stateful"
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
                                                     name="wp"))
        assert t_pal.store.row_plane == "fused_stateful"
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
