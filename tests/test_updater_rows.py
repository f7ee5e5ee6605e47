"""The stateful row update's contract (ISSUE 29, core/updater.py).

``combine_duplicate_rows`` hands the write-back ids that are sorted, unique
and compacted to the front, and the fused row kernel
(``ops/pallas_rows.fused_stateful_rows``) skips every group of lanes behind
the live prefix and writes its lanes unordered on the strength of it. So
the invariant is pinned here; each stateful updater is held to a sequential
NumPy reference per table, through a ``TableGroup``, on the 8-device CPU
mesh with sharded state and on the fused plane; and the plane a store picks
from its shape is pinned with the counter that says which ran. CPU: values
and counts only."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import multiverso_tpu as mv
from multiverso_tpu.core.options import AddOption, MatrixTableOption
from multiverso_tpu.core.updater import combine_duplicate_rows
from multiverso_tpu.models.dlrm.stream import zipf_ids

# -- the fold's contract ------------------------------------------------------
FOLD_CASES = {
    "all_one_id": (lambda rng: np.full(16, 5, np.int32), 32),
    "all_distinct": (lambda rng: rng.permutation(32)[:16].astype(np.int32),
                     32),
    "n_is_1": (lambda rng: np.asarray([7], np.int32), 32),
    "ids_at_the_last_row": (
        lambda rng: np.asarray([31, 3, 31, 31, 0, 3], np.int32), 32),
    "zipf_2048_of_262144": (lambda rng: zipf_ids(rng, 1.2, 2048, 262144),
                            262144),
    "out_of_range_ids_are_dropped": (
        lambda rng: np.asarray([40, 3, -1, 3, 32, 9, 40, 33], np.int32), 32),
}


@pytest.mark.parametrize("case", sorted(FOLD_CASES))
def test_fold_returns_sorted_unique_compacted_ids_and_their_totals(case):
    make, num_rows = FOLD_CASES[case]
    rng = np.random.default_rng(29)
    rows = make(rng)
    n, cols = len(rows), 8
    delta = rng.normal(size=(n, cols)).astype(np.float32)
    ids, totals = jax.jit(combine_duplicate_rows, static_argnums=2)(
        jnp.asarray(rows), jnp.asarray(delta), num_rows)
    ids, totals = np.asarray(ids), np.asarray(totals)
    assert ids.shape == rows.shape and totals.shape == delta.shape
    assert np.all(np.diff(ids.astype(np.int64)) > 0), "strictly ascending"
    in_range = (rows >= 0) & (rows < num_rows)
    want = np.unique(rows[in_range])
    k = len(want)
    assert np.array_equal(ids[:k], want)
    assert np.all(ids[k:] >= num_rows)
    fold = np.zeros((k, cols), np.float64)
    np.add.at(fold, np.searchsorted(want, rows[in_range]), delta[in_range])
    np.testing.assert_allclose(totals[:k], fold, rtol=1e-5, atol=1e-6)
    if in_range.all():
        assert not totals[k:].any()


def test_fold_of_nothing_is_nothing():
    rows = jnp.zeros((0,), jnp.int32)
    delta = jnp.zeros((0, 8), jnp.float32)
    ids, totals = combine_duplicate_rows(rows, delta, 32)
    assert ids.shape == (0,) and totals.shape == (0, 8)


# -- the four stateful updaters against a sequential reference ---------------
ROWS, COLS, WORKERS = 64, 8, 1
OPTION = AddOption(worker_id=0, momentum=0.5, learning_rate=0.1, rho=0.1,
                   lambda_=0.01)
PLACEMENTS = {
    "one_device": dict(args=[], one=True),
    "server8": dict(args=[], one=False),
    "server2xworker4_state_sharded": dict(
        args=["-mesh_shape=server:2,worker:4", "-state_sharding=on"],
        one=False),
}


def _reference_step(updater, w, state, d, o):
    """One row's update in float32 NumPy, the updater's own formulas
    (core/updater.py) written out; ``d`` is the row's folded delta."""
    f = np.float32
    if updater == "momentum_sgd":
        state["smooth"] = f(o.momentum) * state["smooth"] + \
            f(1 - o.momentum) * d
        return w - state["smooth"]
    if updater == "adagrad":
        g = d / f(o.learning_rate)
        state["g2"] = state["g2"] + g * g
        return w - f(o.rho) / np.sqrt(state["g2"] + f(1e-6)) * g
    if updater == "dcasgd":
        new = w - f(o.learning_rate) * (
            d + f(o.lambda_) * d * d * (w - state["backup"]))
        state["backup"] = new
        return new
    assert updater == "ftrl"
    l2, alpha, beta, l1 = (f(o.momentum), f(o.learning_rate), f(o.rho),
                           f(o.lambda_))
    n_new = state["n"] + d * d
    sigma = (np.sqrt(n_new) - np.sqrt(state["n"])) / alpha
    z_new = state["z"] + d - sigma * w
    state["z"], state["n"] = z_new, n_new
    return np.where(np.abs(z_new) > l1,
                    -(z_new - np.sign(z_new) * l1) /
                    ((beta + np.sqrt(n_new)) / alpha + l2),
                    f(0)).astype(np.float32)


def _reference(updater, data, pushes):
    """Rows one at a time, each push's duplicates folded first (in id
    order, as they arrive)."""
    leaves = {"momentum_sgd": ["smooth"], "adagrad": ["g2"],
              "dcasgd": ["backup"], "ftrl": ["z", "n"]}[updater]
    data = data.copy()
    state = {k: np.zeros_like(data) for k in leaves}
    for ids, delta in pushes:
        for r in np.unique(ids):
            d = np.zeros(data.shape[1], np.float32)
            for x in delta[ids == r]:
                d = d + x
            row_state = {k: v[r] for k, v in state.items()}
            data[r] = _reference_step(updater, data[r], row_state, d, OPTION)
            for k in state:
                state[k][r] = row_state[k]
    return data, state


def _state_leaf(table, key):
    leaf = np.asarray(table.store.state[key])
    return leaf[0] if leaf.ndim == 3 else leaf   # per-worker: worker 0


@pytest.mark.parametrize("route", ["per_table", "table_group"])
@pytest.mark.parametrize("placement", sorted(PLACEMENTS))
@pytest.mark.parametrize("updater",
                         ["momentum_sgd", "adagrad", "dcasgd", "ftrl"])
def test_stateful_row_update_equals_the_sequential_reference(
        updater, placement, route):
    place = PLACEMENTS[placement]
    mv.init(place["args"], devices=jax.devices()[:1] if place["one"]
            else None)
    try:
        tables = [mv.create_table(MatrixTableOption(
            num_row=ROWS, num_col=COLS, random_init=True, seed=3 + i,
            updater=updater, name=f"t{i}")) for i in range(2)]
        sharded = placement.endswith("state_sharded")
        assert all(t.store.state_sharded == sharded for t in tables)
        start = [t.get().copy() for t in tables]
        rng = np.random.default_rng(5)
        pushes = []
        for _ in range(3):   # duplicates in every push, the last row too
            ids = np.concatenate([rng.integers(0, ROWS // 4, (22, 2)),
                                  np.full((2, 2), ROWS - 1)]).astype(np.int32)
            pushes.append((ids, rng.normal(size=(24, 2, COLS))
                           .astype(np.float32) * 0.1))
        group = mv.create_table_group(tables)
        for ids, delta in pushes:
            if route == "table_group":
                group.add_rows(ids, delta, OPTION)
            else:
                for i, t in enumerate(tables):
                    t.add_rows(ids[:, i], delta[:, i], OPTION)
        for i, t in enumerate(tables):
            want, want_state = _reference(
                updater, start[i], [(ids[:, i], d[:, i]) for ids, d in pushes])
            np.testing.assert_allclose(t.get(), want, rtol=2e-5, atol=1e-6)
            assert sorted(t.store.state) == sorted(want_state)
            for key, leaf in want_state.items():
                np.testing.assert_allclose(_state_leaf(t, key), leaf,
                                           rtol=2e-5, atol=1e-6, err_msg=key)
    finally:
        mv.shutdown()


# -- the row plane a store picks, and the counter that says which ran --------
def _selected(updater, cols, dtype=np.float32, one_shard=True,
              state_sharded=False):
    from multiverso_tpu.core.table import fused_rows_selected
    from multiverso_tpu.core.updater import get_updater
    return fused_rows_selected(get_updater(np.dtype(dtype), updater),
                               (64, cols), dtype, one_shard, state_sharded)


# Mosaic refuses a one-row slice of any width but 128 (compiled for the
# v5e, ISSUE 29): the LM's 2,688-column embedding stays on XLA.
SELECTION = {
    "adagrad_128": (dict(updater="adagrad", cols=128), True),
    "momentum_128": (dict(updater="momentum_sgd", cols=128), True),
    "ftrl_128": (dict(updater="ftrl", cols=128), True),
    "cols_256": (dict(updater="adagrad", cols=256), False),
    "cols_2688": (dict(updater="adagrad", cols=2688), False),
    "cols_50": (dict(updater="adagrad", cols=50), False),
    "bfloat16": (dict(updater="adagrad", cols=128, dtype=jnp.bfloat16),
                 False),
    "several_shards": (dict(updater="adagrad", cols=128, one_shard=False),
                       False),
    "sharded_state": (dict(updater="adagrad", cols=128, state_sharded=True),
                      False),
    "dcasgd_no_fused_capability": (dict(updater="dcasgd", cols=128), False),
    "sgd_stateless": (dict(updater="sgd", cols=128), False),
}


@pytest.mark.parametrize("name", sorted(SELECTION))
def test_the_fused_row_update_is_picked_from_what_the_table_shows(name):
    case, want = SELECTION[name]
    assert _selected(**case) == want


@pytest.mark.parametrize("placement,cols,plane", [
    ("one_device", 128, "fused_stateful"), ("one_device", 8, "xla"),
    ("server8", 128, "xla")])
def test_a_grouped_push_counts_the_row_plane_that_ran(placement, cols,
                                                      plane):
    """No option picks the plane, so the counter is how a run says which
    one served it: ``table.rows.plane.<plane>``, once a grouped push."""
    from multiverso_tpu.telemetry.metrics import get_registry
    mv.init([], devices=jax.devices()[:1] if placement == "one_device"
            else None)
    try:
        tables = [mv.create_table(MatrixTableOption(
            num_row=ROWS, num_col=cols, updater="adagrad", name=f"c{i}"))
            for i in range(2)]
        assert all(t.store.row_plane == plane for t in tables)
        group = mv.create_table_group(tables)
        rng = np.random.default_rng(7)
        ids = rng.integers(0, ROWS // 4, (24, 2)).astype(np.int32)
        delta = rng.normal(size=(24, 2, cols)).astype(np.float32) * 0.1
        for _ in range(2):
            group.add_rows(ids, delta, OPTION)
        counts = {p: get_registry().counter(f"table.rows.plane.{p}").value
                  for p in ("xla", "fused_stateful")}
        assert counts == {p: 2 * (p == plane) for p in counts}
    finally:
        mv.shutdown()


@pytest.mark.parametrize("updater", ["momentum_sgd", "adagrad", "ftrl"])
def test_the_plane_a_store_picks_equals_the_sequential_reference(updater):
    """One device, 128 columns: the fused kernel, picked by shape, through
    a TableGroup and its local twin; 70 ids a push, so a group of lanes
    holds live ids AND sentinels and the last groups hold sentinels only."""
    from multiverso_tpu.tables.table_group import LocalTableGroup
    mv.init([], devices=jax.devices()[:1])
    try:
        option = MatrixTableOption(num_row=ROWS, num_col=128,
                                   random_init=True, seed=9,
                                   updater=updater, name="f0")
        table = mv.create_table(option)
        assert table.store.row_plane == "fused_stateful"
        twin = LocalTableGroup([option])
        start = table.get().copy()
        assert np.array_equal(twin.local_rows(0), start)
        rng = np.random.default_rng(11)
        group = mv.create_table_group([table])
        pushes = []
        for _ in range(3):
            ids = np.concatenate([rng.integers(0, ROWS // 2, 68),
                                  [ROWS - 1, ROWS - 1]]).astype(np.int32)
            delta = rng.normal(size=(70, 128)).astype(np.float32) * 0.1
            pushes.append((ids, delta))
            group.add_rows([ids], [delta], OPTION)
            twin.add_rows([ids], [delta], OPTION)
        want, want_state = _reference(updater, start, pushes)
        np.testing.assert_allclose(table.get(), want, rtol=2e-5, atol=1e-6)
        assert np.array_equal(twin.local_rows(0), table.get())
        for key, leaf in want_state.items():
            np.testing.assert_allclose(_state_leaf(table, key), leaf,
                                       rtol=2e-5, atol=1e-6, err_msg=key)
    finally:
        mv.shutdown()
