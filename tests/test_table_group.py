"""Grouped row operations (ISSUE 25, tables/table_group.py): a
``TableGroup`` answers ``get_rows`` and ``add_rows`` for all its members in
one launch and one copy each way, and is bit for bit the per-table calls.
The device form (ISSUE 27) leaves the pulled rows on the device and takes
device deltas: the same bytes, no copy. CPU: bytes and counts only."""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import multiverso_tpu as mv
from multiverso_tpu.core.options import AddOption, MatrixTableOption
from multiverso_tpu.telemetry.metrics import get_registry
from multiverso_tpu.utils.log import FatalError

# Meshes: every device a server shard (the default), and a replica axis with
# the updater state sharded over it.
MESHES = {"server8": [],
          "server2xworker2_state_sharded": ["-mesh_shape=server:2,worker:2",
                                            "-state_sharding=on"]}
# Members as (rows, cols): one width (the [B, n] matrix layout) and members
# that differ in rows and width (one id vector a table).
SHAPES = {"equal": [(64, 8), (64, 8), (64, 8)],
          "ragged": [(64, 8), (32, 16), (128, 4)]}
OPTION = AddOption(worker_id=0, learning_rate=0.05, rho=0.05, momentum=0.9)


@pytest.fixture(params=sorted(MESHES))
def mesh_env(request):
    mv.init(MESHES[request.param])
    yield request.param
    mv.shutdown()


def _tables(shapes, updater, prefix, **kwargs):
    return [mv.create_table(MatrixTableOption(
        num_row=r, num_col=c, random_init=True, seed=11 + i, updater=updater,
        name=f"{prefix}{i}", **kwargs)) for i, (r, c) in enumerate(shapes)]


def _ids(shapes, kind, rng, batch=24):
    """Ids with duplicates in every batch: a [B, n] matrix, or vectors of
    different lengths."""
    if kind == "equal":
        return np.stack([rng.integers(0, r // 4, batch) for r, _ in shapes],
                        axis=1).astype(np.int32)
    return [rng.integers(0, r // 4, batch + 3 * i).astype(np.int32)
            for i, (r, _) in enumerate(shapes)]


def _columns(ids, n):
    return [ids[:, i] for i in range(n)] if isinstance(ids, np.ndarray) \
        else ids


def _state_np(table):
    return {k: np.asarray(v) for k, v in table.store.state.items()}


FORMS = ["host", "device"]
DEVICE_COUNTERS = ("table.group.device_pulls", "table.group.device_pushes")


def _device_calls():
    reg = get_registry()
    return [reg.counter(n).value for n in DEVICE_COUNTERS]


def _deltas(blocks, kind, form):
    """One push's deltas as the layout takes them, from the host or from
    the device."""
    if kind == "equal":
        deltas = np.stack(blocks, axis=1)
        return deltas if form == "host" else jnp.asarray(deltas)
    return blocks if form == "host" else [jnp.asarray(b) for b in blocks]


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("kind", sorted(SHAPES))
def test_grouped_get_equals_the_per_table_gets(mesh_env, kind, form):
    """Both forms of the pull, bit for bit; the device form hands out
    ``jax.Array``\\ s and counts itself."""
    shapes = SHAPES[kind]
    tables = _tables(shapes, "adagrad", "g")
    group = mv.create_table_group(tables)
    ids = _ids(shapes, kind, np.random.default_rng(0))
    before = _device_calls()
    got = group.get_rows(ids) if form == "host" else \
        group.get_rows_device(ids)
    assert _device_calls() == [before[0] + (form == "device"), before[1]]
    want = [t.get_rows(c) for t, c in zip(tables, _columns(ids, len(tables)))]
    if kind == "equal":
        assert got.shape == (24, 3, 8)
        got = [got[:, i] for i in range(3)]
    for g, w in zip(got, want):
        assert isinstance(g, jax.Array if form == "device" else np.ndarray)
        assert g.dtype == w.dtype and np.array_equal(g, w)


def test_id_matrix_over_unequal_widths_returns_one_block_a_table(mv_env):
    shapes = [(64, 8), (64, 16)]
    tables = _tables(shapes, "sgd", "w")
    ids = np.random.default_rng(1).integers(0, 64, (10, 2)).astype(np.int32)
    got = mv.create_table_group(tables).get_rows(ids)
    assert [g.shape for g in got] == [(10, 8), (10, 16)]
    for g, t, col in zip(got, tables, ids.T):
        assert np.array_equal(g, t.get_rows(col))


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("kind", sorted(SHAPES))
@pytest.mark.parametrize("updater", ["adagrad", "sgd", "momentum_sgd"])
def test_grouped_add_equals_the_per_table_adds(mesh_env, updater, kind,
                                               form):
    """Data AND state, after three adds with duplicate ids in every batch,
    the deltas handed over from the host or from the device."""
    shapes = SHAPES[kind]
    solo = _tables(shapes, updater, "solo")
    grouped = _tables(shapes, updater, "grp")
    group = mv.create_table_group(grouped)
    rng = np.random.default_rng(2)
    before = _device_calls()
    for _ in range(3):
        ids = _ids(shapes, kind, rng)
        cols = _columns(ids, len(shapes))
        deltas = [rng.normal(size=(len(c), w)).astype(np.float32)
                  for c, (_, w) in zip(cols, shapes)]
        for t, c, d in zip(solo, cols, deltas):
            t.add_rows(c, d, OPTION)
        group.add_rows(ids, _deltas(deltas, kind, form), OPTION)
    assert _device_calls() == [before[0],
                               before[1] + 3 * (form == "device")]
    for a, b in zip(solo, grouped):
        assert np.array_equal(a.get(), b.get())
        sa, sb = _state_np(a), _state_np(b)
        assert sa.keys() == sb.keys()
        for k in sa:
            assert np.array_equal(sa[k], sb[k]), k
        assert {k: v.sharding for k, v in a.store.state.items()} == \
            {k: v.sharding for k, v in b.store.state.items()}
        assert a.store.data.sharding == b.store.data.sharding


def test_a_get_after_an_add_sees_it(mesh_env):
    tables = _tables(SHAPES["equal"], "default", "ryw")
    group = mv.create_table_group(tables)
    ids = np.tile(np.arange(6, dtype=np.int32)[:, None], (1, 3))
    before = group.get_rows(ids)
    delta = np.full((6, 3, 8), 2.5, np.float32)
    group.add_rows(ids, delta)
    assert np.array_equal(group.get_rows(ids), before + delta)
    # and the per-table client reads the same bytes
    assert np.array_equal(tables[1].get_rows(ids[:, 1]),
                          (before + delta)[:, 1])


@pytest.mark.parametrize("kind", sorted(SHAPES))
def test_rows_pulled_on_the_device_are_a_snapshot(mesh_env, kind):
    """The snapshot contract against donation: rows pulled on the device
    BEFORE a push hold the pre-push values after it (the push donates every
    member's table), and pushed themselves they are what their host copy
    is."""
    shapes = SHAPES[kind]
    tables, twins = (_tables(shapes, "default", p) for p in ("snap", "twin"))
    group, twin = mv.create_table_group(tables), mv.create_table_group(twins)
    ids = _ids(shapes, kind, np.random.default_rng(4))
    pulled = group.get_rows_device(ids)
    before = group.get_rows(ids)
    group.add_rows(ids, pulled)
    twin.add_rows(ids, before)
    blocks = (lambda rows: [rows]) if kind == "equal" else list
    for kept, was, now in zip(blocks(pulled), blocks(before),
                              blocks(group.get_rows(ids))):
        assert np.array_equal(kept, was)
        assert not np.array_equal(now, was)
    for a, b in zip(tables, twins):
        assert np.array_equal(a.get(), b.get())


@pytest.mark.parametrize("swap", ["load_state", "write_dense"])
def test_a_store_swapped_between_calls_is_honoured(mv_env, swap):
    """Nothing is cached across calls: the next call reads the new buffer."""
    tables = _tables(SHAPES["equal"], "default", "swap")
    group = mv.create_table_group(tables)
    ids = np.tile(np.arange(8, dtype=np.int32)[:, None], (1, 3))
    group.get_rows(ids)
    fresh = np.full((64, 8), 7.0, np.float32)
    if swap == "load_state":
        tables[1].store.load_state({"data": fresh})
    else:
        tables[1].store.write_dense(fresh)
    got = group.get_rows(ids)
    assert np.array_equal(got[:, 1], fresh[:8])
    group.add_rows(ids, np.ones((8, 3, 8), np.float32))
    assert np.array_equal(tables[1].get_rows(ids[:, 1]), fresh[:8] + 1)


def test_a_reader_during_grouped_adds_never_meets_a_donated_buffer(mv_env):
    """The serving plane reads a member through ``read_rows_with`` from
    another thread while the group donates it: every read is a whole,
    live snapshot (all rows of one add count)."""
    tables = _tables(SHAPES["equal"], "default", "rd")
    for t in tables:
        t.store.write_dense(np.zeros((64, 8), np.float32))
    group = mv.create_table_group(tables)
    runner = tables[2].serving_runner()
    keys = np.arange(16, dtype=np.int32)
    ids = np.tile(keys[:, None], (1, 3))
    ones = np.ones((16, 3, 8), np.float32)
    rounds, errors, seen = 60, [], []
    done = threading.Event()

    def read():
        try:
            while not done.is_set():
                rows = runner.slice_result(
                    runner.run(keys[None, :], np.array([16])), 0, 16)
                seen.append(np.unique(np.asarray(rows)))
        except Exception as e:      # noqa: BLE001 - reported by the assert
            errors.append(repr(e))

    reader = threading.Thread(target=read)
    reader.start()
    try:
        for _ in range(rounds):
            group.add_rows(ids, ones)
    finally:
        done.set()
        reader.join(timeout=60)
    assert not reader.is_alive() and not errors, errors
    assert seen and all(len(u) == 1 for u in seen)
    assert 0 <= min(u[0] for u in seen) <= max(u[0] for u in seen) <= rounds
    assert np.array_equal(group.get_rows(ids), rounds * ones)


def test_two_bsp_workers_through_the_gates_in_table_order():
    """-sync=true, two local workers, each a get-add loop through the group:
    every member's gate is entered around the one dispatch, nobody wedges,
    and every add lands once."""
    mv.init(["-sync=true"], num_local_workers=2)
    try:
        tables = _tables(SHAPES["equal"], "default", "bsp")
        for t in tables:
            t.store.write_dense(np.zeros((64, 8), np.float32))
        group = mv.create_table_group(tables)
        ids = np.tile(np.arange(4, dtype=np.int32)[:, None], (1, 3))
        rounds, views, errors = 5, {0: [], 1: []}, []

        def work(wid):
            try:
                for _ in range(rounds):
                    views[wid].append(group.get_rows(
                        ids, mv.GetOption(worker_id=wid))[0, 0, 0])
                    group.add_rows(ids, np.ones((4, 3, 8), np.float32),
                                   AddOption(worker_id=wid))
            except Exception as e:  # noqa: BLE001 - reported by the assert
                errors.append(repr(e))

        threads = [threading.Thread(target=work, args=(w,)) for w in (0, 1)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads) and not errors, errors
        # BSP: both workers' i-th views are identical (2 adds a round)
        assert views[0] == views[1] == [2.0 * i for i in range(rounds)]
        assert np.array_equal(tables[0].get()[:4],
                              np.full((4, 8), 2.0 * rounds, np.float32))
    finally:
        mv.shutdown()


def test_a_pallas_plane_member_contributes_its_own_row_functions():
    """One shard, 128 columns: the stateful member takes the fused Pallas
    kernel (interpret mode here), and its group equals its per-table calls."""
    mv.init([], devices=jax.devices()[:1])
    try:
        shapes = [(64, 128), (32, 128)]
        solo = _tables(shapes, "adagrad", "ps")
        grouped = _tables(shapes, "adagrad", "pg")
        assert all(t.store.row_plane == "fused_stateful"
                   for t in solo + grouped)
        group = mv.create_table_group(grouped)
        rng = np.random.default_rng(3)
        for _ in range(2):
            ids = _ids(shapes, "equal", rng, batch=12)
            delta = rng.normal(size=(12, 2, 128)).astype(np.float32)
            for i, t in enumerate(solo):
                t.add_rows(ids[:, i], delta[:, i], OPTION)
            group.add_rows(ids, delta, OPTION)
        got = group.get_rows(ids)
        for i, (a, b) in enumerate(zip(solo, grouped)):
            assert np.array_equal(a.get(), b.get())
            assert np.array_equal(_state_np(a)["g2"], _state_np(b)["g2"])
            assert np.array_equal(got[:, i], a.get_rows(ids[:, i]))
    finally:
        mv.shutdown()


@pytest.mark.parametrize("why", ["sparse_member", "member_twice",
                                 "another_dtype", "no_member"])
def test_what_the_group_does_not_cover_is_refused_at_construction(mv_env,
                                                                   why):
    a, b = _tables([(32, 8), (32, 8)], "default", "ok")
    members = {
        "sparse_member": lambda: [a, mv.create_table(MatrixTableOption(
            num_row=32, num_col=8, is_sparse=True))],
        "member_twice": lambda: [a, b, a],
        "another_dtype": lambda: [a, mv.create_table(MatrixTableOption(
            num_row=32, num_col=8, dtype=np.int32))],
        "no_member": lambda: [],
    }[why]()
    with pytest.raises(FatalError):
        mv.create_table_group(members)


@pytest.mark.parametrize("form", FORMS)
def test_wrong_shapes_are_refused_before_anything_is_donated(mv_env, form):
    tables = _tables(SHAPES["equal"], "default", "bad")
    group = mv.create_table_group(tables)
    ids = np.zeros((4, 3), np.int32)
    ones = np.ones if form == "host" else jnp.ones
    pull = group.get_rows if form == "host" else group.get_rows_device
    before = _device_calls()
    with pytest.raises(FatalError):
        group.add_rows(ids, ones((4, 3, 9), np.float32))
    with pytest.raises(FatalError):
        group.add_rows(list(ids.T), [ones((4, 8), np.float32)] * 2 +
                       [ones((5, 8), np.float32)])
    with pytest.raises(FatalError):
        pull(np.zeros((4, 2), np.int32))
    assert _device_calls() == before
    assert np.array_equal(tables[0].get_rows([0]), tables[0].get()[:1])


@pytest.mark.parametrize("devices", ["one_device", "mesh_of_8"])
@pytest.mark.parametrize("fields", [3, 5])
def test_a_dlrm_step_is_two_grouped_calls_and_copies_no_table(
        fields, devices, monkeypatch):
    """``table.group.calls`` / ``table.group.member_ops`` count 2 and
    2 x fields a step; the ``comm.ps.*`` totals stay per member; and no
    member's whole-table ``jit_access`` program is ever launched (the
    per-table add's wait, ``ServerStore.block``, runs one per call).
    With the tables on the one device of the dense programs the step is one
    device pull and one device push, no row block visits the host and its
    programs compile once; over a mesh it is the host form."""
    from multiverso_tpu.models.dlrm import (DLRMConfig, DLRMModel,
                                            ImpressionStream, StreamConfig)
    from multiverso_tpu.tables.table_group import TableGroup
    one = devices == "one_device"
    mv.init([], devices=jax.devices()[:1] if one else None)
    try:
        cfg = DLRMConfig(fields=fields, vocab=64, embed_dim=8, dense_dim=4,
                         bottom_mlp=(8,), top_mlp=(8,))
        stream = ImpressionStream(StreamConfig(
            fields=fields, vocab=64, dense_dim=4, zipf=1.3, seed=1,
            drift_every=0))
        model = DLRMModel(cfg, mode="ps")
        pushed, host_pulls, add_rows, get_rows = [], [], \
            TableGroup.add_rows, TableGroup.get_rows

        def spy_add(self, ids, deltas, option=None):
            pushed.append(type(deltas))
            return add_rows(self, ids, deltas, option)

        def spy_get(self, ids, option=None):
            host_pulls.append(ids.shape)
            return get_rows(self, ids, option)
        monkeypatch.setattr(TableGroup, "add_rows", spy_add)
        monkeypatch.setattr(TableGroup, "get_rows", spy_get)
        reg = get_registry()
        names = ("table.group.calls", "table.group.member_ops",
                 "comm.ps.ops", "comm.ps.bytes") + DEVICE_COUNTERS
        before = {n: reg.counter(n).value for n in names}
        steps, batch = 4, 16
        for _ in range(steps):
            b = stream.batch(batch)
            model.step(b.ids, b.dense, b.labels)
        moved = {n: reg.counter(n).value - before[n] for n in names}
        assert moved["table.group.calls"] == 2 * steps
        assert moved["table.group.member_ops"] == 2 * fields * steps
        assert moved["comm.ps.ops"] == 2 * fields * steps
        assert moved["comm.ps.bytes"] == 2 * fields * steps * batch * 8 * 4
        assert [moved[n] for n in DEVICE_COUNTERS] == [one * steps] * 2
        assert len(host_pulls) == (0 if one else steps)
        assert all(issubclass(t, jax.Array) == one for t in pushed)
        # the first step compiles what every later one runs: the fresh
        # dense leaves are placed beside the (committed) pulled rows
        assert model._hybrid.delta._cache_size() == 1
        assert model._hybrid.apply._cache_size() == 1
        assert all(t.store._access._cache_size() == 0 for t in model.tables)
        # the per-table add DOES run one, which is what the group leaves out
        model.tables[0].add_rows([1], np.zeros((1, 8), np.float32),
                                 model._add_option)
        assert model.tables[0].store._access._cache_size() == 1
    finally:
        mv.shutdown()


# -- the twin without tables (ISSUE 28) ------------------------------------
@pytest.fixture(params=["mesh_of_8", "one_device"])
def table_devices(request):
    mv.init([], devices=jax.devices()[:1] if request.param == "one_device"
            else None)
    yield request.param
    mv.shutdown()


def _twin_and_tables(shapes, updater):
    """A ``LocalTableGroup`` and a ``TableGroup`` of tables made from the
    SAME options."""
    from multiverso_tpu.tables.table_group import LocalTableGroup
    options = [MatrixTableOption(
        num_row=r, num_col=c, random_init=i != 1, seed=11 + i,
        init_low=-0.25, init_high=0.75, updater=updater, name=f"twin{i}")
        for i, (r, c) in enumerate(shapes)]
    tables = [mv.create_table(o) for o in options]
    return LocalTableGroup(options), mv.create_table_group(tables), tables


@pytest.mark.parametrize("kind", sorted(SHAPES))
def test_local_group_starts_and_pulls_as_the_tables_do(table_devices, kind):
    """The initial draw (one member zeros: no ``random_init``) and both
    forms of the pull, bit for bit, in the matrix and the flat layout."""
    shapes = SHAPES[kind]
    local, group, tables = _twin_and_tables(shapes, "adagrad")
    for i, t in enumerate(tables):
        assert np.array_equal(local.local_rows(i), t.get())
    assert not local.local_rows(1).any() and local.local_rows(0).any()
    ids = _ids(shapes, kind, np.random.default_rng(3))
    for form in FORMS:
        pull = "get_rows" if form == "host" else "get_rows_device"
        got, want = getattr(local, pull)(ids), getattr(group, pull)(ids)
        if kind == "equal":
            got, want = [got], [want]
        assert len(got) == len(want)
        for g, w in zip(got, want):
            assert isinstance(g, jax.Array if form == "device"
                              else np.ndarray)
            assert g.dtype == w.dtype and g.shape == w.shape
            assert np.array_equal(g, w)
    assert local.lives_with(jnp.zeros(1))


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("kind", sorted(SHAPES))
@pytest.mark.parametrize("updater", ["adagrad", "momentum_sgd"])
def test_local_group_adds_as_the_table_group_does(table_devices, updater,
                                                  kind, form):
    """Data AND state after three pushes with duplicate ids in every batch,
    the deltas from the host or from the device."""
    shapes = SHAPES[kind]
    local, group, tables = _twin_and_tables(shapes, updater)
    rng = np.random.default_rng(4)
    for _ in range(3):
        ids = _ids(shapes, kind, rng)
        blocks = [rng.normal(size=(len(col), c)).astype(np.float32)
                  for col, (_, c) in zip(_columns(ids, len(shapes)), shapes)]
        local.add_rows(ids, _deltas(blocks, kind, form), OPTION)
        group.add_rows(ids, _deltas(blocks, kind, form), OPTION)
    for i, t in enumerate(tables):
        assert np.array_equal(local.local_rows(i), t.get())
        state = _state_np(t)
        assert sorted(state) == sorted(local._states[i])
        for k, v in state.items():
            assert np.array_equal(local._states[i][k], v), (i, k)
    ids = _ids(shapes, kind, rng)        # and a pull sees the pushes
    got, want = local.get_rows(ids), group.get_rows(ids)
    for g, w in zip(*(([got], [want]) if kind == "equal" else (got, want))):
        assert np.array_equal(g, w)


@pytest.fixture
def witness():
    """The runtime lock witness ON for locks made inside the test."""
    from multiverso_tpu.telemetry.lockwitness import reset_lockwitness
    from multiverso_tpu.utils.locks import set_witness_enabled
    set_witness_enabled(True)
    reset_lockwitness()
    yield
    set_witness_enabled(None)


@pytest.mark.parametrize("order", ["table_id_order", "against_table_id"])
def test_store_locks_are_an_ordered_family_the_witness_checks(witness, order):
    """A group holds its members' ``core.store`` locks together, in table-id
    order: the witness records no ``core.store -> core.store`` self-loop
    for that, and DOES for the same locks taken against the order."""
    from multiverso_tpu.telemetry.lockwitness import (check_inversions,
                                                      observed_edges)
    mv.init([])
    try:
        tables = _tables(SHAPES["equal"], "default", "lw")
        if order == "table_id_order":
            # members given in another order than their ids
            group = mv.create_table_group(tables[::-1])
            ids = np.zeros((4, 3), np.int32)
            group.add_rows(ids, np.ones((4, 3, 8), np.float32))
            group.get_rows(ids)
            assert ("core.store", "core.store") not in observed_edges()
            assert check_inversions(postmortem=False) == []
        else:
            with tables[2].store._lock, tables[0].store._lock:
                pass
            assert check_inversions(postmortem=False) == [("core.store",)]
    finally:
        mv.shutdown()
