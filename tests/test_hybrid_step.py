"""The hybrid PS step written once (ISSUE 28, parallel/hybrid_step.py), seen
through both models that state a delta, an apply and a group and leave the
cycle to it: the benchmark's control hook, the device calls of a step (no
dense merge without an axis to reduce over, one over the tree with one), one
compile a program, and the one name of the pulled rows. CPU: counts only."""
import weakref

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

import multiverso_tpu as mv
from multiverso_tpu.models.dlrm import (DLRMConfig, DLRMModel,
                                        ImpressionStream, StreamConfig)
from multiverso_tpu.models.hybrid_lm import HybridLM, HybridLMConfig
from multiverso_tpu.parallel.hybrid_step import HybridStep
from multiverso_tpu.tables.table_group import LocalTableGroup, TableGroup
from multiverso_tpu.telemetry import counter


def _dlrm(mode):
    cfg = DLRMConfig(fields=3, vocab=64, embed_dim=8, dense_dim=4,
                     bottom_mlp=(8,), top_mlp=(8,))
    stream = ImpressionStream(StreamConfig(
        fields=3, vocab=64, dense_dim=4, zipf=1.3, seed=1, drift_every=0))

    def batch():
        b = stream.batch(16)
        return b.ids, b.dense, b.labels
    return DLRMModel(cfg, mode=mode), batch


def _lm(mode):
    # the config's defaults are the small model (hidden 64, vocabulary 64)
    cfg = HybridLMConfig(pattern="ME*", attn_block=8, moe_block=4,
                         loss_block=16, row_bucket=16)
    rng = np.random.default_rng(5)
    model = HybridLM(cfg, mode=mode)
    model.min_rows = 48         # one padded shape whatever the batch draws
    return model, lambda: (rng.integers(0, 64, (2, 21)).astype(np.int32),)


MODELS = {"dlrm": _dlrm, "hybrid_lm": _lm}
# Where the tables live: over tier-1's 8 forced devices (host rows), on the
# dense leaves' one device (device rows), or nowhere (the twin).
PLANES = {"ps_mesh_of_8": ("ps", None), "ps_one_device": ("ps", 1),
          "local": ("local", None)}


@pytest.fixture(params=sorted(PLANES))
def plane(request):
    mode, devices = PLANES[request.param]
    if mode == "ps":
        mv.init([], devices=jax.devices()[:devices] if devices else None)
    yield mode, request.param != "ps_mesh_of_8"
    if mode == "ps":
        mv.shutdown()


@pytest.mark.parametrize("name", sorted(MODELS))
def test_a_model_states_its_programs_and_its_group(plane, name):
    mode, on_device = plane
    model, _ = MODELS[name](mode)
    assert isinstance(model._hybrid, HybridStep)
    assert isinstance(model.group,
                      TableGroup if mode == "ps" else LocalTableGroup)
    assert model._hybrid._on_device == on_device
    assert model._hybrid.delta.__name__ == {
        "dlrm": "delta_step", "hybrid_lm": "lm_delta_step"}[name]


@pytest.mark.parametrize("name", sorted(MODELS))
def test_push_rows_patched_on_the_class_is_what_a_step_calls(
        plane, name, monkeypatch):
    """The benchmark's dropped-push control patches ``_push_rows`` ON THE
    CLASS after the model is built (benchmark/tests/test_controls.py,
    test_train_lm.py): the step must look the method up when it pushes."""
    mode, on_device = plane
    model, batch = MODELS[name](mode)
    cls, seen = type(model), []
    monkeypatch.setattr(cls, "_push_rows",
                        lambda self, *args: seen.append(args))
    model.step(*batch())
    assert len(seen) == 1
    *head, ids, delta = seen[0]
    assert head == ([None] if name == "dlrm" else [])
    assert isinstance(delta, jax.Array if on_device else np.ndarray)
    assert delta.shape[:ids.ndim] == ids.shape
    # and nothing was pushed: the rows are what they were
    monkeypatch.undo()
    before = model.gather_emb(ids) if name == "dlrm" else model.pull_rows(ids)
    monkeypatch.setattr(cls, "_push_rows", lambda self, *args: None)
    model.step(*batch())
    monkeypatch.undo()
    after = model.gather_emb(ids) if name == "dlrm" else model.pull_rows(ids)
    np.testing.assert_array_equal(before, after)


@pytest.mark.parametrize("name", sorted(MODELS))
def test_without_an_axis_a_step_launches_no_merge_and_compiles_each_program_once(
        plane, name):
    """No ``dp_mesh`` (every benchmark cell, the twin, one process): there
    is nothing to reduce over, so no merge program exists and the deltas go
    from the delta program to the apply as they are."""
    mode, _ = plane
    model, batch = MODELS[name](mode)
    step = model._hybrid
    assert step.dense_sync is None
    steps = 3
    for _ in range(steps):
        model.step(*batch())
    assert counter("hybrid.dense_merge.elided").value == steps
    assert counter("hybrid.dense_merge.programs").value == 0
    assert step.delta._cache_size() == 1
    assert step.apply._cache_size() == 1
    assert model.group._access._cache_size() == 1
    assert model.steps == steps


@pytest.mark.parametrize("plane", ["local", "ps_one_device"], indirect=True)
@pytest.mark.parametrize("name", sorted(MODELS))
def test_the_pulled_rows_have_one_name_and_it_goes_at_the_launch(
        plane, name, monkeypatch):
    """``peak_hbm_gb``'s guard (PERF.md 6, PR 27): once the delta program is
    launched no Python name holds the array the group returned, so its
    buffer goes when the program ends and not when the step does. Witnessed
    at the apply (the call after the launch) by a weakref; where the rows
    are the host's there is no device buffer to hold."""
    model, batch = MODELS[name](plane[0])
    step, group = model._hybrid, model.group
    pulled, alive, pull = [], [], group.get_rows_device
    apply = step.apply

    def spy_pull(ids, *args):
        rows = pull(ids, *args)
        pulled.extend(weakref.ref(r) for r in (
            rows if isinstance(rows, list) else [rows]))
        return rows

    def spy_apply(*args):
        alive.append([ref() is not None for ref in pulled])
        return apply(*args)
    monkeypatch.setattr(group, "get_rows_device", spy_pull)
    monkeypatch.setattr(step, "apply", spy_apply)
    model.step(*batch())
    model.step(*batch())
    assert len(pulled) == 2 and len(alive) == 2
    assert not any(any(flags) for flags in alive), alive


def _mesh_of(n):
    return Mesh(np.array(jax.devices()[:n]), ("server",))


@pytest.mark.parametrize("devices", [2, 4, 8])
@pytest.mark.parametrize("name", sorted(MODELS))
def test_over_an_axis_a_step_merges_the_tree_in_one_launch(name, devices):
    """``dp_mesh`` with an axis larger than 1: ONE donated program over the
    whole tree of dense deltas, launched once a step and compiled once, the
    dense leaves left on every device of the mesh with the values of the
    model without a mesh. ``psum / n`` over identical contributions is exact
    for a power of two: bitwise where XLA:CPU compiles the step's programs
    for the mesh as it does for one device (2 and 4 devices here), to the
    last bits where it does not (8: the programs' own arithmetic differs)."""
    plain, batch = MODELS[name]("local")
    _, again = MODELS[name]("local")        # the same draws for the second
    model = type(plain)(plain.cfg, mode="local", dp_mesh=_mesh_of(devices),
                        dp_axis="server")
    model.min_rows = plain.min_rows if name == "hybrid_lm" else 0
    step = model._hybrid
    leaves = jax.tree_util.tree_leaves(getattr(model, step._dense[0]))
    steps = 3
    for _ in range(steps):
        plain.step(*batch())
        model.step(*again())
    assert counter("hybrid.dense_merge.programs").value == steps
    # the model without a mesh counted its own steps
    assert counter("hybrid.dense_merge.elided").value == steps
    assert step.dense_sync._cache_size() == 1
    assert step.apply._cache_size() == 1
    merged = jax.tree_util.tree_leaves(getattr(model, step._dense[0]))
    assert len(merged) == len(leaves) > 1
    for ours, theirs in zip(merged, jax.tree_util.tree_leaves(
            getattr(plain, step._dense[0]))):
        assert len(ours.devices()) == devices
        assert ours.sharding.is_fully_replicated
        if devices < 8:
            np.testing.assert_array_equal(np.asarray(ours),
                                          np.asarray(theirs))
        else:
            np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs),
                                       rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("plane", ["ps_mesh_of_8", "ps_one_device"],
                         indirect=True)
@pytest.mark.parametrize("name", sorted(MODELS))
def test_on_the_ps_plane_a_merge_takes_host_rows_and_refuses_committed_ones(
        plane, name):
    """``ps`` mode under a ``dp_mesh``: tables over the store's own mesh hand
    the step host rows, and it merges as the twin does; a group on ONE device
    commits its rows there, which no program takes beside leaves replicated
    over four (the per-leaf merge failed inside jax on it too): the step says
    so, by name, before it launches anything."""
    plain, batch = MODELS[name]("ps")
    model = type(plain)(plain.cfg, mode="ps", dp_mesh=_mesh_of(4),
                        dp_axis="server")
    model.min_rows = plain.min_rows if name == "hybrid_lm" else 0
    step = model._hybrid
    if plane[1]:
        with pytest.raises(ValueError, match="rows are committed"):
            model.step(*batch())
        assert counter("hybrid.dense_merge.programs").value == 0
        return
    for _ in range(2):
        model.step(*batch())
    assert counter("hybrid.dense_merge.programs").value == 2
    assert step.dense_sync._cache_size() == step.apply._cache_size() == 1
    for leaf in jax.tree_util.tree_leaves(getattr(model, step._dense[0])):
        assert len(leaf.devices()) == 4 and leaf.sharding.is_fully_replicated


@pytest.mark.parametrize("devices", [None, 1])
def test_a_mesh_without_an_axis_to_reduce_over_builds_no_merge(devices):
    """The rule is the axis's size, not the mesh's presence: an axis of one
    device, or an axis the mesh does not have, is no axis."""
    model, batch = MODELS["dlrm"]("local")
    mesh = _mesh_of(devices or 4)
    model = DLRMModel(model.cfg, mode="local", dp_mesh=mesh,
                      dp_axis="server" if devices else "worker")
    assert model._hybrid.dense_sync is None
    model.step(*batch())
    assert counter("hybrid.dense_merge.elided").value == 1
    assert counter("hybrid.dense_merge.programs").value == 0


@pytest.mark.parametrize("plane", ["local", "ps_one_device"], indirect=True)
@pytest.mark.parametrize("name", sorted(MODELS))
def test_a_step_on_the_device_path_makes_one_device_call_a_phase(
        plane, name, monkeypatch):
    """Counts only (CPU yields counts, never rates): between "ids known" and
    the step's return the host calls ``jax.device_put`` once (the batch),
    the pull, the delta, the apply, the update, and ``jax.device_get`` once
    (the results), in that order. What it cannot see: transfers made another
    way (the ids' ``jnp.asarray`` inside pull and push, a stray
    ``np.asarray`` or ``float()`` of a device array); it does catch a return
    to one copy an array or to two blocking result copies. The first step is
    left out: it commits the fresh leaves beside the rows."""
    model, batch = MODELS[name](plane[0])
    step, group, calls = model._hybrid, model.group, []
    model.step(*batch())

    def logged(label, fn):
        def call(*args, **kwargs):
            calls.append(label)
            # The batch goes up uncommitted (no target): committed, the
            # delta program's text spells its shardings out, and a compile
            # cache filled before PR 38 misses it.
            assert label != "batch up" or (len(args) == 1 and not kwargs)
            return fn(*args, **kwargs)
        return call
    for owner, attr, label in ((step, "delta", "delta"),
                               (step, "apply", "apply"),
                               (group, "_access", "pull"),
                               (group, "_update", "update"),
                               (jax, "device_put", "batch up"),
                               (jax, "device_get", "results down")):
        monkeypatch.setattr(owner, attr, logged(label, getattr(owner, attr)))
    for _ in range(2):
        model.step(*batch())
    assert calls == ["pull", "batch up", "delta", "apply", "update",
                     "results down"] * 2
