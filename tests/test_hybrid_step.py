"""The hybrid PS step written once (ISSUE 28, parallel/hybrid_step.py), seen
through both models that state a delta, an apply and a group and leave the
cycle to it: the benchmark's control hook, the launches of a step, one
compile a program, and the one name of the pulled rows. CPU: counts only."""
import weakref

import jax
import numpy as np
import pytest

import multiverso_tpu as mv
from multiverso_tpu.models.dlrm import (DLRMConfig, DLRMModel,
                                        ImpressionStream, StreamConfig)
from multiverso_tpu.models.hybrid_lm import HybridLM, HybridLMConfig
from multiverso_tpu.parallel.hybrid_step import HybridStep
from multiverso_tpu.tables.table_group import LocalTableGroup, TableGroup


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
def test_a_step_merges_once_a_leaf_and_compiles_each_program_once(
        plane, name, monkeypatch):
    mode, _ = plane
    model, batch = MODELS[name](mode)
    step = model._hybrid
    leaves = len(jax.tree_util.tree_leaves(getattr(model, step._dense[0])))
    merges, dense_sync = [], step.dense_sync
    monkeypatch.setattr(step, "dense_sync",
                        lambda leaf: merges.append(leaf.shape)
                        or dense_sync(leaf))
    steps = 3
    for _ in range(steps):
        model.step(*batch())
    assert len(merges) == steps * leaves
    assert merges[:leaves] == [leaf.shape for leaf in jax.tree_util.
                               tree_leaves(getattr(model, step._dense[0]))]
    assert step.delta._cache_size() == 1
    assert step.apply._cache_size() == 1
    assert dense_sync._cache_size() == len(set(merges))
    assert model.group._access._cache_size() == 1
    assert model.steps == steps


@pytest.mark.parametrize("plane", ["local", "ps_one_device"], indirect=True)
@pytest.mark.parametrize("name", sorted(MODELS))
def test_the_pulled_rows_have_one_name_and_it_goes_at_the_launch(
        plane, name, monkeypatch):
    """``peak_hbm_gb``'s guard (PERF.md 6, PR 27): once the delta program is
    launched no Python name holds the array the group returned, so its
    buffer goes when the program ends and not when the step does. Witnessed
    at every merge (the calls after the launch) by a weakref; where the rows
    are the host's there is no device buffer to hold."""
    model, batch = MODELS[name](plane[0])
    step, group = model._hybrid, model.group
    pulled, alive, pull = [], [], group.get_rows_device
    dense_sync = step.dense_sync

    def spy_pull(ids, *args):
        rows = pull(ids, *args)
        pulled.extend(weakref.ref(r) for r in (
            rows if isinstance(rows, list) else [rows]))
        return rows

    def spy_sync(leaf):
        alive.append([ref() is not None for ref in pulled])
        return dense_sync(leaf)
    monkeypatch.setattr(group, "get_rows_device", spy_pull)
    monkeypatch.setattr(step, "dense_sync", spy_sync)
    model.step(*batch())
    model.step(*batch())
    assert len(pulled) == 2 and alive
    assert not any(any(flags) for flags in alive), alive
