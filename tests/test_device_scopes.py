"""``telemetry/device_scopes.py``: a registered step program's scopes read
back from its compiled text (inside ``while`` bodies, under remat and under
``transpose(jvp(...))``), what registration keeps and what it costs, and the
proof that the scopes are metadata: the compiled text without its metadata is
the text of the program without them."""
import contextlib
import gc
import re
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from multiverso_tpu.models.hybrid_lm.model import (HybridLM, HybridLMConfig,
                                                   pack_batch)
from multiverso_tpu.models.word2vec.model import build_device_block_step
from multiverso_tpu.telemetry import device_scopes
from multiverso_tpu.telemetry.device_scopes import (parse_scopes,
                                                    program_scopes,
                                                    register_program,
                                                    reset_device_scopes,
                                                    scope_names)

#: The scopes this file's PR wrote; the LM's block scopes were there before.
NEW_SCOPES = {"w2v_pairs", "w2v_gather", "w2v_grads", "w2v_rows",
              "w2v_rows_in", "w2v_rows_out", "lm_embed", "lm_head_loss",
              "lm_scale"}


@pytest.fixture(autouse=True)
def fresh_registry():
    reset_device_scopes()
    yield
    reset_device_scopes()


def _paths_under(paths, scope):
    """The op_name paths that have ``scope`` as a component, as it stands or
    wrapped (``transpose(jvp(scope))``)."""
    return [p for p in paths if scope in scope_names(p)]


def _tiny_lm(mode="local"):
    cfg = HybridLMConfig(row_bucket=16, attn_block=8, moe_block=4,
                         loss_block=16)
    tokens = np.random.default_rng(0).integers(0, 64, (2, 21)).astype(
        np.int32)
    return HybridLM(cfg, mode=mode), tokens


def _block_args(V=512, D=128):
    tables = [jnp.full((V, D), 0.01 * (i + 1), jnp.float32)
              for i in range(4)]
    rng = np.random.default_rng(1)
    return (*tables, jnp.arange(1024, dtype=jnp.int32) % V,
            jnp.ones((V,), jnp.float32),
            jnp.asarray(rng.integers(0, V, (8, 32)), jnp.int32),
            jnp.full((8,), 32, jnp.int32), jax.random.PRNGKey(0), 0.025)


@pytest.mark.parametrize("mode", ["local", "ps"])
def test_lm_step_maps_instructions_to_every_scope(mode, mv_env):
    """The hybrid step registers its two programs in both modes; on the PS
    plane the table group registers its grouped gather and update too."""
    lm, tokens = _tiny_lm(mode)
    lm.step(tokens)
    scopes = program_scopes()
    assert {"jit_lm_delta_step", "jit_lm_apply"} <= set(scopes)
    assert ({"jit_group_access_rows", "jit_group_rows"} <= set(scopes)) \
        == (mode == "ps")
    paths = set(scopes["jit_lm_delta_step"].values())
    for scope in ("lm_mamba2", "lm_attention", "lm_experts", "lm_embed",
                  "lm_head_loss", "lm_scale"):
        assert _paths_under(paths, scope), scope
    # inside while bodies, in the backward pass, and rematerialised
    mamba = _paths_under(paths, "lm_mamba2")
    assert any("/while/body/" in p for p in mamba)
    assert any(p.split("/")[1] == "transpose(jvp(lm_mamba2))"
               for p in mamba)
    assert any("transpose(jvp(lm_head_loss))" in p
               and "/while/body/" in p for p in paths)
    assert any("rematted_computation" in p
               for p in _paths_under(paths, "lm_experts"))
    # an instruction name is the trace's: no "%", its number kept
    assert all(re.fullmatch(r"[\w.\-]+", name)
               for name in scopes["jit_lm_delta_step"])


@pytest.mark.parametrize("one_device", [True, False],
                         ids=["row_kernel", "xla_rows"])
def test_block_step_maps_instructions_to_every_scope(one_device):
    """On one device the tables are the row kernel's (interpreted here); as
    host-backed arrays of 64 columns they are XLA's: the scopes own the
    sorts, the takes and the updates on both planes."""
    step = build_device_block_step(2, 3, 64, True)
    args = _block_args() if one_device else _block_args(D=64)
    if one_device:
        args = tuple(jax.device_put(a, jax.devices()[0]) for a in args[:4]) \
            + args[4:]
    out = step(*args)
    assert np.isfinite(float(out[4]))
    (module, instructions), = program_scopes().items()
    assert module == "jit_block_step"
    paths = set(instructions.values())
    for scope in ("w2v_pairs", "w2v_gather", "w2v_grads", "w2v_rows",
                  "w2v_rows_in", "w2v_rows_out"):
        assert _paths_under(paths, scope), scope
    assert all("/while/body/" in p for p in _paths_under(paths, "w2v_rows"))
    assert not any("/while/" in p.split("w2v_pairs")[0]
                   for p in _paths_under(paths, "w2v_pairs"))
    assert set(_paths_under(paths, "w2v_rows")) == \
        set(_paths_under(paths, "w2v_rows_in")) \
        | set(_paths_under(paths, "w2v_rows_out"))


def test_mesh_block_program_lowers_again_from_its_registered_shardings(
        mv_env):
    """``Word2Vec(mesh_data=2, mesh_model=2)``: the dp x tp block program is
    registered with the shardings its arguments came in (tables in row
    ranges over ``model``, host sentences), lowers again from them, and the
    row kernel a shard sits under ``w2v_rows`` with the ``shard_map``."""
    from multiverso_tpu.models.word2vec import (Dictionary, Word2Vec,
                                                Word2VecConfig)
    rng = np.random.default_rng(0)
    d = Dictionary(min_count=1)
    d.counts = [50] * 80
    d.words = [str(i) for i in range(80)]
    w2v = Word2Vec(Word2VecConfig(
        embedding_size=128, window=2, negative=3, sample=0, batch_size=32,
        block_sentences=4, pad_sentence_length=16, device_pipeline=True,
        seed=3, mesh_data=2, mesh_model=2), d)
    w2v.train(sentences=[rng.integers(0, 80, 16).tolist() for _ in range(8)])
    paths = set(program_scopes()["jit_block_step"].values())
    rows = _paths_under(paths, "w2v_rows")
    assert rows and any("/shard_map/" in p for p in rows)
    assert _paths_under(paths, "w2v_gather") and \
        _paths_under(paths, "w2v_pairs")


def test_registration_keeps_shapes_and_no_array():
    @jax.jit
    def bump(table, rows, lr):
        with jax.named_scope("bump_rows"):
            return table.at[rows].add(lr)

    table = jnp.zeros((64, 8), jnp.float32)
    rows = np.arange(4, dtype=np.int32)
    register_program(bump, (table, rows, 0.5))
    assert len(device_scopes._programs) == 1
    # a second call with the same shapes (another table, another rate)
    register_program(bump, (jnp.ones((64, 8), jnp.float32), rows + 1, 0.25))
    assert len(device_scopes._programs) == 1
    register_program(bump, (table, np.arange(5, dtype=np.int32), 0.5))
    assert len(device_scopes._programs) == 2
    dead = weakref.ref(table)
    del table
    gc.collect()
    assert dead() is None
    scopes = program_scopes()["jit_bump"]
    assert any("bump_rows" in path for path in scopes.values())
    # the program is held weakly: its registrations go with it
    del bump
    gc.collect()
    assert not device_scopes._programs and program_scopes() == {}


def test_static_keyword_arguments_are_kept_as_they_are():
    def pick(xs, lengths=None):
        return xs[:lengths[0]] * 2.0

    pick = jax.jit(pick, static_argnames="lengths")
    register_program(pick, (np.zeros(8, np.float32),), {"lengths": (3, 5)})
    register_program(pick, (np.zeros(8, np.float32),), {"lengths": (3, 5)})
    register_program(pick, (np.zeros(8, np.float32),), {"lengths": (4, 4)})
    assert len(device_scopes._programs) == 2
    assert "jit_pick" in program_scopes()


def test_a_program_that_does_not_lower_is_left_out():
    @jax.jit
    def needs_two(a, b):
        return a + b

    register_program(needs_two, (np.zeros(3, np.float32),))
    assert program_scopes() == {}


def test_programs_of_one_name_that_disagree_drop_the_instruction():
    def make(scope):
        def same_name(x):
            with jax.named_scope(scope):
                return jnp.sin(x) * 2.0
        return jax.jit(same_name)

    one, two = make("first"), make("second")
    register_program(one, (np.zeros(4, np.float32),))
    register_program(two, (np.zeros(4, np.float32),))
    scopes = program_scopes()["jit_same_name"]
    assert not any("first" in p or "second" in p for p in scopes.values())


def test_scope_names_peel_the_wrappers_jax_puts_around_a_scope():
    assert scope_names(
        "jit(lm_delta_step)/transpose(jvp(lm_mla))/while/body/checkpoint/"
        "rematted_computation/jit(_where)/select_n") == [
            "lm_delta_step", "lm_mla", "while", "body", "checkpoint",
            "rematted_computation", "_where", "select_n"]
    assert scope_names("jit(f)/jvp()/bd,bkd->bk/dot_general") == [
        "f", "", "bd,bkd->bk", "dot_general"]


def test_parse_scopes_reads_every_computation():
    text = """HloModule jit_f, is_scheduled=true

%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %multiply.3 = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(f)/lm_scale/mul" source_file="m.py" source_line=3}
}

%body (c: (s32[], f32[4])) -> (s32[], f32[4]) {
  %fusion.12 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/while/body/transpose(jvp(lm_mla))/dot_general"}
  %copy.1 = f32[4]{0} copy(%fusion.12)
}

ENTRY %main (a: f32[4]) -> f32[4] {
  ROOT %while.2 = (s32[], f32[4]) while(%t), body=%body, metadata={op_name="jit(f)/while"}
}
"""
    module, scopes = parse_scopes(text)
    assert module == "jit_f"
    assert scopes == {
        "multiply.3": "jit(f)/lm_scale/mul",
        "fusion.12": "jit(f)/while/body/transpose(jvp(lm_mla))/dot_general",
        "while.2": "jit(f)/while"}


def _stripped(text: str) -> str:
    """Compiled text without its metadata: the ``metadata={...}`` of every
    instruction and the tables of file names and stack frames they index."""
    text = re.sub(r",?\s*metadata=\{[^}]*\}", "", text)
    return re.sub(r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n"
                  r"(?:\d+ .*\n)*", "", text, flags=re.M)


@pytest.mark.parametrize("program", ["lm_delta_step", "block_step"])
def test_the_new_scopes_are_metadata_only(program, monkeypatch):
    """The program compiled with the new scopes and compiled with them
    taken out (``jax.named_scope`` answering them with nothing) differ in
    metadata alone: with tracing off the scopes cost the device nothing."""
    def compiled_text():
        if program == "lm_delta_step":
            lm, tokens = _tiny_lm()
            ids, _, where, targets, mask = pack_batch(
                tokens, lm.cfg.row_bucket, 0, 1)
            rows = lm.group.get_rows_device([ids])[0]
            lowered = lm._hybrid.delta.lower(lm.params, rows, lm.buffers,
                                             where, targets, mask)
        else:
            lowered = build_device_block_step(2, 3, 64, True).lower(
                *_block_args())
        return lowered.compile().as_text()

    with_scopes = compiled_text()
    assert all(s in with_scopes for s in NEW_SCOPES
               if s.startswith("lm_" if program.startswith("lm") else "w2v"))
    named_scope = jax.named_scope
    monkeypatch.setattr(
        jax, "named_scope",
        lambda name: contextlib.nullcontext() if name in NEW_SCOPES
        else named_scope(name))
    without = compiled_text()
    assert not any(s in without for s in NEW_SCOPES)
    assert _stripped(with_scopes) == _stripped(without)
    assert with_scopes != without
