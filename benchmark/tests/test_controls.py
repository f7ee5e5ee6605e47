"""The comparison that decides ``correct`` has been shown to fail: (1) the
control - the reference computed in the next lower precision, put in the
program's place - fails the cell's limits on every seed while the sound
program passes; (2) with the timed path broken underneath (a step that
returns its state unchanged, a push that is dropped, an answer altered where
it is produced) a whole run comes out ``correct: false``. Tiny sizes; the same
readings at the cells' own sizes are in PERF.md."""
import numpy as np
import pytest

import harness
import tiny

SEEDS = [5, 2 ** 31 + 6, 77]


@pytest.fixture()
def tiny_root(tmp_path):
    return tiny.make_root(str(tmp_path / "root"))


def _context_maker(root, bench_dir, workload):
    ctx, driver = harness.open_cell(workload, 0, 1.0, False,
                                    require_chip=False, root=root,
                                    bench_dir=bench_dir)
    return driver, ctx.traffic, lambda seed: harness.Context(
        ctx.cell, ctx.config, ctx.traffic, seed, 1.0, False, ctx.device,
        bench_dir)


@pytest.mark.parametrize("workload", ["w2v_train", "dlrm_train"])
def test_lower_precision_control_fails_the_limits(tiny_root, workload):
    root, bench_dir = tiny_root
    driver, traffic, make_ctx = _context_maker(root, bench_dir, workload)
    rows = driver.limit_readings(make_ctx, SEEDS, len(SEEDS))
    limits = traffic["limits"]
    for row in rows:
        passed = all(v <= limits[k] for k, v in row["gaps"].items())
        if row["side"] == "sound":
            assert passed, row
        else:
            assert not passed, row      # one number over its limit suffices


def _run(root, bench_dir, workload, seed=9):
    return harness.run_cell(workload, seed, 1.0, False, require_chip=False,
                            root=root, bench_dir=bench_dir)


def test_w2v_step_that_returns_its_state_unchanged(tiny_root, monkeypatch,
                                                   capsys):
    from multiverso_tpu.models.word2vec import model as w2v_model

    def frozen_step(adagrad):
        def step(w_in, w_out, g_in, g_out, centers, contexts, negatives,
                 mask, lr):
            return w_in, w_out, g_in, g_out, np.float32(1.0)
        return step
    monkeypatch.setattr(w2v_model, "raw_sg_ns_step", frozen_step)
    result = _run(*tiny_root, "w2v_train")
    assert result["correct"] is False
    out = capsys.readouterr().out
    assert "check first_block_tables_moved" in out and "FAILED" in out
    assert "check step_rows_rel_gap" in out


def test_dlrm_push_that_is_dropped(tiny_root, monkeypatch, capsys):
    from multiverso_tpu.models.dlrm.model import DLRMModel
    monkeypatch.setattr(DLRMModel, "_push_rows",
                        lambda self, field, ids, delta: None)
    result = _run(*tiny_root, "dlrm_train")
    assert result["correct"] is False
    assert "rows_rel_gap" in capsys.readouterr().out


def test_dlrm_half_the_batch_left_out(tiny_root, monkeypatch):
    from multiverso_tpu.models.dlrm.model import DLRMModel
    whole = DLRMModel.step

    def half(self, ids, dense_x, labels):
        n = len(labels) // 2
        return whole(self, ids[:n], dense_x[:n], labels[:n])
    monkeypatch.setattr(DLRMModel, "step", half)
    assert _run(*tiny_root, "dlrm_train")["correct"] is False


def test_lookup_answer_altered_where_it_is_produced(tiny_root, monkeypatch,
                                                    capsys):
    from multiverso_tpu.serving.runners import SparseLookupRunner
    sound = SparseLookupRunner.collect

    def altered(self, *args, **kwargs):
        out = np.array(sound(self, *args, **kwargs))
        out[..., 0] += np.float32(1e-3)
        return out
    monkeypatch.setattr(SparseLookupRunner, "collect", altered)
    result = _run(*tiny_root, "w2v_lookup")
    assert result["correct"] is False
    assert "check sample_replies_wrong" in capsys.readouterr().out


def test_sound_cells_pass_in_process(tiny_root):
    for workload in ("w2v_train", "dlrm_train", "w2v_lookup"):
        assert _run(*tiny_root, workload)["correct"] is True, workload
