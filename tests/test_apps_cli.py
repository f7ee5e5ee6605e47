"""End-to-end CLI tests for the two reference applications."""

import numpy as np
import pytest


def _write_corpus(path, n=200, seed=0):
    rng = np.random.default_rng(seed)
    with open(path, "w") as f:
        for i in range(n):
            topic = "a" if i % 2 == 0 else "b"
            words = [f"{topic}{rng.integers(0, 5)}" for _ in range(15)]
            f.write(" ".join(words) + "\n")


def test_word2vec_cli(tmp_path):
    from multiverso_tpu.apps.word2vec_main import main

    corpus = tmp_path / "corpus.txt"
    out = tmp_path / "vectors.txt"
    _write_corpus(str(corpus))
    rc = main([f"-train_file={corpus}", f"-output_file={out}",
               "-size=16", "-window=3", "-negative=3", "-min_count=1",
               "-epoch=1", "-batch_size=256",
               "-use_device_pipeline=false"])
    assert rc == 0
    lines = out.read_text().strip().split("\n")
    v, d = lines[0].split()
    assert int(v) == 10 and int(d) == 16
    assert len(lines) == 11


def test_word2vec_cli_device_pipeline(tmp_path):
    from multiverso_tpu.apps.word2vec_main import main

    corpus = tmp_path / "corpus.txt"
    out = tmp_path / "vectors.txt"
    _write_corpus(str(corpus))
    rc = main([f"-train_file={corpus}", f"-output_file={out}",
               "-size=16", "-min_count=1", "-epoch=1", "-batch_size=256",
               "-use_device_pipeline=true", "-block_sentences=64",
               "-pad_sentence_length=16"])
    assert rc == 0
    assert out.exists()


@pytest.mark.parametrize("mode", ["in_graph", "pipelined_host"])
def test_word2vec_cli_dispatch_modes(tmp_path, mode):
    """-dispatch_mode reaches the model (Round 6 selector): every explicit
    mode trains end to end through the CLI."""
    from multiverso_tpu.apps.word2vec_main import main

    corpus = tmp_path / "corpus.txt"
    out = tmp_path / "vectors.txt"
    _write_corpus(str(corpus), n=60)
    rc = main([f"-train_file={corpus}", f"-output_file={out}",
               "-size=16", "-min_count=1", "-epoch=1", "-batch_size=128",
               "-use_device_pipeline=true", "-block_sentences=64",
               "-pad_sentence_length=16", f"-dispatch_mode={mode}",
               "-dispatch_depth=2"])
    assert rc == 0
    assert out.exists()


def test_word2vec_cli_missing_file():
    from multiverso_tpu.apps.word2vec_main import main

    assert main([]) == 1


def test_word2vec_cli_distributed(tmp_path):
    """-world_size=2: the launcher spawns two real worker processes that
    shard the tables over the PS service (the `mpirun -np 2` analog);
    rank 0 exports the merged embeddings."""
    import subprocess
    import sys

    corpus = tmp_path / "corpus.txt"
    out = tmp_path / "vectors.txt"
    _write_corpus(str(corpus))
    # launch through a real process so the spawned ranks' platform pinning
    # (not the test conftest) is what's exercised
    rc = subprocess.run(
        [sys.executable, "-m", "multiverso_tpu.apps.word2vec_main",
         f"-train_file={corpus}", f"-output_file={out}", "-world_size=2",
         "-size=16", "-window=3", "-negative=3", "-min_count=1",
         "-epoch=2", "-batch_size=256", "-sample=0",
         f"-rendezvous_dir={tmp_path}"],
        timeout=420).returncode
    assert rc == 0
    lines = out.read_text().strip().split("\n")
    v, d = lines[0].split()
    assert int(v) == 10 and int(d) == 16
    assert len(lines) == 11
    # the trained vectors separate the two corpus topics
    vecs = {}
    for line in lines[1:]:
        parts = line.split()
        vecs[parts[0]] = np.asarray([float(x) for x in parts[1:]])
    def cos(a, b):
        return float(a @ b / (np.linalg.norm(a) * np.linalg.norm(b) + 1e-12))
    intra = np.mean([cos(vecs[f"a{i}"], vecs[f"a{j}"])
                     for i in range(5) for j in range(i + 1, 5)])
    cross = np.mean([cos(vecs[f"a{i}"], vecs[f"b{j}"])
                     for i in range(5) for j in range(5)])
    assert intra > cross, (intra, cross)


def test_logreg_cli(tmp_path):
    from multiverso_tpu.apps.logreg_main import main

    rng = np.random.default_rng(0)
    w = rng.normal(size=8)
    train = tmp_path / "train.libsvm"
    test = tmp_path / "test.libsvm"
    for path, n in ((train, 300), (test, 100)):
        with open(path, "w") as f:
            for _ in range(n):
                x = rng.normal(size=8)
                y = int(x @ w > 0)
                feats = " ".join(f"{i}:{x[i]:.4f}" for i in range(8))
                f.write(f"{y} {feats}\n")
    conf = tmp_path / "lr.conf"
    conf.write_text("objective=sigmoid\nnum_feature=8\nlearning_rate=1.0\n"
                    "minibatch_size=32\nepochs=10\n")
    preds = tmp_path / "preds.txt"
    rc = main([f"-config_file={conf}", f"-lr_train_file={train}",
               f"-lr_test_file={test}", f"-output_file={preds}"])
    assert rc == 0
    assert len(preds.read_text().strip().split("\n")) == 100


def test_logreg_cli_distributed(tmp_path):
    """-world_size=2: two real PS ranks share the sharded weight table and
    each trains on half the samples; rank 0 tests and writes predictions."""
    import subprocess
    import sys

    rng = np.random.default_rng(1)
    w = rng.normal(size=8)
    train = tmp_path / "train.libsvm"
    test = tmp_path / "test.libsvm"
    for path, n in ((train, 400), (test, 100)):
        with open(path, "w") as f:
            for _ in range(n):
                x = rng.normal(size=8)
                y = int(x @ w > 0)
                feats = " ".join(f"{i}:{x[i]:.4f}" for i in range(8))
                f.write(f"{y} {feats}\n")
    conf = tmp_path / "lr.conf"
    conf.write_text("objective=sigmoid\nnum_feature=8\nlearning_rate=0.5\n"
                    "minibatch_size=32\nepochs=10\nsync_frequency=1\n")
    preds = tmp_path / "preds.txt"
    proc = subprocess.run(
        [sys.executable, "-m", "multiverso_tpu.apps.logreg_main",
         f"-config_file={conf}", f"-lr_train_file={train}",
         f"-lr_test_file={test}", f"-output_file={preds}", "-world_size=2",
         f"-rendezvous_dir={tmp_path}"],
        timeout=420, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert len(preds.read_text().strip().split("\n")) == 100
    # rank 0 logs a test accuracy line; the task is separable-ish
    import re
    m = re.search(r"test accuracy: (0\.\d+|1\.0+)",
                  proc.stderr + proc.stdout)
    assert m, (proc.stderr[-1500:], proc.stdout[-1500:])
    assert float(m.group(1)) > 0.85, m.group(1)


def test_lda_cli(tmp_path, capsys):
    from multiverso_tpu.apps.lda_main import main

    rng = np.random.default_rng(0)
    docs = tmp_path / "docs.txt"
    with open(docs, "w") as f:
        for i in range(60):
            lo = 0 if i % 2 == 0 else 10
            words = [f"w{rng.integers(lo, lo + 10)}" for _ in range(40)]
            f.write(" ".join(words) + "\n")
    rc = main([f"-docs_file={docs}", "-num_topics=2",
               "-lda_iterations=20", "-topn=5"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "topic   0:" in out and "topic   1:" in out
