#!/usr/bin/env python
"""Two-process distributed word2vec over the DCN PS service.

Spawns two worker processes on this host; each owns half the embedding
tables, trains on half the corpus (pull-train-push), and the merged global
embeddings separate the corpus topics.

Run:  python examples/distributed_word2vec_demo.py
Each worker holds a device: on a TPU host the launcher gives each its own
chip (multiverso_tpu/utils/chips.py); ``JAX_PLATFORMS=cpu`` runs anywhere.
"""

import os
import subprocess
import sys
import tempfile

import numpy as np

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

WORKER = r"""
import os, sys, time
import numpy as np
import multiverso_tpu as mv
from multiverso_tpu.models.word2vec import Dictionary, Word2VecConfig
from multiverso_tpu.models.word2vec.distributed import DistributedWord2Vec
from multiverso_tpu.parallel.ps_service import PSService

rank, workdir = int(sys.argv[1]), sys.argv[2]
mv.init([])
svc = PSService()
with open(os.path.join(workdir, f"addr{rank}"), "w") as f:
    f.write(f"{svc.address[0]}:{svc.address[1]}")
other = os.path.join(workdir, f"addr{1 - rank}")
while not os.path.exists(other):
    time.sleep(0.05)
host, port = open(other).read().split(":")
peers = [None, None]
peers[rank] = svc.address
peers[1 - rank] = (host, int(port))

sents = [l.split() for l in open(os.path.join(workdir, "corpus.txt"))]
d = Dictionary.build(sents, min_count=1)
ids = [d.encode(s) for s in sents][rank::2]     # my half of the corpus
cfg = Word2VecConfig(embedding_size=32, window=4, negative=5, min_count=1,
                     sample=0, epochs=3, learning_rate=0.1,
                     optimizer="adagrad", block_words=2000, pipeline=False)
w2v = DistributedWord2Vec(cfg, d, svc, peers, rank=rank)
stats = w2v.train(ids)
print(f"rank {rank}: {stats['words']} words "
      f"at {stats['words_per_sec']:.0f} words/sec", flush=True)

if rank == 0:
    emb = w2v.embeddings()
    emb = emb / (np.linalg.norm(emb, axis=1, keepdims=True) + 1e-12)
    for word in ("a0", "b0"):
        wid = d.word2id[word]
        sims = emb @ emb[wid]
        top = np.argsort(-sims)[1:4]
        print(f"  {word} -> " +
              ", ".join(f"{d.words[i]} ({sims[i]:.2f})" for i in top),
              flush=True)
# hold the service open until the peer finishes too
with open(os.path.join(workdir, f"done{rank}"), "w") as f:
    f.write("ok")
while not os.path.exists(os.path.join(workdir, f"done{1 - rank}")):
    time.sleep(0.05)
mv.shutdown()
"""


def main() -> int:
    workdir = tempfile.mkdtemp(prefix="dw2v_")
    rng = np.random.default_rng(0)
    with open(os.path.join(workdir, "corpus.txt"), "w") as f:
        for i in range(400):
            topic = "a" if i % 2 == 0 else "b"
            f.write(" ".join(f"{topic}{rng.integers(0, 5)}"
                             for _ in range(12)) + "\n")
    script = os.path.join(workdir, "worker.py")
    with open(script, "w") as f:
        f.write(WORKER)
    sys.path.insert(0, REPO)
    from multiverso_tpu.utils.chips import child_env

    procs = []
    for r in range(2):
        env = child_env(r, 2, "distributed_word2vec_demo") or dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        procs.append(subprocess.Popen(
            [sys.executable, script, str(r), workdir], env=env))
    rc = 0
    for p in procs:
        p.wait(timeout=600)
        rc |= p.returncode
    return rc


if __name__ == "__main__":
    sys.exit(main())
