"""One rank of the app-level fault drill — run as a REAL process.

Modes (argv[1] is a JSON dict):
* ``train``        — a full DistributedWord2Vec worker+shard rank: rendezvous,
                     train its corpus shard, write per-block progress marks,
                     rank 0 saves embeddings, write ``done<rank>``.
* ``seat_restart`` — restart rank R's SEAT only (service + registered table
                     shards, no training loop) after the original process was
                     SIGKILLed, and retire R's BSP clocks via finish_train —
                     the Server_Finish_Train straggler path
                     (ref src/server.cpp:190-213) driven end to end. Serves
                     until every surviving rank's done-file appears.
"""

import json
import os
import sys
import time


def _pin_cpu(repo):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, repo)


def _build(args):
    import numpy as np  # noqa: F401

    from multiverso_tpu.models.word2vec import Dictionary, Word2VecConfig

    sents = [ln.split() for ln in open(args["corpus"])]
    d = Dictionary.build(sents, min_count=1)
    ids = [d.encode(s) for s in sents]
    cfg = Word2VecConfig(**args["cfg"])
    return d, ids, cfg


def main():
    args = json.loads(sys.argv[1])
    _pin_cpu(args["repo"])

    import numpy as np

    import multiverso_tpu as mv
    from multiverso_tpu.apps._runner import rendezvous
    from multiverso_tpu.parallel.ps_service import (DistributedKVTable,
                                                    DistributedMatrixTable,
                                                    DistributedTableBase,
                                                    PSService)

    # Slow-box drill: a killed rank needs time to re-import jax before the
    # survivors' rediscovery window closes.
    DistributedTableBase.RETRY_WINDOW = float(args.get("retry_window", 60.0))

    mode = args["mode"]
    rank, world, rdv = args["rank"], args["world"], args["rdv"]
    mv.init(["-sync=true"] if args.get("sync") else [])
    d, ids, cfg = _build(args)

    if mode == "seat_restart":
        from multiverso_tpu.models.word2vec.distributed import \
            DistributedWord2Vec as W
        svc = PSService()
        # Original addresses from the rendezvous dir, ours replaced.
        peers = []
        for r in range(world):
            host, port = open(os.path.join(rdv, f"addr{r}")).read().split(":")
            peers.append((host, int(port)))
        peers[rank] = svc.address
        V, D = len(d), cfg.embedding_size
        out_rows = max((V - 1) if cfg.hs else V, 1)
        tables = [DistributedMatrixTable(W.TABLE_IN, V, D, svc, peers, rank),
                  DistributedMatrixTable(W.TABLE_OUT, out_rows, D, svc,
                                         peers, rank),
                  DistributedKVTable(W.TABLE_WORD_COUNT, svc, peers, rank,
                                     dtype=np.int64)]
        if cfg.optimizer == "adagrad":
            tables.append(DistributedMatrixTable(W.TABLE_G_IN, V, D, svc,
                                                 peers, rank))
            tables.append(DistributedMatrixTable(W.TABLE_G_OUT, out_rows, D,
                                                 svc, peers, rank))
        for t in tables:
            t.finish_train()
        open(os.path.join(rdv, f"seat{rank}"), "w").write("up")
        # Serve the (fresh) shard until the survivors all finish.
        deadline = time.time() + args.get("serve_timeout", 600)
        waiting = [r for r in range(world) if r != rank]
        while waiting and time.time() < deadline:
            waiting = [r for r in waiting
                       if not os.path.exists(os.path.join(rdv, f"done{r}"))]
            time.sleep(0.2)
        svc.close()
        mv.shutdown()
        sys.exit(0 if not waiting else 3)

    # -- mode == "train" ---------------------------------------------------
    from multiverso_tpu.models.word2vec.distributed import DistributedWord2Vec

    svc = PSService()
    peers = rendezvous(rdv, rank, world, svc.address)
    w2v = DistributedWord2Vec(cfg, d, svc, peers, rank=rank)
    progress = os.path.join(rdv, f"progress{rank}")

    def mark(block_i, words):
        with open(progress, "w") as f:
            f.write(f"{block_i} {words}")

    stats = w2v.train(ids[rank::world], on_block=mark)
    if rank == 0:
        emb = w2v.embeddings()
        np.save(os.path.join(rdv, "embeddings.npy"), emb)
    with open(os.path.join(rdv, f"stats{rank}.json"), "w") as f:
        json.dump({"words": int(stats["words"]),
                   "words_per_sec": stats["words_per_sec"]}, f)
    open(os.path.join(rdv, f"done{rank}"), "w").write("ok")
    # Hold the shard up until every peer is done (wait_all_done analog,
    # ref distributed_wordembedding.cpp:232) — but tolerate a DEAD peer:
    # the drill's async variant has no seat_restart holding the barrier.
    deadline = time.time() + args.get("serve_timeout", 600)
    expected = set(args.get("barrier_ranks", range(world)))
    while time.time() < deadline:
        if all(os.path.exists(os.path.join(rdv, f"done{r}"))
               for r in expected):
            break
        time.sleep(0.2)
    svc.close()
    mv.shutdown()
    sys.exit(0)


if __name__ == "__main__":
    main()
