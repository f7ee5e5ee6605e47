#!/usr/bin/env python
"""On-chip sweep: words/sec vs (batch_size, block_sentences).

If the ~20x in-graph chunk-loop de-optimization (docs/BENCHMARK.md,
ROADMAP perf #2) carries a fixed per-iteration cost, LARGER chunks and
blocks amortize it — a pure config win needing no kernel fix. This
sweep measures that directly on the chip so the bench config can be
retuned in the same window.

Run ON the chip:  python scripts/bench_batch_sweep.py
"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> None:
    import jax

    import multiverso_tpu as mv
    from multiverso_tpu.models.word2vec import (Dictionary, Word2Vec,
                                                Word2VecConfig)

    backend = jax.devices()[0].platform
    print(f"backend: {backend}")
    on_cpu = backend == "cpu"

    rng = np.random.default_rng(0)
    vocab_size = 50_000 if not on_cpu else 5_000
    n_sent, sent_len = (1200, 500) if not on_cpu else (32, 128)
    d, zipf = Dictionary.synthetic_zipf(vocab_size, n_sent * sent_len)
    sentences = [rng.choice(vocab_size, size=sent_len, p=zipf)
                 .astype(np.int32) for _ in range(n_sent)]

    mv.init([])
    try:
        sweep = ((8192, 512), (16384, 512), (32768, 512), (65536, 512),
                 (8192, 1024), (32768, 1024)) if not on_cpu \
            else ((2048, 32),)
        for batch, block in sweep:
            if block > n_sent:
                continue
            cfg = Word2VecConfig(
                embedding_size=128, window=5, negative=5, batch_size=batch,
                sample=1e-3, sg=True, hs=False, optimizer="adagrad",
                epochs=1, pipeline=True, device_pipeline=True,
                block_sentences=block, pad_sentence_length=sent_len,
                seed=0)
            try:
                w2v = Word2Vec(cfg, d)
                w2v.train(sentences=sentences[:max(block // 128, 2)])
                w2v.trained_words = 0
                stats = w2v.train(sentences=sentences)
                print(f"batch={batch} block_sentences={block}: "
                      f"{stats['words_per_sec']:.0f} words/sec "
                      f"(loss {stats['loss']:.2f})", flush=True)
            except Exception as e:  # noqa: BLE001 - sweep survives OOMs
                print(f"batch={batch} block_sentences={block}: FAILED {e}",
                      flush=True)
    finally:
        mv.shutdown()


if __name__ == "__main__":
    main()
