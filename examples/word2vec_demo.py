#!/usr/bin/env python
"""End-to-end word2vec demo: generate a corpus, train, inspect neighbors.

Run:  python examples/word2vec_demo.py
(Runs on the devices jax finds; ``JAX_PLATFORMS=cpu`` for a CPU run.
Everything else is self-contained — the demo writes its corpus to a temp
dir.)
"""

import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))



def make_corpus(path: str, n_sentences: int = 2000) -> None:
    """Three word 'topics' with distinct co-occurrence patterns."""
    rng = np.random.default_rng(0)
    topics = {
        "fruit": ["apple", "pear", "banana", "grape", "melon", "juice"],
        "metal": ["iron", "steel", "copper", "forge", "alloy", "rust"],
        "ocean": ["wave", "tide", "coral", "reef", "fish", "salt"],
    }
    with open(path, "w") as f:
        names = list(topics)
        for i in range(n_sentences):
            words = rng.choice(topics[names[i % 3]], size=18)
            f.write(" ".join(words) + "\n")


def main() -> int:
    import multiverso_tpu as mv
    from multiverso_tpu.models.word2vec import (Dictionary, Word2Vec,
                                                Word2VecConfig, read_corpus)

    workdir = tempfile.mkdtemp(prefix="w2v_demo_")
    corpus = os.path.join(workdir, "corpus.txt")
    make_corpus(corpus)

    mv.init([])
    try:
        dictionary = Dictionary.build(read_corpus(corpus), min_count=1)
        print(f"vocabulary: {len(dictionary)} words, "
              f"{dictionary.total_count} tokens")
        cfg = Word2VecConfig(embedding_size=64, window=4, negative=5,
                             min_count=1, sample=0, epochs=3,
                             batch_size=1024, learning_rate=0.05)
        w2v = Word2Vec(cfg, dictionary)
        stats = w2v.train(corpus_path=corpus)
        print(f"trained {stats['words']} words "
              f"at {stats['words_per_sec']:.0f} words/sec")
        for word in ("apple", "iron", "wave"):
            neighbors = ", ".join(
                f"{w} ({s:.2f})" for w, s in w2v.most_similar(word, 3))
            print(f"  {word:8s} -> {neighbors}")
        out = os.path.join(workdir, "vectors.txt")
        w2v.save(out)
        print(f"embeddings written to {out}")
        return 0
    finally:
        mv.shutdown()


if __name__ == "__main__":
    sys.exit(main())
