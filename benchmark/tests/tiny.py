"""A tiny copy of the benchmark for CPU rehearsals: the same files, the same
harness, configurations and mixes cut to sizes a test run can hold."""
from __future__ import annotations

import json
import os
import shutil

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

TINY_CONFIG = {
    "w2v-sgns-4m": {"vocab": 2000, "embedding_size": 16,
                    "corpus_words": 200000},
    "w2v-sgns-8m-x4": {"vocab": 2048, "embedding_size": 16,
                       "corpus_words": 200000},
    "dlrm-criteo-tb": {"rows_per_table": 512, "fields": 4, "embed_dim": 16,
                       "bottom_mlp": [32, 16], "top_mlp": [32, 16]},
}
TINY_TRAFFIC = {
    "sgns_zipf_b8192": {"batch_size": 256, "block_sentences": 8,
                        "pad_sentence_length": 64, "sentence_words": 50,
                        "blocks": 2, "trace_seconds": 1,
                        "limits": {"first_block_pairs_rel_gap": 0.5,
                                   "first_block_tables_moved": 1e-6,
                                   "step_loss_rel_gap": 1e-5,
                                   "step_grad_norm_rel_gap": 1e-5,
                                   "step_rows_rel_gap": 1e-4}},
    "impressions_zipf_b2048": {"batch": 64, "batches": 4,
                               "trace_seconds": 1,
                               "limits": {"step_loss_rel_gap": 1e-5,
                                          "dense_rel_gap": 1e-3,
                                          "rows_rel_gap": 1e-3}},
    "lookup_open_r80": {"rate": 40, "trace_seconds": 1, "sample": 16,
                        "straggler_s": 5},
}


def make_root(tmp: str) -> tuple:
    """(root, bench_dir) of a tiny benchmark under ``tmp``."""
    bench = os.path.join(tmp, "benchmark")
    shutil.copytree(BENCH_DIR, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests", "tools"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"),
                os.path.join(tmp, "BENCHMARK.json"))
    for kind, table in (("configs", TINY_CONFIG), ("traffic", TINY_TRAFFIC)):
        for name, changes in table.items():
            path = os.path.join(bench, kind, name + ".json")
            if not os.path.isfile(path):
                continue
            with open(path) as f:
                data = json.load(f)
            data.update(changes)
            with open(path, "w") as f:
                json.dump(data, f)
    return tmp, bench
