#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Run from the repo root on a machine with a TPU: ``python chip_smoke.py``.

One process, no child process, no platform override: it takes whatever devices
jax finds, and exits non-zero at once unless the first of them is a TPU. Then
it drives the parameter-server main path once through the entry points a user
calls, at the full width of the flagship model (word2vec V=1M x D=128; depth
cut to a few blocks, weights random from a seed), every stage ending in a
device sync and a check against NumPy or against the repo's own reference
path. Any failed check raises and fails the run.

Stages, in order:

1. ``cli``     — ``apps.word2vec_main.main`` on a seeded two-topic corpus,
                 ``-dispatch_mode=auto``; exported vectors separate the topics.
2. ``tables``  — ``mv.create_table(MatrixTableOption(1M, 50))`` (the
                 reference's test_matrix_perf shape): add_rows/get_rows with
                 the default and the adagrad updater on duplicate-bearing row
                 sets, sampled rows equal a NumPy twin.
3. ``trainer`` — ``Word2Vec(cfg, dict).train`` sg-ns + AdaGrad at V=1M, D=128,
                 batch 8192, device pipeline on, synthetic Zipf corpus: finite
                 loss, all four tables changed, words trained > 0. With >= 4
                 devices also the ``mesh_data=2, mesh_model=2`` step.
4. ``server``  — ``ServingService`` + ``table.serving_runner()`` on the
                 trained input table: ``ServingClient.lookup`` over the socket
                 returns rows bitwise equal to ``table.get_rows``.
5. ``lm``      — ``AttentionLM.fit`` a few steps, then
                 ``ContinuousBatcher(paged=True)`` generates for two prompts,
                 tokens equal to ``AttentionLMRunner.run``.
6. ``kernels`` — every Pallas kernel that compiles, once, at a main-path
                 shape, against its XLA reference (single-shard tables only).

With more than one device every table's rows must be spread evenly over all
of them. The last stdout line is one JSON object naming the device.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import tempfile
import time

import numpy as np


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Stage sizes. The defaults are the chip run; tests/test_chip_smoke.py
    passes tiny ones to run every stage on CPU."""
    # cli: the verify skill's two-topic drive
    cli_sentences: int = 600
    # tables: the reference's test_matrix_perf shape (bench_matrix_table)
    table_rows: int = 1_000_000
    table_cols: int = 50
    table_batch: int = 8192
    # trainer: bench_big_vocab's configuration, three blocks deep
    vocab: int = 1_000_000
    dim: int = 128
    batch: int = 8192
    block_sentences: int = 512
    sentence_len: int = 500
    pad_sentence_length: int = 512
    n_sentences: int = 1536
    # lm: LMConfig's own defaults
    lm_vocab: int = 256
    lm_dim: int = 64
    lm_heads: int = 4
    lm_layers: int = 2
    lm_seq: int = 128
    lm_steps: int = 3
    lm_max_new: int = 8
    # kernels
    kernel_rows: int = 100_000
    kernel_dim: int = 128
    kernel_batch: int = 8192
    kernel_updaters: tuple = ("momentum_sgd", "adagrad", "ftrl")
    attn_seq: int = 512
    attn_head_dims: tuple = (64, 128)


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(f"chip_smoke check failed: {msg}")


class stage:
    """Times one stage and names it in the output and in any failure."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        say(f"[{self.name}] start")
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        dt = time.perf_counter() - self.t0
        say(f"[{self.name}] {'FAILED' if exc_type else 'ok'} in {dt:.1f}s")
        return False


def describe_device() -> dict:
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    say(f"jax={jax.__version__} platform={dev.platform} "
        f"device_kind={dev.device_kind} devices={device['count']}")
    return device


def assert_even_shards(table) -> None:
    """Every visible device holds an equal slice of the table's rows:
    nothing whole on device 0, nothing replicated."""
    import jax

    n = len(jax.devices())
    if n == 1:
        return
    arr = table.store.data
    shards = arr.addressable_shards
    rows = sorted(s.data.shape[0] for s in shards)
    holders = {s.device.id for s in shards}
    check(len(holders) == n and rows[0] == rows[-1]
          and rows[0] * n == arr.shape[0],
          f"{table.name}: rows not spread evenly over {n} devices "
          f"(per-shard rows {rows}, total {arr.shape[0]})")
    say(f"  {table.name}: {rows[0]} rows on each of {n} devices")


# ---------------------------------------------------------------------------
# 1. CLI trainer, small
# ---------------------------------------------------------------------------
def stage_cli(sz: Sizes, workdir: str) -> None:
    from multiverso_tpu.apps.word2vec_main import main as w2v_main

    rng = np.random.default_rng(0)
    corpus = os.path.join(workdir, "corpus.txt")
    out = os.path.join(workdir, "vectors.txt")
    with open(corpus, "w") as f:
        for i in range(sz.cli_sentences):
            topic = "a" if i % 2 == 0 else "b"
            f.write(" ".join(f"{topic}{rng.integers(0, 8)}"
                             for _ in range(15)) + "\n")
    rc = w2v_main([f"-train_file={corpus}", f"-output_file={out}",
                   "-size=32", "-min_count=1", "-epoch=3",
                   "-batch_size=512", "-sample=0", "-block_sentences=64",
                   "-pad_sentence_length=16", "-dispatch_mode=auto"])
    check(rc == 0, f"word2vec_main exited {rc}")
    lines = open(out).read().strip().split("\n")
    vecs = {ln.split()[0]: np.asarray(ln.split()[1:], np.float32)
            for ln in lines[1:]}
    check(len(vecs) == 16 and all(np.isfinite(v).all()
                                  for v in vecs.values()),
          f"expected 16 finite vectors, got {len(vecs)}")

    def cos(a, b):
        return float(a @ b / (np.linalg.norm(a) * np.linalg.norm(b) + 1e-12))

    a = [vecs[f"a{i}"] for i in range(8)]
    b = [vecs[f"b{i}"] for i in range(8)]
    intra = np.mean([cos(x, y) for grp in (a, b)
                     for i, x in enumerate(grp) for y in grp[i + 1:]])
    cross = np.mean([cos(x, y) for x in a for y in b])
    say(f"  topic cosine: intra {intra:.3f} vs cross {cross:.3f}")
    check(intra > cross + 0.2, f"topics not separated: intra {intra:.3f} "
          f"cross {cross:.3f}")


def report_dispatch() -> None:
    """What AUTO resolves to on this host, and the launch latency it saw."""
    from multiverso_tpu.models.word2vec import (Word2VecConfig,
                                                measured_dispatch_latency_ms,
                                                resolve_dispatch_mode)

    say(f"  launch latency {measured_dispatch_latency_ms():.3f} ms; "
        "dispatch_mode auto -> "
        f"{resolve_dispatch_mode(Word2VecConfig(device_pipeline=True))}")


# ---------------------------------------------------------------------------
# 2. PS table plane at the reference's size
# ---------------------------------------------------------------------------
def _dup_rows(rng, rows: int, n: int) -> np.ndarray:
    """Row ids with guaranteed duplicates: half from a narrow hot range."""
    hot = rng.integers(0, max(rows // 1000, 2), size=n // 2)
    cold = rng.integers(0, rows, size=n - n // 2)
    ids = np.concatenate([hot, cold]).astype(np.int32)
    rng.shuffle(ids)
    return ids


def stage_tables(sz: Sizes) -> None:
    import multiverso_tpu as mv
    from multiverso_tpu.core.options import AddOption

    rows, cols, n = sz.table_rows, sz.table_cols, sz.table_batch
    rng = np.random.default_rng(1)
    plain = mv.create_table(mv.MatrixTableOption(
        rows, cols, updater="default", name="smoke_plain"))
    ada = mv.create_table(mv.MatrixTableOption(
        rows, cols, updater="adagrad", name="smoke_adagrad"))
    assert_even_shards(plain)
    assert_even_shards(ada)

    twin_plain = np.zeros((rows, cols), np.float32)
    twin_ada = np.zeros((rows, cols), np.float32)
    twin_g2 = np.zeros((rows, cols), np.float32)
    opt = AddOption(learning_rate=0.1, rho=0.1)
    touched = []
    for _ in range(3):
        ids = _dup_rows(rng, rows, n)
        check(len(np.unique(ids)) < len(ids), "row set has no duplicates")
        deltas = rng.normal(size=(n, cols)).astype(np.float32)
        plain.add_rows(ids, deltas)
        ada.add_rows(ids, deltas, opt)
        np.add.at(twin_plain, ids, deltas)
        # AdaGrad folds duplicates into one combined delta per row, then
        # G += (delta/lr)^2; data -= rho / sqrt(G + eps) * delta/lr.
        uniq, inv = np.unique(ids, return_inverse=True)
        comb = np.zeros((len(uniq), cols), np.float32)
        np.add.at(comb, inv, deltas)
        g = comb / np.float32(opt.learning_rate)
        twin_g2[uniq] += g * g
        twin_ada[uniq] -= (np.float32(opt.rho)
                           / np.sqrt(twin_g2[uniq] + np.float32(1e-6)) * g)
        touched.append(uniq)
    sample = np.concatenate(
        [np.concatenate(touched)[:: max(len(touched[0]) // 256, 1)],
         rng.integers(0, rows, size=256)]).astype(np.int32)
    got_plain = plain.get_rows(sample)
    got_ada = ada.get_rows(sample)
    check(got_plain.shape == (len(sample), cols), "get_rows shape")
    np.testing.assert_allclose(got_plain, twin_plain[sample], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(got_ada, twin_ada[sample], rtol=2e-4,
                               atol=2e-5)
    check(np.abs(got_ada).sum() > 0, "adagrad table never moved")
    say(f"  {rows}x{cols}: 3 x {n} row adds (default + adagrad), "
        f"{len(sample)} sampled rows match NumPy")


# ---------------------------------------------------------------------------
# 3. Trainer at full width
# ---------------------------------------------------------------------------
def _train_w2v(sz: Sizes, dictionary, sentences, **mesh):
    from multiverso_tpu.models.word2vec import Word2Vec, Word2VecConfig

    cfg = Word2VecConfig(
        embedding_size=sz.dim, window=5, negative=5, batch_size=sz.batch,
        sample=1e-3, sg=True, hs=False, optimizer="adagrad", epochs=1,
        pipeline=True, device_pipeline=True,
        block_sentences=sz.block_sentences,
        pad_sentence_length=sz.pad_sentence_length, seed=0, **mesh)
    w2v = Word2Vec(cfg, dictionary)
    tables = {"input": w2v.input_table, "output": w2v.output_table,
              "adagrad_in": w2v.adagrad_in, "adagrad_out": w2v.adagrad_out}
    if not mesh:     # the dp x tp step re-lays them over its own axes
        for t in tables.values():
            assert_even_shards(t)
    # Zipf: the lowest ids are the most frequent words, certainly trained.
    probe = np.arange(min(256, sz.vocab), dtype=np.int32)
    before = {k: t.get_rows(probe) for k, t in tables.items()}
    stats = w2v.train(sentences=sentences)   # ends in block_until_ready
    check(np.isfinite(stats["loss"]) and stats["loss"] > 0,
          f"loss {stats['loss']}")
    check(stats["words"] > 0 and stats["pairs"] > 0,
          f"nothing trained: {stats}")
    for k, t in tables.items():
        after = t.get_rows(probe)
        check(np.isfinite(after).all(), f"{k} table has non-finite rows")
        check(np.abs(after - before[k]).max() > 0,
              f"{k} table did not change")
    tag = (f"dp{mesh['mesh_data']}xtp{mesh['mesh_model']}" if mesh
           else f"auto -> {stats['dispatch_mode']}")
    say(f"  V={sz.vocab} D={sz.dim} [{tag}]: {stats['words']} words, "
        f"{stats['pairs']} pairs in {stats['seconds']:.1f}s "
        f"(compile included), loss {stats['loss']:.1f}; all four tables "
        "changed")
    return w2v


def stage_trainer(sz: Sizes):
    import jax

    from multiverso_tpu.models.word2vec import Dictionary

    rng = np.random.default_rng(3)
    dictionary, zipf = Dictionary.synthetic_zipf(sz.vocab, int(1e8))
    sentences = list(rng.choice(sz.vocab, size=(sz.n_sentences,
                                                sz.sentence_len), p=zipf)
                     .astype(np.int32))
    w2v = _train_w2v(sz, dictionary, sentences)
    if len(jax.devices()) >= 4:
        _train_w2v(sz, dictionary, sentences, mesh_data=2, mesh_model=2)
    return w2v


# ---------------------------------------------------------------------------
# 4. A server that answers
# ---------------------------------------------------------------------------
def stage_server(sz: Sizes, w2v) -> None:
    from multiverso_tpu.serving import ServingClient, ServingService

    table = w2v.input_table
    service = ServingService()
    client = None
    try:
        service.register_runner(table.serving_runner(), buckets=(16,),
                                max_batch=8, max_wait_ms=1.0)
        client = ServingClient(*service.address)
        rng = np.random.default_rng(4)
        for _ in range(4):
            keys = rng.integers(0, sz.vocab, size=16).astype(np.int32)
            got = client.lookup(keys, deadline_ms=60_000, timeout=300)
            want = table.get_rows(keys)
            check(got.shape == want.shape and got.dtype == want.dtype
                  and np.array_equal(got, want),
                  "served rows differ from table.get_rows")
        say(f"  4 x 16-row lookups over {service.address[0]}:"
            f"{service.address[1]} bitwise equal to get_rows")
    finally:
        if client is not None:
            client.close()
        service.close()


def stage_lm(sz: Sizes) -> None:
    from multiverso_tpu.models.attention_lm import AttentionLM, LMConfig
    from multiverso_tpu.serving import AttentionLMRunner, ContinuousBatcher

    cfg = LMConfig(vocab=sz.lm_vocab, dim=sz.lm_dim, heads=sz.lm_heads,
                   layers=sz.lm_layers, seq=sz.lm_seq)
    lm = AttentionLM(cfg)
    rng = np.random.default_rng(5)
    batch = max(lm.mesh.shape["data"], 2)
    losses = lm.fit([rng.integers(0, cfg.vocab, size=(batch, cfg.seq))
                     for _ in range(sz.lm_steps)])
    check(len(losses) == sz.lm_steps and np.isfinite(losses).all(),
          f"LM losses {losses}")
    say(f"  AttentionLM.fit on a {dict(lm.mesh.shape)} mesh: losses "
        + ", ".join(f"{x:.3f}" for x in losses))

    params = {k: np.asarray(v) for k, v in lm.params.items()}
    max_batch, bucket = 4, 16
    runner = AttentionLMRunner(params, cfg, max_new=sz.lm_max_new,
                               max_batch=max_batch)
    prompts = [[5, 9, 2, 11], [7, 3, 3, 3, 8, 2, 40, 1, 6]]
    want = []
    for p in prompts:
        mat = np.zeros((max_batch, bucket), np.int32)
        mat[0, :len(p)] = p
        lens = np.zeros(max_batch, np.int32)
        lens[0] = len(p)
        want.append(np.asarray(runner.run(mat, lens))[0].tolist())
    batcher = ContinuousBatcher(runner, buckets=(bucket,),
                                max_batch=max_batch, paged=True, page=4)
    try:
        futs = [batcher.submit(np.asarray(p, np.int32),
                               deadline_ms=600_000) for p in prompts]
        got = [f.wait(600).tolist() for f in futs]
    finally:
        batcher.close()
    check(all(len(g) == sz.lm_max_new for g in got), f"token counts {got}")
    check(got == want, f"paged continuous decode {got} != drain path {want}")
    say(f"  ContinuousBatcher(paged=True): {len(prompts)} prompts x "
        f"{sz.lm_max_new} tokens equal to AttentionLMRunner.run")


# ---------------------------------------------------------------------------
# 6. The Pallas kernels, once each, against their XLA references
# ---------------------------------------------------------------------------
def stage_kernels(sz: Sizes) -> None:
    import jax
    import jax.numpy as jnp

    import multiverso_tpu as mv
    from multiverso_tpu.core.options import AddOption
    from multiverso_tpu.ops import pallas_interpret
    from multiverso_tpu.ops.pallas_attention import paged_decode_attn

    if mv.num_servers() > 1:
        say("  skipped: the row kernels are single-shard (core/table.py) "
            f"and this runtime shards tables over {mv.num_servers()} "
            "devices")
        return
    interpret = pallas_interpret(jax.devices())
    rows, d, n = sz.kernel_rows, sz.kernel_dim, sz.kernel_batch
    rng = np.random.default_rng(6)
    ids = np.sort(_dup_rows(rng, rows, n))
    deltas = rng.normal(size=(n, d)).astype(np.float32)

    opt = AddOption(learning_rate=0.1, rho=0.1, momentum=0.5)
    planes = {}
    from multiverso_tpu.core.table import build_row_update
    for upd in sz.kernel_updaters:
        pal = mv.create_table(mv.MatrixTableOption(
            rows, d, updater=upd, name=f"smoke_p_{upd}"))
        planes[upd] = pal.store.row_plane
        check(pal.store.row_plane == "fused_stateful",
              f"a one-shard {rows}x{d} float32 {upd} table picked the "
              f"plane {pal.store.row_plane}")
        # The XLA plane's row update of the same updater, over copies: a
        # stateful table of this shape picks the fused kernel by itself,
        # so no second table is the reference.
        xla_update = jax.jit(build_row_update(pal.store.updater, False))
        xla = (jnp.array(pal.store.data),
               jax.tree_util.tree_map(jnp.array, pal.store.state))
        for _ in range(2):
            pal.add_rows(ids, deltas, opt)
            xla = xla_update(*xla, jnp.asarray(ids), jnp.asarray(deltas),
                             *opt.scalars())
        sample = np.unique(ids)[:512]
        # The hot rows fold ~80 duplicate deltas each, in a different
        # order on the two planes: f32 sums of magnitude ~20 agree to 1e-4.
        np.testing.assert_allclose(pal.get_rows(sample),
                                   np.asarray(xla[0][sample]), rtol=1e-5,
                                   atol=1e-4, err_msg=f"fused rows {upd}")
    say(f"  the row planes the stores picked, {planes}, match the XLA row "
        "updates")

    _kernel_flash(sz)

    h, dh, page, bucket, b = 4, 16, 16, 64, 8
    g = (bucket + 32) // page
    q = jnp.asarray(rng.normal(size=(b, h, dh)).astype(np.float32))
    kp = jnp.asarray(rng.normal(size=(64, h, page, dh)).astype(np.float32))
    vp = jnp.asarray(rng.normal(size=(64, h, page, dh)).astype(np.float32))
    ptab = jnp.asarray(rng.integers(0, 64, (b, g)).astype(np.int32))
    lengths = jnp.asarray(rng.integers(1, bucket, b).astype(np.int32))
    t = jnp.asarray(rng.integers(0, 31, b).astype(np.int32))
    scale = 1.0 / np.sqrt(dh)
    kf = jnp.take(kp, ptab, axis=0).transpose(0, 2, 1, 3, 4) \
        .reshape(b, h, g * page, dh)
    vf = jnp.take(vp, ptab, axis=0).transpose(0, 2, 1, 3, 4) \
        .reshape(b, h, g * page, dh)
    slot = jnp.arange(g * page)[None, :]
    mask = (slot < lengths[:, None]) | \
        ((slot >= bucket) & (slot <= (bucket + t)[:, None]))
    s = jnp.einsum("bhd,bhkd->bhk", q, kf) * scale
    probs = jax.nn.softmax(jnp.where(mask[:, None], s, -jnp.inf), axis=-1)
    want = np.asarray(jnp.einsum("bhk,bhkd->bhd", probs, vf))
    got = np.asarray(paged_decode_attn(
        q, kp, vp, ptab, lengths, t, bucket=bucket, page=page,
        scale=float(scale), interpret=interpret))
    # default matmul precision differs between the two formulations
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2,
                               err_msg="paged_decode_attn")
    say("  paged_decode_attn matches the gather-then-attend step")


def _kernel_flash(sz: Sizes) -> None:
    """flash_block_attn through ``-flash_attention=true`` on the ring LM
    (forward: the kernel has no backward pass), per head dim."""
    import jax

    import multiverso_tpu as mv
    from multiverso_tpu.models.attention_lm import AttentionLM, LMConfig

    rng = np.random.default_rng(8)
    for dh in sz.attn_head_dims:
        cfg = LMConfig(vocab=256, dim=4 * dh, heads=4, layers=1,
                       seq=sz.attn_seq, seq_parallel=1, data_parallel=1)
        lm = AttentionLM(cfg, devices=jax.devices()[:1])
        tokens = rng.integers(0, 256, size=(2, cfg.seq))
        want = lm.loss(tokens)
        mv.set_flag("flash_attention", True)
        try:
            got = lm.loss(tokens)
        finally:
            mv.set_flag("flash_attention", False)
        check(np.isfinite(got) and abs(got - want) <= 2e-2 * abs(want),
              f"flash loss {got} vs XLA {want} at head dim {dh}")
    say(f"  flash_block_attn via -flash_attention=true on the ring LM: "
        f"loss matches XLA at head dims {list(sz.attn_head_dims)}")


# ---------------------------------------------------------------------------
def main(sizes: Sizes = Sizes()) -> dict:
    """Run every stage on the devices jax finds; returns the device record.
    Raises on the first failed check."""
    import jax

    import multiverso_tpu as mv

    t0 = time.perf_counter()
    device = describe_device()
    say(f"compile cache: {jax.config.jax_compilation_cache_dir}")
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        with stage("cli"):
            stage_cli(sizes, workdir)
    mv.init([])
    try:
        with stage("dispatch"):
            report_dispatch()
        with stage("tables"):
            stage_tables(sizes)
        with stage("trainer"):
            w2v = stage_trainer(sizes)
        with stage("server"):
            stage_server(sizes, w2v)
        with stage("lm"):
            stage_lm(sizes)
        with stage("kernels"):
            stage_kernels(sizes)
    finally:
        mv.shutdown()
    say(f"PASSED: every stage, on {device['count']} x {device['kind']} "
        f"({device['platform']}), in {time.perf_counter() - t0:.1f}s")
    return device


def cli() -> int:
    """The command line: no option, and no way to pass off the chip."""
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"chip_smoke: jax found platform={platform!r}, not a TPU; "
              "nothing run", file=sys.stderr, flush=True)
        return 2
    device = main()
    sys.stdout.flush()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(cli())
