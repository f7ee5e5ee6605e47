"""chip_smoke.py off the chip: every stage runs at tiny sizes on the CPU
mesh (so a refactor cannot break the script between chip runs), and the
command itself refuses to pass without a TPU."""

import importlib.util
import os
import subprocess
import sys
import time

import jax

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SCRIPT = os.path.join(_REPO, "chip_smoke.py")


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", _SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod     # dataclasses resolves the module
    spec.loader.exec_module(mod)
    return mod


def _tiny(mod):
    # Row counts divide the 8 virtual devices (the even-shard assertion).
    return mod.Sizes(
        cli_sentences=120, table_rows=4096, table_cols=50, table_batch=256,
        vocab=2048, dim=16, batch=256, block_sentences=16, sentence_len=30,
        pad_sentence_length=32, n_sentences=32,
        lm_vocab=64, lm_dim=32, lm_heads=4, lm_layers=1, lm_seq=32,
        lm_steps=2, lm_max_new=4,
        kernel_rows=64, kernel_dim=128, kernel_batch=16,
        kernel_updaters=("adagrad",),
        attn_seq=128, attn_head_dims=(64,))


def test_every_stage_runs_tiny_on_cpu(capsys):
    mod = _load()
    device = mod.main(_tiny(mod))
    assert device == {"platform": "cpu", "kind": "cpu",
                      "count": len(jax.devices())}
    out = capsys.readouterr().out
    for name in ("cli", "dispatch", "tables", "trainer", "server", "lm",
                 "kernels"):
        assert f"chip_smoke: [{name}] ok" in out, out[-3000:]
    assert "dp2xtp2" in out                      # >= 4 devices: sharded step
    assert "rows on each of 8 devices" in out    # tables spread evenly
    assert "chip_smoke: PASSED" in out


def test_kernel_stage_runs_interpreted_on_one_device(capsys):
    """With 8 devices the runtime shards tables and the kernel stage skips;
    on one device it runs every kernel under the Pallas interpreter."""
    import multiverso_tpu as mv

    mod = _load()
    mv.init([], devices=jax.devices()[:1])
    try:
        mod.stage_kernels(_tiny(mod))
    finally:
        mv.shutdown()
    out = capsys.readouterr().out
    assert "'adagrad': 'fused_stateful'" in out
    assert "paged_decode_attn matches" in out


def test_command_refuses_to_pass_off_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    proc = subprocess.run([sys.executable, _SCRIPT], capture_output=True,
                          text=True, timeout=120, cwd=_REPO, env=env)
    assert proc.returncode not in (0, None), proc.stdout[-2000:]
    assert time.monotonic() - t0 < 60
    assert proc.stdout.strip() == "", proc.stdout       # no result printed
    assert "not a TPU" in proc.stderr
    # ...and has no way to: no option parsing, no platform override, no
    # child process.
    src = open(_SCRIPT).read()
    for banned in ("jax_platforms", "JAX_PLATFORMS", "argparse", "sys.argv",
                   "subprocess", "Popen"):
        assert banned not in src, banned
