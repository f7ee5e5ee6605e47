"""Share of the chip's peak that a model's step programs reach, in percent:
the operations the MODEL needs for a step (``<flops>.train_flops(config,
per_step)``, ``flops`` the name of a module beside the harness, ``per_step``
the window's counts that module names in its ``COUNTS`` over the window's
steps; rematerialisation not counted) over the mean DEVICE time of the step
programs that ran wholly inside the traced window (reader ``module_mean_ms``
on ``pattern``) over the published bf16 peak. None where the trace has no such
program or the window lacks one of the counts (a program without the
counters)."""
import importlib
import os

import byte_models
import harness

_BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(ctx, pattern, flops, device=0):
    model = importlib.import_module(flops)
    counters = ctx.measured.get("counters", {})
    steps = counters.get("steps")
    if not steps or not all(counters.get(name) for name in model.COUNTS):
        return None
    mean_ms = harness.load_module("readers", "module_mean_ms",
                                  _BENCH_DIR).read(ctx, pattern, device)
    if not mean_ms:
        return None
    config = dict(ctx.config)
    if ctx.device["platform"] != "tpu":
        config.update(config.get("tiny", {}))
    needed = model.train_flops(
        config, {name: counters[name] / steps for name in model.COUNTS})
    peak = byte_models.peaks(ctx.device["kind"])["bf16_flops_per_s"]
    return 100.0 * needed / (mean_ms / 1e3) / peak
