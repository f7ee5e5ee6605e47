"""Share of the chip's peak that a layer-typed LM's step programs reach, in
percent: the operations the MODEL needs for the steps whose program ran wholly
inside the traced window (``lm_models.train_flops`` from the window's counted
tokens, sequence length and held assignments, per step; rematerialisation not
counted) over those programs' DEVICE time (the trace's ``XLA Modules`` events
matching ``pattern``) over the published bf16 peak. None where the trace has no
such program or the window counted no token (a program without the counters)."""
import re

import byte_models
import lm_models
import trace_reduce


def read(ctx, pattern, device=0):
    tr, win = ctx.trace_data, ctx.trace_window
    counters = ctx.measured.get("counters", {})
    steps, tokens = counters.get("steps"), counters.get("lm_tokens")
    if tr is None or win is None or device not in tr.modules \
            or not steps or not tokens:
        return None
    rx = re.compile(pattern)
    runs = [(b - a) / 1e9 for a, b, name in tr.modules[device]
            if a >= win[0] and b <= win[1]
            and rx.search(trace_reduce.module_name(name))]
    if not runs or sum(runs) <= 0:
        return None
    config = dict(ctx.config)
    if ctx.device["platform"] != "tpu":
        config.update(config.get("tiny", {}))
    per_step = lm_models.train_flops(
        config, tokens / steps, counters["lm_seq_len"],
        counters.get("lm_assignments_held", 0) / steps)
    peak = byte_models.peaks(ctx.device["kind"])["bf16_flops_per_s"]
    return 100.0 * per_step * len(runs) / sum(runs) / peak
