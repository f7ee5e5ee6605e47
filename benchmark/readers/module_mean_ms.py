"""Mean DEVICE duration, in milliseconds, of the executed programs (the
trace's ``XLA Modules`` events, named ``jit_<fn>``) whose name matches
``pattern`` and that ran wholly inside the traced window, on one device:
what a block or a step costs the chip, where the program's own span around
the launch times only an asynchronous dispatch."""
import re

import trace_reduce


def read(ctx, pattern, device=0):
    tr, win = ctx.trace_data, ctx.trace_window
    if tr is None or win is None or device not in tr.modules:
        return None
    rx = re.compile(pattern)
    runs = [(b - a) / 1e6 for a, b, name in tr.modules[device]
            if a >= win[0] and b <= win[1]
            and rx.search(trace_reduce.module_name(name))]
    return sum(runs) / len(runs) if runs else None
