"""A statistic of the host annotations (``TraceAnnotation`` names: the
program's ``span()`` phases and the benchmark's own ``bench.*``) whose name
matches ``pattern``, over the traced window, on any host thread.

``stat``: ``mean_ms`` / ``max_ms`` over the occurrences that lie WHOLLY inside
the window (one the window's edge cuts is not a whole occurrence);
``window_share``: percent of the window under the union of the matching
annotations, each clipped to the window. None where no annotation matches (a
program without the span), so the metric is left out of the line."""
import re

import trace_reduce


def matching(trace, pattern):
    rx = re.compile(pattern)
    return [(a, b) for a, b, name in trace.annotations if rx.search(name)]


def clipped(intervals, window):
    t0, t1 = window
    return [(max(a, t0), min(b, t1)) for a, b in intervals
            if b > t0 and a < t1]


def read(ctx, pattern, stat="mean_ms"):
    tr, win = ctx.trace_data, ctx.trace_window
    if tr is None or win is None or win[1] <= win[0]:
        return None
    hits = matching(tr, pattern)
    if stat == "window_share":
        cut = clipped(hits, win)
        if not cut:
            return None
        return 100.0 * trace_reduce.total(trace_reduce.union(cut)) \
            / (win[1] - win[0])
    whole = [(b - a) / 1e6 for a, b in hits if a >= win[0] and b <= win[1]]
    if not whole:
        return None
    if stat == "mean_ms":
        return sum(whole) / len(whole)
    if stat == "max_ms":
        return max(whole)
    raise ValueError(f"unknown stat {stat!r}")
