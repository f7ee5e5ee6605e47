"""Percent of one device's IDLE time in the traced window that lies under the
union of the host annotations matching ``pattern`` (any host thread), or,
with ``unattributed``, under NO program annotation (every annotation but the
benchmark's own ``bench.*``).

Each idle gap is split at annotation edges: a gap half under a phase counts
half, and an enclosing annotation takes nothing from the phases nested in it
or running on other threads (``trace_reduce.idle_gaps`` gives each gap whole
to one name). None where the trace has no such device, the device never
idled, or no annotation matches ``pattern`` (a program without the span)."""
import re

import trace_reduce

BENCH = re.compile(r"^bench\.")


def idle_gaps(trace, window, device):
    """The complement of the device's busy union inside the window."""
    t0, t1 = window
    gaps, cur = [], t0
    for a, b in trace.busy(device, window):
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if cur < t1:
        gaps.append((cur, t1))
    return gaps


def overlap(xs, ys):
    """Total length of the intersection of two sorted disjoint covers."""
    i = j = 0
    out = 0.0
    while i < len(xs) and j < len(ys):
        lo, hi = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if hi > lo:
            out += hi - lo
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_seconds_under(trace, window, device, keep):
    """(idle seconds under the union of the annotations ``keep(name)``
    admits, idle seconds in all, how many annotations it admitted)."""
    gaps = idle_gaps(trace, window, device)
    hits = [(a, b) for a, b, name in trace.annotations if keep(name)]
    cover = trace_reduce.union(hits)
    return (overlap(gaps, cover) / 1e9, trace_reduce.total(gaps) / 1e9,
            len(hits))


def read(ctx, pattern=None, unattributed=False, device=0):
    tr, win = ctx.trace_data, ctx.trace_window
    if tr is None or win is None or device not in tr.devices():
        return None
    if unattributed:
        under, idle, _ = idle_seconds_under(
            tr, win, device, lambda name: not BENCH.match(name))
        return None if idle <= 0 else 100.0 * (idle - under) / idle
    rx = re.compile(pattern)
    under, idle, n = idle_seconds_under(
        tr, win, device, lambda name: bool(rx.search(name)))
    if idle <= 0 or n == 0:
        return None
    return 100.0 * under / idle
