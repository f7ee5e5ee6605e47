"""Share of the traced window, in percent, in which operations matching
``pattern`` ran on one device (``where``: ``op`` matches the HLO operation's
name, ``module`` the program's)."""


def read(ctx, pattern, where="op", device=0):
    tr, win = ctx.trace_data, ctx.trace_window
    if tr is None or win is None or device not in tr.devices():
        return None
    seconds = tr.matching_seconds(pattern, device, where, win)
    return 100.0 * seconds / ((win[1] - win[0]) / 1e9)
