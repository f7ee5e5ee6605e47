"""Device idle share of the traced window, in percent: 1 - the union of the
device's operation intervals over the window, averaged over the chips."""


def read(ctx):
    if ctx.trace_data is None or ctx.trace_window is None:
        return None
    share = ctx.trace_data.idle_share(ctx.trace_window)
    return None if share is None else 100.0 * share
