"""Share of the chip's HBM bandwidth, in percent: the bytes the algorithm
needs for the work counted in the traced window (``byte_models``) over the
device time that work took, over the published peak. ``time`` is ``busy``
(the device's busy union) or a pattern of operations or programs."""
import byte_models


def read(ctx, model, count, time="busy", pattern=None, where="op"):
    tr, win = ctx.trace_data, ctx.trace_window
    units = ctx.measured.get("counters", {}).get(count)
    if tr is None or win is None or not units or not tr.devices():
        return None
    c = ctx.config
    if model == "sgns_adagrad_pair":
        per_unit = byte_models.sgns_adagrad_bytes_per_pair(
            c["embedding_size"], c["negative"])
    elif model == "gather_row":
        per_unit = byte_models.gather_bytes_per_row(c["embedding_size"])
    else:
        raise ValueError(f"unknown byte model {model!r}")
    if time == "busy":
        seconds = tr.busy_s(win)
    else:
        seconds = tr.matching_seconds(pattern, tr.devices()[0], where, win)
    if seconds <= 0:
        return None
    peak = byte_models.peaks(ctx.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * units * per_unit / seconds / peak
