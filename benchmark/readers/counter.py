"""A number the driver counted or clocked itself (``measured['counters']``),
times ``scale``."""


def read(ctx, name, scale=1.0):
    value = ctx.measured.get("counters", {}).get(name)
    return None if value is None else float(value) * scale
