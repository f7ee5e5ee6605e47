"""Summed seconds of the program's ``span.<name>`` histograms over the
process's life: for phases that run once, in set-up, before any traced
window (so the profiler never sees them). None where none of the spans was
ever observed (a program without them)."""


def read(ctx, spans):
    from multiverso_tpu.telemetry.metrics import get_registry
    registry = get_registry()
    count, total_ms = 0, 0.0
    for name in spans:
        h = registry.histogram(f"span.{name}")
        count += int(h.count)
        total_ms += float(h.sum)
    return total_ms / 1e3 if count else None
