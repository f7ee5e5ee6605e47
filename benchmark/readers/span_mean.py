"""Mean milliseconds per occurrence of the program's spans over the window:
the summed durations of ``spans`` over the count of the first of them (so
``recsys.pull`` + ``recsys.push`` per step)."""


def read(ctx, spans):
    got = ctx.measured.get("spans", {})
    if not spans or any(s not in got for s in spans):
        return None
    count = got[spans[0]][0]
    if count <= 0:
        return None
    return sum(got[s][1] for s in spans) / count
