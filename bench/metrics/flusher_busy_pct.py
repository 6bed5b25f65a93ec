"""Service: share of the window the flusher spent executing and answering
chunks, the program's ``chunk.exec`` plus ``chunk.respond`` spans over the
window's length."""

from bench.metrics import window_spans


def read(ctx):
    responds = window_spans(ctx, "chunk.respond")
    length = ctx.t_close - ctx.t0
    if not responds or length <= 0:
        return None
    busy = sum(s["t1"] - s["t0"]
               for s in window_spans(ctx, "chunk.exec") + responds)
    return 100.0 * busy / length
