"""Service: admission to execution start, the program's ``queue.wait`` spans,
95th percentile over the window."""

from bench.metrics import p95, window_spans


def read(ctx):
    return p95((s["t1"] - s["t0"]) * 1e3 for s in window_spans(ctx, "queue.wait"))
