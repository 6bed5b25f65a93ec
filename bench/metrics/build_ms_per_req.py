"""Service: host time building each chunk's batched objective (the payload
copies to the device, padding, stacking and keys), the program's
``chunk.build`` spans, per query executed in the window."""

from bench.metrics import window_spans


def read(ctx):
    builds = window_spans(ctx, "chunk.build")
    queries = sum(int(s["attrs"]["batch"]) for s in window_spans(ctx, "chunk.exec"))
    if not builds or not queries:
        return None
    return 1e3 * sum(s["t1"] - s["t0"] for s in builds) / queries
