"""Service: host time from a chunk's execution end to the end of its
accounting (the per-query response reads, ticket settles, stats and
registry), the program's ``chunk.respond`` spans, per query executed in the
window."""

from bench.metrics import window_spans


def read(ctx):
    responds = window_spans(ctx, "chunk.respond")
    queries = sum(int(s["attrs"]["batch"]) for s in window_spans(ctx, "chunk.exec"))
    if not responds or not queries:
        return None
    return 1e3 * sum(s["t1"] - s["t0"] for s in responds) / queries
