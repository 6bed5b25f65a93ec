"""Load generator: how late the benchmark sent each query, actual send minus
due time, 95th percentile over the window (benchmark's clock)."""

from bench.metrics import p95


def read(ctx):
    return p95((r.sent - r.due) * 1e3 for r in ctx.recs if r.sent == r.sent)
