"""Service: host waits on the device (device-to-host reads and ready
waits) on the served path, the ``syncs`` counter the program keeps on its
spans, per query executed in the window.  The tracer's own reads count
under ``obs_syncs`` and are left out."""

from bench.metrics import window_spans


def read(ctx):
    counted = [s for s in ctx.spans if "syncs" in s["attrs"] and s["t1"] is not None
               and ctx.t0 <= s["t0"] <= ctx.t_close]
    queries = sum(int(s["attrs"]["batch"]) for s in window_spans(ctx, "chunk.exec"))
    if not counted or not queries:
        return None
    return sum(int(s["attrs"]["syncs"]) for s in counted) / queries
