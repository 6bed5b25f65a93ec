"""Service: bytes copied from the host to the device on the served path
(admission's finiteness check and the chunk build), the ``h2d_bytes``
counter the program keeps on its spans, in MB (1e6 bytes) per query
executed in the window."""

from bench.metrics import window_spans


def read(ctx):
    counted = [s for s in ctx.spans if "h2d_bytes" in s["attrs"]
               and s["t1"] is not None and ctx.t0 <= s["t0"] <= ctx.t_close]
    queries = sum(int(s["attrs"]["batch"]) for s in window_spans(ctx, "chunk.exec"))
    if not counted or not queries:
        return None
    return sum(int(s["attrs"]["h2d_bytes"]) for s in counted) / 1e6 / queries
