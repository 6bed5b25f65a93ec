"""Service batching: real queries over padded batch slots, from the
``chunk.exec`` spans' ``batch`` and ``bucket`` attributes, over the window."""

from bench.metrics import window_spans


def read(ctx):
    chunks = window_spans(ctx, "chunk.exec")
    slots = sum(int(s["attrs"]["bucket"]) for s in chunks)
    if not slots:
        return None
    return 100.0 * sum(int(s["attrs"]["batch"]) for s in chunks) / slots
