"""Kernels (SS divergence hooks): least time of the SS divergence work over
SS device time (``bench/cost/ss.py``; live counts from ``alive_trace`` as
the SS span records them)."""

from bench.cost import ss
from bench.metrics.roofline import rows, share_pct


def _work(ctx, chunk_span, ss_span):
    a = ss_span["attrs"]
    obj = ctx.config["objective"]["objective"]
    for item, j in rows(ctx, chunk_span):
        lives = [d["live"] for d in a["rounds_detail"][j]]
        yield ss.row_work(obj, int(a["n"]), item.n, item.features.shape[1],
                          int(a["r"]), lives)


def read(ctx):
    return share_pct(ctx, "ss", _work)
