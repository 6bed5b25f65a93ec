"""Kernels (greedy gains hooks): least time of greedy's gains over the
retained candidates, min(k, |V'|) steps, over greedy device time
(``bench/cost/greedy.py``; |V'| from the SS span)."""

from bench.cost import greedy
from bench.metrics.roofline import rows, share_pct


def _work(ctx, chunk_span, greedy_span):
    ss_span = [c for c in ctx.profile["chunks"] if c["span"] is chunk_span][0]["ss"]
    sizes = ss_span["attrs"]["vprime_size"]
    obj = ctx.config["objective"]["objective"]
    for item, j in rows(ctx, chunk_span):
        yield greedy.row_work(obj, item.n, item.features.shape[1], item.k,
                              int(sizes[j]))


def read(ctx):
    return share_pct(ctx, "greedy", _work)
