"""Shared by the roofline readers: the least time of the algorithm's work in
the chunks wholly inside the profiled window, over those chunks' device
time of the layer (``bench/cost``, ``bench/peaks.json``)."""

from bench.harness import log


def share_pct(ctx, layer: str, row_work) -> float | None:
    from bench.cost import least_time

    p = ctx.profile
    if not p or ctx.peaks is None:
        return None
    ops = nbytes = least = device = 0.0
    bounds = {}
    for ch in p["chunks"]:
        dev = ch["device_s"].get(layer, 0.0)
        span = ch[layer]
        if span is None or dev <= 0:
            continue
        t_sum = 0.0
        for o, b in row_work(ctx, ch["span"], span):
            t, bound = least_time(o, b, ctx.peaks)
            t_sum += t
            ops, nbytes = ops + o, nbytes + b
            bounds[bound] = bounds.get(bound, 0) + 1
        least += t_sum
        device += dev
    if device <= 0:
        return None
    log(f"bench: {layer}_roofline ops={ops!r} bytes={nbytes!r} "
        f"least_s={least!r} device_s={device!r} bound_rows={bounds}")
    return 100.0 * least / device


def rows(ctx, chunk_span):
    """(pool item, row index) of the real queries of a chunk, in row order."""
    by_ticket = {r.ticket.index: r for r in ctx.all_recs if r.ticket is not None}
    ids = chunk_span["attrs"]["request_ids"]
    return [(ctx.pool[by_ticket[i].item], j) for j, i in enumerate(ids)
            if i in by_ticket]
