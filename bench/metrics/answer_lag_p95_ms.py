"""Load generator: how late the harness stamps a completion, its waiter's
stamp minus the ``settled_t`` the program's ticket took when it settled,
95th percentile over the window's answers."""

from bench.metrics import p95


def read(ctx):
    lags = []
    for r in ctx.recs:
        settled = getattr(r.ticket, "settled_t", None)
        if r.response is not None and settled is not None:
            lags.append((r.resolved - settled) * 1e3)
    return p95(lags)
