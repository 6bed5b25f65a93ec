"""Per-layer metric readers, one module per metric, found by the metric's
name in BENCHMARK.json.  Each ``read(ctx)`` takes a :class:`bench.trace.Context`
and returns a number, or None when the run gave it nothing to read."""

import numpy as np


def p95(values) -> float | None:
    values = list(values)
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), 95))


def window_spans(ctx, name: str) -> list[dict]:
    """The program's spans of one name that started in the main window."""
    return [s for s in ctx.spans if s["name"] == name and s["t1"] is not None
            and ctx.t0 <= s["t0"] <= ctx.t_close]
