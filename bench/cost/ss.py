"""SS divergence work (Algorithm 1): each round probes m elements and
computes w(u, v) = f(v | u) - f(v | V \\ v) for every live candidate v
against every probe u, then keeps each candidate's minimum.

Per (probe, candidate) pair and per term of the gain: add, concave
transform (sqrt) or max, subtract, accumulate for coverage (4); max,
subtract, accumulate for facility location (3); plus the residual
subtraction and the running minimum (2).  Bytes: the row's ground set read
once, and its divergence vector written once.
"""

from bench.cost import F32, pair_width, probe_count

PAIR_OPS = {"coverage": 4, "fl": 3}


def row_work(objective: str, n: int, n_real: int, n_features: int, r: int,
             live_after: list[int]) -> tuple[float, float]:
    """(ops, bytes) of one query's SS, from its live count after each round
    (``alive_trace``).  ``n`` is the ground-set size SS ran on (it sets m),
    ``n_real`` the query's own size (its live count before round 1)."""
    m = min(probe_count(n, r), n)
    width = pair_width(objective, n_real, n_features)
    ops = 0.0
    live = n_real
    for after in live_after:
        ops += float(live) * m * (PAIR_OPS[objective] * width + 2)
        live = after
    nbytes = float(n_real) * width * F32 + float(n_real) * F32
    return ops, nbytes
