"""Greedy selection work: min(k, |V'|) steps, each evaluating the marginal
gain of every retained candidate and committing the best.

Per candidate and per term: add, sqrt, subtract, accumulate for coverage
(4); subtract, max, accumulate for facility location (3); plus the argmax
(1 per candidate) and the state update (1 per term).  Bytes: the retained
candidates' rows read once, and the state written once per step.
"""

from bench.cost import F32, pair_width

GAIN_OPS = {"coverage": 4, "fl": 3}


def row_work(objective: str, n_real: int, n_features: int, k: int,
             vprime: int) -> tuple[float, float]:
    """(ops, bytes) of one query's selection over its retained set V'."""
    width = pair_width(objective, n_real, n_features)
    steps = min(k, vprime)
    ops = float(steps) * (vprime * (GAIN_OPS[objective] * width + 1) + width)
    nbytes = float(vprime) * width * F32 + float(steps) * width * F32
    return ops, nbytes
