"""The one traffic generator: a cell's arrival schedule from its mix file.

A mix file (``bench/workloads/<traffic>.json``) holds parameters only:

- ``arrivals``: ``"poisson"`` (exponential gaps) or ``"gamma"`` (Gamma gaps
  with coefficient of variation ``cv``; ``cv = 1`` is Poisson);
- ``rate_per_s``: the offered rate;
- ``close``: ``"last_answer"`` (the window closes when the last query due
  in it is answered) or ``"seconds"`` (answers within ``--seconds`` count).

Arrivals are the renewal process conditioned on its count: exactly
``N = round(rate * seconds)`` due times in ``[0, seconds)``, so the offered
work does not vary with the seed.  Each pool item is used equally often
(the item sequence is a run of seeded permutations of the pool), so every
seed sends the same sizes, in another order and at other times.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from bench.data import rng_for

ARRIVALS = ("poisson", "gamma")
CLOSES = ("last_answer", "seconds")


@dataclasses.dataclass(frozen=True)
class Schedule:
    due_s: np.ndarray       # (N,) offsets from the window's open, ascending
    item: np.ndarray        # (N,) pool index of each query
    key: np.ndarray         # (N,) each query's PRNG seed


def validate(mix: dict) -> dict:
    if mix.get("arrivals") not in ARRIVALS:
        raise ValueError(f"arrivals must be one of {ARRIVALS}: {mix!r}")
    if mix.get("close") not in CLOSES:
        raise ValueError(f"close must be one of {CLOSES}: {mix!r}")
    if not float(mix.get("rate_per_s", 0)) > 0:
        raise ValueError(f"rate_per_s must be positive: {mix!r}")
    if mix["arrivals"] == "gamma" and not float(mix.get("cv", 0)) > 0:
        raise ValueError(f"gamma arrivals need cv > 0: {mix!r}")
    return mix


def arrival_count(mix: dict, seconds: float) -> int:
    return max(1, int(round(float(mix["rate_per_s"]) * seconds)))


def due_times(mix: dict, seconds: float, rng: np.random.Generator) -> np.ndarray:
    """N due times in [0, seconds): N + 1 renewal gaps scaled to span the
    window, so the count is fixed and the gaps keep their shape."""
    n = arrival_count(mix, seconds)
    cv = 1.0 if mix["arrivals"] == "poisson" else float(mix["cv"])
    shape = 1.0 / (cv * cv)
    gaps = rng.gamma(shape, 1.0, size=n + 1)
    return seconds * np.cumsum(gaps)[:n] / gaps.sum()


def schedule(mix: dict, seconds: float, pool_size: int, seed: int,
             stream: int = 0) -> Schedule:
    """The arrival schedule of one run.  ``stream`` separates the window's
    traffic (0) from warm-up traffic drawn under the same seed."""
    validate(mix)
    rng = rng_for(seed, 3, stream)
    due = due_times(mix, seconds, rng)
    n = len(due)
    reps = -(-n // pool_size)
    item = np.concatenate([rng.permutation(pool_size) for _ in range(reps)])[:n]
    key = rng.integers(0, 2**31 - 1, size=n)
    return Schedule(due, item, key)
