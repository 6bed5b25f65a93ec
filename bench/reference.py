"""The plain reference and the comparison that decides ``correct``.

The reference evaluates the objective that a configuration states, in
float32 numpy on the host, straight from the definitions in arXiv:1606.00399
§5.  It imports nothing of the program and takes nothing the program made:
only the payload the benchmark generated and the answer the service
returned.

- FeatureCoverage, ``phi = sqrt``: ``f(S) = sum_f sqrt(sum_{v in S} W[v, f])``.
- Facility location, cosine kernel:
  ``f(S) = sum_i max(0, max_{s in S} cos(x_i, x_s))``.

For each answer it recomputes the value of the selected set and the
marginal gain of each selection step, in the order served, and runs plain
greedy (lazy evaluation, float32) on the query's whole ground set once per
distinct payload.  It compares:

- ``value_gap``: the largest ``|value - f_ref(S)| / f_ref(S)``;
- ``gain_gap``: the largest ``|gain_t - g_ref_t| / f_ref(S)`` over steps;
- ``quality_gap``: the largest ``1 - f_ref(S) / f_ref(S_greedy)``, how far
  the served selection falls short of greedy on the whole ground set (SS
  keeps a near-greedy subset, so a sound answer reads a few tenths of a
  percent; a wrong pick or a retained set cut short reads far more);
- ``bad_answers``: answers that never came, came with an error or a
  degradation record, or whose selection is malformed (an index outside
  the query's own ground set, a repeat, or a step past the retained set
  that is not the exhausted-row marker ``(0, 0.0)``).  Exact: limit 0.
"""

from __future__ import annotations

import numpy as np


def coverage_prefix_values(W: np.ndarray, S: np.ndarray) -> np.ndarray:
    """f(S_1..t) for t = 1..|S|, FeatureCoverage with phi = sqrt."""
    c = np.cumsum(W[S].astype(np.float32), axis=0, dtype=np.float32)
    return np.sqrt(np.maximum(c, 0.0)).sum(axis=1, dtype=np.float32)


def facility_prefix_values(X: np.ndarray, S: np.ndarray) -> np.ndarray:
    """f(S_1..t) for t = 1..|S|, facility location over cosine similarity."""
    X = X.astype(np.float32)
    Xn = X / np.maximum(np.linalg.norm(X, axis=1, keepdims=True), 1e-9)
    sim = np.maximum(Xn @ Xn[S].T, 0.0)                    # (n, |S|)
    cover = np.maximum.accumulate(sim, axis=1)
    return cover.sum(axis=0, dtype=np.float32)


PREFIX_VALUES = {"coverage": coverage_prefix_values, "fl": facility_prefix_values}

LAZY_BATCH = 16     # stale upper bounds re-evaluated together in lazy greedy


def lazy_greedy(first: np.ndarray, gains_of, add, k: int) -> np.ndarray:
    """Greedy by lazy evaluation: ``first`` holds every element's gain on the
    empty set; ``gains_of(idx)`` the current gains of ``idx``; ``add(v)``
    commits ``v``.  Gains only shrink (submodularity), so a stale gain is an
    upper bound and the top element is taken once its gain is fresh."""
    n = first.shape[0]
    bound = first.astype(np.float32).copy()
    stamp = np.zeros(n, np.int64)
    chosen = np.empty(min(k, n), np.int64)
    for t in range(len(chosen)):
        m = min(LAZY_BATCH, n - t)
        while True:
            top = np.argpartition(bound, n - m)[n - m:]
            top = top[np.lexsort((top, -bound[top]))]     # ties: lowest index
            if stamp[top[0]] == t:
                break
            stale = top[stamp[top] != t]
            bound[stale] = gains_of(stale)
            stamp[stale] = t
        v = int(top[0])
        chosen[t] = v
        add(v)
        bound[v] = -np.inf
    return chosen


def coverage_greedy(W: np.ndarray, k: int) -> np.ndarray:
    W = W.astype(np.float32)
    c = np.zeros(W.shape[1], np.float32)

    def gains_of(idx):
        return (np.sqrt(c + W[idx]) - np.sqrt(c)).sum(axis=1, dtype=np.float32)

    def add(v):
        c[:] = c + W[v]

    return lazy_greedy(np.sqrt(W).sum(axis=1, dtype=np.float32), gains_of,
                       add, k)


def facility_greedy(X: np.ndarray, k: int) -> np.ndarray:
    X = X.astype(np.float32)
    Xn = X / np.maximum(np.linalg.norm(X, axis=1, keepdims=True), 1e-9)
    sim = np.maximum(Xn @ Xn.T, 0.0)          # (n, n), symmetric: rows are columns
    cur = np.zeros(X.shape[0], np.float32)

    def gains_of(idx):
        return np.maximum(sim[idx] - cur[None, :], 0.0).sum(
            axis=1, dtype=np.float32)

    def add(v):
        np.maximum(cur, sim[v], out=cur)

    return lazy_greedy(sim.sum(axis=1, dtype=np.float32), gains_of, add, k)


GREEDY = {"coverage": coverage_greedy, "fl": facility_greedy}


def greedy_value(objective: str, features: np.ndarray, k: int) -> float:
    """f_ref of plain float32 greedy on the whole ground set."""
    S = GREEDY[objective](features, k)
    return float(PREFIX_VALUES[objective](features, S)[-1])


def steps_served(k: int, vprime_size: int | None) -> int:
    """Greedy commits one retained element per step until V' runs out."""
    return k if vprime_size is None else min(k, int(vprime_size))


def check_answer(objective: str, features: np.ndarray, k: int, selected,
                 gains, value: float, vprime_size: int | None,
                 greedy_ref: float) -> tuple[float, float, float, bool]:
    """(value_gap, gain_gap, quality_gap, malformed) of one answer, given
    ``greedy_ref``, the value of greedy on its whole ground set."""
    sel = np.asarray(selected).astype(np.int64).ravel()
    g = np.asarray(gains, np.float64).ravel()
    n = features.shape[0]
    t = steps_served(k, vprime_size)
    bad = (float("inf"), float("inf"), float("inf"), True)
    if sel.shape != (k,) or g.shape != (k,) or t < 1:
        return bad
    real = sel[:t]
    malformed = bool(
        (real < 0).any() or (real >= n).any()
        or len(np.unique(real)) != t
        or (sel[t:] != 0).any() or (g[t:] != 0).any()
    )
    if malformed:
        return bad
    prefix = PREFIX_VALUES[objective](features, real).astype(np.float64)
    ref_value = prefix[-1]
    ref_gains = np.diff(np.concatenate([[0.0], prefix]))
    scale = max(abs(ref_value), 1e-30)
    value_gap = abs(float(value) - ref_value) / scale
    gain_gap = float(np.max(np.abs(g[:t] - ref_gains))) / scale
    quality_gap = 1.0 - ref_value / max(greedy_ref, 1e-30)
    return value_gap, gain_gap, quality_gap, False


def compare(objective: str, answers: list[dict], limits: dict,
            greedy_ref: dict | None = None) -> dict:
    """Judge a run's answers.  Each answer is a dict with ``item`` (its
    payload's identity: greedy on the whole ground set runs once per item),
    ``features``, ``k`` and either ``error`` or the served ``selected``,
    ``gains``, ``value``, ``vprime_size`` and ``degraded``.  Returns the
    numbers, each with its limit, and ``correct``.  ``greedy_ref`` may hold
    greedy's value per item already."""
    value_gap = gain_gap = quality_gap = 0.0
    bad = 0
    greedy_ref = {} if greedy_ref is None else greedy_ref
    for a in answers:
        if a.get("error") is not None or a.get("degraded"):
            bad += 1
            continue
        if a["item"] not in greedy_ref:
            greedy_ref[a["item"]] = greedy_value(objective, a["features"],
                                                 a["k"])
        v, g, q, malformed = check_answer(
            objective, a["features"], a["k"], a["selected"], a["gains"],
            a["value"], a["vprime_size"], greedy_ref[a["item"]],
        )
        if malformed:
            bad += 1
            continue
        value_gap, gain_gap = max(value_gap, float(v)), max(gain_gap, float(g))
        quality_gap = max(quality_gap, float(q))
    numbers = {
        "bad_answers": (bad, 0),
        "value_gap": (value_gap, float(limits["value_gap"])),
        "gain_gap": (gain_gap, float(limits["gain_gap"])),
        "quality_gap": (quality_gap, float(limits["quality_gap"])),
    }
    correct = bool(answers) and all(x <= lim for x, lim in numbers.values())
    return {"correct": correct, "numbers": numbers, "checked": len(answers)}
