#!/usr/bin/env python3
"""The control of ``correct``, and the planted selection faults.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s> \
        [--answer bf16,argmin,random,probes,bf16sel]

For each seed it builds the run's payload pool and window schedule, answers
every query of the window with a stand-in for the program, and judges those
answers with the run's own comparison (``bench/reference.py``) and limits.
It prints one JSON line per seed and stand-in with the numbers compared.
The stand-ins, each computed once per distinct payload:

- ``bf16``, the control: plain greedy on the whole ground set in bfloat16
  on the device, one precision below the configuration's float32, with its
  gains and value as bfloat16 computes them;
- ``argmin``: float32 greedy that takes the least gain at every step (a
  greedy argmax that picks the wrong element);
- ``random``: k rows drawn at random (an SS that skips its rounds and
  greedy that takes any rows);
- ``probes``: float32 greedy over one round's ``r log2 n`` random probes
  only (a retained set cut short);
- ``bf16sel``: the bfloat16 selection with its gains and value read out in
  float32 by the reference.

Every stand-in but ``bf16`` reports its gains and value exactly as the
reference reads them, so only ``quality_gap`` can catch it.  Not part of a
run; the CPU tests run it at a small size.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from bench import harness, reference, traffic  # noqa: E402


def plain_greedy(jax, objective: str, X: np.ndarray, k: int, dtype,
                 pick: str = "max"):
    """Greedy on the whole ground set in ``dtype``, taking the largest gain
    (``pick="max"``) or the least: (selected, gains, value)."""
    jnp = jax.numpy
    choose = jnp.argmax if pick == "max" else jnp.argmin
    skip = -jnp.inf if pick == "max" else jnp.inf

    @jax.jit
    def run(X):
        X = X.astype(dtype)
        n = X.shape[0]
        if objective == "coverage":
            data, state0 = X, jnp.zeros((X.shape[1],), dtype)

            def gains(c):
                return (jnp.sqrt(c[None, :] + data)
                        - jnp.sqrt(c)[None, :]).sum(axis=1, dtype=dtype)

            def add(c, v):
                return c + data[v]

            def value(c):
                return jnp.sqrt(c).sum(dtype=dtype)
        else:
            Xn = X / jnp.maximum(jnp.linalg.norm(X, axis=1, keepdims=True),
                                 jnp.asarray(1e-9, dtype))
            data = jnp.maximum(
                jnp.matmul(Xn, Xn.T, preferred_element_type=dtype), 0)
            state0 = jnp.zeros((n,), dtype)

            def gains(cur):
                return jnp.maximum(data - cur[:, None], 0).sum(axis=0, dtype=dtype)

            def add(cur, v):
                return jnp.maximum(cur, data[:, v])

            def value(cur):
                return cur.sum(dtype=dtype)

        def step(carry, _):
            st, avail = carry
            g = jnp.where(avail, gains(st), skip)
            v = choose(g)
            return (add(st, v), avail.at[v].set(False)), (v, g[v])

        (st, _), (sel, gs) = jax.lax.scan(
            step, (state0, jnp.ones((n,), bool)), None, length=k)
        return sel, gs.astype(jnp.float32), value(st).astype(jnp.float32)

    sel, gs, val = run(jax.numpy.asarray(X))
    return np.asarray(sel), np.asarray(gs), float(val)


def read_out(objective: str, X: np.ndarray, k: int, sel, vprime_size=None):
    """An answer whose gains and value are the reference's own reading of
    ``sel``, padded past the retained set with the exhausted-row marker."""
    sel = np.asarray(sel, np.int64)
    prefix = reference.PREFIX_VALUES[objective](X, sel).astype(np.float64)
    gains = np.zeros(k, np.float32)
    gains[:len(sel)] = np.diff(np.concatenate([[0.0], prefix]))
    full = np.zeros(k, np.int64)
    full[:len(sel)] = sel
    return {"selected": full, "gains": gains, "value": float(prefix[-1]),
            "vprime_size": vprime_size, "degraded": False}


def stand_in(jax, answer: str, objective: str, X: np.ndarray, k: int,
             r: int, rng: np.random.Generator) -> dict:
    """One payload's answer from the stand-in named ``answer``."""
    jnp = jax.numpy
    n = X.shape[0]
    if answer in ("bf16", "f32"):
        dtype = jnp.bfloat16 if answer == "bf16" else jnp.float32
        sel, gs, val = plain_greedy(jax, objective, X, k, dtype)
        return {"selected": sel, "gains": gs, "value": val,
                "vprime_size": None, "degraded": False}
    if answer == "bf16sel":
        sel, _, _ = plain_greedy(jax, objective, X, k, jnp.bfloat16)
        return read_out(objective, X, k, sel)
    if answer == "argmin":
        sel, _, _ = plain_greedy(jax, objective, X, k, jnp.float32, pick="min")
        return read_out(objective, X, k, sel)
    if answer == "random":
        return read_out(objective, X, k, rng.choice(n, min(k, n), replace=False))
    if answer == "probes":
        m = min(n, max(1, int(r * np.log2(max(n, 2)))))
        probes = np.sort(rng.choice(n, m, replace=False))
        sub = reference.GREEDY[objective](X[probes], min(k, m))
        return read_out(objective, X, k, probes[sub], vprime_size=m)
    raise ValueError(f"unknown stand-in {answer!r}")


def control_readings(jax, cell, seed: int, seconds: float,
                     answers=("bf16",)) -> dict:
    """{stand-in: the comparison's verdict} for one seed; the reference's
    greedy runs once per payload for all stand-ins."""
    pool = harness.make_pool(cell.config, seed)
    sched = traffic.schedule(cell.mix, seconds, len(pool), seed, stream=0)
    objective = cell.config["objective"]["objective"]
    r = int(cell.config["run_config"]["r"])
    items = sorted(set(int(x) for x in sched.item))
    greedy_ref = {i: reference.greedy_value(objective, pool[i].features,
                                            pool[i].k) for i in items}
    out = {}
    for answer in answers:
        rng = np.random.default_rng([int(seed) % (1 << 63), 5])
        served = {i: stand_in(jax, answer, objective, pool[i].features,
                              pool[i].k, r, rng) for i in items}
        judged = [dict(item=i, features=pool[i].features, k=pool[i].k,
                       **served[int(i)]) for i in sched.item]
        out[answer] = reference.compare(objective, judged,
                                        cell.config["check"],
                                        greedy_ref=greedy_ref)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--answer", default="bf16,argmin,random,probes,bf16sel")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    jax = harness.setup_jax()
    device = harness.device_info(jax, int(cell.entry["chips"]))
    with jax.default_matmul_precision("highest"):
        for seed in (int(s) for s in args.seeds.split(",")):
            judged = control_readings(jax, cell, seed, args.seconds,
                                      args.answer.split(","))
            for answer, j in judged.items():
                print(json.dumps({
                    "seed": seed, "answer": answer, "device": device["kind"],
                    "correct": j["correct"], "checked": j["checked"],
                    "numbers": {k: v for k, (v, _) in j["numbers"].items()},
                }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
