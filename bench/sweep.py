#!/usr/bin/env python3
"""Find a configuration's knee once, on the chip: the highest offered rate
at which answers keep pace with arrivals.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 50,100,200

One process warms the cell once, then offers the cell's mix at each rate in
turn (a fresh service per rate) and prints one JSON line per rate: the
answered rate, how far past the window the last answer came (``lag_s``),
the latency median and 95th percentile, and how late the generator ran.
A rate keeps pace when ``lag_s`` stays small and the latencies do not grow
with the window.  The knee goes into the cell's mix file as a number; this
script is not part of a run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from bench import harness, traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    jax = harness.setup_jax()
    device = harness.device_info(jax, int(cell.entry["chips"]))
    counter = harness.CompileCounter(jax)
    api, _ = harness.import_program()
    pool = harness.make_pool(cell.config, args.seed)
    cfg = harness.run_config(api, cell.config)
    t = time.perf_counter()
    warm = harness.warm_up(api, cell.config, cfg, pool, cell.mix, args.seed,
                           counter, obs_on=False)
    harness.log(f"sweep: warm_s={time.perf_counter() - t:.3f} {json.dumps(warm)}")
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(cell.mix, rate_per_s=rate, close="last_answer")
        sched = traffic.schedule(mix, args.seconds, len(pool), args.seed,
                                 stream=100 + i)
        svc = api.serve(cfg)
        before = counter.backend
        recs, t0, t_close = harness.run_window(
            api, svc, cell.config, pool, sched, args.seconds, "last_answer",
            obs_on=False, late_s=120.0)
        svc.stop()
        ok = [r for r in recs if r.response is not None]
        lat = np.array([(r.resolved - r.due) * 1e3 for r in ok])
        late = np.array([(r.sent - r.due) * 1e3 for r in recs])
        half = len(ok) // 2
        print(json.dumps({
            "rate": rate, "offered": len(recs), "answered": len(ok),
            "answered_per_s": len(ok) / (t_close - t0),
            "lag_s": t_close - t0 - args.seconds,
            "p50_ms": float(np.percentile(lat, 50)) if len(ok) else None,
            "p95_ms": float(np.percentile(lat, 95)) if len(ok) else None,
            "p95_first_half_ms": float(np.percentile(lat[:half], 95)) if half else None,
            "p95_second_half_ms": float(np.percentile(lat[half:], 95)) if half else None,
            "gen_late_p95_ms": float(np.percentile(late, 95)),
            "compiles": counter.backend - before,
            "device": device["kind"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
