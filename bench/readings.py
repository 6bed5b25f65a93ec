#!/usr/bin/env python3
"""The program's readings of the numbers ``correct`` compares, on many seeds
in one process, for setting their limits.

    python bench/readings.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed it builds the run's payload pool and window schedule, sends
exactly the window's queries (each pool item under the same key as in a
run) through a synchronous service of the cell's configuration, in chunks
of up to ``max_batch`` queries of one lane, and judges the answers with the
run's own comparison and limits.  A query's answer does not depend on its
batch mates, so these are the answers a run gets; one process pays the
set-up once for all seeds.  It prints one JSON line per seed.  Not part of
a run.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness, reference, traffic  # noqa: E402


def seed_readings(api, svc, cell, cfg, seed: int, seconds: float) -> dict:
    pool = harness.make_pool(cell.config, seed)
    sched = traffic.schedule(cell.mix, seconds, len(pool), seed, stream=0)
    lanes: dict[tuple, list[int]] = {}
    for q, i in enumerate(sched.item):
        lanes.setdefault(harness.lane_of(cfg, pool[int(i)]), []).append(q)
    judged = []
    for qs in lanes.values():
        for j in range(0, len(qs), cfg.max_batch):
            chunk = qs[j: j + cfg.max_batch]
            resps = svc.run([harness.request(api, cell.config,
                                             pool[int(sched.item[q])],
                                             int(sched.key[q]))
                             for q in chunk])
            for q, resp in zip(chunk, resps):
                item = pool[int(sched.item[q])]
                judged.append(dict(
                    item=int(sched.item[q]), features=item.features, k=item.k,
                    selected=resp.selected, gains=resp.gains,
                    value=resp.value, vprime_size=resp.vprime_size,
                    degraded=resp.degradation is not None))
    return reference.compare(cell.config["objective"]["objective"], judged,
                             cell.config["check"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    jax = harness.setup_jax()
    device = harness.device_info(jax, int(cell.entry["chips"]))
    api, _ = harness.import_program()
    cfg = harness.run_config(api, cell.config)
    svc = api.serve(dataclasses.replace(cfg, scheduler="sync"))
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        j = seed_readings(api, svc, cell, cfg, seed, args.seconds)
        print(json.dumps({
            "seed": seed, "device": device["kind"], "correct": j["correct"],
            "checked": j["checked"], "seconds": time.perf_counter() - t,
            "numbers": {k: v for k, (v, _) in j["numbers"].items()},
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
