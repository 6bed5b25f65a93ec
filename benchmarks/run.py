"""Benchmark aggregator: one entry per paper table/figure + the beyond-paper
extras.  ``PYTHONPATH=src python -m benchmarks.run [--quick] [--json PATH]``.

``--json PATH`` writes every job's payload to one consolidated JSON — the
kernel jobs' rows carry the ``bench_key``/``wall_s`` fields consumed by the
CI bench-regression gate (``benchmarks.kernel_bench --baseline``).
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller sizes (CI smoke)")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write all job payloads to one consolidated JSON")
    args = ap.parse_args()

    from benchmarks import (data_selection, fig1_scaling, fig2_reduced_size,
                            fig3_news, kernel_bench, table2_video)

    jobs = {
        "fig1": lambda: fig1_scaling.run(
            sizes=(512, 1024, 2048) if args.quick
            else (512, 1024, 2048, 4096, 8192)),
        "fig2": lambda: fig2_reduced_size.run(
            n=1024 if args.quick else 4096,
            rs=tuple(range(2, 13, 4)) if args.quick else tuple(range(2, 21, 2))),
        "fig3": lambda: fig3_news.run(days=4 if args.quick else 16),
        "table2": lambda: table2_video.run(
            scale=0.08 if args.quick else 0.25),
        "kernels": lambda: kernel_bench.run(smoke=args.quick),
        "kernels_fl": lambda: kernel_bench.run_fl(smoke=args.quick),
        "kernels_dispatch": lambda: kernel_bench.run_dispatch(smoke=args.quick),
        "kernels_flash": lambda: kernel_bench.run_flash(smoke=args.quick),
        "data_selection": data_selection.run,
    }
    only = set(args.only.split(",")) if args.only else None
    payloads = {}
    t00 = time.time()
    for name, job in jobs.items():
        if only and name not in only:
            continue
        print(f"\n=== {name} {'='*50}", flush=True)
        t0 = time.time()
        payloads[name] = job()
        print(f"=== {name} done in {time.time()-t0:.1f}s", flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"quick": args.quick, "jobs": payloads}, f, indent=1,
                      default=str)
        print(f"\nwrote consolidated payloads to {args.json}")
    print(f"\nall benchmarks done in {time.time()-t00:.1f}s "
          f"(results under results/bench/)")
    return 0


if __name__ == "__main__":
    from repro.compile_cache import setup_compile_cache

    setup_compile_cache()
    sys.exit(main())
