"""Paper Figure 1: utility f(S) and wall time vs ground-set size n, for
lazy greedy, sieve-streaming, and SS(+greedy).  Synthetic NYT-like corpus.

``backend`` selects the execution path of the SS + greedy stages through the
unified dispatch layer (repro.core.backend): "oracle" (default), "pallas",
or "sharded".

CLI: ``python -m benchmarks.fig1_scaling --json PATH`` emits, per
(n, backend), a ``fig1/...`` row with a *warm* SS(+greedy) wall time
(``wall_s`` — best of ``--repeat`` runs, so jit tracing is amortized out of
the gated metric) plus ``greedy/...`` and ``stochastic_greedy/...`` rows
whose ``wall_s`` is the *post-SS selection stage alone* (the compact
selection engine's gated metric — each row also records which path the
engine took).  ``--baseline PATH`` gates every fresh row against a committed
JSON (``BENCH_e2e.json`` at the repo root is the CI baseline, sharing the
regression logic of ``benchmarks.kernel_bench``) and exits nonzero on a
wall-time regression.

``--objective fl_stream`` switches the sweep to the matrix-free
StreamingFacilityLocation over clustered unit-norm embeddings
(``data/synthetic.clustered_embeddings``) — the axis that runs at n where
dense FacilityLocation cannot allocate its (n, n) sim matrix (default size
65536 ≙ a 16 GiB matrix that is never built).  Rows gate under the same
baseline file, filtered to their own objective slice.
"""

from __future__ import annotations

import argparse
import json
import sys

import jax
import jax.numpy as jnp

from benchmarks.common import save, timed
from repro.core import (
    FeatureCoverage,
    StreamingFacilityLocation,
    greedy,
    lazy_greedy,
    selection_bucket,
    sieve_streaming,
    stochastic_greedy,
)
from repro.core.sparsify import ss_sparsify
from repro.data import clustered_embeddings, news_day

K = 10
R, C = 8, 8.0


def run(sizes=(512, 1024, 2048, 4096, 8192), n_features=512, seed=0,
        backend="oracle", repeat=1) -> dict:
    rows = []
    key = jax.random.PRNGKey(seed)
    for n in sizes:
        W = jnp.asarray(news_day(seed + n, n, n_features))
        fn = FeatureCoverage(W=W, phi="sqrt")

        res_g, t_full_g = timed(lambda: jax.block_until_ready(
            greedy(fn, K, backend=backend)))
        _, t_lazy = timed(lambda: lazy_greedy(fn, K))

        def run_ss():
            ss = ss_sparsify(fn, key, r=R, c=C, backend=backend)
            out = greedy(fn, K, alive=ss.vprime, backend=backend)
            return jax.block_until_ready(out), ss

        (res_ss, ss), t_ss = timed(run_ss, repeat=repeat)
        res_sv, t_sv = timed(
            lambda: jax.block_until_ready(sieve_streaming(fn, K))
        )

        # Post-SS selection stage alone — the compact selection engine's
        # gated metric (SS already shrank the live set to |V'| ≪ n; per-step
        # selection cost must track |V'|, not n).
        live = int(jnp.sum(ss.vprime))
        bucket = selection_bucket(n, live)
        path = "full" if bucket is None else f"compact-{bucket}"
        _, t_sel = timed(lambda: jax.block_until_ready(
            greedy(fn, K, alive=ss.vprime, backend=backend)), repeat=repeat)
        sg_key = jax.random.fold_in(key, 1)
        _, t_sg = timed(lambda: jax.block_until_ready(
            stochastic_greedy(fn, K, sg_key, alive=ss.vprime,
                              backend=backend)), repeat=repeat)

        fg = float(res_g.value)
        rows.append({
            "n": int(n),
            "backend": backend,
            "bench_key": f"fig1/{backend}-n{n}",
            "wall_s": t_ss,
            "f_greedy": fg,
            "rel_ss": float(res_ss.value) / fg,
            "rel_sieve": float(res_sv.value) / fg,
            "vprime": live,
            "selection_path": path,
            "t_greedy_s": t_sel,
            "t_sgreedy_s": t_sg,
            "t_full_greedy_s": t_full_g,
            "t_lazy_s": t_lazy,
            "t_ss_s": t_ss,
            "t_sieve_s": t_sv,
        })
        rows.append({
            "n": int(n), "backend": backend,
            "bench_key": f"greedy/{backend}-n{n}", "wall_s": t_sel,
            "vprime": live, "selection_path": path,
        })
        rows.append({
            "n": int(n), "backend": backend,
            "bench_key": f"stochastic_greedy/{backend}-n{n}", "wall_s": t_sg,
            "vprime": live, "selection_path": path,
        })
        print(f"fig1 n={n:6d} rel_ss={rows[-3]['rel_ss']:.4f} "
              f"rel_sieve={rows[-3]['rel_sieve']:.4f} |V'|={live:5d} "
              f"sel={path} t(greedy/lazy/ss/sel/sg/sieve)="
              f"{t_full_g:.2f}/{t_lazy:.2f}/{t_ss:.2f}/{t_sel:.2f}/"
              f"{t_sg:.2f}/{t_sv:.2f}s", flush=True)
    save("fig1_scaling", rows)
    return {"rows": rows}


def run_stream(sizes=(65536,), d=16, seed=0, backend="oracle", repeat=1,
               ss_r=2) -> dict:
    """The ``--objective fl_stream`` axis: SS(+greedy) on the matrix-free
    StreamingFacilityLocation at ground-set sizes where dense FL cannot even
    allocate its (n, n) sim matrix (the ``from_features`` guard trips at
    16384 rows; the default 65536 would be 16 GiB).  There is no full-greedy
    quality reference at these n — the rows pin wall time, |V'|, and f(S)
    instead; dense-parity of the underlying primitives is pinned at small n
    by tests/test_fl_stream.py and the ``fl_stream/...`` kernel rows."""
    rows = []
    key = jax.random.PRNGKey(seed)
    for n in sizes:
        X = jnp.asarray(clustered_embeddings(seed + n, n, d))
        fn = StreamingFacilityLocation.from_features(X, kernel="dot")

        def run_ss():
            ss = ss_sparsify(fn, key, r=ss_r, c=C, backend=backend)
            out = greedy(fn, K, alive=ss.vprime, backend=backend)
            return jax.block_until_ready(out), ss

        (res_ss, ss), t_ss = timed(run_ss, repeat=repeat)
        live = int(jnp.sum(ss.vprime))
        bucket = selection_bucket(n, live)
        path = "full" if bucket is None else f"compact-{bucket}"
        _, t_sel = timed(lambda: jax.block_until_ready(
            greedy(fn, K, alive=ss.vprime, backend=backend)), repeat=repeat)
        sg_key = jax.random.fold_in(key, 1)
        _, t_sg = timed(lambda: jax.block_until_ready(
            stochastic_greedy(fn, K, sg_key, alive=ss.vprime,
                              backend=backend)), repeat=repeat)

        rows.append({
            "n": int(n), "d": int(d), "backend": backend,
            "bench_key": f"fig1/fl_stream-{backend}-n{n}",
            "wall_s": t_ss,
            "f_ss": float(res_ss.value),
            "vprime": live,
            "rounds": int(ss.rounds),
            "selection_path": path,
            "t_ss_s": t_ss,
            "t_greedy_s": t_sel,
            "t_sgreedy_s": t_sg,
            "dense_sim_gib": 4.0 * n * n / 2**30,
            "stream_mib": 4.0 * n * d / 2**20,
        })
        rows.append({
            "n": int(n), "backend": backend,
            "bench_key": f"greedy/fl_stream-{backend}-n{n}", "wall_s": t_sel,
            "vprime": live, "selection_path": path,
        })
        rows.append({
            "n": int(n), "backend": backend,
            "bench_key": f"stochastic_greedy/fl_stream-{backend}-n{n}",
            "wall_s": t_sg, "vprime": live, "selection_path": path,
        })
        print(f"fig1[fl_stream] n={n:6d} f_ss={float(res_ss.value):.1f} "
              f"|V'|={live:5d} rounds={int(ss.rounds)} sel={path} "
              f"t(ss/sel/sg)={t_ss:.2f}/{t_sel:.2f}/{t_sg:.2f}s "
              f"(dense sim would be "
              f"{rows[-3]['dense_sim_gib']:.1f} GiB)", flush=True)
    save("fig1_scaling_fl_stream", rows)
    return {"rows": rows}


def main() -> int:
    from benchmarks.kernel_bench import check_regression

    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", type=int, nargs="+",
                    default=[512, 1024, 2048, 4096, 8192])
    ap.add_argument("--backends", nargs="+", default=["oracle"])
    ap.add_argument("--objective", choices=["fc", "fl_stream"], default="fc",
                    help="fc: the paper's FeatureCoverage sweep; fl_stream: "
                    "matrix-free StreamingFacilityLocation at n past the "
                    "dense (n, n) wall (default size 65536)")
    ap.add_argument("--ss-r", type=int, default=2,
                    help="SS redundancy parameter r for the fl_stream axis "
                    "(probe count scales as r*log2(n); large-n rows keep it "
                    "small to bound single-core wall time)")
    ap.add_argument("--ss-d", type=int, default=16,
                    help="embedding dim for the fl_stream axis")
    ap.add_argument("--repeat", type=int, default=2,
                    help="timing repeats for the SS stage (>=2 gives warm "
                    "wall times — the gated metric)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write all rows (bench_key + warm SS wall_s) to PATH")
    ap.add_argument("--baseline", default=None, metavar="PATH",
                    help="committed baseline JSON (BENCH_e2e.json) to gate "
                    "SS wall times against")
    ap.add_argument("--max-ratio", type=float, default=2.0,
                    help="fail when wall_s exceeds baseline * this ratio")
    ap.add_argument("--abs-floor", type=float, default=0.25,
                    help="seconds over baseline a key must also regress by "
                    "(end-to-end timings carry more machine noise than the "
                    "kernel smoke, hence the higher floor)")
    args = ap.parse_args()

    rows = []
    for backend in args.backends:
        if args.objective == "fl_stream":
            rows += run_stream(sizes=tuple(args.sizes), d=args.ss_d,
                               backend=backend, repeat=args.repeat,
                               ss_r=args.ss_r)["rows"]
        else:
            rows += run(sizes=tuple(args.sizes), backend=backend,
                        repeat=args.repeat)["rows"]
    if len(args.backends) > 1:
        # run() saves its own backend's rows each call — rewrite the legacy
        # artifact with the combined set so no backend's rows are dropped.
        save("fig1_scaling" if args.objective == "fc"
             else "fig1_scaling_fl_stream", rows)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"rows": rows}, f, indent=1)
        print(f"wrote {len(rows)} rows to {args.json}", flush=True)
    if args.baseline:
        # BENCH_e2e.json is shared by both objective axes; each invocation
        # gates only its own slice so the other axis's keys aren't counted
        # as unmeasured.
        key_ok = (lambda k: ("fl_stream" in k) == (args.objective
                                                  == "fl_stream"))
        bad, unmeasured = check_regression(rows, args.baseline,
                                           args.max_ratio, args.abs_floor,
                                           key_ok=key_ok)
        if bad or unmeasured:
            print(f"regression-gate: {bad} e2e row(s) regressed "
                  f">{args.max_ratio}x and {unmeasured} baseline key(s) "
                  f"unmeasured vs {args.baseline} (run all baseline "
                  "sizes/backends, or refresh the baseline)",
                  file=sys.stderr)
            return 1
        print(f"regression-gate: all e2e rows within {args.max_ratio}x "
              "of baseline", flush=True)
    return 0


if __name__ == "__main__":
    from repro.compile_cache import setup_compile_cache

    setup_compile_cache()
    raise SystemExit(main())
