"""Serving-engine throughput/latency benchmark: the micro-batched
summarization service vs the sequential single-query loop.

A synthetic load generator builds ``num`` summarization queries (news_day
feature payloads, per-query PRNG keys), which are served two ways:

- **sequential loop** — the pre-service calling pattern: per query, one
  ``ss_sparsify`` + ``greedy`` invocation (default settings, warm jit
  caches), timed per query.  Recorded per backend as ``serve/seq-...`` rows.
- **micro-batched service** — all queries submitted to a
  :class:`repro.serve.summarize_service.SummarizeService` with
  ``max_batch=B`` and flushed; per-query latency = queue delay + the wall
  time of the micro-batch the query rode in.  Recorded as
  ``serve/batch-...`` rows.

Every row carries a stable ``bench_key`` and ``wall_s`` = seconds *per
query* (so the shared ``check_regression`` gate reads it like any other
wall time), plus ``qps`` and p50/p99 latency.  Batched rows also record
``speedup_vs_seq_same_backend`` and ``speedup_vs_seq_oracle`` (the default
sequential loop a pre-service caller runs).

CPU-container note (measured, 2 cores): at n=1024 the interpret-mode pallas
sequential loop is already within ~1.4x of the machine's arithmetic floor
for SS's probe-divergence work, so the batched engine's win *over that
specific loop* is modest here (~1.3x); against the default (oracle)
sequential loop the batched pallas service clears 3x with room.  On TPU the
batched organization is the one that amortizes kernel launches and keeps
grids full — re-record the baseline there once a runner exists.

**Poisson open-loop mode** (``--poisson``, PR 7): a seeded Poisson arrival
process drives the *async* scheduler at a fraction of the measured
saturation rate (saturation = max_batch / full-batch execution time), and
two flusher policies serve the identical arrival trace:

- ``deadline`` — the SLO-aware policy: ``scheduler="async"`` with
  ``max_wait_s`` ≈ half a full-batch execution and a per-request
  ``deadline_s`` of 3 executions, so lanes fire on (full ∨ deadline-slack ∨
  max-wait);
- ``flush_on_full`` — the pre-PR-7 behavior as a policy: lanes fire only
  when full (``max_wait_s`` effectively infinite), leftovers on drain;
- ``deadline_ladder`` (PR 8) — the ``deadline`` policy plus the
  degradation ladder ``("bump_c", "shrink_r")``: when a lane's EWMA
  predicts a deadline miss the service trades SS accuracy (paper
  Theorem 1's c/r knobs) for execution time instead of missing.  Degraded
  signatures are warmed up front so the first ladder firing is not a
  compile.  Soft gate: at >= 0.8x load the ladder policy must not miss
  *more* deadlines than the plain deadline policy on the same trace
  (warn-only — miss counts ride runner noise; the hard acceptance pin
  lives in tests/test_serve_faults.py).

Per-query latency (queue delay + batch execution) is recorded as
``serve/poisson-{policy}-load{..}-...`` rows at 0.5x and 0.8x saturation;
the ``deadline`` rows also record ``p99_vs_flush_on_full`` — the
acceptance pin is that this ratio stays < 1 at 0.8x load (bounded queue
residency beats waiting for a full bucket once arrival gaps stretch).

**Fault-injection mode** (``--faults``, PR 8): a seeded
:class:`repro.serve.FaultPlan` (exec errors + latency spikes + malformed
results at fixed per-attempt rates) is threaded into a closed-loop sync
run; the recovery path (bounded retry → backend failover → per-query
isolation) must serve every query anyway.  Recorded as
``serve/faults-{backend}-...`` rows whose ``wall_s`` (seconds/query *with*
recovery overhead) rides the same regression gate, alongside
``completion_rate`` (hard-gated at 1.0 — fault schedules are
deterministic, so a lost ticket is a recovery bug, not noise), p50/p99,
and the recovery counters.

``--smoke`` runs the acceptance shape (n=1024, B=8) with a small query
count; ``--json`` / ``--baseline`` share ``kernel_bench.check_regression``
(``BENCH_serve.json`` at the repo root is the committed CI baseline; a run
gates only the slices it measured — skip ``--poisson`` / ``--faults`` and
those baseline keys are exempted, not counted unmeasured).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import save
from repro import obs
from repro.core import FeatureCoverage, greedy, ss_sparsify
from repro.data import news_day
from repro.serve import (
    FaultPlan,
    RunConfig,
    SummarizeRequest,
    SummarizeService,
    batch_buckets,
)

K = 10

# The degradation ladder the ``deadline_ladder`` poisson policy runs.  On
# this container's CPU sizes the stochastic_greedy step saves nothing
# (selection is not the bottleneck at n~1e3), so the bench exercises the
# two SS-side steps — measured degraded/full execution ratio ~0.55-0.6.
LADDER = ("bump_c", "shrink_r")

# Per-attempt fault rates for ``--faults`` (roughly one faulted attempt
# per 3-4 chunk executions, mixing all recoverable kinds; hangs are
# exercised in the chaos tests, not the bench — a watchdog timeout would
# put seconds of injected sleep into the gated wall time).
FAULT_RATES = dict(p_exec_error=0.15, p_latency=0.1, p_malformed=0.05)


def make_queries(num: int, n: int, n_features: int, k: int = K,
                 seed: int = 0) -> list[SummarizeRequest]:
    """Synthetic load: ``num`` single-day news corpora with distinct seeds
    and per-query PRNG keys."""
    return [
        SummarizeRequest(
            k=k,
            key=jax.random.PRNGKey(seed * 10_000 + i),
            features=jnp.asarray(news_day(seed * 10_000 + i, n, n_features)),
        )
        for i in range(num)
    ]


def _pctl(lat: list[float], q: float) -> float:
    return float(np.percentile(np.asarray(lat), q))


def run_sequential(queries, backend: str) -> dict:
    """The pre-service loop: one ss_sparsify + greedy call per query.

    With tracing enabled (``REPRO_TRACE=1`` / ``--obs-overhead``) the
    per-query latencies are read back off the ``bench.query`` trace spans
    instead of a bespoke ``perf_counter`` list — the bench consumes the
    same timing surface it is benchmarking (docs/observability.md)."""
    def one(q):
        fn = FeatureCoverage(W=q.features, phi="sqrt")
        ss = ss_sparsify(fn, q.prng_key(), backend=backend)
        res = greedy(fn, q.k, alive=ss.vprime, backend=backend)
        return jax.block_until_ready(res.value)

    one(queries[0])                       # warm the jit caches
    tr = obs.get_tracer()
    lat = []
    t0 = time.perf_counter()
    if tr.enabled:
        for i, q in enumerate(queries):
            with tr.span("bench.query", query=i, backend=backend,
                         mode="sequential"):
                one(q)
        wall = time.perf_counter() - t0
        lat = [
            s.wall_s for s in tr.spans(name="bench.query")
            if s.attrs.get("backend") == backend
            and s.attrs.get("mode") == "sequential"
        ][-len(queries):]
    else:
        for q in queries:
            t = time.perf_counter()
            one(q)
            lat.append(time.perf_counter() - t)
        wall = time.perf_counter() - t0
    return {
        "wall_s": wall / len(queries),
        "qps": len(queries) / wall,
        "p50_s": _pctl(lat, 50),
        "p99_s": _pctl(lat, 99),
    }


def run_batched(queries, backend: str, max_batch: int) -> dict:
    """The service path: submit everything, flush, read per-query latency
    (queue delay + micro-batch execution) off the responses — or, when
    tracing is on, off each request's ``queue.wait`` + ``chunk.exec``
    spans (the service emits them anyway; the bench just stops keeping a
    parallel set of books)."""
    def serve():
        svc = SummarizeService(
            RunConfig(backend=backend, max_batch=max_batch)
        )
        t0 = time.perf_counter()
        responses = svc.run(queries)
        wall = time.perf_counter() - t0
        return svc, responses, wall

    serve()                               # warm the jit caches
    tr = obs.get_tracer()
    if tr.enabled:
        # Ticket indices restart at 0 per service, so drop the warm run's
        # spans before the measured one — req-i must resolve uniquely.
        tr.clear()
    svc, responses, wall = serve()
    if tr.enabled:
        lat = []
        for i in range(len(queries)):
            spans = tr.spans_for_request(i)
            wait = sum(s.wall_s for s in spans if s.name == "queue.wait")
            execs = sum(s.wall_s for s in spans if s.name == "chunk.exec")
            lat.append(wait + execs)
    else:
        lat = [r.queue_delay_s + r.exec_s for r in responses]
    st = svc.stats()
    return {
        "wall_s": wall / len(queries),
        "qps": len(queries) / wall,
        "p50_s": _pctl(lat, 50),
        "p99_s": _pctl(lat, 99),
        "batches": st["batches"],
        "padding_waste_frac": st["padding_waste_frac"],
        "queue_delay_s_mean": st["queue_delay_s_mean"],
    }


def _measure_exec_full(queries, backend: str, max_batch: int) -> float:
    """Warm every (lane, B-bucket) signature the open-loop run can hit, then
    measure one full-batch execution — the unit the load generator and both
    flusher policies are calibrated in."""
    svc = SummarizeService(RunConfig(backend=backend, max_batch=max_batch))
    for b in batch_buckets(max_batch):
        svc.run(queries[:b])
    full = svc.run(queries[:max_batch])
    return full[0].exec_s


def _warm_ladder_levels(queries, backend: str, max_batch: int) -> None:
    """Compile every degraded (level, B-bucket) signature the ladder can
    fire — compile caches are process-wide, so forcing each level through
    a throwaway service leaves the measured run's first degraded batch
    warm."""
    for level in range(1, len(LADDER) + 1):
        svc = SummarizeService(RunConfig(
            backend=backend, max_batch=max_batch,
            ladder=LADDER, ladder_force=level,
        ))
        for b in batch_buckets(max_batch):
            svc.run(queries[:b])


def run_faults_once(queries, backend: str, max_batch: int,
                    seed: int = 0) -> dict:
    """One closed-loop sync run under a seeded FaultPlan: every chunk
    attempt may draw an exec error / latency spike / malformed result, and
    the retry → failover → isolation path must serve every query anyway.
    ``wall_s`` is seconds/query *including* recovery overhead.

    Failover is pinned to the *other* backend (the default
    ``failover_backend="oracle"`` is a no-op when oracle IS the primary):
    with a real failover stage in play, reaching per-query isolation —
    where a single faulted attempt fails a query for good — takes six
    consecutive faulted attempts, which the seeded rates make
    vanishingly rare."""
    cfg = RunConfig(
        backend=backend, max_batch=max_batch,
        failover_backend="oracle" if backend != "oracle" else "pallas",
    )
    # Warm every signature recovery can reach: primary and failover
    # backends at every bucket (isolation serves B=1 chunks), so the gated
    # wall time measures recovery, not compiles.
    for be in dict.fromkeys((backend, cfg.failover_backend)):
        if be is None:
            continue
        warm = SummarizeService(RunConfig(backend=be, max_batch=max_batch))
        for b in batch_buckets(max_batch):
            warm.run(queries[:b])
    plan = FaultPlan.seeded(
        seed, n_attempts=max(256, 8 * len(queries)),
        latency_s=0.02, **FAULT_RATES,
    )
    svc = SummarizeService(cfg, faults=plan)
    t0 = time.perf_counter()
    tickets = [svc.submit(q) for q in queries]
    svc.drain()
    wall = time.perf_counter() - t0
    served = [
        t.result(timeout=0) for t in tickets
        if t.exception(timeout=0) is None
    ]
    lat = [r.queue_delay_s + r.exec_s for r in served]
    st = svc.stats()
    injected: dict[str, int] = {}
    for ev in plan.log:
        injected[ev.fault.kind] = injected.get(ev.fault.kind, 0) + 1
    return {
        "wall_s": wall / len(queries),
        "completion_rate": len(served) / len(queries),
        "p50_s": _pctl(lat, 50) if lat else float("nan"),
        "p99_s": _pctl(lat, 99) if lat else float("nan"),
        "failed": st["failed"],
        "retries": st["retries"],
        "failovers": st["failovers"],
        "isolated_queries": st["isolated_queries"],
        "faults_injected": injected,
    }


def run_faults(num: int = 32, n: int = 1024, n_features: int = 512,
               k: int = K, max_batch: int = 8,
               backends=("oracle", "pallas"), seed: int = 0) -> dict:
    """The fault-injection grid: one seeded chaos run per backend."""
    queries = make_queries(num, n, n_features, k, seed)
    rows = []
    for backend in backends:
        r = run_faults_once(queries, backend, max_batch, seed)
        rows.append({
            "mode": "faults", "backend": backend, "n": n, "k": k,
            "B": max_batch, "num_queries": num, "fault_seed": seed,
            "fault_rates": dict(FAULT_RATES),
            "bench_key": f"serve/faults-{backend}-n{n}-B{max_batch}-k{k}",
            **r,
        })
        print(
            f"serve fault [{backend}] n={n} B={max_batch}: "
            f"completion {r['completion_rate']:.2f}  "
            f"p99 {r['p99_s']*1e3:6.1f}ms  "
            f"(injected {r['faults_injected']}, retries {r['retries']}, "
            f"failovers {r['failovers']}, "
            f"isolated {r['isolated_queries']})", flush=True)
    save("serve_bench_faults", rows)
    return {"rows": rows}


def run_poisson_once(queries, backend: str, max_batch: int, load: float,
                     policy: str, exec_full: float, seed: int = 0) -> dict:
    """One open-loop run: Poisson arrivals at ``load`` x saturation against
    the async scheduler under ``policy`` (same seeded arrival trace for
    every policy, so the comparison is paired)."""
    saturation_qps = max_batch / exec_full
    qps = load * saturation_qps
    if policy == "deadline":
        cfg = RunConfig(
            backend=backend, max_batch=max_batch, scheduler="async",
            max_wait_s=0.5 * exec_full,
        )
        deadline_s = 3.0 * exec_full
    elif policy == "deadline_ladder":
        # The deadline policy plus the degradation ladder: same trace,
        # same SLO — but when a lane's EWMA predicts a miss the chunk
        # runs with bumped c / halved r instead of missing.
        cfg = RunConfig(
            backend=backend, max_batch=max_batch, scheduler="async",
            max_wait_s=0.5 * exec_full, ladder=LADDER,
        )
        deadline_s = 3.0 * exec_full
    elif policy == "flush_on_full":
        # The pre-PR-7 behavior as a policy: a lane fires only when full
        # (1e9 s ~ never for max_wait), leftovers fire on the final drain.
        cfg = RunConfig(
            backend=backend, max_batch=max_batch, scheduler="async",
            max_wait_s=1e9,
        )
        deadline_s = None
    else:
        raise ValueError(policy)
    gaps = np.random.default_rng(seed).exponential(1.0 / qps, len(queries))
    with SummarizeService(cfg) as svc:
        tickets = []
        for q, gap in zip(queries, gaps):
            time.sleep(gap)
            tickets.append(
                svc.submit(dataclasses.replace(q, deadline_s=deadline_s))
            )
        svc.drain()
        responses = [t.result(timeout=0) for t in tickets]
        st = svc.stats()
    lat = [r.queue_delay_s + r.exec_s for r in responses]
    return {
        "wall_s": float(np.mean(lat)),     # mean latency/query (gated key)
        "p50_s": _pctl(lat, 50),
        "p99_s": _pctl(lat, 99),
        "qps_offered": qps,
        "saturation_qps": saturation_qps,
        "batches": st["batches"],
        "triggers": st["triggers"],
        "deadlines_missed": st["deadlines_missed"],
        "degraded": st["degraded"],
    }


def run_poisson(num: int = 32, n: int = 1024, n_features: int = 512,
                k: int = K, max_batch: int = 8,
                backends=("oracle", "pallas"), loads=(0.5, 0.8),
                seed: int = 0,
                policies=("flush_on_full", "deadline",
                          "deadline_ladder")) -> dict:
    """The latency-vs-load grid: {backend} x {load} x {policy} rows."""
    queries = make_queries(num, n, n_features, k, seed)
    rows = []
    for backend in backends:
        exec_full = _measure_exec_full(queries, backend, max_batch)
        if "deadline_ladder" in policies:
            _warm_ladder_levels(queries, backend, max_batch)
        for load in loads:
            by_policy = {}
            row_of = {}
            for policy in policies:
                r = run_poisson_once(
                    queries, backend, max_batch, load, policy, exec_full,
                    seed,
                )
                by_policy[policy] = r
                tag = f"load{int(load * 100)}"
                row = {
                    "mode": "poisson", "policy": policy, "load": load,
                    "backend": backend, "n": n, "k": k, "B": max_batch,
                    "num_queries": num,
                    "bench_key": (
                        f"serve/poisson-{policy}-{tag}-{backend}"
                        f"-n{n}-B{max_batch}-k{k}"
                    ),
                    **r,
                }
                rows.append(row)
                row_of[policy] = row
            if {"deadline", "flush_on_full"} <= by_policy.keys():
                d, f = by_policy["deadline"], by_policy["flush_on_full"]
                row_of["deadline"]["p99_vs_flush_on_full"] = (
                    d["p99_s"] / f["p99_s"]
                )
            if {"deadline_ladder", "deadline"} <= by_policy.keys():
                # The miss-rate comparison the soft gate reads: the ladder
                # run must not miss more than plain deadline on this trace.
                row_of["deadline_ladder"]["deadline_policy_missed"] = (
                    by_policy["deadline"]["deadlines_missed"]
                )
            for policy, r in by_policy.items():
                print(
                    f"serve poisson [{backend}] load={load:.1f} "
                    f"{policy:>15}: p50 {r['p50_s']*1e3:6.1f}ms  "
                    f"p99 {r['p99_s']*1e3:6.1f}ms  "
                    f"({r['qps_offered']:.1f} qps offered, "
                    f"{r['batches']} batches, "
                    f"missed {r['deadlines_missed']}, "
                    f"degraded {r['degraded']}, "
                    f"triggers {r['triggers']})", flush=True)
    save("serve_bench_poisson", rows)
    return {"rows": rows}


def run(num: int = 16, n: int = 1024, n_features: int = 512, k: int = K,
        max_batch: int = 8, backends=("oracle", "pallas"),
        seed: int = 0) -> dict:
    queries = make_queries(num, n, n_features, k, seed)
    rows = []
    seq_qps: dict[str, float] = {}
    for backend in backends:
        r = run_sequential(queries, backend)
        seq_qps[backend] = r["qps"]
        rows.append({
            "mode": "sequential", "backend": backend, "n": n, "k": k,
            "num_queries": num,
            "bench_key": f"serve/seq-{backend}-n{n}-k{k}", **r,
        })
        print(f"serve seq   [{backend}] n={n} k={k}: "
              f"{r['qps']:6.1f} qps  p50 {r['p50_s']*1e3:6.1f}ms  "
              f"p99 {r['p99_s']*1e3:6.1f}ms", flush=True)
    for backend in backends:
        r = run_batched(queries, backend, max_batch)
        r["speedup_vs_seq_same_backend"] = r["qps"] / seq_qps[backend]
        if "oracle" in seq_qps:
            r["speedup_vs_seq_oracle"] = r["qps"] / seq_qps["oracle"]
        rows.append({
            "mode": "batched", "backend": backend, "n": n, "k": k,
            "B": max_batch, "num_queries": num,
            "bench_key": f"serve/batch-{backend}-n{n}-B{max_batch}-k{k}",
            **r,
        })
        print(f"serve batch [{backend}] n={n} B={max_batch}: "
              f"{r['qps']:6.1f} qps  p50 {r['p50_s']*1e3:6.1f}ms  "
              f"p99 {r['p99_s']*1e3:6.1f}ms  "
              f"x{r['speedup_vs_seq_same_backend']:.2f} vs own seq"
              + (f"  x{r['speedup_vs_seq_oracle']:.2f} vs oracle seq"
                 if "speedup_vs_seq_oracle" in r else ""),
              flush=True)
    save("serve_bench", rows)
    return {"rows": rows}


OBS_OVERHEAD_MAX = 1.1


def run_obs_overhead(num: int, n: int, n_features: int, k: int,
                     max_batch: int, backends) -> dict:
    """The observability overhead gate: the same seq+batched grid, traced
    vs untraced, in one process.  A first untraced pass warms every jit
    signature so both measured passes see identical cache state; the gate
    is ``wall(traced) <= OBS_OVERHEAD_MAX x wall(untraced)``
    (docs/observability.md "Overhead contract")."""
    was_enabled = obs.trace_enabled()
    obs.configure(trace=False)
    run(num=num, n=n, n_features=n_features, k=k,
        max_batch=max_batch, backends=backends)          # warm everything
    try:
        obs.configure(trace=True)
        obs.get_tracer().clear()
        t0 = time.perf_counter()
        run(num=num, n=n, n_features=n_features, k=k,
            max_batch=max_batch, backends=backends)
        wall_on = time.perf_counter() - t0
        n_spans = len(obs.get_tracer().export())
        obs.configure(trace=False)
        t0 = time.perf_counter()
        run(num=num, n=n, n_features=n_features, k=k,
            max_batch=max_batch, backends=backends)
        wall_off = time.perf_counter() - t0
    finally:
        obs.configure(trace=was_enabled)
    ratio = wall_on / wall_off
    row = {
        "mode": "obs_overhead", "n": n, "k": k, "B": max_batch,
        "num_queries": num, "backends": list(backends),
        "bench_key": f"serve/obs-overhead-n{n}-B{max_batch}-k{k}",
        "wall_on_s": wall_on, "wall_off_s": wall_off,
        "overhead_ratio": ratio, "spans_recorded": n_spans,
        "max_ratio": OBS_OVERHEAD_MAX,
    }
    print(
        f"serve obs-overhead: traced {wall_on:.2f}s vs untraced "
        f"{wall_off:.2f}s -> x{ratio:.3f} "
        f"(gate {OBS_OVERHEAD_MAX}x, {n_spans} spans)", flush=True)
    save("serve_bench_obs", [row])
    return {"rows": [row]}


def write_trace_artifact(path: str) -> None:
    """Dump the process-wide observability state (spans + bus events +
    metrics) as one JSON artifact — the trace upload the CI obs job
    attaches to each run."""
    tr = obs.get_tracer()
    bus = obs.get_bus()
    artifact = {
        "spans": tr.export(),
        "spans_dropped": tr.dropped,
        "events": bus.export(),
        "events_dropped": bus.dropped,
        "metrics": obs.get_registry().to_json(),
    }
    with open(path, "w") as f:
        json.dump(artifact, f, indent=1)
    print(
        f"wrote trace artifact to {path} ({len(artifact['spans'])} spans, "
        f"{len(artifact['events'])} events)", flush=True)


def main() -> int:
    from benchmarks.kernel_bench import check_regression

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate shape: n=1024, B=8, 16 queries")
    ap.add_argument("--num", type=int, default=32)
    ap.add_argument("--n", type=int, default=1024)
    ap.add_argument("--features", type=int, default=512)
    ap.add_argument("--k", type=int, default=K)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--backends", nargs="+", default=["oracle", "pallas"])
    ap.add_argument("--poisson", action="store_true",
                    help="also run the open-loop Poisson latency-vs-load "
                    "grid through the async flusher (deadline vs "
                    "flush-on-full vs deadline+degradation-ladder "
                    "policies)")
    ap.add_argument("--faults", action="store_true",
                    help="also run the seeded fault-injection grid: exec "
                    "errors + latency spikes + malformed results against "
                    "the retry/failover/isolation recovery path "
                    "(completion rate hard-gated at 1.0)")
    ap.add_argument("--loads", nargs="+", type=float, default=[0.5, 0.8],
                    help="offered-load fractions of measured saturation")
    ap.add_argument("--obs-overhead", action="store_true",
                    help="also run the tracing-overhead gate: the same grid "
                    "traced vs untraced (warm caches shared); fails if the "
                    f"traced wall exceeds {OBS_OVERHEAD_MAX}x untraced")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the observability state (spans + bus events "
                    "+ metrics JSON) as one artifact after the run")
    ap.add_argument("--json", default=None, metavar="PATH")
    ap.add_argument("--baseline", default=None, metavar="PATH",
                    help="committed baseline JSON (BENCH_serve.json) to gate "
                    "per-query wall times against")
    ap.add_argument("--max-ratio", type=float, default=2.0)
    ap.add_argument("--abs-floor", type=float, default=0.05,
                    help="seconds/query over baseline a key must also "
                    "regress by (service timings ride host wall clocks)")
    args = ap.parse_args()
    if args.smoke:
        args.num, args.n, args.batch = 16, 1024, 8

    rows = run(num=args.num, n=args.n, n_features=args.features, k=args.k,
               max_batch=args.batch, backends=tuple(args.backends))["rows"]
    if args.poisson:
        prows = run_poisson(
            num=2 * args.num, n=args.n, n_features=args.features, k=args.k,
            max_batch=args.batch, backends=tuple(args.backends),
            loads=tuple(args.loads),
        )["rows"]
        rows += prows
        worst = max(
            (r for r in prows
             if r["policy"] == "deadline" and r["load"] >= 0.8),
            key=lambda r: r["p99_vs_flush_on_full"], default=None,
        )
        if worst is not None and worst["p99_vs_flush_on_full"] >= 1.0:
            print(
                "poisson-gate: deadline-flusher p99 did not beat "
                f"flush-on-full at load {worst['load']} "
                f"({worst['backend']}): ratio "
                f"{worst['p99_vs_flush_on_full']:.2f}", file=sys.stderr)
            return 1
        for r in prows:
            # Soft gate (warn-only — miss counts ride runner noise; the
            # hard ladder acceptance pin is in tests/test_serve_faults.py):
            # at high load the ladder policy must not miss MORE deadlines
            # than plain deadline on the identical trace.
            if (r["policy"] == "deadline_ladder" and r["load"] >= 0.8
                    and r["deadlines_missed"] > r["deadline_policy_missed"]):
                print(
                    "ladder-gate (soft): deadline_ladder missed "
                    f"{r['deadlines_missed']} > deadline's "
                    f"{r['deadline_policy_missed']} at load {r['load']} "
                    f"({r['backend']})", file=sys.stderr)
    if args.faults:
        frows = run_faults(
            num=args.num, n=args.n, n_features=args.features, k=args.k,
            max_batch=args.batch, backends=tuple(args.backends),
        )["rows"]
        rows += frows
        lost = [r for r in frows if r["completion_rate"] < 1.0]
        if lost:
            # Fault schedules are seeded and chunk execution is serial, so
            # a lost ticket is a recovery-path bug, not runner noise.
            for r in lost:
                print(
                    "fault-gate: recovery lost queries under the seeded "
                    f"FaultPlan ({r['backend']}): completion rate "
                    f"{r['completion_rate']:.2f}, {r['failed']} failed",
                    file=sys.stderr)
            return 1
    obs_failed = False
    if args.obs_overhead:
        orows = run_obs_overhead(
            num=args.num, n=args.n, n_features=args.features, k=args.k,
            max_batch=args.batch, backends=tuple(args.backends),
        )["rows"]
        rows += orows
        for r in orows:
            if r["overhead_ratio"] > OBS_OVERHEAD_MAX:
                print(
                    "obs-overhead-gate: tracing-enabled wall is "
                    f"x{r['overhead_ratio']:.3f} the disabled wall "
                    f"(gate {OBS_OVERHEAD_MAX}x)", file=sys.stderr)
                obs_failed = True
    if args.trace_out:
        write_trace_artifact(args.trace_out)
    if obs_failed:
        return 1
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"rows": rows}, f, indent=1)
        print(f"wrote {len(rows)} rows to {args.json}", flush=True)
    if args.baseline:
        # A run gates only the baseline slices it actually measured.
        skip = []
        if not args.poisson:
            skip.append("serve/poisson-")
        if not args.faults:
            skip.append("serve/faults-")
        key_ok = (
            (lambda key: not any(key.startswith(p) for p in skip))
            if skip else None
        )
        bad, unmeasured = check_regression(rows, args.baseline,
                                           args.max_ratio, args.abs_floor,
                                           key_ok=key_ok)
        if bad or unmeasured:
            print(f"regression-gate: {bad} serve row(s) regressed "
                  f">{args.max_ratio}x and {unmeasured} baseline key(s) "
                  f"unmeasured vs {args.baseline}", file=sys.stderr)
            return 1
        print(f"regression-gate: all serve rows within {args.max_ratio}x "
              "of baseline", flush=True)
    return 0


if __name__ == "__main__":
    from repro.compile_cache import setup_compile_cache

    setup_compile_cache()
    raise SystemExit(main())
