#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process generates the cell's payloads from the seed, starts the
summarization service (``repro.api.serve``) with the configuration's
``RunConfig``, warms every program the cell's traffic runs, and then offers
the cell's open-loop traffic for ``--seconds``.  Each query is timed from
its due time to its ticket resolving.  After the window the answers are
compared with the plain reference (``bench/reference.py``).

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` turns on
the program's spans and a profiler trace of part of the window, and reports
the per-layer metrics (``bench/metrics/<name>.py``) and a breakdown.

The last stdout line is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and
last ``checks`` (each number compared, beside its limit).  Without a TPU,
or with fewer chips than the cell asks for, it exits non-zero and prints
no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from bench import harness, reference, traffic  # noqa: E402
from bench.harness import log  # noqa: E402

PROFILE_S = 4.0     # length of the profiler trace in a traced run


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def end_to_end(cell, recs, t0: float, t_close: float, seconds: float,
               setup_s: float) -> dict:
    lat_ms = [(r.resolved - r.due) * 1e3 for r in recs if r.response is not None]
    if cell.mix["close"] == "seconds":
        answered = sum(1 for r in recs if r.response is not None
                       and r.resolved <= t0 + seconds)
        rate = answered / seconds
    else:
        rate = sum(1 for r in recs if r.response is not None) / (t_close - t0)
    values = {"requests_per_s": rate, "setup_s": setup_s}
    if lat_ms:
        values["latency_p50_ms"] = percentile(lat_ms, 50)
        values["latency_p95_ms"] = percentile(lat_ms, 95)
    return values


def answers(pool, recs) -> list[dict]:
    out = []
    for r in recs:
        item = pool[r.item]
        a = {"item": r.item, "features": item.features, "k": item.k}
        resp = r.response
        if resp is None:
            a["error"] = repr(r.error) if r.error is not None else "no answer"
        else:
            a.update(selected=np.asarray(resp.selected),
                     gains=np.asarray(resp.gains), value=resp.value,
                     vprime_size=resp.vprime_size,
                     degraded=resp.degradation is not None)
        out.append(a)
    return out


def per_layer(cell, ctx) -> dict:
    values = {}
    for m in cell.metrics("per_layer"):
        v = importlib.import_module(f"bench.metrics.{m['name']}").read(ctx)
        if v is not None:
            values[m["name"]] = float(v)
    return values


def profile_window(jax, api, svc, cell, pool, seed: int, seconds: float):
    """A short window of the cell's own traffic (another stream of the seed)
    under the profiler, reduced to per-layer device numbers."""
    from bench import trace as trace_mod  # noqa: PLC0415

    length = min(PROFILE_S, seconds)
    prof = trace_mod.Profiler(jax, os.path.join(harness.BENCH, ".trace"))
    sched = traffic.schedule(cell.mix, length, len(pool), seed, stream=9)
    _, obs = harness.import_program()
    prof.start()
    try:
        recs, _, _ = harness.run_window(
            api, svc, cell.config, pool, sched, length, "last_answer",
            obs_on=True, on_open=prof.open_window, on_end=prof.close_window)
    finally:
        path = prof.stop()
    spans = obs.get_tracer().export()
    events = trace_mod.load(path)
    return trace_mod.reduce(events, trace_mod.load_layers(), spans,
                            prof.p_open), recs


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, root: str = harness.ROOT,
             cell=None, t_start: float = T_START,
             cache_dir: str = harness.CACHE_DIR) -> dict:
    """One run of one cell; returns the result line's object.  Tests pass
    ``require_tpu=False`` and a reduced ``cell``."""
    cell = cell or harness.load_cell(name, root)
    jax = harness.setup_jax(cache_dir)
    device = harness.device_info(jax, int(cell.entry["chips"]), require_tpu)
    peaks = None
    if trace:
        with open(os.path.join(harness.BENCH, "peaks.json")) as f:
            table = json.load(f)
        if device["kind"] not in table:
            raise SystemExit(f"bench: no peaks for device {device['kind']!r} "
                             "in bench/peaks.json")
        peaks = table[device["kind"]]
    counter = harness.CompileCounter(jax)
    api, obs = harness.import_program()
    if trace:
        obs.configure(trace=True, capacity=1 << 20)

    t = time.perf_counter()
    pool = harness.make_pool(cell.config, seed)
    t_data = time.perf_counter() - t
    cfg = harness.run_config(api, cell.config)
    t = time.perf_counter()
    c0 = counter.snapshot()
    warm = harness.warm_up(api, cell.config, cfg, pool, cell.mix, seed,
                           counter, obs_on=trace)
    c1 = counter.snapshot()
    t_warm = time.perf_counter() - t
    sched = traffic.schedule(cell.mix, seconds, len(pool), seed, stream=0)
    svc = api.serve(cfg)
    if trace:
        obs.get_tracer().clear()
    compiles_before = counter.backend
    t_open = time.perf_counter()
    setup_s = t_open - t_start
    log(f"bench: setup_s={setup_s:.3f} data_s={t_data:.3f} "
        f"warm_s={t_warm:.3f} programs_compiled={c1[0] - c0[0] - (c1[1] - c0[1])} "
        f"programs_loaded={c1[1] - c0[1]} warm={json.dumps(warm)}")
    recs, t0, t_close = harness.run_window(
        api, svc, cell.config, pool, sched, seconds, cell.mix["close"],
        obs_on=trace)
    compiles_in_window = counter.backend - compiles_before
    device["memory_peak_bytes"] = harness.memory_peak(
        jax, int(cell.entry["chips"]))
    checked = list(recs)
    result = {"correct": False}
    if trace:
        spans = obs.get_tracer().export()
        obs.get_tracer().clear()
        profile, prof_recs = profile_window(jax, api, svc, cell, pool, seed,
                                            seconds)
        checked += prof_recs
        compiles_in_window = counter.backend - compiles_before
    svc.stop()
    del svc
    gc.collect()

    result["attempted"] = len(checked)
    result["failed"] = sum(1 for r in checked if r.response is None
                           or r.response.degradation is not None)
    recovered = sum(1 for r in checked if r.response is not None
                    and r.response.recovery is not None)
    log(f"bench: attempted={len(recs)} checked={len(checked)} "
        f"failed={result['failed']} recovered={recovered} "
        f"compiles_in_window={compiles_in_window} window_s={t_close - t0:.3f}")
    if trace:
        from bench import trace as trace_mod  # noqa: PLC0415

        ctx = trace_mod.Context(
            recs=recs, all_recs=checked, spans=spans, t0=t0,
            t_close=t_close, pool=pool, config=cell.config, cfg=cfg, peaks=peaks,
            compiles_in_window=compiles_in_window, profile=profile,
        )
        values = per_layer(cell, ctx)
        device["busy_s"] = profile["busy_s"]
        device["window_s"] = profile["window_s"]
        log(f"bench: idle_by_label={json.dumps(profile['idle_by_label'])} "
            f"layers_s={json.dumps(profile['layers_s'])} "
            f"requests_profiled={profile['requests']}")
        result["breakdown"] = profile["breakdown"]
        section = "per_layer"
    else:
        values = end_to_end(cell, recs, t0, t_close, seconds, setup_s)
        section = "end_to_end"
    result["metrics"] = {
        m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
        for m in cell.metrics(section) if m["name"] in values
    }
    result["device"] = device

    judged = reference.compare(cell.config["objective"]["objective"],
                               answers(pool, checked),
                               cell.config["check"])
    result["correct"] = judged["correct"]
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in judged["numbers"].items()}
    for k, (v, lim) in judged["numbers"].items():
        log(f"check {k}={v!r} limit={lim!r}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
