"""Durable streaming-session benchmark: ingest throughput, snapshot cost,
and crash-recovery wall time for the sieve×SS session tier (PR 9).

A synthetic drifting stream (element magnitudes grow, so the sieve's
absolute-guess window keeps sliding and SS compaction actually fires)
drives ``sessions`` concurrent sessions on a durable
:class:`repro.serve.sessions.SessionEngine`:

- **append** — ``appends`` elements per session, interleaved round-robin so
  waves batch across sessions; recorded as ``stream/append-{backend}-...``
  rows with ``wall_s`` = seconds *per append* (WAL write + amortized wave
  execution + due SS compactions + due snapshots) and ``appends_per_s``.
- **snapshot** — one forced :meth:`SessionEngine.snapshot` per session;
  ``stream/snapshot-{backend}-...`` rows record ``wall_s`` per snapshot and
  ``snapshot_bytes`` (the npz on disk).
- **recover** — a fresh engine on the same root rehydrates every session
  (newest snapshot + WAL-tail replay through the same wave kernels);
  ``stream/recover-{backend}-...`` rows record ``wall_s`` = recovery
  seconds *per session*, plus ``wal_bytes``/``snapshot_bytes`` per session
  and the mean replayed-record count.

Correctness rides the bench (hard gate, not a timing): every recovered
session's state must be **bit-identical** — every leaf: thresholds,
retained buffer, PRNG key, counters — to the live engine's state at kill
time, the acceptance pin of docs/streaming.md.  A mismatch fails the run
with exit 1 regardless of wall times.

``--smoke`` runs the CI shape; ``--json`` / ``--baseline`` share
``kernel_bench.check_regression`` (``BENCH_stream.json`` at the repo root
is the committed baseline — the ``stream-chaos`` CI job gates recovery
wall time and ingest throughput against it).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

from benchmarks.kernel_bench import check_regression
from repro import obs
from repro.serve.sessions import SessionConfig, SessionEngine


def drift_rows(seed: int, n: int, n_features: int, drift: float = 6.0):
    r = np.random.default_rng(seed)
    scale = 1.0 + drift * np.arange(n, dtype=np.float32) / n
    return r.random((n, n_features)).astype(np.float32) * scale[:, None]


def _state_leaves(state):
    import jax

    return [np.asarray(x) for x in jax.tree_util.tree_leaves(state)]


def _dir_bytes(root: str, sid: str, prefix: str) -> int:
    sdir = os.path.join(root, sid)
    return sum(
        os.path.getsize(os.path.join(sdir, f))
        for f in os.listdir(sdir) if f.startswith(prefix)
    )


def run_backend(
    backend: str, sessions: int, appends: int, n_features: int,
    cfg_kw: dict, workdir: str,
) -> tuple[list[dict], int]:
    """One backend's append/snapshot/recover measurement; returns (rows,
    n_mismatched_sessions)."""
    cfg = SessionConfig(backend=backend, n_features=n_features, **cfg_kw)
    shape = f"{backend}-S{sessions}xN{appends}-F{n_features}"
    root = os.path.join(workdir, shape)
    # ingest measures the first ``appends`` elements; ``tail`` more land
    # after the forced snapshots so recovery has a real WAL tail to replay
    tail = cfg.resparsify_every
    streams = {
        f"u{i:03d}": drift_rows(i, appends + tail, n_features)
        for i in range(sessions)
    }

    eng = SessionEngine(cfg, root)
    for i, sid in enumerate(streams):
        eng.open_session(sid=sid, key=i)
    # warm the wave/compaction signatures so the timed loop measures
    # steady-state ingest, not jit compiles
    warm = SessionEngine(cfg, os.path.join(workdir, shape + "-warm"))
    for i, sid in enumerate(streams):
        warm.open_session(sid=sid, key=i)
    for t in range(min(appends, 2 * cfg.resparsify_every)):
        for sid, R in streams.items():
            warm.append(sid, R[t])
    warm.flush()
    del warm   # dropped cold (no close → no snapshot): the warm recovery
    # below replays its full WAL, compiling the B=1 replay signature too
    warm_rec = SessionEngine(cfg, os.path.join(workdir, shape + "-warm"))
    for sid in streams:
        warm_rec.state(sid)

    t0 = time.perf_counter()
    for t in range(appends):
        for sid, R in streams.items():
            eng.append(sid, R[t])
    eng.flush()
    ingest_wall = time.perf_counter() - t0
    n_app = sessions * appends
    st = eng.stats()
    rows = [{
        "bench_key": f"stream/append-{shape}",
        "wall_s": ingest_wall / n_app,
        "appends_per_s": n_app / ingest_wall,
        "waves": st["waves"],
        "resparsifies": st["resparsifies"],
        "snapshots": st["snapshots"],
        "backend": backend,
    }]

    # With tracing on, the per-snapshot / per-recovery walls are read back
    # off the engine's own sessions.snapshot / sessions.recover spans
    # instead of a second set of perf_counter books around the calls.
    tr = obs.get_tracer()
    snap_mark = len(tr.spans(name="sessions.snapshot"))
    t0 = time.perf_counter()
    for sid in streams:
        eng.snapshot(sid)
    if tr.enabled:
        snap_wall = float(np.mean([
            s.wall_s for s in tr.spans(name="sessions.snapshot")[snap_mark:]
        ]))
    else:
        snap_wall = (time.perf_counter() - t0) / sessions
    snap_bytes = int(np.mean(
        [_dir_bytes(root, sid, "snap-") for sid in streams]
    ))
    rows.append({
        "bench_key": f"stream/snapshot-{shape}",
        "wall_s": snap_wall,
        "snapshot_bytes": snap_bytes,
        "backend": backend,
    })

    # post-snapshot tail: recovery must do real WAL replay, not just a load
    for t in range(appends, appends + tail):
        for sid, R in streams.items():
            eng.append(sid, R[t])
    eng.flush()
    live = {sid: _state_leaves(eng.state(sid)) for sid in streams}
    wal_bytes = int(np.mean(
        [_dir_bytes(root, sid, "wal.log") for sid in streams]
    ))

    # the crash: the engine object is dropped cold, a fresh one recovers
    del eng
    rec_mark = len(tr.spans(name="sessions.recover"))
    t0 = time.perf_counter()
    rec = SessionEngine(cfg, root)
    for sid in streams:
        rec.state(sid)              # forces snapshot load + WAL-tail replay
    if tr.enabled:
        rec_wall = float(np.mean([
            s.wall_s for s in tr.spans(name="sessions.recover")[rec_mark:]
        ]))
    else:
        rec_wall = (time.perf_counter() - t0) / sessions
    replayed = [e["replayed"] for e in rec.events if e["step"] == "rehydrate"]
    rows.append({
        "bench_key": f"stream/recover-{shape}",
        "wall_s": rec_wall,
        "wal_bytes": wal_bytes,
        "snapshot_bytes": snap_bytes,
        "replayed_mean": float(np.mean(replayed)) if replayed else 0.0,
        "backend": backend,
    })

    mismatched = 0
    for sid in streams:
        got = _state_leaves(rec.state(sid))
        if not all(np.array_equal(a, b) for a, b in zip(live[sid], got)):
            print(f"recovery-gate: session {sid} ({backend}) recovered to a "
                  "DIFFERENT state than the live engine", file=sys.stderr)
            mismatched += 1
    return rows, mismatched


def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n")[0],
    )
    ap.add_argument("--smoke", action="store_true",
                    help="the CI shape: small counts, both backends")
    ap.add_argument("--sessions", type=int, default=8)
    ap.add_argument("--appends", type=int, default=256,
                    help="elements per session")
    ap.add_argument("--features", type=int, default=32)
    ap.add_argument("--backends", nargs="+", default=["oracle", "pallas"])
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the observability state (spans + bus events "
                    "+ metrics JSON) as one artifact after the run")
    ap.add_argument("--json", default=None, metavar="PATH")
    ap.add_argument("--baseline", default=None, metavar="PATH",
                    help="committed BENCH_stream.json to gate against")
    ap.add_argument("--max-ratio", type=float, default=2.0)
    ap.add_argument("--abs-floor", type=float, default=0.010)
    args = ap.parse_args()

    sessions, appends = args.sessions, args.appends
    if args.smoke:
        sessions, appends = 4, 96
    cfg_kw = dict(
        k=8, eps=0.2, buffer_cap=64, resparsify_every=16, ss_r=3,
        max_batch=4, snapshot_every=48,
    )

    rows: list[dict] = []
    mismatched = 0
    with tempfile.TemporaryDirectory(prefix="stream_bench_") as workdir:
        for backend in args.backends:
            r, bad = run_backend(
                backend, sessions, appends, args.features, cfg_kw, workdir,
            )
            rows += r
            mismatched += bad
            for row in r:
                extra = ", ".join(
                    f"{k}={v}" for k, v in row.items()
                    if k not in ("bench_key", "wall_s", "backend")
                )
                print(f"{row['bench_key']:44s} {row['wall_s']*1e3:8.2f}ms "
                      f"({extra})", flush=True)

    if mismatched:
        print(f"recovery-gate: {mismatched} session(s) failed bit-exact "
              "replay — recovery is broken, wall times are moot",
              file=sys.stderr)
        return 1
    print("recovery-gate: every recovered session bit-identical to the "
          "live engine", flush=True)

    if args.trace_out:
        tr = obs.get_tracer()
        bus = obs.get_bus()
        artifact = {
            "spans": tr.export(),
            "spans_dropped": tr.dropped,
            "events": bus.export(),
            "events_dropped": bus.dropped,
            "metrics": obs.get_registry().to_json(),
        }
        with open(args.trace_out, "w") as f:
            json.dump(artifact, f, indent=1)
        print(f"wrote trace artifact to {args.trace_out} "
              f"({len(artifact['spans'])} spans, "
              f"{len(artifact['events'])} events)", flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"rows": rows}, f, indent=1)
        print(f"wrote {len(rows)} rows to {args.json}", flush=True)
    if args.baseline:
        bad, unmeasured = check_regression(
            rows, args.baseline, args.max_ratio, args.abs_floor,
        )
        if bad or unmeasured:
            print(f"regression-gate: {bad} stream row(s) regressed "
                  f">{args.max_ratio}x and {unmeasured} baseline key(s) "
                  f"unmeasured vs {args.baseline}", file=sys.stderr)
            return 1
        print(f"regression-gate: all stream rows within {args.max_ratio}x "
              "of baseline", flush=True)
    return 0


if __name__ == "__main__":
    from repro.compile_cache import setup_compile_cache

    setup_compile_cache()
    raise SystemExit(main())
