#!/usr/bin/env python3
"""Drive the SS -> greedy serving path once on a TPU and check its results.

    python chip_smoke.py             # one chip: device, service, single, sessions
    python chip_smoke.py --chips 4   # four chips: the sharded phase only

One chip runs four phases.  Each prints one line.

- device: the default backend is a TPU, and the pallas backend compiles its
  kernels (no interpret mode).
- service: 8 news-day coverage queries go through ``api.serve`` with
  ``backend="pallas"`` and no failover.  Each value must be within 1e-3 of
  the same queries served with ``backend="oracle"``, and no response may
  carry a recovery or degradation record.
- single: ``ss_sparsify`` + ``greedy`` run with ``backend="pallas"`` on
  FeatureCoverage, dense FacilityLocation and StreamingFacilityLocation.
  The compiled kernels must agree with the jnp oracle, and f(S) must reach
  0.95 of greedy on the whole ground set (0.9 for streaming FL).
- sessions: a durable ``SessionEngine`` is abandoned without ``close()`` and
  reopened on the same root.  Every recovered summary must be bit-identical.

``--chips 4`` runs SS, exact greedy and stochastic greedy with a
``ShardedBackend`` on a 4-device mesh, compared with ``backend="oracle"`` on
one device.

The last line of stdout is ``{"ok": true, "device": {...}}``.  Without a
TPU, or when any check fails, the script exits non-zero before that line.
Everything runs in this one process.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(HERE, "src"), HERE]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from benchmarks.common import TopicNews  # noqa: E402
from repro import api  # noqa: E402
from repro.compile_cache import setup_compile_cache  # noqa: E402
from repro.core import (  # noqa: E402
    FacilityLocation,
    FeatureCoverage,
    ShardedBackend,
    StreamingFacilityLocation,
    get_backend,
    greedy,
    ss_sparsify,
    stochastic_greedy,
)
from repro.core.backend import default_pallas_interpret  # noqa: E402
from repro.core.distributed import make_mesh  # noqa: E402
from repro.data import clustered_embeddings, news_day, video  # noqa: E402


class SmokeFailure(AssertionError):
    """A check of the smoke run failed."""


def require(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-12)


def timed(fn):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------- phases ----

def phase_device(chips: int) -> dict:
    devs = jax.devices()
    d = devs[0]
    require(d.platform == "tpu",
            f"no TPU: jax.devices()[0].platform is {d.platform!r}")
    require(len(devs) == chips,
            f"expected {chips} TPU device(s), found {len(devs)}")
    require("REPRO_PALLAS_INTERPRET" not in os.environ,
            "REPRO_PALLAS_INTERPRET is set; a chip run compiles its kernels")
    require(default_pallas_interpret() is False,
            "pallas would run in interpret mode on the chip")
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devs)}
    log("device", platform=d.platform, kind=repr(d.device_kind),
        count=len(devs), jax=jax.__version__)
    return device


def phase_service(seed: int, n_requests: int = 8, n_range=(800, 6000),
                  n_features: int = 1024, k: int = 10) -> None:
    """News-day coverage queries (benchmarks/fig3_news.py's n range and
    TopicNews features) through the service, pallas vs oracle."""
    rng = np.random.default_rng(seed)
    sizes = [int(rng.integers(*n_range)) for _ in range(n_requests)]
    reqs = [
        api.SummarizeRequest(
            k=k, key=i,
            features=jnp.asarray(
                TopicNews(seed * 1000 + i, n).features(n_features)),
        )
        for i, n in enumerate(sizes)
    ]

    def serve(backend):
        cfg = api.RunConfig(backend=backend, failover_backend=None)
        t0 = time.perf_counter()
        with api.serve(cfg) as svc:
            tickets = [svc.submit(r) for r in reqs]
            svc.flush()
            out = [t.result() for t in tickets]
        return out, time.perf_counter() - t0

    got, t_pallas = serve("pallas")
    ref, t_oracle = serve("oracle")
    worst = 0.0
    for n, g, o in zip(sizes, got, ref):
        require(g.recovery is None, f"n={n}: recovery record {g.recovery}")
        require(g.degradation is None,
                f"n={n}: degradation record {g.degradation}")
        require(math.isfinite(g.value) and g.value > 0, f"n={n}: f={g.value}")
        worst = max(worst, rel(g.value, o.value))
        require(rel(g.value, o.value) <= 1e-3,
                f"n={n}: pallas {g.value} vs oracle {o.value}")
    log("service", requests=n_requests, n=sizes, F=n_features, k=k,
        max_rel_vs_oracle=f"{worst:.3g}",
        vprime=[g.vprime_size for g in got],
        wall_s_pallas_cold=f"{t_pallas:.2f}",
        wall_s_oracle_cold=f"{t_oracle:.2f}")


def _single(name: str, fn, k: int, key, floor: float, r: int = 8,
            c: float = 8.0, parity_probes: int = 8) -> None:
    """ss_sparsify + greedy on the compiled pallas kernels against greedy
    on the whole ground set, plus a direct kernel-vs-oracle parity check."""
    pallas, oracle = get_backend("pallas"), get_backend("oracle")
    probes = jax.random.choice(jax.random.fold_in(key, 1), fn.n,
                               (parity_probes,), replace=False)
    residual = fn.residual_gains()
    live = np.ones((fn.n,), bool)
    live[np.asarray(probes)] = False      # probe entries belong to V'
    div_p = np.asarray(pallas.divergence(fn, probes, residual=residual))[live]
    div_o = np.asarray(oracle.divergence(fn, probes, residual=residual))[live]
    div_err = float(np.max(np.abs(div_p - div_o)) / np.max(np.abs(div_o)))
    require(np.all(np.isfinite(div_p)) and div_err <= 1e-4,
            f"{name}: pallas divergence off the oracle by {div_err}")

    def run():
        ss = ss_sparsify(fn, key, r=r, c=c, backend=pallas)
        return ss, greedy(fn, k, alive=ss.vprime, backend=pallas)

    (ss, red), t_cold = timed(run)
    (ss2, _), t_warm = timed(run)
    require(bool(jnp.all(ss.vprime == ss2.vprime)), f"{name}: SS not stable")
    full = greedy(fn, k, backend=oracle)
    ratio = float(red.value) / float(full.value)
    require(ratio >= floor, f"{name}: f(S)/f(greedy on V) = {ratio} < {floor}")
    # Gains kernel vs oracle at the state greedy on V ends in.  (Greedy runs
    # themselves are not compared: near-tied gains let a 1-ulp difference
    # pick another, equally greedy, path.)
    g_p = np.asarray(pallas.gains(fn, full.state))
    g_o = np.asarray(oracle.gains(fn, full.state))
    gain_err = float(np.max(np.abs(g_p - g_o)) / np.max(np.abs(g_o)))
    require(np.all(np.isfinite(g_p)) and gain_err <= 1e-4,
            f"{name}: pallas gains off the oracle by {gain_err}")
    log("single", objective=name, n=fn.n, k=k,
        vprime=int(jnp.sum(ss.vprime)), rounds=int(ss.rounds),
        ratio=f"{ratio:.4f}", kernel_div_rel_err=f"{div_err:.3g}",
        kernel_gains_rel_err=f"{gain_err:.3g}",
        wall_s_cold=f"{t_cold:.3f}", wall_s_warm=f"{t_warm:.3f}")


def phase_single(seed: int, n_cov: int = 6000, n_fl: int = 9721,
                 n_stream: int = 65536) -> None:
    key = jax.random.PRNGKey(seed)
    # News day at the top of fig3_news's n range, F = 1024.
    W = TopicNews(seed, n_cov).features(1024)
    _single("coverage", FeatureCoverage(W=jnp.asarray(W), phi="sqrt"),
            10, key, 0.95)
    # The longest SumMe video of table2_video (9,721 frames) at full length;
    # the summary budget is 15% of frames, as its _reference sets it.
    X = video(seed * 100 + 10, n_fl, n_features=256)
    _single("facility_location",
            FacilityLocation.from_features(jnp.asarray(X), kernel="cosine"),
            max(1, int(0.15 * n_fl)), key, 0.95)
    # fig1_scaling.run_stream's matrix-free FL ground set.
    E = clustered_embeddings(seed + n_stream, n_stream, 16)
    _single("streaming_fl",
            StreamingFacilityLocation.from_features(jnp.asarray(E),
                                                    kernel="dot"),
            10, key, 0.9)


def phase_sessions(seed: int, n_sessions: int = 4, rows: int = 768,
                   n_features: int = 256) -> None:
    """Durable sessions: ingest, abandon the engine, reopen, compare."""
    cfg = api.SessionConfig(k=10, n_features=n_features, buffer_cap=128,
                            resparsify_every=32, snapshot_every=256,
                            backend="pallas")
    rng = np.random.default_rng(seed)
    scale = 1.0 + 6.0 * np.arange(rows, dtype=np.float32) / rows
    streams = {
        f"u{i}": rng.random((rows, n_features)).astype(np.float32)
        * scale[:, None]
        for i in range(n_sessions)
    }
    with tempfile.TemporaryDirectory() as root:
        eng = api.SessionEngine(cfg, root)
        for i, sid in enumerate(streams):
            eng.open_session(sid=sid, key=i)
        t0 = time.perf_counter()
        for t in range(rows):
            for sid, xs in streams.items():
                eng.append(sid, xs[t])
        before = {sid: eng.summary(sid) for sid in streams}
        t_ingest = time.perf_counter() - t0
        del eng                 # abandoned: no close(), no final snapshot
        t0 = time.perf_counter()
        rec = api.SessionEngine(cfg, root)
        after = {sid: rec.summary(sid) for sid in streams}
        t_recover = time.perf_counter() - t0
        require(rec.sessions() == sorted(streams), "sessions lost")
        for sid in streams:
            a, b = before[sid], after[sid]
            require(np.array_equal(a.selected, b.selected)
                    and np.array_equal(a.gains, b.gains)
                    and (a.value, a.sieve_value, a.retained, a.seen, a.drops,
                         a.resparsifies)
                    == (b.value, b.sieve_value, b.retained, b.seen, b.drops,
                        b.resparsifies),
                    f"session {sid}: recovered summary differs")
        rec.close()
    log("sessions", sessions=n_sessions, appends=n_sessions * rows,
        F=n_features, resparsifies=[before[s].resparsifies for s in streams],
        values=[f"{before[s].value:.4f}" for s in streams],
        bit_identical=True, wall_s_ingest=f"{t_ingest:.2f}",
        wall_s_recover=f"{t_recover:.2f}")


def phase_sharded(seed: int, n: int = 65536, n_features: int = 1024,
                  k: int = 10, chips: int = 4) -> None:
    """ShardedBackend on a ``chips``-device mesh vs the one-device oracle,
    with the value tolerances of tests/test_distributed.py."""
    W = news_day(seed, n, n_features)
    mesh = make_mesh((chips,), ("data",))
    be = ShardedBackend(mesh=mesh)
    # The sharded objective's rows live spread over the mesh from the start
    # (ss_sparsify_sharded places them the same way); the oracle's copy
    # lives on device 0 only and is made after the spread is read.
    fn_s = FeatureCoverage(
        W=jax.device_put(W, NamedSharding(mesh, P("data"))), phi="sqrt")
    spread = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in jax.devices()]
    shard_bytes = W.nbytes // chips
    require(all(b is None or b >= shard_bytes for b in spread),
            f"W is not spread over the mesh: bytes_in_use {spread}")
    fn = FeatureCoverage(W=jnp.asarray(W), phi="sqrt")
    key = jax.random.PRNGKey(seed)

    def ss_then_greedy(f, backend):
        ss = ss_sparsify(f, key, r=8, c=8.0, backend=backend)
        return ss, greedy(f, k, alive=ss.vprime, backend=backend)

    (ss_s, g_s), t_s = timed(lambda: ss_then_greedy(fn_s, be))
    (ss_o, g_o), t_o = timed(lambda: ss_then_greedy(fn, "oracle"))
    full = greedy(fn, k, backend="oracle")
    ratio = float(g_s.value) / float(full.value)
    require(0 < int(jnp.sum(ss_s.vprime)) < n, "sharded SS pruned nothing")
    require(ratio > 0.95, f"sharded f(S)/f(greedy on V) = {ratio}")
    ss_rel = rel(float(g_s.value), float(g_o.value))
    require(ss_rel < 2e-2, f"sharded vs oracle V' value: {ss_rel}")

    # Same V' and key: the distributed selectors must pick the oracle's set.
    alive = ss_s.vprime
    g_ref = greedy(fn, k, alive=alive, backend="oracle")
    sk = jax.random.fold_in(key, 7)
    sg_s = stochastic_greedy(fn_s, k, sk, alive=alive, backend=be)
    sg_o = stochastic_greedy(fn, k, sk, alive=alive, backend="oracle")
    for what, a, b in (("greedy", g_s, g_ref), ("stochastic", sg_s, sg_o)):
        require(np.array_equal(np.asarray(a.selected), np.asarray(b.selected)),
                f"sharded {what} selection differs from the oracle's")
        np.testing.assert_allclose(np.asarray(a.gains), np.asarray(b.gains),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(float(a.value), float(b.value), rtol=1e-5)
    log("sharded", chips=chips, n=n, F=n_features, k=k,
        vprime=int(jnp.sum(ss_s.vprime)), vprime_oracle=int(jnp.sum(ss_o.vprime)),
        ratio=f"{ratio:.4f}", rel_vs_oracle_vprime=f"{ss_rel:.3g}",
        selections_match=True, W_shard_bytes=shard_bytes,
        bytes_in_use_sharded=spread,
        wall_s_sharded_cold=f"{t_s:.2f}", wall_s_oracle_cold=f"{t_o:.2f}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded phase on a 4-chip mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    cache = setup_compile_cache()
    device = phase_device(args.chips)
    log("cache", compile_cache_dir=cache)
    if args.chips == 4:
        phase_sharded(args.seed, chips=4)
    else:
        phase_service(args.seed)
        phase_single(args.seed)
        phase_sessions(args.seed)
    log("done", wall_s=f"{time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
