"""Quickstart: the paper in 40 lines.

Builds a synthetic news day, runs the full greedy baseline, then Submodular
Sparsification (Algorithm 1) + greedy on the reduced set, and prints the
utility ratio, reduction, and the Theorem-2-style certificate.  The same
pipeline is then re-run on each available execution backend (oracle jnp,
Pallas kernels in interpret mode on CPU, shard_map) through the unified
``backend=`` dispatch — identical algorithm, different execution.

    PYTHONPATH=src python examples/quickstart.py [backend]
"""

import sys

import jax
import jax.numpy as jnp

from repro.core import (
    FeatureCoverage,
    StreamingFacilityLocation,
    greedy,
    selection_bucket,
    sieve_streaming,
)
from repro import api, obs
from repro.core.sparsify import ss_sparsify, summarize
from repro.data import clustered_embeddings, news_day
from repro.compile_cache import setup_compile_cache

setup_compile_cache()

N, K = 4096, 10
BACKEND = sys.argv[1] if len(sys.argv) > 1 else "oracle"

print(f"ground set: {N} sentences (synthetic NYT-like day)")
W = jnp.asarray(news_day(seed=0, n_sentences=N, n_features=512))
fn = FeatureCoverage(W=W, phi="sqrt")   # the paper's f(S) = Σ_f sqrt(c_f(S))

# --- offline baseline: greedy on the full ground set -----------------------
full = greedy(fn, K, backend=BACKEND)
print(f"greedy on V:        f(S) = {float(full.value):.4f}")

# --- the paper: SS (c=8, r=8) then greedy on V' -----------------------------
# greedy auto-compacts: V' is sparse, so the per-step gains/argmax run over a
# static |V'|-sized bucket instead of all n (repro.core.greedy).
key = jax.random.PRNGKey(0)
ss = ss_sparsify(fn, key, r=8, c=8.0, backend=BACKEND)
reduced = greedy(fn, K, alive=ss.vprime, backend=BACKEND)
nv = int(jnp.sum(ss.vprime))
bucket = selection_bucket(N, nv)
sel_path = "full-width" if bucket is None else f"compact bucket={bucket}"
print(f"SS -> |V'| = {nv} ({100 * nv / N:.1f}% of V, "
      f"{int(ss.rounds)} rounds, backend={BACKEND}, selection={sel_path})")
print(f"greedy on V':       f(S) = {float(reduced.value):.4f}  "
      f"(relative = {float(reduced.value / full.value):.4f})")
print(f"certificate eps^ = {float(ss.eps_hat):.4f}  "
      f"(Thm 2: f(S') >= (1-1/e)(f(S*) - 2k*eps))")

# --- backend parity: one SS round on every registered backend ---------------
for be in ("oracle", "pallas", "sharded"):
    ss_be = ss_sparsify(fn, key, r=8, c=8.0, backend=be)
    val = float(greedy(fn, K, alive=ss_be.vprime).value)
    print(f"backend {be:8s}: |V'| = {int(jnp.sum(ss_be.vprime)):5d}  "
          f"f(S) = {val:.4f}")

# --- streaming baseline ------------------------------------------------------
sv = sieve_streaming(fn, K)
print(f"sieve-streaming:    f(S) = {float(sv.value):.4f}  "
      f"(relative = {float(sv.value / full.value):.4f})")

# --- one-call pipeline -------------------------------------------------------
res, ss2 = summarize(fn, K, key, preprune=True, importance=True)
print(f"summarize(+§3.4):   f(S) = {float(res.value):.4f}")

# --- one-call facade (the stable public surface, repro.api) ------------------
# docs/serving.md covers the full surface: RunConfig, the async SLO-aware
# scheduler (scheduler="async" + per-request deadline_s), Ticket futures,
# and the "Failure semantics" contract — admission validation, bounded
# retry + backend failover (RunConfig.max_retries / failover_backend), the
# chunk watchdog, the deadline-pressure degradation ladder
# (RunConfig.ladder), and the FaultPlan chaos-testing hook.
# Tracing on for this one request (docs/observability.md): the service
# emits request.admit / queue.wait / chunk.exec spans and the core emits
# ss.sparsify / greedy spans under them — results stay bit-identical
# (telemetry only observes outputs; tests/test_obs.py pins this).
obs.configure(trace=True)
resp = api.summarize(
    W, k=K, key=0,
    config=api.RunConfig(backend=BACKEND if BACKEND != "sharded"
                         else "oracle"),
)
obs.configure(trace=False)
if BACKEND == "oracle":                  # same key + arithmetic -> same picks
    assert (resp.selected == reduced.selected).all()
else:
    # pallas/sharded sequential runs use different execution strategies
    # (fused kernels / distributed probes); values agree, picks may not.
    assert abs(resp.value - float(reduced.value)) < 1e-3 * abs(resp.value)
print(f"api.summarize:      f(S) = {resp.value:.4f}  "
      f"(|V'| = {resp.vprime_size}, batch {resp.batch_size}/"
      f"{resp.batch_bucket}, queue {resp.queue_delay_s * 1e3:.1f} ms)")
print(obs.trace_summary())               # the request's span tree

# --- durable streaming sessions ----------------------------------------------
# A live summary per session over an unbounded element stream: each session
# runs a multi-threshold sieve online, SS periodically prunes its retained
# buffer, and (with root=<dir>) a WAL + snapshots make recovery after a
# crash bit-identical — docs/streaming.md has the full contract.  Volatile
# engine here (root=None); F matches the session config, elements stream
# one (F,) row at a time.
F_s = 64
eng = api.sessions(api.SessionConfig(k=K, n_features=F_s, buffer_cap=64,
                                     resparsify_every=16))
sid = api.open_session(key=0, engine=eng)
for row in jnp.asarray(news_day(seed=1, n_sentences=256, n_features=F_s)):
    api.append(sid, row, engine=eng)
live = api.summary(sid, engine=eng)
print(f"api.summary (live): f(S) = {live.value:.4f}  "
      f"(seen {live.seen}, retained {live.retained}, "
      f"{live.resparsifies} SS compactions)")

# --- matrix-free facility location round-trip --------------------------------
# StreamingFacilityLocation stores only (n, d) embeddings and computes
# similarity tiles on the fly — the objective for ground sets where the dense
# (n, n) sim matrix would not fit (kernels/fl_stream.py, docs/backends.md).
X = jnp.asarray(clustered_embeddings(seed=0, n=N, d=16))
sfl = StreamingFacilityLocation.from_features(X, kernel="dot")
ss_fl = ss_sparsify(sfl, key, r=8, c=8.0, backend=BACKEND)
red_fl = greedy(sfl, K, alive=ss_fl.vprime, backend=BACKEND)
full_fl = greedy(sfl, K, backend=BACKEND)
print(f"streaming FL:       f(S) = {float(red_fl.value):.4f}  "
      f"(relative = {float(red_fl.value / full_fl.value):.4f}, "
      f"|V'| = {int(jnp.sum(ss_fl.vprime))}, memory O(n*d) not O(n^2))")
assert float(red_fl.value / full_fl.value) > 0.9

assert float(reduced.value / full.value) > 0.95
print("OK: SS matches greedy at a fraction of the ground set.")
