"""The readers of the service's host-side metrics (``bench/metrics``), on a
hand-made context whose sums are known, and on one that has nothing of
theirs to read: a window without their spans (as a program without those
spans gives) reads None."""

import importlib
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, trace  # noqa: E402

SERVICE = ["build_ms_per_req", "respond_ms_per_req", "flusher_busy_pct",
           "host_syncs_per_req", "h2d_mb_per_req"]


def span(sid, name, t0, t1, parent=None, **attrs):
    return {"span_id": sid, "parent_id": parent, "name": name, "t0": t0,
            "t1": t1, "attrs": attrs}


def context(spans=(), recs=(), t0=10.0, t_close=20.0):
    return trace.Context(
        recs=list(recs), all_recs=list(recs), spans=list(spans), t0=t0,
        t_close=t_close, pool=[], config={}, cfg=None, peaks=None,
        compiles_in_window=0, profile=None)


def hand_made():
    """Two chunks in the window (3 and 1 queries) and one before it, whose
    spans every reader must leave out."""
    return context([
        # before the window opens
        span(0, "chunk.exec", 9.0, 9.5, batch=8, bucket=8, syncs=2),
        span(1, "chunk.build", 9.1, 9.2, parent=0, h2d_bytes=10**9),
        span(2, "chunk.respond", 9.5, 9.6, batch=8, syncs=30),
        # chunk A: 3 queries
        span(10, "request.admit", 10.5, 10.51, syncs=0),
        span(11, "request.validate", 10.5, 10.509, parent=10, syncs=1,
             h2d_bytes=3_000_000),
        span(12, "chunk.exec", 11.0, 11.4, batch=3, bucket=4, syncs=2),
        span(13, "chunk.build", 11.0, 11.03, parent=12, h2d_bytes=12_000_000),
        span(14, "greedy.plan", 11.2, 11.21, parent=12, syncs=1),
        span(15, "ss.sparsify_batched", 11.05, 11.2, parent=12, obs_syncs=2),
        span(16, "chunk.respond", 11.4, 11.43, batch=3, syncs=12),
        # chunk B: 1 query
        span(20, "chunk.exec", 15.0, 15.2, batch=1, bucket=1, syncs=2),
        span(21, "chunk.build", 15.0, 15.01, parent=20, h2d_bytes=1_000_000),
        span(22, "greedy.plan", 15.1, 15.11, parent=20, syncs=1),
        span(23, "chunk.respond", 15.2, 15.21, batch=1, syncs=4),
        # queue residency carries no counters
        span(24, "queue.wait", 14.0, 15.0),
    ])


def read(name, ctx):
    return importlib.import_module(f"bench.metrics.{name}").read(ctx)


def test_build_and_respond_ms_per_query():
    ctx = hand_made()
    assert read("build_ms_per_req", ctx) == pytest.approx(1e3 * (0.03 + 0.01) / 4)
    assert read("respond_ms_per_req", ctx) == pytest.approx(1e3 * (0.03 + 0.01) / 4)


def test_flusher_busy_pct_is_exec_and_respond_over_the_window():
    busy = (0.4 + 0.03) + (0.2 + 0.01)
    assert read("flusher_busy_pct", hand_made()) == pytest.approx(100 * busy / 10.0)


def test_host_syncs_per_query_leave_out_the_tracers_own_reads():
    syncs = 0 + 1 + 2 + 1 + 12 + 2 + 1 + 4    # obs_syncs=2 is not counted
    assert read("host_syncs_per_req", hand_made()) == pytest.approx(syncs / 4)


def test_h2d_mb_per_query():
    mb = (3_000_000 + 12_000_000 + 1_000_000) / 1e6
    assert read("h2d_mb_per_req", hand_made()) == pytest.approx(mb / 4)


def rec(resolved, settled_t, response=True):
    r = harness.Rec(0, 0, 0, due=0.0, resolved=resolved)
    r.ticket = types.SimpleNamespace(settled_t=settled_t)
    r.response = object() if response else None
    return r


def test_answer_lag_p95_ms():
    recs = [rec(1.0 + i * 1e-3 + 0.002, 1.0 + i * 1e-3) for i in range(20)]
    recs.append(rec(5.0, 4.0, response=False))     # an error: left out
    assert read("answer_lag_p95_ms", context(recs=recs)) == pytest.approx(2.0)


@pytest.mark.parametrize("name", SERVICE + ["answer_lag_p95_ms"])
def test_nothing_to_read_is_none(name):
    """A window with no spans at all, and a program whose spans and tickets
    lack what the reader reads: the reader says nothing and does not
    raise."""
    assert read(name, context()) is None
    older = context([span(1, "chunk.exec", 11.0, 11.4, batch=3, bucket=4),
                     span(2, "ss.sparsify_batched", 11.0, 11.2, parent=1)])
    r = harness.Rec(0, 0, 0, due=0.0, resolved=2.0)
    r.ticket, r.response = types.SimpleNamespace(), object()
    older.recs = [r]
    assert read(name, older) is None


@pytest.mark.parametrize("name", SERVICE)
def test_spans_outside_the_window_are_not_read(name):
    ctx = hand_made()
    ctx.t0, ctx.t_close = 0.0, 8.0
    assert read(name, ctx) is None
