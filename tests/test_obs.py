"""Observability contract tests (docs/observability.md).

The pins, in order of importance:

1. **Purity** — telemetry is a pure observer: `vprime` / `eps_hat` /
   `selected` / `gains` are bit-identical with tracing on and off, on
   oracle AND pallas, and under an outer `jit` the hooks vanish entirely.
2. **Fidelity** — what telemetry reports matches what the computation did:
   per-round SS records agree with `alive_trace`, the greedy gain
   trajectory with `GreedyResult.gains`, the obs histograms with the
   service's own `stats()` aggregates.
3. **One bus** — a seeded chaos run's fault draws, recovery steps and
   session audit events land on the unified bus with consistent
   request/session ids under one global ordering.
4. The plumbing itself: bounded ring logs, span trees, exporter formats,
   the pull endpoint, the EWMA helper the flusher estimates ride.
"""

import json
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, obs
from repro.core import FeatureCoverage, PallasBackend, greedy, ss_sparsify
from repro.data import news_day
from repro.serve import (
    FaultPlan,
    RunConfig,
    SummarizeRequest,
    SummarizeService,
)
from repro.serve.sessions import SessionConfig, SessionEngine
from repro.serve.summarize_service import ewma_update

BACKENDS = {
    "oracle": lambda: "oracle",
    "pallas": lambda: PallasBackend(interpret=True),
}


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts from empty global sinks and tracing off."""
    obs.reset()
    obs.configure(trace=False)
    yield
    obs.reset()
    obs.configure(trace=False)


def make_fn(n=256, F=64, seed=0):
    return FeatureCoverage(W=jnp.asarray(news_day(seed, n, F)), phi="sqrt")


def make_queries(num, n=256, F=64, k=5, seed=0):
    return [
        SummarizeRequest(
            k=k, key=jax.random.PRNGKey(seed * 1000 + i),
            features=jnp.asarray(news_day(seed * 1000 + i, n, F)),
        )
        for i in range(num)
    ]


# ------------------------------------------------------------- ring log ----

class TestRingLog:
    def test_bounded_with_drop_counter(self):
        log = obs.RingLog(capacity=4)
        for i in range(10):
            log.append(i)
        assert log.list() == [6, 7, 8, 9]
        assert log.dropped == 6
        assert len(log) == 4

    def test_list_compat(self):
        log = obs.RingLog(capacity=8)
        assert not log                      # empty is falsy, like a list
        assert log == []
        for i in range(3):
            log.append(i)
        assert log == [0, 1, 2]             # equality against a plain list
        assert log[1] == 1
        assert log[-1] == 2
        assert list(log) == [0, 1, 2]
        other = obs.RingLog(capacity=5)
        for i in range(3):
            other.append(i)
        assert log == other                 # and against another RingLog
        log.clear()
        assert log == [] and log.dropped == 0

    def test_capacity_validation(self):
        with pytest.raises(ValueError):
            obs.RingLog(capacity=0)


# ------------------------------------------------------------ event bus ----

class TestEventBus:
    def test_ordering_and_filters(self):
        bus = obs.get_bus()
        bus.emit("fault", subsystem="faults", request_ids=(1, 2), kind_x=1)
        bus.emit("recovery", subsystem="service", request_ids=(2,))
        bus.emit("rehydrate", subsystem="sessions", session_id="u1", n=3)
        seqs = [e.seq for e in bus.events()]
        assert seqs == sorted(seqs) and len(seqs) == 3
        assert [e.kind for e in bus.events(subsystem="service")] == [
            "recovery"
        ]
        assert [e.kind for e in bus.events(request_id=2)] == [
            "fault", "recovery"
        ]
        assert bus.events(session_id="u1")[0].data == {"n": 3}

    def test_export_is_json_serializable(self):
        bus = obs.get_bus()
        bus.emit("fault", subsystem="faults", request_ids=(7,), detail="x")
        dump = json.loads(json.dumps(bus.export()))
        assert dump[0]["request_ids"] == [7]
        assert dump[0]["subsystem"] == "faults"


# --------------------------------------------------------------- tracer ----

class TestTracer:
    def test_disabled_spans_are_noops(self):
        assert not obs.trace_enabled()
        with obs.span("anything", x=1) as sp:
            sp.set(y=2)
        assert obs.get_tracer().spans() == []

    def test_span_tree_parenting(self):
        obs.configure(trace=True)
        tr = obs.get_tracer()
        with tr.span("outer", trace_id="req-0") as outer:
            with tr.span("inner") as inner:
                pass
        spans = tr.spans(trace_id="req-0")
        assert [s.name for s in spans] == ["inner", "outer"]
        assert inner.parent_id == outer.span_id
        assert inner.trace_id == "req-0"    # inherited from the parent
        assert outer.t0 <= inner.t0 and inner.t1 <= outer.t1

    def test_error_status_and_retroactive_record(self):
        obs.configure(trace=True)
        tr = obs.get_tracer()
        with pytest.raises(RuntimeError):
            with tr.span("boom"):
                raise RuntimeError("x")
        assert tr.spans(name="boom")[0].status == "error"
        t0 = time.perf_counter() - 0.5
        tr.record("queue.wait", t0, t0 + 0.25, trace_id="req-3", lane="a")
        (sp,) = tr.spans(name="queue.wait")
        assert abs(sp.wall_s - 0.25) < 1e-9
        assert sp.trace_id == "req-3"

    def test_spans_for_request_includes_shared_and_descendants(self):
        obs.configure(trace=True)
        tr = obs.get_tracer()
        tr.record("request.admit", 0.0, 0.1, trace_id="req-1")
        with tr.span("chunk.exec", trace_id="batch", request_ids=(0, 1)):
            with tr.span("ss.sparsify"):
                pass
        names = {s.name for s in tr.spans_for_request(1)}
        assert names == {"request.admit", "chunk.exec", "ss.sparsify"}
        # request 2 rode no chunk: nothing leaks into its tree
        assert tr.spans_for_request(2) == []

    def test_ring_is_bounded(self):
        obs.configure(trace=True, capacity=8)
        try:
            tr = obs.get_tracer()
            for i in range(20):
                with tr.span(f"s{i}"):
                    pass
            assert len(tr.spans()) == 8
            assert tr.dropped == 12
        finally:
            obs.configure(capacity=obs.trace.DEFAULT_CAPACITY)

    def test_transfer_helpers_count_on_the_innermost_span(self):
        host = np.ones((3, 4), np.float64)      # copied as float32
        dev = jnp.ones((5,))
        assert obs.device_put("x", host).dtype == jnp.float32   # off: no span
        obs.configure(trace=True)
        tr = obs.get_tracer()
        with tr.span("outer") as outer:
            with tr.span("inner") as inner:
                obs.device_put("put", (host, dev))   # dev is already there
                got = obs.device_get("get", dev)
                obs.block_until_ready("wait", dev)
                obs.device_get("own", dev, instrumentation=True)
            obs.device_get("get", dev)
        assert isinstance(got, np.ndarray) and (got == 1).all()
        assert inner.attrs["h2d_bytes"] == 3 * 4 * 4
        assert inner.attrs["syncs"] == 2 and inner.attrs["obs_syncs"] == 1
        assert inner.attrs["sites"] == {"put": 1, "get": 1, "wait": 1,
                                        "own": 1}
        assert outer.attrs == {"syncs": 1, "sites": {"get": 1}}

    def test_format_trace_renders_tree(self):
        obs.configure(trace=True)
        tr = obs.get_tracer()
        with tr.span("chunk.exec", trace_id="req-0", request_ids=(0,)):
            with tr.span("greedy.select", k=5):
                pass
        txt = obs.format_trace("req-0")
        assert "chunk.exec" in txt and "greedy.select" in txt
        assert "k=5" in txt
        assert obs.trace_summary().startswith("trace req-0")


# -------------------------------------------------------------- metrics ----

class TestMetrics:
    def test_counter_gauge_histogram(self):
        reg = obs.get_registry()
        c = reg.counter("t_total", "a counter", labels=("kind",))
        c.inc(kind="x")
        c.inc(2, kind="x")
        c.inc(kind="y")
        assert c.value(kind="x") == 3 and c.value(kind="y") == 1
        g = reg.gauge("t_gauge", "a gauge")
        g.set(5)
        g.add(-2)
        assert g.value() == 3
        h = reg.histogram("t_seconds", "a histogram")
        for v in (0.01, 0.02, 0.03):
            h.observe(v)
        st = h.stats()
        assert st["count"] == 3
        assert abs(st["sum"] - 0.06) < 1e-12
        assert abs(st["mean"] - 0.02) < 1e-12

    def test_create_or_get_rejects_mismatch(self):
        reg = obs.get_registry()
        reg.counter("t_total", "c", labels=("a",))
        assert reg.counter("t_total", "c", labels=("a",)) is reg.get(
            "t_total"
        )
        with pytest.raises(ValueError):
            reg.gauge("t_total", "now a gauge?")
        with pytest.raises(ValueError):
            reg.counter("t_total", "c", labels=("b",))

    def test_prometheus_exposition(self):
        reg = obs.get_registry()
        reg.counter("t_total", "hits", labels=("kind",)).inc(3, kind="x")
        reg.histogram("t_seconds", "lat").observe(0.5)
        text = reg.to_prometheus()
        assert "# HELP t_total hits" in text
        assert "# TYPE t_total counter" in text
        assert 't_total{kind="x"} 3' in text
        assert "# TYPE t_seconds histogram" in text
        assert 't_seconds_bucket{le="+Inf"} 1' in text
        assert "t_seconds_count 1" in text
        # buckets are cumulative: every le >= 0.5 counts the observation
        lines = [ln for ln in text.splitlines() if "t_seconds_bucket" in ln]
        counts = [int(float(ln.rsplit(" ", 1)[1])) for ln in lines]
        assert counts == sorted(counts)

    def test_json_export(self):
        reg = obs.get_registry()
        reg.counter("t_total", "hits", labels=("kind",)).inc(kind="x")
        reg.histogram("t_seconds", "lat").observe(0.5)
        dump = json.loads(json.dumps(reg.to_json()))
        m = dump["t_total"]
        assert m["kind"] == "counter"
        assert m["series"][0]["labels"] == {"kind": "x"}
        assert m["series"][0]["value"] == 1
        h = dump["t_seconds"]["series"][0]
        assert h["count"] == 1 and h["sum"] == 0.5
        assert len(h["buckets"]) == len(h["bounds"])

    def test_pull_endpoint(self):
        obs.get_registry().counter("t_total", "hits").inc(4)
        try:
            srv = obs.start_metrics_server(port=0)
        except OSError:
            pytest.skip("cannot bind a local port in this sandbox")
        try:
            host, port = srv.server_address[:2]
            text = urllib.request.urlopen(
                f"http://{host}:{port}/metrics", timeout=5
            ).read().decode()
            assert "t_total 4" in text
            blob = urllib.request.urlopen(
                f"http://{host}:{port}/metrics.json", timeout=5
            ).read().decode()
            assert "t_total" in json.loads(blob)
        finally:
            srv.shutdown()


# ------------------------------------------------- purity and fidelity ----

@pytest.mark.parametrize("backend", list(BACKENDS))
class TestTelemetryPurity:
    def test_results_bit_identical_traced_vs_untraced(self, backend):
        be = BACKENDS[backend]()
        fn = make_fn(n=256, F=64)
        key = jax.random.PRNGKey(0)

        ss0 = ss_sparsify(fn, key, r=3, backend=be)
        res0 = greedy(fn, 5, alive=ss0.vprime, backend=be)

        obs.configure(trace=True)
        ss1 = ss_sparsify(fn, key, r=3, backend=be)
        res1 = greedy(fn, 5, alive=ss1.vprime, backend=be)

        assert (np.asarray(ss0.vprime) == np.asarray(ss1.vprime)).all()
        assert np.asarray(ss0.eps_hat) == np.asarray(ss1.eps_hat)
        assert int(ss0.rounds) == int(ss1.rounds)
        assert (
            np.asarray(ss0.alive_trace) == np.asarray(ss1.alive_trace)
        ).all()
        assert (
            np.asarray(res0.selected) == np.asarray(res1.selected)
        ).all()
        assert (np.asarray(res0.gains) == np.asarray(res1.gains)).all()
        assert np.asarray(res0.value) == np.asarray(res1.value)

    def test_span_telemetry_matches_results(self, backend):
        be = BACKENDS[backend]()
        fn = make_fn(n=256, F=64)
        key = jax.random.PRNGKey(1)
        obs.configure(trace=True)
        ss = ss_sparsify(fn, key, r=3, backend=be)
        res = greedy(fn, 5, alive=ss.vprime, backend=be)

        (sp,) = obs.get_tracer().spans(name="ss.sparsify")
        assert sp.attrs["rounds"] == int(ss.rounds)
        assert sp.attrs["eps_hat"] == float(ss.eps_hat)
        assert sp.attrs["vprime_size"] == int(jnp.sum(ss.vprime))
        # per-round records derive from alive_trace: same live counts, and
        # the model-apportioned wall estimates sum to the measured total
        lives = [int(v) for v in np.asarray(ss.alive_trace) if v >= 0]
        detail = sp.attrs["rounds_detail"]
        assert [d["live"] for d in detail] == lives
        assert len(detail) == int(ss.rounds)
        # estimates apportion the measured compute wall exactly, and that
        # wall sits inside the span (which also covers the host readout)
        wall = sp.attrs["wall_s"]
        assert sum(d["wall_est_s"] for d in detail) == pytest.approx(wall)
        assert 0.0 < wall <= sp.wall_s

        (gp,) = obs.get_tracer().spans(name="greedy.select")
        assert gp.attrs["gains"] == [float(g) for g in np.asarray(res.gains)]
        assert gp.attrs["value"] == float(res.value)
        assert gp.attrs["selector"] == "greedy"

        reg = obs.get_registry()
        be_name = "oracle" if backend == "oracle" else "pallas"
        assert reg.get("repro_ss_wall_seconds").stats(
            backend=be_name
        )["count"] == 1
        assert reg.get("repro_ss_rounds_total").value(
            backend=be_name
        ) == int(ss.rounds)

    def test_hooks_vanish_under_jit(self, backend):
        be = BACKENDS[backend]()
        fn = make_fn(n=128, F=32)

        def pipeline(key):
            ss = ss_sparsify(fn, key, r=2, backend=be)
            return greedy(fn, 4, alive=ss.vprime, backend=be).selected

        key = jax.random.PRNGKey(2)
        eager = np.asarray(pipeline(key))
        obs.configure(trace=True)
        obs.get_tracer().clear()
        jitted = np.asarray(jax.jit(pipeline)(key))
        assert (eager == jitted).all()
        # inputs were tracers -> no span, and crucially no host sync inside
        # the compiled region (the call would have raised otherwise)
        assert obs.get_tracer().spans(name="ss.sparsify") == []


# ----------------------------------------------- service-layer metrics ----

class TestServiceTelemetry:
    def test_histograms_agree_with_stats(self):
        queries = make_queries(6, n=128, F=32, k=4)
        svc = SummarizeService(RunConfig(max_batch=4))
        svc.run(queries)
        st = svc.stats()
        reg = obs.get_registry()
        assert reg.get("repro_service_queries_total").value() == st[
            "queries"
        ]
        assert sum(
            reg.get("repro_service_batches_total").value(trigger=t)
            for t in st["triggers"]
        ) == st["batches"]
        ex = reg.get("repro_service_exec_seconds")
        total = sum(s["sum"] for s in ex.snapshot().values())
        assert abs(total - st["exec_s_total"]) < 1e-9
        qd = reg.get("repro_service_queue_delay_seconds")
        counts = sum(s["count"] for s in qd.snapshot().values())
        assert counts == st["queries"]
        slots = reg.get("repro_service_slots_total").value()
        padded = reg.get("repro_service_padded_slots_total").value()
        assert st["padding_waste_frac"] == padded / slots
        admitted = reg.get("repro_service_requests_total").value(
            outcome="admitted"
        )
        assert admitted == st["queries"]

    def test_request_spans_cover_the_request(self):
        obs.configure(trace=True)
        queries = make_queries(3, n=128, F=32, k=4)
        svc = SummarizeService(RunConfig(max_batch=4))
        responses = svc.run(queries)
        for i, resp in enumerate(responses):
            spans = obs.get_tracer().spans_for_request(i)
            names = [s.name for s in spans]
            assert "request.admit" in names
            assert "queue.wait" in names
            assert "chunk.exec" in names
            assert "ss.sparsify_batched" in names
            (wait,) = [s for s in spans if s.name == "queue.wait"]
            # the span IS the timing source (serve_bench reads it): it must
            # agree with the response's own queue-delay bookkeeping
            assert abs(wait.wall_s - resp.queue_delay_s) < 0.05

    def test_api_stats_and_metrics(self):
        queries = make_queries(2, n=128, F=32, k=4)
        svc = SummarizeService(RunConfig(max_batch=2))
        svc.run(queries)
        assert api.stats(svc) == svc.stats()
        text = api.metrics()
        assert isinstance(text, str)
        assert "repro_service_queries_total" in text
        blob = api.metrics(fmt="json")
        assert "repro_service_queries_total" in blob
        with pytest.raises(ValueError):
            api.metrics(fmt="yaml")

    def test_stats_snapshot_is_consistent_under_load(self):
        queries = make_queries(8, n=128, F=32, k=4)
        svc = SummarizeService(
            RunConfig(max_batch=4, scheduler="async", max_wait_s=0.01)
        )
        snaps = []
        stop = threading.Event()

        def hammer():
            while not stop.is_set():
                snaps.append(svc.stats())

        t = threading.Thread(target=hammer)
        t.start()
        try:
            with svc:
                for q in queries:
                    svc.submit(q)
                svc.drain()
        finally:
            stop.set()
            t.join()
        snaps.append(svc.stats())
        seen = 0
        for st in snaps:
            # monotone counters: no snapshot can tear backwards
            assert st["queries"] >= seen
            seen = st["queries"]
            # derived values are computed under the same lock as the
            # counters they divide — a torn snapshot would break this
            if st["queries"]:
                assert (
                    st["queue_delay_s_mean"]
                    <= st["queue_delay_s_max"] + 1e-12
                )
        assert snaps[-1]["queries"] == len(queries)


# ------------------------------------------------ host boundaries ----

def host_queries(num=2, n=128, F=32, k=4, seed=3):
    """Requests whose payloads are host (numpy) arrays, as a client sends
    them: admission and the chunk build each copy them to the device."""
    return [
        SummarizeRequest(
            k=k, key=jax.random.PRNGKey(seed * 100 + i),
            features=news_day(seed * 100 + i, n, F),
        )
        for i in range(num)
    ]


def payload_bytes(req):
    return req.features.shape[0] * req.features.shape[1] * 4   # float32


class TestHostBoundaries:
    """Spans at each host boundary of a served query, the host<->device
    traffic counted on them, and the bridge to the profiler's clock."""

    def test_chunk_tree_holds_each_host_boundary(self):
        obs.configure(trace=True)
        reqs = host_queries(num=2)
        SummarizeService(RunConfig(max_batch=2)).run(reqs)
        tr = obs.get_tracer()
        tree = tr.spans_for_request(0)
        by_name = {s.name: s for s in tree}
        for name in ("request.admit", "request.validate", "queue.wait",
                     "chunk.exec", "chunk.build", "ss.sparsify_batched",
                     "greedy.plan", "greedy.select_batched", "chunk.respond"):
            assert name in by_name, name
        chunk = by_name["chunk.exec"]
        build, plan = by_name["chunk.build"], by_name["greedy.plan"]
        select, respond = by_name["greedy.select_batched"], by_name["chunk.respond"]
        assert by_name["request.validate"].parent_id == \
            by_name["request.admit"].span_id
        assert build.parent_id == chunk.span_id
        assert plan.parent_id == chunk.span_id and plan.t1 <= select.t0
        # chunk.respond follows chunk.exec and names the same chunk
        assert respond.t0 >= chunk.t1
        assert respond.trace_id == chunk.trace_id
        assert respond.attrs["request_ids"] == chunk.attrs["request_ids"]
        assert respond.attrs["batch"] == chunk.attrs["batch"] == 2

        # bytes: each payload once at admission, once at the build
        sizes = [payload_bytes(r) for r in reqs]
        validates = tr.spans(name="request.validate")
        assert sorted(s.attrs["h2d_bytes"] for s in validates) == sorted(sizes)
        assert build.attrs["h2d_bytes"] == sum(sizes)
        assert sum(s.attrs.get("h2d_bytes", 0) for s in tr.spans()) == \
            2 * sum(sizes)

        # syncs: admission's check, the live-count read, and the chunk's
        # one readout; response assembly reads nothing from the device
        assert [s.attrs["syncs"] for s in validates] == [1, 1]
        assert plan.attrs["syncs"] == 1
        assert chunk.attrs["syncs"] == 1
        assert chunk.attrs["sites"] == {"chunk.readout": 1}
        assert "syncs" not in respond.attrs
        assert "sites" not in respond.attrs
        # the tracer's own reads are kept apart: one wait and one read each
        for name in ("ss.sparsify_batched", "greedy.select_batched"):
            assert by_name[name].attrs["obs_syncs"] == 2
            assert "syncs" not in by_name[name].attrs

    @pytest.mark.parametrize("num", [1, 2, 3])
    def test_a_served_chunk_reads_the_device_once_after_selection(
        self, num, monkeypatch
    ):
        """Whatever the chunk's size (3 is padded to a bucket of 4), one
        ``jax.device_get`` follows selection, and the responses are host
        rows of ``summarize_batch``'s output."""
        from repro.serve import summarize_service as svc_mod

        obs.configure(trace=True)
        real_get, real_batch = jax.device_get, svc_mod.summarize_batch
        outputs, reads = [], []

        def counting_get(x):
            if outputs:
                reads.append(x)
            return real_get(x)

        def recording_batch(*a, **kw):
            out = real_batch(*a, **kw)
            outputs.append(out)
            return out

        monkeypatch.setattr(jax, "device_get", counting_get)
        monkeypatch.setattr(svc_mod, "summarize_batch", recording_batch)
        got = SummarizeService(RunConfig(max_batch=4)).run(
            host_queries(num=num))
        monkeypatch.undo()

        assert len(outputs) == 1 and len(reads) == 1
        tr = obs.get_tracer()
        (chunk,) = tr.spans(name="chunk.exec")
        (respond,) = tr.spans(name="chunk.respond")
        assert chunk.attrs["batch"] == num
        assert chunk.attrs["sites"] == {"chunk.readout": 1}
        assert "syncs" not in respond.attrs

        (res, ss), = outputs
        sel, gains = np.asarray(res.selected), np.asarray(res.gains)
        vprime = np.asarray(ss.vprime)
        for i, resp in enumerate(got):
            assert isinstance(resp.selected, np.ndarray)
            assert isinstance(resp.gains, np.ndarray)
            assert resp.selected.dtype == sel.dtype
            assert resp.gains.dtype == gains.dtype
            assert (resp.selected == sel[i]).all()
            assert (resp.gains == gains[i]).all()
            assert resp.value == float(np.asarray(res.value)[i])
            assert resp.vprime_size == int(vprime[i].sum())
            assert resp.eps_hat == float(np.asarray(ss.eps_hat)[i])
            assert resp.rounds == int(np.asarray(ss.rounds)[i])

    def test_rejected_admission_marks_its_span_failed(self):
        obs.configure(trace=True)
        bad = host_queries(num=1)[0]
        bad.features[0, 0] = np.nan
        ticket = SummarizeService(RunConfig()).submit(bad)
        assert isinstance(ticket.exception(timeout=0), ValueError)
        (admit,) = obs.get_tracer().spans(name="request.admit")
        assert admit.status == "error" and admit.attrs["error"] == "ValueError"
        assert "lane" not in admit.attrs
        (check,) = obs.get_tracer().spans(name="request.validate")
        assert check.parent_id == admit.span_id and check.attrs["syncs"] == 1

    @pytest.mark.parametrize("trace", [False, True])
    @pytest.mark.parametrize("scheduler", ["sync", "async"])
    def test_no_device_read_bypasses_the_helpers(self, trace, scheduler,
                                                 monkeypatch):
        """A service round with implicit device-to-host transfers
        disallowed, in every thread.  JAX's guard does not fire on the CPU
        backend, whose arrays are read in place, so the test also refuses
        any read of an array's host value outside an explicit
        ``jax.device_get``, which is what the guard does on a TPU.

        The host-to-device direction cannot be guarded so: Python scalars
        trip that guard (an int index into a device array, the fill of
        ``jnp.zeros``), and ``jnp.asarray`` of a host array counts as
        explicit.  The payload copies are held by the byte count of
        ``test_chunk_tree_holds_each_host_boundary`` instead."""
        from jax._src import array as jax_array
        from jax._src.lib import guard_lib

        reqs = host_queries(num=3)
        want = SummarizeService(RunConfig(max_batch=2)).run(reqs)
        obs.configure(trace=trace)
        cfg = RunConfig(max_batch=2, scheduler=scheduler, max_wait_s=0.01,
                        stream_steps=True)
        host_value = jax_array.ArrayImpl._value

        def guarded(arr):
            if not guard_lib.thread_local_state().explicit_device_get:
                raise AssertionError("implicit device-to-host read")
            return host_value.fget(arr)

        jax.config.update("jax_transfer_guard_device_to_host", "disallow")
        monkeypatch.setattr(jax_array.ArrayImpl, "_value", property(guarded))
        try:
            with SummarizeService(cfg) as svc:
                got = svc.run(reqs)
        finally:
            monkeypatch.undo()
            jax.config.update("jax_transfer_guard_device_to_host", "allow")
        for a, b in zip(want, got):
            assert (np.asarray(a.selected) == np.asarray(b.selected)).all()
            assert (np.asarray(a.gains) == np.asarray(b.gains)).all()
            assert a.value == b.value and a.vprime_size == b.vprime_size

    def test_each_lexical_span_enters_one_trace_annotation(self, monkeypatch):
        entered = []

        class Annotation:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                entered.append(self.name)
                return self

            def __exit__(self, *exc):
                return False

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
        SummarizeService(RunConfig(max_batch=2)).run(host_queries(num=2))
        assert entered == []                    # tracing off: never entered
        obs.configure(trace=True)
        SummarizeService(RunConfig(max_batch=2)).run(host_queries(num=2))
        lexical = [s.name for s in obs.get_tracer().spans()
                   if s.name not in ("queue.wait", "jax.compile")]
        assert sorted(entered) == sorted(lexical)
        assert {"request.admit", "chunk.exec", "chunk.respond"} <= set(entered)

    def test_a_new_lane_shape_compiles_under_its_chunk(self):
        obs.configure(trace=True)
        # a shape no other test serves, so its programs compile here
        SummarizeService(RunConfig(max_batch=1)).run(
            host_queries(num=1, n=75, F=19)
        )
        tr = obs.get_tracer()
        by_id = {s.span_id: s for s in tr.spans()}
        (chunk,) = tr.spans(name="chunk.exec")

        def under_chunk(s):
            while s is not None and s.parent_id is not None:
                if s.parent_id == chunk.span_id:
                    return True
                s = by_id.get(s.parent_id)
            return False

        compiles = [s for s in tr.spans(name="jax.compile") if under_chunk(s)]
        assert compiles
        assert {s.attrs["lane"] for s in compiles} == {chunk.attrs["lane"]}
        assert any("sparsify_batched" in s.attrs["fun_name"] for s in compiles)
        assert all(s.t1 >= s.t0 for s in compiles)

    def test_settled_t_lies_between_submit_and_the_waiters_stamp(self):
        stamps = {}

        def wait(ticket):
            ticket.result(timeout=120)
            stamps[ticket.index] = time.perf_counter()

        cfg = RunConfig(scheduler="async", max_batch=2, max_wait_s=0.01)
        with SummarizeService(cfg) as svc:
            tickets = [svc.submit(r) for r in host_queries(num=3)]
            waiters = [threading.Thread(target=wait, args=(t,))
                       for t in tickets]
            for w in waiters:
                w.start()
            for w in waiters:
                w.join(timeout=120)
            assert not any(w.is_alive() for w in waiters)
        for t in tickets:
            assert t._submit_t <= t.settled_t <= stamps[t.index]


# ----------------------------------------------------------------- ewma ----

class TestEwma:
    def test_first_sample_initializes(self):
        assert ewma_update(None, 0.25) == 0.25

    def test_converges_to_constant_signal(self):
        est = None
        for _ in range(20):
            est = ewma_update(est, 0.05)
        assert abs(est - 0.05) < 1e-9

    def test_tracks_synthetic_exec_trace(self):
        # a synthetic exec-time trace that settles after a warmup spike —
        # the flusher's estimate must converge to the steady state
        trace = [0.5, 0.4] + [0.1] * 18
        est = None
        for s in trace:
            est = ewma_update(est, s)
        assert abs(est - 0.1) < 1e-3
        # alpha=0.5 halves the error each step: after the spike it takes
        # ~10 steps to shed the 0.4 delta below 1e-3
        assert ewma_update(0.2, 0.1) == pytest.approx(0.15)

    def test_service_estimate_matches_replayed_ewma(self):
        queries = make_queries(8, n=128, F=32, k=4)
        svc = SummarizeService(RunConfig(max_batch=4))
        responses = svc.run(queries)
        # one lane, serial chunks: the chunk execution sequence is the
        # deduplicated exec_s sequence of the in-order responses — replay
        # it through ewma_update and the flusher's estimate must match
        exec_by_chunk: list[float] = []
        for r in responses:
            if not exec_by_chunk or exec_by_chunk[-1] != r.exec_s:
                exec_by_chunk.append(r.exec_s)
        assert len(exec_by_chunk) == svc.stats()["batches"]
        expected = None
        for e in exec_by_chunk:
            expected = ewma_update(expected, e)
        (key,) = [k for k in svc._exec_est if k[1] == 0]
        assert svc._exec_est[key] == pytest.approx(expected)


# ---------------------------------------------------------- unified bus ----

class TestUnifiedBus:
    def test_chaos_run_lands_on_one_bus_with_consistent_ids(self):
        queries = make_queries(8, n=128, F=32, k=4)
        plan = FaultPlan.seeded(
            0, n_attempts=64, p_exec_error=0.3, p_latency=0.2,
            latency_s=0.005,
        )
        svc = SummarizeService(
            RunConfig(max_batch=4, failover_backend="oracle"), faults=plan,
        )
        responses = svc.run(queries)
        assert len(responses) == len(queries)
        bus = obs.get_bus()

        faults = bus.events(kind="fault", subsystem="faults")
        recoveries = bus.events(kind="recovery", subsystem="service")
        assert faults, "the seeded plan injected nothing?"
        assert recoveries, "faults were injected but no recovery ran?"
        # the same draws the legacy FaultPlan.log records are on the bus
        assert len(faults) == len(plan.log)
        for ev, legacy in zip(faults, plan.log):
            assert ev.data["fault_kind"] == legacy.fault.kind
            assert ev.data["attempt"] == legacy.attempt
            assert tuple(legacy.tickets) == ev.request_ids

        # consistent ids: every event's request_ids are real tickets, and
        # every recovery shares its ids with an earlier fault on the bus
        valid = set(range(len(queries)))
        for ev in faults + recoveries:
            assert set(ev.request_ids) <= valid
        for rec in recoveries:
            prior = [
                f for f in faults
                if f.seq < rec.seq and set(f.request_ids)
                & set(rec.request_ids)
            ]
            assert prior, f"recovery {rec.data} with no matching fault"

        # one global ordering across subsystems
        seqs = [e.seq for e in bus.events()]
        assert seqs == sorted(seqs)

        # and the counters tell the same story
        reg = obs.get_registry()
        injected = sum(
            reg.get("repro_faults_injected_total").value(kind=k)
            for k in {e.data["fault_kind"] for e in faults}
        )
        assert injected == len(faults)
        st = svc.stats()
        retr = reg.get("repro_service_retries_total")
        assert sum(retr.snapshot().values()) == st["retries"]

    def test_session_audit_events_join_the_bus(self, tmp_path):
        cfg = SessionConfig(
            k=4, n_features=16, buffer_cap=32, resparsify_every=8,
            max_batch=2, snapshot_every=16,
        )
        rng = np.random.default_rng(0)
        eng = SessionEngine(cfg, str(tmp_path))
        sid = eng.open_session(sid="u001", key=0)
        for _ in range(20):
            eng.append(sid, rng.random(16).astype(np.float32))
        eng.flush()
        del eng
        rec = SessionEngine(cfg, str(tmp_path))
        rec.state(sid)

        bus = obs.get_bus()
        (ev,) = bus.events(kind="rehydrate", subsystem="sessions")
        assert ev.session_id == sid
        assert ev.data["replayed"] >= 0
        # the legacy list-shaped audit surface still carries the same step
        assert any(e["step"] == "rehydrate" for e in rec.events)
        assert rec.stats()["events_dropped"] == 0
        assert obs.get_registry().get(
            "repro_sessions_events_total"
        ).value(step="rehydrate") == 1
        # host-side durability histograms are always on
        wal = obs.get_registry().get("repro_wal_append_seconds")
        assert wal is not None and wal.stats()["count"] >= 20
        snap = obs.get_registry().get("repro_sessions_snapshot_seconds")
        assert snap is not None and snap.stats()["count"] >= 1
        rcv = obs.get_registry().get("repro_sessions_recover_seconds")
        assert rcv is not None and rcv.stats()["count"] == 1
