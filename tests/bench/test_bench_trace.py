"""The reduction from a profiler trace and the program's spans to busy and
idle time, per-layer device time and the breakdown: on a hand-made trace
whose numbers are known, and on a small trace recorded on a TPU v5e."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import trace  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
LAYERS = {"ss": ["^jit__sparsify_batched"], "greedy": ["^jit__greedy_batched"]}


def hand_made():
    # Window 0..1000 ns; the host clock reads 10.0 s at the window's open.
    events = {
        "host": [["bench.profile_window", 0, 1000], ["bench.submit", 520, 580]],
        "modules": [["jit__sparsify_batched(3)", 100, 300],
                    ["jit__greedy_batched(4)", 400, 500],
                    ["jit_pad(5)", 600, 650]],
        "ops": [["fusion.1", 100, 200], ["fusion.2", 150, 300],
                ["fusion.3", 400, 500], ["copy.1", 600, 650],
                ["late", 990, 1100]],
    }
    at = lambda ns: 10.0 + ns * 1e-9  # noqa: E731
    spans = [
        {"span_id": 1, "parent_id": None, "name": "chunk.exec", "t0": at(60),
         "t1": at(515), "attrs": {"batch": 3, "bucket": 4,
                                  "request_ids": [0, 1, 2]}},
        {"span_id": 2, "parent_id": 1, "name": "ss.sparsify_batched",
         "t0": at(90), "t1": at(310), "attrs": {}},
        {"span_id": 3, "parent_id": 1, "name": "greedy.select_batched",
         "t0": at(390), "t1": at(510), "attrs": {}},
    ]
    return events, spans


def test_known_busy_idle_and_layer_times():
    events, spans = hand_made()
    out = trace.reduce(events, LAYERS, spans, p_open=10.0)
    assert out["window_s"] == pytest.approx(1000e-9)
    # Busy: 100-300, 400-500, 600-650 and 990-1000 (clipped at the window).
    assert out["busy_s"] == pytest.approx(360e-9)
    assert out["layers_s"]["ss"] == pytest.approx(200e-9)
    assert out["layers_s"]["greedy"] == pytest.approx(100e-9)
    assert out["requests"] == 3
    (chunk,) = out["chunks"]
    assert chunk["device_s"]["ss"] == pytest.approx(200e-9)
    assert chunk["device_s"]["greedy"] == pytest.approx(100e-9)
    gaps = sorted((round(v * 1e9), k) for k, v in out["breakdown"]["idle_gaps"])
    assert gaps == [
        (100, "bench.submit"),              # 500..600: the host was submitting
        (100, "chunk.exec"),                # 300..400: between SS and greedy
        (100, "none"),                      # 0..100: before the chunk
        (340, "none"),                      # 650..990: nothing on the host
    ]
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["jit__sparsify_batched(3)/fusion.2"] == pytest.approx(150e-9)
    assert ops["?/late"] == pytest.approx(10e-9)


def test_a_layer_pattern_that_matches_nothing_raises():
    events, spans = hand_made()
    with pytest.raises(RuntimeError, match="matched no device time"):
        trace.reduce(events, dict(LAYERS, objective=["^jit_renamed"]),
                     spans, p_open=10.0)


def test_union_length():
    total, merged = trace.union_length([(0, 2), (1, 3), (5, 6), (6, 7)])
    assert total == 5 and merged == [[0, 3], [5, 7]]


def test_recorded_trace_matches_a_brute_force_count():
    """A 150 ms slice of a traced news-steady window on a TPU v5e: busy time
    and per-layer module time equal a nanosecond-grid count of the same
    events, and the layer table finds both layers in it."""
    with open(os.path.join(DATA, "news_steady_v5e.json")) as f:
        events = json.load(f)
    _, w0, w1 = events["host"][0]
    out = trace.reduce(events, trace.load_layers(), [], p_open=0.0)
    grid = 1000  # ns
    n = int((w1 - w0) // grid) + 1
    busy = [False] * n
    for _, a, b in events["ops"]:
        for i in range(max(0, int((a - w0) // grid)), min(n, int((b - w0) // grid))):
            busy[i] = True
    assert out["busy_s"] == pytest.approx(sum(busy) * grid / 1e9, rel=0.02)
    for layer, pats in trace.load_layers().items():
        want = sum(min(b, w1) - max(a, w0) for name, a, b in events["modules"]
                   if trace.layer_of(name, {layer: pats}) and min(b, w1) > max(a, w0))
        assert want > 0
        assert out["layers_s"][layer] == pytest.approx(want / 1e9)
    assert 0 < out["busy_s"] < out["window_s"]
