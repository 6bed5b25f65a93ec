"""From a profiler trace and the program's spans to per-layer numbers.

A traced run records one profiler trace around a short window of the cell's
own traffic (:class:`Profiler`).  :func:`load` reads the ``.xplane.pb`` into
plain event lists; :func:`reduce` turns them into device busy and idle time,
device time per layer, the chunks whose spans lie in the window, and the
breakdown.  Both work on plain lists, so the tests check them on a small
recorded trace (``tests/bench/data``).

Layers are data: ``bench/layers.json`` maps each layer to regular
expressions over XLA module names.  A layer whose patterns match no device
time in the window is an error, not a 0: a rename in the program then
fails the run rather than reading as a gain.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import re
import shutil
import time
from typing import Any

from bench import harness

DEVICE_PLANE = "/device:TPU:0"
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
WINDOW_MARK = "bench.profile_window"
LAYERS_FILE = os.path.join(harness.BENCH, "layers.json")
# Host spans an idle gap may be labelled with, innermost first.
SPAN_LABELS = ("greedy.select_batched", "ss.sparsify_batched", "chunk.exec",
               "request.admit", "bench.submit", "bench.idle", "bench.drain")


@dataclasses.dataclass
class Context:
    """What the per-layer readers (``bench/metrics``) read."""
    recs: list          # the main window's queries
    all_recs: list      # and the profiled window's
    spans: list[dict]   # the program's spans of the main window
    t0: float
    t_close: float
    pool: list
    config: dict
    cfg: Any
    peaks: dict | None
    compiles_in_window: int
    profile: dict | None


def load_layers(path: str = LAYERS_FILE) -> dict[str, list[str]]:
    with open(path) as f:
        return {k: v for k, v in json.load(f).items() if not k.startswith("_")}


# ---------------------------------------------------------------- load ----

def load(path: str) -> dict:
    """Device modules and ops of chip 0, and the host's bench annotations,
    as ``[name, start_ns, end_ns]`` lists."""
    from jax.profiler import ProfileData  # noqa: PLC0415

    pd = ProfileData.from_file(path)
    out = {"modules": [], "ops": [], "host": []}
    for plane in pd.planes:
        if plane.name == DEVICE_PLANE:
            for line in plane.lines:
                key = {MODULE_LINE: "modules", OPS_LINE: "ops"}.get(line.name)
                if key is None:
                    continue
                for e in line.events:
                    name = e.name if key == "modules" else op_name(e.name)
                    out[key].append([name, e.start_ns, e.start_ns + e.duration_ns])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        out["host"].append(
                            [e.name, e.start_ns, e.start_ns + e.duration_ns])
    return out


def op_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%").strip()[:80]


def with_modules(ops, modules):
    """Each op named ``<module>/<op>`` by the module it ran in."""
    starts = sorted((a, b, name) for name, a, b in modules)
    keys = [a for a, _, _ in starts]
    out = []
    for name, a, b in ops:
        i = bisect.bisect_right(keys, a) - 1
        mod = starts[i][2] if i >= 0 and starts[i][1] >= a else "?"
        out.append([f"{mod}/{name}", a, b])
    return out


# -------------------------------------------------------------- reduce ----

def union_length(intervals: list[tuple[float, float]]) -> tuple[float, list]:
    """Total length of the union of intervals, and the merged intervals."""
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), merged


def clip(events, w0, w1):
    for name, a, b in events:
        a, b = max(a, w0), min(b, w1)
        if b > a:
            yield name, a, b


def layer_of(name: str, layers: dict[str, list[str]]) -> str | None:
    for layer, pats in layers.items():
        if any(re.search(p, name) for p in pats):
            return layer
    return None


def reduce(events: dict, layers: dict[str, list[str]], spans: list[dict],
           p_open: float) -> dict:
    """Per-layer device time, busy and idle, chunk attribution, breakdown.

    ``spans`` are the program's spans (host ``perf_counter`` seconds);
    ``p_open`` is the ``perf_counter`` reading at which the window mark was
    entered, which ties the two clocks together."""
    marks = [h for h in events["host"] if h[0] == WINDOW_MARK]
    if not marks:
        raise RuntimeError(f"no {WINDOW_MARK!r} annotation in the trace")
    _, w0, w1 = marks[0]
    window_ns = w1 - w0

    def to_ns(t: float) -> float:
        return w0 + (t - p_open) * 1e9

    ops = list(clip(events["ops"], w0, w1))
    if not ops:
        raise RuntimeError("no device operation ran in the traced window")
    busy_ns, merged = union_length([(a, b) for _, a, b in ops])

    modules = list(clip(events["modules"], w0, w1))
    layer_ns = {k: 0.0 for k in layers}
    for name, a, b in modules:
        layer = layer_of(name, layers)
        if layer is not None:
            layer_ns[layer] += b - a
    empty = [k for k, v in layer_ns.items() if v <= 0]
    if empty:
        raise RuntimeError(
            f"layer pattern(s) matched no device time: {empty}; module names "
            f"in the window: {sorted({m[0] for m in modules})[:20]}")

    # Chunks whose whole span lies in the window, each with its SS and
    # selection spans and the device time of its layers inside them.
    chunks = []
    for s in spans:
        if s["name"] != "chunk.exec" or s["t1"] is None:
            continue
        a, b = to_ns(s["t0"]), to_ns(s["t1"])
        if a < w0 or b > w1:
            continue
        kids = {c["name"]: c for c in spans if c["parent_id"] == s["span_id"]}
        dev = {}
        for layer, span_name in (("ss", "ss.sparsify_batched"),
                                 ("greedy", "greedy.select_batched")):
            kid = kids.get(span_name)
            if kid is None or layer not in layers:
                continue
            ka, kb = to_ns(kid["t0"]), to_ns(kid["t1"])
            dev[layer] = sum(
                min(b2, kb) - max(a2, ka) for name, a2, b2 in modules
                if layer_of(name, layers) == layer and min(b2, kb) > max(a2, ka)
            ) / 1e9
        chunks.append({"span": s, "ss": kids.get("ss.sparsify_batched"),
                       "greedy": kids.get("greedy.select_batched"),
                       "device_s": dev})
    requests = sum(int(s["attrs"].get("batch", 0)) for s in spans
                   if s["name"] == "chunk.exec"
                   and w0 <= to_ns(s["t0"]) <= w1)

    # Breakdown: heaviest device operations, longest idle gaps by host span.
    per_op: dict[str, float] = {}
    for name, a, b in with_modules(ops, modules):
        per_op[name] = per_op.get(name, 0.0) + (b - a)
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    gaps = []
    edges = [w0] + [x for ab in merged for x in ab] + [w1]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            gaps.append((a, b))
    host = [(h[0], h[1], h[2]) for h in events["host"] if h[0] != WINDOW_MARK]
    host += [(s["name"], to_ns(s["t0"]), to_ns(s["t1"])) for s in spans
             if s["t1"] is not None and s["name"] in SPAN_LABELS]
    rank = {name: i for i, name in enumerate(SPAN_LABELS)}

    def label(a, b):
        mid = (a + b) / 2
        over = [n for n, h0, h1 in host if h0 <= mid <= h1]
        return min(over, key=lambda n: rank.get(n, len(rank))) if over else "none"

    gaps.sort(key=lambda ab: -(ab[1] - ab[0]))
    idle_by_label: dict[str, float] = {}
    for a, b in gaps:
        lab = label(a, b)
        idle_by_label[lab] = idle_by_label.get(lab, 0.0) + (b - a) / 1e9
    breakdown = {
        "device_ops": [[n, v / 1e9] for n, v in top_ops],
        "idle_gaps": [[label(a, b), (b - a) / 1e9] for a, b in gaps[:10]],
    }
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_ns / 1e9,
        "layers_s": {k: v / 1e9 for k, v in layer_ns.items()},
        "requests": requests,
        "chunks": chunks,
        "breakdown": breakdown,
        "idle_by_label": idle_by_label,
    }


# ------------------------------------------------------------- profile ----

class Profiler:
    """One profiler trace around a window of the cell's traffic: the trace
    starts before the window opens and stops after its answers are in, and
    the ``bench.profile_window`` mark covers the window itself."""

    def __init__(self, jax, out_dir: str):
        self.jax = jax
        self.out_dir = out_dir
        self.p_open = None
        self._mark = None

    def start(self) -> None:
        shutil.rmtree(self.out_dir, ignore_errors=True)
        self.jax.profiler.start_trace(self.out_dir)

    def open_window(self) -> None:
        self._mark = self.jax.profiler.TraceAnnotation(WINDOW_MARK)
        self._mark.__enter__()
        self.p_open = time.perf_counter()

    def close_window(self) -> None:
        self._mark.__exit__(None, None, None)

    def stop(self) -> str:
        self.jax.profiler.stop_trace()
        found = glob.glob(os.path.join(self.out_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise RuntimeError("the profiler wrote no trace")
        return found[0]
