"""Everything a run does between reading its cell and printing its line:
find the cell's files by name, set up JAX, make the payload pool, build and
warm the service, and drive the open-loop window.

Nothing here imports the program until :func:`import_program`, so that
``bench/run.py`` can refuse a machine without a TPU before touching it.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import sys
import threading
import time
from typing import Any

import numpy as np

from bench import data, traffic

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(BENCH, ".jax_cache")
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


def log(*parts: Any) -> None:
    print(*parts, file=sys.stderr, flush=True)


# ------------------------------------------------------------- the cell ----

@dataclasses.dataclass
class Cell:
    name: str
    spec: dict          # the whole BENCHMARK.json
    entry: dict         # its ``workloads`` entry
    config: dict        # bench/configs/<config>.json
    mix: dict           # bench/workloads/<traffic>.json

    def metrics(self, section: str) -> list[dict]:
        """The metrics of ``section`` that this cell reports."""
        return [m for m in self.spec[section]
                if self.name in m.get("workloads", [self.name])]


def load_cell(name: str, root: str = ROOT) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entries = [w for w in spec["workloads"] if w["name"] == name]
    if not entries:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    entry = entries[0]
    conf = [c for c in spec["configs"] if c["name"] == entry["config"]][0]
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "workloads", entry["traffic"] + ".json")) as f:
        mix = traffic.validate(json.load(f))
    return Cell(name, spec, entry, config, mix)


# ------------------------------------------------------------------ JAX ----

def setup_jax(cache_dir: str = CACHE_DIR):
    """The benchmark's own persistent compilation cache, at a fixed path
    inside the checkout, caching every program however short its compile."""
    import jax

    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # No size limit, hence no eviction: JAX's eviction walks the directory
    # under a file lock that does not exclude threads of one process, and
    # the service compiles on two threads (admission and flusher).
    jax.config.update("jax_compilation_cache_max_size", -1)
    return jax


def device_info(jax, chips: int, require_tpu: bool = True) -> dict:
    """The device as JAX reports it; exits non-zero without a TPU or with
    fewer chips than the cell asks for."""
    devs = jax.devices()
    d = devs[0]
    if require_tpu and (d.platform != "tpu" or len(devs) < chips):
        log(f"bench: needs {chips} TPU chip(s); JAX found {len(devs)} "
            f"{d.platform} device(s) ({d.device_kind})")
        raise SystemExit(3)
    # ``kind`` is the result line's key; ``device_kind`` is JAX's own name.
    return {"platform": d.platform, "kind": d.device_kind,
            "device_kind": d.device_kind, "count": chips}


def memory_peak(jax, chips: int) -> int:
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache, by
    ``jax.monitoring`` events, from the moment it is installed."""

    def __init__(self, jax):
        self.backend = 0      # backend compiles, cache loads included
        self.hits = 0         # of which loaded from the persistent cache
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **kw) -> None:
        if event == BACKEND_COMPILE:
            self.backend += 1

    def _event(self, event: str, **kw) -> None:
        if event == CACHE_HIT:
            self.hits += 1

    def snapshot(self) -> tuple[int, int]:
        return self.backend, self.hits


# ----------------------------------------------------------- the payload ----

@dataclasses.dataclass(frozen=True)
class Item:
    features: np.ndarray    # (n, F) float32, host memory
    k: int

    @property
    def n(self) -> int:
        return self.features.shape[0]


def make_pool(config: dict, seed: int) -> list[Item]:
    p = config["payload"]
    if p["generator"] == "news_day":
        lo, hi, size = p["n_min"], p["n_max"], p["pool"]
        ns = [lo + int((i + 0.5) * (hi - lo) / size) for i in range(size)]
        return [Item(data.news_day(seed, i, n, p["n_features"]), config["k"])
                for i, n in enumerate(ns)]
    if p["generator"] == "video":
        return [Item(data.video(seed, i, n, p["n_features"]),
                     max(1, int(config["k_frac"] * n)))
                for i, n in enumerate(p["frames"])]
    raise ValueError(f"unknown payload generator {p['generator']!r}")


# ------------------------------------------------------------ the program ----

def import_program():
    """The system under test, from the checkout's ``src``."""
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro import api, obs  # noqa: PLC0415

    return api, obs


def run_config(api, config: dict):
    """``RunConfig`` from the configuration file, every field written out.
    A field the program no longer has is an error: the deployment would
    change unseen."""
    fields = {f.name for f in dataclasses.fields(api.RunConfig)}
    unknown = sorted(set(config["run_config"]) - fields)
    if unknown:
        raise SystemExit(f"bench: RunConfig has no field(s) {unknown}; the "
                         f"configuration {config['name']!r} cannot be run as "
                         "it is written")
    return api.RunConfig(**{k: tuple(v) if isinstance(v, list) else v
                            for k, v in config["run_config"].items()})


def request(api, config: dict, item: Item, key: int):
    return api.SummarizeRequest(
        k=item.k, key=int(key), features=item.features, **config["objective"]
    )


def lane_of(cfg, item: Item) -> tuple[int, int]:
    """The (ground-set size as served, k) group an item batches under."""
    n = item.n
    if cfg.n_buckets:
        n = min(b for b in cfg.n_buckets if b >= n)
    return n, item.k


# -------------------------------------------------------------- warm-up ----

def warm_up(api, config: dict, cfg, pool: list[Item], cell_mix: dict,
            seed: int, counter: CompileCounter, obs_on: bool,
            max_passes: int = 4) -> dict:
    """Compile or load every program the cell's traffic will run, through
    the program's public API only.

    1. A synchronous service of the same configuration runs, for every lane,
       one chunk of each size 1..max_batch; then passes that send every pool
       item once more, under fresh keys, until a pass compiles nothing (the
       selection stage's programs follow the live count SS leaves, which
       the key moves).
    2. Warm-up traffic of the cell's own mix (other streams of the seed)
       through the asynchronous service, until a pass compiles nothing.
    """
    rng = data.rng_for(seed, 4)
    keys = iter(rng.integers(0, 2**31 - 1, size=1 << 20))
    lanes: dict[tuple, list[int]] = {}
    for i, it in enumerate(pool):
        lanes.setdefault(lane_of(cfg, it), []).append(i)
    out = {"lanes": len(lanes)}

    def run_sync(chunks):
        before = counter.backend
        for chunk in chunks:
            sync.run([request(api, config, pool[i], next(keys)) for i in chunk])
        return counter.backend - before

    t = time.perf_counter()
    sync = api.serve(dataclasses.replace(cfg, scheduler="sync"))
    chunks = []
    for idx in lanes.values():
        for j in range(1, cfg.max_batch + 1):
            chunks.append([idx[q % len(idx)] for q in range(j)])
    passes = [run_sync(chunks)]
    every_item = [idx[q: q + cfg.max_batch] for idx in lanes.values()
                  for q in range(0, len(idx), cfg.max_batch)]
    while len(passes) < max_passes:
        passes.append(run_sync(every_item))
        if passes[-1] == 0:
            break
    out["chunks_s"] = time.perf_counter() - t
    out["sync_pass_compiles"] = passes

    t = time.perf_counter()
    passes = []
    svc = api.serve(cfg)
    try:
        for p in range(max_passes):
            before = counter.backend
            sched = traffic.schedule(cell_mix, 2.0, len(pool), seed,
                                     stream=1 + p)
            recs, _, _ = run_window(api, svc, config, pool, sched, 2.0,
                                    "last_answer", obs_on=False)
            passes.append(counter.backend - before)
            if passes[-1] == 0 and all(r.response is not None for r in recs):
                break
    finally:
        svc.stop()
    out["traffic_s"] = time.perf_counter() - t
    out["traffic_pass_compiles"] = passes
    return out


# --------------------------------------------------------------- window ----

@dataclasses.dataclass
class Rec:
    i: int
    item: int
    key: int
    due: float
    sent: float = float("nan")
    resolved: float = float("nan")
    ticket: Any = None
    error: BaseException | None = None
    response: Any = None


def _waiter(rec: Rec, done: threading.Semaphore) -> None:
    try:
        rec.error = rec.ticket.exception()
        rec.resolved = time.perf_counter()
        if rec.error is None:
            rec.response = rec.ticket.result(timeout=0)
    finally:
        done.release()


def run_window(api, svc, config: dict, pool: list[Item], sched, seconds: float,
               close: str, obs_on: bool, on_open=None, on_end=None,
               late_s: float = 60.0) -> tuple[list[Rec], float, float]:
    """Open-loop window: submit each query at its due time and stamp it when
    its ticket resolves (one waiter thread per query, so out-of-order
    completions are stamped when they happen).  ``on_open`` and ``on_end``
    are called as the window opens and as its ``seconds`` run out.  Returns
    the records, the window's open and its close: the later of ``seconds``
    and the last answer when ``close`` is ``last_answer``, else
    ``seconds``."""
    import jax  # noqa: PLC0415

    ann = jax.profiler.TraceAnnotation if obs_on else (
        lambda name: contextlib.nullcontext())
    recs = [Rec(i, int(it), int(k), float(d))
            for i, (d, it, k) in enumerate(zip(sched.due_s, sched.item,
                                               sched.key))]
    done = threading.Semaphore(0)
    if on_open is not None:
        on_open()
    t0 = time.perf_counter()
    for rec in recs:
        rec.due += t0
        wait = rec.due - time.perf_counter()
        if wait > 0:
            with ann("bench.idle"):
                time.sleep(wait)
        rec.sent = time.perf_counter()
        with ann("bench.submit"):
            rec.ticket = svc.submit(
                request(api, config, pool[rec.item], rec.key))
        threading.Thread(target=_waiter, args=(rec, done), daemon=True).start()
    end = t0 + seconds
    if on_end is not None:
        time.sleep(max(0.0, end - time.perf_counter()))
        on_end()
    limit = end + late_s
    for _ in recs:
        if not done.acquire(timeout=max(0.0, limit - time.perf_counter())):
            break
    if close == "last_answer":
        last = max((r.resolved for r in recs if r.resolved == r.resolved),
                   default=end)
        t_close = max(end, last)
    else:
        t_close = end
    return recs, t0, t_close
