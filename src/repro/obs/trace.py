"""Structured request tracing: span trees over the serving stack.

A **span** is one timed region — monotonic ``t0``/``t1`` from
``time.perf_counter()``, a ``span_id``, its ``parent_id`` (implicit: the
span that was active on this thread when it started), a ``trace_id``
grouping one request's tree, and free-form ``attrs``.  Finished spans land
in a bounded in-process ring buffer (:class:`repro.obs.events.RingLog`)
with a drop counter; nothing is ever written synchronously to disk.

Tracing is **off by default** (enable with :func:`configure` or
``REPRO_TRACE=1``).  When off, :func:`span` returns a shared no-op context
manager after one attribute check — the hooks stay in compiled-adjacent
hot paths at effectively zero cost, and results are bit-identical either
way because instrumentation only *observes* outputs (tests/test_obs.py).

Compiled-code safety contract: spans time **host-side around jitted
calls** only.  A traced region may host-read the *results* of a compiled
call after it returns (that sync was about to happen anyway), but never
injects a host sync inside a traced ``lax.scan``/``while_loop`` — per-round
SS records are derived post-hoc from ``SSResult.alive_trace``, and
per-round wall times are model-apportioned estimates of the measured total
(``wall_est``), not in-loop measurements.

With tracing on, every lexical span (:func:`span`) also enters
``jax.profiler.TraceAnnotation(name)``, so under a profiler the spans sit
on its host plane, on the device trace's clock.  Retroactive spans
(:meth:`Tracer.record`: ``queue.wait``, ``jax.compile``) stay on the
program's clock only.  ``jax.compile`` spans come from a
``jax.monitoring`` backend-compile listener installed the first time
tracing is turned on; each is recorded under the span open on the
compiling thread.

Host↔device boundary counters: the served path moves data through
:func:`device_put`, :func:`device_get` and :func:`block_until_ready`.
Each makes exactly the transfer or wait its plain JAX namesake makes; with
tracing on it also adds to the innermost open span's ``syncs`` (device
reads and waits), ``h2d_bytes`` (host bytes copied to the device) and
``sites`` (calls per named site).  Reads and waits that exist only for
the tracer itself pass ``instrumentation=True`` and count under
``obs_syncs`` instead.

Span trees are assembled per request: a span belongs to request ``i`` when
its ``trace_id == f"req-{i}"`` or its ``request_ids`` attr contains ``i``
(a chunk span is shared by its batch mates).  :func:`format_trace` renders
one request's tree; :func:`trace_summary` renders the most recent one
(the quickstart prints this).
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import itertools
import os
import threading
import time
from typing import Any, Iterator

import jax
import numpy as np
from jax import profiler as _profiler

from repro.obs.events import RingLog

DEFAULT_CAPACITY = 8192


class Span:
    """One timed region.  Mutable while open (``attrs`` may be filled as
    results become known); immutable by convention once finished."""

    __slots__ = (
        "span_id", "parent_id", "trace_id", "name", "t0", "t1", "status",
        "attrs", "parent",
    )

    def __init__(self, span_id: int, parent_id: int | None, trace_id: str,
                 name: str, t0: float, attrs: dict,
                 parent: "Span | None" = None):
        self.span_id = span_id
        self.parent_id = parent_id
        self.parent = parent
        self.trace_id = trace_id
        self.name = name
        self.t0 = t0
        self.t1: float | None = None
        self.status = "ok"
        self.attrs = attrs

    @property
    def wall_s(self) -> float:
        return (self.t1 if self.t1 is not None else time.perf_counter()) \
            - self.t0

    def set(self, **attrs: Any) -> "Span":
        self.attrs.update(attrs)
        return self

    def fail(self, **attrs: Any) -> "Span":
        """Mark the span failed (``status="error"``) without raising through
        it, e.g. an admission whose error is captured on its ticket."""
        self.status = "error"
        self.attrs.update(attrs)
        return self

    def to_dict(self) -> dict:
        return {
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "trace_id": self.trace_id,
            "name": self.name,
            "t0": self.t0,
            "t1": self.t1,
            "wall_s": None if self.t1 is None else self.t1 - self.t0,
            "status": self.status,
            "attrs": self.attrs,
        }

    def __repr__(self) -> str:
        return (
            f"Span({self.name!r}, id={self.span_id}, "
            f"trace={self.trace_id!r}, wall={self.wall_s * 1e3:.2f}ms)"
        )


class _NoopSpan:
    """Shared do-nothing span handed out while tracing is disabled — every
    mutator is a cheap no-op so call sites need no ``if`` guards."""

    __slots__ = ()
    span_id = -1
    parent_id = None
    trace_id = ""
    name = ""
    status = "ok"
    attrs: dict = {}
    wall_s = 0.0

    def set(self, **attrs: Any) -> "_NoopSpan":
        return self

    def fail(self, **attrs: Any) -> "_NoopSpan":
        return self


_NOOP_SPAN = _NoopSpan()


@contextlib.contextmanager
def _noop_cm() -> Iterator[_NoopSpan]:
    yield _NOOP_SPAN


class Tracer:
    """Span recorder: bounded ring buffer + contextvar-based implicit
    parenting (thread- and task-local, so the async flusher's spans never
    adopt a submitter's parent)."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 enabled: bool | None = None):
        if enabled is None:
            enabled = os.environ.get("REPRO_TRACE", "") == "1"
        self.enabled = bool(enabled)
        self._ring = RingLog(capacity)
        self._ids = itertools.count()
        self._current: contextvars.ContextVar[Span | None] = \
            contextvars.ContextVar("repro_obs_span", default=None)
        self._lock = threading.Lock()

    # -- recording ---------------------------------------------------------
    def start_span(self, name: str, *, trace_id: str | None = None,
                   parent: Span | None = None, **attrs: Any) -> Span:
        """Open a span explicitly (for lifetimes that don't nest lexically,
        e.g. a request span living from submit to settle).  The caller owns
        calling :meth:`finish`."""
        if not self.enabled:
            return _NOOP_SPAN  # type: ignore[return-value]
        if parent is None:
            parent = self._current.get()
        if trace_id is None:
            trace_id = parent.trace_id if parent is not None else "untraced"
        with self._lock:
            sid = next(self._ids)
        return Span(
            span_id=sid,
            parent_id=None if parent is None else parent.span_id,
            trace_id=trace_id, name=name, t0=time.perf_counter(),
            attrs=dict(attrs), parent=parent,
        )

    def finish(self, sp: Span, status: str = "ok") -> None:
        """Close and record an explicitly-started span."""
        if sp is _NOOP_SPAN or not self.enabled:
            return
        if sp.t1 is None:
            sp.t1 = time.perf_counter()
        sp.status = status
        sp.parent = None        # only open spans need their parent object
        self._ring.append(sp)

    def span(self, name: str, *, trace_id: str | None = None,
             **attrs: Any):
        """Context manager: open a child of the currently-active span, make
        it current for the body, record it on exit (``status="error"`` when
        the body raises or calls :meth:`Span.fail`).  The body also runs
        inside ``jax.profiler.TraceAnnotation(name)``."""
        if not self.enabled:
            return _noop_cm()
        return self._span_cm(name, trace_id, attrs)

    @contextlib.contextmanager
    def _span_cm(self, name: str, trace_id: str | None,
                 attrs: dict) -> Iterator[Span]:
        sp = self.start_span(name, trace_id=trace_id, **attrs)
        token = self._current.set(sp)
        try:
            with _profiler.TraceAnnotation(name):
                yield sp
        except BaseException:
            self.finish(sp, status="error")
            raise
        finally:
            self._current.reset(token)
        self.finish(sp, sp.status)

    def record(self, name: str, t0: float, t1: float, *,
               trace_id: str | None = None, parent: Span | None = None,
               status: str = "ok", **attrs: Any) -> None:
        """Record a span retroactively from already-measured perf_counter
        endpoints (e.g. a queue-residency span derived at execution start
        from the admission timestamp)."""
        if not self.enabled:
            return
        sp = self.start_span(name, trace_id=trace_id, parent=parent, **attrs)
        sp.t0, sp.t1 = t0, t1
        sp.status = status
        sp.parent = None
        self._ring.append(sp)

    def current_span(self) -> Span | None:
        """The span active on this thread/task (None outside any span)."""
        return self._current.get()

    # -- reading -----------------------------------------------------------
    @property
    def dropped(self) -> int:
        return self._ring.dropped

    def spans(self, trace_id: str | None = None,
              name: str | None = None) -> list[Span]:
        """Finished spans (oldest first), optionally filtered."""
        return [
            s for s in self._ring
            if (trace_id is None or s.trace_id == trace_id)
            and (name is None or s.name == name)
        ]

    def spans_for_request(self, index: int) -> list[Span]:
        """Every span belonging to request ``index``'s tree: its own
        ``req-<i>`` trace plus shared spans (chunk executions and their
        children) whose ``request_ids`` attr contains ``i``."""
        tid = f"req-{index}"
        out, shared_roots = [], set()
        for s in self._ring:
            if s.trace_id == tid:
                out.append(s)
            elif index in s.attrs.get("request_ids", ()):
                out.append(s)
                shared_roots.add(s.span_id)
        if shared_roots:
            # pull in descendants of the shared (chunk) spans: SS / greedy /
            # objective-build children recorded under the chunk's trace.
            known = {s.span_id for s in out}
            grew = True
            while grew:
                grew = False
                for s in self._ring:
                    if s.span_id not in known and s.parent_id in known:
                        out.append(s)
                        known.add(s.span_id)
                        grew = True
        return sorted(out, key=lambda s: (s.t0, s.span_id))

    def export(self) -> list[dict]:
        """JSON-serializable dump of every retained span (the trace
        artifact serve_bench/stream_bench emit)."""
        return [s.to_dict() for s in self._ring]

    def clear(self) -> None:
        self._ring.clear()


_tracer = Tracer()


def get_tracer() -> Tracer:
    """The process-wide tracer every ``span()`` hook records into."""
    return _tracer


BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_listener_installed = False


def _on_jax_event(event: str, duration: float, **kwargs: Any) -> None:
    """``jax.monitoring`` duration listener: each backend compile (a
    persistent-cache load included) becomes a retroactive ``jax.compile``
    span under the span open on the compiling thread, with the ``lane`` of
    that span or of its nearest open ancestor that has one."""
    if event != BACKEND_COMPILE_EVENT or not _tracer.enabled:
        return
    parent = _tracer.current_span()
    lane, sp = None, parent
    while sp is not None and lane is None:
        lane, sp = sp.attrs.get("lane"), sp.parent
    t1 = time.perf_counter()
    _tracer.record(
        "jax.compile", t1 - duration, t1, parent=parent,
        fun_name=kwargs.get("fun_name"), lane=lane,
    )


def _install_compile_listener() -> None:
    global _compile_listener_installed
    if not _compile_listener_installed:
        jax.monitoring.register_event_duration_secs_listener(_on_jax_event)
        _compile_listener_installed = True


def configure(*, trace: bool | None = None,
              capacity: int | None = None) -> Tracer:
    """Enable/disable tracing and/or resize the span ring.  Resizing drops
    recorded spans (the ring is rebuilt); the enable flag is cheap to flip
    at any time.  Turning tracing on installs the ``jax.compile`` listener
    (once per process; it does nothing while tracing is off)."""
    global _tracer
    if capacity is not None and capacity != _tracer._ring.capacity:
        _tracer = Tracer(capacity=capacity, enabled=_tracer.enabled)
    if trace is not None:
        _tracer.enabled = bool(trace)
    if _tracer.enabled:
        _install_compile_listener()
    return _tracer


if _tracer.enabled:     # REPRO_TRACE=1 at import
    _install_compile_listener()


def trace_enabled() -> bool:
    return _tracer.enabled


def span(name: str, *, trace_id: str | None = None, **attrs: Any):
    """Module-level convenience: a span on the global tracer (no-op context
    manager when tracing is disabled)."""
    return _tracer.span(name, trace_id=trace_id, **attrs)


def named_scope(name: str):
    """Decorator: run the function's body under ``jax.named_scope(name)``,
    so the ops it traces carry ``name`` in their metadata and a device
    trace can map them to a layer.  ``wraps`` keeps the function's name,
    hence the name of the module ``jax.jit`` compiles from it."""
    def deco(f):
        @functools.wraps(f)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return f(*args, **kwargs)
        return scoped
    return deco


# ------------------------------------------------ host<->device counters ----

def _count(attr: str, site: str, amount: int) -> None:
    """Add ``amount`` to the innermost open span's ``attr`` and one call
    to its ``sites[site]`` (spans are thread-local, so only the owning
    thread mutates them)."""
    sp = _tracer.current_span()
    if sp is None:
        return
    sp.attrs[attr] = sp.attrs.get(attr, 0) + amount
    sites = sp.attrs.setdefault("sites", {})
    sites[site] = sites.get(site, 0) + 1


def _host_nbytes(leaf: Any) -> int:
    """Bytes a ``device_put`` of ``leaf`` copies to the device: 0 for an
    array already there, else its size at JAX's canonical dtype."""
    if isinstance(leaf, jax.Array):
        return 0
    a = np.asarray(leaf)
    return a.size * np.dtype(jax.dtypes.canonicalize_dtype(a.dtype)).itemsize


def device_put(site: str, x: Any) -> Any:
    """``jax.device_put(x)``, counted: with tracing on, the host bytes it
    copies go to the innermost open span's ``h2d_bytes``."""
    if _tracer.enabled:
        _count("h2d_bytes", site, sum(
            _host_nbytes(leaf) for leaf in jax.tree_util.tree_leaves(x)))
    return jax.device_put(x)


def device_get(site: str, x: Any, *, instrumentation: bool = False) -> Any:
    """``jax.device_get(x)``, counted: with tracing on, one ``syncs`` (or
    ``obs_syncs`` for a read only the tracer makes) on the innermost open
    span."""
    if _tracer.enabled:
        _count("obs_syncs" if instrumentation else "syncs", site, 1)
    return jax.device_get(x)


def block_until_ready(site: str, x: Any, *,
                      instrumentation: bool = False) -> Any:
    """``jax.block_until_ready(x)``, counted like :func:`device_get`: a wait
    for the device is a host sync even when nothing is copied."""
    if _tracer.enabled:
        _count("obs_syncs" if instrumentation else "syncs", site, 1)
    return jax.block_until_ready(x)


# ------------------------------------------------------------- rendering ----

def _render(spans: list[Span]) -> str:
    if not spans:
        return "(no spans recorded — is tracing enabled?)"
    by_parent: dict[int | None, list[Span]] = {}
    ids = {s.span_id for s in spans}
    for s in spans:
        parent = s.parent_id if s.parent_id in ids else None
        by_parent.setdefault(parent, []).append(s)
    t_base = min(s.t0 for s in spans)
    lines: list[str] = []

    def walk(parent: int | None, depth: int) -> None:
        for s in sorted(by_parent.get(parent, ()),
                        key=lambda s: (s.t0, s.span_id)):
            extra = ""
            keep = {
                k: v for k, v in s.attrs.items()
                if isinstance(v, (int, float, str, bool)) and k != "wall_s"
            }
            if keep:
                extra = "  " + ", ".join(
                    f"{k}={v:.4g}" if isinstance(v, float) else f"{k}={v}"
                    for k, v in sorted(keep.items())
                )
            flag = "" if s.status == "ok" else f"  [{s.status}]"
            lines.append(
                f"{'  ' * depth}{s.name:<24s} "
                f"+{(s.t0 - t_base) * 1e3:8.2f}ms "
                f"{s.wall_s * 1e3:8.2f}ms{flag}{extra}"
            )
            walk(s.span_id, depth + 1)

    walk(None, 0)
    return "\n".join(lines)


def format_trace(trace_id: str) -> str:
    """One trace's span tree as indented text (name, start offset,
    duration, scalar attrs) — offsets are relative to the tree's first
    span.  For a request id ``i`` pass ``f"req-{i}"``; shared chunk spans
    and their SS/greedy children are included."""
    if trace_id.startswith("req-"):
        spans_ = _tracer.spans_for_request(int(trace_id[4:]))
    else:
        spans_ = _tracer.spans(trace_id=trace_id)
    return _render(spans_)


def trace_summary(request: int | None = None) -> str:
    """The span tree of request ``request`` — default: the most recently
    traced request (the quickstart's one-request trace summary)."""
    if request is None:
        reqs = [
            s for s in _tracer.spans() if s.trace_id.startswith("req-")
        ]
        if not reqs:
            return "(no request spans recorded — is tracing enabled?)"
        request = int(reqs[-1].trace_id[4:])
    return f"trace req-{request}\n" + format_trace(f"req-{request}")
