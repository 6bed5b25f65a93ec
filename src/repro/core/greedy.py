"""Greedy maximizers: jit-compiled masked greedy, lazy greedy, stochastic
greedy, and bidirectional randomized greedy (for the non-monotone Eq. 9).

TPU adaptation (DESIGN.md §3): the classic lazy-greedy priority queue is a
pointer structure with data-dependent control flow — poison for accelerators.
On TPU the efficient formulation is *incremental dense recomputation*: keep the
summary state, recompute all masked gains with one fused op per step, and take
a masked argmax.  The per-step gains call is dispatched through the execution
backend layer (``backend="pallas"`` routes it to the fused Pallas kernel; the
default oracle is plain jnp — see repro.core.backend).  Lazy greedy is still
provided (host/numpy) because it is the paper's wall-clock baseline on CPU.

Compact selection engine: after SS the live set is |V'| = O(log² n) ≪ n, yet
a full-width step would still pay n gains + an n argmax.  When ``alive`` is a
concrete sparse mask (the post-SS default), ``greedy`` / ``stochastic_greedy``
gather the live set once into a static bucket-sized candidate buffer (the SS
shrink schedule's :func:`repro.core.sparsify.bucket_schedule` sizes), run
every per-step gain / argmax / Gumbel draw in compact index space via the
``gains_compact`` backend primitive, and map selections back to ground
indices — so per-step cost tracks |V'|, not n.  Compact and full-width
selection pick identical sets under the same key (tests/test_compact_greedy).
"""

from __future__ import annotations

import heapq
import logging
import math
import time
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.backend import Backend, resolve_backend
from repro.core.functions import NEG, SubmodularFunction

Array = jax.Array

logger = logging.getLogger("repro.core.greedy")


def _traceable(*objs) -> bool:
    """Telemetry eligibility: tracing is on AND every input is concrete.
    Under jit/vmap (tracer inputs — e.g. greedy called from the compiled
    KV-pruning loop) the hooks must vanish: host reads are impossible there,
    and the compiled-code-safety contract (docs/observability.md) forbids
    injecting a sync into a traced region."""
    if not obs.trace_enabled():
        return False
    return not any(
        isinstance(leaf, jax.core.Tracer)
        for leaf in jax.tree_util.tree_leaves(objs)
    )


def _record_greedy(sp, res: "GreedyResult", k: int, backend: str,
                   wall_s: float, *, selector: str, batched: bool) -> None:
    """Fill a selection span + metrics from a finished (host-read) result.
    The per-step gain trajectory is read from ``GreedyResult.gains`` *after*
    the compiled loop returns — pure observation, never an in-loop sync —
    in one device read, counted as the tracer's own (``obs_syncs``)."""
    gains, value = obs.device_get(
        "greedy.record", (res.gains, res.value), instrumentation=True
    )
    if batched:
        sp.set(
            B=int(gains.shape[0]),
            value=[float(v) for v in value],
            gains=[[float(g) for g in row] for row in gains],
        )
    else:
        sp.set(value=float(value), gains=[float(g) for g in gains])
    sp.set(k=k, backend=backend, selector=selector)
    obs.get_registry().histogram(
        "repro_greedy_wall_seconds", "greedy selection wall time per call",
        labels=("backend", "selector"),
    ).observe(wall_s, backend=backend, selector=selector)


class GreedyResult(NamedTuple):
    selected: Array      # (k,) int32 indices, in selection order
    gains: Array         # (k,) marginal gain at each step
    value: Array         # scalar f(S)
    state: Array         # final summary state


# ------------------------------------------------------- selection planning --

def selection_bucket(
    n: int, live: int, c: float = 8.0, tile: int = 128
) -> int | None:
    """Static compact candidate-buffer size for the selection stage.

    The smallest :func:`repro.core.sparsify.bucket_schedule` size that holds
    ``live`` candidates, or None when only the full-width bucket fits —
    compaction would then be pure gather/scatter overhead.  Reusing the SS
    schedule means the selection grids share the SS compaction grid shapes
    (same kernel specializations, no extra compile cache pressure).
    """
    from repro.core.sparsify import bucket_schedule

    size = min(b for b in bucket_schedule(n, c, tile) if b >= live)
    return None if size >= n else size


def auto_sample_size(
    n: int, k: int, eps: float = 0.1, live: int | None = None
) -> int:
    """Stochastic-greedy sample size s = ceil((n'/k)·ln(1/eps)) — the
    "lazier than lazy greedy" heuristic [Mirzasoleiman et al. 2015] — with
    n' the live count (post-SS |V'|) when known, else the ground-set size."""
    base = (n if live is None else live) / max(k, 1)
    return max(1, int(math.ceil(base * math.log(1.0 / eps))))


_PATHS_LOGGED: set[tuple[str, bool]] = set()


def _log_path(kind: str, n: int, live: int | None, size: int | None) -> None:
    """One log line per (entry point, path) pair — benchmarks and long
    pipelines see which engine their selection stage actually ran on."""
    tag = (kind, size is not None)
    if tag in _PATHS_LOGGED:
        return
    _PATHS_LOGGED.add(tag)
    if size is None:
        logger.info("%s: full-width selection (n=%d, live=%s)", kind, n, live)
    else:
        logger.info(
            "%s: compact selection, bucket=%d (n=%d, live=%d)",
            kind, size, n, live,
        )


def _compact_plan(
    n: int, alive, compact, kind: str
) -> tuple[int | None, int | None]:
    """Resolve the compact-selection decision outside the jit boundary.

    Returns ``(bucket_size, live)``: the static compact buffer size (None =
    full-width path) and the best-known live count (None when ``alive`` is a
    tracer and no bound was given — the s=None heuristic then falls back to
    n).  ``compact`` semantics:

    - None / True — auto: compact when ``alive`` is a *concrete* mask whose
      live count (one host read) fits a sub-n bucket;
    - False — force the full-width path;
    - int — a static upper bound on the live count, usable when ``alive`` is
      a tracer (greedy under jit/vmap, where the mask cannot be host-read) —
      e.g. the O(log² n) SS retained-set bound m·(max_rounds+1).
    """
    if compact is False or alive is None:
        # Full-width path — but still report the live count when the mask is
        # host-readable, so the s=None sample-size heuristic (and the sharded
        # sampler, which resolves the same plan) sees |alive|, not n.
        live = (
            None
            if alive is None or isinstance(alive, jax.core.Tracer)
            else int(jnp.sum(alive))
        )
        _log_path(kind, n, live, None)
        return None, live
    if compact is None or isinstance(compact, bool):
        if isinstance(alive, jax.core.Tracer):
            # No host-readable live count inside jit/vmap: stay full-width
            # (pass an int live bound via ``compact`` to opt in under
            # tracing).
            _log_path(kind, n, None, None)
            return None, None
        live = int(jnp.sum(alive))
        size = selection_bucket(n, live)
        _log_path(kind, n, live, size)
        return size, live
    bound = int(compact)
    if not 0 <= bound <= n:
        raise ValueError(
            f"compact live bound must be in [0, n={n}]; got {bound}"
        )
    if not isinstance(alive, jax.core.Tracer):
        live = int(jnp.sum(alive))
        if live > bound:
            # A bucket sized from the bound would silently truncate the
            # candidate buffer (jnp.where(..., size=...) drops overflow) and
            # selections would be wrong — fail loudly instead.
            raise ValueError(
                f"compact live bound {bound} < |alive| = {live}; pass a "
                "correct bound (or compact=True to derive it from the mask)"
            )
        # The mask is host-readable: size the bucket from the exact live
        # count, not the (possibly loose) bound — we already paid the read.
        bound = live
    size = selection_bucket(n, bound)
    _log_path(kind, n, bound, size)
    return size, bound


# ------------------------------------------------------------------ greedy --

def greedy(
    fn: SubmodularFunction,
    k: int,
    alive: Array | None = None,
    backend: "str | Backend | None" = None,
    state: Array | None = None,
    compact: "bool | int | None" = None,
) -> GreedyResult:
    """Standard greedy under a cardinality constraint, restricted to ``alive``.

    Runs exactly k steps (static).  Once the alive set is exhausted the
    remaining slots record index 0 with gain forced to 0 — the returned value
    is still f of the alive selections only, because exhausted steps never
    touch the state.  ``state`` starts the run conditionally from an existing
    summary state (S ≠ ∅); recorded gains are marginals on top of it and
    ``value`` is f of the combined set.  ``backend`` selects the execution
    path for the per-step gains (repro.core.backend); it is resolved here,
    outside the jit boundary, so the env-var default is honored per call
    rather than baked into the first trace.

    ``compact`` controls the compact selection engine (see module docstring):
    None/True auto-compacts when ``alive`` is a concrete sparse mask (one
    host read of the live count), False forces the full-width path, and an
    int supplies a static live-count bound so tracer masks (greedy under
    jit/vmap) can compact too.  Compact and full-width runs select identical
    sets.

    The whole loop dispatches through the backend: ``backend="sharded"``
    runs the distributed exact argmax of
    :func:`repro.core.distributed.greedy_sharded` (selection-identical to
    the dense path) when the objective implements the shard selection hooks.
    """
    be = resolve_backend(backend)
    if not _traceable(fn, alive, state):
        return be.greedy(fn, k, alive=alive, state=state, compact=compact)
    with obs.span("greedy.select") as sp:
        t0 = time.perf_counter()
        res = be.greedy(fn, k, alive=alive, state=state, compact=compact)
        obs.block_until_ready("greedy.wall", res.selected,
                              instrumentation=True)
        wall = time.perf_counter() - t0
        _record_greedy(sp, res, k, be.name, wall,
                       selector="greedy", batched=False)
    return res


def _greedy_dense(
    fn: SubmodularFunction,
    k: int,
    alive: Array | None = None,
    state: Array | None = None,
    compact: "bool | int | None" = None,
    backend: Backend | None = None,
) -> GreedyResult:
    """Dense greedy entry (Backend.greedy default): resolves the compact
    plan outside jit, then runs the full-width or compact loop."""
    be = backend if backend is not None else resolve_backend(None)
    size, _ = _compact_plan(fn.n, alive, compact, "greedy")
    if size is None:
        return _greedy(fn, k, alive, state, be)
    return _greedy_compact(fn, k, size, alive, state, be)


@partial(jax.jit, static_argnames=("k", "backend"))
def _greedy(
    fn: SubmodularFunction, k: int, alive: Array | None, state: Array | None,
    backend: Backend,
) -> GreedyResult:
    be = backend
    n = fn.n
    alive = jnp.ones((n,), bool) if alive is None else alive
    state0 = fn.empty_state() if state is None else state

    def step(carry, _):
        st, avail = carry
        g = jnp.where(avail, be.gains(fn, st), NEG)
        v = jnp.argmax(g)
        ok = avail[v]
        new_state = jax.tree.map(
            lambda a, b: jnp.where(ok, a, b), fn.add(st, v), st
        )
        return (new_state, avail.at[v].set(False)), (v, jnp.where(ok, g[v], 0.0))

    (final, _), (sel, gains) = jax.lax.scan(
        step, (state0, alive), None, length=k
    )
    return GreedyResult(sel.astype(jnp.int32), gains, fn.value(final), final)


@partial(jax.jit, static_argnames=("k", "size", "backend"))
def _greedy_compact(
    fn: SubmodularFunction, k: int, size: int, alive: Array,
    state: Array | None, backend: Backend,
) -> GreedyResult:
    """Compact-engine greedy: gains/argmax in (size,)-slot index space.

    ``cand_idx`` (ascending ground indices — the same order the full-width
    argmax breaks ties in) is gathered once; every step dispatches the
    ``gains_compact`` backend primitive over it.  Exhausted steps record
    ground index 0 / gain 0, exactly like the full-width path.
    """
    be = backend
    cand_idx = jnp.where(alive, size=size, fill_value=0)[0]
    avail0 = jnp.arange(size) < jnp.sum(alive)       # padding slots are dead
    state0 = fn.empty_state() if state is None else state

    def step(carry, _):
        st, avail = carry
        g = jnp.where(avail, be.gains_compact(fn, st, cand_idx), NEG)
        vc = jnp.argmax(g)
        v = cand_idx[vc]
        ok = avail[vc]
        new_state = jax.tree.map(
            lambda a, b: jnp.where(ok, a, b), fn.add(st, v), st
        )
        return (new_state, avail.at[vc].set(False)), (
            jnp.where(ok, v, 0), jnp.where(ok, g[vc], 0.0),
        )

    (final, _), (sel, gains) = jax.lax.scan(
        step, (state0, avail0), None, length=k
    )
    return GreedyResult(sel.astype(jnp.int32), gains, fn.value(final), final)


# --------------------------------------------------------- batched greedy --

def greedy_batched(
    fn: SubmodularFunction,
    k: int,
    alive: Array | None = None,
    backend: "str | Backend | None" = None,
    state: Array | None = None,
    compact: "bool | int | None" = None,
    on_step: "StepCallback | None" = None,
) -> GreedyResult:
    """Exact greedy for B same-shape queries as **one** compiled loop.

    ``fn`` is a *stacked* objective (the same pytree class with a leading
    batch axis on every array leaf — see the micro-batching hooks in
    repro.core.functions); ``alive`` is (B, n) (or None = everything live)
    and ``state`` a stacked conditional start.  Returns a batched
    GreedyResult (leading B axis on every field).

    Row b selects *identically* to ``greedy(fn_b, k, alive=alive_b, ...)`` —
    batching is a pure execution strategy (tests/test_serve_service.py pins
    this).  ``compact`` mirrors :func:`greedy`: None/True host-reads the
    per-row live counts of a concrete mask and compacts every row into one
    shared bucket sized by the batch max (per-row parity holds for any
    bucket that fits — the compact-selection contract), False forces
    full-width, an int supplies a static shared live-count bound for tracer
    masks.

    ``on_step`` opts into *streamed selection*: greedy is sequential per
    step anyway, so instead of one ``lax.scan`` over k steps the loop runs
    k launches of the same jit-compiled step and calls
    ``on_step(step, selected (B,), gains (B,), ok (B,))`` after each commits
    — the serving layer uses this to stream partial summaries back to
    tickets while later steps still run.  Both paths execute the identical
    per-step arithmetic (the scan body *is* the compiled step function), so
    selections match the un-streamed call (tests/test_serve_async.py pins
    this).  ``on_step`` requires concrete inputs (it is a host callback).
    """
    be = resolve_backend(backend)
    if alive is not None and alive.ndim != 2:
        raise ValueError(f"greedy_batched needs a (B, n) alive mask; "
                         f"got shape {alive.shape}")
    n = jax.tree.map(lambda x: x[0], fn).n
    size, _ = _batched_compact_plan(n, alive, compact)

    def _run():
        if on_step is None:
            return _greedy_batched(fn, k, size, alive, state, be)
        return _greedy_batched_stepped(fn, k, size, alive, state, be, on_step)

    if not _traceable(fn, alive, state):
        return _run()
    with obs.span("greedy.select_batched", n=n, bucket=size) as sp:
        t0 = time.perf_counter()
        res = _run()
        obs.block_until_ready("greedy.wall", res.selected,
                              instrumentation=True)
        wall = time.perf_counter() - t0
        _record_greedy(sp, res, k, be.name, wall,
                       selector="greedy", batched=True)
    return res


# ``on_step(step_index, selected (B,), gains (B,), ok (B,))`` — arrays are
# concrete device arrays (reading one waits for its step); exhausted rows
# carry index 0 / gain 0 with ok=False.
StepCallback = "Callable[[int, Array, Array, Array], None]"


def _batched_frame(
    fn: SubmodularFunction,
    size: int | None,
    alive: Array | None,
    state: Array | None,
) -> tuple[Array | None, Array, Array]:
    """Shared prologue of both batched loops: the (B, slots) availability
    frame, the compact candidate index map (None = ground index space), and
    the stacked start state."""
    B = jax.tree.leaves(fn)[0].shape[0]
    n = jax.tree.map(lambda x: x[0], fn).n
    if alive is None:
        cand_idx = None
        avail0 = jnp.ones((B, n), bool)
    elif size is None:
        cand_idx = None
        avail0 = alive
    else:
        cand_idx = jax.vmap(
            lambda a: jnp.where(a, size=size, fill_value=0)[0]
        )(alive)                                                  # (B, size)
        avail0 = jnp.arange(size)[None, :] < jnp.sum(alive, axis=1)[:, None]
    state0 = (
        jax.vmap(lambda f: f.empty_state())(fn) if state is None else state
    )
    return cand_idx, avail0, state0


def _batched_step(
    fn: SubmodularFunction,
    st,
    avail: Array,
    cand_idx: Array | None,
    backend: Backend,
):
    """One committed batched greedy step — the scan body of
    :func:`_greedy_batched` *and* the unit the streamed path launches k
    times, so both paths run the identical arithmetic.  Returns
    ``(state, avail, selected (B,), gains (B,), ok (B,))`` with exhausted
    rows recording index 0 / gain 0."""
    be = backend
    B = avail.shape[0]
    rows = jnp.arange(B)
    g = jnp.where(avail, be.gains_batched(fn, st, cand_idx), NEG)
    vc = jnp.argmax(g, axis=1)                                    # (B,)
    v = (
        vc
        if cand_idx is None
        else jnp.take_along_axis(cand_idx, vc[:, None], axis=1)[:, 0]
    )
    ok = avail[rows, vc]
    new_state = jax.vmap(lambda f, s, vv: f.add(s, vv))(fn, st, v)
    st = jax.tree.map(
        lambda a, b: jnp.where(
            ok.reshape((B,) + (1,) * (a.ndim - 1)), a, b
        ),
        new_state,
        st,
    )
    return (
        st,
        avail.at[rows, vc].set(False),
        jnp.where(ok, v, 0),
        jnp.where(ok, g[rows, vc], 0.0),
        ok,
    )


@partial(jax.jit, static_argnames=("k", "size", "backend"))
@obs.named_scope("greedy")
def _greedy_batched(
    fn: SubmodularFunction,
    k: int,
    size: int | None,
    alive: Array | None,
    state: Array | None,
    backend: Backend,
) -> GreedyResult:
    """The batched selection loop: every per-step gains/argmax runs over the
    whole (B, bucket) frame at once via the ``gains_batched`` backend
    primitive — one argmax launch for the batch instead of B."""
    cand_idx, avail0, state0 = _batched_frame(fn, size, alive, state)

    def step(carry, _):
        st, avail = carry
        st, avail, v, g, _ = _batched_step(fn, st, avail, cand_idx, backend)
        return (st, avail), (v, g)

    (final, _), (sel, gains) = jax.lax.scan(
        step, (state0, avail0), None, length=k
    )
    value = jax.vmap(lambda f, s: f.value(s))(fn, final)
    return GreedyResult(
        sel.T.astype(jnp.int32), gains.T, value, final
    )


_batched_step_jit = partial(jax.jit, static_argnames=("backend",))(
    obs.named_scope("greedy")(_batched_step)
)


@jax.jit
@obs.named_scope("greedy")
def _batched_value(fn: SubmodularFunction, state) -> Array:
    return jax.vmap(lambda f, s: f.value(s))(fn, state)


def _greedy_batched_stepped(
    fn: SubmodularFunction,
    k: int,
    size: int | None,
    alive: Array | None,
    state: Array | None,
    backend: Backend,
    on_step,
) -> GreedyResult:
    """Streamed batched greedy: k host-driven launches of the compiled
    :func:`_batched_step`, emitting each committed step through ``on_step``
    before the next one runs.  Greedy is sequential per step, so the extra
    dispatches cost launch overhead only; the arithmetic — and therefore
    the selections — are those of the ``lax.scan`` path."""
    cand_idx, avail, st = _batched_frame(fn, size, alive, state)
    sel, gains = [], []
    for i in range(k):
        st, avail, v, g, ok = _batched_step_jit(
            fn, st, avail, cand_idx, backend
        )
        # The callback reads the committed step (its one host sync); the
        # next launch proceeds once it returns.
        on_step(i, v, g, ok)
        sel.append(v)
        gains.append(g)
    return GreedyResult(
        jnp.stack(sel, axis=1).astype(jnp.int32),
        jnp.stack(gains, axis=1),
        _batched_value(fn, st),
        st,
    )


# --------------------------------------------------- batched stochastic greedy --

def stochastic_greedy_batched(
    fn: SubmodularFunction,
    k: int,
    keys: Array,
    s: int | None = None,
    alive: Array | None = None,
    backend: "str | Backend | None" = None,
    state: Array | None = None,
    compact: "bool | int | None" = None,
    eps: float = 0.1,
    on_step: "StepCallback | None" = None,
) -> GreedyResult:
    """Stochastic greedy for B same-shape queries as **one** compiled loop —
    the serving engine's degradation-ladder re-entry point (docs/serving.md
    "Failure semantics"): the same stacked-objective frame as
    :func:`greedy_batched`, but each step evaluates gains only on a
    per-row Gumbel-sampled subset of ``s`` candidate slots, so per-step cost
    tracks s instead of the compact bucket ("lazier than lazy greedy",
    Mirzasoleiman et al. 2015 — the paper-side cost of the quality step is
    the (1 - 1/e - eps) guarantee instead of (1 - 1/e)).

    Row b selects *identically* to the dense
    ``stochastic_greedy(fn_b, k, keys[b], s=s, alive=alive_b, ...)`` under
    the same per-row key **and the same resolved plan**: the Gumbel frame,
    sample set, per-element gain arithmetic, and tie order (sampled slots
    are sorted ascending before the argmax, reproducing the full-frame
    masked argmax's lowest-slot tie-break) all match
    (tests/test_serve_faults.py pins this).  Unlike exact greedy, the
    sampler's draws live in the compact frame, so the plan *is* part of the
    key: the batched loop shares one bucket (the batch max, like
    ``greedy_batched``) — pass ``compact=<that bucket's live bound>`` and
    the same effective ``s`` to the dense call when comparing rows.
    ``s=None`` derives the sample size from the batch-max live count;
    ``on_step`` streams committed steps exactly like
    :func:`greedy_batched`."""
    be = resolve_backend(backend)
    if alive is not None and alive.ndim != 2:
        raise ValueError(
            f"stochastic_greedy_batched needs a (B, n) alive mask; "
            f"got shape {alive.shape}"
        )
    n = jax.tree.map(lambda x: x[0], fn).n
    size, live = _batched_compact_plan(n, alive, compact)
    if s is None:
        s = auto_sample_size(n, k, eps, live=live)
    s = int(min(s, n if size is None else size))
    if s < 1:
        raise ValueError(f"sample size must be >= 1; got {s}")
    # Per-row per-step keys: exactly the dense loop's split(key, k), stacked
    # over rows, transposed to scan order (k, B, 2).
    step_keys = jnp.swapaxes(
        jax.vmap(lambda kk: jax.random.split(kk, k))(keys), 0, 1,
    )
    def _run():
        if on_step is None:
            return _stochastic_greedy_batched(
                fn, k, step_keys, s, size, alive, state, be
            )
        return _stochastic_greedy_batched_stepped(
            fn, k, step_keys, s, size, alive, state, be, on_step
        )

    if not _traceable(fn, keys, alive, state):
        return _run()
    with obs.span("greedy.stochastic_batched", n=n, bucket=size, s=s) as sp:
        t0 = time.perf_counter()
        res = _run()
        obs.block_until_ready("greedy.wall", res.selected,
                              instrumentation=True)
        wall = time.perf_counter() - t0
        _record_greedy(sp, res, k, be.name, wall,
                       selector="stochastic", batched=True)
    return res


def _batched_compact_plan(
    n: int, alive, compact
) -> tuple[int | None, int | None]:
    """The batched analogue of :func:`_compact_plan`: resolve the shared
    compact bucket (from the batch-max live count, or an int bound for
    tracer masks) outside the jit boundary.  Returns ``(size, live_max)``
    with both None when full-width / unknown."""
    if alive is None or compact is False:
        return None, None
    if isinstance(compact, (bool, type(None))):
        if isinstance(alive, jax.core.Tracer):
            return None, None
        live_max = _read_live_max(alive)
        return selection_bucket(n, live_max), live_max
    bound = int(compact)
    if not 0 <= bound <= n:
        raise ValueError(
            f"compact live bound must be in [0, n={n}]; got {bound}"
        )
    if not isinstance(alive, jax.core.Tracer):
        live_max = _read_live_max(alive)
        if live_max > bound:
            raise ValueError(
                f"compact live bound {bound} < max row |alive| = "
                f"{live_max}; pass a correct bound (or compact=True "
                "to derive it from the mask)"
            )
        bound = live_max
    return selection_bucket(n, bound), bound


def _read_live_max(alive: Array) -> int:
    """The batch-max live count of a concrete (B, n) mask: one device read,
    in its own ``greedy.plan`` span."""
    with obs.span("greedy.plan"):
        return int(obs.device_get(
            "greedy.live_max", jnp.max(jnp.sum(alive, axis=1))
        ))


def _sg_batched_step(
    fn: SubmodularFunction,
    st,
    avail: Array,
    cand_idx: Array | None,
    keys_i: Array,
    s: int,
    backend: Backend,
):
    """One committed batched stochastic-greedy step: per-row Gumbel top-s
    over available frame slots, gains on the gathered sample only, masked
    argmax back through the sample.  The sampled slots are sorted ascending
    so argmax ties break to the lowest frame slot — the same winner the
    dense loop's full-frame masked argmax picks."""
    be = backend
    B, width = avail.shape
    rows = jnp.arange(B)
    gumb = jax.vmap(lambda kk: jax.random.gumbel(kk, (width,)))(keys_i)
    gumb = gumb + jnp.where(avail, 0.0, NEG)
    cand = jnp.sort(jax.lax.top_k(gumb, s)[1], axis=1)            # (B, s)
    sub_avail = jnp.take_along_axis(avail, cand, axis=1)
    sub_idx = (
        cand if cand_idx is None
        else jnp.take_along_axis(cand_idx, cand, axis=1)
    )
    g = jnp.where(sub_avail, be.gains_batched(fn, st, sub_idx), NEG)
    vs = jnp.argmax(g, axis=1)                                    # (B,)
    vc = jnp.take_along_axis(cand, vs[:, None], axis=1)[:, 0]     # frame slot
    v = jnp.take_along_axis(sub_idx, vs[:, None], axis=1)[:, 0]   # ground idx
    ok = jnp.take_along_axis(sub_avail, vs[:, None], axis=1)[:, 0]
    new_state = jax.vmap(lambda f, ss, vv: f.add(ss, vv))(fn, st, v)
    st = jax.tree.map(
        lambda a, b: jnp.where(
            ok.reshape((B,) + (1,) * (a.ndim - 1)), a, b
        ),
        new_state,
        st,
    )
    return (
        st,
        avail.at[rows, vc].set(False),
        jnp.where(ok, v, 0),
        jnp.where(ok, jnp.take_along_axis(g, vs[:, None], axis=1)[:, 0], 0.0),
        ok,
    )


@partial(jax.jit, static_argnames=("k", "s", "size", "backend"))
def _stochastic_greedy_batched(
    fn: SubmodularFunction,
    k: int,
    step_keys: Array,
    s: int,
    size: int | None,
    alive: Array | None,
    state: Array | None,
    backend: Backend,
) -> GreedyResult:
    cand_idx, avail0, state0 = _batched_frame(fn, size, alive, state)

    def step(carry, keys_i):
        st, avail = carry
        st, avail, v, g, _ = _sg_batched_step(
            fn, st, avail, cand_idx, keys_i, s, backend
        )
        return (st, avail), (v, g)

    (final, _), (sel, gains) = jax.lax.scan(
        step, (state0, avail0), step_keys
    )
    value = jax.vmap(lambda f, st: f.value(st))(fn, final)
    return GreedyResult(sel.T.astype(jnp.int32), gains.T, value, final)


_sg_batched_step_jit = partial(jax.jit, static_argnames=("s", "backend"))(
    _sg_batched_step
)


def _stochastic_greedy_batched_stepped(
    fn: SubmodularFunction,
    k: int,
    step_keys: Array,
    s: int,
    size: int | None,
    alive: Array | None,
    state: Array | None,
    backend: Backend,
    on_step,
) -> GreedyResult:
    """Streamed batched stochastic greedy — k launches of the compiled step
    (the scan body), mirroring :func:`_greedy_batched_stepped`."""
    cand_idx, avail, st = _batched_frame(fn, size, alive, state)
    sel, gains = [], []
    for i in range(k):
        st, avail, v, g, ok = _sg_batched_step_jit(
            fn, st, avail, cand_idx, step_keys[i], s, backend
        )
        on_step(i, v, g, ok)
        sel.append(v)
        gains.append(g)
    return GreedyResult(
        jnp.stack(sel, axis=1).astype(jnp.int32),
        jnp.stack(gains, axis=1),
        _batched_value(fn, st),
        st,
    )


# ------------------------------------------------------------- lazy greedy --

@jax.jit
def _gain_at(fn: SubmodularFunction, state, v: Array) -> Array:
    """f(v|S) for one candidate — lazy greedy's re-evaluation primitive.

    Module-level so the trace cache is shared across ``lazy_greedy`` calls
    (a per-call ``jax.jit(lambda ...)`` wrapper would be a fresh cache every
    call and retrace on each one)."""
    return fn.gains(state)[v]


def lazy_greedy(
    fn: SubmodularFunction, k: int, alive: np.ndarray | None = None
) -> GreedyResult:
    """Minoux's accelerated greedy with a priority queue (host-side).

    Identical output to ``greedy`` (up to argmax tie order); used as the
    paper's CPU wall-clock baseline.  Function evaluations happen one
    candidate at a time via ``fn.gains`` restricted with a one-hot mask — the
    point here is counting/evaluating *lazily*, not vectorizing.
    """
    n = fn.n
    alive = np.ones((n,), bool) if alive is None else np.asarray(alive)
    state = fn.empty_state()
    # Initial upper bounds: singleton gains (computed vectorized — this is
    # also what a practical lazy greedy does for its first pass).
    ub = np.asarray(fn.gains(state))
    heap = [(-ub[v], int(v), 0) for v in range(n) if alive[v]]  # (-gain, v, stamp)
    heapq.heapify(heap)

    sel, gains, stamp = [], [], 0
    while heap and len(sel) < k:
        neg_g, v, s = heapq.heappop(heap)
        if s == stamp:                       # bound is current -> exact max
            sel.append(v)
            gains.append(-neg_g)
            state = fn.add(state, jnp.asarray(v))
            stamp += 1
        else:                                # stale: re-evaluate and push back
            g = float(_gain_at(fn, state, jnp.asarray(v)))
            heapq.heappush(heap, (-g, v, stamp))
    sel = np.asarray(sel + [0] * (k - len(sel)), np.int32)
    gains = np.asarray(gains + [0.0] * (k - len(gains)), np.float32)
    return GreedyResult(jnp.asarray(sel), jnp.asarray(gains), fn.value(state), state)


# ------------------------------------------------------- stochastic greedy --

def stochastic_greedy(
    fn: SubmodularFunction,
    k: int,
    key: Array,
    s: int | None = None,
    alive: Array | None = None,
    backend: "str | Backend | None" = None,
    state: Array | None = None,
    compact: "bool | int | None" = None,
    eps: float = 0.1,
) -> GreedyResult:
    """"Lazier than lazy greedy" [Mirzasoleiman et al. 2015]: per step, take
    the best element of a uniform random subset of size ``s``.

    ``s=None`` derives the sample size from the live count:
    s = ceil((|alive|/k)·ln(1/eps)) (:func:`auto_sample_size`) — post-SS this
    scales with |V'|, not n.  On the compact path (``compact``, same
    semantics as :func:`greedy`) the Gumbel noise is sampled directly in
    compact index space, so sampling cost also tracks |V'|.  The whole loop
    dispatches through the backend (``backend="sharded"`` runs the
    distributed sampler of :mod:`repro.core.distributed`, which matches this
    dense compact path selection-for-selection under the same key).
    """
    be = resolve_backend(backend)
    return be.stochastic_greedy(
        fn, k, key, s=s, alive=alive, state=state, compact=compact, eps=eps
    )


def _stochastic_greedy_dense(
    fn: SubmodularFunction,
    k: int,
    key: Array,
    s: int | None = None,
    alive: Array | None = None,
    state: Array | None = None,
    compact: "bool | int | None" = None,
    eps: float = 0.1,
    backend: Backend | None = None,
) -> GreedyResult:
    """Dense stochastic-greedy entry (Backend.stochastic_greedy default):
    resolves the compact plan and sample size outside jit, then runs the
    full-width or compact loop."""
    be = backend if backend is not None else resolve_backend(None)
    n = fn.n
    size, live = _compact_plan(n, alive, compact, "stochastic_greedy")
    if s is None:
        s = auto_sample_size(n, k, eps, live=live)
    s = int(min(s, n if size is None else size))
    if s < 1:
        raise ValueError(f"sample size must be >= 1; got {s}")
    if size is None:
        return _stochastic_greedy_full(fn, k, key, s, alive, state, be)
    return _stochastic_greedy_compact(fn, k, key, s, size, alive, state, be)


@partial(jax.jit, static_argnames=("k", "s", "backend"))
def _stochastic_greedy_full(
    fn: SubmodularFunction,
    k: int,
    key: Array,
    s: int,
    alive: Array | None,
    state: Array | None,
    backend: Backend,
) -> GreedyResult:
    be = backend
    n = fn.n
    alive = jnp.ones((n,), bool) if alive is None else alive
    state0 = fn.empty_state() if state is None else state

    def step(carry, key_i):
        st, avail = carry
        # Sample s candidates without replacement via Gumbel top-k on avail.
        gumb = jax.random.gumbel(key_i, (n,)) + jnp.where(avail, 0.0, NEG)
        cand = jax.lax.top_k(gumb, s)[1]
        sub_mask = jnp.zeros((n,), bool).at[cand].set(True) & avail
        g = jnp.where(sub_mask, be.gains(fn, st), NEG)
        v = jnp.argmax(g)
        ok = avail[v]
        new_state = jax.tree.map(
            lambda a, b: jnp.where(ok, a, b), fn.add(st, v), st
        )
        return (new_state, avail.at[v].set(False)), (v, jnp.where(ok, g[v], 0.0))

    (final, _), (sel, gains) = jax.lax.scan(
        step, (state0, alive), jax.random.split(key, k)
    )
    return GreedyResult(sel.astype(jnp.int32), gains, fn.value(final), final)


@partial(jax.jit, static_argnames=("k", "s", "size", "backend"))
def _stochastic_greedy_compact(
    fn: SubmodularFunction,
    k: int,
    key: Array,
    s: int,
    size: int,
    alive: Array,
    state: Array | None,
    backend: Backend,
) -> GreedyResult:
    """Compact-engine stochastic greedy: the Gumbel draw, top-k sampling,
    gains, and argmax all live in (size,)-slot index space — sampling noise
    is never materialized over the n dead candidates."""
    be = backend
    cand_idx = jnp.where(alive, size=size, fill_value=0)[0]
    avail0 = jnp.arange(size) < jnp.sum(alive)
    state0 = fn.empty_state() if state is None else state

    def step(carry, key_i):
        st, avail = carry
        gumb = jax.random.gumbel(key_i, (size,)) + jnp.where(avail, 0.0, NEG)
        cand = jax.lax.top_k(gumb, s)[1]
        sub = jnp.zeros((size,), bool).at[cand].set(True) & avail
        g = jnp.where(sub, be.gains_compact(fn, st, cand_idx), NEG)
        vc = jnp.argmax(g)
        v = cand_idx[vc]
        ok = avail[vc]
        new_state = jax.tree.map(
            lambda a, b: jnp.where(ok, a, b), fn.add(st, v), st
        )
        return (new_state, avail.at[vc].set(False)), (
            jnp.where(ok, v, 0), jnp.where(ok, g[vc], 0.0),
        )

    (final, _), (sel, gains) = jax.lax.scan(
        step, (state0, avail0), jax.random.split(key, k)
    )
    return GreedyResult(sel.astype(jnp.int32), gains, fn.value(final), final)


# ----------------------------------------------------- bidirectional greedy --

def bidirectional_greedy(
    gain_fn, n: int, key: Array, randomized: bool = True
) -> Array:
    """Buchbinder et al. (1/2)-approx for *unconstrained non-monotone*
    submodular maximization, used for the §3.4 post-reduction of V' via Eq. 9.

    ``gain_fn(mask_lo, mask_hi, v) -> (a, b)`` must return the marginal gains
    a = h(v | X) with X = {i : mask_lo[i]} and b = -h(v | Y - v) with
    Y = {i : mask_hi[i]}; it must be jax-traceable in all three arguments
    (``v`` arrives as a traced int32).  The n steps run as one
    ``lax.scan`` — a single compiled loop instead of n host iterations with
    two device round-trips each.  Returns the selected mask (n,) bool.
    """
    keys = jax.random.split(key, n)

    def step(carry, inp):
        lo, hi = carry
        v, key_v = inp
        a, b = gain_fn(lo, hi, v)
        a = jnp.asarray(a, jnp.float32)
        b = jnp.asarray(b, jnp.float32)
        if randomized:
            ap, bp = jnp.maximum(a, 0.0), jnp.maximum(b, 0.0)
            tot = ap + bp
            p = jnp.where(tot == 0.0, 1.0, ap / jnp.where(tot == 0.0, 1.0, tot))
            take = jax.random.bernoulli(key_v, p)
        else:
            take = a >= b
        lo = jnp.where(take, lo.at[v].set(True), lo)
        hi = jnp.where(take, hi, hi.at[v].set(False))
        return (lo, hi), None

    (lo, _), _ = jax.lax.scan(
        step,
        (jnp.zeros((n,), bool), jnp.ones((n,), bool)),
        (jnp.arange(n), keys),
    )
    return lo
