"""Submodular Sparsification (SS) — Algorithm 1 of the paper, plus the §3.4
improvements (pre-pruning, importance sampling, bidirectional post-reduction).

TPU adaptation (DESIGN.md §3): the ground set never changes shape.  ``V`` is a
static n-slot tensor with a boolean ``alive`` mask; each SS round
  1. samples ``m = r·log2(n)`` probe indices from the live set (Gumbel top-k),
  2. moves them from ``alive`` into the retained mask ``vprime``,
  3. computes divergences w_{U,v} (paper Def. 2) for all live v in one fused
     (m, n, F) block, dispatched through the selected execution backend
     (jnp oracle, Pallas kernel, or shard_map — see repro.core.backend),
  4. drops the (1 - 1/sqrt(c)) fraction of live elements with the smallest
     *running* divergence (min over all probes sampled so far).
The loop runs under ``jax.lax.while_loop`` with fully static shapes, so the
whole sparsifier jit-compiles and can run inside the sharded data pipeline.

Backend selection: ``ss_sparsify(fn, key, backend="pallas")`` (or a
``Backend`` instance).  ``backend="sharded"`` swaps in the distributed loop
from :mod:`repro.core.distributed` — the whole round then runs under
shard_map over a mesh, for any objective implementing the shard hooks.

Quality certificate: ``eps_hat`` is max_{v pruned} w_{U,v} at prune time — an
upper bound on max_{v in V\\V'} w_{V',v} since the probe union only grows (the
running min only decreases).  By the paper's Theorem 1 argument,
f(greedy on V') >= (1 - 1/e)(f(S*) - k * eps_hat).
"""

from __future__ import annotations

import math
import time
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import graph
from repro.core.backend import Backend, resolve_backend
from repro.core.functions import NEG, SubmodularFunction
from repro.core.greedy import _traceable, bidirectional_greedy, greedy

Array = jax.Array
INF = -NEG  # +1e30


def _round_detail(
    trace: np.ndarray, n: int, r: int, c: float, live0: int, wall_s: float,
) -> list[dict]:
    """Per-round records derived *post-hoc* from ``SSResult.alive_trace`` —
    live count after the round, the compact bucket the round dispatched
    over, and a model-apportioned share of the measured total wall time
    (``wall_est_s``; the fused ``while_loop`` cannot be host-timed per
    round without a sync inside the traced scan, so per-round wall is an
    estimate weighted by probe-rows x bucket-slots work)."""
    m = min(probe_count(n, r), n)
    buckets = bucket_schedule(n, c)
    lives = [int(v) for v in trace if v >= 0]
    detail, weights, live_before = [], [], live0
    for live_after in lives:
        bucket = min((b for b in buckets if b >= live_before), default=n)
        weights.append(float(m * bucket))
        detail.append({"live": live_after, "bucket": bucket})
        live_before = live_after
    total_w = sum(weights) or 1.0
    for j, d in enumerate(detail):
        d["round"] = j
        d["wall_est_s"] = wall_s * weights[j] / total_w
    return detail


def _record_ss(
    sp, ss: "SSResult", n: int, r: int, c: float, backend: str,
    wall_s: float, alive, *, batched: bool,
) -> None:
    """Fill an SS span + metrics from a finished result, host-read in one
    device read (counted as the tracer's own, ``obs_syncs``).  ``alive`` is
    the initial live mask (None = every element live)."""
    reg = obs.get_registry()
    live0 = None if alive is None else jnp.sum(alive, axis=-1)
    trace, rounds, eps_hat, vp, live0 = obs.device_get(
        "ss.record",
        (ss.alive_trace, ss.rounds, ss.eps_hat,
         jnp.sum(ss.vprime, axis=-1), live0),
        instrumentation=True,
    )
    if batched:
        if live0 is None:
            live0 = [n] * int(trace.shape[0])
        sp.set(
            B=int(trace.shape[0]),
            rounds=[int(x) for x in rounds],
            eps_hat=[float(x) for x in eps_hat],
            vprime_size=[int(x) for x in vp],
            rounds_detail=[
                _round_detail(row, n, r, c, int(l0), wall_s)
                for row, l0 in zip(trace, live0)
            ],
        )
        total_rounds = int(rounds.sum())
    else:
        live0 = n if live0 is None else int(live0)
        sp.set(
            rounds=int(rounds), eps_hat=float(eps_hat),
            vprime_size=int(vp),
            rounds_detail=_round_detail(trace, n, r, c, live0, wall_s),
        )
        total_rounds = int(rounds)
    # wall_s is the measured compute wall (t0 -> block_until_ready), the
    # quantity the per-round estimates apportion; the span's own t0..t1
    # additionally covers this host-side readout.
    sp.set(n=n, r=r, c=c, backend=backend, wall_s=wall_s)
    reg.histogram(
        "repro_ss_wall_seconds", "ss_sparsify wall time per call",
        labels=("backend",),
    ).observe(wall_s, backend=backend)
    reg.counter(
        "repro_ss_rounds_total", "SS rounds executed", labels=("backend",),
    ).inc(total_rounds, backend=backend)


class SSResult(NamedTuple):
    vprime: Array      # (n,) bool — retained set V'
    divergence: Array  # (n,) running divergence w_{U,v} (INF where never probed)
    eps_hat: Array     # scalar — certificate: max divergence among pruned items
    rounds: Array      # scalar int32 — rounds executed
    alive_trace: Array  # (max_rounds,) int32 live count after each round (-1 pad)


def probe_count(n: int, r: int = 8) -> int:
    """m = r * log2(n) (paper samples ``r log n`` per round, log base 2)."""
    return max(1, int(r * math.log2(max(n, 2))))

def max_rounds(n: int, r: int = 8, c: float = 8.0) -> int:
    """log_{sqrt(c)}(n) rounds suffice (paper §3.2); +2 slack for rounding."""
    return max(1, int(math.ceil(math.log(max(n, 2)) / math.log(math.sqrt(c)))) + 2)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def ss_live_bound(n: int, r: int = 8, c: float = 8.0) -> int:
    """Static upper bound on the SS retained-set size |V'| — the paper's
    O(log² n): at most m = r·log2(n) probes per round for at most
    ``max_rounds`` rounds plus an m-sized tail.  Shared by postreduce's slot
    sizing, the vmapped KV-cache selection, and the serving engine's
    compact-greedy bound (anywhere a tracer mask needs a static |V'|)."""
    m = min(probe_count(n, r), n)
    return min(n, m * (max_rounds(n, r, c) + 1))


def bucket_schedule(n: int, c: float = 8.0, tile: int = 128) -> tuple[int, ...]:
    """Static compact-buffer sizes for the shrink-aware SS loop.

    The live set shrinks by 1/sqrt(c) per round, so round j's divergence only
    has ~ceil(n / c^{j/2}) live candidates.  Returns those sizes rounded up to
    ``tile`` multiples (kernel-grid alignment), clamped to n, deduplicated,
    descending — one ``lax.switch`` branch (one static shape) per bucket, so
    the loop never recompiles and never syncs to the host.
    """
    if c <= 1.0:
        raise ValueError(f"bucket_schedule needs c > 1 (got c={c}): the SS "
                         "live set shrinks by 1 - 1/sqrt(c) per round")
    if tile < 1:
        raise ValueError(f"tile must be >= 1 (got {tile})")
    sizes: list[int] = []
    j = 0
    while True:
        raw = math.ceil(n / (math.sqrt(c) ** j))
        s = min(n, _round_up(raw, tile))
        if not sizes or s < sizes[-1]:
            sizes.append(s)
        if raw <= tile:
            return tuple(sizes)
        j += 1


def predicted_live_counts(
    n: int, r: int = 8, c: float = 8.0, alive0: int | None = None
) -> list[int]:
    """The deterministic live-count-after-each-round sequence of Algorithm 1
    (exactly what ``SSResult.alive_trace`` records): each round removes m
    probes, then floor(live * (1 - 1/sqrt(c))) pruned elements."""
    m = min(probe_count(n, r), n)
    shrink = 1.0 - 1.0 / math.sqrt(c)
    live = n if alive0 is None else alive0
    out: list[int] = []
    for _ in range(max_rounds(n, r, c)):
        if live <= m:
            break
        live -= m
        live -= math.floor(live * shrink)
        out.append(live)
    return out


def ss_cost_model(
    n: int, r: int = 8, c: float = 8.0, alive0: int | None = None
) -> float:
    """Predicted SS divergence work — probe rows × compact candidate slots,
    summed over the deterministic round schedule of Algorithm 1 (the same
    recurrence as :func:`predicted_live_counts`, bucket-rounded like the
    shrink-aware executor actually dispatches).

    This is the *relative* cost model the serving degradation ladder uses
    (docs/serving.md "Failure semantics"): bumping ``c`` shrinks the live
    set faster (fewer, smaller rounds) and shrinking ``r`` cuts the probe
    rows, so ``ss_cost_model(n, r2, c2) / ss_cost_model(n, r1, c1)`` predicts
    the execution-time ratio of a degraded config before it has ever been
    measured.  Arbitrary units — only ratios are meaningful.
    """
    m = min(probe_count(n, r), n)
    buckets = bucket_schedule(n, c)
    shrink = 1.0 - 1.0 / math.sqrt(c)
    live = n if alive0 is None else alive0
    total = 0.0
    for _ in range(max_rounds(n, r, c)):
        if live <= m:
            break
        live -= m
        bucket = min((b for b in buckets if b >= live), default=n)
        total += m * bucket
        live -= math.floor(live * shrink)
    return max(total, 1.0)


def ss_sparsify(
    fn: SubmodularFunction,
    key: Array,
    r: int = 8,
    c: float = 8.0,
    alive: Array | None = None,
    state: Array | None = None,
    importance: bool = False,
    backend: "str | Backend | None" = None,
    compact: bool = True,
) -> SSResult:
    """Algorithm 1 (Submodular Sparsification).

    Args:
      fn: submodular objective over n ground elements.
      key: PRNG key for probe sampling.
      r: probe multiplier (paper uses r = 8 = c).
      c: accuracy/speed tradeoff; shrink rate is 1/sqrt(c) per round.
      alive: optional (n,) bool — initial live mask (e.g. after pre-pruning).
      state: optional summary state for *conditional* SS on G(V, E|S).
      importance: §3.4 improvement 2 — sample probes with probability
        proportional to f(u) + f(u|V\\u) instead of uniformly.
      backend: execution backend — "oracle" (default), "pallas", "sharded",
        or a Backend instance (repro.core.backend).
      compact: shrink-aware execution (default) — each round's divergence is
        dispatched over a compacted live-candidate buffer whose static size
        follows :func:`bucket_schedule`, so round cost tracks the live count
        instead of n.  ``compact=False`` forces the full-width path (the two
        produce identical ``vprime`` under the same key).
    """
    be = resolve_backend(backend)
    if not _traceable(fn, key, alive, state):
        return be.sparsify(
            fn, key, r=r, c=c, alive=alive, state=state,
            importance=importance, compact=compact,
        )
    with obs.span("ss.sparsify") as sp:
        t0 = time.perf_counter()
        ss = be.sparsify(
            fn, key, r=r, c=c, alive=alive, state=state,
            importance=importance, compact=compact,
        )
        obs.block_until_ready("ss.wall", ss.vprime, instrumentation=True)
        wall = time.perf_counter() - t0
        _record_ss(sp, ss, fn.n, r, c, be.name, wall, alive, batched=False)
    return ss


@partial(jax.jit, static_argnames=("r", "c", "importance", "backend", "compact"))
def _sparsify_dense(
    fn: SubmodularFunction,
    key: Array,
    r: int = 8,
    c: float = 8.0,
    alive: Array | None = None,
    state: Array | None = None,
    importance: bool = False,
    backend: Backend | None = None,
    compact: bool = True,
) -> SSResult:
    """The dense single-process SS loop; ``backend`` (an already-resolved
    Backend instance — callers go through ss_sparsify) supplies divergence.

    With ``compact`` (the default), each round gathers the surviving
    candidates into a static bucket-sized index buffer (one ``lax.switch``
    branch per :func:`bucket_schedule` size — no recompilation, no host
    sync), dispatches ``divergence_compact`` over the buffer, and
    scatter-mins the (k,) result back to ground indices.  Divergence entries
    of probe/dead slots are then *stale* rather than refreshed — the loop
    never reads them (pruning and eps_hat only consult live entries), so
    ``vprime``/``eps_hat`` are identical to the full-width path.
    """
    be = backend if backend is not None else resolve_backend(None)
    n = fn.n
    m = min(probe_count(n, r), n)  # tiny ground sets: everything is a probe
    rounds_cap = max_rounds(n, r, c)
    shrink = 1.0 - 1.0 / math.sqrt(c)
    buckets = bucket_schedule(n, c) if compact else None

    alive0 = jnp.ones((n,), bool) if alive is None else alive
    residual = fn.residual_gains()

    if importance:
        score = fn.singleton_gains() + residual
        logits = jnp.log(jnp.maximum(score, 1e-12))
    else:
        logits = jnp.zeros((n,))

    def _divergence(probes):
        return be.divergence(fn, probes, residual=residual, state=state)

    def _make_branch(size: int):
        if size >= n:
            # Full-width bucket (round 1, before any shrink): the gather +
            # scatter would be pure overhead over the plain divergence.
            def full(args):
                _, probes, div = args
                return jnp.minimum(div, _divergence(probes))
            return full

        # One static compact width: gather live candidates into a (size,)
        # buffer, compute their divergences, scatter-min back to ground.
        def branch(args):
            alive, probes, div = args
            cand_idx = jnp.where(alive, size=size, fill_value=0)[0]
            cand_mask = jnp.arange(size) < jnp.sum(alive)
            w = be.divergence_compact(
                fn, probes, cand_idx, residual=residual, state=state
            )
            # Padding slots repeat index 0 — masked to +INF, their
            # scatter-min is a no-op.
            w = jnp.where(cand_mask, w, INF)
            return div.at[cand_idx].min(w)
        return branch

    branches = [_make_branch(s) for s in buckets] if compact else None

    def cond(carry):
        alive, vprime, div, eps_hat, key, rnd, trace = carry
        return (jnp.sum(alive) > m) & (rnd < rounds_cap)

    def body(carry):
        alive, vprime, div, eps_hat, key, rnd, trace = carry
        key, k1 = jax.random.split(key)

        # (1) sample m probes from the live set (Gumbel top-k == uniform or
        # importance-weighted sampling without replacement).
        g = jax.random.gumbel(k1, (n,)) + logits + jnp.where(alive, 0.0, NEG)
        probes = jax.lax.top_k(g, m)[1]
        probe_hot = jnp.zeros((n,), bool).at[probes].set(True) & alive

        # (2) U moves from V to V'.
        vprime = vprime | probe_hot
        alive = alive & ~probe_hot

        # (3) running divergence against the union of all probes so far —
        # over the compacted live buffer (smallest bucket that fits the live
        # count) or the full width.
        if compact:
            barr = jnp.asarray(buckets)
            bidx = jnp.sum(barr >= jnp.sum(alive)) - 1
            div = jax.lax.switch(bidx, branches, (alive, probes, div))
        else:
            div = jnp.minimum(div, _divergence(probes))

        # (4) drop the (1 - 1/sqrt(c)) fraction of live items with smallest
        # divergence.  Rank via masked argsort (dead -> +INF sorts last).
        live = jnp.sum(alive)
        n_remove = jnp.floor(live * shrink).astype(jnp.int32)
        keyed = jnp.where(alive, div, INF)
        order = jnp.argsort(keyed)                       # ascending
        pos = jnp.zeros((n,), jnp.int32).at[order].set(jnp.arange(n, dtype=jnp.int32))
        removed = alive & (pos < n_remove)
        eps_hat = jnp.maximum(
            eps_hat, jnp.max(jnp.where(removed, div, NEG))
        )
        alive = alive & ~removed
        trace = trace.at[rnd].set(jnp.sum(alive).astype(jnp.int32))
        return (alive, vprime, div, eps_hat, key, rnd + 1, trace)

    carry = (
        alive0,
        jnp.zeros((n,), bool),
        jnp.full((n,), INF),
        jnp.float32(NEG),
        key,
        jnp.int32(0),
        jnp.full((rounds_cap,), -1, jnp.int32),
    )
    alive, vprime, div, eps_hat, _, rnd, trace = jax.lax.while_loop(cond, body, carry)
    # Tail: remaining live elements all survive into V' (Algorithm 1, line 13).
    vprime = vprime | alive
    return SSResult(vprime, div, jnp.maximum(eps_hat, 0.0), rnd, trace)


def ss_sparsify_batched(
    fn: SubmodularFunction,
    keys: Array,
    r: int = 8,
    c: float = 8.0,
    alive: Array | None = None,
    state: Array | None = None,
    importance: bool = False,
    backend: "str | Backend | None" = None,
    compact: bool = True,
) -> SSResult:
    """Algorithm 1 for B same-shape queries as **one** compiled loop.

    ``fn`` is a *stacked* objective (the same pytree class with a leading
    batch axis on every array leaf — see the micro-batching hooks in
    repro.core.functions), ``keys`` the (B, 2) per-query PRNG keys, ``alive``
    an optional (B, n) mask and ``state`` an optional stacked conditional
    state.  Returns a batched SSResult (leading B axis on every field).

    Row b is *identical* to ``ss_sparsify(fn_b, keys[b], ...)`` on that
    query alone — same ``vprime``, ``eps_hat``, ``rounds`` and
    ``alive_trace`` under the same per-query key (micro-batching is a pure
    execution strategy; tests/test_serve_service.py pins this).  Rows that
    exhaust early freeze in place while the rest keep iterating.  As with
    the compacted single-query loop, ``divergence`` entries of probe/dead
    slots are stale by design; additionally the batched loop shares one
    bucket (the batch max) per round, so stale entries may differ from the
    single-query run — never read them at non-live indices.
    """
    be = resolve_backend(backend)
    if not _traceable(fn, keys, alive, state):
        return be.sparsify_batched(
            fn, keys, r=r, c=c, alive=alive, state=state,
            importance=importance, compact=compact,
        )
    with obs.span("ss.sparsify_batched") as sp:
        t0 = time.perf_counter()
        ss = be.sparsify_batched(
            fn, keys, r=r, c=c, alive=alive, state=state,
            importance=importance, compact=compact,
        )
        obs.block_until_ready("ss.wall", ss.vprime, instrumentation=True)
        wall = time.perf_counter() - t0
        n = jax.tree.map(lambda x: x[0], fn).n
        _record_ss(sp, ss, n, r, c, be.name, wall, alive, batched=True)
    return ss


@partial(jax.jit, static_argnames=("r", "c", "importance", "backend", "compact"))
@obs.named_scope("ss")
def _sparsify_batched(
    fn: SubmodularFunction,
    keys: Array,
    r: int = 8,
    c: float = 8.0,
    alive: Array | None = None,
    state: Array | None = None,
    importance: bool = False,
    backend: Backend | None = None,
    compact: bool = True,
) -> SSResult:
    """The batched dense SS loop (Backend.sparsify_batched default).

    Structure mirrors :func:`_sparsify_dense` exactly, with every per-query
    op vmapped over the batch and two shared pieces of control flow: one
    global round counter (rows that finish freeze via a per-row ``active``
    mask — their carry is reselected unchanged, so a frozen row's result
    cannot drift from its single-query run), and one compact bucket per
    round chosen from the max live count over *active* rows (per-row
    results are bucket-size-invariant by the compaction contract, so
    sharing the branch preserves row-for-row parity while keeping a single
    ``lax.switch`` — under vmapped control flow every branch would run).
    Divergence dispatches through the ``divergence_batched`` backend
    primitive: one cache-blocked launch for the whole batch per round.
    """
    be = backend if backend is not None else resolve_backend(None)
    fn0 = jax.tree.map(lambda x: x[0], fn)
    n = fn0.n
    B = keys.shape[0]
    m = min(probe_count(n, r), n)
    rounds_cap = max_rounds(n, r, c)
    shrink = 1.0 - 1.0 / math.sqrt(c)
    buckets = bucket_schedule(n, c) if compact else None

    alive0 = jnp.ones((B, n), bool) if alive is None else alive
    residual = jax.vmap(lambda f: f.residual_gains())(fn)        # (B, n)

    if importance:
        score = jax.vmap(lambda f: f.singleton_gains())(fn) + residual
        logits = jnp.log(jnp.maximum(score, 1e-12))
    else:
        logits = jnp.zeros((B, n))

    def _divergence(probes, cand_idx):
        return be.divergence_batched(
            fn, probes, cand_idx, residual=residual, state=state
        )

    def _make_branch(size: int):
        if size >= n:
            def full(args):
                _, probes, div = args
                return jnp.minimum(div, _divergence(probes, None))
            return full

        def branch(args):
            alive_b, probes, div = args
            cand_idx = jax.vmap(
                lambda a: jnp.where(a, size=size, fill_value=0)[0]
            )(alive_b)                                           # (B, size)
            cand_mask = (
                jnp.arange(size)[None, :] < jnp.sum(alive_b, axis=1)[:, None]
            )
            w = _divergence(probes, cand_idx)                    # (B, size)
            w = jnp.where(cand_mask, w, INF)
            return jax.vmap(lambda d, ci, ww: d.at[ci].min(ww))(
                div, cand_idx, w
            )
        return branch

    branches = [_make_branch(s) for s in buckets] if compact else None

    def row_active(alive_b, rnd_b):
        return (jnp.sum(alive_b, axis=1) > m) & (rnd_b < rounds_cap)

    def cond(carry):
        alive_b, vprime, div, eps, keys_b, rnd_b, trace = carry
        return jnp.any(row_active(alive_b, rnd_b))

    def body(carry):
        alive_b, vprime, div, eps, keys_b, rnd_b, trace = carry
        active = row_active(alive_b, rnd_b)                      # (B,)
        new_keys, k1 = jax.vmap(
            lambda kk: tuple(jax.random.split(kk))
        )(keys_b)

        # (1) per-row probe sampling — identical draws to the single-query
        # loop under the same per-row key.
        g = (
            jax.vmap(lambda kk: jax.random.gumbel(kk, (n,)))(k1)
            + logits
            + jnp.where(alive_b, 0.0, NEG)
        )
        probes = jax.lax.top_k(g, m)[1]                          # (B, m)
        probe_hot = (
            jnp.zeros((B, n), bool)
            .at[jnp.arange(B)[:, None], probes]
            .set(True)
            & alive_b
        )

        # (2) U moves from V to V'.
        new_vprime = vprime | probe_hot
        new_alive = alive_b & ~probe_hot

        # (3) running divergence over the shared bucket (batch max of the
        # active rows' live counts; inactive rows' results are discarded).
        if compact:
            barr = jnp.asarray(buckets)
            live_max = jnp.max(
                jnp.where(active, jnp.sum(new_alive, axis=1), 0)
            )
            bidx = jnp.sum(barr >= live_max) - 1
            new_div = jax.lax.switch(
                bidx, branches, (new_alive, probes, div)
            )
        else:
            new_div = jnp.minimum(div, _divergence(probes, None))

        # (4) per-row prune of the smallest-divergence fraction.
        live = jnp.sum(new_alive, axis=1)
        n_remove = jnp.floor(live * shrink).astype(jnp.int32)
        keyed = jnp.where(new_alive, new_div, INF)
        order = jnp.argsort(keyed, axis=1)
        pos = jnp.zeros((B, n), jnp.int32).at[
            jnp.arange(B)[:, None], order
        ].set(jnp.arange(n, dtype=jnp.int32)[None, :])
        removed = new_alive & (pos < n_remove[:, None])
        new_eps = jnp.maximum(
            eps, jnp.max(jnp.where(removed, new_div, NEG), axis=1)
        )
        new_alive = new_alive & ~removed
        new_trace = jax.vmap(
            lambda t, rr, a: t.at[rr].set(jnp.sum(a).astype(jnp.int32))
        )(trace, rnd_b, new_alive)

        # Frozen rows keep their entire carry — bit-identical to having
        # exited the single-query loop.
        sel = lambda new, old: jnp.where(
            active.reshape((B,) + (1,) * (new.ndim - 1)), new, old
        )
        return (
            sel(new_alive, alive_b),
            sel(new_vprime, vprime),
            sel(new_div, div),
            sel(new_eps, eps),
            sel(new_keys, keys_b),
            rnd_b + active.astype(jnp.int32),
            sel(new_trace, trace),
        )

    carry = (
        alive0,
        jnp.zeros((B, n), bool),
        jnp.full((B, n), INF),
        jnp.full((B,), NEG, jnp.float32),
        keys,
        jnp.zeros((B,), jnp.int32),
        jnp.full((B, rounds_cap), -1, jnp.int32),
    )
    alive_b, vprime, div, eps, _, rnd_b, trace = jax.lax.while_loop(
        cond, body, carry
    )
    vprime = vprime | alive_b
    return SSResult(vprime, div, jnp.maximum(eps, 0.0), rnd_b, trace)


def preprune_mask(fn: SubmodularFunction, k: int) -> Array:
    """Wei-et-al pre-pruning (§3.4 improvement 1): drop u whose singleton gain
    f(u) is below the k-th largest residual f(v|V\\v) — provably safe."""
    residual = fn.residual_gains()
    kth = jax.lax.top_k(residual, k)[0][-1]
    return fn.singleton_gains() >= kth


def postreduce(
    fn: SubmodularFunction,
    result: SSResult,
    eps: float,
    key: Array,
    max_members: "int | str | None" = None,
    r: int = 8,
    c: float = 8.0,
) -> Array:
    """§3.4 improvement 3: shrink V' further by (approximately) solving Eq. 9
    restricted to V' with bidirectional greedy.  Returns a new vprime mask.

    h(V') = |{v in V \\ V' : w_{V'v} <= eps}|  -  computed against the edge
    weights from V'-members to all pruned v.  Member bookkeeping is vectorized
    over a static block of slots (padded with -1) and scattered back to
    ground indices in one masked scatter — no per-element host loop.

    ``max_members`` is the static slot count.  The default (None) derives it
    from the paper's O(log² n) retained-set size: SS adds at most m =
    r·log2(n) probes per round for at most ``max_rounds`` rounds plus an
    m-sized tail, so m·(max_rounds+1) slots always fit V' (pass ``r``/``c``
    matching the SS run if non-default — a mismatch that would truncate V'
    raises).  ``max_members="exact"`` opts into one host-sync read of |V'|
    for the tightest block; an int pins the bound explicitly and is trusted
    *unchecked* (no sync — the caller owns the fit).  The reduction itself
    (bidirectional greedy) is a host-side loop by design — V' is
    polylog-sized after SS.
    """
    n = fn.n
    derived = max_members is None
    if max_members == "exact":
        max_members = int(jnp.sum(result.vprime))  # one sizing sync (opt-in)
    elif derived:
        max_members = ss_live_bound(n, r, c)
    slots = max(1, min(n, max_members))
    if derived and slots < n and int(jnp.sum(result.vprime)) > slots:
        # jnp.where(..., size=slots) would silently drop V' members and the
        # reduction would return a wrong mask — fail loudly instead.  (One
        # host read, only on the derived-default path and only when the block
        # is actually restrictive; an explicit int bound is trusted unchecked
        # precisely so callers can avoid this sync.)
        raise ValueError(
            f"postreduce slot bound {slots} < |V'|: the SS run used a "
            "different r/c than passed here — pass matching r/c, an explicit "
            "max_members, or max_members='exact'"
        )
    vp_idx = jnp.where(result.vprime, size=slots, fill_value=-1)[0]  # (slots,)
    valid = vp_idx >= 0
    members = jnp.where(valid, vp_idx, 0)
    residual = fn.residual_gains()
    # Edge weights from every V' member slot to every ground element:
    # (slots, n).  Invalid (padding) slots get +INF rows: they never cover.
    W = graph.edge_weights(fn, members, residual=residual)
    W = jnp.where(valid[:, None], W, INF)
    pruned = ~result.vprime

    def h_of(mask_members: Array) -> Array:
        # mask_members: (slots,) bool over member slots
        wmin = jnp.min(jnp.where(mask_members[:, None], W, INF), axis=0)
        covered = pruned & (wmin <= eps)
        return jnp.sum(covered) - 0.0  # |V'| term handled by caller's deltas

    def gain_fn(lo, hi, v):
        # marginal of adding v to lo, and of removing v from hi, under
        # h(X) = covered(X) - |X|  (Eq. 9 as coverage minus cardinality).
        a = h_of(lo.at[v].set(True)) - h_of(lo) - 1.0
        b = (h_of(hi.at[v].set(False)) - h_of(hi)) + 1.0
        # Padding slots cover nothing: a = -1 < 0 <= b = +1, never taken.
        return a, b

    keep_slots = bidirectional_greedy(gain_fn, slots, key)
    # Vectorized member-mask scatter: slot i keeps ground element vp_idx[i].
    # Invalid slots scatter to index n, dropped by out-of-bounds mode.
    target = jnp.where(valid & keep_slots, vp_idx, n)
    return jnp.zeros((n,), bool).at[target].set(True, mode="drop")


def summarize(
    fn: SubmodularFunction,
    k: int,
    key: Array,
    r: int = 8,
    c: float = 8.0,
    preprune: bool = False,
    importance: bool = False,
    backend: "str | Backend | None" = None,
    compact: bool = True,
):
    """End-to-end paper pipeline: (optional pre-prune) -> SS -> greedy on V'.

    ``backend`` selects the execution path for both stages.  ``compact``
    covers both stages too: shrink-aware SS rounds *and* the compact
    selection engine for the downstream greedy (post-SS |V'| ≪ n always fits
    a sub-n bucket, so the selection stage runs at |V'| cost by default).
    Returns (GreedyResult, SSResult).
    """
    alive = preprune_mask(fn, k) if preprune else None
    ss = ss_sparsify(
        fn, key, r=r, c=c, alive=alive, importance=importance, backend=backend,
        compact=compact,
    )
    res = greedy(fn, k, alive=ss.vprime, backend=backend, compact=compact)
    return res, ss
