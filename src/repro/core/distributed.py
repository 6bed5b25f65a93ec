"""Distributed Submodular Sparsification: shard_map over the data axis.

This realizes the paper's "per-iteration computation ... is small and highly
parallelizable" claim on a TPU mesh, for **any** objective implementing the
shard hooks of :class:`repro.core.functions.SubmodularFunction` (per-shard
function views — no objective-specific math lives here).  Each SS round is:

  1. **distributed probe sampling** — every device draws Gumbel scores for its
     live candidates, proposes its local top-m, all-gathers the candidate
     (score, payload, residual) triples, and takes the global top-m.  (Gumbel
     top-k == uniform sampling without replacement, so this is exactly
     Algorithm 1's sampler.)  A probe's *payload* is whatever its objective
     declares sufficient for any shard to evaluate probe-conditioned gains —
     a coverage row for FeatureCoverage, a similarity column for
     FacilityLocation (which StreamingFacilityLocation reproduces from its
     embedding rows on the fly, so the wire format — and this loop — are
     identical for the matrix-free objective).
  2. **local divergence** — the (m, payload_dim) probe block is tiny and
     replicated; each device computes w_{U,v} for its own candidates only via
     ``fn.shard_payload_gains``: embarrassingly parallel, as the paper
     promises.
  3. **distributed quantile prune** — instead of a global sort, a fixed-bin
     histogram of live divergences is psum'd and the (1 - 1/sqrt(c))-quantile
     threshold read off it.  We prune *at most* that fraction (the bin edge
     rounds down), preserving Proposition 4's safety direction.
  4. masks update locally; the loop is a ``lax.while_loop`` with fully static
     shapes inside one ``shard_map``.

**Hierarchical pod aggregation** (the composable-coreset pattern of paper
§1.2, with SS in place of per-machine greedy): when the mesh has a ``pod``
axis, every pod treats its own row range as a standalone ground set —
collectives bind only the ``data`` axis — and the returned V' is the union of
per-pod V' sets.  Cross-pod (DCN) traffic is zero during sparsification; only
the final (tiny) reduced set crosses pods.  Pod hierarchy requires the
objective's arrays to be row-local (``supports_pod_sharding``): FeatureCoverage
qualifies, FacilityLocation (whose served rows span the full ground set) does
not.

Entry points: ``ss_sparsify(fn, key, backend="sharded")`` (via
:class:`repro.core.backend.ShardedBackend`) or :func:`ss_sparsify_sharded`
directly with an explicit mesh.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.functions import NEG, FeatureCoverage, SubmodularFunction
from repro.core.greedy import (
    GreedyResult,
    auto_sample_size,
    greedy,
    selection_bucket,
)
from repro.core.sparsify import SSResult, bucket_schedule, max_rounds, probe_count

Array = jax.Array
INF = -NEG


def make_mesh(shape, axes) -> Mesh:
    """A device mesh with Auto axis types (jax.make_mesh defaults to
    Explicit), the sharding mode every shard_map in this repository is
    written for."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


def _as_objective(fn, phi: str = "sqrt") -> SubmodularFunction:
    """Legacy entry point: a raw (n, F) feature array means FeatureCoverage."""
    if isinstance(fn, SubmodularFunction):
        return fn
    return FeatureCoverage(W=jnp.asarray(fn), phi=phi)


def ss_sparsify_sharded(
    fn,                        # SubmodularFunction or legacy (n, F) array
    key: Array,
    mesh: Mesh,
    *,
    data_axis: str = "data",
    pod_axis: str | None = None,
    r: int = 8,
    c: float = 8.0,
    phi: str = "sqrt",
    bins: int = 512,
    alive: Array | None = None,
    state: Array | None = None,
    importance: bool = False,
    compact: bool = True,
) -> SSResult:
    """Distributed Algorithm 1 over any shard-capable objective.

    The objective's arrays are placed candidate-sharded over (pod, data) via
    its ``shard_pack`` spec; each pod sparsifies its own candidate range
    independently (collectives over ``data`` only).  Returns a full
    :class:`SSResult` (``alive_trace`` is only recorded for single-level
    meshes; with a pod hierarchy it is -1, since pods run independent loops).

    ``state`` runs *conditional* SS on G(V, E|S): the replicated summary
    state is folded into each probe's payload (``shard_payloads(idx,
    state)``), so every shard evaluates f(v | S + u) with the exact dense
    arithmetic — residuals stay unconditional, matching the dense loop.
    ``importance`` (§3.4 improvement 2) weights each shard's Gumbel draws by
    log(f(u) + f(u|V\\u)) of its local candidates, computed via the
    ``shard_gains`` selection hook (requires ``supports_shard_greedy``).

    ``compact`` (default, for objectives with ``supports_shard_compact``)
    makes each shard gather its surviving candidates into a bucket-sized
    static buffer (``lax.switch`` over the per-shard :func:`bucket_schedule`)
    before evaluating payload gains — only the *grid* is rebalanced; the
    objective's sharded arrays never move.  The bucket index comes from the
    pmax of the per-shard live counts, so every shard of a pod takes the same
    branch and the branches stay collective-free.
    """
    fn = _as_objective(fn, phi)
    if importance and not fn.supports_shard_greedy:
        raise NotImplementedError(
            f"{type(fn).__name__} does not implement shard_gains — sharded "
            "importance sampling needs the per-shard singleton gains"
        )
    n = fn.n
    axes = (pod_axis, data_axis) if pod_axis else (data_axis,)
    nshards = 1
    for a in axes:
        nshards *= mesh.shape[a]
    ndata = mesh.shape[data_axis]
    npods = mesh.shape[pod_axis] if pod_axis else 1
    if pod_axis and not fn.supports_pod_sharding:
        raise NotImplementedError(
            f"{type(fn).__name__} does not support pod-hierarchical sharding"
        )
    assert n % nshards == 0, f"n={n} must divide {nshards} shards (pad rows)"
    n_pod = n // npods                       # per-pod ground set size
    n_loc = n // nshards                     # per-device candidate count
    m = min(probe_count(n_pod, r), n_pod)    # probes per round (per pod)
    # Each device proposes its local top-m_loc; proposing every local row is
    # enough when a shard holds fewer than m candidates (ndata * m_loc >= m).
    m_loc = min(m, n_loc)
    rounds_cap = max_rounds(n_pod, r, c)
    shrink = 1.0 - 1.0 / math.sqrt(c)
    # Per-shard compact buckets: jnp payload gains need no tile alignment, so
    # a fine-grained tile keeps compaction effective on small shards too.
    compact = compact and fn.supports_shard_compact
    buckets = bucket_schedule(n_loc, c, tile=8) if compact else None

    arrays, specs, rebuild = fn.shard_pack(axes)
    arrays = tuple(
        jax.device_put(a, NamedSharding(mesh, s)) for a, s in zip(arrays, specs)
    )
    mask_spec = P(axes if len(axes) > 1 else axes[0])
    alive0 = jnp.ones((n,), bool) if alive is None else jnp.asarray(alive)
    alive0 = jax.device_put(alive0, NamedSharding(mesh, mask_spec))
    has_state = state is not None

    keys = jax.random.split(key, npods)      # per-pod independent streams
    keys_spec = P(pod_axis) if pod_axis else P()
    if pod_axis:
        keys = jax.device_put(keys, NamedSharding(mesh, keys_spec))
    else:
        keys = keys[0]

    def kernel(key_loc: Array, alive_loc: Array, state_rep, *arrs):
        # All collectives bind data_axis only: pods run independently.
        fn_loc = rebuild(*arrs)
        if pod_axis:
            key_loc = key_loc[0]             # (1, 2) -> (2,)
        assert fn_loc.local_n() == n_loc
        didx = jax.lax.axis_index(data_axis)
        st = state_rep if has_state else None

        ctx = fn_loc.shard_init(data_axis)
        resid_loc = fn_loc.shard_residuals(ctx)       # (n_loc,)
        if importance:
            # §3.4: probe u with probability ∝ f(u) + f(u|V\u) — the same
            # logit expression as the dense loop, over local candidates.
            sing_loc = fn_loc.shard_gains(fn_loc.empty_state(), ctx)
            logits_loc = jnp.log(jnp.maximum(sing_loc + resid_loc, 1e-12))
        else:
            logits_loc = jnp.zeros((n_loc,))

        def cond(carry):
            alive, vprime, div, eps, k, rnd, trace = carry
            total = jax.lax.psum(jnp.sum(alive), data_axis)
            return (total > m) & (rnd < rounds_cap)

        def body(carry):
            alive, vprime, div, eps, k, rnd, trace = carry
            k, k1 = jax.random.split(k)
            # identical stream on every data shard; fold in the shard id for
            # distinct local gumbel draws
            g = (
                jax.random.gumbel(jax.random.fold_in(k1, didx), (n_loc,))
                + logits_loc
                + jnp.where(alive, 0.0, NEG)
            )
            loc_val, loc_idx = jax.lax.top_k(g, m_loc)
            loc_pay = fn_loc.shard_payloads(loc_idx, st)      # (m_loc, D)
            loc_res = resid_loc[loc_idx]                      # (m_loc,)
            all_val = jax.lax.all_gather(loc_val, data_axis).reshape(-1)
            all_pay = jax.lax.all_gather(loc_pay, data_axis)
            all_pay = all_pay.reshape(-1, all_pay.shape[-1])
            all_res = jax.lax.all_gather(loc_res, data_axis).reshape(-1)
            top_val, top_pos = jax.lax.top_k(all_val, m)      # global top-m
            payloads = all_pay[top_pos]                       # (m, D)
            resid_p = all_res[top_pos]                        # (m,)

            # membership: my local candidate j became a probe iff its gumbel
            # value is among the global top-m (values are a.s. distinct)
            thresh_val = top_val[-1]
            probe_hot = alive & (g >= thresh_val)
            vprime = vprime | probe_hot
            alive = alive & ~probe_hot

            # local divergence w_{U, v} for my candidates, via the per-shard
            # function view: f(v | U+u) from the replicated payload block.
            # Compacted: gather my live candidates into the smallest static
            # bucket that fits every shard's live count (pmax -> all shards
            # take the same collective-free branch), evaluate the (m, k)
            # block on the restricted view, scatter-min back.
            if compact:
                live_max = jax.lax.pmax(jnp.sum(alive), data_axis)
                bidx = jnp.sum(jnp.asarray(buckets) >= live_max) - 1

                def _make_branch(size):
                    if size >= n_loc:
                        def full(args):
                            _, payloads_b, resid_b, div_b = args
                            pair = fn_loc.shard_payload_gains(payloads_b, ctx)
                            w = pair - resid_b[:, None]
                            return jnp.minimum(div_b, jnp.min(w, axis=0))
                        return full

                    def branch(args):
                        alive_b, payloads_b, resid_b, div_b = args
                        cand_idx = jnp.where(alive_b, size=size, fill_value=0)[0]
                        cand_mask = jnp.arange(size) < jnp.sum(alive_b)
                        pair_c = fn_loc.shard_take(cand_idx).shard_payload_gains(
                            payloads_b, ctx
                        )                                     # (m, size)
                        w_c = jnp.min(pair_c - resid_b[:, None], axis=0)
                        w_c = jnp.where(cand_mask, w_c, INF)
                        return div_b.at[cand_idx].min(w_c)
                    return branch

                div = jax.lax.switch(
                    bidx,
                    [_make_branch(s) for s in buckets],
                    (alive, payloads, resid_p, div),
                )
            else:
                pair = fn_loc.shard_payload_gains(payloads, ctx)  # (m, n_loc)
                w = pair - resid_p[:, None]
                div = jnp.minimum(div, jnp.min(w, axis=0))

            # distributed quantile: histogram of live divergences
            lo = jax.lax.pmin(
                jnp.min(jnp.where(alive, div, INF)), data_axis
            )
            hi = jax.lax.pmax(
                jnp.max(jnp.where(alive, div, -INF)), data_axis
            )
            width = jnp.maximum(hi - lo, 1e-9)
            bidx = jnp.clip(
                ((div - lo) / width * bins).astype(jnp.int32), 0, bins - 1
            )
            hist = jnp.zeros((bins,), jnp.int32).at[bidx].add(
                alive.astype(jnp.int32)
            )
            hist = jax.lax.psum(hist, data_axis)
            total = jnp.sum(hist)
            target = jnp.floor(total * shrink).astype(jnp.int32)
            cum = jnp.cumsum(hist)
            # largest bin edge with cumulative count <= target (prune <= frac)
            nbin = jnp.sum(cum <= target)                      # bins fully below
            thresh = lo + width * nbin.astype(jnp.float32) / bins
            removed = alive & (div < thresh)
            eps = jnp.maximum(
                eps, jax.lax.pmax(
                    jnp.max(jnp.where(removed, div, NEG)), data_axis
                )
            )
            alive = alive & ~removed
            trace = trace.at[rnd].set(
                jax.lax.psum(jnp.sum(alive), data_axis).astype(jnp.int32)
            )
            return (alive, vprime, div, eps, k, rnd + 1, trace)

        carry = (
            alive_loc,
            jnp.zeros((n_loc,), bool),
            jnp.full((n_loc,), INF),
            jnp.float32(NEG),
            key_loc,
            jnp.int32(0),
            jnp.full((rounds_cap,), -1, jnp.int32),
        )
        alive, vprime, div, eps, _, rnd, trace = jax.lax.while_loop(
            cond, body, carry
        )
        vprime = vprime | alive
        eps = jnp.maximum(eps, 0.0)
        if pod_axis:
            return vprime, div, eps[None], rnd[None], trace[None]
        return vprime, div, eps, rnd, trace

    scalar_spec = P(pod_axis) if pod_axis else P()
    trace_spec = P(pod_axis, None) if pod_axis else P()
    state_in = state if has_state else jnp.zeros((1,), jnp.float32)
    fn_sm = jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=(keys_spec, mask_spec, P()) + specs,
        out_specs=(mask_spec, mask_spec, scalar_spec, scalar_spec, trace_spec),
        check_vma=False,
    )
    vprime, div, eps, rounds, trace = fn_sm(keys, alive0, state_in, *arrays)
    eps_hat = jnp.max(eps)
    rounds_out = jnp.max(rounds)
    if pod_axis:
        # Pods run independent loops of (possibly) different length — a single
        # global live-count trace is not well defined, so mark unrecorded.
        trace_out = jnp.full((rounds_cap,), -1, jnp.int32)
    else:
        trace_out = trace
    return SSResult(vprime, div, eps_hat, rounds_out, trace_out)


def stochastic_greedy_sharded(
    fn,                        # SubmodularFunction or legacy (n, F) array
    k: int,
    key: Array,
    mesh: Mesh,
    *,
    s: int | None = None,
    alive: Array | None = None,
    state: Array | None = None,
    compact: "bool | int | None" = None,
    data_axis: str = "data",
    c: float = 8.0,
    eps: float = 0.1,
    phi: str = "sqrt",
) -> GreedyResult:
    """Distributed stochastic greedy [Mirzasoleiman et al.] over the mesh —
    the selection-stage counterpart of :func:`ss_sparsify_sharded`.

    The sampler works in the same *frame* the dense path
    (:mod:`repro.core.greedy`) would pick for the same inputs, so the two are
    selection-for-selection identical under the same key in every case: when
    the live count fits a sub-n bucket (and ``compact`` is not False), the
    compact frame — candidates addressed by their rank among the
    initially-alive set, gathered once per shard into a static bucket-sized
    local buffer; otherwise the ground frame — candidates addressed by ground
    index, matching the dense full-width loop.  Each step:

    1. every shard draws the **identical** (B,)-sized Gumbel vector (the key
       is replicated and never folded with the shard id — this is what makes
       the sharded sampler selection-for-selection identical to the dense
       path under the same key) and computes the replicated top-s sample
       mask;
    2. each shard evaluates gains for its own sampled candidates only, via
       ``shard_take`` + ``shard_gains`` on the replicated summary state —
       compact per-shard work, embarrassingly parallel;
    3. the winner is a psum'd argmax: ``pmax`` of per-shard best gains, ties
       broken to the lowest compact position via ``pmin`` (matching the dense
       argmax tie order), and the replicated state advances by a one-hot
       ``psum`` of the winning shard's ``shard_add``.

    ``alive`` must be a *concrete* mask (the live count sizes the static
    buffers).  ``s=None`` derives the sample size from the live count.
    Requires the objective's ``supports_shard_greedy`` hooks.
    """
    return _select_sharded(
        fn, k, key, mesh, s=s, alive=alive, state=state, compact=compact,
        data_axis=data_axis, c=c, eps=eps, phi=phi, exact=False,
    )


def greedy_sharded(
    fn,                        # SubmodularFunction or legacy (n, F) array
    k: int,
    mesh: Mesh,
    *,
    alive: Array | None = None,
    state: Array | None = None,
    compact: "bool | int | None" = None,
    data_axis: str = "data",
    c: float = 8.0,
    phi: str = "sqrt",
) -> GreedyResult:
    """Distributed *exact* greedy over the mesh: the same compact frame and
    psum'd argmax as :func:`stochastic_greedy_sharded`, with every available
    candidate considered each step (no sampling, no PRNG key) — so
    ``greedy(backend="sharded")`` no longer evaluates gains on one process.

    Each step every shard evaluates gains for its own candidates on the
    replicated summary state (``shard_take`` + ``shard_gains``), the winner
    is the ``pmax`` of per-shard best gains (ties to the lowest frame
    position via ``pmin`` — the dense argmax tie order), and the replicated
    state advances by a one-hot ``psum`` of the winning shard's
    ``shard_add``.  Deterministic, and *selection-identical* to the dense
    ``greedy`` on the same inputs (pinned in tests/test_distributed.py).

    ``alive`` must be a concrete mask (the live count sizes the static
    buffers); requires the objective's ``supports_shard_greedy`` hooks.
    """
    return _select_sharded(
        fn, k, None, mesh, s=None, alive=alive, state=state, compact=compact,
        data_axis=data_axis, c=c, eps=0.1, phi=phi, exact=True,
    )


def _select_sharded(
    fn,
    k: int,
    key: Array | None,
    mesh: Mesh,
    *,
    s: int | None,
    alive: Array | None,
    state: Array | None,
    compact: "bool | int | None",
    data_axis: str,
    c: float,
    eps: float,
    phi: str,
    exact: bool,
) -> GreedyResult:
    """Shared distributed selection loop: exact greedy (``exact=True`` —
    every available candidate is a sample) and Gumbel-top-s stochastic
    greedy ride the identical frame/gains/argmax collectives."""
    fn = _as_objective(fn, phi)
    if not fn.supports_shard_greedy:
        raise NotImplementedError(
            f"{type(fn).__name__} does not implement the sharded selection "
            "hooks (shard_gains / shard_add)"
        )
    n = fn.n
    ndata = mesh.shape[data_axis]
    assert n % ndata == 0, f"n={n} must divide {ndata} shards (pad rows)"
    n_loc = n // ndata

    alive0 = jnp.ones((n,), bool) if alive is None else jnp.asarray(alive)
    alive_host = np.asarray(alive0)
    live = int(alive_host.sum())
    # Frame selection mirrors the dense plan exactly: compact frame iff the
    # dense path would compact (alive is concrete here, so an int ``compact``
    # bound reduces to the auto decision).
    bucket = None if compact is False else selection_bucket(n, live, c)
    compact_frame = bucket is not None
    B = bucket if compact_frame else n
    if compact_frame:
        # Static per-shard buffer: smallest fine-grained bucket holding every
        # shard's local live count (jnp gains need no tile alignment — tile=8
        # matches the sharded SS loop's compaction).
        loc_max = int(alive_host.reshape(ndata, n_loc).sum(axis=1).max())
        loc_fits = [
            b for b in bucket_schedule(n_loc, c, tile=8) if b >= loc_max
        ]
        loc_size = min(loc_fits) if loc_fits else n_loc
    else:
        loc_size = n_loc
    if exact:
        s = B
    elif s is None:
        s = auto_sample_size(n, k, eps, live=live)
    s = max(1, int(min(s, B)))
    state0 = fn.empty_state() if state is None else state

    arrays, specs, rebuild = fn.shard_pack((data_axis,))
    arrays = tuple(
        jax.device_put(a, NamedSharding(mesh, sp)) for a, sp in zip(arrays, specs)
    )
    mask_spec = P(data_axis)
    alive0 = jax.device_put(alive0, NamedSharding(mesh, mask_spec))
    BIG = jnp.int32(2**30)

    def kernel(alive_loc: Array, st0, *arrs):
        fn_loc = rebuild(*arrs)
        didx = jax.lax.axis_index(data_axis)
        if compact_frame:
            cnt = jnp.sum(alive_loc)
            counts = jax.lax.all_gather(cnt, data_axis)          # (S,)
            offset = jnp.sum(jnp.where(jnp.arange(ndata) < didx, counts, 0))
            # Local candidates and their global compact-frame positions:
            # shards own contiguous ground ranges, so ascending (shard, slot)
            # order is ascending ground order — position = alive-rank =
            # offset + slot.
            lidx = jnp.where(alive_loc, size=loc_size, fill_value=0)[0]
            lvalid = jnp.arange(loc_size) < cnt
            pos = (offset + jnp.arange(loc_size)).astype(jnp.int32)
            view = fn_loc.shard_take(lidx)
            avail0 = jnp.arange(B) < jax.lax.psum(cnt, data_axis)
        else:
            # Ground frame (the dense full-width loop's addressing): every
            # local slot is a candidate; dead slots are masked by the
            # replicated availability mask, exactly like the dense path.
            lidx = jnp.arange(loc_size)
            lvalid = jnp.ones((loc_size,), bool)
            pos = (didx * n_loc + jnp.arange(loc_size)).astype(jnp.int32)
            view = fn_loc
            avail0 = jax.lax.all_gather(alive_loc, data_axis).reshape(-1)
        pos_c = jnp.minimum(pos, B - 1)                          # safe gather
        ctx = fn_loc.shard_init(data_axis)

        def step(carry, key_i):
            st, avail = carry
            if exact:
                # Exact greedy: every available candidate is "sampled".
                sub = avail
            else:
                # (1) replicated Gumbel top-s over the compact frame.
                gumb = (
                    jax.random.gumbel(key_i, (B,))
                    + jnp.where(avail, 0.0, NEG)
                )
                cand = jax.lax.top_k(gumb, s)[1]
                sub = jnp.zeros((B,), bool).at[cand].set(True) & avail
            # (2) compact per-shard gains on the replicated state.
            g_loc = view.shard_gains(st, ctx)                    # (loc_size,)
            sub_loc = sub[pos_c] & lvalid
            g = jnp.where(sub_loc, g_loc, NEG)
            i_loc = jnp.argmax(g)
            gbest = g[i_loc]
            # (3) psum'd argmax: max gain, ties to the lowest position.
            gmax = jax.lax.pmax(gbest, data_axis)
            ok = gmax > NEG * 0.5
            pos_best = jnp.where(gbest >= gmax, pos[i_loc], BIG)
            pos_win = jax.lax.pmin(pos_best, data_axis)
            win = ok & (gbest >= gmax) & (pos[i_loc] == pos_win)
            ground = didx.astype(jnp.int32) * n_loc + lidx[i_loc]
            v = jax.lax.psum(jnp.where(win, ground, 0), data_axis)
            cand_state = fn_loc.shard_add(st, lidx[i_loc], ctx)
            summed = jax.tree.map(
                lambda x: jax.lax.psum(
                    jnp.where(win, x, jnp.zeros_like(x)), data_axis
                ),
                cand_state,
            )
            new_state = jax.tree.map(
                lambda sm, old: jnp.where(ok, sm, old), summed, st
            )
            avail = avail.at[jnp.where(ok, pos_win, B)].set(False, mode="drop")
            return (new_state, avail), (
                v.astype(jnp.int32), jnp.where(ok, gmax, 0.0),
            )

        xs = jnp.zeros((k, 2), jnp.uint32) if exact else jax.random.split(key, k)
        (st_f, _), (sel, gains) = jax.lax.scan(step, (st0, avail0), xs)
        return sel, gains, st_f

    fn_sm = jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=(mask_spec, P()) + specs,
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    sel, gains, st_f = fn_sm(alive0, state0, *arrays)
    return GreedyResult(sel, gains, fn.value(st_f), st_f)


def summarize_sharded(
    fn,                        # SubmodularFunction or legacy (n, F) array
    k: int,
    key: Array,
    mesh: Mesh,
    *,
    data_axis: str = "data",
    pod_axis: str | None = None,
    r: int = 8,
    c: float = 8.0,
    phi: str = "sqrt",
    bins: int = 512,
):
    """End-to-end distributed pipeline: sharded SS -> greedy on the union V'.

    The greedy stage sees only |V'| = O(log² n) live candidates — it runs on
    the full (replicated) objective like the paper's final stage.  Returns
    (selected (k,) indices into the original ground set, f(S), vprime mask,
    eps_hat certificate).
    """
    fn = _as_objective(fn, phi)
    ss = ss_sparsify_sharded(
        fn, key, mesh,
        data_axis=data_axis, pod_axis=pod_axis, r=r, c=c, bins=bins,
    )
    res = greedy(fn, k, alive=jnp.asarray(ss.vprime))
    return res.selected, res.value, ss.vprime, ss.eps_hat
