"""Submodular objective functions with batched, TPU-friendly marginal-gain APIs.

Every objective subclasses :class:`SubmodularFunction`, a formal abstract base
built around a compact *state* that summarizes the current solution set ``S``
so that marginal gains ``f(v|S)`` for **all** candidates ``v`` are computed in
one dense, matmul-shaped operation (no per-element Python loops — the TPU
adaptation of the paper's per-pair function evaluations, see DESIGN.md §3):

- ``empty_state()``             -> state for S = ∅
- ``value(state)``              -> f(S)
- ``gains(state)``              -> (n,) vector of f(v|S) for every v in V
- ``add(state, v)``             -> state for S + v          (rank-1 update)
- ``add_many(state, mask)``     -> state for S + {v : mask[v]}
- ``pairwise_gains(probes, state)`` -> (r, n) matrix of f(v | S + u) for u in probes
- ``residual_gains()``          -> (n,) vector of f(v | V \\ v)
- ``singleton_gains()``         -> (n,) vector of f(v)  ( = gains(empty_state()) )

``pairwise_gains`` + ``residual_gains`` are exactly the ingredients of the
submodularity-graph edge weight  w_{u->v} = f(v|u) - f(u|V\\u)  (paper Eq. 3) and
its conditional version w_{uv|S} (paper Eq. 4).

Beyond the core protocol, the base class defines two groups of *optional*
execution hooks consumed by :mod:`repro.core.backend` (see docs/backends.md):

- **Pallas hooks** (``pallas_divergence`` / ``pallas_gains``) let an objective
  provide a fused-kernel implementation of the SS hot spots; returning ``None``
  (the default) makes the pallas backend fall back to the jnp oracle.
- **Shard hooks** (``shard_pack`` / ``local_n`` / ``shard_init`` /
  ``shard_residuals`` / ``shard_payloads`` / ``shard_payload_gains``) describe
  a per-shard *function view*: how the objective's arrays are partitioned over
  a mesh and how each device computes residuals and probe-conditioned gains for
  its local slice of the ground set.  Any objective implementing them runs
  under the sharded SS loop in :mod:`repro.core.distributed` unchanged.

Implemented objectives:

- :class:`FeatureCoverage` — the paper's experimental objective
  ``f(S) = sum_feat phi(c_feat(S))`` with ``c_feat(S) = sum_{v in S} W[v,feat]``
  and a concave ``phi`` (sqrt by default).  With ``phi="setcover"`` this is
  weighted set cover; with ``phi="satcov"`` it is saturated coverage
  ``min(c, alpha * c_total)``.
- :class:`FacilityLocation` — ``f(S) = sum_i max_{s in S} sim(i, s)``.
- :class:`StreamingFacilityLocation` — the same objective, matrix-free: it
  stores only the (n, d) embedding rows and computes similarity tiles
  ``relu(X_blk @ X_blkᵀ)`` on the fly inside every reduction
  (:mod:`repro.kernels.fl_stream`), so no path ever materializes ``(n, n)``.
  This is the objective for 64k+ ground sets where dense
  ``FacilityLocation.from_features`` cannot even allocate its sim matrix.

All classes are registered pytrees, so they can be passed through jit/shard_map
boundaries; static (non-array) config lives in the pytree aux data.
"""

from __future__ import annotations

import abc
import dataclasses
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Array = jax.Array

# Large-but-finite negative used to mask out dead candidates in argmax/min ops.
# (Using -inf can poison min/where chains under fast-math; this is safer.)
NEG = -1e30


def _map_pairwise_rows(fn, probes, cand_idx, state, row_call):
    """``lax.map`` a per-row probe-gains computation over a *stacked*
    objective — one row in flight at a time, so peak memory matches the
    sequential path.  ``row_call(fn_row, probes_row, cand_row|None,
    state_row|None)`` does the per-row work; None-valued cand_idx/state are
    threaded as None rather than mapped."""
    def row(args):
        fn_b, rest = args[0], args[1:]
        ci = rest[1] if cand_idx is not None else None
        st = rest[-1] if state is not None else None
        return row_call(fn_b, rest[0], ci, st)

    xs: tuple = (fn, probes)
    if cand_idx is not None:
        xs = xs + (cand_idx,)
    if state is not None:
        xs = xs + (state,)
    return jax.lax.map(row, xs)


def _phi(kind: str, c: Array, cap: Array | None) -> Array:
    """Concave scalar transforms phi(c), applied elementwise to coverage."""
    if kind == "sqrt":
        return jnp.sqrt(jnp.maximum(c, 0.0))
    if kind == "log1p":
        return jnp.log1p(jnp.maximum(c, 0.0))
    if kind == "setcover":
        return jnp.minimum(c, 1.0)
    if kind == "satcov":
        assert cap is not None
        return jnp.minimum(c, cap)
    if kind == "linear":  # modular (for testing: submodular with equality)
        return c
    raise ValueError(f"unknown concave transform {kind!r}")


class SubmodularFunction(abc.ABC):
    """Abstract base for monotone submodular objectives over n ground elements.

    Subclasses must be registered pytrees (array leaves, static config in aux)
    so instances cross jit / shard_map boundaries.  The abstract core protocol
    is what every algorithm in :mod:`repro.core` consumes; the ``pallas_*`` and
    ``shard_*`` hooks are optional capability extensions used by the execution
    backends in :mod:`repro.core.backend`.
    """

    # -- core protocol (required) ------------------------------------------
    @property
    @abc.abstractmethod
    def n(self) -> int:
        """Ground-set size."""

    @abc.abstractmethod
    def empty_state(self) -> Any:
        """Summary state for S = ∅."""

    @abc.abstractmethod
    def value(self, state: Any) -> Array:
        """f(S) from the summary state."""

    @abc.abstractmethod
    def gains(self, state: Any) -> Array:
        """f(v|S) for all v.  Shape (n,)."""

    @abc.abstractmethod
    def add(self, state: Any, v: Array) -> Any:
        """State for S + v (rank-1 update)."""

    @abc.abstractmethod
    def add_many(self, state: Any, mask: Array) -> Any:
        """State for S + {v : mask[v]}."""

    @abc.abstractmethod
    def pairwise_gains(self, probes: Array, state: Any | None = None) -> Array:
        """f(v | S + u) for u in probes (r,), all v.  Shape (r, n)."""

    @abc.abstractmethod
    def residual_gains(self) -> Array:
        """f(v | V \\ v) for all v.  Shape (n,)."""

    def singleton_gains(self) -> Array:
        """f(v) for all v ( = gains on the empty state)."""
        return self.gains(self.empty_state())

    # -- compaction (optional override, always correct) --------------------
    # The SS loop's live set shrinks geometrically; the compacted execution
    # path (see repro.core.sparsify) evaluates probe-conditioned gains only
    # for a gathered buffer of surviving candidates.  The base implementation
    # computes the full (r, n) block and gathers — correct for any objective;
    # override it to actually skip the dead-candidate work (both shipped
    # objectives do).

    def pairwise_gains_compact(
        self, probes: Array, cand_idx: Array, state: Any | None = None
    ) -> Array:
        """f(v | S + u) for u in probes (r,) and v = cand_idx (k,).  (r, k).

        ``cand_idx`` holds ground indices of the compacted candidate buffer
        (padding entries may repeat a valid index; callers mask them out).
        """
        return jnp.take(self.pairwise_gains(probes, state), cand_idx, axis=1)

    def gains_compact(self, state: Any, cand_idx: Array) -> Array:
        """f(v|S) for v = cand_idx (k,).  Shape (k,).

        The selection-engine analogue of ``pairwise_gains_compact``: greedy's
        per-step gains restricted to the compacted candidate buffer (ground
        indices; padding entries may repeat a valid index — callers mask).
        The base implementation is a full-width compute + gather — always
        correct; override it so per-step cost scales with k, not n (both
        shipped objectives do)."""
        return jnp.take(self.gains(state), cand_idx)

    # -- micro-batching (optional override, always correct) ----------------
    # The serving engine (repro.serve.summarize_service) runs B independent
    # queries of identical shape as one *stacked* objective: the same pytree
    # class with a leading batch axis on every array leaf.  A stacked
    # instance is NOT a valid single objective (``n`` etc. read the wrong
    # axis); only the ``*_batched`` hooks below may be called on it.  The
    # base implementations map the per-row compact hooks over the batch with
    # ``lax.map`` — one row in flight at a time, so peak memory matches the
    # sequential path — and are therefore always correct for any objective.
    # Both shipped objectives override with probe-chunked row computations
    # that stay cache-resident (never materializing the (r, k, F) block),
    # which is what makes the batched engine faster than a sequential loop
    # of per-query calls on every platform.

    def pairwise_gains_batched(
        self, probes: Array, cand_idx: Array | None, state: Any | None = None
    ) -> Array:
        """f(v | S_b + u) per batch row b, probes u (B, r), candidates
        v = cand_idx (B, k) (or the full ground set when None).  (B, r, k).

        ``self`` is a stacked objective.  Row semantics are exactly
        ``pairwise_gains_compact(probes[b], cand_idx[b], state[b])``."""
        return _map_pairwise_rows(
            self, probes, cand_idx, state,
            lambda f, p, ci, st: (
                f.pairwise_gains(p, st) if ci is None
                else f.pairwise_gains_compact(p, ci, st)
            ),
        )

    def gains_batched(self, state: Any, cand_idx: Array | None) -> Array:
        """f(v|S_b) per batch row b for v = cand_idx (B, k) (full ground set
        when None).  Shape (B, k).  ``self`` is a stacked objective; row
        semantics are exactly ``gains_compact(state[b], cand_idx[b])``."""
        if cand_idx is None:
            return jax.vmap(lambda f, s: f.gains(s))(self, state)
        return jax.vmap(lambda f, s, ci: f.gains_compact(s, ci))(
            self, state, cand_idx
        )

    # -- pallas hooks ------------------------------------------------------
    # Returning None means "no fused kernel for this configuration"; the
    # pallas backend then raises NotImplementedError naming the objective and
    # the primitive (it never drops to the jnp oracle).  ``interpret`` selects
    # Pallas interpret mode (CPU correctness path) vs. the compiled TPU kernel.

    def pallas_divergence(
        self,
        probes: Array,
        residual: Array,
        state: Any | None = None,
        probe_mask: Array | None = None,
        *,
        interpret: bool,
        cand_idx: Array | None = None,
        **block_kw,
    ) -> Array | None:
        """Fused divergence w_{U,v} (paper Def. 2) for all v, or None.

        With ``cand_idx`` (k,) the output is restricted to the compacted
        candidate buffer — shape (k,) instead of (n,) — and the kernel grid
        should only cover the gathered candidates.  Returning None (for any
        ``cand_idx``) makes the pallas backend raise."""
        return None

    def pallas_gains(
        self,
        state: Any,
        *,
        interpret: bool,
        cand_idx: Array | None = None,
        **block_kw,
    ) -> Array | None:
        """Fused greedy gains f(v|S) for all v, or None.

        With ``cand_idx`` (k,) the output is restricted to the compacted
        candidate buffer — shape (k,) — and the kernel grid should only
        cover the gathered candidates.  Returning None (for any ``cand_idx``)
        makes the pallas backend raise."""
        return None

    # -- shard hooks (optional) --------------------------------------------
    # Together these define a per-shard *function view*: `shard_pack` says how
    # the objective's arrays are laid out over the mesh; the remaining hooks
    # are called *inside* shard_map on the rebuilt local view, where array
    # leaves hold only this device's slice of the ground set.

    #: whether per-pod hierarchical sharding (a standalone ground set per pod)
    #: is supported — requires the objective's arrays to be row-local.
    supports_pod_sharding: bool = False

    #: whether the local view supports candidate restriction via
    #: :meth:`shard_take` — required for the sharded loop's live-set
    #: compaction (the loop silently runs uncompacted otherwise).
    supports_shard_compact: bool = False

    #: whether the local view supports the sharded *selection* stage
    #: (:func:`repro.core.distributed.stochastic_greedy_sharded`) — requires
    #: :meth:`shard_gains` / :meth:`shard_add` over a *replicated* summary
    #: state, plus :meth:`shard_take`.
    supports_shard_greedy: bool = False

    def shard_pack(
        self, axes: Sequence[str]
    ) -> tuple[tuple[Array, ...], tuple[P, ...], Callable[..., "SubmodularFunction"]]:
        """(arrays, partition specs, rebuild) for entering shard_map.

        ``arrays`` are the objective's array leaves, ``specs`` their
        PartitionSpecs over mesh ``axes`` (candidate dimension sharded), and
        ``rebuild(*local_arrays)`` reconstructs the local function view inside
        the shard_map body.
        """
        raise NotImplementedError(
            f"{type(self).__name__} does not implement the sharded protocol"
        )

    def local_n(self) -> int:
        """Number of *local* candidates held by this shard view."""
        raise NotImplementedError

    def shard_init(self, axis: str) -> Any:
        """One-time collective setup: pod-global context (psum/all_gather over
        ``axis``) reused by shard_residuals / shard_payload_gains."""
        raise NotImplementedError

    def shard_residuals(self, ctx: Any) -> Array:
        """f(u | V \\ u) for the local candidates.  Shape (n_local,)."""
        raise NotImplementedError

    def shard_payloads(self, idx: Array, state: Any | None = None) -> Array:
        """Payload rows for local candidate indices ``idx`` (k,) — a compact
        description of each probe sufficient for any shard to evaluate
        probe-conditioned gains.  Shape (k, payload_dim).

        ``state`` (a *replicated* summary state, or None for S = ∅) folds the
        conditional context into the payload, so ``shard_payload_gains`` on
        a state-conditioned payload evaluates f(v | S + u) — the sharded
        analogue of ``pairwise_gains(probes, state)``."""
        raise NotImplementedError

    def shard_payload_gains(self, payloads: Array, ctx: Any) -> Array:
        """f(v | S + u) for gathered probe ``payloads`` (m, payload_dim) and
        all local candidates v, where S is whatever state the payloads were
        built with (∅ by default).  Shape (m, n_local)."""
        raise NotImplementedError

    def shard_take(self, cand_idx: Array) -> "SubmodularFunction":
        """Local view restricted to the local candidate subset ``cand_idx``
        (k,) — ``shard_payload_gains`` on the returned view must produce the
        (m, k) gather of the full view's (m, n_local) output.  Must be
        collective-free (it runs inside data-dependent ``lax.switch``
        branches).  Only required when ``supports_shard_compact``."""
        raise NotImplementedError

    def shard_gains(self, state: Any, ctx: Any) -> Array:
        """f(v|S) for the local candidates, from a *replicated* summary state.

        Must be elementwise identical arithmetic to the dense ``gains`` /
        ``gains_compact`` (the sharded selection loop asserts same-key
        selection parity against the dense compact path).  ``ctx`` is the
        ``shard_init`` context (pod-global quantities such as the satcov
        cap).  Shape (n_local,).  Only required when
        ``supports_shard_greedy``."""
        raise NotImplementedError

    def shard_add(self, state: Any, v: Array, ctx: Any) -> Any:
        """Replicated state for S + v, ``v`` a *local* candidate index.
        Must match the dense ``add`` on the corresponding ground index
        bitwise.  Only required when ``supports_shard_greedy``."""
        raise NotImplementedError


def _row_spec(axes: Sequence[str]) -> P:
    return P(tuple(axes) if len(axes) > 1 else axes[0], None)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class FeatureCoverage(SubmodularFunction):
    """Feature-based concave-over-modular coverage function (paper §4).

    f(S) = sum_f  w_f * phi( c_f(S) ),   c_f(S) = sum_{v in S} W[v, f]

    ``W`` is the (n, n_features) nonnegative affinity matrix (e.g. TFIDF).
    ``feat_w`` optionally weights features.  ``phi`` is one of
    {"sqrt", "log1p", "setcover", "satcov", "linear"}.

    The *state* is the coverage vector c in R^{n_features}.
    """

    W: Array                    # (n, F) nonnegative
    feat_w: Array | None = None  # (F,) or None
    phi: str = "sqrt"
    alpha: float = 0.2          # saturation fraction for phi="satcov"

    supports_pod_sharding = True
    supports_shard_compact = True
    supports_shard_greedy = True

    # -- pytree plumbing ---------------------------------------------------
    def tree_flatten(self):
        return (self.W, self.feat_w), (self.phi, self.alpha)

    @classmethod
    def tree_unflatten(cls, aux, children):
        W, feat_w = children
        phi, alpha = aux
        return cls(W=W, feat_w=feat_w, phi=phi, alpha=alpha)

    # -- protocol ----------------------------------------------------------
    @property
    def n(self) -> int:
        return self.W.shape[0]

    def _cap(self) -> Array | None:
        if self.phi != "satcov":
            return None
        return self.alpha * jnp.sum(self.W, axis=0)

    def _wsum(self, x: Array) -> Array:
        """Weighted sum over the trailing feature axis."""
        if self.feat_w is not None:
            x = x * self.feat_w
        return jnp.sum(x, axis=-1)

    def empty_state(self) -> Array:
        return jnp.zeros((self.W.shape[1],), dtype=self.W.dtype)

    def value(self, state: Array) -> Array:
        return self._wsum(_phi(self.phi, state, self._cap()))

    def gains(self, state: Array) -> Array:
        """f(v|S) for all v: sum_f [phi(c + W_v) - phi(c)].  Shape (n,)."""
        cap = self._cap()
        return self._wsum(
            _phi(self.phi, state[None, :] + self.W, cap)
            - _phi(self.phi, state[None, :], cap)
        )

    def add(self, state: Array, v: Array) -> Array:
        return state + self.W[v]

    def add_many(self, state: Array, mask: Array) -> Array:
        return state + mask.astype(self.W.dtype) @ self.W

    def pairwise_gains(self, probes: Array, state: Array | None = None) -> Array:
        """f(v | S + u) for u in probes (r,), all v.  Shape (r, n).

        This is the hot spot of submodular sparsification: an (r, n, F)
        computation reduced over F.  The Pallas kernel in
        ``repro.kernels.ss_weights`` fuses it with the edge-weight min; this
        jnp version is the oracle / CPU path.
        """
        base = self.empty_state() if state is None else state
        cap = self._cap()
        cu = base[None, :] + self.W[probes]                      # (r, F)
        phi_cu = self._wsum(_phi(self.phi, cu, cap))             # (r,)
        # (r, n, F) intermediate — fused away in the Pallas kernel.
        both = cu[:, None, :] + self.W[None, :, :]
        out = self._wsum(_phi(self.phi, both, cap)) - phi_cu[:, None]
        # Set semantics: f(u | S + u) = 0 (coverage state is a sum, so the
        # diagonal v == probe would otherwise double-count W[u]).
        v_eq_u = probes[:, None] == jnp.arange(self.n)[None, :]
        return jnp.where(v_eq_u, 0.0, out)

    def residual_gains(self) -> Array:
        """f(v | V \\ v) = sum_f [phi(C) - phi(C - W_v)] for all v.  Shape (n,)."""
        cap = self._cap()
        C = jnp.sum(self.W, axis=0)                              # (F,)
        return self._wsum(
            _phi(self.phi, C[None, :], cap)
            - _phi(self.phi, C[None, :] - self.W, cap)
        )

    def pairwise_gains_compact(
        self, probes: Array, cand_idx: Array, state: Array | None = None
    ) -> Array:
        """Compact (r, k, F) block — per-element identical arithmetic to the
        full ``pairwise_gains`` restricted to ``cand_idx``, so the compacted
        SS loop prunes bit-identically to the uncompacted one."""
        base = self.empty_state() if state is None else state
        cap = self._cap()
        cu = base[None, :] + self.W[probes]                      # (r, F)
        phi_cu = self._wsum(_phi(self.phi, cu, cap))             # (r,)
        Wc = jnp.take(self.W, cand_idx, axis=0)                  # (k, F)
        both = cu[:, None, :] + Wc[None, :, :]
        out = self._wsum(_phi(self.phi, both, cap)) - phi_cu[:, None]
        v_eq_u = probes[:, None] == cand_idx[None, :]
        return jnp.where(v_eq_u, 0.0, out)

    def gains_compact(self, state: Array, cand_idx: Array) -> Array:
        """Per-step greedy gains over the gathered candidate rows only —
        per-element identical arithmetic to ``gains`` restricted to
        ``cand_idx``, so compact and full selection pick identical sets."""
        cap = self._cap()
        Wc = jnp.take(self.W, cand_idx, axis=0)                  # (k, F)
        return self._wsum(
            _phi(self.phi, state[None, :] + Wc, cap)
            - _phi(self.phi, state[None, :], cap)
        )

    def _pairwise_gains_chunked(
        self,
        probes: Array,
        cand_idx: Array | None,
        state: Array | None = None,
        probe_chunk: int = 8,
    ) -> Array:
        """Probe-chunked row computation for the batched engine: identical
        per-element arithmetic to ``pairwise_gains_compact``, but the (r, k,
        F) block is never materialized — a ``lax.scan`` over probe chunks
        keeps each (chunk, k, F) slab cache-resident, which on CPU beats the
        full-block formulation severalfold at serving shapes."""
        base = self.empty_state() if state is None else state
        cap = self._cap()
        Wc = self.W if cand_idx is None else jnp.take(self.W, cand_idx, axis=0)
        cand = jnp.arange(self.W.shape[0]) if cand_idx is None else cand_idx
        cu = base[None, :] + self.W[probes]                      # (r, F)
        phi_cu = self._wsum(_phi(self.phi, cu, cap))             # (r,)
        r = probes.shape[0]
        rp = -(-r // probe_chunk) * probe_chunk
        pad = rp - r
        cu_p = jnp.concatenate([cu, jnp.repeat(cu[:1], pad, axis=0)])
        phicu_p = jnp.concatenate([phi_cu, jnp.repeat(phi_cu[:1], pad)])
        probes_p = jnp.concatenate([probes, jnp.repeat(probes[:1], pad)])

        def chunk(_, inp):
            cu_j, phicu_j, probes_j = inp
            both = cu_j[:, None, :] + Wc[None, :, :]             # (PC, k, F)
            out = self._wsum(_phi(self.phi, both, cap)) - phicu_j[:, None]
            v_eq_u = probes_j[:, None] == cand[None, :]
            return None, jnp.where(v_eq_u, 0.0, out)

        _, rows = jax.lax.scan(chunk, None, (
            cu_p.reshape(-1, probe_chunk, cu.shape[-1]),
            phicu_p.reshape(-1, probe_chunk),
            probes_p.reshape(-1, probe_chunk),
        ))
        return rows.reshape(rp, -1)[:r]

    def pairwise_gains_batched(
        self, probes: Array, cand_idx: Array | None, state: Array | None = None
    ) -> Array:
        """(B, r, k) batched block via the cache-blocked chunked rows."""
        return _map_pairwise_rows(
            self, probes, cand_idx, state,
            lambda f, p, ci, st: f._pairwise_gains_chunked(p, ci, st),
        )

    # -- pallas hooks ------------------------------------------------------
    def pallas_divergence(
        self,
        probes: Array,
        residual: Array,
        state: Array | None = None,
        probe_mask: Array | None = None,
        *,
        interpret: bool,
        cand_idx: Array | None = None,
        **block_kw,
    ) -> Array | None:
        from repro.kernels.ss_weights import ss_divergence_kernel

        base = self.empty_state() if state is None else state
        cap = self._cap()
        CU = base[None, :] + self.W[probes]                      # (r, F)
        # The kernel carries feat_w through the phi-reduction, so the probe
        # baseline must be the same weighted sum.
        phi_cu = self._wsum(_phi(self.phi, CU.astype(jnp.float32), cap))
        resid = residual[probes]
        if probe_mask is not None:
            # Masked probes use the kernel's pad-row convention: phi_cu = -INF
            # makes their edge weight +INF, so they never win the min.
            phi_cu = jnp.where(probe_mask, phi_cu, NEG)
            resid = jnp.where(probe_mask, resid, 0.0)
        return ss_divergence_kernel(
            self.W, CU, phi_cu, resid, cap, self.feat_w, cand_idx,
            phi=self.phi, interpret=interpret, **block_kw,
        )

    def pallas_gains(
        self,
        state: Array,
        *,
        interpret: bool,
        cand_idx: Array | None = None,
        **block_kw,
    ) -> Array | None:
        from repro.kernels.feature_gains import feature_gains_kernel

        cap = self._cap()
        phi_c = self._wsum(_phi(self.phi, state.astype(jnp.float32), cap))
        return feature_gains_kernel(
            self.W, state, phi_c, cap, self.feat_w, cand_idx,
            phi=self.phi, interpret=interpret, **block_kw,
        )

    # -- shard hooks (row-sharded: each device owns a block of W's rows) ----
    def shard_pack(self, axes):
        spec = _row_spec(axes)
        if self.feat_w is None:
            return (self.W,), (spec,), (
                lambda W_loc: dataclasses.replace(self, W=W_loc)
            )
        return (self.W, self.feat_w), (spec, P(None)), (
            lambda W_loc, fw: dataclasses.replace(self, W=W_loc, feat_w=fw)
        )

    def local_n(self) -> int:
        return self.W.shape[0]

    def shard_init(self, axis: str):
        # Pod-global coverage totals: everything downstream is local given C.
        C = jax.lax.psum(jnp.sum(self.W, axis=0), axis)          # (F,)
        cap = self.alpha * C if self.phi == "satcov" else None
        return (C, cap)

    def shard_residuals(self, ctx) -> Array:
        # Same arithmetic as the dense residual_gains: difference per feature,
        # then the weighted sum.  Subtracting the two O(F)-magnitude sums
        # instead loses ~1e-4 of an O(1) residual to f32 cancellation.
        C, cap = ctx
        return self._wsum(
            _phi(self.phi, C[None, :], cap)
            - _phi(self.phi, C[None, :] - self.W, cap)
        )

    def shard_payloads(self, idx: Array, state: Array | None = None) -> Array:
        # The payload *is* the probe's conditional coverage row c(S + u):
        # shard_payload_gains computes phi(payload + W_v) - phi(payload),
        # which is exactly f(v | S + u) — same arithmetic as the dense
        # pairwise_gains with a state.
        if state is None:
            return self.W[idx]                                   # (k, F)
        return state[None, :] + self.W[idx]

    def shard_payload_gains(self, payloads: Array, ctx) -> Array:
        _, cap = ctx
        phi_cu = self._wsum(_phi(self.phi, payloads, cap))       # (m,)
        both = payloads[:, None, :] + self.W[None, :, :]         # (m, nl, F)
        return self._wsum(_phi(self.phi, both, cap)) - phi_cu[:, None]

    def shard_take(self, cand_idx: Array) -> "FeatureCoverage":
        return dataclasses.replace(self, W=jnp.take(self.W, cand_idx, axis=0))

    def shard_gains(self, state: Array, ctx) -> Array:
        # Same expression as the dense gains, with the pod-global satcov cap
        # from ctx (the local W slice would under-saturate it).
        _, cap = ctx
        return self._wsum(
            _phi(self.phi, state[None, :] + self.W, cap)
            - _phi(self.phi, state[None, :], cap)
        )

    def shard_add(self, state: Array, v: Array, ctx) -> Array:
        return state + self.W[v]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class FacilityLocation(SubmodularFunction):
    """Facility location: f(S) = sum_i max(0, max_{s in S} sim[i, s]).

    ``sim`` is the (n, n) similarity matrix (assumed nonnegative for
    monotonicity; negative entries are clipped at 0 by the implicit "serve
    yourself at 0" baseline, which also normalizes f(∅)=0).

    The *state* is the per-row current best coverage m in R^n,
    m_i = max(0, max_{s in S} sim[i, s]).
    """

    sim: Array  # (n, n)

    supports_shard_compact = True
    supports_shard_greedy = True

    def tree_flatten(self):
        return (self.sim,), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(sim=children[0])

    #: from_features refuses to materialize (n, n) above this many rows
    #: unless explicitly overridden — 16k is already a 1 GiB f32 sim matrix.
    N_THRESHOLD = 16384

    @classmethod
    def from_features(
        cls,
        X: Array,
        kernel: str = "dot",
        *,
        n_threshold: int | None = N_THRESHOLD,
    ) -> "FacilityLocation":
        n = X.shape[0]
        if n_threshold is not None and n > n_threshold:
            raise ValueError(
                f"FacilityLocation.from_features would materialize an "
                f"(n, n) = ({n}, {n}) similarity matrix "
                f"({4 * n * n / 2**30:.1f} GiB of f32). For kernel="
                f"'dot'/'cosine' use the matrix-free equivalent instead:\n"
                f"    StreamingFacilityLocation.from_features(X, "
                f"kernel={kernel!r})\n"
                f"which stores only the (n, d) embeddings and computes "
                f"similarity tiles on the fly. Pass n_threshold=None to "
                f"force the dense construction anyway."
            )
        if kernel == "dot":
            sim = jnp.maximum(X @ X.T, 0.0)
        elif kernel == "rbf":
            d2 = (
                jnp.sum(X * X, axis=1)[:, None]
                - 2.0 * X @ X.T
                + jnp.sum(X * X, axis=1)[None, :]
            )
            sim = jnp.exp(-d2 / jnp.maximum(jnp.mean(d2), 1e-9))
        elif kernel == "cosine":
            Xn = X / jnp.maximum(jnp.linalg.norm(X, axis=1, keepdims=True), 1e-9)
            sim = jnp.maximum(Xn @ Xn.T, 0.0)
        else:
            raise ValueError(kernel)
        return cls(sim=sim)

    @property
    def n(self) -> int:
        return self.sim.shape[0]

    def empty_state(self) -> Array:
        return jnp.zeros((self.sim.shape[0],), dtype=self.sim.dtype)

    def value(self, state: Array) -> Array:
        return jnp.sum(state)

    def gains(self, state: Array) -> Array:
        # f(v|S) = sum_i max(sim[i, v] - m_i, 0) -> column reduction of (n, n)
        return jnp.sum(jnp.maximum(self.sim - state[:, None], 0.0), axis=0)

    def add(self, state: Array, v: Array) -> Array:
        return jnp.maximum(state, self.sim[:, v])

    def add_many(self, state: Array, mask: Array) -> Array:
        masked = jnp.where(mask[None, :], self.sim, NEG)
        return jnp.maximum(state, jnp.max(masked, axis=1))

    def pairwise_gains(self, probes: Array, state: Array | None = None) -> Array:
        base = self.empty_state() if state is None else state
        mu = jnp.maximum(base[None, :], self.sim[:, probes].T)   # (r, n) rows=probe cov
        # f(v | S+u) = sum_i max(sim[i, v] - mu[u, i], 0)
        return jnp.sum(
            jnp.maximum(self.sim.T[None, :, :] - mu[:, None, :], 0.0), axis=-1
        )

    def residual_gains(self) -> Array:
        # f(V) - f(V \ v) per v: only rows where v is the unique argmax lose,
        # dropping to the second-best. Use top-2 per row.
        top2 = jax.lax.top_k(self.sim, 2)[0]                     # (n, 2)
        best, second = top2[:, 0], top2[:, 1]
        is_best = self.sim >= best[:, None]                      # ties: no loss
        tie = jnp.sum(is_best, axis=1) > 1
        loss_per_row = jnp.where(tie, 0.0, jnp.maximum(best, 0.0) - jnp.maximum(second, 0.0))
        return jnp.sum(jnp.where(is_best, loss_per_row[:, None], 0.0), axis=0)

    def pairwise_gains_compact(
        self, probes: Array, cand_idx: Array, state: Array | None = None
    ) -> Array:
        """Compact hinge block: the served-row reduction still spans all n
        rows (that is f's definition); only the candidate axis is gathered."""
        base = self.empty_state() if state is None else state
        mu = jnp.maximum(base[None, :], self.sim[:, probes].T)   # (r, n)
        simc = jnp.take(self.sim, cand_idx, axis=1)              # (n, k)
        return jnp.sum(
            jnp.maximum(simc.T[None, :, :] - mu[:, None, :], 0.0), axis=-1
        )

    def gains_compact(self, state: Array, cand_idx: Array) -> Array:
        """f(v|S) over the gathered candidate columns only (the served-row
        reduction still spans all n rows — that is f's definition)."""
        simc = jnp.take(self.sim, cand_idx, axis=1)              # (n, k)
        return jnp.sum(jnp.maximum(simc - state[:, None], 0.0), axis=0)

    def _pairwise_gains_chunked(
        self,
        probes: Array,
        cand_idx: Array | None,
        state: Array | None = None,
        probe_chunk: int = 8,
    ) -> Array:
        """Probe-chunked row computation for the batched engine — identical
        per-element hinge arithmetic to ``pairwise_gains_compact``, with the
        (r, k, n) block replaced by cache-resident (chunk, k, n) slabs."""
        base = self.empty_state() if state is None else state
        mu = jnp.maximum(base[None, :], self.sim[:, probes].T)   # (r, n)
        simc = (self.sim if cand_idx is None
                else jnp.take(self.sim, cand_idx, axis=1))       # (n, k)
        r = probes.shape[0]
        rp = -(-r // probe_chunk) * probe_chunk
        mu_p = jnp.concatenate([mu, jnp.repeat(mu[:1], rp - r, axis=0)])

        def chunk(_, mu_j):
            out = jnp.sum(
                jnp.maximum(simc.T[None, :, :] - mu_j[:, None, :], 0.0),
                axis=-1,
            )
            return None, out                                     # (PC, k)

        _, rows = jax.lax.scan(
            chunk, None, mu_p.reshape(-1, probe_chunk, mu.shape[-1])
        )
        return rows.reshape(rp, -1)[:r]

    def pairwise_gains_batched(
        self, probes: Array, cand_idx: Array | None, state: Array | None = None
    ) -> Array:
        """(B, r, k) batched block via the cache-blocked chunked rows."""
        return _map_pairwise_rows(
            self, probes, cand_idx, state,
            lambda f, p, ci, st: f._pairwise_gains_chunked(p, ci, st),
        )

    # -- pallas hooks ------------------------------------------------------
    def pallas_divergence(
        self,
        probes: Array,
        residual: Array,
        state: Array | None = None,
        probe_mask: Array | None = None,
        *,
        interpret: bool,
        cand_idx: Array | None = None,
        **block_kw,
    ) -> Array | None:
        from repro.kernels.fl_divergence import fl_divergence_kernel

        base = self.empty_state() if state is None else state
        MU = jnp.maximum(base[None, :], self.sim[:, probes].T)   # (r, n)
        resid = residual[probes]
        if probe_mask is not None:
            # Kernel pad-row convention: resid = -INF makes the edge weight
            # +INF, so masked probes never win the min.
            resid = jnp.where(probe_mask, resid, NEG)
        return fl_divergence_kernel(
            self.sim, MU, resid, cand_idx, interpret=interpret, **block_kw
        )

    def pallas_gains(
        self,
        state: Array,
        *,
        interpret: bool,
        cand_idx: Array | None = None,
        **block_kw,
    ) -> Array | None:
        from repro.kernels.fl_divergence import fl_gains_kernel

        return fl_gains_kernel(
            self.sim, state, cand_idx, interpret=interpret, **block_kw
        )

    # -- shard hooks (column-sharded: each device owns a block of candidate
    # columns, with the full set of served rows) ---------------------------
    # A probe's payload is its n-dim coverage column, so any shard can
    # evaluate f(v | ∅ + u) against it locally.  Pod hierarchy would need
    # row-local views too, hence supports_pod_sharding = False.

    def shard_pack(self, axes):
        if len(axes) > 1:
            raise NotImplementedError(
                "FacilityLocation shards candidates only (no pod hierarchy): "
                "its served rows span the full ground set"
            )
        return (self.sim,), (P(None, axes[0]),), (
            lambda sim_loc: dataclasses.replace(self, sim=sim_loc)
        )

    def local_n(self) -> int:
        return self.sim.shape[1]

    def shard_init(self, axis: str):
        # Global per-row top-2 similarities (for residuals): gather each
        # shard's local top-2 and reduce.
        k2 = min(2, self.sim.shape[1])
        loc_top = jax.lax.top_k(self.sim, k2)[0]                 # (n, k2)
        allt = jax.lax.all_gather(loc_top, axis)                 # (S, n, k2)
        allt = jnp.moveaxis(allt, 0, 1).reshape(self.sim.shape[0], -1)
        pad = jnp.full((self.sim.shape[0], 2), NEG, allt.dtype)
        top2 = jax.lax.top_k(jnp.concatenate([allt, pad], axis=1), 2)[0]
        best, second = top2[:, 0], top2[:, 1]
        # ties: number of global columns achieving the per-row max
        cnt = jax.lax.psum(
            jnp.sum(self.sim >= best[:, None], axis=1), axis
        )
        loss = jnp.where(
            cnt > 1, 0.0, jnp.maximum(best, 0.0) - jnp.maximum(second, 0.0)
        )
        return (best, loss)

    def shard_residuals(self, ctx) -> Array:
        best, loss = ctx
        is_best = self.sim >= best[:, None]                      # (n, n_loc)
        return jnp.sum(jnp.where(is_best, loss[:, None], 0.0), axis=0)

    def shard_payloads(self, idx: Array, state: Array | None = None) -> Array:
        # Probe coverage columns mu_u = max(state, sim[:, u]) — (k, n); with
        # S = ∅ the baseline is the implicit serve-yourself-at-0 coverage.
        base = jnp.zeros((self.sim.shape[0],)) if state is None else state
        return jnp.maximum(base[None, :], self.sim[:, idx].T)

    def shard_payload_gains(self, payloads: Array, ctx) -> Array:
        # f(v | ∅+u) = sum_i max(sim[i, v] - mu[u, i], 0) for local columns v.
        return jnp.sum(
            jnp.maximum(self.sim.T[None, :, :] - payloads[:, None, :], 0.0),
            axis=-1,
        )

    def shard_take(self, cand_idx: Array) -> "FacilityLocation":
        # Candidates are columns; the served rows stay whole.
        return dataclasses.replace(
            self, sim=jnp.take(self.sim, cand_idx, axis=1)
        )

    def shard_gains(self, state: Array, ctx) -> Array:
        # The replicated state is the (n,) served-row coverage; the local sim
        # slice holds this shard's candidate columns over all served rows, so
        # this is exactly the dense gains reduction on the local columns.
        return jnp.sum(jnp.maximum(self.sim - state[:, None], 0.0), axis=0)

    def shard_add(self, state: Array, v: Array, ctx) -> Array:
        return jnp.maximum(state, self.sim[:, v])


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class StreamingFacilityLocation(SubmodularFunction):
    """Matrix-free facility location over embedding rows (ISSUE 6 tentpole).

    Same objective as :class:`FacilityLocation` with the "dot" kernel —
    ``sim[i, v] = max(x_i . x_v, 0)`` — but the (n, n) similarity matrix is
    *never* materialized: only the ``(n, d)`` feature rows are stored, and
    every reduction streams similarity tiles ``relu(X_blk @ X_blkᵀ)`` through
    the block primitives in :mod:`repro.kernels.fl_stream` (lax.scan block
    references on the oracle path, fused flash-style kernels on the pallas
    path).  The cosine kernel is dot after one-time row normalization, so it
    shares the same machinery.

    ``X`` holds the *candidate* rows.  ``Xs`` (None for the global objective,
    where served == candidates) holds the *served* rows and exists so the
    sharded local views — candidate rows sharded, served rows replicated —
    and compacted views keep serving the full ground set while restricting
    the candidate axis.  The *state* is the served-row coverage
    ``m_i = max(0, max_{s in S} sim[i, s])``, exactly the dense state.

    Parity contract: for the same features this objective matches dense
    ``FacilityLocation.from_features(X, kernel="dot"|"cosine")`` on every
    primitive up to f32 tile-summation order (block partial sums vs. one
    full-width reduction), which is inside the repo's 1e-4 parity tolerance.
    """

    X: Array                 # (n, d) candidate embedding rows
    Xs: Array | None = None  # (ni, d) served rows; None = X (global objective)

    supports_pod_sharding = False
    supports_shard_compact = True
    supports_shard_greedy = True

    def tree_flatten(self):
        return (self.X, self.Xs), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(X=children[0], Xs=children[1])

    @classmethod
    def from_features(
        cls, X: Array, kernel: str = "dot"
    ) -> "StreamingFacilityLocation":
        X = jnp.asarray(X, jnp.float32)
        if kernel == "dot":
            pass
        elif kernel == "cosine":
            # Identical normalization to the dense cosine path, done once;
            # afterwards cosine *is* dot.
            X = X / jnp.maximum(
                jnp.linalg.norm(X, axis=1, keepdims=True), 1e-9
            )
        else:
            raise ValueError(
                f"StreamingFacilityLocation supports kernel='dot'/'cosine' "
                f"(similarities factor through the embedding rows); "
                f"got {kernel!r}"
            )
        return cls(X=X)

    def _served(self) -> Array:
        return self.X if self.Xs is None else self.Xs

    @property
    def n(self) -> int:
        return self.X.shape[0]

    def empty_state(self) -> Array:
        return jnp.zeros((self._served().shape[0],), dtype=jnp.float32)

    def value(self, state: Array) -> Array:
        return jnp.sum(state)

    def _probe_mu(self, probes: Array, state: Array | None) -> Array:
        """Probe coverage rows mu_u = max(state, relu(Xs @ x_u)).  (r, ni) —
        an (r, d) gather plus a thin matmul, never anything O(n^2)."""
        from repro.kernels.fl_stream import fl_stream_pair_ref  # noqa: F401

        base = self.empty_state() if state is None else state
        cols = jnp.maximum(
            self._served().astype(jnp.float32)
            @ jnp.take(self.X, probes, axis=0).astype(jnp.float32).T,
            0.0,
        )                                                        # (ni, r)
        return jnp.maximum(base[None, :], cols.T)

    def gains(self, state: Array) -> Array:
        from repro.kernels.fl_stream import fl_stream_pair_ref

        return fl_stream_pair_ref(
            self._served(), state.astype(jnp.float32)[None, :], Xc=self.X
        )[0]

    def add(self, state: Array, v: Array) -> Array:
        col = jnp.maximum(
            self._served().astype(jnp.float32) @ self.X[v].astype(jnp.float32),
            0.0,
        )
        return jnp.maximum(state, col)

    def add_many(self, state: Array, mask: Array) -> Array:
        from repro.kernels.fl_stream import fl_stream_col_max

        return jnp.maximum(
            state, fl_stream_col_max(self._served(), self.X, mask)
        )

    def pairwise_gains(self, probes: Array, state: Array | None = None) -> Array:
        from repro.kernels.fl_stream import fl_stream_pair_ref

        return fl_stream_pair_ref(
            self._served(), self._probe_mu(probes, state), Xc=self.X
        )

    def residual_gains(self) -> Array:
        from repro.kernels.fl_stream import fl_stream_residuals

        return fl_stream_residuals(self._served(), self.X)

    def pairwise_gains_compact(
        self, probes: Array, cand_idx: Array, state: Array | None = None
    ) -> Array:
        """Compact streaming block: ``cand_idx`` gathers candidate *feature
        rows* (k, d) — a tiny gather — while the served-row reduction still
        spans all rows (that is f's definition)."""
        from repro.kernels.fl_stream import fl_stream_pair_ref

        return fl_stream_pair_ref(
            self._served(), self._probe_mu(probes, state), cand_idx, Xc=self.X
        )

    def gains_compact(self, state: Array, cand_idx: Array) -> Array:
        from repro.kernels.fl_stream import fl_stream_pair_ref

        return fl_stream_pair_ref(
            self._served(), state.astype(jnp.float32)[None, :], cand_idx,
            Xc=self.X,
        )[0]

    # The inherited *_batched defaults lax.map the compact hooks above — the
    # rows are already streaming/memory-bounded, so they are the batched
    # implementation too (one row's block scan in flight at a time).

    # -- pallas hooks ------------------------------------------------------
    def pallas_divergence(
        self,
        probes: Array,
        residual: Array,
        state: Array | None = None,
        probe_mask: Array | None = None,
        *,
        interpret: bool,
        cand_idx: Array | None = None,
        **block_kw,
    ) -> Array | None:
        from repro.kernels.fl_stream import fl_stream_divergence_kernel

        MU = self._probe_mu(probes, state)                       # (r, ni)
        resid = residual[probes]
        if probe_mask is not None:
            # Kernel pad-row convention: resid = -INF makes the edge weight
            # +INF, so masked probes never win the min.
            resid = jnp.where(probe_mask, resid, NEG)
        return fl_stream_divergence_kernel(
            self._served(), MU, resid, cand_idx, self.X,
            interpret=interpret, **block_kw,
        )

    def pallas_gains(
        self,
        state: Array,
        *,
        interpret: bool,
        cand_idx: Array | None = None,
        **block_kw,
    ) -> Array | None:
        from repro.kernels.fl_stream import fl_stream_gains_kernel

        return fl_stream_gains_kernel(
            self._served(), state, cand_idx, self.X,
            interpret=interpret, **block_kw,
        )

    # -- shard hooks (row-sharded candidates, replicated served rows) ------
    # Each device owns a contiguous block of candidate rows of X; the (n, d)
    # served rows are replicated (tiny — that is the whole point of the
    # matrix-free objective).  Payloads are (k, n) probe coverage rows, the
    # same wire format as the dense column-sharded FacilityLocation, so the
    # sharded SS loop in repro.core.distributed runs unchanged.

    def shard_pack(self, axes):
        if len(axes) > 1:
            raise NotImplementedError(
                "StreamingFacilityLocation shards candidates only (no pod "
                "hierarchy): its served rows span the full ground set"
            )
        return (self.X, self._served()), (P(axes[0], None), P(None, None)), (
            lambda X_loc, Xs_all: dataclasses.replace(
                self, X=X_loc, Xs=Xs_all
            )
        )

    def local_n(self) -> int:
        return self.X.shape[0]

    def shard_init(self, axis: str):
        from repro.kernels.fl_stream import (
            fl_stream_count_best,
            fl_stream_top2,
        )

        served = self._served()
        loc_top = fl_stream_top2(served, self.X)                 # (ni, 2)
        allt = jax.lax.all_gather(loc_top, axis)                 # (S, ni, 2)
        allt = jnp.moveaxis(allt, 0, 1).reshape(served.shape[0], -1)
        pad = jnp.full((served.shape[0], 2), NEG, allt.dtype)
        top2 = jax.lax.top_k(jnp.concatenate([allt, pad], axis=1), 2)[0]
        best, second = top2[:, 0], top2[:, 1]
        cnt = jax.lax.psum(fl_stream_count_best(served, self.X, best), axis)
        loss = jnp.where(
            cnt > 1, 0.0, jnp.maximum(best, 0.0) - jnp.maximum(second, 0.0)
        )
        return (best, loss)

    def shard_residuals(self, ctx) -> Array:
        from repro.kernels.fl_stream import fl_stream_best_loss_sum

        best, loss = ctx
        return fl_stream_best_loss_sum(self._served(), self.X, best, loss)

    def shard_payloads(self, idx: Array, state: Array | None = None) -> Array:
        return self._probe_mu(idx, state)                        # (k, ni)

    def shard_payload_gains(self, payloads: Array, ctx) -> Array:
        from repro.kernels.fl_stream import fl_stream_pair_ref

        return fl_stream_pair_ref(self._served(), payloads, Xc=self.X)

    def shard_take(self, cand_idx: Array) -> "StreamingFacilityLocation":
        # Candidates are rows of X; pin Xs so the served set stays whole.
        return dataclasses.replace(
            self,
            X=jnp.take(self.X, cand_idx, axis=0),
            Xs=self._served(),
        )

    def shard_gains(self, state: Array, ctx) -> Array:
        from repro.kernels.fl_stream import fl_stream_pair_ref

        return fl_stream_pair_ref(
            self._served(), state.astype(jnp.float32)[None, :], Xc=self.X
        )[0]

    def shard_add(self, state: Array, v: Array, ctx) -> Array:
        return self.add(state, v)
