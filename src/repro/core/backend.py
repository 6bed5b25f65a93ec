"""Execution backends for the submodular-maximization hot paths.

Every algorithm in :mod:`repro.core` evaluates the same few primitives —
``gains`` / ``gains_compact`` (greedy's inner loop, full-width and restricted
to a compacted candidate buffer), ``pairwise_gains`` and ``divergence`` /
``divergence_compact`` (the SS round, paper Def. 2) — but *how* those are
executed depends on where the code runs.  This module is the single dispatch
point:

- ``oracle``  — plain jnp (XLA) on whatever the default device is.  The
  reference semantics; always available.
- ``pallas``  — the fused TPU kernels in :mod:`repro.kernels` (interpret mode
  on CPU).  Every shipped objective provides kernels for every configuration
  (FeatureCoverage with and without ``feat_w``, FacilityLocation, and the
  matrix-free StreamingFacilityLocation, whose kernels compute similarity
  tiles on the fly from embedding rows — see :mod:`repro.kernels.fl_stream`).
  An objective without a kernel hook is an error under this backend, never a
  silent drop to the oracle: a run that says "pallas" ran the kernels.
- ``sharded`` — shard_map over a device mesh: the whole SS loop runs
  distributed via the per-shard function views declared on the objective
  (see :mod:`repro.core.distributed`).

Selection is by a ``backend=`` argument accepted throughout the stack: a
string (registry lookup), a :class:`Backend` instance (e.g. a
:class:`ShardedBackend` carrying a specific mesh), or None for the default
(the ``REPRO_SS_BACKEND`` environment variable, else ``oracle``).  Backends
are hashable frozen dataclasses so they ride through ``jax.jit`` as static
arguments.

Adding a backend: subclass :class:`Backend`, override the primitives you
accelerate (anything left alone inherits the oracle semantics), then
``register_backend("name", factory)``.  See docs/backends.md for the full
contract, including what a new *objective* must implement to be reachable
from each backend.
"""

from __future__ import annotations

import abc
import dataclasses
import os
from typing import Callable

import jax

from repro.core import graph
from repro.core.functions import SubmodularFunction

Array = jax.Array


def default_pallas_interpret() -> bool:
    """Pallas interpret mode unless we are actually on TPU.

    ``REPRO_PALLAS_INTERPRET=1`` forces interpret mode — the CPU / CI
    correctness switch, refused when the default backend is a TPU so that a
    chip run always executes the compiled kernels; ``=0`` forces the
    compiled kernel.
    """
    on_tpu = jax.default_backend() == "tpu"
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if env == "1" and on_tpu:
        raise RuntimeError(
            "REPRO_PALLAS_INTERPRET=1 is a CPU/CI switch; on a TPU the pallas "
            "backend runs the compiled kernels (unset the variable)"
        )
    if env:
        return env == "1"
    return not on_tpu


@dataclasses.dataclass(frozen=True)
class Backend(abc.ABC):
    """Execution strategy for the submodular primitives.

    The base class implements every primitive with the jnp oracle; subclasses
    override what they accelerate.  Instances are immutable and hashable so
    they can be jit-static.
    """

    name = "oracle"

    # -- primitives --------------------------------------------------------
    def gains(self, fn: SubmodularFunction, state, **kw) -> Array:
        """f(v|S) for all v.  Shape (n,)."""
        return fn.gains(state)

    def gains_compact(
        self, fn: SubmodularFunction, state, cand_idx: Array, **kw
    ) -> Array:
        """f(v|S) for the compacted candidate buffer ``cand_idx`` (k,).

        Returns (k,) gains, elementwise equal to ``gains(...)[cand_idx]``.
        The compact selection engine (repro.core.greedy) calls this once per
        greedy step with a bucket-sized static buffer of post-SS survivors so
        per-step cost tracks |V'| instead of n.  The base implementation
        routes through the objective's ``gains_compact`` (whose default is a
        full-width gather — the always-correct oracle fallback)."""
        return fn.gains_compact(state, cand_idx)

    def pairwise_gains(
        self, fn: SubmodularFunction, probes: Array, state=None, **kw
    ) -> Array:
        """f(v | S + u) for u in probes.  Shape (r, n)."""
        return fn.pairwise_gains(probes, state)

    def divergence(
        self,
        fn: SubmodularFunction,
        probes: Array,
        probe_mask: Array | None = None,
        residual: Array | None = None,
        state=None,
        **kw,
    ) -> Array:
        """w_{U,v} = min_{u in U} [f(v|S+u) - f(u|V\\u)] for all v.  (n,)."""
        return graph.divergence(fn, probes, probe_mask, residual, state)

    def divergence_compact(
        self,
        fn: SubmodularFunction,
        probes: Array,
        cand_idx: Array,
        probe_mask: Array | None = None,
        residual: Array | None = None,
        state=None,
        **kw,
    ) -> Array:
        """w_{U,v} for the compacted candidate buffer ``cand_idx`` (k,).

        Returns (k,) divergences, elementwise equal to
        ``divergence(...)[cand_idx]``.  The shrink-aware SS loop calls this
        with a bucket-sized static buffer of live candidates so round cost
        tracks the live count instead of n (see repro.core.sparsify).  The
        base implementation routes through the objective's
        ``pairwise_gains_compact`` (whose default is a full-width gather —
        the always-correct oracle fallback)."""
        return graph.divergence_compact(
            fn, probes, cand_idx, probe_mask, residual, state
        )

    # -- batched primitives (micro-batched serving) ------------------------
    def divergence_batched(
        self,
        fn: SubmodularFunction,
        probes: Array,
        cand_idx: Array | None = None,
        residual: Array | None = None,
        state=None,
        **kw,
    ) -> Array:
        """w_{U_b,v} per batch row for a *stacked* objective.  Shape (B, k).

        ``probes`` is (B, r), ``cand_idx`` (B, k) (full width when None),
        ``residual`` the stacked (B, n) block.  Row b is elementwise equal
        to the *oracle* ``divergence(...)`` / ``divergence_compact(...)`` on
        that row alone — the batched SS loop (repro.core.sparsify) is built
        on this invariance.  The base implementation routes through the
        objective's ``pairwise_gains_batched`` (cache-blocked probe-chunk
        scans on both shipped objectives; the always-correct ``lax.map``
        fallback otherwise).  No backend overrides it yet: a native
        batch-grid pallas kernel is an open ROADMAP item, and on CPU the
        blocked jnp formulation is already the fastest execution of this
        arithmetic.  The interpret-mode kernels happen to match it bitwise
        at shipped feature widths (the parity tests compare exactly);
        compiled-kernel sequential runs are only guaranteed fp-close."""
        return graph.divergence_batched(fn, probes, cand_idx, residual, state)

    def gains_batched(
        self, fn: SubmodularFunction, state, cand_idx: Array | None, **kw
    ) -> Array:
        """f(v|S_b) per batch row for a *stacked* objective and stacked
        states.  Shape (B, k); row b equals ``gains_compact(state[b],
        cand_idx[b])`` (full-width ``gains`` when ``cand_idx`` is None)."""
        return fn.gains_batched(state, cand_idx)

    # -- whole-loop entry points -------------------------------------------
    def sparsify(self, fn: SubmodularFunction, key: Array, **kw):
        """Run SS (Algorithm 1) under this backend.  Returns an SSResult.

        The default runs the dense single-process loop with this backend's
        ``divergence``; the sharded backend overrides the whole loop.
        """
        from repro.core.sparsify import _sparsify_dense

        return _sparsify_dense(fn, key, backend=self, **kw)

    def sparsify_batched(self, fn: SubmodularFunction, keys: Array, **kw):
        """Run SS for B same-shape queries (a *stacked* objective) as one
        compiled loop.  Returns a batched SSResult (leading B axis on every
        field); row b is identical to ``sparsify`` on that query alone under
        the same key.  The sharded backend owns the whole mesh per query and
        does not batch."""
        from repro.core.sparsify import _sparsify_batched

        return _sparsify_batched(fn, keys, backend=self, **kw)

    def greedy(self, fn: SubmodularFunction, k: int, **kw):
        """Run exact greedy under this backend.  Returns a GreedyResult.

        The default resolves the compact-selection plan and runs the dense
        per-step loop with this backend's ``gains`` / ``gains_compact``; the
        sharded backend overrides the whole loop with the distributed argmax
        (see repro.core.distributed.greedy_sharded).
        """
        from repro.core.greedy import _greedy_dense

        return _greedy_dense(fn, k, backend=self, **kw)

    def stochastic_greedy(self, fn: SubmodularFunction, k: int, key: Array, **kw):
        """Run stochastic greedy [Mirzasoleiman et al.] under this backend.

        The default runs the dense single-process loop (compact candidate
        buffer when ``alive`` is sparse) with this backend's ``gains`` /
        ``gains_compact``; the sharded backend overrides the whole loop with
        the distributed sampler.  Returns a GreedyResult.
        """
        from repro.core.greedy import _stochastic_greedy_dense

        return _stochastic_greedy_dense(fn, k, key, backend=self, **kw)


@dataclasses.dataclass(frozen=True)
class OracleBackend(Backend):
    """Reference jnp semantics — inherits every primitive unchanged."""

    name = "oracle"


@dataclasses.dataclass(frozen=True)
class PallasBackend(Backend):
    """Fused Pallas kernels.

    ``interpret=None`` auto-detects (interpret mode off-TPU, honoring
    ``REPRO_PALLAS_INTERPRET``).  Objectives provide their kernels through
    the ``pallas_divergence`` / ``pallas_gains`` hooks; every shipped
    objective implements them for every configuration.  A hook that returns
    ``None`` (no kernel) raises :class:`NotImplementedError` naming the
    objective and the primitive — there is no oracle fallback to hide it.
    """

    name = "pallas"
    interpret: bool | None = None

    def _interpret(self) -> bool:
        if self.interpret is None:
            return default_pallas_interpret()
        return self.interpret

    def _kernel(self, fn: SubmodularFunction, primitive: str, out):
        if out is None:
            raise NotImplementedError(
                f"{type(fn).__name__} has no Pallas kernel for {primitive} "
                f"(its pallas hook returned None); implement the hook or run "
                f"it under backend='oracle'"
            )
        return out

    def gains(self, fn: SubmodularFunction, state, **kw) -> Array:
        out = fn.pallas_gains(state, interpret=self._interpret(), **kw)
        return self._kernel(fn, "gains", out)

    def gains_compact(
        self, fn: SubmodularFunction, state, cand_idx: Array, **kw
    ) -> Array:
        out = fn.pallas_gains(
            state, interpret=self._interpret(), cand_idx=cand_idx, **kw
        )
        return self._kernel(fn, "gains_compact", out)

    def divergence(
        self,
        fn: SubmodularFunction,
        probes: Array,
        probe_mask: Array | None = None,
        residual: Array | None = None,
        state=None,
        **kw,
    ) -> Array:
        if residual is None:
            residual = fn.residual_gains()
        out = fn.pallas_divergence(
            probes, residual, state, probe_mask,
            interpret=self._interpret(), **kw,
        )
        return self._kernel(fn, "divergence", out)

    def divergence_compact(
        self,
        fn: SubmodularFunction,
        probes: Array,
        cand_idx: Array,
        probe_mask: Array | None = None,
        residual: Array | None = None,
        state=None,
        **kw,
    ) -> Array:
        if residual is None:
            residual = fn.residual_gains()
        out = fn.pallas_divergence(
            probes, residual, state, probe_mask,
            interpret=self._interpret(), cand_idx=cand_idx, **kw,
        )
        return self._kernel(fn, "divergence_compact", out)


@dataclasses.dataclass(frozen=True)
class ShardedBackend(Backend):
    """shard_map execution over a device mesh.

    ``sparsify`` runs the whole SS loop distributed (collectives over
    ``data_axis``; optional per-pod hierarchy over ``pod_axis``) — see
    :func:`repro.core.distributed.ss_sparsify_sharded`.  The per-call
    primitives (``gains`` etc.) inherit the oracle path: after SS the
    surviving ground set is polylog-sized, so greedy's inner loop does not
    benefit from sharding.

    ``mesh=None`` builds a 1-D mesh over all visible devices at call time.
    """

    name = "sharded"
    mesh: jax.sharding.Mesh | None = None
    data_axis: str = "data"
    pod_axis: str | None = None
    bins: int = 512

    def _mesh(self) -> jax.sharding.Mesh:
        if self.mesh is not None:
            return self.mesh
        from repro.core.distributed import make_mesh

        return make_mesh((jax.device_count(),), (self.data_axis,))

    def sparsify(self, fn: SubmodularFunction, key: Array, **kw):
        from repro.core import distributed

        return distributed.ss_sparsify_sharded(
            fn, key, self._mesh(),
            data_axis=self.data_axis, pod_axis=self.pod_axis,
            bins=self.bins, **kw,
        )

    def sparsify_batched(self, fn: SubmodularFunction, keys: Array, **kw):
        raise NotImplementedError(
            "the sharded backend owns the whole mesh per query and does not "
            "micro-batch; use backend='oracle' or 'pallas' for the batched "
            "serving path"
        )

    def greedy(self, fn: SubmodularFunction, k: int, **kw):
        from repro.core import distributed

        alive = kw.get("alive")
        mesh = None if self.pod_axis else self._mesh()
        if (
            mesh is None
            or not fn.supports_shard_greedy
            or fn.n % mesh.shape[self.data_axis] != 0
            or isinstance(alive, jax.core.Tracer)
        ):
            # Distributed exact greedy needs the shard selection hooks, a
            # shard-divisible ground set, and a concrete mask (the live count
            # sizes its static buffers), and is single-level; otherwise fall
            # back to the dense loop — the pre-distributed behavior, always
            # correct.
            return super().greedy(fn, k, **kw)
        return distributed.greedy_sharded(
            fn, k, mesh, data_axis=self.data_axis, **kw
        )

    def stochastic_greedy(self, fn: SubmodularFunction, k: int, key: Array, **kw):
        from repro.core import distributed

        if self.pod_axis:
            raise NotImplementedError(
                "sharded stochastic greedy is single-level (the selection "
                "stage is global); use a data-axis-only ShardedBackend"
            )
        return distributed.stochastic_greedy_sharded(
            fn, k, key, self._mesh(), data_axis=self.data_axis, **kw
        )


# -- registry ---------------------------------------------------------------

_REGISTRY: dict[str, Callable[[], Backend]] = {}
_INSTANCES: dict[str, Backend] = {}


def register_backend(name: str, factory: Callable[[], Backend]) -> None:
    """Register (or replace) a backend factory under ``name``."""
    _REGISTRY[name] = factory
    _INSTANCES.pop(name, None)


def available_backends() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get_backend(name: str) -> Backend:
    """Singleton backend instance for a registered name."""
    if name not in _REGISTRY:
        raise KeyError(
            f"unknown backend {name!r}; available: {available_backends()}"
        )
    if name not in _INSTANCES:
        _INSTANCES[name] = _REGISTRY[name]()
    return _INSTANCES[name]


def resolve_backend(spec: "str | Backend | None" = None) -> Backend:
    """Resolve a ``backend=`` argument: Backend instance (as-is), registry
    name, or None -> ``$REPRO_SS_BACKEND`` else ``oracle``."""
    if isinstance(spec, Backend):
        return spec
    if spec is None:
        spec = os.environ.get("REPRO_SS_BACKEND", "oracle")
    if isinstance(spec, str):
        return get_backend(spec)
    raise TypeError(f"backend must be a name, Backend, or None; got {spec!r}")


register_backend("oracle", OracleBackend)
register_backend("pallas", PallasBackend)
register_backend("sharded", ShardedBackend)
