"""Backend dispatch layer tests: registry contract + numerical parity of the
oracle / pallas (interpret) / sharded execution backends on both objectives
and all phi variants.  Every shipped configuration — FeatureCoverage with and
without feat_w feature weights, and FacilityLocation — has a fused kernel,
so the pallas legs exercise real kernels (test_pallas_hooks_no_fallback pins
that), and an objective without one is an error under the pallas backend,
never a silent drop to the oracle (test_pallas_backend_raises_without_kernel).

Multi-device sharded parity lives in test_distributed.py (needs forced host
devices); here the sharded backend runs on the default single-device mesh —
same shard_map code path, collectives of size 1.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    Backend,
    FacilityLocation,
    FeatureCoverage,
    OracleBackend,
    StreamingFacilityLocation,
    PallasBackend,
    ShardedBackend,
    available_backends,
    get_backend,
    greedy,
    register_backend,
    resolve_backend,
    ss_sparsify,
)
from repro.core.graph import divergence


def make_fc(seed=0, n=200, F=64, phi="sqrt", feat_w=False, alpha=0.2):
    key = jax.random.PRNGKey(seed)
    W = jax.random.uniform(key, (n, F))
    fw = jnp.linspace(0.5, 1.5, F) if feat_w else None
    return FeatureCoverage(W=W, feat_w=fw, phi=phi, alpha=alpha)


def make_fl(seed=0, n=200, d=12, kernel="cosine"):
    X = jax.random.normal(jax.random.PRNGKey(seed), (n, d))
    return FacilityLocation.from_features(X, kernel=kernel)


def make_sfl(seed=0, n=200, d=12, kernel="cosine"):
    X = jax.random.normal(jax.random.PRNGKey(seed), (n, d))
    return StreamingFacilityLocation.from_features(X, kernel=kernel)


OBJECTIVES = {
    "fc_sqrt": lambda: make_fc(phi="sqrt"),
    "fc_log1p": lambda: make_fc(phi="log1p"),
    "fc_setcover": lambda: make_fc(phi="setcover"),
    "fc_satcov": lambda: make_fc(phi="satcov", alpha=0.3),
    "fc_linear": lambda: make_fc(phi="linear"),
    "fc_featw": lambda: make_fc(phi="sqrt", feat_w=True),
    "fc_featw_log1p": lambda: make_fc(phi="log1p", feat_w=True),
    "fc_featw_satcov": lambda: make_fc(phi="satcov", feat_w=True, alpha=0.3),
    "fl": lambda: make_fl(),
    "fl_rbf": lambda: make_fl(kernel="rbf"),
    "fl_stream": lambda: make_sfl(),
}


# ------------------------------------------------------------- registry ----
def test_registry_contract(monkeypatch):
    assert {"oracle", "pallas", "sharded"} <= set(available_backends())
    assert isinstance(get_backend("oracle"), OracleBackend)
    assert isinstance(resolve_backend("pallas"), PallasBackend)
    # None resolves to the env default (the CI matrix sets it), else oracle.
    monkeypatch.delenv("REPRO_SS_BACKEND", raising=False)
    assert resolve_backend(None).name == "oracle"
    be = PallasBackend(interpret=True)
    assert resolve_backend(be) is be
    with pytest.raises(KeyError):
        get_backend("no-such-backend")
    with pytest.raises(TypeError):
        resolve_backend(123)


def test_registry_extension():
    class EchoBackend(OracleBackend):
        name = "echo"

    register_backend("echo", EchoBackend)
    try:
        assert isinstance(get_backend("echo"), EchoBackend)
        assert "echo" in available_backends()
    finally:
        import repro.core.backend as B

        B._REGISTRY.pop("echo", None)
        B._INSTANCES.pop("echo", None)


def test_backends_are_jit_static():
    # hashable + eq so they ride through jax.jit static args
    assert hash(OracleBackend()) == hash(OracleBackend())
    assert PallasBackend(interpret=True) == PallasBackend(interpret=True)
    assert PallasBackend(interpret=True) != PallasBackend(interpret=False)


# --------------------------------------------------------- no fallback ----
@pytest.mark.parametrize("name", sorted(OBJECTIVES))
def test_pallas_hooks_no_fallback(name):
    """backend="pallas" is total: every shipped objective configuration
    provides both kernel hooks (a None return would silently re-route to the
    jnp oracle and the kernels would stop being exercised)."""
    fn = OBJECTIVES[name]()
    probes = jnp.asarray([1, 42, 99])
    out = fn.pallas_divergence(
        probes, fn.residual_gains(), interpret=True
    )
    assert out is not None and out.shape == (fn.n,)
    g = fn.pallas_gains(fn.empty_state(), interpret=True)
    assert g is not None and g.shape == (fn.n,)


@pytest.mark.parametrize(
    "primitive", ["gains", "gains_compact", "divergence", "divergence_compact"]
)
def test_pallas_backend_raises_without_kernel(primitive, monkeypatch):
    """A hook that returns None (the SubmodularFunction default) makes the
    pallas backend raise, naming the objective and the primitive."""
    from repro.core.functions import SubmodularFunction

    for hook in ("pallas_gains", "pallas_divergence"):
        monkeypatch.setattr(
            FeatureCoverage, hook, getattr(SubmodularFunction, hook)
        )
    fn = make_fc()
    be = PallasBackend(interpret=True)
    probes = jnp.asarray([1, 42, 99])
    cand_idx = jnp.asarray([0, 5, 7])
    calls = {
        "gains": lambda: be.gains(fn, fn.empty_state()),
        "gains_compact": lambda: be.gains_compact(
            fn, fn.empty_state(), cand_idx),
        "divergence": lambda: be.divergence(fn, probes),
        "divergence_compact": lambda: be.divergence_compact(
            fn, probes, cand_idx),
    }
    with pytest.raises(NotImplementedError,
                       match=f"FeatureCoverage .* for {primitive} "):
        calls[primitive]()


def test_interpret_env_refused_on_tpu(monkeypatch):
    """REPRO_PALLAS_INTERPRET=1 is the CPU/CI switch: honored off-TPU,
    refused when the default backend is a TPU."""
    from repro.core.backend import default_pallas_interpret

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert default_pallas_interpret() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="REPRO_PALLAS_INTERPRET"):
        default_pallas_interpret()
    with pytest.raises(RuntimeError, match="REPRO_PALLAS_INTERPRET"):
        PallasBackend().gains(make_fc(), jnp.zeros((64,)))
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert default_pallas_interpret() is False
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET")
    assert default_pallas_interpret() is False


# ------------------------------------------------------ divergence parity ----
@pytest.mark.parametrize("name", sorted(OBJECTIVES))
def test_divergence_parity_oracle_vs_pallas(name):
    fn = OBJECTIVES[name]()
    probes = jnp.asarray([3, 50, 111, 166])
    residual = fn.residual_gains()
    ref = get_backend("oracle").divergence(fn, probes, residual=residual)
    out = PallasBackend(interpret=True).divergence(
        fn, probes, residual=residual
    )
    live = np.ones((fn.n,), bool)
    live[np.asarray(probes)] = False  # probe entries are unspecified (owned by V')
    np.testing.assert_allclose(
        np.asarray(out)[live], np.asarray(ref)[live], rtol=1e-4, atol=1e-4
    )


@pytest.mark.parametrize("name", ["fc_sqrt", "fc_featw", "fl"])
def test_divergence_parity_with_state(name):
    fn = OBJECTIVES[name]()
    state = fn.add_many(fn.empty_state(), jnp.arange(fn.n) < 7)
    probes = jnp.asarray([20, 90, 150])
    residual = fn.residual_gains()
    ref = divergence(fn, probes, residual=residual, state=state)
    out = PallasBackend(interpret=True).divergence(
        fn, probes, residual=residual, state=state
    )
    live = np.ones((fn.n,), bool)
    live[np.asarray(probes)] = False
    np.testing.assert_allclose(
        np.asarray(out)[live], np.asarray(ref)[live], rtol=1e-4, atol=1e-4
    )


@pytest.mark.parametrize("name", ["fc_sqrt", "fc_featw", "fl"])
def test_divergence_parity_probe_mask(name):
    fn = OBJECTIVES[name]()
    probes = jnp.asarray([10, 60, 120])
    mask = jnp.asarray([True, False, True])
    residual = fn.residual_gains()
    ref = divergence(fn, probes, probe_mask=mask, residual=residual)
    out = PallasBackend(interpret=True).divergence(
        fn, probes, probe_mask=mask, residual=residual
    )
    live = np.ones((fn.n,), bool)
    live[[10, 120]] = False  # masked-out probe 60 stays a live candidate
    np.testing.assert_allclose(
        np.asarray(out)[live], np.asarray(ref)[live], rtol=1e-4, atol=1e-4
    )


# ----------------------------------------------------------- gains parity ----
@pytest.mark.parametrize("name", sorted(OBJECTIVES))
def test_gains_parity_oracle_vs_pallas(name):
    fn = OBJECTIVES[name]()
    state = fn.add_many(
        fn.empty_state(), jnp.zeros((fn.n,), bool).at[jnp.asarray([2, 5, 99])].set(True)
    )
    ref = get_backend("oracle").gains(fn, state)
    out = PallasBackend(interpret=True).gains(fn, state)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4
    )


@pytest.mark.parametrize("name", sorted(OBJECTIVES))
def test_greedy_parity_across_backends(name):
    fn = OBJECTIVES[name]()
    ref = greedy(fn, 6)
    out = greedy(fn, 6, backend=PallasBackend(interpret=True))
    assert list(np.asarray(ref.selected)) == list(np.asarray(out.selected))
    np.testing.assert_allclose(
        float(ref.value), float(out.value), rtol=1e-4
    )


# ------------------------------------------------------- sparsify parity ----
@pytest.mark.parametrize(
    "name",
    ["fc_sqrt", "fc_satcov", "fc_featw", "fc_featw_satcov", "fl", "fl_stream"],
)
def test_ss_sparsify_oracle_pallas_identical(name):
    """Same PRNG stream => identical probe sets; divergences agree to fp
    error, so the retained sets match elementwise."""
    fn = OBJECTIVES[name]()
    key = jax.random.PRNGKey(4)
    ss_o = ss_sparsify(fn, key, r=6, c=8.0)
    ss_p = ss_sparsify(fn, key, r=6, c=8.0, backend=PallasBackend(interpret=True))
    assert bool(jnp.all(ss_o.vprime == ss_p.vprime))
    assert int(ss_o.rounds) == int(ss_p.rounds)


@pytest.mark.parametrize("mk,kw", [
    (make_fc, dict(phi="sqrt")),
    (make_fc, dict(phi="satcov", alpha=0.3)),
    (make_fc, dict(phi="sqrt", feat_w=True)),
    (make_fl, dict(kernel="rbf")),
])
def test_sharded_backend_matches_oracle_value(mk, kw):
    """Acceptance: ss_sparsify(..., backend="sharded") runs both objectives
    end-to-end on a CPU mesh; greedy on the sharded V' matches greedy on the
    oracle V' within 1e-3 relative."""
    fn = mk(n=256, **kw)
    key = jax.random.PRNGKey(0)
    ss_s = ss_sparsify(fn, key, r=8, c=8.0, backend="sharded")
    ss_o = ss_sparsify(fn, key, r=8, c=8.0)
    assert 0 < int(jnp.sum(ss_s.vprime)) < fn.n
    v_s = float(greedy(fn, 8, alive=ss_s.vprime).value)
    v_o = float(greedy(fn, 8, alive=ss_o.vprime).value)
    assert abs(v_s - v_o) / v_o < 1e-3, (v_s, v_o)


def test_sharded_backend_conditional_and_importance_run():
    """Conditional state and importance sampling are supported in the
    sharded SS loop as of PR 5 (quality-parity pins live in
    tests/test_distributed.py; here a 1-device mesh checks the plumbing)."""
    fn = make_fc(n=64, F=16)
    key = jax.random.PRNGKey(0)
    ss = ss_sparsify(fn, key, backend="sharded", importance=True)
    assert 0 < int(jnp.sum(ss.vprime)) <= 64
    state = fn.add_many(fn.empty_state(), jnp.arange(64) < 3)
    ss2 = ss_sparsify(fn, key, backend="sharded", state=state)
    assert 0 < int(jnp.sum(ss2.vprime)) <= 64


def test_sharded_backend_respects_alive():
    fn = make_fc(n=256, F=32)
    alive = jnp.arange(256) < 128
    ss = ss_sparsify(fn, jax.random.PRNGKey(0), alive=alive, backend="sharded")
    assert not bool(jnp.any(ss.vprime[128:]))


def test_fl_pod_sharding_rejected():
    fn = make_fl(n=64)
    assert not fn.supports_pod_sharding
    with pytest.raises(NotImplementedError):
        fn.shard_pack(("pod", "data"))


def test_env_default_backend(monkeypatch):
    monkeypatch.setenv("REPRO_SS_BACKEND", "pallas")
    assert resolve_backend(None).name == "pallas"
    monkeypatch.delenv("REPRO_SS_BACKEND")
    assert resolve_backend(None).name == "oracle"
