"""Compact selection engine: the ``gains_compact`` backend primitive and the
compact greedy / stochastic-greedy paths (post-SS selection at |V'| cost).

The contract under test (docs/backends.md "Compact selection"): compaction is
a pure execution-strategy change — under the same inputs (and, for stochastic
greedy, the same PRNG key) the compact path must produce the *identical*
``selected`` / ``gains`` / ``value`` as the full-width path, on every
backend, including non-tile-multiple live counts, k > |alive| exhaustion,
and conditional (state != empty) starts.  The sharded stochastic-greedy loop
must match the dense compact path selection-for-selection under the same key
(multi-device coverage lives in tests/test_distributed.py; here a 1-device
mesh exercises the same kernel).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    FacilityLocation,
    FeatureCoverage,
    PallasBackend,
    ShardedBackend,
    auto_sample_size,
    get_backend,
    greedy,
    selection_bucket,
    ss_sparsify,
    stochastic_greedy,
    summarize,
)


def make_fc(seed=0, n=300, F=48, phi="sqrt", feat_w=False):
    key = jax.random.PRNGKey(seed)
    W = jax.random.uniform(key, (n, F))
    fw = jnp.linspace(0.5, 1.5, F) if feat_w else None
    return FeatureCoverage(W=W, feat_w=fw, phi=phi)


def make_fl(seed=0, n=300, d=12):
    X = jax.random.normal(jax.random.PRNGKey(seed), (n, d))
    return FacilityLocation.from_features(X, kernel="cosine")


OBJECTIVES = {
    "fc": lambda n: make_fc(0, n=n),
    "fc_featw": lambda n: make_fc(1, n=n, feat_w=True),
    "fc_satcov": lambda n: make_fc(2, n=n, phi="satcov"),
    "fl": lambda n: make_fl(3, n=n),
}
BACKENDS = {
    "oracle": lambda: get_backend("oracle"),
    "pallas": lambda: PallasBackend(interpret=True),
    "sharded": lambda: "sharded",   # greedy's per-step gains inherit oracle
}


def _sparse_alive(fn, seed=11):
    ss = ss_sparsify(fn, jax.random.PRNGKey(seed), r=6, c=8.0)
    live = int(jnp.sum(ss.vprime))
    assert 0 < live < fn.n
    assert selection_bucket(fn.n, live) is not None, "alive not sparse enough"
    return ss.vprime


def _assert_equal_results(a, b, exact_gains=False):
    assert (np.asarray(a.selected) == np.asarray(b.selected)).all(), (
        a.selected, b.selected)
    if exact_gains:
        np.testing.assert_array_equal(np.asarray(a.gains), np.asarray(b.gains))
    else:
        np.testing.assert_allclose(
            np.asarray(a.gains), np.asarray(b.gains), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(a.value), float(b.value), rtol=1e-5)


# ------------------------------------------------ gains_compact primitive ----
@pytest.mark.parametrize("mk", sorted(OBJECTIVES))
@pytest.mark.parametrize("backend", ["oracle", "pallas"])
def test_gains_compact_matches_full_gather(mk, backend):
    fn = OBJECTIVES[mk](300)
    be = BACKENDS[backend]()
    state = fn.add_many(fn.empty_state(), jnp.arange(fn.n) < 7)
    cand_idx = jnp.asarray([0, 3, 64, 65, 150, 299])
    full = be.gains(fn, state)
    out = be.gains_compact(fn, state, cand_idx)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(full)[np.asarray(cand_idx)],
        rtol=1e-5, atol=1e-5,
    )


def test_gains_compact_default_is_gather():
    """The base-class fallback (full gains + gather) keeps out-of-tree
    objectives correct on the compact path, and the shipped overrides agree
    with it."""
    from repro.core.functions import SubmodularFunction

    fn = make_fc(3, n=120, F=16)
    state = fn.add_many(fn.empty_state(), jnp.arange(120) < 4)
    cand_idx = jnp.asarray([2, 50, 119])
    ref = np.asarray(fn.gains(state))[np.asarray(cand_idx)]
    out = SubmodularFunction.gains_compact(fn, state, cand_idx)
    np.testing.assert_allclose(np.asarray(out), ref)
    np.testing.assert_allclose(
        np.asarray(fn.gains_compact(state, cand_idx)), ref,
        rtol=1e-6, atol=1e-6,
    )


# ------------------------------------------------- greedy compact parity ----
@pytest.mark.parametrize("name", sorted(OBJECTIVES))
@pytest.mark.parametrize("backend", sorted(BACKENDS))
def test_greedy_compact_matches_full(name, backend):
    """Acceptance: compact and full-width greedy select identical sets on
    every backend, from a real post-SS alive mask."""
    fn = OBJECTIVES[name](256)
    be = BACKENDS[backend]()
    alive = _sparse_alive(fn)
    full = greedy(fn, 8, alive=alive, backend=be, compact=False)
    comp = greedy(fn, 8, alive=alive, backend=be, compact=True)
    _assert_equal_results(full, comp)
    # selections come from the alive set
    assert bool(jnp.all(alive[comp.selected]))


@pytest.mark.parametrize("n", [200, 300, 333])
@pytest.mark.parametrize("backend", ["oracle", "pallas"])
def test_greedy_compact_non_tile_multiple(n, backend):
    """Live counts and ground sizes that are not multiples of the 128 tile:
    the gathered bucket is tile-rounded, padding slots must stay inert."""
    fn = make_fc(5, n=n, F=24)
    be = BACKENDS[backend]()
    alive = jnp.isin(jnp.arange(n), jnp.arange(0, n, 2)[:137])  # 137 live
    full = greedy(fn, 6, alive=alive, backend=be, compact=False)
    comp = greedy(fn, 6, alive=alive, backend=be, compact=True)
    _assert_equal_results(full, comp)


@pytest.mark.parametrize("backend", ["oracle", "pallas"])
def test_greedy_compact_k_exceeds_alive(backend):
    """k > |alive|: exhausted steps record index 0 with gain 0 on both
    paths, and the value counts the alive selections only."""
    fn = make_fc(6, n=256, F=24)
    be = BACKENDS[backend]()
    alive = jnp.arange(256) < 5
    full = greedy(fn, 9, alive=alive, backend=be, compact=False)
    comp = greedy(fn, 9, alive=alive, backend=be, compact=True)
    _assert_equal_results(full, comp)
    assert np.allclose(np.asarray(comp.gains)[5:], 0.0)
    assert (np.asarray(comp.selected)[5:] == 0).all()


@pytest.mark.parametrize("backend", ["oracle", "pallas"])
def test_greedy_compact_conditional_state(backend):
    """Conditional (state != empty) starts: gains are marginals on top of the
    given state and parity still holds."""
    fn = make_fc(7, n=256, F=24)
    be = BACKENDS[backend]()
    alive = _sparse_alive(fn)
    state = fn.add_many(fn.empty_state(), jnp.arange(256) < 4)
    full = greedy(fn, 6, alive=alive, backend=be, state=state, compact=False)
    comp = greedy(fn, 6, alive=alive, backend=be, state=state, compact=True)
    _assert_equal_results(full, comp)
    # conditional value includes the initial state's coverage
    assert float(comp.value) > float(fn.value(state))


def test_greedy_compact_int_bound_and_tracer_fallback():
    """An int ``compact`` bound engages the compact path without host-reading
    alive (the jit/vmap case); a plain tracer mask falls back to full-width;
    a bound smaller than the live count fails loudly."""
    fn = make_fc(8, n=256, F=16)
    alive = jnp.arange(256) < 100
    ref = greedy(fn, 5, alive=alive, compact=False)
    out = greedy(fn, 5, alive=alive, compact=128)
    _assert_equal_results(ref, out)
    with pytest.raises(ValueError, match="live bound"):
        greedy(fn, 5, alive=alive, compact=50)

    # under vmap the mask is a tracer: auto falls back, int bound compacts
    masks = jnp.stack([alive, jnp.arange(256) < 60])
    sel_auto = jax.vmap(lambda a: greedy(fn, 5, alive=a).selected)(masks)
    sel_bound = jax.vmap(
        lambda a: greedy(fn, 5, alive=a, compact=128).selected)(masks)
    np.testing.assert_array_equal(np.asarray(sel_auto), np.asarray(sel_bound))


def test_summarize_routes_through_compact():
    """The end-to-end pipeline's downstream greedy runs compact by default
    and compact=False reproduces it exactly."""
    fn = make_fc(9, n=300, F=32)
    key = jax.random.PRNGKey(2)
    res_c, ss_c = summarize(fn, 8, key, r=6, c=8.0, compact=True)
    res_f, ss_f = summarize(fn, 8, key, r=6, c=8.0, compact=False)
    assert bool(jnp.all(ss_c.vprime == ss_f.vprime))
    _assert_equal_results(res_c, res_f)


# -------------------------------------------- stochastic greedy (compact) ----
def test_stochastic_compact_cross_backend_same_key():
    """Oracle and pallas produce identical selections under the same key on
    the compact path (the kernel output matches the oracle gather bitwise)."""
    fn = make_fc(10, n=300, F=32)
    alive = _sparse_alive(fn)
    key = jax.random.PRNGKey(4)
    o = stochastic_greedy(fn, 8, key, alive=alive, backend="oracle")
    p = stochastic_greedy(fn, 8, key, alive=alive,
                          backend=PallasBackend(interpret=True))
    _assert_equal_results(o, p)


def test_stochastic_compact_samples_in_compact_space():
    """s=None auto mode: the sample size derives from the live count, not n,
    and every selection is an alive element."""
    fn = make_fc(11, n=512, F=32)
    alive = _sparse_alive(fn)
    live = int(jnp.sum(alive))
    s_live = auto_sample_size(512, 8, eps=0.1, live=live)
    s_full = auto_sample_size(512, 8, eps=0.1)
    assert s_live < s_full                         # the point of the heuristic
    res = stochastic_greedy(fn, 8, jax.random.PRNGKey(5), alive=alive)
    sel = np.asarray(res.selected)
    assert len(set(sel.tolist())) == 8             # distinct selections
    assert bool(jnp.all(alive[res.selected]))
    assert float(res.value) > 0


def test_stochastic_compact_k_exceeds_alive_and_state():
    fn = make_fc(12, n=256, F=24)
    alive = jnp.arange(256) < 4
    key = jax.random.PRNGKey(6)
    res = stochastic_greedy(fn, 7, key, alive=alive)
    assert np.allclose(np.asarray(res.gains)[4:], 0.0)
    assert (np.asarray(res.selected)[4:] == 0).all()
    assert set(np.asarray(res.selected)[:4].tolist()) == {0, 1, 2, 3}
    # conditional start runs on the compact path too
    state = fn.add_many(fn.empty_state(), jnp.arange(256) < 4)
    alive2 = _sparse_alive(fn)
    res2 = stochastic_greedy(fn, 5, key, alive=alive2, state=state)
    assert float(res2.value) > float(fn.value(state))


def test_stochastic_quality_close_to_greedy():
    """Post-SS stochastic greedy with the auto sample size stays within a few
    percent of exact greedy on the same live set."""
    fn = make_fc(13, n=400, F=48)
    alive = _sparse_alive(fn)
    g = greedy(fn, 8, alive=alive)
    sg = stochastic_greedy(fn, 8, jax.random.PRNGKey(8), alive=alive, eps=0.05)
    assert float(sg.value) >= 0.9 * float(g.value)


# ------------------------------------------------ sharded stochastic greedy --
def test_sharded_stochastic_matches_dense_compact_1dev():
    """The distributed sampler is selection-for-selection identical to the
    dense compact path under the same key (1-device mesh; the 8-device case
    is pinned in tests/test_distributed.py)."""
    from repro.core.distributed import make_mesh

    mesh = make_mesh((jax.device_count(),), ("data",))
    for fn in (make_fc(14, n=256, F=32), make_fl(15, n=256)):
        alive = _sparse_alive(fn)
        key = jax.random.PRNGKey(9)
        dense = stochastic_greedy(fn, 8, key, alive=alive, backend="oracle")
        shard = stochastic_greedy(fn, 8, key, alive=alive,
                                  backend=ShardedBackend(mesh=mesh))
        _assert_equal_results(dense, shard)


def test_sharded_stochastic_matches_dense_full_width():
    """When the dense plan is full-width (live count fits no sub-n bucket,
    or compact=False), the sharded sampler switches to the ground frame and
    still matches the dense path under the same key."""
    from repro.core.distributed import make_mesh

    mesh = make_mesh((jax.device_count(),), ("data",))
    be = ShardedBackend(mesh=mesh)
    fn = make_fc(17, n=256, F=32)
    key = jax.random.PRNGKey(10)
    # 200/256 live: only the full bucket fits -> dense runs full-width
    dense_mask = jnp.arange(256) < 200
    assert selection_bucket(256, 200) is None
    d = stochastic_greedy(fn, 8, key, alive=dense_mask, backend="oracle")
    sh = stochastic_greedy(fn, 8, key, alive=dense_mask, backend=be)
    _assert_equal_results(d, sh)
    # compact=False forces the ground frame even on a sparse mask
    sparse = jnp.arange(256) < 60
    d = stochastic_greedy(fn, 8, key, alive=sparse, backend="oracle",
                          compact=False)
    sh = stochastic_greedy(fn, 8, key, alive=sparse, backend=be,
                           compact=False)
    _assert_equal_results(d, sh)
    # alive=None (everything live) matches too
    d = stochastic_greedy(fn, 6, key, backend="oracle")
    sh = stochastic_greedy(fn, 6, key, backend=be)
    _assert_equal_results(d, sh)


def test_stochastic_full_width_s_derives_from_live_count():
    """compact=False still host-reads a concrete mask for the s=None
    heuristic: the full-width and compact runs of the same sparse mask use
    the same live-count-derived sample size (and the compact run reproduces
    a loose int bound's selections once the mask is readable)."""
    fn = make_fc(18, n=300, F=24)
    alive = _sparse_alive(fn)
    key = jax.random.PRNGKey(11)
    a = greedy(fn, 6, alive=alive, compact=int(jnp.sum(alive)) + 50)
    b = greedy(fn, 6, alive=alive, compact=True)
    _assert_equal_results(a, b)


def test_sharded_stochastic_rejects_pod_axis():
    from repro.core.distributed import make_mesh

    mesh = make_mesh((1, 1), ("pod", "data"))
    fn = make_fc(16, n=64, F=8)
    with pytest.raises(NotImplementedError, match="single-level"):
        stochastic_greedy(fn, 4, jax.random.PRNGKey(0),
                          backend=ShardedBackend(mesh=mesh, pod_axis="pod"))


# ------------------------------------------------------ planning helpers ----
def test_selection_bucket_properties():
    from repro.core.sparsify import bucket_schedule

    for n in (256, 300, 2048):
        buckets = bucket_schedule(n, 8.0, 128)
        for live in (1, 17, n // 4, n - 1, n):
            size = selection_bucket(n, live)
            if size is None:
                # only the full bucket fits
                assert all(b >= n or b < live for b in buckets)
            else:
                assert size >= live and size < n
                assert size in buckets


def test_auto_sample_size_bounds():
    assert auto_sample_size(1000, 10, eps=0.1, live=100) == 24  # 10*ln(10)
    assert auto_sample_size(1000, 10, eps=0.1) >= 230
    assert auto_sample_size(16, 64, eps=0.5) == 1               # floor at 1
