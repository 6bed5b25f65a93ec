"""Distribution tests: these need >1 device, so each runs in a subprocess
with ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the main pytest
process keeps the default 1 CPU device, per the dry-run isolation rule)."""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, devices: int = 8, timeout: int = 520) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        env=env, capture_output=True, text=True, timeout=timeout,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    return out.stdout


def test_sharded_ss_matches_full_greedy():
    out = run_sub("""
        import jax, jax.numpy as jnp
        from repro.core.distributed import summarize_sharded
        from repro.core import FeatureCoverage, greedy
        from repro.core.distributed import make_mesh
        from repro.data import news_day

        W = news_day(0, 1024, 128)
        fn = FeatureCoverage(W=jnp.asarray(W), phi="sqrt")
        ref = greedy(fn, 8)
        mesh = make_mesh((8,), ("data",))
        sel, val, vp, eps = summarize_sharded(W, 8, jax.random.PRNGKey(0), mesh)
        ratio = float(val / ref.value)
        assert ratio > 0.95, ratio
        assert int(jnp.sum(vp)) < 1024
        print("RATIO", ratio)
    """)
    assert "RATIO" in out


def test_sharded_ss_hierarchical_pods():
    out = run_sub("""
        import jax, jax.numpy as jnp
        from repro.core.distributed import summarize_sharded
        from repro.core import FeatureCoverage, greedy
        from repro.core.distributed import make_mesh
        from repro.data import news_day

        W = news_day(1, 1024, 128)
        fn = FeatureCoverage(W=jnp.asarray(W), phi="sqrt")
        ref = greedy(fn, 8)
        mesh = make_mesh((2, 4), ("pod", "data"))
        sel, val, vp, eps = summarize_sharded(
            W, 8, jax.random.PRNGKey(0), mesh, pod_axis="pod")
        ratio = float(val / ref.value)
        assert ratio > 0.95, ratio
        print("OK", ratio)
    """)
    assert "OK" in out


def test_sharded_backend_facility_location_multidevice():
    """Acceptance: ss_sparsify(backend=...) runs FacilityLocation on a real
    multi-device CPU mesh through a ShardedBackend, and greedy on the sharded
    V' matches greedy on the oracle V' within 1e-3 relative."""
    out = run_sub("""
        import jax, jax.numpy as jnp
        from repro.core import FacilityLocation, ShardedBackend, greedy, ss_sparsify
        from repro.core.distributed import make_mesh

        X = jax.random.normal(jax.random.PRNGKey(1), (512, 16))
        fn = FacilityLocation.from_features(X, kernel="rbf")
        key = jax.random.PRNGKey(0)
        be = ShardedBackend(mesh=make_mesh((8,), ("data",)))
        ss_s = ss_sparsify(fn, key, r=8, c=8.0, backend=be)
        ss_o = ss_sparsify(fn, key, r=8, c=8.0, backend="oracle")
        v_s = float(greedy(fn, 8, alive=ss_s.vprime).value)
        v_o = float(greedy(fn, 8, alive=ss_o.vprime).value)
        rel = abs(v_s - v_o) / v_o
        assert rel < 1e-3, (v_s, v_o, rel)
        assert int(jnp.sum(ss_s.vprime)) < 512
        print("FL_PARITY", rel)
    """)
    assert "FL_PARITY" in out


def test_sharded_backend_fl_stream_multidevice():
    """Matrix-free StreamingFacilityLocation on a real 8-device mesh: the
    row-sharded embedding hooks (replicated served rows, (k, n) coverage
    payloads) prune exactly like the dense column-sharded FacilityLocation
    on the same features/key, and per-shard residuals match the dense
    oracle."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import (FacilityLocation, ShardedBackend,
                                StreamingFacilityLocation, greedy, ss_sparsify)
        from repro.core.distributed import make_mesh
        from jax.sharding import PartitionSpec as P

        mesh = make_mesh((8,), ("data",))
        X = jax.random.normal(jax.random.PRNGKey(1), (512, 16))
        dense = FacilityLocation.from_features(X, kernel="cosine")
        sfl = StreamingFacilityLocation.from_features(X, kernel="cosine")

        # per-shard residuals == dense oracle residuals
        arrays, specs, rebuild = sfl.shard_pack(("data",))
        def res_kernel(*arrs):
            loc = rebuild(*arrs)
            return loc.shard_residuals(loc.shard_init("data"))
        res = jax.shard_map(res_kernel, mesh=mesh, in_specs=specs,
                        out_specs=P("data"), check_vma=False)(*arrays)
        np.testing.assert_allclose(np.asarray(res),
                                   np.asarray(dense.residual_gains()),
                                   rtol=1e-4, atol=1e-4)

        key = jax.random.PRNGKey(0)
        be = ShardedBackend(mesh=mesh)
        ss_s = ss_sparsify(sfl, key, r=8, c=8.0, backend=be)
        ss_d = ss_sparsify(dense, key, r=8, c=8.0, backend=be)
        assert 0 < int(jnp.sum(ss_s.vprime)) < 512
        assert bool(jnp.all(ss_s.vprime == ss_d.vprime))
        v_s = float(greedy(sfl, 8, alive=ss_s.vprime).value)
        v_d = float(greedy(dense, 8, alive=ss_d.vprime).value)
        rel = abs(v_s - v_d) / v_d
        assert rel < 1e-5, (v_s, v_d, rel)
        print("FL_STREAM_PARITY", rel)
    """)
    assert "FL_STREAM_PARITY" in out


def test_sharded_backend_objective_generic():
    """The sharded loop is objective-generic: both objectives run through the
    same shard_map kernel via their shard hooks, and per-shard residuals
    match the dense oracle.  The shard residuals use the dense arithmetic
    (per-feature difference, then the sum); only the pod-global coverage
    total C is summed in a different order (per-shard sums, then psum), which
    moves an O(1) residual by ~1e-5, well inside rtol=atol=1e-4."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import FacilityLocation, FeatureCoverage
        from repro.core.distributed import ss_sparsify_sharded
        from repro.core.distributed import make_mesh
        from jax.sharding import PartitionSpec as P

        mesh = make_mesh((8,), ("data",))
        key = jax.random.PRNGKey(0)
        W = jax.random.uniform(key, (512, 64))
        fns = [FeatureCoverage(W=W, phi="sqrt"),
               FeatureCoverage(W=W, phi="satcov", alpha=0.3),
               FeatureCoverage(W=W, feat_w=jnp.linspace(0.5, 1.5, 64)),
               FacilityLocation.from_features(
                   jax.random.normal(key, (512, 8)), kernel="cosine")]
        for fn in fns:
            # per-shard residuals == dense residuals
            arrays, specs, rebuild = fn.shard_pack(("data",))
            def res_kernel(*arrs):
                loc = rebuild(*arrs)
                return loc.shard_residuals(loc.shard_init("data"))
            res = jax.shard_map(res_kernel, mesh=mesh, in_specs=specs,
                            out_specs=P("data"), check_vma=False)(*arrays)
            np.testing.assert_allclose(np.asarray(res),
                                       np.asarray(fn.residual_gains()),
                                       rtol=1e-4, atol=1e-4)
            # and the full sharded loop runs
            ss = ss_sparsify_sharded(fn, key, mesh)
            assert 0 < int(jnp.sum(ss.vprime)) < fn.n
        print("GENERIC_OK")
    """)
    assert "GENERIC_OK" in out


def test_sharded_stochastic_greedy_matches_dense_compact():
    """Acceptance: the distributed stochastic-greedy sampler (per-shard
    compact gains, replicated Gumbel frame, psum'd argmax) selects the
    *identical* set as the dense compact path under the same key, on a real
    8-device mesh, for both objective families — including the k > |alive|
    exhausted tail."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import (FacilityLocation, FeatureCoverage,
                                ShardedBackend, ss_sparsify, stochastic_greedy)
        from repro.core.distributed import make_mesh

        mesh = make_mesh((8,), ("data",))
        be = ShardedBackend(mesh=mesh)
        key = jax.random.PRNGKey(0)
        fns = [FeatureCoverage(W=jax.random.uniform(key, (512, 64))),
               FacilityLocation.from_features(
                   jax.random.normal(key, (512, 16)), kernel="cosine")]
        for i, fn in enumerate(fns):
            alive = ss_sparsify(fn, jax.random.fold_in(key, i), r=6).vprime
            k2 = jax.random.PRNGKey(7 + i)
            dense = stochastic_greedy(fn, 10, k2, alive=alive,
                                      backend="oracle")
            shard = stochastic_greedy(fn, 10, k2, alive=alive, backend=be)
            assert (np.asarray(dense.selected)
                    == np.asarray(shard.selected)).all(), (
                dense.selected, shard.selected)
            np.testing.assert_allclose(np.asarray(dense.gains),
                                       np.asarray(shard.gains),
                                       rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(float(dense.value),
                                       float(shard.value), rtol=1e-5)
        # exhausted tail: k > |alive|
        fn = fns[0]
        small = jnp.arange(512) < 6
        k3 = jax.random.PRNGKey(3)
        dense = stochastic_greedy(fn, 9, k3, alive=small, backend="oracle")
        shard = stochastic_greedy(fn, 9, k3, alive=small, backend=be)
        assert (np.asarray(dense.selected)
                == np.asarray(shard.selected)).all()
        # ground frame: a live count that fits no sub-n bucket makes the
        # dense plan full-width; the sharded sampler must match that too
        big = jax.random.permutation(jax.random.PRNGKey(4),
                                     jnp.arange(512) < 400)
        dense = stochastic_greedy(fn, 10, k3, alive=big, backend="oracle")
        shard = stochastic_greedy(fn, 10, k3, alive=big, backend=be)
        assert (np.asarray(dense.selected)
                == np.asarray(shard.selected)).all()
        print("STOCH_PARITY")
    """)
    assert "STOCH_PARITY" in out


def test_sharded_exact_greedy_matches_dense():
    """Acceptance: greedy(backend="sharded") runs the distributed exact
    argmax (psum'd max-gain, min-position tie-break) over the same compact
    frame as the stochastic sampler, and is *selection-identical* to the
    dense compact path — both objective families, full-width / exhausted /
    conditional-state edges, on a real 8-device mesh."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import (FacilityLocation, FeatureCoverage,
                                ShardedBackend, greedy, ss_sparsify)
        from repro.core.distributed import make_mesh

        mesh = make_mesh((8,), ("data",))
        be = ShardedBackend(mesh=mesh)
        key = jax.random.PRNGKey(0)
        fns = [FeatureCoverage(W=jax.random.uniform(key, (512, 64))),
               FacilityLocation.from_features(
                   jax.random.normal(key, (512, 16)), kernel="cosine")]
        def check(fn, k, **kw):
            d = greedy(fn, k, backend="oracle", **kw)
            sh = greedy(fn, k, backend=be, **kw)
            assert (np.asarray(d.selected) == np.asarray(sh.selected)).all(), (
                d.selected, sh.selected)
            np.testing.assert_allclose(np.asarray(d.gains),
                                       np.asarray(sh.gains),
                                       rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(float(d.value), float(sh.value),
                                       rtol=1e-5)
        for i, fn in enumerate(fns):
            alive = ss_sparsify(fn, jax.random.fold_in(key, i), r=6).vprime
            check(fn, 10, alive=alive)          # compact frame
        fn = fns[0]
        check(fn, 6)                            # full width, alive=None
        check(fn, 7, alive=jnp.arange(512) < 4) # exhausted tail
        st = fn.add_many(fn.empty_state(), jnp.arange(512) < 3)
        alive = ss_sparsify(fn, key, r=6).vprime
        check(fn, 5, alive=alive, state=st)     # conditional start
        print("EXACT_PARITY")
    """)
    assert "EXACT_PARITY" in out


def test_sharded_ss_conditional_and_importance():
    """Conditional (state != empty) and importance-sampling SS run sharded
    (ROADMAP open item) with quality parity against the oracle backend: the
    greedy value on the sharded V' matches the oracle V' value closely
    (different probe streams — sampling variance, not arithmetic)."""
    out = run_sub("""
        import jax, jax.numpy as jnp
        from repro.core import (FacilityLocation, FeatureCoverage,
                                ShardedBackend, greedy, ss_sparsify)
        from repro.core.distributed import make_mesh

        mesh = make_mesh((8,), ("data",))
        be = ShardedBackend(mesh=mesh)
        key = jax.random.PRNGKey(0)
        fns = [FeatureCoverage(W=jax.random.uniform(key, (512, 64))),
               FacilityLocation.from_features(
                   jax.random.normal(key, (512, 16)), kernel="cosine")]
        for i, fn in enumerate(fns):
            st = fn.add_many(fn.empty_state(), jnp.arange(512) < 4)
            ss_s = ss_sparsify(fn, key, backend=be, state=st)
            ss_o = ss_sparsify(fn, key, backend="oracle", state=st)
            assert 0 < int(jnp.sum(ss_s.vprime)) < 512
            v_s = float(greedy(fn, 8, alive=ss_s.vprime, state=st).value)
            v_o = float(greedy(fn, 8, alive=ss_o.vprime, state=st).value)
            rel = abs(v_s - v_o) / abs(v_o)
            assert rel < 2e-2, (i, "state", v_s, v_o)
            ss_s = ss_sparsify(fn, key, backend=be, importance=True)
            ss_o = ss_sparsify(fn, key, backend="oracle", importance=True)
            v_s = float(greedy(fn, 8, alive=ss_s.vprime).value)
            v_o = float(greedy(fn, 8, alive=ss_o.vprime).value)
            rel = abs(v_s - v_o) / abs(v_o)
            assert rel < 2e-2, (i, "importance", v_s, v_o)
        print("COND_IMP_OK")
    """)
    assert "COND_IMP_OK" in out


def test_compressed_pod_training_converges():
    out = run_sub("""
        import jax, jax.numpy as jnp
        from repro import configs
        from repro.train import (TrainConfig, make_train_state, CompressConfig,
                                 init_error_state, make_compressed_train_step)

        from repro.core.distributed import make_mesh
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        cfg = configs.smoke("llama3.2-3b")
        tc = TrainConfig(optimizer="adamw", lr=1e-3, warmup_steps=1,
                         total_steps=20)
        cc = CompressConfig(ratio=0.1, block=64)
        state = make_train_state(jax.random.PRNGKey(0), cfg, tc)
        state["error"] = init_error_state(state["params"])
        with jax.set_mesh(mesh):
            step = jax.jit(make_compressed_train_step(mesh, cfg, tc, cc))
            toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                      cfg.vocab_size)
            batch = {"tokens": toks, "labels": toks}
            losses = []
            for _ in range(6):
                state, m = step(state, batch)
                losses.append(float(m["loss"]))
        assert losses[-1] < losses[0], losses
        assert 0.0 < float(m["compress_density"]) <= 0.15
        print("LOSSES", [round(l, 3) for l in losses])
    """)
    assert "LOSSES" in out


def test_sharded_train_step_on_mesh():
    """The production train step lowers, compiles AND RUNS on a 2x2 mesh
    with real (tiny) data — catches sharding bugs execution-side."""
    out = run_sub("""
        import jax, jax.numpy as jnp
        from repro import configs
        from repro.train import (TrainConfig, abstract_train_state,
                                 make_train_state, shard_train_step)
        from repro.launch.mesh import make_test_mesh

        mesh = make_test_mesh((2, 2), ("data", "model"))
        cfg = configs.smoke("olmoe-1b-7b")      # MoE: the hardest layout
        tc = TrainConfig(optimizer="adafactor", num_microbatches=2,
                         warmup_steps=1, total_steps=8, lr=1e-3)
        shape = abstract_train_state(cfg, tc)
        with jax.set_mesh(mesh):
            fn, state_sh, batch_sh = shard_train_step(mesh, cfg, tc, shape)
            state = make_train_state(jax.random.PRNGKey(0), cfg, tc)
            state = jax.device_put(state, state_sh)
            toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                      cfg.vocab_size)
            batch = {"tokens": toks, "labels": toks}
            l0 = lf = None
            for _ in range(4):
                state, m = fn(state, batch)
                l0 = l0 if l0 is not None else float(m["loss"])
                lf = float(m["loss"])
        assert lf < l0, (l0, lf)
        print("OK", l0, "->", lf)
    """)
    assert "OK" in out


def test_dryrun_cell_small_mesh():
    """The dry-run machinery end-to-end on a (2,2,2) multi-pod mesh with a
    reduced shape table — validates lower+compile+analysis off the 512-dev
    path."""
    out = run_sub("""
        import jax
        from repro.launch.mesh import make_test_mesh
        from repro.launch import dryrun
        from repro.models.config import SHAPES, ShapeConfig

        SHAPES["decode_32k"] = ShapeConfig("decode_32k", 512, 8, "decode")
        SHAPES["train_4k"] = ShapeConfig("train_4k", 128, 8, "train")
        mesh = make_test_mesh((2, 2, 2), ("pod", "data", "model"))
        for arch, shape in [("recurrentgemma-2b", "decode_32k"),
                            ("qwen3-4b", "train_4k")]:
            rec = dryrun.run_cell(arch, shape, mesh, "test")
            assert rec["status"] == "ok"
            assert rec["cost"]["flops_per_chip"] > 0
            assert rec["roofline"]["dominant"] in ("compute", "memory",
                                                   "collective")
        print("CELLS OK")
    """, timeout=540)
    assert "CELLS OK" in out
