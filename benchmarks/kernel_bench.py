"""Pallas kernel microbench: correctness (interpret mode vs jnp oracle) plus
the roofline-derived TPU expectations for the SS hot-spot kernels.

On this CPU container the kernels cannot be *timed* on real hardware; we
(1) verify interpret-mode output against the oracle on a shape sweep — the
    feature-coverage divergence/gains kernels (with and without ``feat_w``
    feature weights) and the facility-location divergence kernel,
(2) verify the unified backend dispatch layer (``repro.core.backend``) —
    oracle vs pallas divergence/gains through the same ``backend=`` routing
    every entry point uses, on both objective families, and
(3) report each kernel's arithmetic intensity and the v5e-roofline time its
    BlockSpec tiling implies, next to the measured wall time of the jnp
    reference path (the thing the kernel replaces).

``--smoke`` runs a single small shape per kernel — the CI regression gate.
``--json PATH`` writes every row (each carrying a stable ``bench_key`` and a
warm ``wall_s`` wall time) to PATH; ``--baseline PATH`` compares the fresh
rows against a previously committed JSON (``BENCH_kernels.json`` at the repo
root is the CI baseline) and exits nonzero on a >``--max-ratio`` per-kernel
wall-time regression.
"""

from __future__ import annotations

import argparse
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import save, timed
from repro.core import (
    FacilityLocation,
    FeatureCoverage,
    bucket_schedule,
    get_backend,
)
from repro.kernels.feature_gains import feature_gains_kernel
from repro.kernels.fl_divergence import fl_divergence_kernel
from repro.kernels.ref import (
    feature_gains_ref,
    fl_divergence_ref,
    ss_divergence_ref,
)
from repro.kernels.ss_weights import ss_divergence_kernel
from repro.launch.mesh import HW

SS_SHAPES = [(2048, 512, 64), (4096, 1024, 96), (8192, 512, 104)]
SS_SHAPES_SMOKE = [(512, 128, 24)]
FG_SHAPES = [(4096, 512), (16384, 1024)]
FG_SHAPES_SMOKE = [(512, 128)]
# facility location: (n, r) — the sim matrix is (n, n)
FL_SHAPES = [(1024, 64), (1536, 48)]
FL_SHAPES_SMOKE = [(256, 16)]
# matrix-free facility location: (n, d, r) dense-parity shapes plus
# (n, d) streaming-only shapes at n past the dense from_features guard
FLS_SHAPES = [(1024, 16, 64), (1536, 16, 48)]
FLS_SHAPES_SMOKE = [(256, 16, 16)]
FLS_LARGE = [(65536, 16)]
FLS_LARGE_SMOKE = [(32768, 16)]


def _feat_w(F: int) -> jax.Array:
    return jnp.linspace(0.5, 1.5, F)


def run(seed: int = 0, smoke: bool = False) -> dict:
    key = jax.random.PRNGKey(seed)
    rows = []
    for (n, F, r) in (SS_SHAPES_SMOKE if smoke else SS_SHAPES):
        W = jax.random.uniform(key, (n, F))
        CU = jax.random.uniform(jax.random.fold_in(key, 1), (r, F))
        resid = jax.random.uniform(jax.random.fold_in(key, 2), (r,))
        for weighted in (False, True):
            fw = _feat_w(F) if weighted else None
            phis = jnp.sqrt(CU) if fw is None else jnp.sqrt(CU) * fw
            phi_cu = jnp.sum(phis, axis=-1)
            name = "ss_divergence_featw" if weighted else "ss_divergence"

            ref, t_ref = timed(lambda: jax.block_until_ready(
                ss_divergence_ref(W, CU, phi_cu, resid, None, "sqrt", fw)))
            out, t_int = timed(lambda: jax.block_until_ready(
                ss_divergence_kernel(W, CU, phi_cu, resid, None, fw,
                                     phi="sqrt", interpret=True)), repeat=3)
            err = float(jnp.max(jnp.abs(ref - out)))
            assert err < 1e-3, f"kernel/oracle divergence mismatch: {err}"

            # roofline for the kernel's HBM traffic: one read of W + CU + out
            bytes_moved = (n * F + r * F + n) * 4
            flops = 2.0 * r * n * F        # add + sqrt per (probe, cand, feat)
            t_mem = bytes_moved / HW["hbm_bw"]
            t_cmp = flops / HW["peak_flops_bf16"]
            rows.append({
                "kernel": name, "n": n, "F": F, "r": r,
                "bench_key": f"{name}/n{n}-F{F}-r{r}", "wall_s": t_int,
                "max_err": err, "t_jnp_cpu_s": t_ref, "t_interp_s": t_int,
                "tpu_bytes": bytes_moved, "tpu_flops": flops,
                "tpu_roofline_s": max(t_mem, t_cmp),
                "arithmetic_intensity": flops / bytes_moved,
            })
            print(f"kernel {name} n={n} F={F} r={r} err={err:.2e} "
                  f"cpu_ref={t_ref*1e3:.1f}ms "
                  f"tpu_bound={max(t_mem, t_cmp)*1e6:.1f}µs",
                  flush=True)

    for (n, F) in (FG_SHAPES_SMOKE if smoke else FG_SHAPES):
        W = jax.random.uniform(key, (n, F))
        c = jax.random.uniform(jax.random.fold_in(key, 3), (F,))
        for weighted in (False, True):
            fw = _feat_w(F) if weighted else None
            phic = jnp.sum(jnp.sqrt(c) if fw is None else jnp.sqrt(c) * fw)
            name = "feature_gains_featw" if weighted else "feature_gains"
            ref, t_ref = timed(lambda: jax.block_until_ready(
                feature_gains_ref(W, c, phic, None, "sqrt", fw)))
            out, t_int = timed(lambda: jax.block_until_ready(
                feature_gains_kernel(W, c, phic, None, fw,
                                     phi="sqrt", interpret=True)), repeat=3)
            err = float(jnp.max(jnp.abs(ref - out)))
            assert err < 1e-3, f"feature_gains kernel mismatch: {err}"
            bytes_moved = (n * F + F + n) * 4
            flops = 2.0 * n * F
            rows.append({
                "kernel": name, "n": n, "F": F,
                "bench_key": f"{name}/n{n}-F{F}", "wall_s": t_int,
                "max_err": err, "t_jnp_cpu_s": t_ref, "t_interp_s": t_int,
                "tpu_bytes": bytes_moved, "tpu_flops": flops,
                "tpu_roofline_s": max(bytes_moved / HW["hbm_bw"],
                                      flops / HW["peak_flops_bf16"]),
                "arithmetic_intensity": flops / bytes_moved,
            })
            print(f"kernel {name} n={n} F={F} err={err:.2e} "
                  f"cpu_ref={t_ref*1e3:.1f}ms", flush=True)
    save("kernel_bench", rows)
    return {"rows": rows}


def run_fl(seed: int = 0, smoke: bool = False) -> dict:
    """Facility-location divergence kernel: interpret-mode parity vs the jnp
    oracle + the v5e roofline of its (candidates x served rows) tiling."""
    key = jax.random.PRNGKey(seed)
    rows = []
    for (n, r) in (FL_SHAPES_SMOKE if smoke else FL_SHAPES):
        X = jax.random.normal(key, (n, 16))
        fn = FacilityLocation.from_features(X, kernel="cosine")
        probes = jnp.arange(0, n, max(1, n // r))[:r]
        MU = jnp.maximum(fn.sim[:, probes].T, 0.0)               # (r, n)
        resid = fn.residual_gains()[probes]

        ref, t_ref = timed(lambda: jax.block_until_ready(
            fl_divergence_ref(fn.sim, MU, resid)))
        out, t_int = timed(lambda: jax.block_until_ready(
            fl_divergence_kernel(fn.sim, MU, resid, interpret=True)),
            repeat=3)
        err = float(jnp.max(jnp.abs(ref - out)))
        assert err < 1e-3, f"fl_divergence kernel mismatch: {err}"

        # kernel HBM traffic: one read of sim + MU + the (n,) result; the
        # naive path round-trips the (r, n, n) max tensor through HBM.
        bytes_moved = (n * n + r * n + n) * 4
        flops = 2.0 * r * n * n            # compare + accumulate per element
        t_mem = bytes_moved / HW["hbm_bw"]
        t_cmp = flops / HW["peak_flops_bf16"]
        rows.append({
            "kernel": "fl_divergence", "n": n, "r": r,
            "bench_key": f"fl_divergence/n{n}-r{r}", "wall_s": t_int,
            "max_err": err, "t_jnp_cpu_s": t_ref, "t_interp_s": t_int,
            "tpu_bytes": bytes_moved, "tpu_flops": flops,
            "tpu_roofline_s": max(t_mem, t_cmp),
            "arithmetic_intensity": flops / bytes_moved,
            "naive_hbm_bytes": 8.0 * r * n * n,
        })
        print(f"kernel fl_divergence n={n} r={r} err={err:.2e} "
              f"cpu_ref={t_ref*1e3:.1f}ms tpu_bound={max(t_mem, t_cmp)*1e6:.1f}µs",
              flush=True)
    save("kernel_fl", rows)
    return {"rows": rows}


def run_fl_stream(seed: int = 0, smoke: bool = False) -> dict:
    """Matrix-free facility location (kernels/fl_stream.py):

    (1) streaming-vs-dense parity at dense-feasible n — the interpret-mode
        fl_stream kernel (similarity tiles computed on the fly from the
        (n, d) rows) against the dense fl_divergence_ref on the same
        features; wall_s is the interpret-mode kernel time, gated like
        every other kernel row;
    (2) streaming-only large-n rows timing the jitted lax.scan block
        reference at n past the dense ``from_features`` guard (a 4+ GiB
        sim matrix) — the regime the kernel exists for, so there is no
        dense reference; the row pins the oracle streaming path's wall
        time instead."""
    from repro.core import StreamingFacilityLocation
    from repro.data import clustered_embeddings
    from repro.kernels.fl_stream import (
        fl_stream_divergence_kernel,
        fl_stream_divergence_ref,
    )

    key = jax.random.PRNGKey(seed)
    rows = []
    for (n, d, r) in (FLS_SHAPES_SMOKE if smoke else FLS_SHAPES):
        X = jax.random.normal(key, (n, d))
        dense = FacilityLocation.from_features(X, kernel="cosine")
        sfl = StreamingFacilityLocation.from_features(X, kernel="cosine")
        probes = jnp.arange(0, n, max(1, n // r))[:r]
        MU = jnp.maximum(sfl.X @ sfl.X[probes].T, 0.0).T          # (r, n)
        resid = dense.residual_gains()[probes]

        ref, t_ref = timed(lambda: jax.block_until_ready(
            fl_divergence_ref(dense.sim, MU, resid)))
        out, t_int = timed(lambda: jax.block_until_ready(
            fl_stream_divergence_kernel(sfl.X, MU, resid, interpret=True)),
            repeat=3)
        err = float(jnp.max(jnp.abs(ref - out)))
        assert err < 1e-3, f"fl_stream kernel vs dense mismatch: {err}"

        # kernel HBM traffic: the embedding rows + MU + the (n,) result —
        # the (n, n) sim matrix never exists (dense fl_divergence reads it).
        bytes_moved = (2 * n * d + r * n + n) * 4
        flops = 2.0 * n * n * d + 2.0 * r * n * n  # tile matmul + hinge
        t_mem = bytes_moved / HW["hbm_bw"]
        t_cmp = flops / HW["peak_flops_bf16"]
        rows.append({
            "kernel": "fl_stream", "n": n, "d": d, "r": r,
            "bench_key": f"fl_stream/n{n}-d{d}-r{r}", "wall_s": t_int,
            "max_err": err, "t_jnp_dense_cpu_s": t_ref, "t_interp_s": t_int,
            "tpu_bytes": bytes_moved, "tpu_flops": flops,
            "tpu_roofline_s": max(t_mem, t_cmp),
            "arithmetic_intensity": flops / bytes_moved,
            "dense_hbm_bytes": (n * n + r * n + n) * 4.0,
        })
        print(f"kernel fl_stream n={n} d={d} r={r} err={err:.2e} "
              f"dense_ref={t_ref*1e3:.1f}ms "
              f"tpu_bound={max(t_mem, t_cmp)*1e6:.1f}µs", flush=True)

    for (n, d) in (FLS_LARGE_SMOKE if smoke else FLS_LARGE):
        r = 4
        X = jnp.asarray(clustered_embeddings(seed, n, d))
        sfl = StreamingFacilityLocation.from_features(X, kernel="dot")
        probes = jnp.arange(0, n, n // r)[:r]
        MU = jnp.maximum(sfl.X @ sfl.X[probes].T, 0.0).T          # (r, n)
        resid = jnp.zeros((r,), jnp.float32)
        div = jax.jit(fl_stream_divergence_ref)
        out, t_blk = timed(lambda: jax.block_until_ready(
            div(sfl.X, MU, resid)), repeat=2)
        assert out.shape == (n,) and bool(jnp.all(jnp.isfinite(out)))
        rows.append({
            "kernel": "fl_stream_large", "n": n, "d": d, "r": r,
            "bench_key": f"fl_stream_large/n{n}-d{d}", "wall_s": t_blk,
            "t_block_ref_s": t_blk,
            "dense_sim_bytes": 4.0 * n * n,   # what this row never allocates
            "stream_bytes": 4.0 * n * d,
        })
        print(f"kernel fl_stream_large n={n} d={d} block_ref={t_blk:.2f}s "
              f"(dense sim would be {4.0 * n * n / 2**30:.1f} GiB; "
              f"streaming holds {4.0 * n * d / 2**20:.1f} MiB)", flush=True)
    save("kernel_fl_stream", rows)
    return {"rows": rows}


def run_compact(seed: int = 0, smoke: bool = False) -> dict:
    """Shrink-aware compacted divergence + compact selection gains: wall time
    must track the live count (the bucket size), not the ground-set size n.

    For every bucket of the SS shrink schedule, gathers a live set of that
    size and times the compact-candidate kernel path through the backend
    dispatch (``divergence_compact`` for the SS round, ``gains_compact`` for
    the per-step cost of the compact selection engine — greedy and
    stochastic greedy share that primitive), asserting elementwise parity
    against the full-n output.  The ``*-full`` row is the same-process full-n
    reference the compacted ratios are taken against; at c = 8 the round-2+
    buckets (live <= n/sqrt(c)) are the acceptance shapes."""
    key = jax.random.PRNGKey(seed)
    be = get_backend("pallas")
    rows = []

    def bench_objective(fam: str, fn, r: int, extra: dict):
        n = fn.n
        probes = jnp.arange(0, n, max(1, n // r))[:r]
        residual = fn.residual_gains()
        full, t_full = timed(lambda: jax.block_until_ready(
            be.divergence(fn, probes, residual=residual)), repeat=3)
        shape_tag = "-".join(f"{k}{v}" for k, v in extra.items())
        rows.append({
            "kernel": f"{fam}_compact", **extra, "k": n,
            "bench_key": f"{fam}_compact/{shape_tag}-full", "wall_s": t_full,
            "ratio_vs_full": 1.0,
        })
        perm = jax.random.permutation(jax.random.fold_in(key, 11), n)
        live_pool = perm[~jnp.isin(perm, probes)]   # live set excludes probes
        for j, size in enumerate(bucket_schedule(n, 8.0)):
            if size >= n:
                continue
            cand_idx = jnp.sort(live_pool[:size])
            out, t_c = timed(lambda: jax.block_until_ready(
                be.divergence_compact(
                    fn, probes, cand_idx, residual=residual)), repeat=3)
            err = float(jnp.max(jnp.abs(out - full[cand_idx])))
            assert err < 1e-3, f"{fam} compact/full mismatch (k={size}): {err}"
            rows.append({
                "kernel": f"{fam}_compact", **extra, "k": int(size),
                "bench_key": f"{fam}_compact/{shape_tag}-k{size}",
                "wall_s": t_c, "max_err": err, "round_geq": j,
                "t_full_s": t_full, "ratio_vs_full": t_c / t_full,
            })
            print(f"kernel {fam}_compact {shape_tag} k={size} (round>={j}) "
                  f"err={err:.2e} {t_c*1e3:.1f}ms vs full {t_full*1e3:.1f}ms "
                  f"= {t_c / t_full:.2f}x", flush=True)

    def bench_gains(fam: str, fn, extra: dict):
        """Per-step selection cost: ``gains_compact`` vs full ``gains``
        through the backend dispatch — the exact call greedy/stochastic
        greedy issue every step on the compact path."""
        n = fn.n
        state = fn.add_many(fn.empty_state(), jnp.arange(n) < 8)
        full, t_full = timed(lambda: jax.block_until_ready(
            be.gains(fn, state)), repeat=3)
        shape_tag = "-".join(f"{k}{v}" for k, v in extra.items())
        rows.append({
            "kernel": "gains_compact", "objective": fam, **extra, "k": n,
            "bench_key": f"gains_compact/{fam}-{shape_tag}-full",
            "wall_s": t_full, "ratio_vs_full": 1.0,
        })
        perm = jax.random.permutation(jax.random.fold_in(key, 17), n)
        for j, size in enumerate(bucket_schedule(n, 8.0)):
            if size >= n:
                continue
            cand_idx = jnp.sort(perm[:size])
            out, t_c = timed(lambda: jax.block_until_ready(
                be.gains_compact(fn, state, cand_idx)), repeat=3)
            err = float(jnp.max(jnp.abs(out - full[cand_idx])))
            assert err < 1e-3, f"{fam} gains compact/full mismatch (k={size}): {err}"
            rows.append({
                "kernel": "gains_compact", "objective": fam, **extra,
                "k": int(size),
                "bench_key": f"gains_compact/{fam}-{shape_tag}-k{size}",
                "wall_s": t_c, "max_err": err, "round_geq": j,
                "t_full_s": t_full, "ratio_vs_full": t_c / t_full,
            })
            print(f"kernel gains_compact [{fam}] {shape_tag} k={size} "
                  f"err={err:.2e} {t_c*1e3:.1f}ms vs full {t_full*1e3:.1f}ms "
                  f"= {t_c / t_full:.2f}x", flush=True)

    for (n, F, r) in (SS_SHAPES_SMOKE if smoke else SS_SHAPES):
        W = jax.random.uniform(key, (n, F))
        bench_objective("ss_divergence", FeatureCoverage(W=W, phi="sqrt"), r,
                        {"n": n, "F": F, "r": r})
    for (n, r) in (FL_SHAPES_SMOKE if smoke else FL_SHAPES):
        X = jax.random.normal(jax.random.fold_in(key, 5), (n, 16))
        bench_objective("fl_divergence",
                        FacilityLocation.from_features(X, kernel="cosine"), r,
                        {"n": n, "r": r})

    for (n, F) in (FG_SHAPES_SMOKE if smoke else FG_SHAPES):
        W = jax.random.uniform(jax.random.fold_in(key, 19), (n, F))
        bench_gains("fc", FeatureCoverage(W=W, phi="sqrt"), {"n": n, "F": F})
    for (n, _) in (FL_SHAPES_SMOKE if smoke else FL_SHAPES):
        X = jax.random.normal(jax.random.fold_in(key, 23), (n, 16))
        bench_gains("fl", FacilityLocation.from_features(X, kernel="cosine"),
                    {"n": n})

    # feature_gains compact-grid path (greedy's inner loop over a live subset)
    for (n, F) in (FG_SHAPES_SMOKE if smoke else FG_SHAPES[:1]):
        W = jax.random.uniform(key, (n, F))
        c = jax.random.uniform(jax.random.fold_in(key, 3), (F,))
        phic = jnp.sum(jnp.sqrt(c))
        full, t_full = timed(lambda: jax.block_until_ready(
            feature_gains_kernel(W, c, phic, phi="sqrt", interpret=True)),
            repeat=3)
        size = bucket_schedule(n, 8.0)[1] if n > 128 else n
        cand_idx = jnp.sort(
            jax.random.permutation(jax.random.fold_in(key, 13), n)[:size])
        out, t_c = timed(lambda: jax.block_until_ready(
            feature_gains_kernel(W, c, phic, None, None, cand_idx,
                                 phi="sqrt", interpret=True)), repeat=3)
        err = float(jnp.max(jnp.abs(out - full[cand_idx])))
        assert err < 1e-3, f"feature_gains compact mismatch: {err}"
        rows.append({
            "kernel": "feature_gains_compact", "n": n, "F": F, "k": int(size),
            "bench_key": f"feature_gains_compact/n{n}-F{F}-k{size}",
            "wall_s": t_c, "max_err": err, "t_full_s": t_full,
            "ratio_vs_full": t_c / t_full,
        })
        print(f"kernel feature_gains_compact n={n} F={F} k={size} "
              f"err={err:.2e} {t_c / t_full:.2f}x vs full", flush=True)
    save("kernel_compact", rows)
    return {"rows": rows}


def run_dispatch(seed: int = 0, smoke: bool = False) -> dict:
    """Backend dispatch parity: oracle vs pallas through repro.core.backend —
    the exact routing ss_sparsify/greedy use — on real objectives, covering
    every objective family the pallas backend now fuses (plain and feat_w
    feature coverage, facility location)."""
    n, F, r = (512, 128, 24) if smoke else (2048, 256, 64)
    n_fl = 256 if smoke else 1024
    key = jax.random.PRNGKey(seed)
    W = jax.random.uniform(key, (n, F))
    objectives = {
        "fc": FeatureCoverage(W=W, phi="sqrt"),
        "fc_featw": FeatureCoverage(W=W, feat_w=_feat_w(F), phi="sqrt"),
        "fl": FacilityLocation.from_features(
            jax.random.normal(jax.random.fold_in(key, 7), (n_fl, 16)),
            kernel="cosine"),
    }

    rows = []
    for name, fn in objectives.items():
        probes = jnp.arange(0, fn.n, max(1, fn.n // r))[:r]
        residual = fn.residual_gains()
        ref, t_o = timed(lambda: jax.block_until_ready(
            get_backend("oracle").divergence(fn, probes, residual=residual)))
        out, t_p = timed(lambda: jax.block_until_ready(
            get_backend("pallas").divergence(fn, probes, residual=residual)),
            repeat=3)
        live = np.ones((fn.n,), bool)
        live[np.asarray(probes)] = False
        err = float(np.max(np.abs(
            np.asarray(ref)[live] - np.asarray(out)[live])))
        assert err < 1e-3, f"backend dispatch divergence mismatch ({name}): {err}"
        rows.append({"op": "divergence", "objective": name, "n": fn.n, "r": r,
                     "bench_key": f"dispatch_divergence/{name}-n{fn.n}-r{r}",
                     "wall_s": t_p,
                     "max_err": err, "t_oracle_s": t_o, "t_pallas_s": t_p})
        print(f"dispatch divergence [{name}] n={fn.n} r={r} err={err:.2e}",
              flush=True)

        state = fn.add_many(fn.empty_state(), jnp.arange(fn.n) < 8)
        ref, t_o = timed(lambda: jax.block_until_ready(
            get_backend("oracle").gains(fn, state)))
        out, t_p = timed(lambda: jax.block_until_ready(
            get_backend("pallas").gains(fn, state)), repeat=3)
        err = float(jnp.max(jnp.abs(ref - out)))
        assert err < 1e-3, f"backend dispatch gains mismatch ({name}): {err}"
        rows.append({"op": "gains", "objective": name, "n": fn.n,
                     "bench_key": f"dispatch_gains/{name}-n{fn.n}",
                     "wall_s": t_p,
                     "max_err": err, "t_oracle_s": t_o, "t_pallas_s": t_p})
        print(f"dispatch gains [{name}] n={fn.n} err={err:.2e}", flush=True)
    save("kernel_dispatch", rows)
    return {"rows": rows}


def run_flash(seed: int = 0, smoke: bool = False) -> dict:
    """flash_attention kernel: correctness + v5e roofline of its tiling vs
    the XLA blockwise path's HBM-resident intermediates."""
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.ref import flash_attention_ref

    rows = []
    shapes = [(4, 256, 64)] if smoke else [(8, 512, 128), (4, 1024, 128)]
    for (BH, S, hd) in shapes:
        key = jax.random.PRNGKey(seed)
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (BH, S, hd), jnp.float32)
        k = jax.random.normal(ks[1], (BH, S, hd), jnp.float32)
        v = jax.random.normal(ks[2], (BH, S, hd), jnp.float32)
        ref, t_ref = timed(lambda: jax.block_until_ready(
            flash_attention_ref(q, k, v)))
        out, t_int = timed(lambda: jax.block_until_ready(
            flash_attention(q, k, v, bq=256, bk=256, interpret=True)),
            repeat=3)
        err = float(jnp.max(jnp.abs(out - ref)))
        assert err < 1e-2, f"flash_attention kernel mismatch: {err}"
        # kernel HBM traffic: q+k+v read + out write (causal ~half the flops)
        io_bytes = 4 * BH * S * hd * 4
        flops = 2 * 2 * BH * S * S * hd / 2
        # XLA path additionally round-trips every (bq, bk) f32 score tile +
        # softmax temps: >= 3 extra writes/reads of S*S scores per head
        xla_extra = 3 * BH * S * S * 4
        rows.append({
            "kernel": "flash_attention", "BH": BH, "S": S, "hd": hd,
            "bench_key": f"flash_attention/BH{BH}-S{S}-hd{hd}", "wall_s": t_int,
            "max_err": err, "t_jnp_cpu_s": t_ref,
            "tpu_bytes_kernel": io_bytes,
            "tpu_bytes_xla_path": io_bytes + xla_extra,
            "hbm_traffic_reduction": (io_bytes + xla_extra) / io_bytes,
            "tpu_roofline_s": max(io_bytes / HW["hbm_bw"],
                                  flops / HW["peak_flops_bf16"]),
        })
        print(f"kernel flash_attention BH={BH} S={S} hd={hd} err={err:.2e} "
              f"hbm_reduction={rows[-1]['hbm_traffic_reduction']:.1f}x",
              flush=True)
    save("kernel_flash", rows)
    return {"rows": rows}


def run_all(seed: int = 0, smoke: bool = False) -> list[dict]:
    """All kernel benches, flattened to one row list (the --json payload)."""
    rows = []
    rows += run(seed, smoke)["rows"]
    rows += run_fl(seed, smoke)["rows"]
    rows += run_fl_stream(seed, smoke)["rows"]
    rows += run_compact(seed, smoke)["rows"]
    rows += run_dispatch(seed, smoke)["rows"]
    rows += run_flash(seed, smoke)["rows"]
    return rows


def check_regression(
    rows: list[dict], baseline_path: str, max_ratio: float = 2.0,
    abs_floor: float = 0.010, key_ok=None,
) -> tuple[int, int]:
    """Compare fresh ``wall_s`` per ``bench_key`` against a committed baseline
    JSON.  Returns ``(regressed, unmeasured)``: kernels slower than
    ``max_ratio`` x baseline, and baseline keys the fresh run did not measure
    at all (a partial local run, or a kernel/shape that was removed) — kept
    separate so callers can report them honestly rather than as regressions.
    New fresh keys with no baseline are informational — they enter the
    trajectory on the next baseline refresh.

    ``key_ok`` (optional predicate on bench_key) restricts the comparison to
    a slice of the baseline — used by invocations that measure one axis of a
    shared baseline file (e.g. fig1's ``--objective`` split of
    BENCH_e2e.json), so keys belonging to the other axes don't count as
    unmeasured.

    A key fails only when it regresses both *relatively* (> max_ratio) and
    *absolutely* (> abs_floor seconds over baseline): sub-10ms interpret-mode
    timings are dominated by timer/machine noise, while the regressions the
    gate exists for (a fusion silently breaking, an accidental O(r n^2)
    materialization) blow wall time up by far more than the floor."""
    with open(baseline_path) as f:
        base = {row["bench_key"]: row for row in json.load(f)["rows"]
                if key_ok is None or key_ok(row["bench_key"])}
    fresh = {row["bench_key"]: row for row in rows
             if "bench_key" in row
             and (key_ok is None or key_ok(row["bench_key"]))}
    violations = 0
    unmeasured = 0
    for key in sorted(base):
        if key not in fresh:
            print(f"regression-gate: baseline key {key} not measured "
                  f"(partial run, or kernel removed / shapes changed?)",
                  flush=True)
            unmeasured += 1
            continue
        b, fr = base[key]["wall_s"], fresh[key]["wall_s"]
        ratio = fr / b if b > 0 else float("inf")
        bad = ratio > max_ratio and (fr - b) > abs_floor
        flag = "FAIL" if bad else (
            "ok (noise floor)" if ratio > max_ratio else "ok")
        print(f"regression-gate: {key:48s} {b*1e3:8.1f}ms -> {fr*1e3:8.1f}ms "
              f"({ratio:4.2f}x) {flag}", flush=True)
        if bad:
            violations += 1
    for key in sorted(set(fresh) - set(base)):
        print(f"regression-gate: new kernel {key} (no baseline yet)",
              flush=True)
    return violations, unmeasured


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="one small shape per kernel (CI regression gate)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write all rows (bench_key + wall_s) to PATH")
    ap.add_argument("--baseline", default=None, metavar="PATH",
                    help="committed baseline JSON to gate wall times against")
    ap.add_argument("--max-ratio", type=float, default=2.0,
                    help="fail when wall_s exceeds baseline * this ratio")
    ap.add_argument("--abs-floor", type=float, default=0.010,
                    help="seconds over baseline a key must also regress by "
                    "before it can fail (noise floor for sub-10ms timings)")
    args = ap.parse_args()
    rows = run_all(smoke=args.smoke)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"smoke": args.smoke, "rows": rows}, f, indent=1)
        print(f"wrote {len(rows)} rows to {args.json}", flush=True)
    if args.baseline:
        bad, unmeasured = check_regression(rows, args.baseline,
                                           args.max_ratio, args.abs_floor)
        if bad or unmeasured:
            print(f"regression-gate: {bad} kernel(s) regressed "
                  f">{args.max_ratio}x and {unmeasured} baseline key(s) "
                  f"unmeasured vs {args.baseline}", file=sys.stderr)
            return 1
        print("regression-gate: all kernels within "
              f"{args.max_ratio}x of baseline", flush=True)
    return 0


if __name__ == "__main__":
    from repro.compile_cache import setup_compile_cache

    setup_compile_cache()
    raise SystemExit(main())
