"""Pallas TPU kernel for the facility-location SS hot spot: fused
submodularity-graph divergence for  f(S) = sum_i max_{s in S} sim(i, s).

Computes   w_{U,v} = min_{u in U} [ f(v | S + u) - f(u | V \\ u) ]   for every
candidate v in one pass.  With the probe coverage rows
``mu[u, i] = max(state_i, sim[i, u])``, the probe-conditioned gain is a
hinge/accumulate reduction:

    f(v | S + u) = sum_i max(sim[i, v] - mu[u, i], 0)

so  w_{U,v} = min_u [ acc[u, v] - resid[u] ]  with
``acc[u, v] = sum_i max(sim[i, v] - mu[u, i], 0)``.  The kernel accumulates
the *hinge terms* directly (not ``sum_i max(sim, mu)`` minus the baseline
``sum_i mu`` afterwards): subtracting two O(n)-magnitude sums would lose the
small inter-candidate divergence gaps to f32 cancellation at exactly the
scales the kernel exists for.

Why a kernel: the naive computation materializes the (r, n, n) hinge tensor
in HBM (r probes — r'·log2 n with the paper's r' = 8 — n candidates, n served
rows).  At n = 1e6, r = 160 that is ~0.6 PB of f32 written and read back:
over a petabyte of HBM traffic per SS round.  The kernel tiles
(candidates x served rows) into VMEM, keeps the probe coverage block resident,
accumulates the served-row reduction in a VMEM scratch accumulator, and fuses
the final min-over-probes — so HBM traffic is exactly one read of ``sim``
(n x n) plus one write of the (n,) result: the roofline minimum.

Layout / tiling (TPU v5e target), mirroring :mod:`repro.kernels.ss_weights`:
  - grid = (n_blocks, i_blocks); candidate blocks are parallel, served-row
    blocks are a sequential reduction (dimension_semantics below).
  - sim tile (BI, BN) : BI=512 served rows x BN=256 candidates = 512 KB f32.
    The tile is indexed (j, i) — rows are the *reduction* dimension — so the
    kernel consumes ``sim`` in its natural (served row, candidate) layout and
    no transpose is ever materialized.
  - MU tile  (RP, BI) : all probe coverage rows resident per served-row block
    (RP = r padded to a multiple of the probe chunk).
  - acc      (RP, BN) f32 VMEM scratch, persistent across the i reduction.
  - out tile (1, BN)  written once, at the last served-row block.
Like the feature-coverage kernel, the reduction is a nonlinear (max) transform
— VPU work by nature; the win is HBM -> VMEM blocking, which dominates at
scale.

The pure-jnp reference lives in :func:`repro.kernels.ref.fl_divergence_ref`;
parity is enforced in interpret mode by tests/test_kernels.py and the CI
kernel-bench gate.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ss_weights import _round_up

Array = jax.Array


def _fl_divergence_kernel(
    sim_ref,     # (BI, BN) similarity tile: rows = served, cols = candidates
    mu_ref,      # (RP, BI) probe coverage tile
    resid_ref,   # (RP, 1)  probe residual gains (-INF for pad rows)
    out_ref,     # (1, BN)  divergence tile
    acc_ref,     # (RP, BN) f32 VMEM scratch accumulator
    *,
    n_i_blocks: int,
    probe_chunk: int,
):
    i_i = pl.program_id(1)

    @pl.when(i_i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    sim = sim_ref[...].astype(jnp.float32)    # (BI, BN)
    n_chunks = mu_ref.shape[0] // probe_chunk

    def body(j, carry):
        # Probe chunk (PC, BI) against the whole candidate tile (BI, BN):
        # contrib[p, v] = sum_i max(sim[i, v] - mu[p, i], 0).  The chunk is
        # sliced on the refs (not on loaded values), which Mosaic lowers.
        rows = pl.ds(pl.multiple_of(j * probe_chunk, probe_chunk), probe_chunk)
        mu_j = mu_ref[rows, :].astype(jnp.float32)
        val = jnp.maximum(sim[None, :, :] - mu_j[:, :, None], 0.0)
        acc_ref[rows, :] += jnp.sum(val, axis=1)   # (PC, BN)
        return carry

    jax.lax.fori_loop(0, n_chunks, body, 0)

    @pl.when(i_i == n_i_blocks - 1)
    def _finish():
        wmat = acc_ref[...] - resid_ref[...]                   # (RP, BN)
        out_ref[...] = jnp.min(wmat, axis=0, keepdims=True)    # (1, BN)


@functools.partial(
    jax.jit,
    static_argnames=("bn", "bi", "probe_chunk", "interpret"),
)
def fl_divergence_kernel(
    sim: Array,       # (ni, n) similarity; sim[i, v] = service of row i by v
    MU: Array,        # (r, ni) probe coverage rows max(state, sim[:, u])
    resid: Array,     # (r,)  residual gains f(u | V \\ u); -INF masks a probe
    cand_idx: Array | None = None,  # (k,) compacted candidate buffer
    *,
    bn: int = 256,
    bi: int = 512,
    probe_chunk: int = 8,
    interpret: bool = False,
) -> Array:
    """Padded + tiled pallas_call wrapper.  Returns (n,) divergences.

    Pad-row convention: padded (and caller-masked) probe rows carry
    ``resid = -INF`` so their edge weight ``acc - resid`` is +INF and they
    never win the min.  Padded served rows are all-zero in both ``sim`` and
    ``MU``, so the hinge ``max(0 - 0, 0) = 0`` contributes nothing.

    Compact-candidate path: with ``cand_idx`` (k,) only the gathered candidate
    *columns* enter the grid (the served-row reduction still spans all ni rows
    — that is f's definition) and the output is the (k,) compacted buffer.
    The served-row blocking is unchanged, so per-candidate accumulation order
    — and hence the output — matches the full grid bitwise.
    """
    if cand_idx is not None:
        sim = jnp.take(sim, cand_idx, axis=1)
    ni, n = sim.shape
    r = MU.shape[0]
    f32 = jnp.float32

    bn = min(bn, _round_up(n, 128))
    bi = min(bi, _round_up(ni, 128))
    npad = _round_up(n, bn)
    ipad = _round_up(ni, bi)
    rp = _round_up(r, probe_chunk)

    INF = jnp.float32(1e30)
    simp = jnp.zeros((ipad, npad), sim.dtype).at[:ni, :n].set(sim)
    MUp = jnp.zeros((rp, ipad), f32).at[:r, :ni].set(MU.astype(f32))
    residp = jnp.full((rp, 1), -INF).at[:r, 0].set(resid.astype(f32))

    grid = (npad // bn, ipad // bi)
    out = pl.pallas_call(
        functools.partial(
            _fl_divergence_kernel,
            n_i_blocks=grid[1],
            probe_chunk=probe_chunk,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bi, bn), lambda i, j: (j, i)),       # sim
            pl.BlockSpec((rp, bi), lambda i, j: (0, j)),       # MU
            pl.BlockSpec((rp, 1), lambda i, j: (0, 0)),        # resid
        ],
        out_specs=pl.BlockSpec((1, bn), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, npad), f32),
        scratch_shapes=[pltpu.VMEM((rp, bn), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(simp, MUp, residp)
    return out[0, :n]


def fl_gains_kernel(
    sim: Array,      # (n, n)
    state: Array,    # (n,) current coverage m_i = max(0, max_{s in S} sim[i, s])
    cand_idx: Array | None = None,  # (k,) compacted candidate buffer
    *,
    interpret: bool = False,
    **block_kw,
) -> Array:
    """Greedy gains f(v|S) = sum_i max(sim[i, v] - m_i, 0) for all v.  (n,)
    — or the (k,) compacted buffer when ``cand_idx`` is given.

    A single-probe instance of the divergence kernel: with MU = state (one
    row) and resid = 0 the fused output is exactly f(v|S) — same tiling, no
    separate kernel to maintain.
    """
    return fl_divergence_kernel(
        sim,
        state.astype(jnp.float32)[None, :],
        jnp.zeros((1,), jnp.float32),
        cand_idx,
        interpret=interpret,
        **block_kw,
    )
