"""Pallas TPU kernel for the SS hot spot: fused submodularity-graph divergence.

Computes   w_{U,v} = min_{u in U} [ f(v | S + u) - f(u | V \\ u) ]   for every
candidate v in one pass, for the feature-based objective
f(S) = sum_f w_f * phi(c_f(S)) — the optional ``feat_w`` feature-weight vector
rides through the phi-reduction as a resident (1, BF) tile (weights default to
ones; padded feature columns carry weight 0, which also makes the padding
exact for any phi).

Why a kernel: the naive computation materializes the (r, n, F) tensor
phi(CU[u] + W[v]) in HBM (r = |U| = r·log n probes, n candidates, F features).
At n = 1e6, r = 160, F = 4096 that is 2.6 PB of f32 traffic.  The kernel tiles
(candidates x features) into VMEM, keeps the probe block resident, accumulates
the feature reduction in a VMEM scratch accumulator, and fuses the final
min-over-probes — so HBM traffic is exactly one read of W (n x F) plus one
write of the (n,) result: the roofline minimum.

Layout / tiling (TPU v5e target):
  - grid = (n_blocks, f_blocks); candidate blocks are parallel, feature blocks
    are a sequential reduction (dimension_semantics below).
  - W tile   (BN, BF)  : BN=256 candidates x BF=512 features = 512 KB f32.
  - CU tile  (RP, BF)  : all probes resident per feature block (RP = r padded
    to a multiple of 8 sublanes).
  - acc      (RP, BN)  f32 VMEM scratch, persistent across the f reduction.
  - out tile (1, BN)   written once, at the last feature block.
MXU note: phi is a nonlinear (concave) transform, so the reduction cannot be
expressed as a matmul — this kernel is VPU work by nature; the MXU-bound parts
of the system live in the LM stack.  The win here is pure memory-hierarchy
management (HBM -> VMEM blocking), which is what dominates at scale.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _phi(kind: str, c, cap):
    if kind == "sqrt":
        return jnp.sqrt(jnp.maximum(c, 0.0))
    if kind == "log1p":
        return jnp.log1p(jnp.maximum(c, 0.0))
    if kind == "setcover":
        return jnp.minimum(c, 1.0)
    if kind == "satcov":
        return jnp.minimum(c, cap)
    if kind == "linear":
        return c
    raise ValueError(kind)


def _ss_divergence_kernel(
    w_ref,       # (BN, BF) candidate features tile
    cu_ref,      # (RP, BF) probe coverage tile
    phicu_ref,   # (RP, 1)  sum_f w_f phi(CU) per probe (-INF for pad rows)
    resid_ref,   # (RP, 1)  probe residual gains
    cap_ref,     # (1, BF)  satcov caps (zeros otherwise)
    fw_ref,      # (1, BF)  feature weights (ones when unweighted; 0 on pads)
    out_ref,     # (1, BN)  divergence tile
    acc_ref,     # (RP, BN) f32 VMEM scratch accumulator
    *,
    phi: str,
    n_f_blocks: int,
    probe_chunk: int,
):
    i_f = pl.program_id(1)

    @pl.when(i_f == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[...].astype(jnp.float32)        # (BN, BF)
    cap = cap_ref[...].astype(jnp.float32)    # (1, BF)
    fw = fw_ref[...].astype(jnp.float32)      # (1, BF)

    n_chunks = cu_ref.shape[0] // probe_chunk

    def body(j, carry):
        # Probe chunk (PC, BF) against the whole candidate tile (BN, BF):
        # contrib[p, v] = sum_f w_f * phi(cu[p, f] + w[v, f]).  The chunk is
        # sliced on the refs (not on loaded values), which Mosaic lowers.
        rows = pl.ds(pl.multiple_of(j * probe_chunk, probe_chunk), probe_chunk)
        cu_j = cu_ref[rows, :].astype(jnp.float32)
        val = _phi(phi, cu_j[:, None, :] + w[None, :, :], cap[None, :, :])
        acc_ref[rows, :] += jnp.sum(val * fw[None, :, :], axis=-1)  # (PC, BN)
        return carry

    jax.lax.fori_loop(0, n_chunks, body, 0)

    @pl.when(i_f == n_f_blocks - 1)
    def _finish():
        wmat = acc_ref[...] - phicu_ref[...] - resid_ref[...]   # (RP, BN)
        out_ref[...] = jnp.min(wmat, axis=0, keepdims=True)     # (1, BN)


@functools.partial(
    jax.jit,
    static_argnames=("phi", "bn", "bf", "probe_chunk", "interpret"),
)
def ss_divergence_kernel(
    W: Array,         # (n, F)
    CU: Array,        # (r, F)
    phi_cu: Array,    # (r,)  sum_f w_f phi(CU)  (weighted when feat_w given)
    resid: Array,     # (r,)
    cap: Array | None = None,
    feat_w: Array | None = None,  # (F,) feature weights, None = unweighted
    cand_idx: Array | None = None,  # (k,) compacted candidate buffer
    *,
    phi: str = "sqrt",
    bn: int = 256,
    bf: int = 512,
    probe_chunk: int = 8,
    interpret: bool = False,
) -> Array:
    """Padded + tiled pallas_call wrapper.  Returns (n,) divergences.

    Compact-candidate path: with ``cand_idx`` (k,) the kernel grid covers only
    the gathered k candidate rows — dead candidates cost neither HBM reads nor
    grid cells — and the output is the (k,) compacted divergence buffer.
    Per-candidate arithmetic (feature blocking, accumulation order) is
    identical to the full grid, so compacted and full outputs match bitwise.
    """
    if cand_idx is not None:
        W = jnp.take(W, cand_idx, axis=0)
    n, F = W.shape
    r = CU.shape[0]
    f32 = jnp.float32

    bn = min(bn, _round_up(n, 128))
    bf = min(bf, _round_up(F, 128))
    npad = _round_up(n, bn)
    fpad = _round_up(F, bf)
    rp = _round_up(r, probe_chunk)

    INF = jnp.float32(1e30)
    Wp = jnp.zeros((npad, fpad), W.dtype).at[:n, :F].set(W)
    CUp = jnp.zeros((rp, fpad), f32).at[:r, :F].set(CU.astype(f32))
    # Pad rows: phi_cu = -INF => weight +INF, never the min.
    phicup = jnp.full((rp, 1), -INF).at[:r, 0].set(phi_cu.astype(f32))
    residp = jnp.zeros((rp, 1), f32).at[:r, 0].set(resid.astype(f32))
    capp = jnp.zeros((1, fpad), f32)
    if cap is not None:
        capp = capp.at[0, :F].set(cap.astype(f32))
    # Weight 1 on real features, 0 on padded columns (padding stays exact for
    # any phi, including hypothetical phi(0) != 0 transforms).
    fwp = jnp.zeros((1, fpad), f32).at[0, :F].set(
        jnp.ones((F,), f32) if feat_w is None else feat_w.astype(f32)
    )

    grid = (npad // bn, fpad // bf)
    out = pl.pallas_call(
        functools.partial(
            _ss_divergence_kernel,
            phi=phi,
            n_f_blocks=grid[1],
            probe_chunk=probe_chunk,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bf), lambda i, j: (i, j)),       # W
            pl.BlockSpec((rp, bf), lambda i, j: (0, j)),       # CU
            pl.BlockSpec((rp, 1), lambda i, j: (0, 0)),        # phi_cu
            pl.BlockSpec((rp, 1), lambda i, j: (0, 0)),        # resid
            pl.BlockSpec((1, bf), lambda i, j: (0, j)),        # cap
            pl.BlockSpec((1, bf), lambda i, j: (0, j)),        # feat_w
        ],
        out_specs=pl.BlockSpec((1, bn), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, npad), f32),
        scratch_shapes=[pltpu.VMEM((rp, bn), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(Wp, CUp, phicup, residp, capp, fwp)
    return out[0, :n]


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
