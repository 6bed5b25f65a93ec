"""Pallas TPU kernel for the greedy inner loop: batched marginal gains of the
feature-based coverage objective.

g[v] = sum_f w_f phi(c_f + W[v, f]) - sum_f w_f phi(c_f)    for all v

(``feat_w`` feature weights w_f default to ones; like the divergence kernel
they ride as a resident (1, BF) tile with 0 on padded feature columns.)

This is evaluated once per greedy step (the TPU replacement for the lazy-
greedy priority queue — see DESIGN.md §3).  The kernel tiles (candidates x
features), keeps the coverage row resident, accumulates the feature reduction
into the output block and subtracts the scalar baseline at the last feature
block.  HBM traffic = one read of W + one (n,) write per step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ss_weights import _phi, _round_up

Array = jax.Array


def _feature_gains_kernel(
    w_ref,      # (BN, BF) candidate features tile
    c_ref,      # (1, BF)  coverage state tile
    phic_ref,   # (1, 1)   scalar sum_f w_f phi(c)
    cap_ref,    # (1, BF)
    fw_ref,     # (1, BF)  feature weights (ones when unweighted; 0 on pads)
    out_ref,    # (1, BN)
    *,
    phi: str,
    n_f_blocks: int,
):
    i_f = pl.program_id(1)

    @pl.when(i_f == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    w = w_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)          # (1, BF)
    cap = cap_ref[...].astype(jnp.float32)
    fw = fw_ref[...].astype(jnp.float32)
    val = _phi(phi, c + w, cap) * fw             # (BN, BF)
    out_ref[...] += jnp.sum(val, axis=1)[None, :]

    @pl.when(i_f == n_f_blocks - 1)
    def _finish():
        out_ref[...] -= phic_ref[0, 0]


@functools.partial(jax.jit, static_argnames=("phi", "bn", "bf", "interpret"))
def feature_gains_kernel(
    W: Array,           # (n, F)
    c: Array,           # (F,)
    phi_c_total: Array,  # scalar: sum_f w_f phi(c) (weighted when feat_w given)
    cap: Array | None = None,
    feat_w: Array | None = None,  # (F,) feature weights, None = unweighted
    cand_idx: Array | None = None,  # (k,) compacted candidate buffer
    *,
    phi: str = "sqrt",
    bn: int = 512,
    bf: int = 512,
    interpret: bool = False,
) -> Array:
    # Compact-candidate path: only the gathered candidate rows enter the
    # grid; the output is the (k,) compacted gains buffer.
    if cand_idx is not None:
        W = jnp.take(W, cand_idx, axis=0)
    n, F = W.shape
    f32 = jnp.float32
    bn = min(bn, _round_up(n, 128))
    bf = min(bf, _round_up(F, 128))
    npad = _round_up(n, bn)
    fpad = _round_up(F, bf)

    Wp = jnp.zeros((npad, fpad), W.dtype).at[:n, :F].set(W)
    cp = jnp.zeros((1, fpad), f32).at[0, :F].set(c.astype(f32))
    capp = jnp.zeros((1, fpad), f32)
    if cap is not None:
        capp = capp.at[0, :F].set(cap.astype(f32))
    fwp = jnp.zeros((1, fpad), f32).at[0, :F].set(
        jnp.ones((F,), f32) if feat_w is None else feat_w.astype(f32)
    )
    phic = jnp.asarray(phi_c_total, f32).reshape(1, 1)

    # Padded feature columns have c = 0, W = 0 and weight 0 -> they contribute
    # nothing, so padding is exact.
    grid = (npad // bn, fpad // bf)
    out = pl.pallas_call(
        functools.partial(_feature_gains_kernel, phi=phi, n_f_blocks=grid[1]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bf), lambda i, j: (i, j)),
            pl.BlockSpec((1, bf), lambda i, j: (0, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bf), lambda i, j: (0, j)),
            pl.BlockSpec((1, bf), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, bn), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, npad), f32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(Wp, cp, phic, capp, fwp)
    return out[0, :n]
