"""Matrix-free facility location: flash-style similarity-on-the-fly kernels.

Every dense FacilityLocation path consumes a materialized (n, n) similarity
matrix, so memory — not compute — is the scaling wall (n = 1M is 4 TB of
f32).  This module applies the memory-efficient-attention trick the repo
already ships in :mod:`repro.kernels.flash_attention` to the SS hot spots:
similarity tiles ``sim = relu(Xs_blk @ Xc_blkᵀ)`` are computed *inside* the
kernel from the (n, d) embedding rows, fused with the hinge/accumulate
reduction of :mod:`repro.kernels.fl_divergence`, and never leave VMEM — the
(n, n) matrix is never materialized anywhere.

The objective semantics are exactly dense ``FacilityLocation.from_features``
with the "dot" / "cosine" kernels (cosine = dot after row normalization, done
once at construction):

    sim[i, v] = max(x_i . x_v, 0)
    f(v | S + u) = sum_i max(sim[i, v] - mu[u, i], 0)
    w_{U,v} = min_u [ f(v | S + u) - resid_u ]

Pallas kernel (``fl_stream_divergence_kernel``), mirroring fl_divergence:
  - grid = (candidate blocks, served-row blocks); candidates parallel,
    served rows a sequential reduction.
  - Xc tile (BN, dp) and Xs tile (BI, dp): the embedding rows for this tile;
    ``sim_tile = relu(dot_general(Xs, Xc^T))`` is computed in f32 on the MXU
    (``preferred_element_type``), consumed immediately by the hinge, and
    discarded — VMEM holds (BI + BN) * dp floats instead of an (n, n) slab.
  - MU tile (RP, BI), resid (RP, 1), acc (RP, BN) persistent VMEM scratch,
    out (1, BN) written at the last served-row block: identical layout and
    accumulation order to fl_divergence's kernel.
  - pad conventions carried over: padded served rows are all-zero embedding
    rows => sim = relu(0) = 0 and mu = 0, so the hinge contributes nothing;
    padded probe rows carry resid = -INF so their weight is +INF and never
    wins the min; padded embedding columns (d -> dp) are zeros and do not
    change any dot product.
  - compact path: ``cand_idx`` gathers candidate *feature rows* (k, d) —
    a tiny gather — so only the surviving candidates enter the grid, while
    the served-row reduction still spans all rows (that is f's definition).
    This is how the streaming objective composes with the PR-3/4 live-set
    compaction for free.

Oracle block reference (``fl_stream_pair_ref``): a ``lax.scan`` over
(candidate block, served-row block) pairs with the kernel's probe-chunk inner
loop and the same served-row block size, so the accumulation order of every
output element matches the kernel's.  Peak intermediate is the
(probe_chunk, BI, BN) hinge slab — the streaming memory contract that
tests/test_fl_stream.py pins on the jaxpr.

Residual gains f(v | V \\ v) need per-served-row top-2 statistics over all
candidate columns; ``fl_stream_top2`` / ``fl_stream_count_best`` /
``fl_stream_best_loss_sum`` compute them in three matrix-free passes (the
sharded backend reuses the same passes per shard and reduces with the
existing all_gather/psum pattern of the dense objective).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ss_weights import _round_up

Array = jax.Array

NEG = -1e30
INF = 1e30


# --------------------------------------------------------------------------
# Pallas kernel: fused sim-tile matmul + hinge/accumulate + min-over-probes
# --------------------------------------------------------------------------
def _fl_stream_kernel(
    xs_ref,      # (BI, dp) served-row embedding tile
    xc_ref,      # (BN, dp) candidate embedding tile
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

    xs = xs_ref[...].astype(jnp.float32)      # (BI, dp)
    xc = xc_ref[...].astype(jnp.float32)      # (BN, dp)
    # The similarity tile, on the fly: relu(Xs_blk @ Xc_blk^T) in f32 on the
    # MXU.  It lives only in registers/VMEM for the duration of this tile.
    sim = jnp.maximum(
        jax.lax.dot_general(
            xs, xc, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ),
        0.0,
    )                                          # (BI, BN)
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
def fl_stream_divergence_kernel(
    X: Array,         # (ni, d) served-row embeddings
    MU: Array,        # (r, ni) probe coverage rows max(state, relu(X @ x_u))
    resid: Array,     # (r,)  residual gains f(u | V \\ u); -INF masks a probe
    cand_idx: Array | None = None,  # (k,) compacted candidate buffer
    Xc: Array | None = None,        # candidate embeddings; None = X
    *,
    bn: int = 256,
    bi: int = 256,
    probe_chunk: int = 8,
    interpret: bool = False,
) -> Array:
    """Padded + tiled pallas_call wrapper.  Returns (n,) divergences
    (or the (k,) compacted buffer when ``cand_idx`` is given).

    ``Xc`` lets a sharded local view pass candidate rows distinct from the
    served rows; ``cand_idx`` gathers rows *of Xc* — the gathered candidates
    pick which embedding rows enter the grid.
    """
    Xc = X if Xc is None else Xc
    if cand_idx is not None:
        Xc = jnp.take(Xc, cand_idx, axis=0)
    ni, d = X.shape
    n = Xc.shape[0]
    r = MU.shape[0]
    f32 = jnp.float32

    dp = _round_up(d, 128)
    bn = min(bn, _round_up(n, 128))
    bi = min(bi, _round_up(ni, 128))
    npad = _round_up(n, bn)
    ipad = _round_up(ni, bi)
    rp = _round_up(r, probe_chunk)

    Xsp = jnp.zeros((ipad, dp), f32).at[:ni, :d].set(X.astype(f32))
    Xcp = jnp.zeros((npad, dp), f32).at[:n, :d].set(Xc.astype(f32))
    MUp = jnp.zeros((rp, ipad), f32).at[:r, :ni].set(MU.astype(f32))
    residp = jnp.full((rp, 1), jnp.float32(-INF)).at[:r, 0].set(
        resid.astype(f32)
    )

    grid = (npad // bn, ipad // bi)
    out = pl.pallas_call(
        functools.partial(
            _fl_stream_kernel,
            n_i_blocks=grid[1],
            probe_chunk=probe_chunk,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bi, dp), lambda i, j: (j, 0)),       # Xs
            pl.BlockSpec((bn, dp), lambda i, j: (i, 0)),       # Xc
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
    )(Xsp, Xcp, MUp, residp)
    return out[0, :n]


def fl_stream_gains_kernel(
    X: Array,        # (ni, d) served-row embeddings
    state: Array,    # (ni,) current coverage m_i
    cand_idx: Array | None = None,
    Xc: Array | None = None,
    *,
    interpret: bool = False,
    **block_kw,
) -> Array:
    """Greedy gains f(v|S) = sum_i max(sim[i, v] - m_i, 0) for all v —
    the single-probe instance of the streaming divergence kernel (MU = the
    state row, resid = 0), exactly like fl_gains_kernel over fl_divergence."""
    return fl_stream_divergence_kernel(
        X,
        state.astype(jnp.float32)[None, :],
        jnp.zeros((1,), jnp.float32),
        cand_idx,
        Xc,
        interpret=interpret,
        **block_kw,
    )


# --------------------------------------------------------------------------
# Oracle block reference: lax.scan with the kernel's accumulation order
# --------------------------------------------------------------------------
def fl_stream_pair_ref(
    X: Array,         # (ni, d) served-row embeddings
    MU: Array,        # (r, ni) probe coverage rows
    cand_idx: Array | None = None,
    Xc: Array | None = None,
    *,
    bn: int = 2048,
    bi: int = 256,
    probe_chunk: int = 8,
) -> Array:
    """acc[u, v] = sum_i max(relu(x_i . xc_v) - mu[u, i], 0).  Shape (r, k).

    Matrix-free ``lax.scan`` block reference with the pallas kernel's
    arithmetic: an outer scan over candidate blocks, an inner scan over
    served-row blocks (same ``bi`` and zero-padding as the kernel, so the
    per-element accumulation order matches), and the kernel's probe-chunk
    fori loop inside.  Peak intermediate is the (probe_chunk, bi, bn) hinge
    slab — never anything O(n^2).
    """
    f32 = jnp.float32
    Xc = X if Xc is None else Xc
    if cand_idx is not None:
        Xc = jnp.take(Xc, cand_idx, axis=0)
    ni, d = X.shape
    n = Xc.shape[0]
    r = MU.shape[0]

    bn = min(bn, max(_round_up(n, 128), 1))
    bi = min(bi, max(_round_up(ni, 128), 1))
    npad = _round_up(n, bn)
    ipad = _round_up(ni, bi)
    rp = _round_up(r, probe_chunk)

    Xsp = jnp.zeros((ipad, d), f32).at[:ni].set(X.astype(f32))
    Xcp = jnp.zeros((npad, d), f32).at[:n].set(Xc.astype(f32))
    MUp = jnp.zeros((rp, ipad), f32).at[:r, :ni].set(MU.astype(f32))

    xs_blocks = Xsp.reshape(ipad // bi, bi, d)
    mu_blocks = jnp.moveaxis(MUp.reshape(rp, ipad // bi, bi), 1, 0)

    def cand_block(_, xc_b):                  # xc_b: (bn, d)
        def row_block(acc, inp):
            xs_b, mu_b = inp                  # (bi, d), (rp, bi)
            sim = jnp.maximum(xs_b @ xc_b.T, 0.0)          # (bi, bn)

            def chunk(j, a):
                mu_j = jax.lax.dynamic_slice_in_dim(
                    mu_b, j * probe_chunk, probe_chunk, 0
                )
                val = jnp.maximum(sim[None, :, :] - mu_j[:, :, None], 0.0)
                contrib = jnp.sum(val, axis=1)             # (PC, bn)
                return jax.lax.dynamic_update_slice_in_dim(
                    a,
                    jax.lax.dynamic_slice_in_dim(
                        a, j * probe_chunk, probe_chunk, 0
                    )
                    + contrib,
                    j * probe_chunk,
                    0,
                )

            acc = jax.lax.fori_loop(0, rp // probe_chunk, chunk, acc)
            return acc, None

        acc0 = jnp.zeros((rp, bn), f32)
        acc, _ = jax.lax.scan(row_block, acc0, (xs_blocks, mu_blocks))
        return None, acc

    _, accs = jax.lax.scan(
        cand_block, None, Xcp.reshape(npad // bn, bn, d)
    )                                          # (ncb, rp, bn)
    acc = jnp.moveaxis(accs, 0, 1).reshape(rp, npad)
    return acc[:r, :n]


def fl_stream_divergence_ref(
    X: Array,
    MU: Array,
    resid: Array,     # (r,); -INF masks a probe
    cand_idx: Array | None = None,
    Xc: Array | None = None,
    **block_kw,
) -> Array:
    """w_{U,v} = min_u [ acc[u, v] - resid_u ].  (n,) (or (k,) compacted).
    The jnp oracle the streaming kernel's parity is pinned against."""
    acc = fl_stream_pair_ref(X, MU, cand_idx, Xc, **block_kw)
    return jnp.min(acc - resid.astype(jnp.float32)[:, None], axis=0)


# --------------------------------------------------------------------------
# Matrix-free column reductions: running max / top-2 / best-count passes
# --------------------------------------------------------------------------
def _cand_blocks(Xc: Array, bv: int):
    """Pad candidate rows to a multiple of ``bv`` and return (blocks, valid):
    (ncb, bv, d) embedding blocks and the (ncb, bv) validity mask."""
    n, d = Xc.shape
    bv = min(bv, max(n, 1))
    npad = _round_up(n, bv)
    Xcp = jnp.zeros((npad, d), jnp.float32).at[:n].set(Xc.astype(jnp.float32))
    valid = (jnp.arange(npad) < n).reshape(-1, bv)
    return Xcp.reshape(-1, bv, d), valid


def fl_stream_col_max(
    X: Array,         # (ni, d) served rows
    Xc: Array,        # (n, d) candidate rows
    mask: Array | None = None,  # (n,) candidate mask; None = all
    *,
    bv: int = 2048,
) -> Array:
    """max over (masked) candidates v of sim[i, v] per served row i.  (ni,).
    All-masked rows return NEG (the dense add_many convention)."""
    Xs = X.astype(jnp.float32)
    blocks, valid = _cand_blocks(Xc, bv)
    if mask is not None:
        npad = valid.size
        mpad = jnp.zeros((npad,), bool).at[: mask.shape[0]].set(mask)
        valid = valid & mpad.reshape(valid.shape)

    def blk(run, inp):
        xc_b, ok_b = inp
        cols = jnp.maximum(Xs @ xc_b.T, 0.0)               # (ni, bv)
        cols = jnp.where(ok_b[None, :], cols, NEG)
        return jnp.maximum(run, jnp.max(cols, axis=1)), None

    run0 = jnp.full((X.shape[0],), jnp.float32(NEG))
    run, _ = jax.lax.scan(blk, run0, (blocks, valid))
    return run


def fl_stream_top2(
    X: Array,         # (ni, d) served rows
    Xc: Array,        # (n, d) candidate rows
    *,
    bv: int = 2048,
) -> Array:
    """Per-served-row top-2 of sim[i, :] over the candidate columns.  (ni, 2).
    Streaming merge of per-block top-2s — equal values merge exactly like the
    dense ``lax.top_k(sim, 2)`` (ties yield best == second)."""
    Xs = X.astype(jnp.float32)
    blocks, valid = _cand_blocks(Xc, bv)
    k2 = min(2, blocks.shape[1])

    def blk(run, inp):
        xc_b, ok_b = inp
        cols = jnp.maximum(Xs @ xc_b.T, 0.0)               # (ni, bv)
        cols = jnp.where(ok_b[None, :], cols, NEG)
        t = jax.lax.top_k(cols, k2)[0]                     # (ni, k2)
        merged = jax.lax.top_k(jnp.concatenate([run, t], axis=1), 2)[0]
        return merged, None

    run0 = jnp.full((X.shape[0], 2), jnp.float32(NEG))
    run, _ = jax.lax.scan(blk, run0, (blocks, valid))
    return run


def fl_stream_count_best(
    X: Array,
    Xc: Array,
    best: Array,      # (ni,) per-row max similarity
    *,
    bv: int = 2048,
) -> Array:
    """Number of candidate columns achieving sim[i, v] >= best_i per row.
    (ni,) int32 — the tie count of the dense residual computation."""
    Xs = X.astype(jnp.float32)
    blocks, valid = _cand_blocks(Xc, bv)

    def blk(run, inp):
        xc_b, ok_b = inp
        cols = jnp.maximum(Xs @ xc_b.T, 0.0)
        hit = (cols >= best[:, None]) & ok_b[None, :]
        return run + jnp.sum(hit, axis=1).astype(jnp.int32), None

    run0 = jnp.zeros((X.shape[0],), jnp.int32)
    run, _ = jax.lax.scan(blk, run0, (blocks, valid))
    return run


def fl_stream_best_loss_sum(
    X: Array,
    Xc: Array,
    best: Array,      # (ni,)
    loss: Array,      # (ni,) per-row loss if v is the unique argmax
    *,
    bv: int = 2048,
) -> Array:
    """resid[v] = sum_i 1[sim[i, v] >= best_i] * loss_i per candidate.  (n,).
    The scatter pass of the matrix-free residual computation."""
    Xs = X.astype(jnp.float32)
    n = Xc.shape[0]
    blocks, valid = _cand_blocks(Xc, bv)

    def blk(_, inp):
        xc_b, ok_b = inp
        cols = jnp.maximum(Xs @ xc_b.T, 0.0)               # (ni, bv)
        is_best = cols >= best[:, None]
        out = jnp.sum(jnp.where(is_best, loss[:, None], 0.0), axis=0)
        return None, jnp.where(ok_b, out, 0.0)

    _, outs = jax.lax.scan(blk, None, (blocks, valid))
    return outs.reshape(-1)[:n]


def fl_stream_residuals(
    X: Array,         # (ni, d) served rows
    Xc: Array | None = None,  # candidate rows; None = X
    *,
    bv: int = 2048,
) -> Array:
    """f(v | V \\ v) for every candidate — three matrix-free passes with the
    dense FacilityLocation.residual_gains tie semantics (rows whose best is
    achieved by >1 column lose nothing when one of them leaves)."""
    Xc = X if Xc is None else Xc
    top2 = fl_stream_top2(X, Xc, bv=bv)
    best, second = top2[:, 0], top2[:, 1]
    cnt = fl_stream_count_best(X, Xc, best, bv=bv)
    loss = jnp.where(
        cnt > 1, 0.0, jnp.maximum(best, 0.0) - jnp.maximum(second, 0.0)
    )
    return fl_stream_best_loss_sum(X, Xc, best, loss, bv=bv)
