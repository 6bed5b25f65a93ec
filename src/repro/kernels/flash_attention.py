"""Pallas TPU flash attention — the fused form of
``models.attention.blockwise_attention``.

Motivation (from the dry-run profile, EXPERIMENTS.md §Perf): in the XLA path
every (bq x bk) score tile and its softmax intermediates round-trip through
HBM (~2.6e13 B/chip of the llama4 prefill_32k memory term is attention-loop
temporaries).  This kernel keeps the whole online-softmax state — scores,
running max m, running sum l, and the output accumulator — in VMEM across
the k-block reduction, so per layer the HBM traffic is exactly
q+k+v reads + out write: the roofline minimum.

Grid/tiling (v5e):
  grid = (B*H, nq, nk) — the k axis is a sequential ("arbitrary") reduction,
  (batch*head, q-block) are parallel.
  q tile   (1, bq, hd)    k/v tiles (1, bk, hd)
  VMEM scratch: acc (bq, hd) f32, m/l (bq, 128) f32 broadcast lanes.
  bq = bk = 512, hd up to 256 -> ~1.3 MB resident per program instance,
  well inside the 128 MB/core VMEM budget, MXU-aligned (multiples of 128).

Causality: k-blocks strictly above the diagonal are masked to -inf; the
caller can skip them entirely by passing ``causal_skip=True`` (grid still
visits them — Pallas grids are dense — but the body exits early, so only
the ~half below the diagonal does matmul work).

GQA is handled by the caller expanding k/v head indices (see ops.py), so the
kernel sees matched (B*H) leading axes.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

NEG_INF = -1e30


def _flash_kernel(
    q_ref,       # (1, bq, hd)
    k_ref,       # (1, bk, hd)
    v_ref,       # (1, bk, hd)
    o_ref,       # (1, bq, hd)
    acc_ref,     # (bq, hd) f32 scratch
    m_ref,       # (bq, 128) f32 scratch (lane-broadcast running max)
    l_ref,       # (bq, 128) f32 scratch
    *,
    scale: float,
    n_k_blocks: int,
    bq: int,
    bk: int,
    causal: bool,
    window: int,
    seq_len: int,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # block-level causal skip: q block i only attends k blocks with
    # start <= q_end; for windows also k_end >= q_start - window
    q_start, k_start = iq * bq, ik * bk
    run = True
    if causal:
        run = k_start <= q_start + bq - 1
        if window > 0:
            run = jnp.logical_and(run, k_start + bk - 1 >= q_start - window)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)                   # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                   # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                           # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < seq_len                               # padding rows
        if causal:
            mask = jnp.logical_and(mask, qpos >= kpos)
            if window > 0:
                mask = jnp.logical_and(mask, qpos - kpos < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0:1]                              # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                              # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)                     # (bq, 1)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(
            p, axis=1, keepdims=True
        ) * jnp.ones_like(l_ref)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new * jnp.ones_like(m_ref)

    @pl.when(ik == n_k_blocks - 1)
    def _finish():
        lse = jnp.maximum(l_ref[:, 0:1], 1e-30)
        o_ref[0, ...] = (acc_ref[...] / lse).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "bq", "bk", "interpret"),
)
def flash_attention(
    q: Array,            # (BH, S, hd)  — batch*heads flattened
    k: Array,            # (BH, S, hd)
    v: Array,            # (BH, S, hd)
    *,
    causal: bool = True,
    window: int = 0,
    bq: int = 512,
    bk: int = 512,
    interpret: bool = False,
) -> Array:
    """Fused online-softmax attention.  Returns (BH, S, hd)."""
    BH, S, hd = q.shape
    scale = 1.0 / math.sqrt(hd)
    bq = min(bq, S)
    bk = min(bk, S)
    Sp = -(-S // max(bq, bk)) * max(bq, bk)
    if Sp != S:
        pad = ((0, 0), (0, Sp - S), (0, 0))
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
    nq, nk = Sp // bq, Sp // bk

    grid = (BH, nq, nk)
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, n_k_blocks=nk, bq=bq, bk=bk,
            causal=causal, window=window, seq_len=S,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sp, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    return out[:, :S]
