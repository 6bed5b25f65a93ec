"""GQA attention: full-causal and sliding-window, with a blockwise
(flash-style, online-softmax) formulation so the S x S score matrix is never
materialized — required for the 32k prefill cells and the right structure for
TPU (VMEM-sized working sets; XLA fuses each block's QK^T / softmax / PV).

Supports: RoPE, qk-norm (qwen3), QKV bias (qwen2), GQA with any
heads/kv-heads ratio, logit soft-capping, decode with a static KV cache.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.models.layers import (
    Params,
    apply_rope,
    dense_init,
    dtype_of,
    rmsnorm_headwise,
)
from repro.models.sharding import DATA, MODEL, POD, constrain

Array = jax.Array


def _constrain_heads(x: Array) -> Array:
    """(B, S, H, hd): heads over model — uneven head counts are legal for
    constraints (GSPMD pads, e.g. llama4's 40 heads -> 3/device on 16) and
    strictly better than sharding head_dim, which puts the QK/PV contraction
    dimension on the model axis and forces an all-reduce of every score block
    (measured: 16.5 TB/chip of collective traffic on llama4 prefill_32k)."""
    from repro.models.sharding import usable_axes

    mesh = jax.sharding.get_abstract_mesh()
    if "model" not in mesh.axis_names:
        return x
    ok = usable_axes(mesh)
    if MODEL not in ok:
        return x
    from jax.sharding import PartitionSpec as P

    batch = tuple(a for a in (POD, DATA) if a in ok)
    if not batch or x.shape[0] % _prod(mesh.shape[a] for a in batch):
        batch_entry = None
    else:
        batch_entry = batch if len(batch) > 1 else batch[0]
    # deliberately NOT fit_spec'd: uneven H sharding is the point
    return jax.lax.with_sharding_constraint(
        x, P(batch_entry, None, MODEL, None)
    )


def _prod(it):
    out = 1
    for v in it:
        out *= v
    return out

NEG_INF = -1e30  # finite: avoids NaN from all-masked softmax rows


def attention_init(key: Array, cfg) -> Params:
    dtype = dtype_of(cfg.param_dtype)
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    ks = jax.random.split(key, 5)
    p: Params = {
        "w_q": dense_init(ks[0], d, qd, dtype),
        "w_k": dense_init(ks[1], d, kvd, dtype),
        "w_v": dense_init(ks[2], d, kvd, dtype),
        "w_o": dense_init(ks[3], qd, d, dtype, scale=1.0 / math.sqrt(qd)),
    }
    if cfg.qkv_bias:
        p["b_q"] = jnp.zeros((qd,), dtype)
        p["b_k"] = jnp.zeros((kvd,), dtype)
        p["b_v"] = jnp.zeros((kvd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((cfg.head_dim,), dtype)
        p["k_norm"] = jnp.ones((cfg.head_dim,), dtype)
    return p


def _project_qkv(p: Params, cfg, x: Array, positions: Array):
    """x (B, S, D) -> q (B, S, H, hd), k/v (B, S, KV, hd), roped + normed."""
    cdt = dtype_of(cfg.compute_dtype)
    B, S, _ = x.shape
    xc = x.astype(cdt)
    q = xc @ p["w_q"].astype(cdt)
    k = xc @ p["w_k"].astype(cdt)
    v = xc @ p["w_v"].astype(cdt)
    if cfg.qkv_bias:
        q = q + p["b_q"].astype(cdt)
        k = k + p["b_k"].astype(cdt)
        v = v + p["b_v"].astype(cdt)
    q = q.reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rmsnorm_headwise(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm_headwise(p["k_norm"], k, cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    # q: heads over model (padded when uneven).  k/v: REPLICATED across model
    # — kv_dim is ~1 KB/token, and sharding so few heads makes GSPMD permute
    # kv shards on every block step of the attention loop (measured 2.5 TB/
    # chip on llama4 prefill); replication turns the GQA head expansion into
    # a local slice.
    return (
        _constrain_heads(q),
        constrain(k, (POD, DATA), None, None, None),
        constrain(v, (POD, DATA), None, None, None),
    )


def _softcap(logits: Array, cap: float) -> Array:
    if cap <= 0.0:
        return logits
    return cap * jnp.tanh(logits / cap)


# ---------------------------------------------------------------------------
# Blockwise causal attention (training / prefill)
# ---------------------------------------------------------------------------

def blockwise_attention(
    q: Array,            # (B, S, H, hd)
    k: Array,            # (B, S, KV, hd)
    v: Array,            # (B, S, KV, hd)
    *,
    causal: bool = True,
    window: int = 0,     # 0 = unbounded; else sliding window (causal)
    block_q: int = 512,
    block_k: int = 1024,
    softcap: float = 0.0,
) -> Array:
    """Online-softmax attention over (q-block x k-block) tiles.

    Memory: O(B * H * block_q * block_k) live scores instead of O(S^2).

    GQA layout note: k/v are *expanded* to the full H heads per k-block (a
    fused broadcast, ~bk*H*hd per block) instead of computing on a split
    (KV, G) head layout.  Every tensor then carries one uniform H axis that
    shards over the model mesh axis — evenly or with GSPMD padding (llama4's
    40 heads) — whereas the (KV, G) form either breaks the sharding on the
    reshape or (worse) puts the contraction on head_dim and all-reduces every
    score block.
    """
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)

    bq = min(block_q, S)
    bk = min(block_k, S)
    # pad S to a multiple of both blocks
    Sq = -(-S // bq) * bq
    Sk = -(-S // bk) * bk
    qp = jnp.pad(q, ((0, 0), (0, Sq - S), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, Sk - S), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Sk - S), (0, 0), (0, 0)))

    # GQA expansion as a static gather (head -> kv-head map), ONCE per call:
    # with k/v replicated across model each device materializes only its own
    # H-shard of the expanded keys/values (`_constrain_heads` pins that), so
    # the expansion is local, collective-free, and amortized over all
    # (q-block x k-block) steps.  broadcast+reshape instead creates a
    # (KV, G)-structured temp GSPMD cannot re-tile without permutes, and
    # per-block expansion re-reads the kv heads nq*nk times.
    head_map = jnp.arange(H) // G
    kx = _constrain_heads(jnp.take(kp, head_map, axis=2))     # (B, Sk, H, hd)
    vx = _constrain_heads(jnp.take(vp, head_map, axis=2))

    nq, nk = Sq // bq, Sk // bk
    # Head-major (B, H, blocks, blk, hd) layout, transposed ONCE: the block
    # einsums then consume operands in their native layout — the per-block
    # transpose_copy fusions this removes were ~half the attention HBM
    # traffic (measured 2.0e13 B/chip on llama4 prefill_32k).
    qb = qp.reshape(B, nq, bq, H, hd).transpose(0, 3, 1, 2, 4)
    kb = kx.reshape(B, nk, bk, H, hd).transpose(0, 3, 1, 2, 4)
    vb = vx.reshape(B, nk, bk, H, hd).transpose(0, 3, 1, 2, 4)

    q_pos = jnp.arange(Sq).reshape(nq, bq)
    k_pos = jnp.arange(Sk).reshape(nk, bk)
    k_valid = k_pos < S                                       # (nk, bk)

    def q_block(i, qi):
        # qi: (B, H, bq, hd)
        def k_step(carry, j):
            acc, m, lse = carry
            kj, vj = kb[:, :, j], vb[:, :, j]                 # (B, H, bk, hd)
            s = jnp.einsum(
                "bhqd,bhsd->bhqs", qi, kj,
                preferred_element_type=jnp.float32,
            ) * scale                                         # (B, H, bq, bk)
            s = _softcap(s, softcap)
            mask = k_valid[j][None, None, None, :]
            if causal:
                dq = q_pos[i][:, None] - k_pos[j][None, :]    # (bq, bk)
                cm = dq >= 0
                if window > 0:
                    cm = cm & (dq < window)
                mask = mask & cm[None, None, :, :]
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))       # (B, H, bq)
            p_ = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            lse_new = lse * alpha + jnp.sum(p_, axis=-1)
            pv = jnp.einsum(
                "bhqs,bhsd->bhqd", p_.astype(vj.dtype), vj,
                preferred_element_type=jnp.float32,
            )
            acc_new = acc * alpha[..., None] + pv
            return (acc_new, m_new, lse_new), None

        acc0 = jnp.zeros((B, H, bq, hd), jnp.float32)
        m0 = jnp.full((B, H, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, bq), jnp.float32)
        # remat the k-step: the backward recomputes the (bq, bk) score tiles
        # instead of stashing the full S×S attention matrix (flash-attention
        # memory behaviour, expressed as scan + checkpoint)
        (acc, m, lse), _ = jax.lax.scan(
            jax.checkpoint(k_step), (acc0, m0, l0), jnp.arange(nk)
        )
        out = acc / jnp.maximum(lse[..., None], 1e-30)
        return out  # (B, H, bq, hd)

    outs = jax.lax.map(lambda i: q_block(i, qb[:, :, i]), jnp.arange(nq))
    # (nq, B, H, bq, hd) -> (B, S, H, hd)
    out = (
        jnp.moveaxis(outs, 0, 1)           # (B, nq, H, bq, hd)
        .transpose(0, 1, 3, 2, 4)          # (B, nq, bq, H, hd)
        .reshape(B, Sq, H, hd)[:, :S]
    )
    return out.astype(q.dtype)


def attention_forward(
    p: Params, cfg, x: Array, positions: Array, *, window: int = 0
) -> Array:
    """Full training/prefill attention sublayer (no cache). x: (B, S, D)."""
    cdt = dtype_of(cfg.compute_dtype)
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, cfg, x, positions)
    out = blockwise_attention(
        q, k, v, causal=True, window=window, softcap=cfg.attn_logit_softcap
    )
    out = out.reshape(B, S, cfg.q_dim)
    return out @ p["w_o"].astype(cdt)


def attention_prefill(
    p: Params, cfg, x: Array, positions: Array, max_len: int, *, window: int = 0
) -> tuple[Array, dict]:
    """Prefill: full attention over (B, S, D) AND the populated KV cache.

    Full attention caches all S positions padded to ``max_len``; local
    attention caches only the trailing ``window`` positions as a ring buffer
    laid out exactly as ``attention_decode`` expects (slot = pos % window).
    """
    cdt = dtype_of(cfg.compute_dtype)
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, cfg, x, positions)
    out = blockwise_attention(
        q, k, v, causal=True, window=window, softcap=cfg.attn_logit_softcap
    )
    out = out.reshape(B, S, cfg.q_dim) @ p["w_o"].astype(cdt)

    if window > 0:
        L = min(window, max_len)
        if S >= L:
            tail_k, tail_v = k[:, -L:], v[:, -L:]
            # position S-L+j lives at slot (S-L+j) % L = (S+j) % L
            ck = jnp.roll(tail_k, S % L, axis=1)
            cv = jnp.roll(tail_v, S % L, axis=1)
        else:
            pad = ((0, 0), (0, L - S), (0, 0), (0, 0))
            ck, cv = jnp.pad(k, pad), jnp.pad(v, pad)
        return out, {"k": ck.astype(cdt), "v": cv.astype(cdt)}
    pad = ((0, 0), (0, max_len - S), (0, 0), (0, 0))
    return out, {"k": jnp.pad(k, pad).astype(cdt),
                 "v": jnp.pad(v, pad).astype(cdt)}


# ---------------------------------------------------------------------------
# Decode with KV cache
# ---------------------------------------------------------------------------

def kv_cache_init(cfg, batch: int, max_len: int, window: int = 0) -> dict:
    """Static cache for one attention layer.  ``window > 0`` allocates only a
    ring buffer of ``window`` slots (local attention / recurrentgemma)."""
    L = min(window, max_len) if window > 0 else max_len
    cdt = dtype_of(cfg.compute_dtype)
    shape = (batch, L, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, cdt), "v": jnp.zeros(shape, cdt)}


def attention_decode(
    p: Params,
    cfg,
    x: Array,          # (B, 1, D)
    cache: dict,       # {"k","v"}: (B, L, KV, hd)
    cache_len: Array,  # scalar int32 — tokens already in the cache
    *,
    window: int = 0,
    pos: Array | None = None,  # RoPE position override (defaults to cache_len)
) -> tuple[Array, dict]:
    """One decode step.  Writes the new k/v at position ``cache_len`` (ring
    slot ``cache_len % window`` for local attention), attends to the valid
    prefix, returns (output (B, 1, D), updated cache).

    ``pos`` decouples the rotary position of the new token from the cache
    slot — used after SS KV-cache pruning, where the cache is compacted but
    generation continues at the true sequence position."""
    cdt = dtype_of(cfg.compute_dtype)
    B = x.shape[0]
    L = cache["k"].shape[1]
    rope_pos = cache_len if pos is None else pos
    posb = jnp.full((B, 1), rope_pos, jnp.int32)
    q, k_new, v_new = _project_qkv(p, cfg, x, posb)

    slot = (cache_len % L).astype(jnp.int32) if window > 0 else cache_len
    k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, slot, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, slot, axis=1)

    KV, H, hd = cfg.num_kv_heads, cfg.num_heads, cfg.head_dim
    G = H // KV
    head_map = jnp.arange(H) // G
    kx = jnp.take(k, head_map, axis=2)
    vx = jnp.take(v, head_map, axis=2)
    s = jnp.einsum(
        "bqhd,bshd->bhqs", q, kx, preferred_element_type=jnp.float32
    ) / math.sqrt(hd)                               # (B, H, 1, L)
    s = _softcap(s, cfg.attn_logit_softcap)

    idx = jnp.arange(L)
    if window > 0:
        # ring buffer: valid slots are the last min(cache_len+1, L) writes
        n_valid = jnp.minimum(cache_len + 1, L)
        age = (slot - idx) % L          # 0 = newest
        valid = age < n_valid
    else:
        valid = idx <= cache_len
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)

    w = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(cdt)
    out = jnp.einsum("bhqs,bshd->bqhd", w, vx)
    out = out.reshape(B, 1, cfg.q_dim) @ p["w_o"].astype(cdt)
    # barrier: the decode scan stacks this cache as its ys — without the
    # barrier XLA folds the attention einsum's f32 upcast into that buffer
    # and materializes the whole stacked KV cache in f32 *and* bf16
    # (measured 18.4 GB vs 6.4 GB on musicgen decode_32k)
    k, v = jax.lax.optimization_barrier((k, v))
    return out, {"k": k, "v": v}
