"""Error-feedback gradient compression for the pod (DCN) axis.

Between pods the links are ~10x slower than intra-pod ICI, so the cross-pod
gradient all-reduce is the one collective worth compressing.  We implement
**error feedback with per-block top-k sparsification**:

    m_t   = g_t + e_t                (add the carried compression error)
    c_t   = topk_blocks(m_t)         (keep the largest-|.| fraction per block)
    e_t+1 = m_t - c_t                (carry what was dropped)
    g̃_t  = all_reduce(c_t, axis=pod) / n_pods

Error feedback makes biased compressors convergent (Karimireddy et al. 2019);
the carried error state shards exactly like the gradients.

The pod reduction must be *manual* (GSPMD would otherwise fuse an exact
all-reduce into the backward), so the compressed step wraps the gradient
computation in ``jax.shard_map`` manual over **only** the pod axis
(``axis_names={"pod"}``) — data/model parallelism inside stays GSPMD-managed.

Top-k is per fixed-size block (1024) rather than per-leaf: O(n) one-pass
work and a static selected count, so the buffer stays dense-with-zeros (what
an SPMD all-reduce needs).  On a real DCN the wire saving comes from sparse
encoding of that buffer; we surface the achieved density as a metric.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.sharding import POD, batch_axes

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class CompressConfig:
    ratio: float = 0.05      # fraction of entries kept per block
    block: int = 1024


def topk_block_sparsify(x: Array, ratio: float, block: int) -> Array:
    """Keep the top-⌈ratio·block⌉ |entries| of every ``block`` chunk of the
    flattened array; zero the rest.  Shape-preserving."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    if n <= block:
        k = max(1, int(ratio * n))
        thresh = jax.lax.top_k(jnp.abs(flat), k)[0][-1]
        return jnp.where(jnp.abs(flat) >= thresh, flat, 0.0).reshape(x.shape)
    pad = (-n) % block
    fp = jnp.pad(flat, (0, pad)).reshape(-1, block)
    k = max(1, int(ratio * block))
    kth = jax.lax.top_k(jnp.abs(fp), k)[0][:, -1:]
    out = jnp.where(jnp.abs(fp) >= kth, fp, 0.0).reshape(-1)[:n]
    return out.reshape(x.shape)


def init_error_state(params) -> dict:
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def compress_and_reduce(grads, error, cc: CompressConfig, axis_name: str = POD):
    """EF top-k + mean all-reduce over ``axis_name`` (must be bound).

    Returns (reduced_grads, new_error, density_metric)."""
    n = jax.lax.psum(1.0, axis_name)

    def leaf(g, e):
        m = g.astype(jnp.float32) + e
        c = topk_block_sparsify(m, cc.ratio, cc.block)
        return jax.lax.psum(c, axis_name) / n, m - c, jnp.sum(c != 0.0), c.size

    flat_g, treedef = jax.tree.flatten(grads)
    flat_e = treedef.flatten_up_to(error)
    out = [leaf(g, e) for g, e in zip(flat_g, flat_e)]
    reduced = treedef.unflatten([o[0] for o in out])
    new_error = treedef.unflatten([o[1] for o in out])
    nnz = sum(o[2] for o in out)
    tot = sum(o[3] for o in out)
    return reduced, new_error, nnz / tot


def make_compressed_train_step(mesh: Mesh, cfg, tc, cc: CompressConfig):
    """Pod-compressed variant of ``trainer.make_train_step``.

    The returned step takes/returns state with an extra ``error`` field
    (init with ``init_error_state``).  Params and optimizer state are
    replicated across pods; the batch's leading dim is split across
    pod x data as usual.  Inside the pod-manual shard_map, gradients are
    computed under GSPMD over (data, model), EF-compressed, psum'd over pod,
    then the optimizer update runs identically on every pod.
    """
    assert POD in mesh.axis_names, "compressed step needs a pod axis"
    from repro.train.optimizer import make_optimizer
    from repro.train.trainer import _loss_fn, _global_norm, lr_schedule

    opt = make_optimizer(
        tc.optimizer,
        **({"weight_decay": tc.weight_decay} if tc.optimizer == "adamw" else {}),
    )

    def step(state, batch):
        params = state["params"]
        (_, metrics), grads = jax.value_and_grad(
            lambda p: _loss_fn(cfg, tc, p, batch), has_aux=True
        )(params)
        grads, new_err, density = compress_and_reduce(grads, state["error"], cc)
        metrics = jax.tree.map(lambda m: jax.lax.pmean(m, POD), metrics)

        gnorm = _global_norm(grads)
        scale = jnp.minimum(1.0, tc.grad_clip / jnp.maximum(gnorm, 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)
        lr = lr_schedule(tc, state["step"])
        new_params, new_opt = opt.update(
            grads, state["opt"], params, lr, state["step"]
        )
        metrics = dict(
            metrics, grad_norm=gnorm, lr=lr, compress_density=density
        )
        return (
            {"params": new_params, "opt": new_opt, "error": new_err,
             "step": state["step"] + 1},
            metrics,
        )

    def wrap(state, batch):
        state_specs = jax.tree.map(lambda _: P(), state)
        batch_specs = jax.tree.map(lambda _: P(POD), batch)
        metric_specs = {
            "loss": P(), "aux": P(), "grad_norm": P(), "lr": P(),
            "compress_density": P(),
        }
        return jax.shard_map(
            step,
            mesh=mesh,
            in_specs=(state_specs, batch_specs),
            out_specs=(state_specs, metric_specs),
            axis_names={POD},
            check_vma=False,
        )(state, batch)

    return wrap
