"""Partition-spec rules: map every parameter / activation / cache leaf to a
``PartitionSpec`` over the (pod, data, model) production mesh.

Philosophy (DESIGN.md §6): 2-D sharding.  The ``model`` axis carries tensor
parallelism (attention heads, FFN hidden, experts, vocab); the ``data`` axis
carries FSDP (the other matrix dimension of every weight + the batch dimension
of every activation); the ``pod`` axis is pure data parallelism (weights
replicated across pods, batch split, gradients all-reduced — with optional
compression, see repro/train/compress.py).

Head counts that do not divide the 16-way model axis (llama4's 40 q-heads,
qwen2's 28, recurrentgemma's 10...) are legal: the model forward uses
jit/GSPMD sharding constraints, and GSPMD pads uneven dimensions internally.
kv-head axes smaller than the model axis are *replicated* instead (classic
MQA/GQA practice) by routing the rule through ``maybe_model``.

Rules key off leaf *names* (the param dicts use stable names exactly so this
table stays small).  Stacked group params get a leading ``None`` axis
automatically.
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig

Array = jax.Array

DATA, MODEL, POD = "data", "model", "pod"


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


def _spec_axis_size(mesh: Mesh, entry) -> int:
    if entry is None:
        return 1
    axes = entry if isinstance(entry, tuple) else (entry,)
    size = 1
    for a in axes:
        size *= _axis_size(mesh, a)
    return size


def fit_spec(spec: P, shape: tuple, mesh: Mesh) -> P:
    """Drop spec axes whose mesh size does not divide the dimension —
    pjit arguments must shard evenly; the dropped dimension is replicated.
    Also drops axes not present in the mesh (single-pod vs multi-pod)."""
    out = []
    for i, entry in enumerate(spec):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        axes = tuple(a for a in axes if a in mesh.axis_names)
        if not axes:
            out.append(None)
            continue
        entry2 = axes if len(axes) > 1 else axes[0]
        if i < len(shape) and shape[i] % _spec_axis_size(mesh, entry2) == 0:
            out.append(entry2)
        else:
            out.append(None)
    return P(*out)


# name -> spec for the *unstacked* parameter
_PARAM_RULES: dict[str, P] = {
    # embeddings
    "tok": P(None, MODEL, DATA),          # (K, V, D)
    "unembed": P(None, DATA, MODEL),      # (K, D, V)
    # norms / small vectors — replicated
    "scale": P(),
    "q_norm": P(), "k_norm": P(),
    "A_log": P(), "dt_bias": P(), "D": P(), "norm": P(), "lam": P(),
    "gate_a_w": P(), "gate_a_b": P(), "gate_i_w": P(), "gate_i_b": P(),
    "conv_w": P(), "conv_b": P(),
    # attention
    "w_q": P(DATA, MODEL), "w_k": P(DATA, MODEL), "w_v": P(DATA, MODEL),
    "w_o": P(MODEL, DATA),
    "b_q": P(MODEL), "b_k": P(MODEL), "b_v": P(MODEL),
    # dense FFN / shared expert
    "w_gate": P(DATA, MODEL), "w_up": P(DATA, MODEL), "w_down": P(MODEL, DATA),
    # moe (expert banks are matched by name+rank below)
    "router": P(DATA, None),
    # mamba2
    "w_in_zx": P(DATA, MODEL), "w_in_bc": P(DATA, None),
    "w_in_dt": P(DATA, MODEL),
    "w_out": P(MODEL, DATA),
    # rglru
    "w_y": P(DATA, MODEL), "w_x": P(DATA, MODEL),
}

# expert banks: (E, D, F) / (E, F, D) — experts over MODEL, D over DATA
_EXPERT_RULES = {
    "w_gate": P(MODEL, DATA, None),
    "w_up": P(MODEL, DATA, None),
    "w_down": P(MODEL, None, DATA),
}


def param_spec_tree(cfg: ModelConfig, params_shape, mesh: Mesh) -> dict:
    """PartitionSpec pytree parallel to the params pytree.

    ``params_shape`` is the params pytree (arrays or ShapeDtypeStructs);
    specs are fitted to ``mesh`` (non-dividing dims fall back to replication
    — e.g. mamba2's 50280 vocab on a 16-way model axis).
    """

    def spec_for(path, leaf) -> P:
        keys = [p.key for p in path if hasattr(p, "key")]
        name = keys[-1]
        in_moe = "moe" in keys
        in_shared = "shared" in keys
        stacked = keys and keys[0] == "blocks"
        if in_moe and not in_shared and name in _EXPERT_RULES and leaf.ndim >= 3:
            spec = _EXPERT_RULES[name]
        elif name in _PARAM_RULES:
            spec = _PARAM_RULES[name]
        else:
            raise KeyError(f"no sharding rule for param {'/'.join(keys)}")
        expected = len(spec) + (1 if stacked else 0)
        if leaf.ndim != expected:
            # rank mismatch (e.g. scalar) -> replicate
            return P(*([None] * leaf.ndim))
        if stacked:
            spec = P(None, *spec)
        return fit_spec(spec, leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(spec_for, params_shape)


def batch_axes(mesh: Mesh) -> tuple:
    """The composite mesh axes that shard the global batch."""
    return (POD, DATA) if POD in mesh.axis_names else (DATA,)


def data_spec(mesh: Mesh, shape_or_ndim) -> P:
    """Spec for a (B, ...) data array: batch over pod x data.  Falls back to
    replication when B does not divide the batch axes (e.g. long_500k B=1)."""
    if isinstance(shape_or_ndim, int):
        return P(batch_axes(mesh), *([None] * (shape_or_ndim - 1)))
    shape = tuple(shape_or_ndim)
    spec = P(batch_axes(mesh), *([None] * (len(shape) - 1)))
    return fit_spec(spec, shape, mesh)


def cache_spec_tree(cfg: ModelConfig, cache_shape, mesh: Mesh) -> dict:
    """KV/SSM cache sharding: batch over (pod, data); the head axis over
    model when it divides, else head_dim over model (GQA kv-counts below 16
    would otherwise force replication of the dominant decode-memory term),
    else replicated."""
    baxes = batch_axes(mesh)
    msize = _axis_size(mesh, MODEL)

    def spec_for(path, leaf) -> P:
        keys = [p.key for p in path if hasattr(p, "key")]
        name = keys[-1]
        stacked = keys and keys[0] == "blocks"
        lead = (None,) if stacked else ()
        if name in ("k", "v"):      # (B, L, KV, hd)
            kv, hd = leaf.shape[-2], leaf.shape[-1]
            if kv % msize == 0:
                spec = (baxes, None, MODEL, None)
            elif hd % msize == 0:
                spec = (baxes, None, None, MODEL)
            else:
                spec = (baxes, None, None, None)
        elif name == "ssm":          # (B, H, P, N)
            spec = (baxes, MODEL, None, None)
        elif name == "conv":         # (B, W-1, C)
            spec = (baxes, None, None)
        elif name == "h":            # (B, dr)
            spec = (baxes, MODEL)
        else:
            raise KeyError(f"no cache rule for {'/'.join(keys)}")
        full = P(*lead, *spec)
        return fit_spec(full, leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(spec_for, cache_shape)


def named(mesh: Mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def usable_axes(mesh) -> set:
    """Mesh axes legal in a with_sharding_constraint here: present and not
    Manual (inside a shard_map body the manual axes are already bound)."""
    try:
        from jax.sharding import AxisType

        return {
            n for n, t in zip(mesh.axis_names, mesh.axis_types)
            if t != AxisType.Manual
        }
    except Exception:
        return set(mesh.axis_names)


def constrain(x, *entries):
    """``with_sharding_constraint`` against the ambient mesh, as a no-op when
    no mesh context is active (CPU unit tests) and with axes dropped when
    absent from the mesh, manual (bound by an enclosing shard_map), or
    non-dividing.  This is how the model code pins activation shardings
    (batch over pod x data, vocab/heads over model) without hard-coding a
    mesh."""
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return x
    ok = usable_axes(mesh)
    cleaned = []
    for e in entries:
        if e is None:
            cleaned.append(None)
            continue
        axes = tuple(a for a in (e if isinstance(e, tuple) else (e,))
                     if a in ok)
        cleaned.append(axes if len(axes) > 1 else (axes[0] if axes else None))
    spec = fit_spec(P(*cleaned), x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, spec)


def batch_spec_entry():
    return (POD, DATA)
