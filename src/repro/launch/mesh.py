"""Production meshes.  Defined as FUNCTIONS so importing this module never
touches jax device state (device count is locked at first jax init — the
dry-run sets XLA_FLAGS before importing anything)."""

from __future__ import annotations

from jax.sharding import Mesh

from repro.core.distributed import make_mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """The assignment's target: 16x16 = 256 chips per pod; 2 pods = 512."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    """Small explicit meshes for CPU tests (e.g. (1,1), (2,2), (2,2,2))."""
    return make_mesh(shape, axes)


def single_device_mesh() -> Mesh:
    return make_mesh((1, 1), ("data", "model"))


# TPU v5e hardware constants used by the roofline analysis (per chip).
HW = {
    "peak_flops_bf16": 197e12,   # FLOP/s
    "hbm_bw": 819e9,             # B/s
    "ici_bw": 50e9,              # B/s per link (~ per-chip usable DCN is far less;
                                 # the pod axis models DCN at ~1/10 of this)
    "dcn_bw": 5e9,
    "hbm_per_chip": 16e9,        # v5e: 16 GB
}
