"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Interpret mode accepts slices and VMEM use that Mosaic (the TPU kernel
compiler) refuses, so the interpret-mode parity tests cannot catch a kernel
that does not lower.  These tests compile each kernel ahead of time for a
described (not attached) v5e chip with the TPU compiler that ships with
jaxlib: nothing runs, so they say nothing about results or speed.

The topology is described inside a module-scoped fixture, never at import:
only one process may hold the TPU library, and every test worker imports
this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.sparsify import probe_count
from repro.kernels.feature_gains import feature_gains_kernel
from repro.kernels.fl_divergence import fl_divergence_kernel
from repro.kernels.fl_stream import (
    fl_stream_divergence_kernel,
    fl_stream_gains_kernel,
)
from repro.kernels.ss_weights import ss_divergence_kernel

F32, I32 = jnp.float32, jnp.int32

# name -> (kernel call, argument shapes).  Widths are the main path's: news
# features W (8192, 1024); the longest SumMe video's dense similarity
# (9721, 9721); fl_stream's matrix-free ground set X (65536, 16).  Probe
# counts are SS's r·log2(n) with r = 8.
CASES = {
    "ss_divergence": (
        lambda W, CU, phi_cu, resid: ss_divergence_kernel(
            W, CU, phi_cu, resid, interpret=False),
        [((8192, 1024), F32), ((probe_count(8192), 1024), F32),
         ((probe_count(8192),), F32), ((probe_count(8192),), F32)],
    ),
    "feature_gains": (
        lambda W, c, phi_c: feature_gains_kernel(W, c, phi_c, interpret=False),
        [((8192, 1024), F32), ((1024,), F32), ((), F32)],
    ),
    "fl_divergence": (
        lambda sim, MU, resid: fl_divergence_kernel(
            sim, MU, resid, interpret=False),
        [((9721, 9721), F32), ((probe_count(9721), 9721), F32),
         ((probe_count(9721),), F32)],
    ),
    "fl_stream_divergence": (
        lambda X, MU, resid: fl_stream_divergence_kernel(
            X, MU, resid, interpret=False),
        [((65536, 16), F32), ((probe_count(65536), 65536), F32),
         ((probe_count(65536),), F32)],
    ),
    "fl_stream_divergence_compact": (
        lambda X, MU, resid, cand_idx: fl_stream_divergence_kernel(
            X, MU, resid, cand_idx, interpret=False),
        [((65536, 16), F32), ((probe_count(65536), 65536), F32),
         ((probe_count(65536),), F32), ((4096,), I32)],
    ),
    "fl_stream_gains": (
        lambda X, state: fl_stream_gains_kernel(X, state, interpret=False),
        [((65536, 16), F32), ((65536,), F32)],
    ),
}

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this jaxlib
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    call, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(call).lower(*args).compile()
    # The kernel is a Mosaic custom call, not an interpreted loop.
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES, used
