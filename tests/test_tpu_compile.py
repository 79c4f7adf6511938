"""Ahead-of-time compiles of the engine's Pallas kernels for a TPU v5e.

Nothing runs: each test lowers a kernel entry point for one chip of a
described ``v5e:2x2`` topology, with ``ShapeDtypeStruct`` operands at the
``dblp`` (scale 1.0, k=8) plan shapes, and asserts that Mosaic compiled the
kernel (a ``tpu_custom_call`` in the executable).  This is what the
interpreter cannot show: a kernel body the TPU compiler refuses, or tiles
that overflow the scoped VMEM at real widths.

The topology is described inside a module fixture, never at import (only
one process at a time may load the TPU library), and the tests skip when it
cannot be described.
"""
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.engine import kernels

# a k=8 plan of the dblp profile at scale 1.0 (951,231 edges) with its
# edges split into balanced blocks: padded half-edges and local vertices
# per partition (the compile needs the shapes only)
K = 8
E_MAX = 237_824
V_MAX = 124_672


@pytest.fixture(scope="module")
def one_chip():
    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache
    prev_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev_cache)
    compilation_cache.reset_cache()
    if prev_log is None:
        os.environ.pop("TPU_LOG_DIR", None)


def _struct(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(lowered):
    text = lowered.compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text


@pytest.mark.parametrize("combine", ["min", "add"])
def test_segment_scan_compiles_for_v5e(one_chip, combine):
    flags = _struct(one_chip, (E_MAX, K), jnp.bool_)
    vals = _struct(one_chip, (E_MAX, K), jnp.float32)
    _assert_kernel(kernels.segment_scan.lower(flags, vals, combine=combine))


@pytest.mark.parametrize("features", [4, 128])
def test_gspmm_scan_compiles_for_v5e(one_chip, features):
    # the lane padding gspmm applies: k_pad·F a multiple of 128
    step = 128 // math.gcd(features, 128)
    k_pad = -(-K // step) * step
    flags = _struct(one_chip, (E_MAX, k_pad), jnp.bool_)
    weights = _struct(one_chip, (E_MAX, k_pad), jnp.float32)
    vals = _struct(one_chip, (E_MAX, k_pad * features), jnp.float32)
    _assert_kernel(kernels._gspmm_scan.lower(flags, flags, weights, vals,
                                             combine="add"))


@pytest.mark.parametrize("combine", ["min", "add"])
def test_masked_update_compiles_for_v5e(one_chip, combine):
    state = _struct(one_chip, (K, V_MAX), jnp.float32)
    mask = _struct(one_chip, (K, V_MAX), jnp.bool_)
    _assert_kernel(kernels.masked_update.lower(state, state, mask, mask,
                                               combine=combine))
