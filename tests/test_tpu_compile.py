"""Ahead-of-time compiles of the engine's Pallas kernels for a TPU v5e.

Nothing runs: each test lowers a kernel entry point for one chip of a
described ``v5e:2x2`` topology, with ``ShapeDtypeStruct`` operands at the
``dblp`` (scale 1.0, k=8) plan shapes, and asserts that Mosaic compiled the
kernel (a ``tpu_custom_call`` in the executable) under its own name, which
is how a profiler trace tells the kernels apart.  This is what the
interpreter cannot show: a kernel body the TPU compiler refuses, or tiles
that overflow the scoped VMEM at real widths.  One more test lowers the
whole single-device superstep loop at small plan shapes and reads where the
compiler put the append-region scatter.

The topology is described inside a module fixture, never at import (only
one process at a time may load the TPU library), and the tests skip when it
cannot be described.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import engine as E
from repro.engine import kernels, runtime
from repro.engine.plan import PartitionPlan

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


def _assert_kernel(lowered, name):
    text = lowered.compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls
    assert all(re.search(rf"%{name}(\.\d+)? = ", c) for c in calls)


@pytest.mark.parametrize("combine", ["min", "add"])
def test_segment_scan_compiles_for_v5e(one_chip, combine):
    flags = _struct(one_chip, (E_MAX, K), jnp.bool_)
    vals = _struct(one_chip, (E_MAX, K), jnp.float32)
    _assert_kernel(kernels.segment_scan.lower(flags, vals, combine=combine),
                   "segment_scan")


@pytest.mark.parametrize("features", [4, 128])
def test_gspmm_scan_compiles_for_v5e(one_chip, features):
    # the lane padding gspmm applies: k_pad·F a multiple of 128
    step = 128 // math.gcd(features, 128)
    k_pad = -(-K // step) * step
    flags = _struct(one_chip, (E_MAX, k_pad), jnp.bool_)
    weights = _struct(one_chip, (E_MAX, k_pad), jnp.float32)
    vals = _struct(one_chip, (E_MAX, k_pad * features), jnp.float32)
    _assert_kernel(kernels._gspmm_scan.lower(flags, flags, weights, vals,
                                             combine="add"), "gspmm")


@pytest.mark.parametrize("combine", ["min", "add"])
def test_masked_update_compiles_for_v5e(one_chip, combine):
    state = _struct(one_chip, (K, V_MAX), jnp.float32)
    mask = _struct(one_chip, (K, V_MAX), jnp.bool_)
    _assert_kernel(kernels.masked_update.lower(state, state, mask, mask,
                                               combine=combine),
                   "masked_update")


def _computations(text: str) -> dict[str, list[str]]:
    """HLO text -> {computation name: its instruction lines}."""
    comps, name = {}, None
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY )?%(\S+) ", line)
        if m and line.rstrip().endswith("{"):
            name = m.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps


def _callers(comps: dict, attr: str) -> dict[str, set]:
    """{callee: computations naming it in ``attr=`` (``calls``, ``body``,
    ``branch_computations``)}."""
    out: dict[str, set] = {}
    for caller, lines in comps.items():
        for line in lines:
            for m in re.finditer(rf"\b{attr}=(\{{[^}}]*\}}|%[\w.\-]+)",
                                 line):
                for callee in re.findall(r"%([\w.\-]+)", m.group(1)):
                    out.setdefault(callee, set()).add(caller)
    return out


def test_append_scatter_runs_only_in_a_conditional_branch(one_chip):
    """The Pallas sweep's scatter into the [K, Vmax] aggregate sits in a
    ``conditional``'s branch, never straight in the superstep ``while``
    body, so a plan with an empty append region skips it."""
    k, e_max, v_max, n = 8, 1024, 256, 1500     # n != k·v_max
    i32, b, f32 = jnp.int32, jnp.bool_, jnp.float32

    def kv(dtype):
        return _struct(one_chip, (k, v_max), dtype)

    def ke(dtype):
        return _struct(one_chip, (k, e_max), dtype)

    def kk():
        return _struct(one_chip, (k,), i32)

    plan = PartitionPlan(
        k=k, n_vertices=n, v_max=v_max, e_max=e_max, epoch=0, e_slots=4096,
        local2global=kv(i32), vmask=kv(b), edge_tgt=ke(i32),
        edge_nbr=ke(i32), emask=ke(b), seg_start=ke(b), last_slot=kv(i32),
        replicated=kv(b), is_master=kv(b), n_local=kk(), n_edges_local=kk(),
        n_replicated=kk(), csr_fill=kk(), v_fill=kk(), edge_w=ke(f32),
        edge_slot=ke(i32))
    kw = {"source": _struct(one_chip, (), i32)}
    text = runtime._run_single.lower(
        plan, E.SSSP, kw, None, runtime._steps(E.SSSP, None), 100_000,
        True).compile().as_text()
    comps = _computations(text)
    fused_by = _callers(comps, "calls")
    scatters = [c for c, lines in comps.items()
                if any(re.search(rf"= f32\[{k * v_max}\]\S* scatter\(", line)
                       for line in lines)]
    assert scatters
    owners, todo = set(), list(scatters)
    while todo:                       # climb out of (nested) fusions
        c = todo.pop()
        if c in fused_by:
            todo.extend(fused_by[c])
        else:
            owners.add(c)
    assert owners <= set(_callers(comps, "branch_computations"))
    assert not owners & set(_callers(comps, "body"))
