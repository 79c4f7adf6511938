"""Ahead-of-time compiles of the engine's Pallas kernels for a TPU v5e.

Nothing runs: each test lowers a kernel entry point for one chip of a
described ``v5e:2x2`` topology, with ``ShapeDtypeStruct`` operands at the
``dblp`` (scale 1.0, k=8) plan shapes, and asserts that Mosaic compiled the
kernel (a ``tpu_custom_call`` in the executable) under its own name, which
is how a profiler trace tells the kernels apart.  This is what the
interpreter cannot show: a kernel body the TPU compiler refuses, or tiles
that overflow the scoped VMEM at real widths.  Three more lower whole
superstep loops at small plan shapes: the single-device loop, to read
where the compiler put the append-region scatter; the vmapped loop of
the serving path, to see the batch on the scan's lanes; and the
shard_map loop over all four chips, to see the kernels inside it and
every cross-chip all-reduce under the ``engine.collective`` scope.

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
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
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
def topo():
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
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev_cache)
    compilation_cache.reset_cache()
    if prev_log is None:
        os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


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


# small plan shapes for the whole-loop compiles (n != k·v_max)
SMALL_K, SMALL_E, SMALL_V, SMALL_N = 8, 1024, 256, 1500


def _small_plan(sharding) -> PartitionPlan:
    """Operand shapes of a small k = 8 plan, every leaf on ``sharding``."""
    k, e_max, v_max = SMALL_K, SMALL_E, SMALL_V
    i32, b, f32 = jnp.int32, jnp.bool_, jnp.float32

    def kv(dtype):
        return _struct(sharding, (k, v_max), dtype)

    def ke(dtype):
        return _struct(sharding, (k, e_max), dtype)

    def kk():
        return _struct(sharding, (k,), i32)

    return PartitionPlan(
        k=k, n_vertices=SMALL_N, v_max=v_max, e_max=e_max, epoch=0,
        e_slots=4096, local2global=kv(i32), vmask=kv(b), edge_tgt=ke(i32),
        edge_nbr=ke(i32), emask=ke(b), seg_start=ke(b), last_slot=kv(i32),
        replicated=kv(b), is_master=kv(b), n_local=kk(), n_edges_local=kk(),
        n_replicated=kk(), csr_fill=kk(), v_fill=kk(), edge_w=ke(f32),
        edge_slot=ke(i32))


def _assert_scatters_only_in_branches(text: str, shape: str) -> None:
    """Every scatter into an ``f32[shape]`` aggregate sits in a
    ``conditional``'s branch, never straight in a ``while`` body."""
    comps = _computations(text)
    fused_by = _callers(comps, "calls")
    scatters = [c for c, lines in comps.items()
                if any(re.search(rf"= f32\[{shape}\]\S* scatter\(", line)
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


def test_append_scatter_runs_only_in_a_conditional_branch(one_chip):
    """The Pallas sweep's scatter into the [K, Vmax] aggregate sits in a
    ``conditional``'s branch, never straight in the superstep ``while``
    body, so a plan with an empty append region skips it."""
    kw = {"source": _struct(one_chip, (), jnp.int32)}
    text = runtime._run_single.lower(
        _small_plan(one_chip), E.SSSP, kw, None,
        runtime._steps(E.SSSP, None), 100_000, True).compile().as_text()
    _assert_scatters_only_in_branches(text, str(SMALL_K * SMALL_V))


def _gathers(text: str) -> list[tuple[int, int, str]]:
    """(output elements, index tuples, line) of each ``gather``: every
    index tuple fetches one ``slice_sizes`` block of the output."""
    out = []
    for line in text.splitlines():
        m = re.search(r"= f32\[([\d,]+)\]\S* gather\(.*slice_sizes="
                      r"\{([\d,]+)\}", line)
        if m:
            n = math.prod(int(d) for d in m.group(1).split(","))
            out.append((n, n // math.prod(int(d) for d in
                                         m.group(2).split(",")), line))
    return out


def test_batched_loop_folds_the_batch_onto_the_scan_lanes(one_chip):
    """The vmapped sssp loop at bucket 32 (the serving path) runs
    ``segment_scan`` over K·B lanes; its scatter into the [K·Vmax, B]
    aggregate runs only in the append-region branch; and each vertex's
    aggregate is one K·Vmax-index gather of B-wide rows, not B·K·Vmax
    single values."""
    k, v_max, b = SMALL_K, SMALL_V, 32

    def batched(plan, sources):
        return jax.vmap(lambda s: runtime._run_single(
            plan, E.SSSP, {"source": s}, None, runtime._steps(E.SSSP, None),
            100_000, True))(sources)

    text = jax.jit(batched).lower(
        _small_plan(one_chip),
        _struct(one_chip, (b,), jnp.int32)).compile().as_text()
    scans = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and re.search(r"%segment_scan(\.\d+)? = ", line)]
    assert scans
    assert all(re.search(rf"= f32\[\d+,{k * b}\]", line) for line in scans)
    _assert_scatters_only_in_branches(text, f"{k * v_max},{b}")
    per_vertex = [(idx, line) for n, idx, line in _gathers(text)
                  if n == k * v_max * b]
    assert any("engine.sweep" in line for _, line in per_vertex)
    assert all(idx == k * v_max for idx, _ in per_vertex)


@pytest.mark.parametrize("program,kernel_names", [
    ("sssp", {"segment_scan", "masked_update"}),
    ("gcn_layer", {"gspmm", "masked_update"})])
def test_sharded_loop_runs_the_kernels_on_four_chips(topo, program,
                                                     kernel_names):
    """The shard_map superstep loop over the four chips of a v5e:2x2, two
    partitions a chip: Mosaic compiles the kernels inside it, and every
    all-reduce the compiler emits sits under ``engine.collective``."""
    mesh = Mesh(topo.devices, ("parts",))
    rep = NamedSharding(mesh, P())
    n, i32, f32 = SMALL_N, jnp.int32, jnp.float32
    prog, kw = {
        "sssp": (E.SSSP, {"source": _struct(rep, (), i32)}),
        "gcn_layer": (E.GCN_LAYER, {
            "degrees": _struct(rep, (n,), i32),
            "x": _struct(rep, (n, 8), f32),
            "weight": _struct(rep, (8, 4), f32)})}[program]
    text = runtime._run_sharded.lower(
        _small_plan(NamedSharding(mesh, P("parts"))), kw, None, prog=prog,
        mesh=mesh, axis="parts", k_local=SMALL_K // 4,
        max_supersteps=runtime._steps(prog, None), max_local_iters=100_000,
        use_pallas=True).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    named = {m.group(1) for c in calls
             for m in [re.search(r"%(\w+?)(\.\d+)? = ", c)] if m}
    assert named == kernel_names
    reduces = [line for line in text.splitlines()
               if re.search(r" all-reduce(-start)?\(", line)]
    assert reduces
    assert all("/engine.collective/" in line for line in reduces)
