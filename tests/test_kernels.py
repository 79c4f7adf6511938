"""Per-kernel validation: sweep shapes/dtypes, assert_allclose vs ref.py
oracles, plus hypothesis property tests. Kernels run in interpret mode
(Python execution of the TPU kernel body) on CPU."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref


# ---------------------------------------------------------------------------
# lane_cumsum
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,k", [(64, 4), (1000, 20), (2048, 128), (777, 33)])
@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
def test_lane_cumsum_shapes(s, k, dtype):
    x = jax.random.randint(jax.random.key(0), (s, k), -5, 10).astype(dtype)
    got = ops.lane_cumsum(x, block_s=256)
    want = ref.cumsum_lanes(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@given(s=st.integers(1, 300), k=st.integers(1, 40), seed=st.integers(0, 99))
@settings(max_examples=12, deadline=None)
def test_lane_cumsum_property(s, k, seed):
    x = jax.random.randint(jax.random.key(seed), (s, k), 0, 7, dtype=jnp.int32)
    got = ops.lane_cumsum(x, block_s=128)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(jnp.cumsum(x, 0)))


# ---------------------------------------------------------------------------
# frontier_min
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k,v", [(4, 100), (20, 5000), (7, 333), (128, 4096)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_frontier_min_shapes(k, v, dtype):
    key = jax.random.key(1)
    k1, k2 = jax.random.split(key)
    state = jax.random.uniform(k1, (k, v), jnp.float32, 0, 100).astype(dtype)
    member = jax.random.bernoulli(k2, 0.4, (k, v))
    got = ops.frontier_min(state, member, block_v=512)
    want = ref.kreduce_min(state, member)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_frontier_min_all_masked_is_inf():
    state = jnp.ones((3, 50), jnp.float32)
    member = jnp.zeros((3, 50), jnp.bool_)
    got = ops.frontier_min(state, member, block_v=128)
    assert np.isinf(np.asarray(got)).all()


# ---------------------------------------------------------------------------
# minplus_sweep
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("v,e", [(100, 300), (513, 1000), (2048, 4096)])
def test_minplus_sweep_shapes(v, e):
    key = jax.random.key(2)
    ks, kd, km, kx = jax.random.split(key, 4)
    src = jax.random.randint(ks, (e,), 0, v, dtype=jnp.int32)
    dst = jax.random.randint(kd, (e,), 0, v, dtype=jnp.int32)
    mask = jax.random.bernoulli(km, 0.9, (e,))
    dist = jnp.where(jax.random.bernoulli(kx, 0.3, (v,)),
                     jax.random.uniform(kx, (v,), jnp.float32, 0, 10),
                     jnp.inf)
    got = ops.minplus_sweep(dist, src, dst, mask, block_v=256, block_e=256)
    want = ref.minplus_relax(dist, src, dst, mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


@given(v=st.integers(2, 200), e=st.integers(1, 400), seed=st.integers(0, 50))
@settings(max_examples=10, deadline=None)
def test_minplus_sweep_property(v, e, seed):
    key = jax.random.key(seed)
    ks, kd, kx = jax.random.split(key, 3)
    src = jax.random.randint(ks, (e,), 0, v, dtype=jnp.int32)
    dst = jax.random.randint(kd, (e,), 0, v, dtype=jnp.int32)
    mask = jnp.ones((e,), jnp.bool_)
    dist = jnp.where(jnp.arange(v) == 0, 0.0, jnp.inf).astype(jnp.float32)
    got = ops.minplus_sweep(dist, src, dst, mask, block_v=128, block_e=128)
    want = ref.minplus_relax(dist, src, dst, mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))
    # a sweep never increases any distance (monotone relaxation)
    assert (np.asarray(got) <= np.asarray(dist)).all()


def test_minplus_iterated_equals_bfs():
    """Iterating the kernel's sweep reaches the BFS fixed point."""
    from repro.core import graph
    from repro.core.algorithms import reference_sssp
    g = graph.watts_strogatz(300, 4, 0.1, seed=0)
    dist = jnp.where(jnp.arange(g.n_vertices) == 0, 0.0, jnp.inf).astype(jnp.float32)
    for _ in range(200):
        nd = ops.minplus_sweep(dist, g.src, g.dst, g.edge_mask,
                               block_v=256, block_e=512)
        if bool(jnp.all(nd == dist)):
            break
        dist = nd
    ref_d, _ = reference_sssp(g, 0)
    np.testing.assert_allclose(np.asarray(dist), np.asarray(ref_d))
