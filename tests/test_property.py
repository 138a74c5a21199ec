"""Hypothesis property tests over the system's invariants."""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="optional dev dependency (pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.core import (
    DeviceGraph, baseline_pull, build_blocked, from_edges, tocab_pull,
    tocab_push,
)


@st.composite
def random_graph(draw):
    n = draw(st.integers(4, 200))
    m = draw(st.integers(1, 600))
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    keep = src != dst
    if not keep.any():
        src, dst = np.array([0]), np.array([min(1, n - 1)])
    else:
        src, dst = src[keep], dst[keep]
    vals = rng.random(len(src), dtype=np.float32)
    return from_edges(n, src, dst, vals=vals, dedup=True)


@given(random_graph(), st.sampled_from([4, 16, 64]))
@settings(max_examples=25, deadline=None)
def test_tocab_equals_baseline(g, block_size):
    """Core invariant: blocking + compaction never changes the result."""
    dg = DeviceGraph.from_host(g)
    bg = build_blocked(g, block_size=block_size)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.random(g.n, dtype=np.float32))
    np.testing.assert_allclose(
        np.asarray(tocab_pull(bg, x)), np.asarray(baseline_pull(dg, x)),
        rtol=1e-4, atol=1e-5)


@given(random_graph(), st.sampled_from([8, 32]))
@settings(max_examples=25, deadline=None)
def test_partition_conservation(g, block_size):
    bg = build_blocked(g, block_size=block_size)
    mask = np.asarray(bg.edge_mask)
    perm = np.asarray(bg.edge_perm)[mask]
    assert np.array_equal(np.sort(perm), np.arange(g.m))
    # compaction: every local id < n_local of its block
    cidx = np.asarray(bg.compact_idx)
    nloc = np.asarray(bg.n_local)
    for b in range(bg.num_blocks):
        if mask[b].any():
            assert cidx[b][mask[b]].max() < nloc[b]
    # the flat phase-2 keys, padding included, never decrease
    flat = cidx + np.arange(bg.num_blocks)[:, None] * bg.local_budget
    assert (np.diff(flat.reshape(-1)) >= 0).all()


@given(random_graph())
@settings(max_examples=15, deadline=None)
def test_push_pull_duality(g):
    """push on G == pull on G (same math, different dataflow)."""
    dg = DeviceGraph.from_host(g)
    bgp = build_blocked(g, block_size=32, direction="push")
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.random(g.n, dtype=np.float32))
    np.testing.assert_allclose(
        np.asarray(tocab_push(bgp, x)), np.asarray(baseline_pull(dg, x)),
        rtol=1e-4, atol=1e-5)


@given(st.integers(0, 2**31 - 1), st.sampled_from([16, 64]))
@settings(max_examples=10, deadline=None)
def test_pagerank_mass_conservation(seed, block_size):
    """PR with dangling redistribution conserves probability mass."""
    from repro.core import pagerank, rmat_graph
    g = rmat_graph(scale=6, edge_factor=4, seed=seed % 1000)
    dg = DeviceGraph.from_host(g)
    bg = build_blocked(g, block_size=block_size)
    r, _ = pagerank(dg, bg, variant="gc-pull", tol=1e-9)
    assert float(jnp.sum(r)) == pytest.approx(1.0, abs=1e-4)
    assert float(jnp.min(r)) > 0
