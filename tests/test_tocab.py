"""Engine equivalence: TOCAB == baseline across semirings/shapes (§7 item 3)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    DeviceGraph, baseline_pull, baseline_push, build_blocked, cb_pull,
    rmat_graph, tocab_pull, tocab_push,
)
from repro.core.tocab import (
    blocked_edge_values, tocab_edge_reduce, tocab_gather_src,
)


@pytest.fixture(scope="module")
def setup():
    g = rmat_graph(scale=9, edge_factor=8, seed=7, weights=True)
    return (
        g,
        DeviceGraph.from_host(g),
        build_blocked(g, block_size=128, direction="pull"),
        build_blocked(g, block_size=128, direction="push"),
    )


def _vals(n, d=None, seed=0):
    rng = np.random.default_rng(seed)
    shape = (n,) if d is None else (n, d)
    return jnp.asarray(rng.random(shape, dtype=np.float32))


@pytest.mark.parametrize("d", [None, 3, 16])
def test_sum_semiring(setup, d):
    g, dg, bg, bgp = setup
    x = _vals(g.n, d)
    ref = baseline_pull(dg, x)
    np.testing.assert_allclose(tocab_pull(bg, x), ref, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(cb_pull(bg, x), ref, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(tocab_push(bgp, x), ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("reduce", ["min", "max"])
def test_minmax_semiring(setup, reduce):
    g, dg, bg, bgp = setup
    x = _vals(g.n)
    ref = np.asarray(baseline_pull(dg, x, reduce=reduce))
    out = np.asarray(tocab_pull(bg, x, reduce=reduce))
    finite = np.isfinite(ref)
    assert (np.isfinite(out) == finite).all()
    np.testing.assert_allclose(out[finite], ref[finite], rtol=1e-6)


@pytest.mark.parametrize("reduce", ["sum", "min", "max"])
def test_pull_with_edge_free_block(edge_free_block_graph, reduce):
    """A block without edges and blocks filled to ``local_budget``: the
    corner cases of the padding that keeps phase 2's keys sorted."""
    g, block_size = edge_free_block_graph
    dg = DeviceGraph.from_host(g)
    bg = build_blocked(g, block_size=block_size, direction="pull")
    x = _vals(g.n)
    ref = np.asarray(baseline_pull(dg, x, reduce=reduce))
    out = np.asarray(tocab_pull(bg, x, reduce=reduce))
    finite = np.isfinite(ref)
    assert (np.isfinite(out) == finite).all()
    np.testing.assert_allclose(out[finite], ref[finite], rtol=1e-6)


def _scatters_sorted(text: str) -> dict:
    """``{output length: indices_are_sorted}`` of each scatter in lowered
    StableHLO text."""
    found = re.findall(
        r'"stablehlo\.scatter".*?indices_are_sorted = (\w+).*?'
        r'\}\) : \(tensor<(\d+)', text, flags=re.S)
    return {int(size): flag == "true" for flag, size in found}


@pytest.mark.parametrize("engine", ["tocab_pull", "tocab_edge_reduce"])
def test_phase2_scatter_declared_sorted(setup, engine):
    """Phase 2's scatter into the flat partial slab carries the sorted
    hint (the TPU compiler then drops its presort); phase 3's, keyed by
    ``id_map``, does not."""
    g, _, bg, _ = setup
    if engine == "tocab_pull":
        lowered = jax.jit(tocab_pull).lower(bg, _vals(g.n))
    else:
        lowered = jax.jit(tocab_edge_reduce).lower(bg, _vals(g.m))
    sorted_by_size = _scatters_sorted(lowered.as_text())
    assert sorted_by_size == {bg.flat_partial_size: True, g.n + 1: False}


def test_combine_minplus(setup):
    """min-plus semiring (SSSP relaxation step)."""
    g, dg, bg, _ = setup
    x = _vals(g.n)
    plus = lambda d, w: d + w
    ref = baseline_pull(dg, x, reduce="min", combine=plus)
    out = tocab_pull(bg, x, reduce="min", combine=plus)
    r, o = np.asarray(ref), np.asarray(out)
    f = np.isfinite(r)
    np.testing.assert_allclose(o[f], r[f], rtol=1e-6)


def test_dynamic_edge_values(setup):
    """GNN path: per-edge dynamic values through the blocked layout."""
    g, dg, bg, _ = setup
    rng = np.random.default_rng(3)
    ev = jnp.asarray(rng.random(g.m, dtype=np.float32))
    # edge-value reduce == flat segment sum by dst
    src, dst = g.edges()
    import jax
    ref = jax.ops.segment_sum(ev, jnp.asarray(dst, jnp.int32), num_segments=g.n)
    out = tocab_edge_reduce(bg, ev, reduce="sum")
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
    # round trip: flat → blocked slabs → (masked) flat
    slab = blocked_edge_values(bg, ev)
    mask = np.asarray(bg.edge_mask)
    flat_back = np.zeros(g.m, np.float32)
    flat_back[np.asarray(bg.edge_perm)[mask]] = np.asarray(slab)[mask]
    np.testing.assert_allclose(flat_back, ev, rtol=0)


def test_gather_src_matches_flat(setup):
    g, dg, bg, _ = setup
    x = _vals(g.n, 4)
    src, _ = g.edges()
    ref = np.asarray(x)[src]
    out = np.asarray(tocab_gather_src(bg, x))
    np.testing.assert_allclose(out, ref, rtol=0)


def test_push_pull_same_math(setup):
    g, dg, bg, bgp = setup
    x = _vals(g.n)
    np.testing.assert_allclose(
        baseline_push(dg, x), baseline_pull(dg, x), rtol=1e-6)


def test_untouched_vertices_identity():
    """Vertices with no in-edges: 0 for sum, ±inf for min/max."""
    import repro.core as c
    g = c.from_edges(8, np.array([0, 1]), np.array([2, 2]))
    bg = c.build_blocked(g, block_size=4)
    x = jnp.arange(8, dtype=jnp.float32)
    s = np.asarray(c.tocab_pull(bg, x))
    assert s[2] == pytest.approx(1.0) and (s[[0, 1, 3, 4, 5, 6, 7]] == 0).all()
    mn = np.asarray(c.tocab_pull(bg, x, reduce="min"))
    assert np.isinf(mn[[0, 1, 3]]).all() and mn[2] == 0.0


@pytest.mark.parametrize("block_size", [32, 128])
def test_2d_blocking_equals_baseline(setup, block_size):
    """Paper §3.1 ablation: 2D blocking is numerically identical (and
    produces quadratically more tiles — the paper's overhead argument)."""
    from repro.core.ablations import build_blocked_2d, tocab_pull_2d
    g, dg, bg, _ = setup
    b2 = build_blocked_2d(g, block_size=block_size)
    x = _vals(g.n)
    np.testing.assert_allclose(
        np.asarray(tocab_pull_2d(b2, x)), np.asarray(baseline_pull(dg, x)),
        rtol=2e-5, atol=2e-5)
    assert b2.tiles_per_side ** 2 >= bg.num_blocks ** 2 // 4


@pytest.mark.parametrize("num_bins", [4, 32])
def test_propagation_blocking_equals_baseline(setup, num_bins):
    from repro.core.ablations import propagation_blocking_pull
    g, dg, bg, _ = setup
    x = _vals(g.n)
    np.testing.assert_allclose(
        np.asarray(propagation_blocking_pull(dg, x, num_bins=num_bins)),
        np.asarray(baseline_pull(dg, x)), rtol=2e-5, atol=2e-5)
