"""Fused TOCAB pipeline: bit-equivalence with the slab engines.

The fused path (``impl="fused"``) keeps the per-block partial accumulator
resident and fuses the per-vertex apply epilogue — it is a pure execution
transform, so every engine call must return the *exact* bits of the slab
path (same per-destination operand order).  Full algorithm loops
(``pagerank``'s ``while_loop``) are compared with a tight ``allclose``
instead: XLA compiles the identical program differently inside a
``while_loop`` body, which perturbs even slab-vs-slab at ~1e-9.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    DeviceGraph, build_blocked, from_edges, pagerank,
    pagerank_iteration, rmat_graph, spmv, tocab_edge_reduce, tocab_pull,
    tocab_push,
)
from repro.core.traversal import bfs, sssp


@pytest.fixture(scope="module")
def setup():
    g = rmat_graph(scale=9, edge_factor=8, seed=7, weights=True)
    dg = DeviceGraph.from_host(g)
    bg = build_blocked(g, block_size=128, direction="pull")
    bgp = build_blocked(g, block_size=128, direction="push")
    return g, dg, bg, bgp


def _vals(n, d=None, seed=0):
    rng = np.random.default_rng(seed)
    shape = (n,) if d is None else (n, d)
    return jnp.asarray(rng.random(shape).astype(np.float32))


def hub_graph(n=256):
    """Everything points at a few hubs — extreme compaction ratio."""
    src = np.concatenate([np.arange(1, n), np.arange(n)])
    dst = np.concatenate([np.zeros(n - 1, np.int64), (np.arange(n) + 1) % n])
    keep = src != dst
    rng = np.random.default_rng(4)
    vals = rng.random(int(keep.sum()), dtype=np.float32)
    return from_edges(n, src[keep], dst[keep], vals=vals, dedup=True)


def balmix_graph(n=2048, deg=8, seed=0):
    """Mixed-density graph (dense/medium/sparse bins by construction) —
    small-scale twin of ``benchmarks.common.balance_mix_graph``."""
    rng = np.random.default_rng(seed)
    q = n // 4
    srcs, dsts = [], []
    for lo, hi, pool in ((0, q, 16), (q, 2 * q, 256), (2 * q, n, n)):
        src = np.repeat(np.arange(lo, hi), deg)
        dst = rng.integers(0, pool, src.shape[0])
        srcs.append(src)
        dsts.append(dst)
    src, dst = np.concatenate(srcs), np.concatenate(dsts)
    keep = src != dst
    vals = rng.random(int(keep.sum()), dtype=np.float32)
    return from_edges(n, src[keep], dst[keep], vals=vals, dedup=True)


# --------------------------------------------------------------------- #
# engine-level bit-identity
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("reduce", ["sum", "min", "max"])
@pytest.mark.parametrize("d", [None, 3])
def test_fused_pull_bitwise(setup, reduce, d):
    g, dg, bg, _ = setup
    x = _vals(g.n, d)
    np.testing.assert_array_equal(
        np.asarray(tocab_pull(bg, x, reduce=reduce, impl="fused")),
        np.asarray(tocab_pull(bg, x, reduce=reduce)))


@pytest.mark.parametrize("reduce", ["sum", "min", "max"])
@pytest.mark.parametrize("d", [None, 3])
def test_fused_push_bitwise(setup, reduce, d):
    g, dg, _, bgp = setup
    x = _vals(g.n, d, seed=1)
    np.testing.assert_array_equal(
        np.asarray(tocab_push(bgp, x, reduce=reduce, impl="fused")),
        np.asarray(tocab_push(bgp, x, reduce=reduce)))


def test_fused_combine_semiring(setup):
    g, dg, bg, bgp = setup
    x = _vals(g.n, seed=2)
    minplus = lambda v, ev: v + ev  # noqa: E731
    for fn, b in ((tocab_pull, bg), (tocab_push, bgp)):
        np.testing.assert_array_equal(
            np.asarray(fn(b, x, reduce="min", combine=minplus, impl="fused")),
            np.asarray(fn(b, x, reduce="min", combine=minplus)))


@pytest.mark.parametrize("direction", ["pull", "push"])
def test_fused_edge_reduce_bitwise(setup, direction):
    g, dg, bg, bgp = setup
    b = bg if direction == "pull" else bgp
    ev = _vals(g.m, seed=3)
    np.testing.assert_array_equal(
        np.asarray(tocab_edge_reduce(b, ev, impl="fused")),
        np.asarray(tocab_edge_reduce(b, ev)))


def test_fused_epilogue_bitwise(setup):
    """The fused kernel's baked-in affine apply == the slab path's trailing
    pass, bit for bit — the property PageRank's iteration relies on."""
    g, dg, bg, bgp = setup
    x = _vals(g.n, seed=4)
    eps = (0.85, 0.15 / g.n)
    for fn, b in ((tocab_pull, bg), (tocab_push, bgp)):
        slab = np.asarray(fn(b, x, epilogue=eps))
        np.testing.assert_array_equal(
            np.asarray(fn(b, x, epilogue=eps, impl="fused")), slab)
        np.testing.assert_array_equal(
            slab, np.asarray(fn(b, x)) * eps[0] + eps[1])


def test_fused_epilogue_requires_sum(setup):
    g, _, bg, _ = setup
    with pytest.raises(ValueError, match="sum"):
        tocab_pull(bg, _vals(g.n), reduce="min", epilogue=(1.0, 0.0),
                   impl="fused")


@pytest.mark.parametrize("build", [hub_graph, balmix_graph],
                         ids=["hub", "balmix"])
@pytest.mark.parametrize("direction", ["pull", "push"])
def test_fused_graph_families(build, direction):
    g = build()
    b = build_blocked(g, block_size=64, direction=direction)
    fn = tocab_pull if direction == "pull" else tocab_push
    x = _vals(g.n, seed=5)
    np.testing.assert_array_equal(
        np.asarray(fn(b, x, impl="fused")), np.asarray(fn(b, x)))
    np.testing.assert_array_equal(
        np.asarray(tocab_edge_reduce(b, _vals(g.m, seed=6), impl="fused")),
        np.asarray(tocab_edge_reduce(b, _vals(g.m, seed=6))))


@pytest.mark.parametrize("direction", ["pull", "push"])
def test_fused_pallas_interpret(setup, direction):
    """The Pallas kernels (interpret mode off-TPU) agree with the slab
    engines too, scalar and (n, d)."""
    from repro.kernels.tocab_fused import fused_pull, fused_push

    g, dg, bg, bgp = setup
    b = bg if direction == "pull" else bgp
    fused = fused_pull if direction == "pull" else fused_push
    slab = tocab_pull if direction == "pull" else tocab_push
    for d in (None, 2):
        x = _vals(g.n, d, seed=7)
        np.testing.assert_array_equal(
            np.asarray(fused(b, x, backend="pallas", interpret=True)),
            np.asarray(slab(b, x)))


def test_fused_push_bin_major_order(setup):
    """Disjoint destination windows ⇒ the balance module's bin-major visit
    order (the default when a schedule is attached) is bit-identical."""
    from repro.core.balance import fused_block_order
    from repro.kernels.tocab_fused import fused_push

    g, dg, _, bgp = setup
    order = fused_block_order(bgp)
    assert sorted(order) == list(range(bgp.num_blocks))
    x = _vals(g.n, seed=8)
    ref = np.asarray(tocab_push(bgp, x))
    np.testing.assert_array_equal(
        np.asarray(fused_push(bgp, x, block_order=order)), ref)
    np.testing.assert_array_equal(
        np.asarray(fused_push(bgp, x, block_order=None)), ref)


# --------------------------------------------------------------------- #
# dispatch
# --------------------------------------------------------------------- #
def test_fused_balanced_conflict(setup):
    g, _, bg, _ = setup
    with pytest.raises(ValueError, match="balanced"):
        tocab_pull(bg, _vals(g.n), schedule="balanced", impl="fused")


_ENTRIES = {
    "tocab_pull": lambda g, dg, bg, bgp, impl: tocab_pull(
        bg, _vals(g.n), impl=impl),
    "tocab_push": lambda g, dg, bg, bgp, impl: tocab_push(
        bgp, _vals(g.n), impl=impl),
    "tocab_edge_reduce": lambda g, dg, bg, bgp, impl: tocab_edge_reduce(
        bg, _vals(g.m), impl=impl),
    "pagerank": lambda g, dg, bg, bgp, impl: pagerank(dg, bg, impl=impl),
    "spmv": lambda g, dg, bg, bgp, impl: spmv(dg, bg, _vals(g.n), impl=impl),
}


@pytest.mark.parametrize("impl", ["warp", "auto"])
@pytest.mark.parametrize("entry", sorted(_ENTRIES))
def test_fused_unknown_impl(setup, entry, impl):
    """The caller's ``impl`` is the engine: a name that is not one, the
    retired ``"auto"`` included, raises at the entry."""
    with pytest.raises(ValueError, match="impl"):
        _ENTRIES[entry](*setup, impl)


# --------------------------------------------------------------------- #
# algorithm integration
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("variant", ["gc-pull", "gc-push"])
def test_pagerank_iteration_bitwise(setup, variant):
    g, dg, bg, bgp = setup
    bgv = bgp if variant == "gc-push" else bg
    rank = jnp.full((g.n,), 1.0 / g.n, jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(pagerank_iteration(variant, dg, bgv, rank, dg.out_degree,
                                      impl="fused")),
        np.asarray(pagerank_iteration(variant, dg, bgv, rank,
                                      dg.out_degree)))


@pytest.mark.parametrize("variant", ["gc-pull", "gc-push"])
def test_pagerank_fused(setup, variant):
    # while_loop bodies compile with different fusion choices than the same
    # program standalone (slab-vs-slab drifts ~1e-9 too) → allclose here.
    g, dg, bg, bgp = setup
    bgv = bgp if variant == "gc-push" else bg
    r_f, it_f = pagerank(dg, bgv, variant=variant, impl="fused", tol=1e-8)
    r_s, it_s = pagerank(dg, bgv, variant=variant, tol=1e-8)
    np.testing.assert_allclose(np.asarray(r_f), np.asarray(r_s),
                               rtol=1e-6, atol=1e-8)
    assert int(it_f) < 200 and int(it_s) < 200  # both converged


@pytest.mark.parametrize("variant", ["gc-pull", "gc-push"])
def test_spmv_fused_bitwise(setup, variant):
    g, dg, bg, bgp = setup
    bgv = bgp if variant == "gc-push" else bg
    x = _vals(g.n, seed=9)
    np.testing.assert_array_equal(
        np.asarray(spmv(dg, bgv, x, variant=variant, impl="fused")),
        np.asarray(spmv(dg, bgv, x, variant=variant)))
    np.testing.assert_array_equal(
        np.asarray(spmv(dg, bgv, x, variant=variant, impl="fused",
                        scale=2.5)),
        np.asarray(spmv(dg, bgv, x, variant=variant, scale=2.5)))


def test_traversal_fused(setup):
    g, dg, bg, _ = setup
    d_f, *_ = bfs(dg, bg, jnp.int32(0), impl="fused")
    d_s, *_ = bfs(dg, bg, jnp.int32(0))
    np.testing.assert_array_equal(np.asarray(d_f), np.asarray(d_s))
    dist_f, _ = sssp(dg, bg, jnp.int32(0), impl="fused")
    dist_s, _ = sssp(dg, bg, jnp.int32(0))
    np.testing.assert_array_equal(np.asarray(dist_f), np.asarray(dist_s))


# --------------------------------------------------------------------- #
# the point of the exercise: no partial slab in HBM
# --------------------------------------------------------------------- #
def test_fused_lowering_has_no_partial_slab(setup):
    """The compiled fused program must not allocate the
    ``(num_blocks, local_budget)`` partial buffer the slab path round-trips
    (asserted on the optimized HLO)."""
    g, dg, _, _ = setup
    bg = build_blocked(g, block_size=64, direction="pull")
    nb, lb = bg.num_blocks, bg.local_budget
    # the slab sizes must not collide with the edge slab's, or the shape
    # strings below can't discriminate the two buffers
    assert nb * lb != bg.edge_budget
    x = _vals(g.n, d=3)
    slab_shapes = (f"f32[{nb},{lb},3]", f"f32[{nb * lb},3]")

    slab_hlo = jax.jit(lambda v: tocab_pull(bg, v)).lower(x) \
        .compile().as_text()
    assert any(s in slab_hlo for s in slab_shapes), \
        "sanity: slab lowering should materialize the partial slab"

    fused_hlo = jax.jit(lambda v: tocab_pull(bg, v, impl="fused")) \
        .lower(x).compile().as_text()
    for s in slab_shapes:
        assert s not in fused_hlo, f"fused lowering materializes {s}"


def test_fused_obs_counters(setup):
    from repro.obs.metrics import registry as _obs

    g, dg, bg, _ = setup
    blocks = _obs.counter("tocab.fused_blocks")
    saved = _obs.counter("tocab.partial_hbm_bytes_saved")
    labels = dict(engine="fused_pull", direction="pull")
    b0 = blocks.value(**labels) or 0
    s0 = saved.value(**labels) or 0
    tocab_pull(bg, _vals(g.n), impl="fused")
    assert blocks.value(**labels) == b0 + bg.num_blocks
    assert saved.value(**labels) == s0 + bg.num_blocks * bg.local_budget * 4


# --------------------------------------------------------------------- #
# ragged edge budgets (tocab_spmm regression)
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("chunk", [7, 100, 999999])
def test_spmm_ragged_chunk(setup, chunk):
    """Any ``chunk`` gives the same partials, including ones that do not
    divide the edge budget: the kernel rounds it to a lane multiple that
    does."""
    from repro.kernels.tocab_spmm.ops import tocab_spmm

    g, dg, bg, _ = setup
    assert bg.edge_budget % 7, "pick a chunk that does not divide evenly"
    x = _vals(g.n, seed=10)
    ref = np.asarray(tocab_pull(bg, x))
    for mode in ("onehot", "scatter"):
        np.testing.assert_allclose(
            np.asarray(tocab_spmm(bg, x, mode=mode, chunk=chunk)),
            ref, rtol=2e-5, atol=2e-5)
