"""Sparsity-aware load balancing: schedule invariants + engine equivalence."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    UNWEIGHTED, DeviceGraph, balanced_pull, baseline_pull, build_blocked,
    make_schedule, pagerank, rmat_graph, spmv, tocab_edge_reduce, tocab_pull,
    tocab_push,
)
from repro.core.balance import (
    BIN_DENSE, BIN_NAMES, BIN_SPARSE, bin_pull_partials, require_schedule,
)

INF = float("inf")


@pytest.fixture(scope="module")
def setup():
    g = rmat_graph(scale=9, edge_factor=8, seed=7, weights=True)
    return (
        g,
        DeviceGraph.from_host(g),
        build_blocked(g, block_size=128, direction="pull",
                      bin_thresholds="auto"),
        build_blocked(g, block_size=128, direction="push",
                      bin_thresholds="auto"),
    )


def _vals(n, d=None, seed=0):
    rng = np.random.default_rng(seed)
    shape = (n,) if d is None else (n, d)
    return jnp.asarray(rng.random(shape, dtype=np.float32))


def test_schedule_computed_at_build(setup):
    g, dg, bg, bgp = setup
    for b in (bg, bgp):
        sched = require_schedule(b)
        assert len(sched.bins) == b.num_blocks
        assert sum(sched.blocks_per_bin) == b.num_blocks
        assert sum(sched.edges_per_bin) == g.m
        # bins partition the block set
        seen = sorted(i for bin_id in range(3) for i in sched.blocks_in(bin_id))
        assert seen == list(range(b.num_blocks))
        hash(sched)  # static jit aux data must be hashable


def test_row_budget_covers_bins(setup):
    g, dg, bg, bgp = setup
    sched = bg.schedule
    n_local = np.asarray(bg.n_local)
    for bin_id in range(3):
        ids = sched.blocks_in(bin_id)
        if not ids:
            continue
        rb = sched.row_budget_per_bin[bin_id]
        assert rb >= int(n_local[list(ids)].max())
        assert rb % 8 == 0
    # push: classification rows are the window side, but the compact budget
    # must still cover compact_idx (n_local) — the edge-reduce slab width
    for b in (bg, bgp):
        sched = b.schedule
        n_local = np.asarray(b.n_local)
        for bin_id in range(3):
            ids = sched.blocks_in(bin_id)
            if not ids:
                continue
            cb = sched.compact_budget_per_bin[bin_id]
            assert cb >= int(n_local[list(ids)].max())
            assert cb % 8 == 0


def test_empty_blocks_go_sparse():
    sched = make_schedule([0, 10, 100], [1, 2, 2])
    assert sched.bins[0] == BIN_SPARSE
    assert sched.bins[2] == BIN_DENSE


@pytest.mark.parametrize("reduce", ["sum", "min", "max"])
def test_balanced_pull_matches_uniform(setup, reduce):
    g, dg, bg, _ = setup
    x = _vals(g.n)
    ref = np.asarray(tocab_pull(bg, x, reduce=reduce))
    out = np.asarray(tocab_pull(bg, x, reduce=reduce, schedule="balanced"))
    f = np.isfinite(ref)
    assert (np.isfinite(out) == f).all()
    np.testing.assert_allclose(out[f], ref[f], rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("d", [None, 5])
def test_balanced_push_matches_baseline(setup, d):
    g, dg, _, bgp = setup
    x = _vals(g.n, d)
    ref = np.asarray(baseline_pull(dg, x))
    out = np.asarray(tocab_push(bgp, x, schedule="balanced"))
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_balanced_unweighted_combine(setup):
    """PageRank semantics: UNWEIGHTED ignores stored edge values and keeps
    the dense tile path eligible."""
    g, dg, bg, _ = setup
    x = _vals(g.n)
    ref = np.asarray(baseline_pull(dg, x, combine=UNWEIGHTED))
    out = np.asarray(tocab_pull(bg, x, combine=UNWEIGHTED, schedule="balanced"))
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("direction", ["pull", "push"])
def test_balanced_edge_reduce(setup, direction):
    import jax
    g, dg, bg, bgp = setup
    b = bg if direction == "pull" else bgp
    rng = np.random.default_rng(3)
    ev = jnp.asarray(rng.random(g.m, dtype=np.float32))
    src, dst = g.edges()
    compact_side = dst if direction == "pull" else src
    ref = jax.ops.segment_sum(
        ev, jnp.asarray(compact_side, jnp.int32), num_segments=g.n)
    out = tocab_edge_reduce(b, ev, schedule="balanced")
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        out, tocab_edge_reduce(b, ev), rtol=2e-5, atol=2e-5)


def test_balanced_edge_reduce_push_hub():
    """Hub-destination push graph: few window rows (dst) but many compact
    rows (src) per block — regression test for sizing the edge-reduce slab
    from the window budget (compact ids spilled into adjacent blocks)."""
    from repro.core import from_edges

    n = 128
    src = np.concatenate([np.arange(1, n), np.arange(n)])
    dst = np.concatenate([np.zeros(n - 1, np.int64), (np.arange(n) + 1) % n])
    keep = src != dst
    g = from_edges(n, src[keep], dst[keep], dedup=True)
    bgp = build_blocked(g, block_size=32, direction="push")
    rng = np.random.default_rng(5)
    ev = jnp.asarray(rng.random(g.m, dtype=np.float32))
    np.testing.assert_allclose(
        np.asarray(tocab_edge_reduce(bgp, ev, schedule="balanced")),
        np.asarray(tocab_edge_reduce(bgp, ev)),
        rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("thresholds", [(INF, INF), (0.0, 0.0), (0.0, INF)])
def test_single_bin_boundaries(setup, thresholds):
    """Degenerate thresholds force every block into one bin — all-sparse,
    all-dense, all-medium — and the result must not change."""
    g, dg, _, _ = setup
    bg = build_blocked(g, block_size=128, bin_thresholds=thresholds)
    lone = [i for i, n in enumerate(bg.schedule.blocks_per_bin)
            if n == bg.num_blocks]
    assert lone, bg.schedule.blocks_per_bin
    x = _vals(g.n)
    ref = np.asarray(baseline_pull(dg, x))
    out = np.asarray(tocab_pull(bg, x, schedule="balanced"))
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_pallas_dense_bin_grid(setup):
    """The Pallas tile kernel on the dense bin only (bin-aware grid)."""
    g, dg, _, _ = setup
    bg = build_blocked(g, block_size=64, bin_thresholds=(0.0, 0.0))
    assert bg.schedule.blocks_per_bin[BIN_DENSE] == bg.num_blocks
    x = _vals(g.n)
    ref = np.asarray(baseline_pull(dg, x))
    out = np.asarray(balanced_pull(bg, x, dense_impl="pallas"))
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_bin_partials_shape(setup):
    g, dg, bg, _ = setup
    x = _vals(g.n)
    sched = bg.schedule
    for bin_id in range(3):
        sub = bin_pull_partials(bg, bin_id, x)
        if not sched.blocks_in(bin_id):
            assert sub is None
            continue
        k = len(sched.blocks_in(bin_id))
        rb = min(sched.row_budget_per_bin[bin_id], bg.local_budget)
        assert sub.shape == (k, rb)


def test_missing_schedule_raises(setup):
    g, dg, _, _ = setup
    bg = build_blocked(g, block_size=128, classify=False)
    assert bg.schedule is None
    with pytest.raises(ValueError, match="BlockSchedule"):
        tocab_pull(bg, _vals(g.n), schedule="balanced")


def test_pagerank_balanced(setup):
    g, dg, bg, _ = setup
    r_u, it_u = pagerank(dg, bg, variant="gc-pull", tol=1e-8)
    r_b, it_b = pagerank(dg, bg, variant="gc-pull", tol=1e-8,
                         schedule="balanced")
    # per-bin reassociation may shift convergence by an iteration near tol
    assert abs(int(it_b) - int(it_u)) <= 1
    np.testing.assert_allclose(np.asarray(r_b), np.asarray(r_u),
                               rtol=1e-5, atol=1e-7)


def test_spmv_balanced(setup):
    g, dg, bg, _ = setup
    x = _vals(g.n)
    np.testing.assert_allclose(
        np.asarray(spmv(dg, bg, x, variant="gc-pull", schedule="balanced")),
        np.asarray(spmv(dg, bg, x, variant="gc-pull")),
        rtol=2e-5, atol=2e-5)


def test_obs_bin_counters(setup):
    from repro.obs.metrics import registry
    g, dg, bg, _ = setup
    tocab_pull(bg, _vals(g.n), schedule="balanced")
    snap = registry.snapshot()
    assert "tocab.balance.bin_blocks" in snap
    labels = {tuple(sorted(s["labels"].items()))
              for s in snap["tocab.balance.bin_blocks"]["series"]}
    assert any(("bin", name) in lab for name in BIN_NAMES for lab in labels)
