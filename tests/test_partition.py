"""TOCAB partitioning invariants (DESIGN.md §7, items 1-2)."""
import numpy as np
import pytest

from repro.core import build_blocked, rmat_graph, uniform_random_graph


@pytest.mark.parametrize("direction", ["pull", "push"])
@pytest.mark.parametrize("block_size", [32, 128, 1024])
def test_edge_conservation(direction, block_size):
    g = rmat_graph(scale=9, edge_factor=8, seed=3)
    bg = build_blocked(g, block_size=block_size, direction=direction)
    # every original edge appears exactly once across subgraph slabs
    mask = np.asarray(bg.edge_mask)
    perm = np.asarray(bg.edge_perm)[mask]
    assert perm.shape[0] == g.m
    assert np.array_equal(np.sort(perm), np.arange(g.m))
    assert int(np.asarray(bg.n_edges).sum()) == g.m


def test_window_confinement():
    """Gather side of each block stays within [b·B, (b+1)·B) — the cache
    window guarantee that makes the scheme work."""
    g = rmat_graph(scale=8, edge_factor=8, seed=1)
    bg = build_blocked(g, block_size=64)
    widx = np.asarray(bg.window_idx)
    mask = np.asarray(bg.edge_mask)
    assert widx[mask].min() >= 0
    assert widx[mask].max() < bg.block_size


def test_local_id_bijection():
    g = rmat_graph(scale=8, edge_factor=8, seed=2)
    bg = build_blocked(g, block_size=64)
    src, dst = g.edges()
    idmap = np.asarray(bg.id_map)
    cidx = np.asarray(bg.compact_idx)
    mask = np.asarray(bg.edge_mask)
    nloc = np.asarray(bg.n_local)
    for b in range(bg.num_blocks):
        em = mask[b]
        if not em.any():
            continue
        locals_used = np.unique(cidx[b][em])
        # dense: 0..n_local-1, no gaps
        assert np.array_equal(locals_used, np.arange(nloc[b]))
        # id_map maps each local to the correct global dst
        globals_mapped = idmap[b][cidx[b][em]]
        lo, hi = b * bg.block_size, (b + 1) * bg.block_size
        orig = np.asarray(bg.edge_perm)[b][em]
        assert np.array_equal(globals_mapped, dst[orig])
        assert (src[orig] >= lo).all() and (src[orig] < hi).all()
        # padded id_map slots point at the drop segment n
        assert (idmap[b][nloc[b]:] == g.n).all()


def test_subgraph_degree_drop():
    """Paper Table 1: average degree inside subgraphs falls vs the original
    graph (the reason VWC loses SIMD efficiency after blocking)."""
    g = rmat_graph(scale=12, edge_factor=12, seed=5)
    bg = build_blocked(g, block_size=256)
    per_block_nloc = np.asarray(bg.n_local).astype(np.float64)
    per_block_edges = np.asarray(bg.n_edges).astype(np.float64)
    sub_deg = per_block_edges.sum() / per_block_nloc.sum()
    assert sub_deg < g.m / g.n  # strictly lower average degree


def test_block_count_scaling():
    g = uniform_random_graph(4096, 32768, seed=0)
    small = build_blocked(g, block_size=128)
    large = build_blocked(g, block_size=1024)
    assert small.num_blocks == 32 and large.num_blocks == 4
    # paper Table 4: L2/VMEM-sized blocks → far fewer partitions


def test_choose_block_size_vmem_budget():
    from repro.core import choose_block_size
    bs = choose_block_size(10**7, fast_mem_bytes=4 * 1024 * 1024)
    assert bs * 4 <= 4 * 1024 * 1024
    assert bs % 128 == 0


@pytest.mark.parametrize("direction", ["pull", "push"])
@pytest.mark.parametrize("graph", ["edge_free_block", "rmat"])
def test_compact_idx_sorted_with_padding(request, graph, direction):
    """The layout contract phase 2 declares to the compiler: each padded
    slot repeats its block's last local id, so the flat scatter keys
    ``compact_idx + b·local_budget`` never decrease over the whole slab."""
    if graph == "rmat":
        g, block_size = rmat_graph(scale=9, edge_factor=8, seed=3), 64
    else:
        g, block_size = request.getfixturevalue("edge_free_block_graph")
    bg = build_blocked(g, block_size=block_size, direction=direction)
    cidx = np.asarray(bg.compact_idx)
    mask = np.asarray(bg.edge_mask)
    nloc = np.asarray(bg.n_local)
    if graph == "edge_free_block":
        assert not mask[2].any() and nloc[2] == 0
        assert (nloc == bg.local_budget).sum() == 2
    assert (~mask).any()  # the slab has padding to check
    for b in range(bg.num_blocks):
        assert (cidx[b][~mask[b]] == max(nloc[b] - 1, 0)).all()
    flat = cidx + np.arange(bg.num_blocks)[:, None] * bg.local_budget
    assert (np.diff(flat.reshape(-1)) >= 0).all()
