"""Row-count pull: on a symmetric graph a pull level counts the frontier
vertices in each CSR row (one gather per arc, an int32 prefix sum) instead
of running the slab engine's max-pull.  ``DeviceGraph.from_host`` must set
``symmetric`` exactly, the count must reach what the max-pull reaches,
BFS and BC must keep networkx's answers, and the pull branch must hold no
scatter and read no blocked layout; a directed graph keeps the slab pull.
"""
import collections
import dataclasses

import jax
import jax.numpy as jnp
import networkx as nx
import numpy as np
import pytest

from repro.core import (
    DeviceGraph, INF_DEPTH, bc, bfs, build_blocked, grid_graph, rmat_graph,
    to_networkx, tocab,
)
from repro.core import graph as G
from repro.core.traversal import (
    DEFAULT_ALPHA, _beamer_switch, _bfs_jit, _frontier_reach,
    _row_count_reach,
)
from repro.obs.metrics import registry


def _undirected(g):
    """``g`` with the reverse of every arc, duplicates removed."""
    src, dst = g.edges()
    return G.from_edges(g.n, np.concatenate([src, dst]),
                        np.concatenate([dst, src]), dedup=True)


def _hub_graph():
    """Undirected: vertex 0 joined to 40 others, a sparse rest among
    vertices 1..150, and vertices 151..255 isolated."""
    rng = np.random.default_rng(7)
    src = np.concatenate([np.zeros(40, np.int64), rng.integers(1, 151, 300)])
    dst = np.concatenate([rng.choice(np.arange(1, 151), 40, False),
                          rng.integers(1, 151, 300)])
    keep = src != dst
    return _undirected(G.from_edges(256, src[keep], dst[keep]))


def _directed_cycle():
    """Every vertex has in-degree 1 and out-degree 1, yet no arc has its
    reverse: the degree test alone would call it symmetric."""
    n = 12
    return G.from_edges(n, np.arange(n), (np.arange(n) + 1) % n)


def _one_reverse_missing():
    g = _undirected(rmat_graph(scale=6, edge_factor=4, seed=13))
    src, dst = g.edges()
    drop = int(np.flatnonzero(src < dst)[5])
    keep = np.arange(g.m) != drop
    return G.from_edges(g.n, src[keep], dst[keep])


FLAG_CASES = {
    "grid_undirected": (lambda: _undirected(grid_graph(6, 7)), True),
    "hub_isolated": (_hub_graph, True),
    "rmat_undirected": (lambda: rmat_graph(scale=7, edge_factor=5, seed=3,
                                           undirected=True), True),
    "self_loops_only": (lambda: G.from_edges(5, np.arange(5), np.arange(5)),
                        True),
    "edgeless": (lambda: G.from_edges(6, np.zeros(0, np.int64),
                                      np.zeros(0, np.int64)), True),
    "rmat_directed": (lambda: rmat_graph(scale=7, edge_factor=5, seed=3),
                      False),
    "grid_right_down": (lambda: grid_graph(6, 7), False),
    "directed_cycle": (_directed_cycle, False),
    "one_reverse_missing": (_one_reverse_missing, False),
}


@pytest.mark.parametrize("name", sorted(FLAG_CASES))
def test_from_host_sets_symmetric_exactly(name):
    make, want = FLAG_CASES[name]
    g = make()
    if name == "directed_cycle":
        assert (g.in_degree == g.out_degree).all()
    if name == "one_reverse_missing":
        assert g.m % 2 == 1
    assert DeviceGraph.from_host(g).symmetric is want


def _counted_truth(g) -> bool:
    src, dst = g.edges()
    arcs = collections.Counter(zip(src.tolist(), dst.tolist()))
    return all(arcs[(v, u)] == c for (u, v), c in arcs.items())


@pytest.mark.parametrize("seed", range(6))
def test_symmetry_test_agrees_with_counting_arcs(seed):
    """Multigraphs too: a pair's arcs must match in number both ways."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 12))
    src, dst = rng.integers(0, n, 30), rng.integers(0, n, 30)
    src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    if seed % 2:  # break it: one arc more, or one fewer
        drop = int(rng.integers(0, src.size))
        keep = np.arange(src.size) != drop
        src, dst = src[keep], dst[keep]
    if seed % 3 == 2:  # a duplicate arc whose reverse is not duplicated
        src, dst = np.append(src, src[0]), np.append(dst, dst[0])
    g = G.from_edges(n, src, dst)
    got = G.is_symmetric(g.n, *g.edges(), g.out_degree, g.in_degree)
    assert got == _counted_truth(g)
    assert DeviceGraph.from_host(g).symmetric == got


def test_hand_built_device_graph_is_not_symmetric():
    g = _hub_graph()
    dg = DeviceGraph.from_host(g)
    fields = {f.name: getattr(dg, f.name) for f in dataclasses.fields(dg)
              if f.name not in ("symmetric", "fingerprint")}
    assert dg.symmetric and DeviceGraph(**fields).symmetric is False


PULL_GRAPHS = {
    "rmat": lambda: rmat_graph(scale=8, edge_factor=6, seed=11,
                               undirected=True),
    "hub": _hub_graph,
    "grid": lambda: _undirected(grid_graph(9, 11)),
}


@pytest.fixture(scope="module", params=sorted(PULL_GRAPHS))
def pull_graph(request):
    g = PULL_GRAPHS[request.param]()
    dg = DeviceGraph.from_host(g)
    assert dg.symmetric
    return g, dg, build_blocked(g, block_size=64)


def _frontier(n, vertices):
    return jnp.zeros((n,), jnp.float32).at[jnp.asarray(vertices, jnp.int32)
                                           ].set(1.0)


def _frontiers(g):
    hub = int(np.argmax(g.out_degree))
    rng = np.random.default_rng(5)
    return {
        "empty": [],
        "single": [int(np.flatnonzero(g.out_degree)[-1])],
        "all": np.arange(g.n),
        "hub_neighbours": g.colidx[g.rowptr[hub]:g.rowptr[hub + 1]],
        "random": np.flatnonzero(rng.random(g.n) < 0.3),
    }


@pytest.mark.parametrize("frontier", ["empty", "single", "all",
                                      "hub_neighbours", "random"])
def test_row_count_reaches_what_the_max_pull_reaches(pull_graph, frontier):
    g, dg, bg = pull_graph
    f = _frontier(g.n, _frontiers(g)[frontier])
    got = np.asarray(_row_count_reach(dg, f))
    want = np.asarray(tocab.tocab_pull(bg, f, reduce="max")) > 0
    assert got.dtype == np.float32 and set(np.unique(got)) <= {0.0, 1.0}
    assert ((got > 0) == want).all()
    if frontier == "empty":
        assert not got.any()


@pytest.mark.parametrize("blocked", [True, False])
def test_bfs_depths_equal_networkx(pull_graph, blocked):
    g, dg, bg = pull_graph
    nxg = to_networkx(g)
    pulled = 0
    sources = (0, int(np.argmax(g.out_degree)), g.n // 2)
    # α 1e9 pulls every level with a frontier arc
    for source, alpha in zip(sources * 2, [DEFAULT_ALPHA] * 3 + [1e9] * 3):
        depth, levels, n_push, n_pull = bfs(dg, bg if blocked else None,
                                            jnp.int32(source), alpha=alpha)
        ref = nx.single_source_shortest_path_length(nxg, source)
        d = np.asarray(depth)
        assert all(d[v] == lv for v, lv in ref.items())
        assert all(d[v] >= INF_DEPTH for v in set(range(g.n)) - set(ref))
        pulled += int(n_pull)
    assert pulled >= 1  # the row-count pull served some level


def test_bc_equals_networkx_and_the_slab_pull():
    g = rmat_graph(scale=6, edge_factor=4, seed=13, undirected=True)
    dg = DeviceGraph.from_host(g)
    slab = dataclasses.replace(dg, symmetric=False)
    bg = build_blocked(g, block_size=16)
    nxg = to_networkx(g)
    total = np.zeros(g.n, np.float64)
    for s in range(g.n):
        scores, depth, sigma = bc(dg, bg, jnp.int32(s), alpha=4.0)
        want_scores, want_depth, want_sigma = bc(slab, bg, jnp.int32(s),
                                                 alpha=4.0)
        np.testing.assert_array_equal(np.asarray(depth),
                                      np.asarray(want_depth))
        np.testing.assert_array_equal(np.asarray(sigma),
                                      np.asarray(want_sigma))
        np.testing.assert_allclose(np.asarray(scores),
                                   np.asarray(want_scores), rtol=1e-6)
        ref = nx.single_source_shortest_path_length(nxg, s)
        d = np.asarray(depth)
        assert all(d[v] == lv for v, lv in ref.items())
        total += np.asarray(scores, np.float64)
    ref = nx.betweenness_centrality(nxg, normalized=False)
    np.testing.assert_allclose(total, [ref[i] for i in range(g.n)],
                               rtol=1e-3, atol=1e-3)


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _bfs_program(g):
    dg = DeviceGraph.from_host(g)
    bg = build_blocked(g, block_size=64)
    closed = jax.make_jaxpr(_bfs_jit, static_argnums=(3, 4, 5, 6))(
        dg, bg, jnp.int32(0), 0, DEFAULT_ALPHA, "uniform", "slab")
    [inner] = [e for e in closed.jaxpr.eqns
               if e.primitive.name in ("pjit", "jit")]
    program = inner.params["jaxpr"].jaxpr
    [cond] = [e for e in _eqns(program) if e.primitive.name == "cond"]
    # lax.cond(use_pull, pull, push): branch 1 is the pull branch
    return dg, bg, program, cond.params["branches"][1].jaxpr


def test_symmetric_pull_branch_has_no_scatter_and_no_blocked_read():
    g = rmat_graph(scale=8, edge_factor=6, seed=11, undirected=True)
    dg, bg, program, pull = _bfs_program(g)
    prims = [e.primitive.name for e in _eqns(pull)]
    assert not any(p.startswith("scatter") for p in prims)
    scans = [e for e in _eqns(pull) if e.primitive.name == "cumsum"]
    assert scans and all(v.aval.dtype == jnp.int32
                         for e in scans for v in e.outvars)
    # the blocked layout's arrays are the program's inputs after the flat
    # graph's and before the source; no equation of the program reads them
    n_dg = len(jax.tree_util.tree_leaves(dg))
    n_bg = len(jax.tree_util.tree_leaves(bg))
    bg_vars = program.invars[n_dg:n_dg + n_bg]
    used = {id(v) for e in _eqns(program) for v in e.invars}
    assert n_bg and not any(id(v) in used for v in bg_vars)
    assert all(len(v.aval.shape) <= 1 for v in pull.invars)


def test_directed_pull_branch_keeps_the_slab_engine():
    g = rmat_graph(scale=8, edge_factor=6, seed=11)
    dg, _, _, pull = _bfs_program(g)
    assert not dg.symmetric
    scopes = " ".join(str(e.source_info.name_stack) for e in _eqns(pull))
    assert all(s in scopes for s in ("tocab.gather", "tocab.partials",
                                     "tocab.reduce"))
    assert any(e.primitive.name.startswith("scatter") for e in _eqns(pull))


def _pull_traces():
    series = registry.counter("traversal.pull_engine_traces").snapshot()
    return collections.Counter({tuple(sorted(s["labels"].items())):
                                s["value"]
                                for s in series["series"]})


@pytest.mark.parametrize("undirected,engine", [(True, "rows"),
                                               (False, "slab")])
@pytest.mark.parametrize("algo", ["bfs", "bc"])
def test_pull_engine_traces_count_the_engine(undirected, engine, algo):
    g = rmat_graph(scale=6, edge_factor=4, seed=13, undirected=undirected)
    dg, bg = DeviceGraph.from_host(g), build_blocked(g, block_size=16)
    f = _frontier(g.n, [0])
    _, use_pull, budget = _beamer_switch(dg, f, DEFAULT_ALPHA)
    before = _pull_traces()
    jax.make_jaxpr(lambda x: _frontier_reach(
        dg, bg, x, use_pull, budget, algo=algo))(f)
    added = _pull_traces() - before
    assert added == {(("algo", algo), ("engine", engine)): 1}
