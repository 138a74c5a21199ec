"""Frontier-bounded push: a push level expands only the frontier's CSR rows
into a static budget of ⌊m/α⌋ arc slots.  It must reach exactly what the
flat O(m) ``baseline_push`` reaches, Beamer's integer test must guarantee
that every push level fits the budget, and no m-sized array may be left on
the push path.
"""
import jax
import jax.numpy as jnp
import networkx as nx
import numpy as np
import pytest

from repro.core import (
    DeviceGraph, INF_DEPTH, baseline_push, bfs, build_blocked, rmat_graph,
    to_networkx,
)
from repro.core import graph as G
from repro.core.traversal import (
    _SCAN_ROW, DEFAULT_ALPHA, _beamer_switch, _bfs_jit, _push_reach, _scan,
)

HUB_DEGREE = 40


def _hub_graph():
    """Vertex 0 is a hub of HUB_DEGREE arcs and m = 15 · HUB_DEGREE, so at
    the default α the hub alone fills the push budget exactly; vertices
    from 151 on have no out-arcs."""
    rng = np.random.default_rng(7)
    rest = 15 * HUB_DEGREE - HUB_DEGREE
    src = np.concatenate([np.zeros(HUB_DEGREE, np.int64),
                          rng.integers(1, 151, rest)])
    dst = np.concatenate([rng.choice(np.arange(1, 256), HUB_DEGREE, False),
                          rng.integers(0, 256, rest)])
    return G.from_edges(256, src, dst)


GRAPHS = {
    "rmat": lambda: rmat_graph(scale=8, edge_factor=6, seed=11),
    "hub": _hub_graph,
}


@pytest.fixture(scope="module", params=sorted(GRAPHS))
def graph(request):
    g = GRAPHS[request.param]()
    return g, DeviceGraph.from_host(g)


def _frontier(n, vertices):
    return jnp.zeros((n,), jnp.float32).at[jnp.asarray(vertices, jnp.int32)
                                           ].set(1.0)


def _cases(g):
    """(name, frontier vertices, budget): the empty frontier, a frontier of
    vertices without out-arcs, the top hub in a budget of exactly its
    degree, and random frontiers whose out-arcs fit the α-derived budget."""
    deg = g.out_degree
    budget = max(1, int(g.m // DEFAULT_ALPHA))
    hub = int(np.argmax(deg))
    cases = [("empty", [], budget),
             ("zero_degree", np.flatnonzero(deg == 0)[:17], budget),
             ("hub", [hub], int(deg[hub]))]
    rng = np.random.default_rng(3)
    while len(cases) < 8:
        pick = rng.choice(g.n, rng.integers(1, 12), replace=False)
        if 0 < deg[pick].sum() <= budget:
            cases.append((f"random{len(cases)}", pick, budget))
    return cases


@pytest.mark.parametrize("case", range(8))
def test_push_reaches_what_the_flat_pass_reaches(graph, case):
    g, dg = graph
    name, vertices, budget = _cases(g)[case]
    f = _frontier(g.n, vertices)
    m_f = int(g.out_degree[np.asarray(vertices, np.int64)].sum())
    assert m_f <= budget
    if name == "zero_degree":
        assert len(vertices) and m_f == 0
    if name == "hub":
        assert m_f == budget
    got = np.asarray(_push_reach(dg, f, budget)) > 0
    want = np.asarray(baseline_push(dg, f, "max")) > 0
    assert (got == want).all()


def test_hub_fills_the_alpha_budget_and_still_pushes():
    g = _hub_graph()
    dg = DeviceGraph.from_host(g)
    m_f, use_pull, budget = _beamer_switch(dg, _frontier(g.n, [0]),
                                           DEFAULT_ALPHA)
    assert budget == g.m // 15 == HUB_DEGREE == int(m_f)
    assert not bool(use_pull)
    other = int(np.flatnonzero(g.out_degree[1:])[0]) + 1
    hub_and_one = _frontier(g.n, [0, other])
    assert bool(_beamer_switch(dg, hub_and_one, DEFAULT_ALPHA)[1])


def _level_frontiers(g, source):
    """Each level's frontier of a host BFS from ``source`` (the last empty
    one excluded), as the program's loop sees them."""
    depth = np.full(g.n, -1)
    depth[source], frontier, out = 0, [source], []
    while len(frontier):
        out.append(np.asarray(frontier))
        nxt = set()
        for v in frontier:
            nxt.update(g.colidx[g.rowptr[v]:g.rowptr[v + 1]].tolist())
        frontier = [v for v in sorted(nxt) if depth[v] < 0]
        depth[frontier] = len(out)
    return out


BFS_GRAPHS = {
    "rmat_s8": lambda: rmat_graph(scale=8, edge_factor=6, seed=11,
                                  weights=True),
    "rmat_s6": lambda: rmat_graph(scale=6, edge_factor=4, seed=13),
    "hub": _hub_graph,
}


@pytest.mark.parametrize("name", sorted(BFS_GRAPHS))
@pytest.mark.parametrize("alpha", [DEFAULT_ALPHA, 4.0])
def test_integer_switch_picks_the_float_rule_levels(name, alpha):
    g = BFS_GRAPHS[name]()
    dg = DeviceGraph.from_host(g)
    source = int(np.argmax(g.out_degree))
    want = []
    for frontier in _level_frontiers(g, source):
        m_f, use_pull, budget = _beamer_switch(dg, _frontier(g.n, frontier),
                                               alpha)
        assert int(m_f) == int(g.out_degree[frontier].sum())
        want.append(int(m_f) > g.m / alpha)
        assert bool(use_pull) == want[-1]
        assert want[-1] or int(m_f) <= budget
    _, levels, n_push, n_pull = bfs(dg, build_blocked(g, block_size=64),
                                    jnp.int32(source), alpha=alpha)
    assert int(levels) == len(want)
    assert (int(n_push), int(n_pull)) == (want.count(False), want.count(True))


@pytest.mark.parametrize("alpha,budget", [(1e-9, "m"), (1e9, 1)])
def test_extreme_alpha_keeps_networkx_depths(alpha, budget):
    g = rmat_graph(scale=8, edge_factor=6, seed=11, weights=True)
    dg = DeviceGraph.from_host(g)
    f = _frontier(g.n, [5])
    assert _beamer_switch(dg, f, alpha)[2] == (g.m if budget == "m" else 1)
    depth, levels, n_push, n_pull = bfs(dg, build_blocked(g, block_size=64),
                                        jnp.int32(5), alpha=alpha)
    ref = nx.single_source_shortest_path_length(to_networkx(g), 5)
    d = np.asarray(depth)
    assert all(d[v] == lv for v, lv in ref.items())
    assert all(d[v] >= INF_DEPTH for v in set(range(g.n)) - set(ref))
    if budget == "m":
        assert int(n_pull) == 0 and int(n_push) == int(levels)


def test_edgeless_graph_pushes_in_an_empty_budget():
    g = G.from_edges(8, np.zeros(0, np.int64), np.zeros(0, np.int64))
    dg = DeviceGraph.from_host(g)
    assert _beamer_switch(dg, _frontier(g.n, [3]), DEFAULT_ALPHA)[2] == 0
    depth, levels, n_push, n_pull = bfs(dg, None, jnp.int32(3))
    assert (int(levels), int(n_push), int(n_pull)) == (1, 1, 0)
    want = np.where(np.arange(g.n) == 3, 0, INF_DEPTH)
    assert (np.asarray(depth) == want).all()


@pytest.mark.parametrize("size", [0, 1, 5, _SCAN_ROW, 3 * _SCAN_ROW + 7])
def test_two_level_scan_matches_numpy(size):
    x = np.random.default_rng(size).integers(0, 50, size).astype(np.int32)
    got_sum = _scan(jnp.asarray(x), jax.lax.cumsum, jnp.add)
    got_max = _scan(jnp.asarray(x), jax.lax.cummax, jnp.maximum)
    np.testing.assert_array_equal(got_sum, np.cumsum(x))
    np.testing.assert_array_equal(got_max, np.maximum.accumulate(x))


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _size(var):
    return int(np.prod(getattr(var.aval, "shape", ())))


def test_push_branch_holds_no_edge_sized_array():
    g = rmat_graph(scale=8, edge_factor=6, seed=11)
    dg = DeviceGraph.from_host(g)
    bg = build_blocked(g, block_size=64)
    budget = _beamer_switch(dg, _frontier(g.n, [0]), DEFAULT_ALPHA)[2]
    assert g.m not in (g.n, g.n + 1, budget)
    closed = jax.make_jaxpr(_bfs_jit, static_argnums=(3, 4, 5, 6))(
        dg, bg, jnp.int32(0), 0, DEFAULT_ALPHA, "uniform", "slab")
    [cond] = [e for e in _eqns(closed.jaxpr) if e.primitive.name == "cond"]
    # lax.cond(use_pull, pull, push): branch 0 is the push branch
    push = cond.params["branches"][0].jaxpr
    prims = {e.primitive.name for e in _eqns(push)}
    assert "cummax" in prims
    assert not any(_size(v) == g.m for e in _eqns(push) for v in e.outvars)
    used = {id(v) for e in _eqns(push) for v in e.invars}
    edge_inputs = [v for v in push.invars if _size(v) == g.m and id(v) in used]
    assert len(edge_inputs) == 1
    [gather] = [e for e in push.eqns
                if any(v is edge_inputs[0] for v in e.invars)]
    assert gather.primitive.name in ("gather", "pjit", "jit")
