"""The ``gap-kron21.bfs`` cell at scale 10 on the CPU: its configuration
draws the PageRank cell's graph, faults planted under the timed call fail
``correct``, seeds do the same work for the same solve, the reference
agrees with networkx, ``least_bytes`` counts the source's component alone,
and the traversal readers read their scopes."""
import os
import time
import types

import networkx as nx
import numpy as np
import pytest

from bench import run as bench_run
from bench.graph import HostGraph, make_graph, simple_undirected
from repro.configs.graphcage import GraphCageCfg

CELL = "gap-kron21.bfs"
PEAKS = {"hbm_bytes_per_s": 819e9}
BLOCK = GraphCageCfg().block_size


@pytest.fixture
def cell():
    cell = bench_run.resolve_cell(CELL)
    cell.config = dict(cell.config, scale=10)
    return cell


def run_small(cell, seed=2**31 + 29):
    return bench_run.run_cell(cell, seed=seed, seconds=0.0, trace=False,
                              peaks=PEAKS, t_start=time.perf_counter())


def _depth_altered(real):
    """The answer altered where it is produced: the source's depth moved."""
    def solve(core, dg, layouts, params, source):
        depth, *rest = real(core, dg, layouts, params, source)
        return (depth.at[source].add(1), *rest)
    return solve


def _source_shifted(real):
    """The search run from another vertex than its input."""
    def solve(core, dg, layouts, params, source):
        return real(core, dg, layouts, params, (source + 1) % dg.n)
    return solve


def _level_dropped(real):
    """One level fewer reported than the search ran."""
    def solve(core, dg, layouts, params, source):
        depth, levels, *rest = real(core, dg, layouts, params, source)
        return (depth, levels - 1, *rest)
    return solve


def _half_the_arcs(real):
    """The search run on every other arc of the graph."""
    cache = {}

    def solve(core, dg, layouts, params, source):
        if "g" not in cache:
            src, dst = np.asarray(dg.src), np.asarray(dg.dst)
            g = core.from_edges(dg.n, src[::2], dst[::2])
            cache["g"] = (core.DeviceGraph.from_host(g),
                          {"pull": core.build_blocked(g, block_size=BLOCK)})
        return real(core, *cache["g"], params, source)
    return solve


FAULTS = {"depth_altered": _depth_altered, "source_shifted": _source_shifted,
          "level_dropped": _level_dropped, "half_the_arcs": _half_the_arcs}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_in_the_timed_path_is_not_correct(cell, fault):
    real, calls = cell.algorithm.solve, []

    def broken(*args):
        calls.append(1)
        return FAULTS[fault](real)(*args)

    cell.algorithm.solve = broken
    result = run_small(cell)
    assert len(calls) >= 2, "the fault was not on the timed path"
    assert not result["correct"], (fault, result["checks"])


def test_seeds_do_the_same_work_for_the_same_solve(cell):
    """Under two seeds solve ``i`` starts at different ids and runs as many
    levels, push and pull alike, with as many vertices at each depth."""
    from repro import core

    algo, params = cell.algorithm, cell.traffic["params"]
    runs = []
    for seed in (7, 2**33 + 5):
        hg = make_graph(cell.config, seed, BLOCK)
        g = core.Graph(n=hg.n, rowptr=hg.rowptr, colidx=hg.colidx)
        dg = core.DeviceGraph.from_host(g)
        layouts = {"pull": core.build_blocked(g, block_size=BLOCK)}
        draw = algo.solve_inputs(hg, params)
        outs = [algo.solve(core, dg, layouts, params, draw(i))
                for i in range(4)]
        runs.append(([draw(i) for i in range(4)],
                     [tuple(int(x) for x in o[1:]) for o in outs],
                     [np.bincount(algo.answer(o) + 1) for o in outs]))
    (ids_a, work_a, hist_a), (ids_b, work_b, hist_b) = runs
    assert ids_a != ids_b
    assert work_a == work_b
    assert all(push >= 1 for _, push, _ in work_a)
    for x, y in zip(hist_a, hist_b):
        np.testing.assert_array_equal(x, y)


def test_config_draws_the_pagerank_graph(cell):
    """The BFS deployment runs the graph of ``gap-kron21``: every key the
    generator reads is the same, and so is the drawn graph."""
    pagerank = bench_run.resolve_cell("gap-kron21.pagerank")
    graph_keys = ("generator", "scale", "edge_factor", "a", "b", "c",
                  "permute_ids", "undirected", "graph_seed")
    bfs_config = bench_run.resolve_cell(CELL).config
    assert {k: bfs_config[k] for k in graph_keys} == {
        k: pagerank.config[k] for k in graph_keys}
    small = dict(pagerank.config, scale=10)
    a, b = make_graph(cell.config, 3, BLOCK), make_graph(small, 3, BLOCK)
    np.testing.assert_array_equal(a.rowptr, b.rowptr)
    np.testing.assert_array_equal(a.colidx, b.colidx)


def test_reference_agrees_with_networkx(cell):
    from repro import core

    algo = cell.algorithm
    assert algo.INF_DEPTH == int(core.INF_DEPTH)
    hg = make_graph(cell.config, 3, BLOCK)
    nxg = nx.Graph()
    nxg.add_nodes_from(range(hg.n))
    nxg.add_edges_from(zip(np.repeat(np.arange(hg.n), hg.degree).tolist(),
                           hg.colidx.tolist()))
    draw = algo.solve_inputs(hg, cell.traffic["params"])
    sources = [draw(i) for i in range(3)] + [int(np.argmin(hg.degree))]
    ref = algo.reference(hg, cell.traffic["params"], [], sources)
    for s, depth, levels in zip(sources, ref["depths"], ref["levels"]):
        want = np.full(hg.n, -1)
        for v, d in nx.single_source_shortest_path_length(nxg, s).items():
            want[v] = d
        np.testing.assert_array_equal(depth, want)
        assert levels == want.max() + 1


def test_least_bytes_counts_the_source_component(cell):
    algo = cell.algorithm
    # a path 0-1-2, a triangle 3-4-5 and an isolated vertex 6
    g = simple_undirected(7, np.array([0, 1, 3, 4, 5]),
                          np.array([1, 2, 4, 5, 3]))
    assert algo.least_bytes(g, 3, 0) == 4 * (4 + 3)
    assert algo.least_bytes(g, 2, 4) == 4 * (6 + 3)
    assert algo.least_bytes(g, 1, 6) == 4 * (0 + 1)
    # another graph with the same source is sized anew
    h = HostGraph(7, np.zeros(8, np.int64), np.zeros(0, np.int32))
    assert algo.least_bytes(h, 1, 0) == 4
    hg = make_graph(cell.config, 3, BLOCK)
    s = algo.solve_inputs(hg, cell.traffic["params"])(0)
    reached = algo.reference(hg, {}, [], [s])["depths"][0] >= 0
    assert 0 < reached.sum() < hg.n
    assert algo.least_bytes(hg, 5, s) == 4 * (
        int(hg.degree[reached].sum()) + int(reached.sum()))


SCOPES = {"traversal.push": 1.5, "traversal.pull": 4.5, "tocab.gather": 2.0,
          "bfs.level": 6.25}


def _reader(name):
    return bench_run.load_module(
        os.path.join(bench_run.BENCH, "metrics", name + ".py"))


@pytest.mark.parametrize("name,scope", [("bfs_push_s", "traversal.push"),
                                        ("bfs_pull_s", "traversal.pull")])
def test_direction_reader_reads_its_scope_per_bfs(name, scope):
    reader = _reader(name)
    run = types.SimpleNamespace(scopes=SCOPES, solves=3)
    assert reader.read(run) == pytest.approx(SCOPES[scope] / 3)
    assert reader.read(types.SimpleNamespace(scopes=None, solves=3)) is None
    # a program without the traversal scopes: nothing to read
    other = {"tocab.gather": 2.0, "pagerank.step": 2.5}
    assert reader.read(types.SimpleNamespace(scopes=other, solves=3)) is None


def test_roofline_reader():
    reader = _reader("bfs_roofline")
    trace = types.SimpleNamespace(busy_s=2.0)
    run = types.SimpleNamespace(trace=trace, least_bytes=819e6, peaks=PEAKS)
    assert reader.read(run) == pytest.approx(100 * 1e-3 / 2.0)
    assert reader.read(types.SimpleNamespace(trace=None)) is None
