"""The PageRank algorithm file: reference, comparison and least bytes."""
import numpy as np
import pytest

from bench import run as bench_run
from bench.graph import HostGraph, make_graph

ALGO = bench_run.load_module(
    bench_run.os.path.join(bench_run.BENCH, "algorithms", "pagerank.py"))
PARAMS = {"tol": 1e-4, "max_iters": 20}


def small_graph(seed=3) -> HostGraph:
    return make_graph({"name": "t", "generator": "kron", "scale": 8,
                       "edge_factor": 16, "a": 0.57, "b": 0.19, "c": 0.19,
                       "permute_ids": True, "undirected": True,
                       "graph_seed": 0}, seed, block=64)


def dense_pagerank(g: HostGraph, steps: int) -> np.ndarray:
    """The same update with a dense matrix, step by step."""
    a = np.zeros((g.n, g.n))
    rows = np.repeat(np.arange(g.n), g.degree)
    a[rows, g.colidx] = 1.0
    deg = a.sum(1)
    rank = np.full(g.n, 1.0 / g.n)
    for _ in range(steps):
        contrib = np.divide(rank, deg, out=np.zeros(g.n), where=deg > 0)
        rank = 0.15 / g.n + 0.85 * (a.T @ contrib + rank[deg == 0].sum() / g.n)
    return rank


def test_reference_matches_a_dense_power_iteration():
    g = small_graph()
    ref = ALGO.reference(g, PARAMS, [3, 25])
    assert 1 < ref["steps"] < PARAMS["max_iters"]
    assert ref["deltas"][ref["steps"] - 1] <= PARAMS["tol"]
    assert ref["deltas"][ref["steps"] - 2] > PARAMS["tol"]
    np.testing.assert_allclose(ref["ranks"][3], dense_pagerank(g, 3),
                               rtol=1e-12, atol=0)
    assert 25 not in ref["ranks"]  # past the iteration cap
    assert ref["ranks"][3].sum() == pytest.approx(1.0)


def test_compare_reads_gaps_and_refuses_bad_answers():
    g = small_graph()
    ref = ALGO.reference(g, PARAMS, [4])
    good = ref["ranks"][4]
    assert ALGO.compare([(good, 4)], ref) == {
        "rank_l1_gap": 0.0, "steps_gap": abs(4 - ref["steps"])}
    bad = good.copy()
    bad[0] += 1e-3
    assert ALGO.compare([(good, 4), (bad, 4)], ref)["rank_l1_gap"] == \
        pytest.approx(1e-3)
    nan = good.copy()
    nan[1] = np.nan
    assert ALGO.compare([(nan, 4)], ref)["rank_l1_gap"] == np.inf
    assert ALGO.compare([(good[:-1], 4)], ref)["rank_l1_gap"] == np.inf
    assert ALGO.compare([(good, 99)], ref)["rank_l1_gap"] == np.inf


def test_least_bytes_counts_one_csr_pull_pass_per_step():
    g = HostGraph(4, np.array([0, 2, 3, 4, 5]),
                  np.array([1, 2, 0, 0, 0], np.int32))
    one = 5 * (4 + 4) + 4 * (4 + 4 + 4)
    assert ALGO.least_bytes(g, 1) == one
    assert ALGO.least_bytes(g, 12) == 12 * one
    big = small_graph()
    assert ALGO.least_bytes(big, 10) == 10 * (8 * big.arcs + 12 * big.n)
