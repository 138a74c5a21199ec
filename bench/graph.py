"""A configuration's graph, made from its generator and the run's seed.

The generator draws the graph from the configuration's own ``graph_seed``,
so every run works on the same graph.  The run's ``--seed`` then relabels
its vertices at random within each block of ``block`` consecutive ids: the
blocks of the program's blocked layout when ``block`` is its block size (or
divides it).  The relabelled graph is isomorphic to the drawn one, and
every block holds the same vertices' arcs and as many distinct
neighbours, so every seed gives the program layouts of the same shapes and
the same amount of work (and, compiled once, the same programs), while the
ids, the adjacency and the answers differ from seed to seed.

The relabelling is kept on the graph (``HostGraph.run_id``: drawn id ->
run id).  An algorithm whose solves take an input of their own, such as a
BFS source, chooses it in the drawn graph and maps it with ``run_id``:
solve ``i`` then starts from the same drawn vertex under every seed and
does isomorphic work, where a vertex id chosen in the relabelled graph
would be another vertex, with another depth profile, for every seed.
"""
from __future__ import annotations

import dataclasses
import importlib

import numpy as np


@dataclasses.dataclass(frozen=True)
class HostGraph:
    """Undirected simple graph as CSR: row ``u`` lists every neighbour of
    ``u`` once, so each undirected edge is two arcs."""

    n: int
    rowptr: np.ndarray  # int64[n + 1]
    colidx: np.ndarray  # int32[arcs]
    #: drawn id -> id in this graph, where the graph is a relabelling
    run_id: np.ndarray | None = None  # int32[n]

    @property
    def arcs(self) -> int:
        return int(self.colidx.shape[0])

    @property
    def degree(self) -> np.ndarray:
        return np.diff(self.rowptr)


def seed_words(seed: int) -> list[int]:
    """A run's seed as non-negative 32-bit words for NumPy's SeedSequence
    (seeds may exceed 32 bits or be negative)."""
    seed %= 1 << 64
    return [seed & 0xFFFFFFFF, seed >> 32]


def simple_undirected(n: int, src: np.ndarray, dst: np.ndarray) -> HostGraph:
    """Both directions of every pair, self-loops and duplicates removed,
    as a CSR with sorted rows (one sort of int64 keys)."""
    keep = src != dst
    src, dst = src[keep].astype(np.int64), dst[keep].astype(np.int64)
    key = np.concatenate([src * n + dst, dst * n + src])
    del src, dst, keep
    key.sort()
    first = np.empty(key.shape[0], bool)
    first[:1] = True
    np.not_equal(key[1:], key[:-1], out=first[1:])
    key = key[first]
    rowptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(key // n, minlength=n), out=rowptr[1:])
    return HostGraph(n, rowptr, (key % n).astype(np.int32))


def relabel_within_blocks(g: HostGraph, seed: int, block: int) -> HostGraph:
    """The graph under a random relabelling that keeps every vertex in its
    block of ``block`` ids: row ``w`` of the result is row ``old[w]`` of
    ``g`` with every neighbour relabelled, and its ``run_id`` maps each id
    of ``g`` to its id in the result."""
    rng = np.random.default_rng(seed_words(seed))
    ids = np.arange(g.n, dtype=np.int64)
    old = np.lexsort((rng.random(g.n), ids // block))  # new id -> old id
    new_id = np.empty(g.n, np.int32)
    new_id[old] = ids
    deg = g.degree[old]
    rowptr = np.zeros(g.n + 1, np.int64)
    np.cumsum(deg, out=rowptr[1:])
    take = np.repeat(g.rowptr[:-1][old] - rowptr[:-1], deg)
    take += np.arange(g.arcs, dtype=np.int64)
    return HostGraph(g.n, rowptr, new_id[g.colidx[take]], run_id=new_id)


def make_graph(config: dict, seed: int, block: int) -> HostGraph:
    """The configuration's graph, relabelled from the run's ``seed``
    within blocks of ``block`` ids."""
    if not config.get("undirected"):
        raise ValueError(f"{config['name']}: only undirected graphs are "
                         "built (GAP's kron and urand are undirected)")
    gen = importlib.import_module(f"bench.generators.{config['generator']}")
    src, dst = gen.draw(config, int(config["graph_seed"]))
    base = simple_undirected(1 << int(config["scale"]), src, dst)
    del src, dst
    return relabel_within_blocks(base, seed, block)
