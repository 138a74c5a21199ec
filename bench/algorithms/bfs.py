"""GAP's BFS (Beamer, Asanović & Patterson 2015, arXiv:1508.03619): a
direction-optimising search from a source of non-zero degree a solve, the
program's entry, the plain reference, the bytes a search traverses, and
the comparison that decides ``correct``.

The sources are vertices of non-zero degree of the graph as drawn, picked
from ``params["source_seed"]`` alone and taken in turn by the solve's
index, then mapped to the run's ids through ``hg.run_id``: solve ``i``
starts from the same drawn vertex under every ``--seed`` and runs as many
levels, push and pull alike.

Depths are exact, so no lower precision makes a control: the checks are
exact, and faults planted under the timed call fail them
(``bench/tests/test_bench_bfs.py``).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

#: blocked layouts the program's entry reads, besides the flat graph
LAYOUTS = ("pull",)

#: the program's depth of an unreached vertex (``repro.core.INF_DEPTH``)
INF_DEPTH = np.iinfo(np.int32).max // 2


def solve_inputs(hg, params: dict):
    """Solve ``i``'s source: the ``i mod sources``-th of the drawn graph's
    vertices of non-zero degree, permuted by the source seed, in run ids."""
    drawn_degree = hg.degree[hg.run_id]
    rng = np.random.default_rng(params["source_seed"])
    picked = rng.permutation(np.flatnonzero(drawn_degree > 0))
    sources = hg.run_id[picked[:params["sources"]]]
    return lambda i: int(sources[i % len(sources)])


def solve(core, dg, layouts: dict, params: dict, source: int):
    """One timed call: the program's public entry with its own defaults
    (Beamer α 15, uniform schedule, slab engine).  Returns ``(depth,
    levels, push levels, pull levels)`` on the device."""
    return core.bfs(dg, layouts["pull"], jnp.int32(source))


def warmup_inputs(dg, layouts: dict):
    """The timed call's graph and layouts with a source of the least
    degree: where that is 0, as in a Kronecker graph, the search stops
    after one level.  Both directions are compiled either way."""
    return dg, layouts, int(jnp.argmin(dg.out_degree))


def steps(out) -> int:
    return int(out[1])


def answer(out) -> np.ndarray:
    """Each vertex's depth, -1 where the program left it unreached."""
    depth = np.asarray(out[0])
    return np.where(depth == INF_DEPTH, -1, depth)


def _search(g, source: int) -> tuple[np.ndarray, int]:
    """Level-by-level BFS over ``g``'s CSR in NumPy: each vertex's depth
    from ``source`` (-1 where unreached) and the levels run, counted as the
    program counts them (the last finds nothing).  A level gathers the
    frontier's CSR rows at once."""
    depth = np.full(g.n, -1, np.int32)
    depth[source], frontier, level = 0, np.array([source], np.int64), 0
    while frontier.size:
        lo = g.rowptr[frontier]
        count = g.rowptr[frontier + 1] - lo
        ends = np.cumsum(count)
        arc = np.repeat(lo - (ends - count), count) + np.arange(ends[-1])
        hit = np.zeros(g.n, bool)
        hit[g.colidx[arc]] = True
        frontier = np.flatnonzero(hit & (depth < 0))
        level += 1
        depth[frontier] = level
    return depth, level


def reference(g, params: dict, at_steps, sources) -> dict:
    """The reference's depths and levels from each kept solve's source."""
    depths, levels = zip(*(_search(g, s) for s in sources))
    return {"depths": list(depths), "levels": list(levels)}


def describe(ref: dict) -> dict:
    return {"reference_levels": ",".join(map(str, ref["levels"])),
            "reference_reached": ",".join(str(int((d >= 0).sum()))
                                          for d in ref["depths"])}


def compare(outs: list, ref: dict, sources) -> dict:
    """``depth_mismatches``: the most vertices whose depth differs from the
    reference's in one kept solve (an answer of the wrong length reads
    inf); ``levels_gap``: the largest distance of a solve's levels from
    the reference's."""
    worst, gap = 0.0, 0
    for (depth, levels), want, want_levels in zip(outs, ref["depths"],
                                                  ref["levels"]):
        worst = max(worst, float(np.sum(depth != want))
                    if depth.shape == want.shape else np.inf)
        gap = max(gap, abs(levels - want_levels))
    return {"depth_mismatches": worst, "levels_gap": gap}


#: the graph whose components ``least_bytes`` last sized, and their sizes
#: by source
_sized = {"graph": None, "by_source": {}}


def least_bytes(g, steps: int, source: int) -> int:
    """Graph500's count of the work a search traverses: 4 B for each arc
    of the source's component (its target's id read once) and 4 B for each
    of the component's vertices (its depth written once).  A bottom-up
    level may read fewer arcs; this is the yardstick whatever implements
    the search.  The component comes from a host search, once per source
    of the graph last asked about."""
    if _sized["graph"] is not g:
        _sized.update(graph=g, by_source={})
    if source not in _sized["by_source"]:
        reached = _search(g, source)[0] >= 0
        _sized["by_source"][source] = 4 * (int(g.degree[reached].sum())
                                          + int(reached.sum()))
    return _sized["by_source"][source]
