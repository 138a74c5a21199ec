"""Level-synchronous reachability from one source a solve: an algorithm
kept for the harness's tests, with a per-solve input (the source), one
exact check of its own name and no control.

The sources are vertices of non-zero degree of the graph as drawn, picked
from ``params["source_seed"]`` alone and taken in turn by the solve's
index, then mapped to the run's ids through ``hg.run_id``: solve ``i``
starts from the same drawn vertex under every ``--seed``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

LAYOUTS = ()


def solve_inputs(hg, params: dict):
    drawn_degree = hg.degree[hg.run_id]
    rng = np.random.default_rng(params["source_seed"])
    picked = rng.permutation(np.flatnonzero(drawn_degree > 0))
    sources = hg.run_id[picked[:params["sources"]]]
    return lambda i: int(sources[i % len(sources)])


def solve(core, dg, layouts: dict, params: dict, source: int):
    """Returns ``(depth, levels)``: each vertex's depth from ``source``
    (-1 where unreached) and the levels run, the last finding nothing."""
    return _reach(dg.src, dg.dst, jnp.int32(source), n=dg.n)


def warmup_inputs(dg, layouts: dict):
    return dg, layouts, 0


def steps(out) -> int:
    return int(out[1])


def answer(out) -> np.ndarray:
    return np.asarray(out[0])


@partial(jax.jit, static_argnames=("n",))
def _reach(src, dst, source, n):
    def body(state):
        depth, level, _ = state
        frontier = depth[src] == level
        hit = jnp.zeros(n, bool).at[dst].max(frontier)
        new = hit & (depth < 0)
        return jnp.where(new, level + 1, depth), level + 1, new.any()

    depth = jnp.full(n, -1, jnp.int32).at[source].set(0)
    depth, level, _ = jax.lax.while_loop(
        lambda s: s[2], body, (depth, jnp.int32(0), jnp.bool_(True)))
    return depth, level


def reference(g, params: dict, at_steps, sources) -> dict:
    """A level-by-level BFS over ``g``'s CSR in NumPy from each kept
    solve's source: its depths, and its levels counted as the program
    counts them (the last finds nothing)."""
    depths = []
    for s in sources:
        depth = np.full(g.n, -1, np.int64)
        depth[s], frontier, level = 0, np.array([s]), 0
        while frontier.size:
            nbrs = np.concatenate([g.colidx[g.rowptr[u]:g.rowptr[u + 1]]
                                   for u in frontier])
            frontier = np.unique(nbrs[depth[nbrs] < 0])
            level += 1
            depth[frontier] = level
        depths.append(depth)
    return {"depths": depths, "levels": [int(d.max()) + 1 for d in depths]}


def describe(ref: dict) -> dict:
    return {"reference_levels": ",".join(map(str, ref["levels"]))}


def compare(outs: list, ref: dict, sources) -> dict:
    """``depth_mismatches``: the most vertices whose depth differs from the
    reference's in one kept solve; an answer of the wrong length reads
    inf."""
    worst = 0.0
    for (depth, _), want in zip(outs, ref["depths"]):
        worst = max(worst, float(np.sum(depth != want))
                    if depth.shape == want.shape else np.inf)
    return {"depth_mismatches": worst}


def least_bytes(g, steps: int, source: int) -> int:
    """Per level one pass that reads each arc's two ends and each vertex's
    depth."""
    return int(steps) * (8 * int(g.colidx.shape[0]) + 4 * int(g.n))
