"""PageRank solves: the program's entry, the plain reference, the
lower-precision control, the least bytes of a solve, and the comparison
that decides ``correct``.

The update, as the program documents it (``repro.core.pagerank``) and as
GAP's PageRank iterates it: every vertex starts at ``1/n``; each step sends
``rank/degree`` along every out-arc, spreads the rank of vertices with no
out-arc evenly over all vertices, and sets
``rank' = (1 - d)/n + d * (incoming + dangling/n)`` with ``d = 0.85``.  A
solve stops after the step whose L1 change is at most ``tol``, or after
``max_iters`` steps.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

#: GAP's damping factor, and the program's default
DAMPING = 0.85

#: blocked layouts the program's entry reads, besides the flat graph
LAYOUTS = ("pull",)


def solve(core, dg, layouts: dict, params: dict):
    """One timed call: the program's public entry with its own defaults
    for everything but GAP's tolerance and iteration cap.  Returns
    ``(rank, steps)`` on the device."""
    return core.pagerank(dg, layouts["pull"], tol=params["tol"],
                         max_iters=params["max_iters"])


def warmup_inputs(dg, layouts: dict):
    """Inputs of the same shapes, types and static metadata as the timed
    call's, so they compile the same program, on which a solve stops after
    one step: with every degree zero all rank is dangling and stays
    uniform."""
    return dataclasses.replace(dg, out_degree=dg.out_degree * 0), layouts


def steps(out) -> int:
    return int(out[1])


def answer(out) -> np.ndarray:
    return np.asarray(out[0], np.float64)


def reference(g, params: dict, at_steps) -> dict:
    """Power iteration in float64 over ``g``'s arcs (scipy CSR, no code of
    the program).  Runs to its own convergence and on to the largest of
    ``at_steps``; returns its step count and its rank after each step
    named in ``at_steps``."""
    import scipy.sparse as sp

    n = g.n
    deg = np.diff(g.rowptr)
    a = sp.csr_matrix((np.ones(g.colidx.shape[0]), g.colidx, g.rowptr),
                      shape=(n, n))
    dangling = deg == 0
    safe = np.maximum(deg, 1).astype(np.float64)
    want = {int(s) for s in at_steps if 0 <= int(s) <= params["max_iters"]}
    rank = np.full(n, 1.0 / n)
    snaps = {0: rank} if 0 in want else {}
    converged, deltas, it = None, [], 0
    last = max(want, default=0)
    while it < params["max_iters"] and (converged is None or it < last):
        contrib = np.where(dangling, 0.0, rank / safe)
        new = (1 - DAMPING) / n + DAMPING * (
            a.T @ contrib + rank[dangling].sum() / n)
        deltas.append(float(np.abs(new - rank).sum()))
        rank, it = new, it + 1
        if it in want:
            snaps[it] = rank
        if converged is None and deltas[-1] <= params["tol"]:
            converged = it
    return {"steps": converged if converged is not None else it,
            "ranks": snaps, "deltas": deltas}


def describe(ref: dict) -> dict:
    """The reference's step count and its L1 change at every step, for the
    check log."""
    return {"reference_steps": ref["steps"],
            "reference_deltas": ",".join(f"{d:.6e}" for d in ref["deltas"])}


def compare(outs: list, ref: dict) -> dict:
    """The numbers that decide ``correct``, worst over the solves kept:
    ``rank_l1_gap``, the L1 distance of a solve's rank from the
    reference's after as many steps (ranks sum to 1, so it is a share of
    all rank), and ``steps_gap``, how far its step count is from the
    reference's.  A rank of the wrong length, or not finite, reads inf."""
    gap, steps_gap = 0.0, 0
    for rank, k in outs:
        want = ref["ranks"].get(k)
        one = (float(np.abs(rank - want).sum())
               if want is not None and rank.shape == want.shape else np.inf)
        gap = max(gap, one if np.isfinite(one) else np.inf)
        steps_gap = max(steps_gap, abs(k - ref["steps"]))
    return {"rank_l1_gap": gap, "steps_gap": steps_gap}


def least_bytes(g, steps: int) -> int:
    """Bytes a solve must move at the least: per step one CSR pull pass
    that reads each arc's source index and that source's contribution
    once (4 B + 4 B), and each vertex's rank, degree and new rank once
    (3 x 4 B).  The same for every engine, so removing padded slots or
    fusing phases raises the roofline share without moving the yardstick."""
    return int(steps) * (8 * int(g.colidx.shape[0]) + 12 * int(g.n))


def control_inputs(g):
    """The control's device arrays, made from the host graph alone: each
    arc's source and target, and each vertex's degree."""
    deg = np.diff(g.rowptr)
    rows = np.repeat(np.arange(g.n, dtype=np.int32), deg)
    return (jnp.asarray(rows), jnp.asarray(g.colidx),
            jnp.asarray(deg.astype(np.int32)))


def control(inputs, params: dict):
    """The reference put in the program's place on the device, computed
    in bfloat16 (the precision below the program's float32).  Returns
    ``(rank, steps)`` like :func:`solve`."""
    rows, cols, deg = inputs
    return _control(rows, cols, deg, n=int(deg.shape[0]),
                    tol=float(params["tol"]),
                    max_iters=int(params["max_iters"]))


@partial(jax.jit, static_argnames=("n", "tol", "max_iters"))
def _control(rows, cols, deg, n, tol, max_iters):
    dt = jnp.bfloat16

    def body(state):
        rank, _, it = state
        contrib = jnp.where(deg > 0, rank / jnp.maximum(deg, 1).astype(dt),
                            0).astype(dt)
        incoming = jax.ops.segment_sum(contrib[rows], cols, n)
        dangling = jnp.where(deg > 0, 0, rank).astype(dt).sum()
        new = ((1 - DAMPING) / n + DAMPING * (incoming + dangling / n)
               ).astype(dt)
        delta = jnp.abs(new.astype(jnp.float32) - rank.astype(jnp.float32))
        return new, delta.sum(), it + 1

    rank, _, it = jax.lax.while_loop(
        lambda s: (s[1] > tol) & (s[2] < max_iters), body,
        (jnp.full((n,), 1.0 / n, dt), jnp.float32(jnp.inf), 0))
    return rank.astype(jnp.float32), it
