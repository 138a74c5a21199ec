"""Traversal-based algorithms: BFS, Betweenness Centrality, SSSP (paper §3.3).

Partial-active algorithms keep a changing frontier.  Per the paper, the
GPU/TPU-friendly representation is the **status array** (topology-driven):
dynamic frontier queues are not expressible with static shapes anyway, and the
paper argues status arrays let the per-subgraph ``next`` frontier ride the
same partial-slab + reduction machinery as ``partial_sums``.

Direction optimization (Beamer): iterations with a sparse frontier run in
**push**; dense-frontier iterations run in **pull** — and only the pull
iterations go through TOCAB (the working set only exceeds fast memory when
the frontier is large).  The hybrid switch uses the classic α heuristic on
the frontier's out-edge count, which also bounds a push level's work: a
push level expands only the frontier's CSR rows, into ⌊m/α⌋ arc slots.
On a symmetric graph (``DeviceGraph.symmetric``) a pull level needs no
TOCAB: each CSR row lists its vertex's in-neighbours, so the level counts
the frontier vertices in every row with one gather per arc and a prefix
sum.
"""
from __future__ import annotations

import math
import os
from fractions import Fraction
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.obs.metrics import registry as _obs
from .graph import DeviceGraph
from .partition import BlockedGraph
from . import tocab

__all__ = ["bfs", "bc", "sssp", "connected_components", "INF_DEPTH",
           "DEFAULT_ALPHA"]

INF_DEPTH = jnp.iinfo(jnp.int32).max // 2

#: the paper's Beamer direction-switch threshold (m_frontier > m/α → pull)
DEFAULT_ALPHA = 15.0


def _callbacks_enabled() -> bool:
    """Per-iteration telemetry uses ``jax.debug.callback`` (a host call per
    loop iteration).  On by default — the CPU-scale graphs don't notice —
    and trace-time gated off with REPRO_OBS_DEVICE_CALLBACKS=0 for
    device-bound runs."""
    return os.environ.get("REPRO_OBS_DEVICE_CALLBACKS", "1") != "0"


def _record_frontier(algo, budget, frontier_size, frontier_edges, use_pull):
    direction = "pull" if bool(use_pull) else "push"
    if direction == "push":
        _obs.histogram(
            "traversal.push_budget_fill",
            "push level's frontier out-edges over its arc slots",
        ).observe(float(frontier_edges) / budget if budget else 0.0, algo=algo)
    _obs.histogram(
        "traversal.frontier_size", "active vertices per iteration"
    ).observe(float(frontier_size), algo=algo)
    _obs.histogram(
        "traversal.frontier_edges", "frontier out-edge volume (Beamer m_f)"
    ).observe(float(frontier_edges), algo=algo)
    _obs.counter(
        "traversal.iterations", "iterations by Beamer direction decision"
    ).inc(algo=algo, direction=direction)


def _record_iteration(algo):
    _obs.counter("traversal.iterations", "").inc(algo=algo, direction="pull")


def _emit_frontier(algo: str, frontier, m_frontier, use_pull, budget: int):
    """Trace-time-gated per-iteration telemetry (runtime values arrive on
    the host via debug.callback)."""
    if _callbacks_enabled():
        jax.debug.callback(partial(_record_frontier, algo, budget),
                           frontier.sum(), m_frontier, use_pull)


def _beamer_switch(dg: DeviceGraph, frontier: jnp.ndarray, alpha: float):
    """Beamer's direction test of one level, shared by ``bfs`` and ``bc``.

    Returns the frontier's out-edge count m_f (int32, exact), whether the
    level pulls (m_f > m/α), and the static push budget
    ``min(m, max(1, ⌊m/α⌋))``.  For an integer m_f, m_f > m/α ⇔
    m_f > ⌊m/α⌋, with ⌊m/α⌋ taken exactly from the float α, so every level
    that pushes has at most ``budget`` frontier arcs."""
    threshold = min(dg.m, math.floor(Fraction(dg.m) / Fraction(alpha)))
    m_frontier = jnp.where(frontier > 0, dg.out_degree, 0).sum()
    return m_frontier, m_frontier > threshold, min(dg.m, max(1, threshold))


#: Row length of :func:`_scan`.  The v5e compiler's time grows with a scan's
#: length (~9 s for one over 2M elements, against under 1 s for this
#: two-level form), and the BFS program is compiled in every process: the key
#: of JAX's persistent cache holds the address of its per-level callback.
_SCAN_ROW = 2048


def _scan(x: jnp.ndarray, scan, combine) -> jnp.ndarray:
    """``scan`` (``lax.cumsum`` or ``lax.cummax``) of a 1-D array of
    non-negative values: rows of ``_SCAN_ROW`` scanned alone, each then
    joined by ``combine`` with the scan of the rows before it (0 for the
    first row, padding with 0s)."""
    size = x.shape[0]
    rows = -(-size // _SCAN_ROW)
    y = scan(jnp.pad(x, (0, rows * _SCAN_ROW - size)).reshape(rows, _SCAN_ROW),
             axis=1)
    before = jnp.pad(scan(y[:, -1], axis=0)[:-1], (1, 0))
    return combine(y, before[:, None]).reshape(-1)[:size]


def _push_reach(dg: DeviceGraph, frontier: jnp.ndarray, budget: int):
    """reached[v] = 1 where an arc leaves a frontier vertex for v, else 0.

    The frontier's CSR rows are laid end to end in ``budget`` arc slots,
    which must hold them all (``_beamer_switch`` sends no larger frontier
    here): a level costs O(budget + n), not O(m)."""
    deg = jnp.where(frontier > 0, dg.out_degree, 0)
    end = _scan(deg, jax.lax.cumsum, jnp.add)
    start = end - deg
    # Row v's arc k sits in slot start[v] + k, so it is arc rowptr[v] -
    # start[v] + slot.  That offset counts the arcs of the non-frontier rows
    # before v: it never decreases with v, nor do the starts, so a running
    # max over the slots of the offsets scattered to their rows' starts
    # gives every slot its row's offset (a row with no arcs shares its
    # start with the next row, whose offset is at least as large).
    slot = jnp.arange(budget, dtype=jnp.int32)
    offset = _scan(jnp.zeros((budget,), jnp.int32).at[start].max(
        dg.rowptr[:-1] - start, mode="drop", indices_are_sorted=True),
        jax.lax.cummax, jnp.maximum)
    arc = jnp.where(slot < end[-1], offset + slot, dg.m)
    nbr = jnp.take(dg.dst, arc, mode="fill", fill_value=dg.n)
    return jnp.zeros((dg.n,), jnp.float32).at[nbr].set(1.0, mode="drop")


def _row_count_reach(dg: DeviceGraph, frontier: jnp.ndarray):
    """reached[v] = 1 where row v of the CSR holds a frontier vertex, else
    0: the pull of a level on a symmetric graph, whose row v lists v's
    in-neighbours.  One gather per arc marks the arcs that end on the
    frontier, an int32 prefix sum counts them (a float32 one is inexact
    past 2^24 arcs), and row v's count is the prefix at its end less the
    prefix at its start: no scatter and no padded slot."""
    if dg.m == 0:
        return jnp.zeros((dg.n,), jnp.float32)
    get = dict(mode="promise_in_bounds", wrap_negative_indices=False)
    hit = frontier.astype(jnp.int32).at[dg.dst].get(**get)
    pre = _scan(hit, jax.lax.cumsum, jnp.add)
    # pre[rowptr[v] - 1] counts the hits before row v (0 for an empty prefix)
    last = jnp.maximum(dg.rowptr - 1, 0)
    before = jnp.where(dg.rowptr > 0,
                       pre.at[last].get(indices_are_sorted=True, **get), 0)
    return (before[1:] > before[:-1]).astype(jnp.float32)


def _record_pull_engine(algo: str, engine: str):
    """Trace-time telemetry, as ``tocab.engine_traces``: one count each
    time a traversal's pull branch is traced, by the engine it lowers."""
    _obs.counter(
        "traversal.pull_engine_traces", "pull-branch traces by engine"
    ).inc(engine=engine, algo=algo)


def _frontier_reach(
    dg: DeviceGraph,
    bg_pull: Optional[BlockedGraph],
    frontier_f32: jnp.ndarray,
    use_pull: jnp.ndarray,
    budget: int,
    schedule: str = "uniform",
    impl: str = "slab",
    algo: str = "bfs",
):
    """reached[dst] > 0 iff an arc (src, dst) has frontier[src] > 0.

    ``use_pull`` selects the pull (dense phase) vs the frontier-bounded
    push of :func:`_push_reach` in ``budget`` arc slots (sparse phase).
    Both are lowered; `lax.cond` picks at runtime.  The branches are the
    named scopes ``traversal.pull`` and ``traversal.push``.  The pull is
    :func:`_row_count_reach` on a symmetric graph, else TOCAB's max-pull
    (the flat ``baseline_pull`` without a blocked layout)."""

    def pull_branch(f):
        with jax.named_scope("traversal.pull"):
            if dg.symmetric:
                _record_pull_engine(algo, "rows")
                return _row_count_reach(dg, f)
            if bg_pull is None:
                _record_pull_engine(algo, "baseline")
                return tocab.baseline_pull(dg, f, reduce="max")
            _record_pull_engine(algo, impl)
            return tocab.tocab_pull(bg_pull, f, reduce="max",
                                    schedule=schedule, impl=impl)

    def push_branch(f):
        with jax.named_scope("traversal.push"):
            return _push_reach(dg, f, budget)

    return jax.lax.cond(use_pull, pull_branch, push_branch, frontier_f32)


def bfs(
    dg: DeviceGraph,
    bg_pull: Optional[BlockedGraph],
    source: jnp.ndarray,
    max_iters: int = 0,
    alpha: float = DEFAULT_ALPHA,
    schedule: str = "uniform",
    impl: str = "slab",
):
    """Direction-optimizing BFS.  ``dg``/``bg_pull`` are over Gᵀ edges
    oriented (src→dst) = (in-neighbour → vertex), i.e. the pull layout.

    ``schedule`` and ``impl`` are the pull levels' engine, as in
    :func:`~repro.core.tocab.tocab_pull`; ``alpha`` is Beamer's switch.
    On a symmetric ``dg`` the pull levels count rows instead
    (:func:`_row_count_reach`) and read neither ``bg_pull`` nor them.

    The call is an ``obs`` span ``bfs`` (dispatch; the search runs on after
    it returns), and each level of the compiled loop is the named scope
    ``bfs.level``.

    Returns (depth int32[n], levels int32, push_iters, pull_iters)."""
    with obs.span("bfs"):
        return _bfs_jit(dg, bg_pull, source, max_iters, float(alpha),
                        schedule, impl)


@partial(jax.jit, static_argnames=("max_iters", "alpha", "schedule", "impl"))
def _bfs_jit(
    dg: DeviceGraph,
    bg_pull: Optional[BlockedGraph],
    source: jnp.ndarray,
    max_iters: int,
    alpha: float,
    schedule: str,
    impl: str = "slab",
):
    n = dg.n
    max_iters = max_iters or n
    depth0 = jnp.full((n,), INF_DEPTH, jnp.int32).at[source].set(0)
    frontier0 = jnp.zeros((n,), jnp.float32).at[source].set(1.0)

    def cond(state):
        _, frontier, level, pp = state
        return (frontier.sum() > 0) & (level < max_iters)

    def body(state):
        depth, frontier, level, (n_push, n_pull) = state
        with jax.named_scope("bfs.level"):
            m_frontier, use_pull, budget = _beamer_switch(dg, frontier, alpha)
            _emit_frontier("bfs", frontier, m_frontier, use_pull, budget)
            reached = _frontier_reach(dg, bg_pull, frontier, use_pull, budget,
                                      schedule, impl)
            new_frontier = (reached > 0) & (depth >= INF_DEPTH)
            depth = jnp.where(new_frontier, level + 1, depth)
            counts = (
                n_push + jnp.where(use_pull, 0, 1),
                n_pull + jnp.where(use_pull, 1, 0),
            )
            return depth, new_frontier.astype(jnp.float32), level + 1, counts

    depth, _, levels, (n_push, n_pull) = jax.lax.while_loop(
        cond, body, (depth0, frontier0, jnp.int32(0), (jnp.int32(0), jnp.int32(0)))
    )
    return depth, levels, n_push, n_pull


def bc(
    dg: DeviceGraph,
    bg_pull: Optional[BlockedGraph],
    source: jnp.ndarray,
    max_levels: int = 64,
    alpha: float = DEFAULT_ALPHA,
    schedule: str = "uniform",
    impl: str = "slab",
):
    """Brandes betweenness centrality from one source (paper Alg. 3 + the
    standard dependency back-propagation).  Forward phase = BFS computing
    depth δ and shortest-path counts σ; backward phase accumulates
    dependencies level by level.  ``schedule`` / ``alpha`` / ``impl`` as in
    :func:`bfs`.

    Returns (bc_scores f32[n], depth, sigma)."""
    return _bc_jit(dg, bg_pull, source, max_levels, float(alpha), schedule,
                   impl)


@partial(jax.jit, static_argnames=("max_levels", "alpha", "schedule",
                                   "impl"))
def _bc_jit(
    dg: DeviceGraph,
    bg_pull: Optional[BlockedGraph],
    source: jnp.ndarray,
    max_levels: int,
    alpha: float,
    schedule: str,
    impl: str = "slab",
):
    n = dg.n
    depth0 = jnp.full((n,), INF_DEPTH, jnp.int32).at[source].set(0)
    sigma0 = jnp.zeros((n,), jnp.float32).at[source].set(1.0)
    frontier0 = jnp.zeros((n,), jnp.float32).at[source].set(1.0)

    # ---------------- forward: depth + sigma ---------------- #
    def fwd_cond(state):
        _, _, frontier, level = state
        return (frontier.sum() > 0) & (level < max_levels)

    def fwd_body(state):
        depth, sigma, frontier, level = state
        m_frontier, use_pull, budget = _beamer_switch(dg, frontier, alpha)
        _emit_frontier("bc", frontier, m_frontier, use_pull, budget)
        reached = _frontier_reach(dg, bg_pull, frontier, use_pull, budget,
                                  schedule, impl, algo="bc")
        new_frontier = (reached > 0) & (depth >= INF_DEPTH)
        depth = jnp.where(new_frontier, level + 1, depth)
        # σ[dst] += Σ σ[src] over tree edges (src on frontier level).
        path_msgs = jnp.where(frontier > 0, sigma, 0.0)
        sig_in = (
            tocab.tocab_pull(bg_pull, path_msgs, reduce="sum",
                             schedule=schedule, impl=impl)
            if bg_pull is not None
            else tocab.baseline_pull(dg, path_msgs, reduce="sum")
        )
        sigma = jnp.where(new_frontier, sig_in, sigma)
        return depth, sigma, new_frontier.astype(jnp.float32), level + 1

    depth, sigma, _, levels = jax.lax.while_loop(
        fwd_cond, fwd_body, (depth0, sigma0, frontier0, jnp.int32(0))
    )

    # ---------------- backward: dependency accumulation ---------------- #
    # δ(v) = Σ_{w: (v,w) tree edge} σ(v)/σ(w) · (1 + δ(w)); iterate levels
    # from deepest-1 down to 0.  Pull over G (v gathers from out-neighbours
    # w) — which is a pull over Gᵀ's reversed edges = push layout of dg;
    # we simply reuse dg with roles flipped (dst→src).
    safe_sigma = jnp.maximum(sigma, 1e-30)

    def bwd_body(i, delta):
        level = levels - 1 - i  # deepest-1 ... 0
        coef = jnp.where(depth < INF_DEPTH, (1.0 + delta) / safe_sigma, 0.0)
        # message flows w → v along edge (v,w): gather at the *src* side of
        # each edge from its dst side (push layout; flat per the paper —
        # backward frontiers are level-sparse).
        msgs = coef[dg.dst] * jnp.where(depth[dg.dst] == level + 1, 1.0, 0.0)
        acc = tocab.segment_reduce(msgs, dg.src, n, "sum")
        contrib = sigma * acc
        delta = jnp.where(depth == level, delta + contrib, delta)
        return delta

    delta = jax.lax.fori_loop(0, levels, bwd_body, jnp.zeros((n,), jnp.float32))
    bc_scores = jnp.where(depth < INF_DEPTH, delta, 0.0).at[source].set(0.0)
    return bc_scores, depth, sigma


def sssp(
    dg: DeviceGraph,
    bg_pull: Optional[BlockedGraph],
    source: jnp.ndarray,
    max_iters: int = 0,
    schedule: str = "uniform",
    impl: str = "slab",
):
    """Bellman-Ford SSSP (min-plus semiring), TOCAB pull per iteration.

    ``dg`` must carry edge weights.  Returns (dist f32[n], iters)."""
    return _sssp_jit(dg, bg_pull, source, max_iters, schedule, impl)


@partial(jax.jit, static_argnames=("max_iters", "schedule", "impl"))
def _sssp_jit(
    dg: DeviceGraph,
    bg_pull: Optional[BlockedGraph],
    source: jnp.ndarray,
    max_iters: int,
    schedule: str,
    impl: str = "slab",
):
    n = dg.n
    max_iters = max_iters or n
    inf = jnp.float32(jnp.inf)
    dist0 = jnp.full((n,), inf).at[source].set(0.0)
    plus = lambda d, w: d + (w if w is not None else 1.0)

    def cond(state):
        _, changed, it = state
        return changed & (it < max_iters)

    def body(state):
        dist, _, it = state
        if _callbacks_enabled():
            jax.debug.callback(partial(_record_iteration, "sssp"))
        relaxed = (
            tocab.tocab_pull(bg_pull, dist, reduce="min", combine=plus,
                             schedule=schedule, impl=impl)
            if bg_pull is not None
            else tocab.baseline_pull(dg, dist, reduce="min", combine=plus)
        )
        new_dist = jnp.minimum(dist, relaxed)
        return new_dist, jnp.any(new_dist < dist), it + 1

    dist, _, iters = jax.lax.while_loop(cond, body, (dist0, jnp.bool_(True), 0))
    return dist, iters


def connected_components(
    dg: DeviceGraph,
    dg_t: DeviceGraph,
    bg_pull: Optional[BlockedGraph] = None,
    max_iters: int = 0,
    schedule: str = "uniform",
    impl: str = "slab",
):
    """Weakly-connected components via min-label propagation (all-active,
    min semiring — the same blocked pull engine as SSSP).

    ``dg_t`` is the transpose edge set (labels must flow both directions
    for *weak* connectivity).  Returns (labels int32[n], iters)."""
    return _cc_jit(dg, dg_t, bg_pull, max_iters, schedule, impl)


@partial(jax.jit, static_argnames=("max_iters", "schedule", "impl"))
def _cc_jit(
    dg: DeviceGraph,
    dg_t: DeviceGraph,
    bg_pull: Optional[BlockedGraph],
    max_iters: int,
    schedule: str,
    impl: str = "slab",
):
    n = dg.n
    max_iters = max_iters or n
    labels0 = jnp.arange(n, dtype=jnp.float32)
    ignore = lambda m, w: m  # unweighted

    def relax(labels):
        fwd = (
            tocab.tocab_pull(bg_pull, labels, reduce="min", combine=ignore,
                             schedule=schedule, impl=impl)
            if bg_pull is not None
            else tocab.baseline_pull(dg, labels, reduce="min", combine=ignore)
        )
        bwd = tocab.baseline_pull(dg_t, labels, reduce="min", combine=ignore)
        return jnp.minimum(labels, jnp.minimum(fwd, bwd))

    def cond(state):
        _, changed, it = state
        return changed & (it < max_iters)

    def body(state):
        labels, _, it = state
        if _callbacks_enabled():
            jax.debug.callback(partial(_record_iteration, "cc"))
        new = relax(labels)
        return new, jnp.any(new < labels), it + 1

    labels, _, iters = jax.lax.while_loop(
        cond, body, (labels0, jnp.bool_(True), 0))
    return labels.astype(jnp.int32), iters
