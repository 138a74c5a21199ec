"""PageRank in all of the paper's configurations (Fig. 6).

Variants (names follow the paper's evaluation bars):

* ``base``      — flat pull, no optimization (Alg. 1)
* ``push``      — flat push (Alg. 2; no atomics on TPU → segment reduce)
* ``cb``        — conventional cache blocking (blocked, no compaction)
* ``gc-pull``   — GraphCage TOCAB pull (Alg. 4 + reduction phase)
* ``gc-push``   — GraphCage TOCAB push (Alg. 5)
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro import obs
from .balance import UNWEIGHTED as _unweighted
from .graph import DeviceGraph
from .partition import BlockedGraph
from . import tocab

__all__ = ["pagerank", "pagerank_iteration", "PR_VARIANTS"]

PR_VARIANTS = ("base", "push", "cb", "gc-pull", "gc-push")


def _gather_sums(variant: str, dg, bg, contributions, schedule="uniform",
                 impl="slab", epilogue=None):
    # PR is unweighted: the UNWEIGHTED sentinel combine ignores any edge
    # values the graph carries (and keeps the dense tile path eligible).
    kw = dict(reduce="sum", combine=_unweighted)
    if variant == "base":
        return tocab.baseline_pull(dg, contributions, **kw)
    if variant == "push":
        return tocab.baseline_push(dg, contributions, **kw)
    if variant == "cb":
        return tocab.cb_pull(bg, contributions, **kw)
    if variant == "gc-pull":
        return tocab.tocab_pull(bg, contributions, schedule=schedule,
                                impl=impl, epilogue=epilogue, **kw)
    if variant == "gc-push":
        return tocab.tocab_push(bg, contributions, schedule=schedule,
                                impl=impl, epilogue=epilogue, **kw)
    raise ValueError(f"unknown PR variant {variant!r}")


def pagerank_iteration(
    variant: str,
    dg: DeviceGraph,
    bg: Optional[BlockedGraph],
    rank: jnp.ndarray,
    out_degree: jnp.ndarray,
    damping: float = 0.85,
    handle_dangling: bool = True,
    schedule: str = "uniform",
    impl: str = "slab",
):
    """One PR iteration: contributions → gather/scatter → apply.

    GraphCage variants hand the apply step to the engine as an affine
    epilogue ``sums*damping + add`` — the fused impl folds it into the
    kernel's final block visit, the slab impl applies the identical
    expression as a trailing pass, so both stay bit-identical.  Dangling
    mass is known before the gather (it only reads ``rank``), which is what
    lets the apply collapse into one affine form."""
    n = rank.shape[0]
    safe_deg = jnp.maximum(out_degree, 1).astype(rank.dtype)
    contributions = rank / safe_deg
    contributions = jnp.where(out_degree > 0, contributions, 0.0)
    dangling = jnp.where(out_degree > 0, 0.0, rank).sum() if handle_dangling else 0.0
    if variant in ("gc-pull", "gc-push"):
        add = (1.0 - damping) / n + damping * (dangling / n)
        return _gather_sums(variant, dg, bg, contributions, schedule,
                            impl, epilogue=(damping, add))
    sums = _gather_sums(variant, dg, bg, contributions, schedule)
    return (1.0 - damping) / n + damping * (sums + dangling / n)


def pagerank(
    dg: DeviceGraph,
    bg: Optional[BlockedGraph] = None,
    variant: str = "gc-pull",
    damping: float = 0.85,
    tol: float = 1e-6,
    max_iters: int = 200,
    handle_dangling: bool = True,
    schedule: str = "uniform",
    impl: str = "slab",
):
    """Iterate PR until the L1 delta falls below ``tol``.

    Returns (rank, iterations).  ``schedule`` and ``impl`` are the engine of
    the gc variants, as in :func:`~repro.core.tocab.tocab_pull`; a tuned
    call reads them from the tuning DB first, through the tuner's
    ``engine_kwargs``: ``pagerank(dg, bg, **engine_kwargs(bg, "pagerank"))``.

    The call is an ``obs`` span ``pagerank`` (dispatch; the solve runs on
    after it returns), and each step of the compiled loop is the named
    scope ``pagerank.step``."""
    with obs.span("pagerank"):
        return _pagerank_jit(
            dg, bg, variant, damping, tol, max_iters, handle_dangling,
            schedule, impl)


@partial(
    jax.jit,
    static_argnames=(
        "variant", "damping", "tol", "max_iters", "handle_dangling",
        "schedule", "impl",
    ),
)
def _pagerank_jit(
    dg: DeviceGraph,
    bg: Optional[BlockedGraph],
    variant: str,
    damping: float,
    tol: float,
    max_iters: int,
    handle_dangling: bool,
    schedule: str,
    impl: str = "slab",
):
    n = dg.n
    rank0 = jnp.full((n,), 1.0 / n, jnp.float32)

    def cond(state):
        _, delta, it = state
        return (delta > tol) & (it < max_iters)

    def body(state):
        rank, _, it = state
        with jax.named_scope("pagerank.step"):
            new_rank = pagerank_iteration(
                variant, dg, bg, rank, dg.out_degree, damping,
                handle_dangling, schedule, impl,
            )
            return new_rank, jnp.abs(new_rank - rank).sum(), it + 1

    rank, _, iters = jax.lax.while_loop(cond, body, (rank0, jnp.inf, 0))
    return rank, iters
