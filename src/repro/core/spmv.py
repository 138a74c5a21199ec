"""SpMV (paper Fig. 7): y = A·x over the graph's weighted adjacency.

Most vertex programs are generalized SpMV (paper cites GraphMat) — this module
is both a benchmark and the oracle for the Pallas ``tocab_spmm`` kernel.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from .graph import DeviceGraph
from .partition import BlockedGraph
from . import tocab

__all__ = ["spmv", "SPMV_VARIANTS"]

SPMV_VARIANTS = ("base", "push", "cb", "gc-pull", "gc-push")


def spmv(
    dg: DeviceGraph,
    bg: Optional[BlockedGraph],
    x: jnp.ndarray,
    variant: str = "gc-pull",
    schedule: str = "uniform",
    dense_impl: Optional[str] = None,
    impl: str = "slab",
    scale=None,
):
    """y[dst] = Σ_{(src,dst)} A[src,dst]·x[src].

    ``x`` may be a vector (n,) — SpMV — or a matrix (n, d) — SpMM, which is
    the GNN aggregation primitive.  ``schedule='balanced'`` runs the blocked
    variants with sparsity-aware per-bin strategies.  ``dense_impl``
    forces the balanced dense-bin backend (``'pallas'`` / ``'onehot'``);
    ``impl='fused'`` routes the gc variants through the persistent
    no-partial-slab pipeline.  ``scale`` fuses ``y*scale`` into the engine
    epilogue (gc variants)."""
    return _spmv_jit(dg, bg, x, variant, schedule, dense_impl, impl, scale)


@partial(jax.jit, static_argnames=("variant", "schedule", "dense_impl",
                                   "impl"))
def _spmv_jit(
    dg: DeviceGraph,
    bg: Optional[BlockedGraph],
    x: jnp.ndarray,
    variant: str,
    schedule: str,
    dense_impl: Optional[str],
    impl: str = "slab",
    scale=None,
):
    epilogue = None if scale is None else (scale, 0.0)
    if variant == "base":
        y = tocab.baseline_pull(dg, x, reduce="sum")
    elif variant == "push":
        y = tocab.baseline_push(dg, x, reduce="sum")
    elif variant == "cb":
        y = tocab.cb_pull(bg, x, reduce="sum")
    elif variant == "gc-pull":
        return tocab.tocab_pull(bg, x, reduce="sum", schedule=schedule,
                                dense_impl=dense_impl, impl=impl,
                                epilogue=epilogue)
    elif variant == "gc-push":
        return tocab.tocab_push(bg, x, reduce="sum", schedule=schedule,
                                impl=impl, epilogue=epilogue)
    else:
        raise ValueError(f"unknown SpMV variant {variant!r}")
    return y if scale is None else y * scale
