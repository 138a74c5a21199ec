"""jit'd public wrapper: BlockedGraph → Pallas TOCAB SpMM → global result.

Handles padding (values to num_blocks·block_size rows; feature dim to the
TPU lane width) and runs the phase-3 reduction.  Numerically identical to
``repro.core.tocab.tocab_pull`` (sum semiring) — asserted in tests.

``tocab_spmm_partials`` additionally supports a **bin-aware grid**: pass
``block_ids`` (a static tuple of block indices, e.g. the dense bin of a
``repro.core.balance.BlockSchedule``) and the Pallas grid covers only those
blocks — the sparsity-aware scheduler runs the tile kernel on dense
subgraphs while sparse bins take cheaper segmented-reduce paths.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.partition import BlockedGraph
from repro.core.tocab import reduce_partials
from repro.kernels.common import LANE, roundup

from .kernel import tocab_spmm_pallas
from .ref import tocab_spmm_ref

__all__ = ["tocab_spmm", "tocab_spmm_partials", "LANE"]


@partial(
    jax.jit,
    static_argnames=(
        "mode", "interpret", "use_ref", "chunk", "block_ids", "unweighted",
        "local_budget",
    ),
)
def tocab_spmm_partials(
    bg: BlockedGraph,
    x: jnp.ndarray,  # f32[n] or f32[n, d]
    mode: str = "onehot",
    chunk: int = 256,
    interpret: bool = True,
    use_ref: bool = False,
    block_ids: Optional[Tuple[int, ...]] = None,
    unweighted: bool = False,
    local_budget: Optional[int] = None,
):
    """Phase-2 partial slabs through the Pallas tile kernel.

    Returns partials of shape ``(k, local_budget)`` (vector ``x``) or
    ``(k, local_budget, d)``, where ``k = len(block_ids)`` (all blocks when
    ``block_ids`` is None, matching the uniform grid).  ``unweighted=True``
    ignores stored edge values (PageRank semantics).  ``local_budget``
    overrides the global partial-slab width — the sparsity-aware scheduler
    passes the dense bin's (much smaller) static row budget, shrinking the
    kernel's one-hot scatter matmul accordingly."""
    assert bg.direction == "pull"
    squeeze = x.ndim == 1
    if squeeze:
        x = x[:, None]
    n, d = x.shape
    d_pad = roundup(d, LANE)
    rows_pad = bg.num_blocks * bg.block_size
    values = jnp.zeros((rows_pad, d_pad), jnp.float32)
    values = values.at[:n, :d].set(x.astype(jnp.float32))

    edge_vals = bg.edge_vals
    if edge_vals is None or unweighted:
        edge_vals = bg.edge_mask.astype(jnp.float32)
    else:
        edge_vals = jnp.where(bg.edge_mask, edge_vals, 0.0)

    window_idx, compact_idx = bg.window_idx, bg.compact_idx
    if block_ids is not None:
        # Bin-aware grid: gather the selected blocks' slabs (and their
        # contiguous value windows) so grid step j maps to block_ids[j].
        ids = jnp.asarray(block_ids, jnp.int32)
        window_idx = jnp.take(window_idx, ids, axis=0)
        compact_idx = jnp.take(compact_idx, ids, axis=0)
        edge_vals = jnp.take(edge_vals, ids, axis=0)
        values = jnp.take(
            values.reshape(bg.num_blocks, bg.block_size, d_pad), ids, axis=0
        ).reshape(len(block_ids) * bg.block_size, d_pad)

    fn = tocab_spmm_ref if use_ref else partial(
        tocab_spmm_pallas, chunk=chunk, mode=mode, interpret=interpret
    )
    partials = fn(
        values,
        window_idx,
        compact_idx,
        edge_vals,
        block_size=bg.block_size,
        local_budget=local_budget or bg.local_budget,
    )
    partials = partials[:, :, :d]
    return partials[:, :, 0] if squeeze else partials


@partial(jax.jit, static_argnames=("mode", "interpret", "use_ref", "chunk"))
def tocab_spmm(
    bg: BlockedGraph,
    x: jnp.ndarray,  # f32[n] or f32[n, d]
    mode: str = "onehot",
    chunk: int = 256,
    interpret: bool = True,
    use_ref: bool = False,
):
    """y = Aᵀ-gather-reduce of x through the TOCAB blocked layout.

    ``x`` may be (n,) — SpMV — or (n, d) — SpMM / GNN aggregation.
    Returns the same rank as the input."""
    partials = tocab_spmm_partials(
        bg, x, mode=mode, chunk=chunk, interpret=interpret, use_ref=use_ref
    )
    # partials rank already matches x's rank (vector → (nb, lb)); the phase-3
    # reduction is tail-shape agnostic.
    return reduce_partials(bg, partials, reduce="sum")
