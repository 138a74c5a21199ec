"""jit'd EmbeddingBag wrapper: padding + backend selection."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.common import roundup

from .kernel import embedding_bag_pallas
from .ref import embedding_bag_ref

__all__ = ["embedding_bag"]


@partial(
    jax.jit,
    static_argnames=("mode", "backend", "rows_per_block", "bag_tile", "interpret"),
)
def embedding_bag(
    table,
    indices,
    weights=None,
    mode: str = "sum",
    backend: str = "xla",
    rows_per_block: int = 4096,
    bag_tile: int = 128,
    interpret: bool = True,
):
    if backend != "pallas":
        return embedding_bag_ref(table, indices, weights, mode=mode)
    V, d = table.shape
    B, L = indices.shape
    if weights is None:
        weights = jnp.ones(indices.shape, table.dtype)
    if mode == "mean":
        denom = jnp.maximum(weights.sum(axis=1, keepdims=True), 1e-9)
        weights = weights / denom
    rows_per_block = min(rows_per_block, roundup(V, 8))
    Vp = roundup(V, rows_per_block)
    Bp = roundup(B, min(bag_tile, roundup(B, 8)))
    bag_tile = min(bag_tile, Bp)
    tbl = jnp.zeros((Vp, d), table.dtype).at[:V].set(table)
    idx = jnp.zeros((Bp, L), indices.dtype).at[:B].set(indices)
    w = jnp.zeros((Bp, L), weights.dtype).at[:B].set(weights)
    out = embedding_bag_pallas(
        tbl, idx, w,
        rows_per_block=rows_per_block, bag_tile=bag_tile, interpret=interpret,
    )
    return out[:B]
