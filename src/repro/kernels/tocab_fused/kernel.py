"""Persistent Pallas kernels for the fused TOCAB pipeline.

The slab engines run three kernels per iteration — phase-2 partials, the
phase-3 segment reduce, and the per-vertex apply — with a
``(num_blocks, local_budget, d)`` partial slab round-tripping through HBM
between them.  These kernels fuse all three:

* **pull** — grid ``(num_tiles, num_blocks, chunks)``: the output tile is a
  VMEM scratch that stays resident across every cache block and is written
  to HBM once, after the epilogue.  Each block's source-value window rides
  a BlockSpec that ignores the chunk axis, so it is fetched once per block
  while the block's edge chunks stream through SMEM.  Each block
  accumulates into a local ``(local_budget, d)`` VMEM buffer and folds it
  into the tile via ``id_map`` — the partial slab never exists.  On the
  last block the epilogue (``out·mul + add``: PageRank damping / SpMV
  scale) is applied in place, so the apply kernel disappears too.
* **push** — grid ``(num_blocks, chunks)``: row blocking gives each block a
  *disjoint* destination window (= the output block), and the whole source
  vector is copied into VMEM once; each edge reads its source row there
  through ``id_map`` instead of from an HBM ``block_contrib`` slab.

Every per-edge index lives in SMEM.  A chunk is processed in two passes:
gather each edge's message (one scalar-addressed row read in VMEM) into a
``(chunk, d)`` buffer, then fold the messages into the accumulator (one
row read-modify-write each) — see :mod:`repro.kernels.common` for why.
Keeping the messages a separate pass also keeps the weight multiply and
the accumulate two roundings, as in the slab engines (a compiler may not
contract them into one FMA).  Only the block's real edges are visited
(``n_edges``), in slot order, so accumulation order matches the slab
engines' scatter order: block-major, edge-slot order within a block.  In
interpret mode that makes the results bit-identical (asserted in
tests/test_fused.py).  ``order`` (scalar-prefetched) is the block visit
order; it only moves index maps, no slab is permuted.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.partition import REDUCE_IDENTITY, REDUCE_OPS
from repro.kernels.common import edge_chunks, lane_chunk, vmem_limit

__all__ = ["fused_pull_pallas", "fused_push_pallas"]


def _chunk_edges(n_edges, c, chunk: int):
    """Real edges of a block that fall in its chunk ``c``."""
    return jnp.clip(n_edges - c * chunk, 0, chunk)


def _message(row, ev_ref, e, combine, weighted: bool):
    """One edge's message from its gathered ``(1, d)`` value row."""
    ev = ev_ref[0, e] if weighted else None
    if combine is not None:
        return combine(row, ev)
    return row * ev if weighted else row


def _edge_specs(chunk: int, weighted: bool, index_map):
    n = 3 if weighted else 2
    return [pl.BlockSpec((None, None, 1, chunk), index_map,
                         memory_space=pltpu.SMEM)] * n


def _fused_pull_kernel(
    order_ref,  # (num_blocks,)  SMEM   block visit order
    ne_ref,     # (num_blocks,)  SMEM   real edges per block
    nl_ref,     # (num_blocks,)  SMEM   compacted rows per block
    win_ref,    # (block_size, d)       VMEM  the block's source-value window
    widx_ref,   # (1, chunk)            SMEM  src index within the window
    cidx_ref,   # (1, chunk)            SMEM  compacted dst local id
    *rest,      # [ev (1, chunk) SMEM], idmap, eps, out, msgs, acc, tile, sem
    reduce: str,
    combine: Optional[Callable],
    weighted: bool,
    fuse_epilogue: bool,
):
    ev_ref = rest[0] if weighted else None
    idmap_ref, eps_ref, out_hbm, msgs_ref, acc_ref, tile_ref, sem = rest[-7:]
    t, b, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nb, nc = pl.num_programs(1), pl.num_programs(2)
    blk = order_ref[b]
    chunk = widx_ref.shape[1]
    tile_rows = tile_ref.shape[0]
    ident = REDUCE_IDENTITY[reduce]
    op = REDUCE_OPS[reduce]

    @pl.when((b == 0) & (c == 0))
    def _init_tile():
        tile_ref[...] = jnp.full(tile_ref.shape, ident, jnp.float32)

    @pl.when(c == 0)
    def _init_acc():
        acc_ref[...] = jnp.full(acc_ref.shape, ident, jnp.float32)

    def gather(e, carry):
        msgs_ref[pl.ds(e, 1), :] = _message(
            win_ref[pl.ds(widx_ref[0, e], 1), :], ev_ref, e, combine,
            weighted)
        return carry

    def scatter(e, carry):
        row = pl.ds(cidx_ref[0, e], 1)
        acc_ref[row, :] = op(acc_ref[row, :], msgs_ref[pl.ds(e, 1), :])
        return carry

    live = _chunk_edges(ne_ref[blk], c, chunk)
    jax.lax.fori_loop(0, live, gather, 0)
    jax.lax.fori_loop(0, live, scatter, 0)

    @pl.when(c == nc - 1)
    def _fold():
        # fold the block's compacted partial straight into the resident tile
        def fold(lid, carry):
            loc = idmap_ref[0, lid] - t * tile_rows

            @pl.when((loc >= 0) & (loc < tile_rows))
            def _():
                tile_ref[pl.ds(loc, 1), :] = op(tile_ref[pl.ds(loc, 1), :],
                                                acc_ref[pl.ds(lid, 1), :])
            return carry

        jax.lax.fori_loop(0, nl_ref[blk], fold, 0)

    @pl.when((b == nb - 1) & (c == nc - 1))
    def _flush():
        if fuse_epilogue:
            tile_ref[...] = tile_ref[...] * eps_ref[0, 0] + eps_ref[0, 1]
        copy = pltpu.make_async_copy(
            tile_ref, out_hbm.at[pl.ds(t * tile_rows, tile_rows)], sem.at[0])
        copy.start()
        copy.wait()


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "local_budget", "tile_rows", "num_tiles",
                     "chunk", "reduce", "combine", "weighted",
                     "fuse_epilogue", "interpret"),
)
def fused_pull_pallas(
    values,       # f32[num_blocks*block_size, d]  (padded, d % 128 == 0)
    window_idx,   # i32[num_blocks, edge_budget]
    compact_idx,  # i32[num_blocks, edge_budget]
    edge_vals,    # f32[num_blocks, edge_budget]   (unused if not weighted)
    n_edges,      # i32[num_blocks]   real edges (slots 0..n_edges-1)
    n_local,      # i32[num_blocks]   compacted rows
    id_map,       # i32[num_blocks, local_budget]  (pad = n → dropped)
    order,        # i32[num_blocks]   block visit order
    epilogue,     # f32[1, 2]  (mul, add); identity when fuse_epilogue=False
    *,
    block_size: int,
    local_budget: int,
    tile_rows: int,
    num_tiles: int = 1,
    chunk: int = 2048,
    reduce: str = "sum",
    combine: Optional[Callable] = None,
    weighted: bool = True,
    fuse_epilogue: bool = False,
    interpret: bool = True,
):
    """Fused pull: returns f32[num_tiles*tile_rows, d] — no partial slab.

    A single tile sized to the padded output covers every graph whose tile
    fits VMEM; larger graphs raise here, before the kernel is built."""
    num_blocks, edge_budget = window_idx.shape
    d = values.shape[1]
    assert values.shape[0] == num_blocks * block_size, (
        f"values must be padded to num_blocks*block_size, got {values.shape}")
    chunk = lane_chunk(edge_budget, chunk)
    need = 4 * d * (2 * block_size + chunk + local_budget + tile_rows)
    # double-buffered index rows + the three scalar-prefetched vectors
    smem = 8 * (local_budget + 3 * chunk) + 12 * num_blocks
    limit = vmem_limit(need, f"fused pull (tile of {tile_rows} rows × {d})",
                       smem)
    slabs = [edge_chunks(window_idx, chunk), edge_chunks(compact_idx, chunk)]
    if weighted:
        slabs.append(edge_chunks(edge_vals, chunk))
    grid = (num_tiles, num_blocks, slabs[0].shape[1])
    kernel = functools.partial(
        _fused_pull_kernel, reduce=reduce, combine=combine,
        weighted=weighted, fuse_epilogue=fuse_epilogue)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_size, d),
                         lambda t, b, c, o, ne, nl: (o[b], 0)),
            *_edge_specs(chunk, weighted,
                         lambda t, b, c, o, ne, nl: (o[b], c, 0, 0)),
            pl.BlockSpec((None, 1, local_budget),
                         lambda t, b, c, o, ne, nl: (o[b], 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((chunk, d), jnp.float32),
            pltpu.VMEM((local_budget, d), jnp.float32),
            pltpu.VMEM((tile_rows, d), jnp.float32),
            pltpu.SemaphoreType.DMA((1,)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_tiles * tile_rows, d),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3, vmem_limit_bytes=limit),
        interpret=interpret,
        name="tocab_fused_pull",
    )(order, n_edges, n_local, values, *slabs,
      id_map[:, None, :], epilogue)


def _fused_push_kernel(
    order_ref,  # (num_blocks,)  SMEM   block visit order
    ne_ref,     # (num_blocks,)  SMEM   real edges per block
    vals_hbm,   # (n_pad, d)     HBM    whole source vector
    widx_ref,   # (1, chunk)     SMEM   dst index within the block window
    cidx_ref,   # (1, chunk)     SMEM   compacted src local id
    *rest,      # [ev (1, chunk) SMEM], idmap, eps, out, msgs, vals, sem
    reduce: str,
    combine: Optional[Callable],
    weighted: bool,
    fuse_epilogue: bool,
):
    ev_ref = rest[0] if weighted else None
    idmap_ref, eps_ref, out_ref, msgs_ref, vals_ref, sem = rest[-6:]
    b, c = pl.program_id(0), pl.program_id(1)
    nc = pl.num_programs(1)
    chunk = widx_ref.shape[1]
    op = REDUCE_OPS[reduce]

    @pl.when((b == 0) & (c == 0))
    def _load_values():
        copy = pltpu.make_async_copy(vals_hbm, vals_ref, sem.at[0])
        copy.start()
        copy.wait()

    @pl.when(c == 0)
    def _init_window():
        out_ref[...] = jnp.full(out_ref.shape, REDUCE_IDENTITY[reduce],
                                jnp.float32)

    def gather(e, carry):
        # block_contrib gather, in VMEM: local src → global src → value row
        src = idmap_ref[0, cidx_ref[0, e]]
        msgs_ref[pl.ds(e, 1), :] = _message(
            vals_ref[pl.ds(src, 1), :], ev_ref, e, combine, weighted)
        return carry

    def scatter(e, carry):
        row = pl.ds(widx_ref[0, e], 1)
        out_ref[row, :] = op(out_ref[row, :], msgs_ref[pl.ds(e, 1), :])
        return carry

    live = _chunk_edges(ne_ref[order_ref[b]], c, chunk)
    jax.lax.fori_loop(0, live, gather, 0)
    jax.lax.fori_loop(0, live, scatter, 0)

    if fuse_epilogue:
        @pl.when(c == nc - 1)
        def _epilogue():
            out_ref[...] = out_ref[...] * eps_ref[0, 0] + eps_ref[0, 1]


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "local_budget", "chunk", "reduce",
                     "combine", "weighted", "fuse_epilogue", "interpret"),
)
def fused_push_pallas(
    values,       # f32[n_pad, d]  (n_pad % 8 == 0, d % 128 == 0)
    window_idx,   # i32[num_blocks, edge_budget]
    compact_idx,  # i32[num_blocks, edge_budget]
    edge_vals,    # f32[num_blocks, edge_budget]   (unused if not weighted)
    n_edges,      # i32[num_blocks]
    id_map,       # i32[num_blocks, local_budget]
    order,        # i32[num_blocks]   block visit order
    epilogue,     # f32[1, 2]
    *,
    block_size: int,
    local_budget: int,
    chunk: int = 2048,
    reduce: str = "sum",
    combine: Optional[Callable] = None,
    weighted: bool = True,
    fuse_epilogue: bool = False,
    interpret: bool = True,
):
    """Fused push: returns f32[num_blocks*block_size, d] (slice to n), in
    natural block order whatever ``order`` visits.

    The ``block_contrib`` slab of the slab engine is replaced by reads of
    the VMEM-resident ``values``."""
    num_blocks, edge_budget = window_idx.shape
    n_pad, d = values.shape
    chunk = lane_chunk(edge_budget, chunk)
    need = 4 * d * (n_pad + 2 * block_size + chunk)
    smem = 8 * (local_budget + 3 * chunk) + 8 * num_blocks
    limit = vmem_limit(need, f"fused push (source vector of {n_pad} rows "
                             f"× {d})", smem)
    slabs = [edge_chunks(window_idx, chunk), edge_chunks(compact_idx, chunk)]
    if weighted:
        slabs.append(edge_chunks(edge_vals, chunk))
    kernel = functools.partial(
        _fused_push_kernel, reduce=reduce, combine=combine,
        weighted=weighted, fuse_epilogue=fuse_epilogue)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(num_blocks, slabs[0].shape[1]),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            *_edge_specs(chunk, weighted,
                         lambda b, c, o, ne: (o[b], c, 0, 0)),
            pl.BlockSpec((None, 1, local_budget),
                         lambda b, c, o, ne: (o[b], 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((block_size, d), lambda b, c, o, ne: (o[b], 0)),
        scratch_shapes=[
            pltpu.VMEM((chunk, d), jnp.float32),
            pltpu.VMEM((n_pad, d), jnp.float32),
            pltpu.SemaphoreType.DMA((1,)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_blocks * block_size, d),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 2, vmem_limit_bytes=limit),
        interpret=interpret,
        name="tocab_fused_push",
    )(order, n_edges, values, *slabs, id_map[:, None, :], epilogue)
