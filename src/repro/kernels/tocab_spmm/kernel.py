"""Pallas TPU kernel for the TOCAB blocked SpMM — the paper's hot loop.

One grid row = one TOCAB subgraph (paper Alg. 4).  The ``BlockSpec`` pins the
block's contiguous source-value window in VMEM — on TPU the residency the
paper gets *probabilistically* from the GPU L2 is *guaranteed* by the DMA
schedule — while the block's edge chunks stream through the grid's second
axis.  Per-edge messages are gathered from the VMEM window (one
scalar-addressed row read per edge, the index in SMEM; see
:mod:`repro.kernels.common`) and accumulated into a dense, compacted
``partials`` slab (local-ID compaction), which is written back as one
coalesced burst.  The cross-block reduction (paper Fig. 5) happens outside
the kernel as a flat segment-sum.

Two accumulation regimes (``mode``):

* ``onehot`` — scatter expressed as ``onehot @ msgs`` dense matmuls on the
  MXU (``precision=HIGHEST``, so the 0/1 selection of f32 messages is
  exact; the MXU's summation order is its own).  The irregular traffic
  becomes systolic work; preferred when ``local_budget`` is small relative
  to the edge chunk.
* ``scatter`` — one row read-modify-write per edge in VMEM, in edge-slot
  order; preferred for very sparse blocks where the one-hot matmul would
  be mostly zeros.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import edge_chunks, lane_chunk, vmem_limit

__all__ = ["tocab_spmm_pallas"]


def _kernel(
    window_ref,  # (block_size, d)   VMEM — the value window
    widx_ref,    # (1, chunk)        SMEM — src index within window
    ev_ref,      # (1, chunk)        SMEM — edge values (0 for padding)
    cidx_ref,    # (1, chunk)        VMEM (onehot) / SMEM (scatter)
    out_ref,     # (local_budget, d) VMEM — dense partial slab
    msgs_ref,    # (chunk, d)        VMEM — the chunk's messages
    *,
    mode: str,
):
    chunk = widx_ref.shape[1]

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    # gather from the VMEM-resident window (the confined random read)
    def gather(e, carry):
        msgs_ref[pl.ds(e, 1), :] = (
            window_ref[pl.ds(widx_ref[0, e], 1), :] * ev_ref[0, e])
        return carry

    jax.lax.fori_loop(0, chunk, gather, 0)

    if mode == "onehot":
        # scatter == one-hot matmul: (local_budget, chunk) @ (chunk, d)
        rows = jax.lax.broadcasted_iota(jnp.int32, (out_ref.shape[0], chunk), 0)
        onehot = (cidx_ref[...] == rows).astype(jnp.float32)
        out_ref[...] += jax.lax.dot(
            onehot, msgs_ref[...], precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
    else:  # scatter (VPU)
        def scatter(e, carry):
            row = pl.ds(cidx_ref[0, e], 1)
            out_ref[row, :] = out_ref[row, :] + msgs_ref[pl.ds(e, 1), :]
            return carry

        jax.lax.fori_loop(0, chunk, scatter, 0)


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "local_budget", "chunk", "mode", "interpret"),
)
def tocab_spmm_pallas(
    values,  # f32[num_blocks*block_size, d]  (padded, d % 128 == 0)
    window_idx,  # i32[num_blocks, edge_budget]
    compact_idx,  # i32[num_blocks, edge_budget]
    edge_vals,  # f32[num_blocks, edge_budget] (0 where padded)
    *,
    block_size: int,
    local_budget: int,
    chunk: int = 512,
    mode: str = "onehot",
    interpret: bool = True,
):
    """Phase-2 partials: returns f32[num_blocks, local_budget, d].

    ``chunk`` is rounded to a lane multiple dividing the padded edge budget
    (padding slots carry edge value 0 and add nothing)."""
    if mode not in ("onehot", "scatter"):
        raise ValueError(f"unknown tocab_spmm mode {mode!r}")
    num_blocks, edge_budget = window_idx.shape
    d = values.shape[1]
    assert values.shape[0] == num_blocks * block_size, (
        f"values must be padded to num_blocks*block_size, got {values.shape}"
    )
    chunk = lane_chunk(edge_budget, chunk)
    need = 4 * (d * (2 * block_size + 2 * local_budget + chunk)
                + (local_budget * chunk + 16 * chunk if mode == "onehot" else 0))
    # double-buffered SMEM index rows: widx and ev, and cidx when scattering
    smem = 8 * chunk * (2 if mode == "onehot" else 3)
    limit = vmem_limit(need, f"tocab_spmm ({mode}, {local_budget} rows × {d})",
                       smem)
    widx, cidx, ev = (edge_chunks(a, chunk)
                      for a in (window_idx, compact_idx, edge_vals))

    def smem_chunk():
        return pl.BlockSpec((None, None, 1, chunk), lambda b, c: (b, c, 0, 0),
                            memory_space=pltpu.SMEM)

    cidx_spec = (pl.BlockSpec((None, None, 1, chunk), lambda b, c: (b, c, 0, 0))
                 if mode == "onehot" else smem_chunk())
    return pl.pallas_call(
        functools.partial(_kernel, mode=mode),
        grid=(num_blocks, widx.shape[1]),
        in_specs=[
            pl.BlockSpec((block_size, d), lambda b, c: (b, 0)),  # VMEM window
            smem_chunk(),
            smem_chunk(),
            cidx_spec,
        ],
        out_specs=pl.BlockSpec((None, local_budget, d), lambda b, c: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((num_blocks, local_budget, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((chunk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=limit),
        interpret=interpret,
        name="tocab_spmm",
    )(values, widx, ev, cidx)
