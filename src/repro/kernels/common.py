"""Layout and VMEM helpers shared by the TOCAB Pallas kernels.

Mosaic (the TPU Pallas compiler) sets three rules these kernels are built
around:

* the last two dims of every block are multiples of (8, 128) or equal the
  array's own — so per-block index slabs travel as ``(num_blocks, chunks,
  1, chunk)`` arrays whose blocks are ``(1, chunk)`` rows;
* it lowers no vector-indexed gather or scatter, but it does lower a
  scalar-addressed row load/store (``ref[pl.ds(i, 1), :]``) — so per-edge
  indices live in SMEM and each edge is one row access in VMEM;
* a kernel's VMEM is one TensorCore's: 128 MiB on v5e (the compiler refuses
  any larger scratch), of which :data:`VMEM_BUDGET` is planned for; its
  SMEM is 1 MiB, which bounds the per-block index rows held there.
"""
from __future__ import annotations

import jax.numpy as jnp

__all__ = ["LANE", "VMEM_BUDGET", "roundup", "lane_chunk", "edge_chunks",
           "vmem_limit"]

LANE = 128  # TPU lane width; feature dims are padded to multiples of this

#: VMEM of one TPU v5e TensorCore, as the v5e compiler enforces it
VMEM_CAPACITY = 128 * 2**20
#: what a kernel may plan for; the rest is left to the compiler's scratch
VMEM_BUDGET = 96 * 2**20
#: SMEM a kernel may plan for, of the 1 MiB the v5e compiler enforces
SMEM_BUDGET = 896 * 2**10


def roundup(x: int, to: int) -> int:
    return -(-x // to) * to


def lane_chunk(edge_budget: int, chunk: int) -> int:
    """Edges per grid step: the largest multiple of :data:`LANE` that divides
    the lane-padded ``edge_budget`` and is at most ``max(chunk, LANE)``."""
    eb = roundup(edge_budget, LANE) // LANE
    c = max(1, min(chunk // LANE, eb))
    while eb % c:
        c -= 1
    return c * LANE


def edge_chunks(slab: jnp.ndarray, chunk: int) -> jnp.ndarray:
    """``(num_blocks, edge_budget)`` → ``(num_blocks, chunks, 1, chunk)``,
    zero-padding the edge axis to a whole number of chunks."""
    nb, eb = slab.shape
    pad = roundup(eb, chunk) - eb
    if pad:
        slab = jnp.pad(slab, ((0, 0), (0, pad)))
    return slab.reshape(nb, -1, 1, chunk)


def vmem_limit(need_bytes: int, what: str, smem_bytes: int = 0) -> int:
    """``vmem_limit_bytes`` for a kernel whose resident buffers take
    ``need_bytes`` of VMEM and ``smem_bytes`` of SMEM; raises before any
    ``pallas_call`` when they cannot fit."""
    for mem, need, budget in (("VMEM", need_bytes, VMEM_BUDGET),
                              ("SMEM", smem_bytes, SMEM_BUDGET)):
        if need > budget:
            raise ValueError(
                f"{what} needs {need / 2**20:.2f} MiB of {mem}, more than "
                f"the {budget / 2**20:.2f} MiB a TPU v5e kernel may plan "
                f"for — use the slab engine (impl='slab') for a graph this "
                f"size")
    return min(VMEM_CAPACITY, need_bytes + 16 * 2**20)
