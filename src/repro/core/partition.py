"""TOCAB static 1D blocking with local-ID compaction (paper §3.1).

Pull direction = *column blocking*: edges are grouped by the block of their
**source** vertex, so the randomly-read ``contributions`` array is confined to
a fast-memory-sized contiguous window per block.  Destinations touched by a
block are compacted to dense local IDs; partial results are written to a dense
``partial_sums[local_budget]`` slab and merged in a second reduction phase.

Push direction = *row blocking*: identical code path on the transposed roles
(the paper: "the same preprocessing code works for both push and pull").

All arrays are padded to static budgets so the representation is
jit/pjit/Pallas friendly:  every block owns an identical-shape slab — this is
the TPU analogue of the paper's TWC shape regularization.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels.common import roundup

from .graph import Graph, GraphValidationError, graph_fingerprint, \
    validate_graph

__all__ = ["BlockedGraph", "build_blocked", "choose_block_size"]

# Bin thresholds forwarded to repro.core.balance.make_schedule by default.
DEFAULT_BIN_THRESHOLDS = (4.0, 32.0)

# Identity elements per reduction op (used to neutralize padded edge slots).
REDUCE_IDENTITY = {
    "sum": 0.0,
    "min": float("inf"),
    "max": float("-inf"),
}
# The reduction op itself, elementwise, for each name above.
REDUCE_OPS = {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum}


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BlockedGraph:
    """TOCAB blocked-CSR representation (device-ready, static shapes).

    Role of the two index planes depends on ``direction``:

    =============  =======================  =======================
    field          pull (column blocking)   push (row blocking)
    =============  =======================  =======================
    window_idx     src − block·B (gather    dst − block·B (scatter
                   side, contiguous VMEM    side, contiguous window
                   window of values)        of the output)
    compact_idx    dst local ID (scatter    src local ID (gather
                   side → partial_sums)     side → block_contrib)
    id_map         local dst → global dst   local src → global src
    =============  =======================  =======================

    Layout contract: ``compact_idx`` is non-decreasing along each block
    row, padding included — the edges of a block are sorted by their
    compacted side, and a padded slot holds the block's last real local id,
    ``max(n_local[b] − 1, 0)``.  Every id lies below ``local_budget``, so
    the flat keys ``compact_idx[b] + b·local_budget`` never decrease over
    the whole slab, which lets phase 2 declare its scatter sorted.  Padded
    slots are masked by ``edge_mask`` wherever their id is read.
    """

    # --- static metadata (aux data, not traced) ---
    n: int = dataclasses.field(metadata=dict(static=True))
    m: int = dataclasses.field(metadata=dict(static=True))
    direction: str = dataclasses.field(metadata=dict(static=True))
    block_size: int = dataclasses.field(metadata=dict(static=True))
    num_blocks: int = dataclasses.field(metadata=dict(static=True))
    edge_budget: int = dataclasses.field(metadata=dict(static=True))
    local_budget: int = dataclasses.field(metadata=dict(static=True))
    # --- traced arrays ---
    window_idx: jnp.ndarray  # int32[num_blocks, edge_budget]
    compact_idx: jnp.ndarray  # int32[num_blocks, edge_budget]
    edge_mask: jnp.ndarray  # bool[num_blocks, edge_budget]
    id_map: jnp.ndarray  # int32[num_blocks, local_budget]  (pad = n)
    n_local: jnp.ndarray  # int32[num_blocks]
    n_edges: jnp.ndarray  # int32[num_blocks]
    edge_perm: jnp.ndarray = None  # int32[num_blocks, edge_budget] original edge id (pad = m)
    edge_vals: Optional[jnp.ndarray] = None  # f32[num_blocks, edge_budget]
    # distinct window-side vertices per block (reduction rows in push)
    n_window: Optional[jnp.ndarray] = None  # int32[num_blocks]
    # static sparsity classification (repro.core.balance.BlockSchedule);
    # static → part of the jit cache key, so per-bin dispatch is free.
    schedule: Optional[object] = dataclasses.field(
        default=None, metadata=dict(static=True))
    # structural fingerprint of the source graph (tuning-db key); static so
    # a tuned plan resolves from the layout alone, even at trace time.
    fingerprint: Optional[str] = dataclasses.field(
        default=None, metadata=dict(static=True))

    # ------------------------------------------------------------------ #
    @property
    def num_subgraphs(self) -> int:  # paper Table 4 metric
        return self.num_blocks

    @property
    def flat_partial_size(self) -> int:
        return self.num_blocks * self.local_budget

    def padding_fraction(self) -> float:
        return 1.0 - self.m / (self.num_blocks * self.edge_budget)

    def window_lo(self) -> jnp.ndarray:
        """Per-block start of the contiguous window (int32[num_blocks])."""
        return jnp.arange(self.num_blocks, dtype=jnp.int32) * self.block_size


def choose_block_size(
    n: int,
    value_bytes: int = 4,
    fast_mem_bytes: int = 4 * 1024 * 1024,
    align: int = 128,
) -> int:
    """Pick the source-window size so the value window fits the fast-memory
    budget.  GPU paper: 256-vertex blocks for a 2.75 MB L2 shared by the whole
    chip; TPU: VMEM is per-core and software managed, we default to a 4 MB
    window (→ up to 2²⁰ fp32 values), yielding *far fewer* subgraphs — the
    paper's own argument against CuSha's tiny shards, taken further."""
    bs = min(max(align, fast_mem_bytes // value_bytes), max(n, align))
    return roundup(bs, align)


def build_blocked(
    g: Graph,
    block_size: Optional[int] = None,
    direction: str = "pull",
    pad_edges_to: int = 128,
    pad_locals_to: int = 8,
    fast_mem_bytes: int = 4 * 1024 * 1024,
    classify: bool = True,
    bin_thresholds: Union[Tuple[float, float], str] = DEFAULT_BIN_THRESHOLDS,
    validate: Optional[str] = None,
) -> BlockedGraph:
    """Host-side TOCAB preprocessing (paper §3.1 phase 1).

    ``direction='pull'`` blocks by source range; ``'push'`` by destination
    range.  Edges within a block are sorted by their *scatter-side* index so
    accumulation is segment-contiguous.

    ``classify=True`` (default) also bins every block by edges-per-row
    sparsity (``repro.core.balance``) — the blocked subgraphs are much
    sparser than the original graph, so the balanced engines dispatch each
    bin to a matched execution strategy.  ``bin_thresholds`` may be an
    ``(lo, hi)`` pair of edges-per-row cutoffs or ``'auto'`` (per-graph
    terciles).

    ``validate="cheap"`` / ``"full"`` runs CSR validation on ``g`` first
    (:func:`repro.core.graph.validate_graph`) — malformed inputs fail with a
    structured :class:`~repro.core.graph.GraphValidationError` instead of
    corrupting the blocked slabs.  Independently of ``validate``, padded
    slab sizes are always checked against int32 addressing.
    """
    with obs.span("build_blocked", direction=direction, n=g.n, m=g.m):
        assert direction in ("pull", "push")
        if validate is not None:
            validate_graph(g, level=validate)
        if block_size is None:
            block_size = choose_block_size(g.n, fast_mem_bytes=fast_mem_bytes)
        src, dst = g.edges()
        src = src.astype(np.int64)
        dst = dst.astype(np.int64)
        if direction == "pull":
            window_g, compact_g = src, dst  # gather from src window, compact dst
        else:
            window_g, compact_g = dst, src  # scatter to dst window, compact src

        num_blocks = max(1, -(-g.n // block_size))
        blk = window_g // block_size

        # Sort edges by (block, compact-global) — gives blocked CSR with the
        # compacted side contiguous, which both makes local-ID assignment a
        # run-length pass and keeps the scatter side sorted for the kernels.
        with obs.span("build_blocked.sort"):
            order = np.lexsort((compact_g, blk))
            blk, window_g, compact_g = blk[order], window_g[order], compact_g[order]
            vals = None if g.vals is None else g.vals[order]

        with obs.span("build_blocked.fill"):
            edge_counts = np.bincount(blk, minlength=num_blocks).astype(np.int64)
            edge_budget = roundup(int(edge_counts.max(initial=1)), pad_edges_to)

            # Local-ID compaction: within each block, unique compact-side
            # vertices in sorted order get ids 0..n_local-1 (paper Fig. 4).
            new_run = np.ones(blk.shape[0], dtype=bool)
            if blk.shape[0] > 1:
                new_run[1:] = ((blk[1:] != blk[:-1])
                               | (compact_g[1:] != compact_g[:-1]))
            run_id = np.cumsum(new_run) - 1  # global run index
            block_start_run = np.zeros(num_blocks + 1, dtype=np.int64)
            # run index at the first edge of each block:
            first_edge = np.cumsum(np.concatenate([[0], edge_counts]))[:-1]
            has_edges = edge_counts > 0
            block_start_run[:-1][has_edges] = run_id[first_edge[has_edges]]
            local_id = run_id - np.repeat(block_start_run[:-1], edge_counts)
            n_local = np.zeros(num_blocks, dtype=np.int64)
            if blk.shape[0]:
                np.maximum.at(n_local, blk, local_id + 1)
            local_budget = roundup(int(n_local.max(initial=1)), pad_locals_to)

            # Padded slabs are flattened and indexed with int32 downstream
            # (the phase-3 segment reduce, the Pallas kernels' id maps) —
            # overflow here would wrap silently at runtime, so it is always a
            # hard error.
            int32_max = np.iinfo(np.int32).max
            for what, size in (("edge", num_blocks * edge_budget),
                               ("partial", num_blocks * local_budget)):
                if size > int32_max:
                    raise GraphValidationError(
                        "budget_overflow",
                        f"flat {what} slab has {size} entries "
                        f"(num_blocks={num_blocks}), exceeding int32 addressing")

            # --- fill padded slabs ---
            shape_e = (num_blocks, edge_budget)
            window_idx = np.zeros(shape_e, dtype=np.int32)
            # padding repeats the block's last local id: keeps each row
            # sorted (the layout contract in BlockedGraph's docstring)
            compact_idx = np.repeat(
                np.maximum(n_local - 1, 0).astype(np.int32)[:, None],
                edge_budget, axis=1)
            edge_mask = np.zeros(shape_e, dtype=bool)
            edge_perm = np.full(shape_e, g.m, dtype=np.int32)
            edge_vals = (None if vals is None
                         else np.zeros(shape_e, dtype=np.float32))
            id_map = np.full((num_blocks, local_budget), g.n, dtype=np.int32)

            slot = np.arange(blk.shape[0]) - np.repeat(first_edge, edge_counts)
            window_idx[blk, slot] = (window_g - blk * block_size).astype(np.int32)
            compact_idx[blk, slot] = local_id.astype(np.int32)
            edge_mask[blk, slot] = True
            edge_perm[blk, slot] = order.astype(np.int32)  # original edge index
            if edge_vals is not None:
                edge_vals[blk, slot] = vals
            id_map[blk, local_id] = compact_g.astype(np.int32)

            # Distinct window-side vertices per block — the reduction-row
            # count of the push direction (pull reduces over the compacted
            # side, n_local).
            n_window = np.zeros(num_blocks, dtype=np.int64)
            if blk.shape[0]:
                pair = np.unique(blk * np.int64(g.n + 1) + window_g)
                np.add.at(n_window, (pair // (g.n + 1)).astype(np.int64), 1)

        schedule = None
        if classify:
            from .balance import make_schedule  # deferred import (cycle-free)

            rows = n_local if direction == "pull" else n_window
            schedule = make_schedule(edge_counts, rows, thresholds=bin_thresholds,
                                     n_compact_rows=n_local)

        with obs.span("build_blocked.place"):
            slabs = dict(
                window_idx=jnp.asarray(window_idx),
                compact_idx=jnp.asarray(compact_idx),
                edge_mask=jnp.asarray(edge_mask),
                id_map=jnp.asarray(id_map),
                n_local=jnp.asarray(n_local, jnp.int32),
                n_edges=jnp.asarray(edge_counts, jnp.int32),
                edge_perm=jnp.asarray(edge_perm),
                edge_vals=None if edge_vals is None else jnp.asarray(edge_vals),
                n_window=jnp.asarray(n_window, jnp.int32),
            )
        return BlockedGraph(
            n=g.n,
            m=g.m,
            direction=direction,
            block_size=int(block_size),
            num_blocks=int(num_blocks),
            edge_budget=int(edge_budget),
            local_budget=int(local_budget),
            schedule=schedule,
            fingerprint=graph_fingerprint(g),
            **slabs,
        )
