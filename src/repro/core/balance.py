"""Sparsity-aware load balancing for TOCAB subgraphs (paper §load-balancing).

GraphCage's integration argument: cache blocking only pays off when it is
*coordinated with load balancing* — blocked subgraphs are much sparser than
the original graph (paper Table 1), so a one-size-fits-all edge mapping
wastes the cache wins.  Following Gunrock's per-frontier strategy selection,
we classify every TOCAB block **once, at build time**, by its edges-per-row
density and dispatch each bin to a matched execution strategy:

==========  =========================  =====================================
bin         edges/row                  strategy
==========  =========================  =====================================
``sparse``  < ``thresholds[0]``        row-per-lane segmented reduce
                                       (sorted segment ids, one lane per
                                       compacted row — short segments)
``medium``  < ``thresholds[1]``        Merrill-style chunked segmented scan
                                       (``lax.scan`` over edge chunks with a
                                       running-segment carry)
``dense``   ≥ ``thresholds[1]``        tile kernel — the Pallas
                                       ``tocab_spmm`` bin-aware grid on TPU,
                                       or a chunked one-hot matmul (MXU
                                       shape) elsewhere
==========  =========================  =====================================

The classification is carried on :class:`~repro.core.partition.BlockedGraph`
as a static :class:`BlockSchedule` (hashable → part of the jit cache key),
so dispatch costs nothing at runtime: each bin's block subset is a Python
tuple and the per-bin computations are ordinary traced subgraph gathers.

Every engine records per-bin block/edge counters into ``repro.obs`` at
trace time; the ``fig8_balance`` benchmark times the bins individually.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.metrics import registry as _obs

from .partition import REDUCE_IDENTITY, REDUCE_OPS, BlockedGraph

__all__ = [
    "BIN_NAMES",
    "DEFAULT_THRESHOLDS",
    "BlockSchedule",
    "UNWEIGHTED",
    "make_schedule",
    "require_schedule",
    "balanced_pull_partials",
    "balanced_pull",
    "balanced_push",
    "balanced_edge_reduce",
    "bin_pull_partials",
    "default_dense_impl",
]

BIN_SPARSE, BIN_MEDIUM, BIN_DENSE = 0, 1, 2
BIN_NAMES = ("sparse", "medium", "dense")

#: edges-per-row cutoffs (sparse < t0 ≤ medium < t1 ≤ dense).  Defaults match
#: the CPU-scale suite: rows shorter than a VPU sublane stay on the segmented
#: reduce; rows long enough to amortize a tile matmul go dense.
DEFAULT_THRESHOLDS = (4.0, 32.0)


def UNWEIGHTED(msgs, edge_vals):
    """Sentinel ``combine`` that ignores edge values (PageRank on weighted
    graphs).  Engines recognize it by identity, which keeps the dense tile
    path eligible (generic callables force the scan fallback)."""
    return msgs


@dataclasses.dataclass(frozen=True)
class BlockSchedule:
    """Static sparsity classification of TOCAB blocks (hashable).

    ``bins[b]`` is the bin id (0=sparse, 1=medium, 2=dense) of block ``b``;
    the per-bin aggregates are precomputed host-side so observability never
    touches traced arrays.
    """

    thresholds: Tuple[float, float]
    bins: Tuple[int, ...]
    blocks_per_bin: Tuple[int, int, int]
    edges_per_bin: Tuple[int, int, int]
    rows_per_bin: Tuple[int, int, int]
    # max reduction rows of any single block in the bin (8-aligned) — the
    # bin-local partial-slab width.  Dense bins have few distinct rows per
    # block, so their tile scatters shrink from the global local_budget to
    # this much smaller static width: the scheduling win in shape form.
    row_budget_per_bin: Tuple[int, int, int] = (0, 0, 0)
    # max *compact-side* rows (n_local) of any block in the bin, 8-aligned.
    # This bounds compact_idx — the scatter target of the pull partials and
    # of balanced_edge_reduce.  For pull layouts it equals row_budget_per_bin
    # (the classification rows *are* n_local); for push the classification
    # rows are the window side (n_window), which says nothing about
    # compact_idx — sizing the edge-reduce slab from it corrupts results.
    compact_budget_per_bin: Tuple[int, int, int] = (0, 0, 0)

    @property
    def num_blocks(self) -> int:
        return len(self.bins)

    def blocks_in(self, bin_id: int) -> Tuple[int, ...]:
        return tuple(b for b, v in enumerate(self.bins) if v == bin_id)

    def summary(self) -> dict:
        return {
            name: {
                "blocks": self.blocks_per_bin[i],
                "edges": self.edges_per_bin[i],
                "rows": self.rows_per_bin[i],
            }
            for i, name in enumerate(BIN_NAMES)
        }


def make_schedule(
    n_edges: Sequence[int],
    n_rows: Sequence[int],
    thresholds: Union[Tuple[float, float], str] = DEFAULT_THRESHOLDS,
    n_compact_rows: Optional[Sequence[int]] = None,
) -> BlockSchedule:
    """Classify blocks by edges-per-row (host-side, build time).

    ``n_rows`` is the reduction-side row count of each block: compacted
    locals for pull, window vertices for push.  ``n_compact_rows`` is the
    compact-side count (``n_local``) when it differs from ``n_rows`` — push
    layouts must pass it so ``compact_budget_per_bin`` bounds ``compact_idx``
    rather than the window.  ``thresholds='auto'`` picks per-graph terciles
    of the observed edges-per-row distribution.
    """
    e = np.asarray(n_edges, dtype=np.float64)
    r = np.maximum(np.asarray(n_rows, dtype=np.float64), 1.0)
    epr = e / r
    if isinstance(thresholds, str):
        if thresholds != "auto":
            raise ValueError(f"unknown thresholds mode {thresholds!r}")
        live = epr[e > 0]
        if live.size == 0:
            lo, hi = DEFAULT_THRESHOLDS
        else:
            lo = float(np.quantile(live, 1 / 3))
            hi = max(float(np.quantile(live, 2 / 3)), lo + 1e-9)
    else:
        lo, hi = float(thresholds[0]), float(thresholds[1])
        if not lo <= hi:
            raise ValueError(f"thresholds must be ascending, got {(lo, hi)}")
    bins = np.where(epr < lo, BIN_SPARSE, np.where(epr < hi, BIN_MEDIUM, BIN_DENSE))
    bins[e == 0] = BIN_SPARSE  # empty blocks ride the cheapest path
    rows = np.asarray(n_rows, dtype=np.int64)
    compact = (
        rows if n_compact_rows is None
        else np.asarray(n_compact_rows, dtype=np.int64)
    )

    def per_bin(arr):
        return tuple(int(arr[bins == b].sum()) for b in range(3))

    def budget(arr, b):
        sel = arr[bins == b]
        top = int(sel.max()) if sel.size else 0
        return max(8, -(-top // 8) * 8)

    return BlockSchedule(
        thresholds=(lo, hi),
        bins=tuple(int(b) for b in bins),
        blocks_per_bin=tuple(int((bins == b).sum()) for b in range(3)),
        edges_per_bin=per_bin(e),
        rows_per_bin=per_bin(rows),
        row_budget_per_bin=tuple(budget(rows, b) for b in range(3)),
        compact_budget_per_bin=tuple(budget(compact, b) for b in range(3)),
    )


def require_schedule(bg: BlockedGraph) -> BlockSchedule:
    if bg.schedule is None:
        raise ValueError(
            "BlockedGraph carries no BlockSchedule — rebuild with "
            "build_blocked(..., classify=True) (the default) or attach one "
            "via dataclasses.replace(bg, schedule=make_schedule(...))."
        )
    return bg.schedule


def fused_block_order(bg: BlockedGraph) -> Tuple[int, ...]:
    """Bin-major visit order for the fused engines: dense → medium → sparse.

    The fused pipeline streams blocks back-to-back through one resident
    accumulator, so the heavy (dense) blocks go first — their gather windows
    are issued while the prefetch queue is still deep, and the short sparse
    tail can't leave the pipeline draining behind a late straggler.  Only
    valid where block order cannot change results: push (disjoint destination
    windows) always; pull only for order-insensitive semirings (min/max).
    """
    sched = require_schedule(bg)
    return (sched.blocks_in(BIN_DENSE) + sched.blocks_in(BIN_MEDIUM)
            + sched.blocks_in(BIN_SPARSE))


def default_dense_impl() -> str:
    """Pallas tile kernel on TPU; chunked one-hot matmul elsewhere (the
    interpret-mode Pallas path pads features to the 128 lane width, which is
    pure overhead off-TPU)."""
    return "pallas" if jax.default_backend() == "tpu" else "onehot"


def _compact_budget(sched: BlockSchedule, bin_id: int, local_budget: int) -> int:
    """Static slab width for reductions over ``compact_idx`` — the bin's
    compact-side budget, falling back to the classification-row budget
    (identical for pull) and then the global ``local_budget`` for
    hand-built schedules that carry neither."""
    rb = sched.compact_budget_per_bin[bin_id] or sched.row_budget_per_bin[bin_id]
    return min(rb or local_budget, local_budget)


def _record_bins(bg: BlockedGraph, direction: str, engine: str):
    """Trace-time per-bin telemetry (static facts — jit-safe, free at run)."""
    sched = bg.schedule
    if sched is None:
        return
    for i, name in enumerate(BIN_NAMES):
        _obs.counter(
            "tocab.balance.bin_traces", "balanced-engine traces by bin"
        ).inc(bin=name, direction=direction, engine=engine)
        _obs.gauge("tocab.balance.bin_blocks", "blocks per sparsity bin").set(
            sched.blocks_per_bin[i], bin=name, direction=direction)
        _obs.gauge("tocab.balance.bin_edges", "edges per sparsity bin").set(
            sched.edges_per_bin[i], bin=name, direction=direction)


# ====================================================================== #
# Shared subset helpers
# ====================================================================== #
def _take_blocks(bg: BlockedGraph, ids: Tuple[int, ...]):
    idx = jnp.asarray(ids, jnp.int32)
    ev = None if bg.edge_vals is None else jnp.take(bg.edge_vals, idx, axis=0)
    return (
        jnp.take(bg.window_idx, idx, axis=0),
        jnp.take(bg.compact_idx, idx, axis=0),
        jnp.take(bg.edge_mask, idx, axis=0),
        ev,
        idx,
    )


def _pick_chunk(edge_budget: int, chunk: int) -> int:
    chunk = max(1, min(chunk, edge_budget))
    while edge_budget % chunk:
        chunk //= 2
    return chunk


# ====================================================================== #
# Pull-layout reduction strategies (reduce blocked messages over compact_idx)
# ====================================================================== #
def _reduce_msgs_sparse(row_budget, cidx, mask, msgs, reduce):
    """Row-per-lane segmented reduce: compact ids are sorted within each
    block (build_blocked sorts edges by compact-global), so the flattened
    segment ids are globally sorted — the short-segment fast path."""
    from .tocab import segment_reduce

    k = cidx.shape[0]
    lb1 = row_budget + 1
    cidx_eff = jnp.where(mask, cidx, row_budget)  # padding → drop row
    flat = cidx_eff + jnp.arange(k, dtype=jnp.int32)[:, None] * lb1
    tail = msgs.shape[2:]
    partials = segment_reduce(
        msgs.reshape((-1,) + tail), flat.reshape(-1), k * lb1, reduce,
        sorted_ids=True,
    )
    return partials.reshape((k, lb1) + tail)[:, :row_budget]


def _reduce_msgs_scan(row_budget, cidx, mask, msgs, reduce, chunk: int = 256):
    """Merrill-style chunked segmented scan for mid-density rows.

    Edges are processed in fixed chunks under ``lax.scan``; the running
    value of the segment left open at each chunk boundary is the carry, and
    within a chunk the segmented prefix is an ``associative_scan``.  Segment
    totals are read at segment tails and scattered once per row."""
    op = REDUCE_OPS[reduce]
    ident = jnp.asarray(REDUCE_IDENTITY[reduce], msgs.dtype)
    k, eb = cidx.shape
    tail = msgs.shape[2:]
    chunk = _pick_chunk(eb, chunk)
    nch = eb // chunk

    cidx_eff = jnp.where(mask, cidx, row_budget)
    heads = jnp.concatenate(
        [jnp.ones((k, 1), bool), cidx_eff[:, 1:] != cidx_eff[:, :-1]], axis=1)

    def expand(flags):
        return flags.reshape(flags.shape + (1,) * len(tail))

    def comb(a, b):
        fa, va = a
        fb, vb = b
        return fa | fb, jnp.where(expand(fb), vb, op(va, vb))

    h_c = jnp.moveaxis(heads.reshape(k, nch, chunk), 1, 0)
    v_c = jnp.moveaxis(msgs.reshape((k, nch, chunk) + tail), 1, 0)

    def chunk_step(carry, xs):
        hh, vv = xs  # (k, chunk[, tail]) — one chunk of every row
        fh, fv = jax.lax.associative_scan(comb, (hh, vv), axis=1)
        # positions before the chunk's first head continue the carried segment
        out = jnp.where(expand(fh), fv, op(carry[:, None], fv))
        return out[:, -1], out

    init = jnp.full((k,) + tail, ident, msgs.dtype)
    _, scanned = jax.lax.scan(chunk_step, init, (h_c, v_c))
    scanned = jnp.moveaxis(scanned, 0, 1).reshape((k, eb) + tail)

    tails = jnp.concatenate(
        [cidx_eff[:, 1:] != cidx_eff[:, :-1], jnp.ones((k, 1), bool)], axis=1)
    write = jnp.where(tails & mask, cidx, row_budget)  # dummy row drops
    lb1 = row_budget + 1
    flat = (write + jnp.arange(k, dtype=jnp.int32)[:, None] * lb1).reshape(-1)
    slab = jnp.full((k * lb1,) + tail, ident, msgs.dtype)
    slab = slab.at[flat].set(scanned.reshape((-1,) + tail), mode="drop")
    return slab.reshape((k, lb1) + tail)[:, :row_budget]


def _reduce_msgs_onehot(row_budget, cidx, mask, msgs, chunk: int = 256):
    """Dense-bin tile path (the default off TPU): scatter as chunked one-hot
    matmuls (sum semiring only) — the MXU-native shape, pure JAX.  The
    one-hot width is the *bin's* row budget, not the global local_budget:
    dense blocks compact to few distinct rows, so the matmul stays small."""
    k, eb = cidx.shape
    tail = msgs.shape[2:]
    chunk = _pick_chunk(eb, chunk)
    nch = eb // chunk
    td = 1
    for t in tail:
        td *= t
    cidx_eff = jnp.where(mask, cidx, row_budget)
    c_c = jnp.moveaxis(cidx_eff.reshape(k, nch, chunk), 1, 0)
    v_c = jnp.moveaxis(
        msgs.reshape((k, nch, chunk, td)), 1, 0)

    lb1 = row_budget + 1

    def chunk_step(acc, xs):
        cc, vv = xs  # (k, chunk), (k, chunk, td)
        onehot = (
            cc[:, :, None] == jnp.arange(lb1, dtype=jnp.int32)[None, None, :]
        ).astype(vv.dtype)
        return acc + jnp.einsum(
            "bel,bed->bld", onehot, vv,
            preferred_element_type=jnp.float32).astype(acc.dtype), None

    init = jnp.zeros((k, lb1, td), msgs.dtype)
    acc, _ = jax.lax.scan(chunk_step, init, (c_c, v_c))
    return acc[:, :row_budget].reshape((k, row_budget) + tail)


def _pull_msgs(bg, ids, values, reduce, combine):
    from .tocab import _edge_messages

    widx, cidx, mask, ev, idx = _take_blocks(bg, ids)
    src_global = widx + (idx * bg.block_size)[:, None]
    if combine is UNWEIGHTED:
        ev, combine = None, None
    msgs = _edge_messages(values, src_global, ev, mask, reduce, combine)
    return cidx, mask, msgs


def _dense_eligible(reduce: str, combine) -> bool:
    return reduce == "sum" and (combine is None or combine is UNWEIGHTED)


def bin_pull_partials(
    bg: BlockedGraph,
    bin_id: int,
    values: jnp.ndarray,
    reduce: str = "sum",
    combine: Optional[Callable] = None,
    dense_impl: Optional[str] = None,
    interpret: Optional[bool] = None,
):
    """Phase-2 partials of one sparsity bin (its blocks only, in schedule
    order), at the bin's static compact-row budget: shape ``(k, budget, …)``.
    Exposed so benchmarks can time bins individually.  ``interpret`` controls
    the Pallas dense path (default: compiled on real TPU, interpret mode
    elsewhere)."""
    sched = require_schedule(bg)
    ids = sched.blocks_in(bin_id)
    if not ids:
        return None
    rb = _compact_budget(sched, bin_id, bg.local_budget)
    if bin_id == BIN_DENSE and _dense_eligible(reduce, combine):
        if (dense_impl or default_dense_impl()) == "pallas":
            from repro.kernels.tocab_spmm.ops import tocab_spmm_partials

            itp = (interpret if interpret is not None
                   else jax.default_backend() != "tpu")
            return tocab_spmm_partials(
                bg, values, block_ids=ids, local_budget=rb,
                unweighted=combine is UNWEIGHTED, interpret=itp)
        cidx, mask, msgs = _pull_msgs(bg, ids, values, reduce, combine)
        return _reduce_msgs_onehot(rb, cidx, mask, msgs)
    cidx, mask, msgs = _pull_msgs(bg, ids, values, reduce, combine)
    if bin_id == BIN_SPARSE:
        return _reduce_msgs_sparse(rb, cidx, mask, msgs, reduce)
    return _reduce_msgs_scan(rb, cidx, mask, msgs, reduce)


def balanced_pull_partials(
    bg: BlockedGraph,
    values: jnp.ndarray,
    reduce: str = "sum",
    combine: Optional[Callable] = None,
    dense_impl: Optional[str] = None,
    interpret: Optional[bool] = None,
):
    """Sparsity-aware phase 2: every bin runs its matched strategy; results
    land in the same (num_blocks, local_budget, …) slab as the uniform path,
    so phase 3 (:func:`repro.core.tocab.reduce_partials`) is unchanged."""
    assert bg.direction == "pull"
    sched = require_schedule(bg)
    tail = values.shape[1:]
    dtype = values.dtype
    partials = jnp.full(
        (bg.num_blocks, bg.local_budget) + tail,
        REDUCE_IDENTITY[reduce], dtype)
    for bin_id in range(len(BIN_NAMES)):
        sub = bin_pull_partials(
            bg, bin_id, values, reduce, combine, dense_impl, interpret)
        if sub is None:
            continue
        ids = jnp.asarray(sched.blocks_in(bin_id), jnp.int32)
        # bin partials are row_budget-wide; rows beyond stay at the identity
        partials = partials.at[ids, : sub.shape[1]].set(sub.astype(dtype))
    return partials


def balanced_pull(
    bg: BlockedGraph,
    values: jnp.ndarray,
    reduce: str = "sum",
    combine: Optional[Callable] = None,
    dense_impl: Optional[str] = None,
    interpret: Optional[bool] = None,
):
    """Sparsity-aware TOCAB pull — bitwise-compatible with ``tocab_pull``
    up to float reassociation (each bin reduces the same edge sets)."""
    from .tocab import reduce_partials

    _record_bins(bg, "pull", "balanced_pull")
    partials = balanced_pull_partials(
        bg, values, reduce, combine, dense_impl, interpret)
    return reduce_partials(bg, partials, reduce)


# ====================================================================== #
# Push direction: per-bin strategies over disjoint destination windows
# ====================================================================== #
def _push_msgs(bg, ids, values, reduce, combine):
    """Per-edge messages for a subset of push blocks (gather each distinct
    source once via id_map, fan out per edge) — mirrors ``tocab_push``."""
    widx, cidx, mask, ev, idx = _take_blocks(bg, ids)
    id_map = jnp.take(bg.id_map, idx, axis=0)
    block_contrib = jnp.take(values, id_map, axis=0, mode="fill", fill_value=0)
    msgs = jnp.take_along_axis(
        block_contrib,
        cidx if block_contrib.ndim == 2 else cidx[..., None],
        axis=1,
    )
    if combine is UNWEIGHTED:
        ev, combine = None, None
    if ev is not None:
        while ev.ndim < msgs.ndim:
            ev = ev[..., None]
    if combine is not None:
        msgs = combine(msgs, ev)
    elif ev is not None:
        msgs = msgs * ev
    ident = jnp.asarray(REDUCE_IDENTITY[reduce], msgs.dtype)
    m = mask if msgs.ndim == mask.ndim else mask[..., None]
    return widx, mask, jnp.where(m, msgs, ident)


def _push_window_sparse(bg, widx, mask, msgs, reduce):
    from .tocab import segment_reduce

    k = widx.shape[0]
    tail = msgs.shape[2:]
    local_dst = jnp.where(
        mask,
        widx + jnp.arange(k, dtype=jnp.int32)[:, None] * bg.block_size,
        k * bg.block_size,
    )
    acc = segment_reduce(
        msgs.reshape((-1,) + tail), local_dst.reshape(-1),
        k * bg.block_size + 1, reduce,
    )[:-1]
    return acc.reshape((k, bg.block_size) + tail)


def _push_window_chunked(bg, widx, mask, msgs, reduce, chunk: int = 256):
    """Chunked-scan push: each ``lax.scan`` step folds one edge chunk into a
    dense per-block window accumulator (the windows are disjoint, so the
    final write-back is a pure reshape — no global scatter)."""
    from .tocab import segment_reduce

    op = REDUCE_OPS[reduce]
    k, eb = widx.shape
    tail = msgs.shape[2:]
    chunk = _pick_chunk(eb, chunk)
    nch = eb // chunk
    local_dst = jnp.where(
        mask,
        widx + jnp.arange(k, dtype=jnp.int32)[:, None] * bg.block_size,
        k * bg.block_size,
    )
    d_c = jnp.moveaxis(local_dst.reshape(k, nch, chunk), 1, 0)
    v_c = jnp.moveaxis(msgs.reshape((k, nch, chunk) + tail), 1, 0)

    def chunk_step(acc, xs):
        dd, vv = xs
        part = segment_reduce(
            vv.reshape((-1,) + tail), dd.reshape(-1),
            k * bg.block_size + 1, reduce,
        )
        return op(acc, part), None

    init = jnp.full((k * bg.block_size + 1,) + tail,
                    REDUCE_IDENTITY[reduce], msgs.dtype)
    acc, _ = jax.lax.scan(chunk_step, init, (d_c, v_c))
    return acc[:-1].reshape((k, bg.block_size) + tail)


def _push_window_onehot(bg, widx, mask, msgs, chunk: int = 128):
    """Dense-bin push: chunked one-hot matmul onto the window (sum only)."""
    k, eb = widx.shape
    tail = msgs.shape[2:]
    td = 1
    for t in tail:
        td *= t
    chunk = _pick_chunk(eb, chunk)
    nch = eb // chunk
    widx_eff = jnp.where(mask, widx, bg.block_size)  # dummy row drops
    w_c = jnp.moveaxis(widx_eff.reshape(k, nch, chunk), 1, 0)
    v_c = jnp.moveaxis(msgs.reshape((k, nch, chunk, td)), 1, 0)
    bs1 = bg.block_size + 1

    def chunk_step(acc, xs):
        ww, vv = xs
        onehot = (
            ww[:, :, None] == jnp.arange(bs1, dtype=jnp.int32)[None, None, :]
        ).astype(vv.dtype)
        return acc + jnp.einsum(
            "bew,bed->bwd", onehot, vv,
            preferred_element_type=jnp.float32).astype(acc.dtype), None

    init = jnp.zeros((k, bs1, td), msgs.dtype)
    acc, _ = jax.lax.scan(chunk_step, init, (w_c, v_c))
    return acc[:, : bg.block_size].reshape((k, bg.block_size) + tail)


def balanced_push(
    bg: BlockedGraph,
    values: jnp.ndarray,
    reduce: str = "sum",
    combine: Optional[Callable] = None,
):
    """Sparsity-aware TOCAB push.  Every bin accumulates into its blocks'
    dense destination windows; windows are disjoint and contiguous so the
    global result is a reshape + slice (no cross-bin conflicts)."""
    assert bg.direction == "push"
    sched = require_schedule(bg)
    _record_bins(bg, "push", "balanced_push")
    tail = values.shape[1:]
    full = jnp.full(
        (bg.num_blocks, bg.block_size) + tail,
        REDUCE_IDENTITY[reduce], values.dtype)
    for bin_id in range(len(BIN_NAMES)):
        ids = sched.blocks_in(bin_id)
        if not ids:
            continue
        widx, mask, msgs = _push_msgs(bg, ids, values, reduce, combine)
        if bin_id == BIN_DENSE and _dense_eligible(reduce, combine):
            slab = _push_window_onehot(bg, widx, mask, msgs)
        elif bin_id == BIN_MEDIUM or bin_id == BIN_DENSE:
            slab = _push_window_chunked(bg, widx, mask, msgs, reduce)
        else:
            slab = _push_window_sparse(bg, widx, mask, msgs, reduce)
        full = full.at[jnp.asarray(ids, jnp.int32)].set(slab.astype(full.dtype))
    return full.reshape((bg.num_blocks * bg.block_size,) + tail)[: bg.n]


# ====================================================================== #
# Edge-value reduce (GNN primitive) through the same bins
# ====================================================================== #
def balanced_edge_reduce(
    bg: BlockedGraph,
    flat_edge_vals: jnp.ndarray,
    reduce: str = "sum",
):
    """Sparsity-aware twin of :func:`repro.core.tocab.tocab_edge_reduce`:
    per-edge values (original order) reduced to the compacted side, with
    each bin on its matched strategy.  Dense bins use the one-hot tile path
    (messages carry no separable ``values``/``edge_vals`` factorization, so
    the Pallas SpMM kernel does not apply)."""
    from .tocab import blocked_edge_values, reduce_partials

    sched = require_schedule(bg)
    _record_bins(bg, bg.direction, "balanced_edge_reduce")
    vals = blocked_edge_values(bg, flat_edge_vals)
    ident = jnp.asarray(REDUCE_IDENTITY[reduce], vals.dtype)
    mask_full = bg.edge_mask
    m = mask_full
    while m.ndim < vals.ndim:
        m = m[..., None]
    vals = jnp.where(m, vals, ident)
    tail = vals.shape[2:]
    partials = jnp.full(
        (bg.num_blocks, bg.local_budget) + tail, ident, vals.dtype)
    for bin_id in range(len(BIN_NAMES)):
        ids = sched.blocks_in(bin_id)
        if not ids:
            continue
        # compact_idx is bounded by n_local, so the slab width must come from
        # the compact budget — row_budget_per_bin is the *window* side on
        # push layouts and under-sizes the scatter (cross-block spill).
        rb = _compact_budget(sched, bin_id, bg.local_budget)
        idx = jnp.asarray(ids, jnp.int32)
        cidx = jnp.take(bg.compact_idx, idx, axis=0)
        mask = jnp.take(mask_full, idx, axis=0)
        msgs = jnp.take(vals, idx, axis=0)
        if bin_id == BIN_DENSE and reduce == "sum":
            sub = _reduce_msgs_onehot(rb, cidx, mask, msgs)
        elif bin_id == BIN_SPARSE:
            sub = _reduce_msgs_sparse(rb, cidx, mask, msgs, reduce)
        else:
            sub = _reduce_msgs_scan(rb, cidx, mask, msgs, reduce)
        partials = partials.at[idx, : sub.shape[1]].set(sub.astype(partials.dtype))
    return reduce_partials(bg, partials, reduce)
