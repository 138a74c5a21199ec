"""TOCAB execution engines (paper §3.1 phases 2+3) and baselines.

Three engines, all pure-JAX (the Pallas fast path lives in
``repro.kernels.tocab_spmm`` and is numerically identical):

* :func:`baseline_pull` / :func:`baseline_push` — flat edge-centric
  segment-reduce over the *global* vertex arrays.  This is the paper's
  "Base" configuration: random reads of ``values[src]`` span all of HBM.
* :func:`cb_pull` — conventional cache blocking (paper's "CB" bar):
  edges are processed block-by-block but partials are written at *global*
  width (no local-ID compaction) → repeated sparse accesses to ``sums``.
* :func:`tocab_pull` / :func:`tocab_push` — the paper's contribution:
  blocked gather confined to a fast-memory window + dense compacted
  partials + a separate coalesced reduction phase.

All engines support ``sum`` / ``min`` / ``max`` semirings so that PageRank,
SpMV (sum×mul), BFS/SSSP (min-plus) and frontier propagation (max/or) share
one code path — this is the framework's "programmers only write pull/push
operators" surface (paper §3.3 last paragraph).

The uniform slab paths name their phases with ``jax.named_scope``, so the
compiled operations carry the names into a profiler trace:

* ``tocab.gather`` — per-edge messages: the reads of ``values`` (or of the
  block's slab), the combine and the padding mask;
* ``tocab.partials`` — the per-block compacted slab: pull's phase-2 segment
  reduce into it, push's fill of it through ``id_map``;
* ``tocab.reduce`` — into the global vertex array: phase 3
  (:func:`reduce_partials`), push's reduce into the destination windows.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.obs.metrics import registry as _obs
from .graph import DeviceGraph
from .partition import REDUCE_IDENTITY, BlockedGraph

__all__ = [
    "segment_reduce",
    "baseline_pull",
    "baseline_push",
    "cb_pull",
    "tocab_pull",
    "tocab_push",
    "tocab_pull_partials",
    "tocab_edge_reduce",
    "blocked_edge_values",
    "tocab_gather_src",
    "reduce_partials",
]


def _record_engine(engine: str, direction: str):
    """Trace-time telemetry: fires once per (re)trace, so it is jit-safe and
    costs nothing at runtime.  A growing ``engine_traces`` count on a steady
    workload is itself a signal (retrace churn)."""
    _obs.counter(
        "tocab.engine_traces", "engine (re)traces by name/direction"
    ).inc(engine=engine, direction=direction)


_SEG_FNS = {
    "sum": jax.ops.segment_sum,
    "min": jax.ops.segment_min,
    "max": jax.ops.segment_max,
}


def segment_reduce(vals, ids, num_segments: int, reduce: str, sorted_ids: bool = False):
    fn = _SEG_FNS[reduce]
    return fn(
        vals,
        ids,
        num_segments=num_segments,
        indices_are_sorted=sorted_ids,
    )


def _edge_messages(values, src_ids, edge_vals, mask, reduce, combine):
    """Gather per-edge messages and neutralize padding with the identity."""
    msgs = jnp.take(values, src_ids, axis=0, mode="fill", fill_value=0)
    if edge_vals is not None:
        while edge_vals.ndim < msgs.ndim:
            edge_vals = edge_vals[..., None]
    if combine is not None:
        msgs = combine(msgs, edge_vals)
    elif edge_vals is not None:
        msgs = msgs * edge_vals
    ident = jnp.asarray(REDUCE_IDENTITY[reduce], msgs.dtype)
    if msgs.ndim > mask.ndim:
        mask = mask[..., None]
    return jnp.where(mask, msgs, ident)


# ====================================================================== #
# Baseline (flat, non-blocked) engines
# ====================================================================== #
@partial(jax.jit, static_argnames=("reduce", "combine"))
def baseline_pull(
    dg: DeviceGraph,
    values: jnp.ndarray,
    reduce: str = "sum",
    combine: Optional[Callable] = None,
):
    """out[dst] = ⊕_{(src,dst)∈E} values[src] (⊗ edge_val).

    Flat segment reduce by destination — the unblocked hand-optimized
    reference (random reads of ``values`` span the full array)."""
    _record_engine("baseline_pull", "pull")
    mask = jnp.ones(dg.src.shape, dtype=bool)
    msgs = _edge_messages(values, dg.src, dg.vals, mask, reduce, combine)
    return segment_reduce(msgs, dg.dst, dg.n, reduce)


@partial(jax.jit, static_argnames=("reduce", "combine"))
def baseline_push(
    dg: DeviceGraph,
    values: jnp.ndarray,
    reduce: str = "sum",
    combine: Optional[Callable] = None,
):
    """Push direction: scatter values[src] to every out-neighbour.  On TPU
    there are no atomics — the scatter is realized as a segment reduce, i.e.
    push ≡ pull with the read side sequential (src-sorted edges)."""
    _record_engine("baseline_push", "push")
    mask = jnp.ones(dg.src.shape, dtype=bool)
    msgs = _edge_messages(values, dg.src, dg.vals, mask, reduce, combine)
    return segment_reduce(msgs, dg.dst, dg.n, reduce)


# ====================================================================== #
# Conventional cache blocking (no compaction) — the paper's CB strawman
# ====================================================================== #
@partial(jax.jit, static_argnames=("reduce", "combine"))
def cb_pull(
    bg: BlockedGraph,
    values: jnp.ndarray,
    reduce: str = "sum",
    combine: Optional[Callable] = None,
):
    """Column blocking only: gathers are window-confined but every block
    writes partials at global width (repeated sparse access to ``sums``)."""
    assert bg.direction == "pull"
    _record_engine("cb_pull", "pull")
    src_global = bg.window_idx + bg.window_lo()[:, None]
    msgs = _edge_messages(values, src_global, bg.edge_vals, bg.edge_mask, reduce, combine)
    # id_map lookup per edge: id_map[b, compact_idx[b,e]]
    dst_global = jnp.take_along_axis(bg.id_map, bg.compact_idx, axis=1)
    dst_global = jnp.where(bg.edge_mask, dst_global, bg.n)

    def body(carry, xs):
        msgs_b, dst_b = xs
        out = segment_reduce(msgs_b, dst_b, bg.n + 1, reduce)[:-1]
        if reduce == "sum":
            carry = carry + out
        elif reduce == "min":
            carry = jnp.minimum(carry, out)
        else:
            carry = jnp.maximum(carry, out)
        return carry, None

    init = jnp.full(
        (bg.n,) + msgs.shape[2:],
        REDUCE_IDENTITY[reduce],
        msgs.dtype,
    )
    out, _ = jax.lax.scan(body, init, (msgs, dst_global))
    return out


# ====================================================================== #
# TOCAB — blocked + compacted (the paper's contribution)
# ====================================================================== #
def tocab_pull_partials(
    bg: BlockedGraph,
    values: jnp.ndarray,
    reduce: str = "sum",
    combine: Optional[Callable] = None,
):
    """Phase 2 (subgraph processing, Alg. 4): per-block dense partial slabs.

    Returns ``partials`` of shape (num_blocks, local_budget, *value_tail).
    Gathers hit only the block's contiguous source window; scatters hit only
    the dense local partial slab — both fast-memory resident on TPU."""
    assert bg.direction == "pull"
    with jax.named_scope("tocab.gather"):
        src_global = bg.window_idx + bg.window_lo()[:, None]
        msgs = _edge_messages(values, src_global, bg.edge_vals, bg.edge_mask,
                              reduce, combine)
    return _block_partials(bg, msgs, reduce)


def _block_partials(bg: BlockedGraph, msgs: jnp.ndarray, reduce: str):
    """Phase 2's segment reduce of per-edge messages ``(num_blocks,
    edge_budget, *tail)`` into the per-block compacted slab.  Its flat keys
    ``compact_idx + b·local_budget`` never decrease over the slab (the
    layout contract of :class:`BlockedGraph`), so the scatter is declared
    sorted: the TPU compiler then skips the presort it runs before a large
    scatter."""
    with jax.named_scope("tocab.partials"):
        flat_idx = (
            bg.compact_idx
            + jnp.arange(bg.num_blocks, dtype=jnp.int32)[:, None] * bg.local_budget
        )
        tail = msgs.shape[2:]
        partials = segment_reduce(
            msgs.reshape((-1,) + tail),
            flat_idx.reshape(-1),
            bg.flat_partial_size,
            reduce,
            sorted_ids=True,
        )
        return partials.reshape((bg.num_blocks, bg.local_budget) + tail)


def reduce_partials(bg: BlockedGraph, partials: jnp.ndarray, reduce: str = "sum"):
    """Phase 3 (accumulation, paper Fig. 5): merge dense per-block partials
    into the global result.  One flat segment reduce keyed by ``id_map`` —
    XLA lowers it to a vectorized single pass; on a sharded mesh the same op
    becomes a reduce-scatter over the destination axis."""
    with jax.named_scope("tocab.reduce"):
        tail = partials.shape[2:]
        out = segment_reduce(
            partials.reshape((-1,) + tail),
            bg.id_map.reshape(-1),
            bg.n + 1,  # padded id_map entries point at segment n → dropped
            reduce,
        )
        return out[:-1]


def _check_engine(schedule: str, impl: str):
    """The caller's ``schedule`` and ``impl`` are the engine.  ``fused`` ×
    ``balanced`` is the one invalid pairing: the fused pipeline runs every
    block through one resident-accumulator kernel (its bin awareness is a
    visit *order*, not per-bin strategies)."""
    if schedule not in ("uniform", "balanced"):
        raise ValueError(f"unknown schedule {schedule!r}")
    if impl not in ("fused", "slab"):
        raise ValueError(f"unknown impl {impl!r}")
    if impl == "fused" and schedule == "balanced":
        raise ValueError(
            "impl='fused' is incompatible with schedule='balanced' — use "
            "schedule='uniform' with the fused pipeline")


def _slab_epilogue(out, reduce: str, epilogue):
    """Per-vertex apply step on the slab path: the same affine expression
    the fused kernels bake into their final block visit, applied as a
    separate (XLA-fused) pass — keeps the two impls bit-identical."""
    if epilogue is None:
        return out
    if reduce != "sum":
        raise ValueError(
            f"epilogue fusion is affine (out*mul+add) — only the sum "
            f"semiring supports it, got reduce={reduce!r}")
    mul, add = epilogue
    return out * mul + add


@partial(jax.jit, static_argnames=("reduce", "combine", "schedule",
                                   "dense_impl"))
def _tocab_pull_jit(
    bg: BlockedGraph,
    values: jnp.ndarray,
    reduce: str = "sum",
    combine: Optional[Callable] = None,
    schedule: str = "uniform",
    dense_impl: Optional[str] = None,
):
    if schedule == "balanced":
        from .balance import balanced_pull

        return balanced_pull(bg, values, reduce, combine,
                             dense_impl=dense_impl)
    _record_engine("tocab_pull", "pull")
    partials = tocab_pull_partials(bg, values, reduce, combine)
    return reduce_partials(bg, partials, reduce)


def tocab_pull(
    bg: BlockedGraph,
    values: jnp.ndarray,
    reduce: str = "sum",
    combine: Optional[Callable] = None,
    schedule: str = "uniform",
    dense_impl: Optional[str] = None,
    impl: str = "slab",
    epilogue=None,
):
    """``schedule='uniform'`` processes every block with the same segmented
    reduce; ``'balanced'`` dispatches each sparsity bin of the build-time
    :class:`~repro.core.balance.BlockSchedule` to its matched strategy.
    ``dense_impl`` forces the balanced dense-bin backend ('pallas' /
    'onehot'; default picks per backend).

    ``impl='fused'`` routes through the persistent single-kernel pipeline
    (``repro.kernels.tocab_fused``): no partial slab in HBM, bit-identical
    results.  ``epilogue=(mul, add)`` fuses the per-vertex apply step
    ``out*mul + add`` (sum semiring only) — the slab path applies the
    identical expression as a trailing pass.  A kernel that fails to lower
    or compile raises; nothing falls back to another engine."""
    _check_engine(schedule, impl)
    if impl == "fused":
        from repro.kernels.tocab_fused import fused_pull

        _record_engine("tocab_pull_fused", "pull")
        return fused_pull(bg, values, reduce, combine, epilogue)
    out = _tocab_pull_jit(bg, values, reduce=reduce, combine=combine,
                          schedule=schedule, dense_impl=dense_impl)
    return _slab_epilogue(out, reduce, epilogue)


@partial(jax.jit, static_argnames=("reduce", "combine", "schedule"))
def _tocab_push_jit(
    bg: BlockedGraph,
    values: jnp.ndarray,
    reduce: str = "sum",
    combine: Optional[Callable] = None,
    schedule: str = "uniform",
):
    assert bg.direction == "push"
    if schedule == "balanced":
        from .balance import balanced_push

        return balanced_push(bg, values, reduce, combine)
    _record_engine("tocab_push", "push")
    # Gather each unique source's value once per block (the data-reuse win).
    with jax.named_scope("tocab.partials"):
        block_contrib = jnp.take(values, bg.id_map, axis=0, mode="fill",
                                 fill_value=0)
    with jax.named_scope("tocab.gather"):
        msgs = jnp.take_along_axis(
            block_contrib,
            bg.compact_idx if block_contrib.ndim == 2
            else bg.compact_idx[..., None],
            axis=1,
        )
        ev = bg.edge_vals
        if ev is not None:
            while ev.ndim < msgs.ndim:
                ev = ev[..., None]
        if combine is not None:
            msgs = combine(msgs, ev)
        elif ev is not None:
            msgs = msgs * ev
        ident = jnp.asarray(REDUCE_IDENTITY[reduce], msgs.dtype)
        mask = (bg.edge_mask if msgs.ndim == bg.edge_mask.ndim
                else bg.edge_mask[..., None])
        msgs = jnp.where(mask, msgs, ident)
    # Scatter into the (disjoint) per-block destination windows.
    with jax.named_scope("tocab.reduce"):
        dst_global = bg.window_idx + bg.window_lo()[:, None]
        dst_global = jnp.where(bg.edge_mask, dst_global, bg.n)
        tail = msgs.shape[2:]
        out = segment_reduce(
            msgs.reshape((-1,) + tail),
            dst_global.reshape(-1),
            bg.n + 1,
            reduce,
        )
        return out[:-1]


def tocab_push(
    bg: BlockedGraph,
    values: jnp.ndarray,
    reduce: str = "sum",
    combine: Optional[Callable] = None,
    schedule: str = "uniform",
    impl: str = "slab",
    epilogue=None,
):
    """Push (Alg. 5): block by destination range; contributions of the few
    distinct sources of a block are fetched *once* through ``id_map``
    (block_contrib slab), then fanned out per edge; accumulation is confined
    to the block's destination window (conflict-free, no atomics on TPU).
    ``schedule``, ``impl`` and ``epilogue`` as in :func:`tocab_pull` — the
    fused push visits blocks in the balance module's bin-major order
    (disjoint destination windows keep that bit-identical)."""
    _check_engine(schedule, impl)
    if impl == "fused":
        from repro.kernels.tocab_fused import fused_push

        _record_engine("tocab_push_fused", "push")
        return fused_push(bg, values, reduce, combine, epilogue)
    out = _tocab_push_jit(bg, values, reduce=reduce, combine=combine,
                          schedule=schedule)
    return _slab_epilogue(out, reduce, epilogue)


# ====================================================================== #
# Dynamic per-edge values (GNN support): flat edge arrays → blocked slabs
# ====================================================================== #
def blocked_edge_values(bg: BlockedGraph, flat_vals: jnp.ndarray) -> jnp.ndarray:
    """Scatter flat per-edge values (original edge order) into the TOCAB
    blocked slab layout via ``edge_perm``.  Padded slots read 0."""
    return jnp.take(flat_vals, bg.edge_perm, axis=0, mode="fill", fill_value=0)


def tocab_edge_reduce(
    bg: BlockedGraph,
    flat_edge_vals: jnp.ndarray,  # (m, ...) in original edge order
    reduce: str = "sum",
    schedule: str = "uniform",
    impl: str = "slab",
    epilogue=None,
):
    """Reduce *edge* values to the compacted side (dst for pull layout)
    through the partial-slab + reduction machinery — the GNN primitive
    (edge messages → node aggregate) in TOCAB form.  ``schedule``, ``impl``
    and ``epilogue`` as in :func:`tocab_pull`."""
    _check_engine(schedule, impl)
    if impl == "fused":
        from repro.kernels.tocab_fused import fused_edge_reduce

        _record_engine("tocab_edge_reduce_fused", bg.direction)
        return fused_edge_reduce(bg, flat_edge_vals, reduce, epilogue)
    if schedule == "balanced":
        from .balance import balanced_edge_reduce

        out = balanced_edge_reduce(bg, flat_edge_vals, reduce)
    else:
        with jax.named_scope("tocab.gather"):
            vals = blocked_edge_values(bg, flat_edge_vals)
            ident = jnp.asarray(REDUCE_IDENTITY[reduce], vals.dtype)
            mask = bg.edge_mask
            while mask.ndim < vals.ndim:
                mask = mask[..., None]
            vals = jnp.where(mask, vals, ident)
        out = reduce_partials(bg, _block_partials(bg, vals, reduce), reduce)
    return _slab_epilogue(out, reduce, epilogue)


def tocab_gather_src(bg: BlockedGraph, values: jnp.ndarray) -> jnp.ndarray:
    """Per-edge gather of source-side values in *original edge order* —
    window-confined reads, then permuted back via edge_perm's inverse.
    Used by GNN layers that need explicit per-edge messages."""
    assert bg.direction == "pull"
    src_global = bg.window_idx + bg.window_lo()[:, None]
    gathered = jnp.take(values, src_global, axis=0)  # (nb, eb, ...)
    tail = gathered.shape[2:]
    flat = jnp.zeros((bg.m + 1,) + tail, gathered.dtype)
    flat = flat.at[bg.edge_perm.reshape(-1)].set(
        gathered.reshape((-1,) + tail)
    )
    return flat[: bg.m]
