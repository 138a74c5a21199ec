"""Public fused-TOCAB entry points: backend pick, padding, telemetry.

``fused_pull`` / ``fused_push`` / ``fused_edge_reduce`` are what
``repro.core.tocab``'s ``impl="fused"`` dispatches to.  Two backends:

* ``"pallas"`` — the persistent kernels in :mod:`.kernel` (compiled on
  TPU; ``interpret=True`` elsewhere, for validation only — interpret mode
  pads features to the 128 lane width, pure overhead off-TPU);
* ``"jax"`` — the scan-over-blocks path in :mod:`.ref`, the default off
  TPU: same fused dataflow (output is the scan carry, no partial slab),
  no lane padding.

Both are bit-identical to the slab engines (tests/test_fused.py).  Each
call records what fusion removed: ``tocab.fused_blocks`` counts blocks run
through the fused path and ``tocab.partial_hbm_bytes_saved`` the partial /
``block_contrib`` slab bytes that never touched HBM.
"""
from __future__ import annotations

import math
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.partition import BlockedGraph
from repro.kernels.common import LANE, roundup
from repro.obs.metrics import registry as _obs

from .kernel import fused_pull_pallas, fused_push_pallas
from .ref import fused_edge_reduce_ref, fused_pull_ref, fused_push_ref

__all__ = ["fused_pull", "fused_push", "fused_edge_reduce",
           "default_backend", "LANE"]


def default_backend() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "jax"


def _record_fused(bg: BlockedGraph, engine: str, tail: Tuple[int, ...],
                  itemsize: int):
    """Trace-time telemetry (static shapes — free at runtime)."""
    _obs.counter(
        "tocab.fused_blocks", "cache blocks run through the fused path"
    ).inc(bg.num_blocks, engine=engine, direction=bg.direction)
    saved = bg.num_blocks * bg.local_budget * itemsize
    saved *= math.prod(tail) if tail else 1
    _obs.counter(
        "tocab.partial_hbm_bytes_saved",
        "partial/contrib slab bytes the fused path never materializes",
    ).inc(saved, engine=engine, direction=bg.direction)


def _pallas_edges(bg: BlockedGraph, combine):
    """Edge-value slab + weighted flag + effective combine for the kernels
    (which visit only real edge slots, so no mask is needed)."""
    from repro.core.balance import UNWEIGHTED

    if combine is UNWEIGHTED:
        return None, False, None
    return bg.edge_vals, bg.edge_vals is not None, combine


def _visit_order(bg: BlockedGraph, block_order) -> jnp.ndarray:
    order = range(bg.num_blocks) if block_order is None else block_order
    return jnp.asarray([int(b) for b in order], jnp.int32)


def _interpret(interpret: Optional[bool]) -> bool:
    return interpret if interpret is not None else \
        jax.default_backend() != "tpu"


def _epilogue_arr(epilogue) -> Tuple[jnp.ndarray, bool]:
    if epilogue is None:
        return jnp.asarray([[1.0, 0.0]], jnp.float32), False
    mul, add = epilogue
    eps = jnp.stack([jnp.asarray(mul, jnp.float32).reshape(()),
                     jnp.asarray(add, jnp.float32).reshape(())])
    return eps[None, :], True


def _check_epilogue(reduce: str, epilogue):
    if epilogue is not None and reduce != "sum":
        raise ValueError(
            f"epilogue fusion is affine (out*mul+add) — only the sum "
            f"semiring supports it, got reduce={reduce!r}")


def fused_pull(
    bg: BlockedGraph,
    values: jnp.ndarray,
    reduce: str = "sum",
    combine: Optional[Callable] = None,
    epilogue: Optional[Tuple] = None,
    backend: Optional[str] = None,
    interpret: Optional[bool] = None,
    block_order: Optional[Sequence[int]] = None,
    tile_rows: Optional[int] = None,
    chunk: int = 2048,
):
    """out[dst] = ⊕ values[src] (⊗ edge_val), partials never leaving fast
    memory; optional affine epilogue ``out*mul + add`` fused in."""
    assert bg.direction == "pull"
    _check_epilogue(reduce, epilogue)
    backend = backend or default_backend()
    _record_fused(bg, "fused_pull", values.shape[1:],
                  jnp.dtype(values.dtype).itemsize)
    if backend == "jax":
        return fused_pull_ref(bg, values, reduce, combine, epilogue,
                              block_order)
    if backend != "pallas":
        raise ValueError(f"unknown fused backend {backend!r}")
    if values.ndim > 2:
        raise NotImplementedError(
            "pallas fused pull supports (n,) or (n, d) values")
    squeeze = values.ndim == 1
    x = values[:, None] if squeeze else values
    n, d = x.shape
    d_pad = roundup(d, LANE)
    rows_pad = bg.num_blocks * bg.block_size
    vals = jnp.zeros((rows_pad, d_pad), jnp.float32)
    vals = vals.at[:n, :d].set(x.astype(jnp.float32))
    ev, weighted, combine = _pallas_edges(bg, combine)
    eps, fuse_eps = _epilogue_arr(epilogue)
    out = fused_pull_pallas(
        vals, bg.window_idx, bg.compact_idx, ev, bg.n_edges, bg.n_local,
        bg.id_map, _visit_order(bg, block_order), eps,
        block_size=bg.block_size, local_budget=bg.local_budget,
        tile_rows=tile_rows or roundup(bg.n, 8), num_tiles=1, chunk=chunk,
        reduce=reduce, combine=combine, weighted=weighted,
        fuse_epilogue=fuse_eps, interpret=_interpret(interpret))
    out = out[: bg.n, :d]
    return out[:, 0] if squeeze else out


def fused_push(
    bg: BlockedGraph,
    values: jnp.ndarray,
    reduce: str = "sum",
    combine: Optional[Callable] = None,
    epilogue: Optional[Tuple] = None,
    backend: Optional[str] = None,
    interpret: Optional[bool] = None,
    block_order: Optional[Sequence[int]] = None,
    chunk: int = 2048,
):
    """Push with the ``block_contrib`` gather kept in fast memory.  Blocks
    own disjoint destination windows, so any ``block_order`` (the balance
    module's bin-major one included) is bit-identical."""
    assert bg.direction == "push"
    _check_epilogue(reduce, epilogue)
    backend = backend or default_backend()
    _record_fused(bg, "fused_push", values.shape[1:],
                  jnp.dtype(values.dtype).itemsize)
    if block_order is None and bg.schedule is not None:
        from repro.core.balance import fused_block_order

        block_order = fused_block_order(bg)
    if backend == "jax":
        return fused_push_ref(bg, values, reduce, combine, epilogue,
                              block_order)
    if backend != "pallas":
        raise ValueError(f"unknown fused backend {backend!r}")
    if values.ndim > 2:
        raise NotImplementedError(
            "pallas fused push supports (n,) or (n, d) values")
    squeeze = values.ndim == 1
    x = values[:, None] if squeeze else values
    n, d = x.shape
    d_pad = roundup(d, LANE)
    vals = jnp.zeros((roundup(n, 8), d_pad), jnp.float32)
    vals = vals.at[:n, :d].set(x.astype(jnp.float32))
    ev, weighted, combine = _pallas_edges(bg, combine)
    eps, fuse_eps = _epilogue_arr(epilogue)
    out = fused_push_pallas(
        vals, bg.window_idx, bg.compact_idx, ev, bg.n_edges, bg.id_map,
        _visit_order(bg, block_order), eps,
        block_size=bg.block_size, local_budget=bg.local_budget, chunk=chunk,
        reduce=reduce, combine=combine, weighted=weighted,
        fuse_epilogue=fuse_eps, interpret=_interpret(interpret))
    out = out[: bg.n, :d]
    return out[:, 0] if squeeze else out


def fused_edge_reduce(
    bg: BlockedGraph,
    flat_edge_vals: jnp.ndarray,
    reduce: str = "sum",
    epilogue: Optional[Tuple] = None,
    backend: Optional[str] = None,
):
    """Edge-value → compacted-side aggregate, no partial slab.  The scan
    path serves both backends — messages come from the blocked edge-value
    slab, not a value window, so there is no gather to confine."""
    _check_epilogue(reduce, epilogue)
    del backend  # single implementation today; kept for API symmetry
    _record_fused(bg, "fused_edge_reduce", flat_edge_vals.shape[1:],
                  jnp.dtype(flat_edge_vals.dtype).itemsize)
    return fused_edge_reduce_ref(bg, flat_edge_vals, reduce, epilogue)
