"""CSR graph container and builders.

The host-side ``Graph`` (numpy) is the preprocessing-time representation: TOCAB
is a *static* blocking scheme, so partitioning happens on the host before any
device computation, exactly as in the paper.  ``DeviceGraph`` is the flat
edge-centric (COO + CSR) representation shipped to the device for the
*baseline* (non-blocked) engines; the blocked representation lives in
:mod:`repro.core.partition`.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

__all__ = [
    "Graph",
    "DeviceGraph",
    "GraphValidationError",
    "from_edges",
    "validate_graph",
    "graph_fingerprint",
    "is_symmetric",
    "rmat_graph",
    "uniform_random_graph",
    "grid_graph",
    "to_networkx",
]

#: cap on how many colidx entries the fingerprint hashes (strided sample)
_FP_SAMPLE = 4096

#: cap on how many colidx entries level="cheap" bounds-checks (strided sample)
_VALIDATE_SAMPLE = 65536

_INT32_MAX = np.iinfo(np.int32).max


class GraphValidationError(ValueError):
    """A CSR structural invariant does not hold.

    ``check`` names the violated invariant (stable identifier, e.g.
    ``"rowptr_monotone"``), ``detail`` is a human-readable description.
    Structured so callers (tests, ingestion pipelines) can branch on the
    failure class without parsing messages."""

    def __init__(self, check: str, detail: str):
        super().__init__(f"{check}: {detail}")
        self.check = check
        self.detail = detail


def validate_graph(g: "Graph", level: str = "cheap") -> "Graph":
    """Check CSR invariants, raising :class:`GraphValidationError`.

    ``level="cheap"`` is O(n) + an O(sample) colidx bounds check: rowptr
    shape/endpoints/monotonicity, strided colidx sample in ``[0, n)``,
    edge-value length, and int32 addressability (the device engines index
    with int32).  ``level="full"`` additionally bounds-checks every colidx
    entry.  Returns ``g`` unchanged on success so calls can be chained."""
    if level not in ("cheap", "full"):
        raise ValueError(f"unknown validation level {level!r}")
    n, rowptr, colidx = g.n, np.asarray(g.rowptr), np.asarray(g.colidx)
    m = int(colidx.shape[0])
    if n < 0:
        raise GraphValidationError("n_negative", f"n={n} < 0")
    if n > _INT32_MAX or m > _INT32_MAX:
        raise GraphValidationError(
            "budget_overflow",
            f"n={n}, m={m} exceed int32 addressing used by device engines")
    if rowptr.ndim != 1 or rowptr.shape[0] != n + 1:
        raise GraphValidationError(
            "rowptr_shape",
            f"rowptr has shape {rowptr.shape}, expected ({n + 1},)")
    if m and not np.issubdtype(rowptr.dtype, np.integer):
        raise GraphValidationError(
            "rowptr_dtype", f"rowptr dtype {rowptr.dtype} is not integral")
    if int(rowptr[0]) != 0:
        raise GraphValidationError(
            "rowptr_origin", f"rowptr[0]={int(rowptr[0])}, expected 0")
    if int(rowptr[-1]) != m:
        raise GraphValidationError(
            "rowptr_total",
            f"rowptr[-1]={int(rowptr[-1])} != m={m} (len(colidx))")
    if n and np.any(np.diff(rowptr) < 0):
        bad = int(np.argmax(np.diff(rowptr) < 0))
        raise GraphValidationError(
            "rowptr_monotone",
            f"rowptr decreases at row {bad} "
            f"({int(rowptr[bad])} -> {int(rowptr[bad + 1])})")
    if g.vals is not None and np.asarray(g.vals).shape[0] != m:
        raise GraphValidationError(
            "vals_length",
            f"vals has {np.asarray(g.vals).shape[0]} entries, expected m={m}")
    if m:
        sample = colidx
        if level == "cheap" and m > _VALIDATE_SAMPLE:
            sample = colidx[:: max(1, m // _VALIDATE_SAMPLE)]
        lo, hi = int(sample.min()), int(sample.max())
        if lo < 0 or hi >= n:
            raise GraphValidationError(
                "colidx_range",
                f"colidx entries span [{lo}, {hi}], expected [0, {n})")
    return g


def _fingerprint_arrays(n: int, m: int, out_degree, colidx) -> str:
    """Canonical structural fingerprint used as the tuning-db key.

    Hashes (n, m, the full out-degree sequence, a strided colidx sample) —
    identical for a host :class:`Graph` and the :class:`DeviceGraph` built
    from it, independent of edge weights (plans key dtype separately), and
    stable across processes (no Python ``hash`` randomization)."""
    import hashlib

    h = hashlib.sha256()
    h.update(f"repro.graph/v1:{n}:{m}:".encode())
    h.update(np.ascontiguousarray(out_degree, dtype=np.int64).tobytes())
    colidx = np.ascontiguousarray(colidx, dtype=np.int32)
    stride = max(1, colidx.shape[0] // _FP_SAMPLE)
    h.update(colidx[::stride].tobytes())
    return h.hexdigest()[:16]


def graph_fingerprint(g) -> str:
    """Fingerprint of a :class:`Graph` or :class:`DeviceGraph` (see
    :func:`_fingerprint_arrays`).  DeviceGraphs built via ``from_host``
    carry it precomputed; hand-built ones are hashed on the fly."""
    fp = getattr(g, "fingerprint", None)
    if isinstance(fp, str):
        return fp
    if isinstance(g, Graph):
        return _fingerprint_arrays(g.n, g.m, g.out_degree, g.colidx)
    return _fingerprint_arrays(
        g.n, g.m, np.asarray(g.out_degree), np.asarray(g.dst))


@dataclasses.dataclass(frozen=True)
class Graph:
    """Host-side CSR graph (out-edges).  ``vals`` optional per-edge weights."""

    n: int
    rowptr: np.ndarray  # int64[n+1]
    colidx: np.ndarray  # int32[m]
    vals: Optional[np.ndarray] = None  # float32[m]

    @property
    def m(self) -> int:
        return int(self.colidx.shape[0])

    @property
    def out_degree(self) -> np.ndarray:
        return np.diff(self.rowptr).astype(np.int32)

    @property
    def in_degree(self) -> np.ndarray:
        return np.bincount(self.colidx, minlength=self.n).astype(np.int32)

    def edges(self) -> tuple[np.ndarray, np.ndarray]:
        """COO view: (src, dst) arrays, src-sorted."""
        src = np.repeat(np.arange(self.n, dtype=np.int32), self.out_degree)
        return src, self.colidx.astype(np.int32)

    def transpose(self) -> "Graph":
        """Gᵀ — used to derive pull (in-edge) iteration and push blocking."""
        src, dst = self.edges()
        return from_edges(self.n, dst, src, vals=self.vals)

    def average_degree(self) -> float:
        return self.m / max(self.n, 1)

    def degree_histogram(self, bounds=(8, 16, 32)) -> dict:
        """Degree distribution buckets — reproduces paper Table 1."""
        deg = self.out_degree
        hist, lo = {}, 0
        for b in bounds:
            hist[f"{lo}~{b - 1}"] = float(np.mean((deg >= lo) & (deg < b)))
            lo = b
        hist[f"{lo}~"] = float(np.mean(deg >= lo))
        return hist

    def validate(self, level: str = "cheap") -> "Graph":
        """Check CSR invariants (see :func:`validate_graph`)."""
        return validate_graph(self, level=level)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DeviceGraph:
    """Flat edge-centric device representation for the baseline engines."""

    n: int = dataclasses.field(metadata=dict(static=True))
    src: jnp.ndarray  # int32[m]  (src-sorted)
    dst: jnp.ndarray  # int32[m]
    rowptr: jnp.ndarray  # int32[n+1]
    out_degree: jnp.ndarray  # int32[n]
    in_degree: jnp.ndarray  # int32[n]
    vals: Optional[jnp.ndarray] = None
    # structural fingerprint (tuning-db key); static → usable at trace time
    fingerprint: Optional[str] = dataclasses.field(
        default=None, metadata=dict(static=True))
    # every arc (u, v) has as many reverse arcs (v, u), so row v of the CSR
    # lists v's in-neighbours (see :func:`is_symmetric`); static, so the
    # traversals choose their pull at trace time.  Hand-built graphs: False.
    symmetric: bool = dataclasses.field(
        default=False, metadata=dict(static=True))

    @property
    def m(self) -> int:
        return int(self.src.shape[0])

    @classmethod
    def from_host(cls, g: Graph) -> "DeviceGraph":
        """Copy ``g`` to the device; the copies are the ``obs`` span
        ``device_graph.place``, then the host's exact test of
        :func:`is_symmetric` the span ``device_graph.symmetric``."""
        src, dst = g.edges()
        out_degree, in_degree = g.out_degree, g.in_degree
        with obs.span("device_graph.place"):
            arrays = dict(
                src=jnp.asarray(src, jnp.int32),
                dst=jnp.asarray(dst, jnp.int32),
                rowptr=jnp.asarray(g.rowptr, jnp.int32),
                out_degree=jnp.asarray(out_degree, jnp.int32),
                in_degree=jnp.asarray(in_degree, jnp.int32),
                vals=None if g.vals is None else jnp.asarray(g.vals, jnp.float32),
            )
        with obs.span("device_graph.symmetric"):
            symmetric = is_symmetric(g.n, src, dst, out_degree, in_degree)
        return cls(n=g.n, fingerprint=graph_fingerprint(g),
                   symmetric=symmetric, **arrays)


def is_symmetric(n: int, src: np.ndarray, dst: np.ndarray,
                 out_degree: np.ndarray, in_degree: np.ndarray) -> bool:
    """Whether every arc (u, v) of the arc list ``src``/``dst`` over ``n``
    vertices has as many reverse arcs (v, u), exactly.

    Unequal in- and out-degrees answer False at once.  Otherwise one sort
    of the arcs that are not self-loops, keyed by their unordered pair and
    then their direction, puts each pair's forward arcs just before its
    backward ones: the graph is symmetric iff the forward arcs and the
    backward arcs, each read in that order, list the same pairs.  Where no
    pair repeats an arc, that is each even position holding a forward arc
    followed by its reverse, checked first."""
    if not np.array_equal(out_degree, in_degree):
        return False
    loop = src == dst
    if loop.any():
        src, dst = src[~loop], dst[~loop]
    # (lo·n + hi)·2 + direction < 2·n² < 2^63 for n < 2^31
    key = np.minimum(src, dst).astype(np.int64)
    key *= n
    key += np.maximum(src, dst)
    key <<= 1
    key += src > dst
    key.sort()
    # each even position a forward arc, followed by its reverse
    if np.array_equal(key[0::2], key[1::2] ^ 1):
        return True
    backward = (key & 1).astype(bool)
    key >>= 1
    return bool(np.array_equal(key[~backward], key[backward]))


def from_edges(
    n: int,
    src: np.ndarray,
    dst: np.ndarray,
    vals: Optional[np.ndarray] = None,
    dedup: bool = False,
    validate: Optional[str] = None,
) -> Graph:
    """Build a CSR :class:`Graph` from COO edges.

    ``validate="cheap"`` / ``"full"`` runs :func:`validate_graph` on the
    result (and raises :class:`GraphValidationError` on malformed COO input
    instead of an assertion)."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    if src.shape != dst.shape:
        raise GraphValidationError(
            "coo_shape", f"src shape {src.shape} != dst shape {dst.shape}")
    if src.size and validate is not None:
        lo = min(int(src.min()), int(dst.min()))
        hi = max(int(src.max()), int(dst.max()))
        if lo < 0 or hi >= n:
            raise GraphValidationError(
                "coo_range",
                f"edge endpoints span [{lo}, {hi}], expected [0, {n})")
    if src.size:
        assert src.min() >= 0 and src.max() < n, "src out of range"
        assert dst.min() >= 0 and dst.max() < n, "dst out of range"
    if dedup and src.size:
        key = src * n + dst
        _, idx = np.unique(key, return_index=True)
        src, dst = src[idx], dst[idx]
        vals = None if vals is None else np.asarray(vals)[idx]
    order = np.argsort(src, kind="stable")
    src, dst = src[order], dst[order]
    if vals is not None:
        vals = np.asarray(vals, dtype=np.float32)[order]
    rowptr = np.zeros(n + 1, dtype=np.int64)
    np.add.at(rowptr, src + 1, 1)
    rowptr = np.cumsum(rowptr)
    g = Graph(n=n, rowptr=rowptr, colidx=dst.astype(np.int32), vals=vals)
    return g if validate is None else validate_graph(g, level=validate)


def rmat_graph(
    scale: int,
    edge_factor: int = 16,
    a: float = 0.57,
    b: float = 0.19,
    c: float = 0.19,
    seed: int = 0,
    undirected: bool = False,
    weights: bool = False,
) -> Graph:
    """R-MAT/Kronecker power-law generator (Graph500-style) — scale-free graphs
    like the paper's Kron21/Twitter suite."""
    rng = np.random.default_rng(seed)
    n = 1 << scale
    m = n * edge_factor
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    for lvl in range(scale):
        r = rng.random(m)
        # quadrant probabilities (a, b, c, d)
        go_right = r >= a + c  # dst high bit
        go_down = ((r >= a) & (r < a + c)) | (r >= a + b + c)  # src high bit
        src |= go_down.astype(np.int64) << lvl
        dst |= go_right.astype(np.int64) << lvl
    # permute vertex ids to kill the locality R-MAT bakes in (paper targets
    # graphs with *poor* layouts)
    perm = rng.permutation(n)
    src, dst = perm[src], perm[dst]
    keep = src != dst
    src, dst = src[keep], dst[keep]
    if undirected:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    vals = rng.random(src.shape[0], dtype=np.float32) if weights else None
    return from_edges(n, src, dst, vals=vals, dedup=True)


def uniform_random_graph(
    n: int, m: int, seed: int = 0, weights: bool = False
) -> Graph:
    """Erdős–Rényi-ish uniform random digraph."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    keep = src != dst
    vals = rng.random(int(keep.sum()), dtype=np.float32) if weights else None
    return from_edges(n, src[keep], dst[keep], vals=vals, dedup=True)


def grid_graph(rows: int, cols: int) -> Graph:
    """2D grid digraph (right+down edges) — a *good-locality* graph, the
    Hollywood-analogue control for the paper's claim that GraphCage causes
    only trivial slowdown on graphs that already have good layouts."""
    n = rows * cols
    ids = np.arange(n).reshape(rows, cols)
    src = np.concatenate([ids[:, :-1].ravel(), ids[:-1, :].ravel()])
    dst = np.concatenate([ids[:, 1:].ravel(), ids[1:, :].ravel()])
    return from_edges(n, src, dst)


def to_networkx(g: Graph):
    import networkx as nx

    G = nx.DiGraph()
    G.add_nodes_from(range(g.n))
    src, dst = g.edges()
    if g.vals is not None:
        G.add_weighted_edges_from(zip(src.tolist(), dst.tolist(), g.vals.tolist()))
    else:
        G.add_edges_from(zip(src.tolist(), dst.tolist()))
    return G
