"""GAP ``urand``: uniform random graph (Erdős–Rényi style).

Each of ``edge_factor << scale`` edges joins two vertices drawn uniformly
and independently, so degrees are near-Poisson and no vertex is a hub.
"""
from __future__ import annotations

import numpy as np

from bench.generators import chunked


def draw(params: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """Directed endpoint pairs (int32), before symmetrising and dedup."""
    n = 1 << int(params["scale"])

    def one(rng: np.random.Generator, count: int):
        return (rng.integers(0, n, count, dtype=np.int32),
                rng.integers(0, n, count, dtype=np.int32))

    return chunked(one, int(params["edge_factor"]) << int(params["scale"]),
                   seed)
