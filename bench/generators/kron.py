"""GAP ``kron``: the Graph500 Kronecker (R-MAT) generator.

Each of ``edge_factor << scale`` edges picks one quadrant of the adjacency
matrix per bit of its endpoints, with probabilities A, B, C and
D = 1 - A - B - C (Graph500 and GAP: A=.57, B=.19, C=.19).  Vertex ids are
then permuted, as GAP's generator does, so that id order carries no
locality.  Draws are float32 and made in fixed chunks, each with its own
stream, so the edges depend on the seed alone and not on the thread count.
"""
from __future__ import annotations

import numpy as np

from bench.generators import chunked


def draw(params: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """Directed endpoint pairs (int32), before symmetrising and dedup."""
    scale = int(params["scale"])
    a, b, c = (np.float32(params[k]) for k in ("a", "b", "c"))
    ab, abc = a + b, a + b + c

    def one(rng: np.random.Generator, count: int):
        src = np.zeros(count, np.int32)
        dst = np.zeros(count, np.int32)
        for lvl in range(scale):
            r = rng.random(count, dtype=np.float32)
            bit = np.int32(1 << lvl)
            src |= (r >= ab) * bit  # quadrant C or D: lower half
            dst |= (((r >= a) & (r < ab)) | (r >= abc)) * bit  # B or D
        return src, dst

    src, dst = chunked(one, int(params["edge_factor"]) << scale, seed)
    if params.get("permute_ids", False):
        rng = np.random.default_rng([seed, 1])
        perm = rng.permutation(1 << scale).astype(np.int32)
        src, dst = perm[src], perm[dst]
    return src, dst
