"""Graph generators, one module per configuration's ``generator`` key.

Each module has ``draw(params, seed) -> (src, dst)``: int32 endpoint pairs
of ``1 << params["scale"]`` vertices, before the harness makes them
undirected and simple (``bench.graph``).
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

#: fixed number of draw chunks: the edges depend on it, so it never changes
CHUNKS = 64


def chunked(draw_chunk, count: int, seed: int):
    """Run ``draw_chunk(rng, size) -> (src, dst)`` over ``CHUNKS`` fixed
    pieces of ``count`` edges, each with its own stream spawned from
    ``seed``, on a few threads (NumPy's draws and ufuncs release the GIL),
    and concatenate the pieces in order."""
    streams = np.random.SeedSequence(seed).spawn(CHUNKS)
    bounds = np.linspace(0, count, CHUNKS + 1).astype(np.int64)
    src = np.empty(count, np.int32)
    dst = np.empty(count, np.int32)

    def fill(i: int):
        lo, hi = int(bounds[i]), int(bounds[i + 1])
        src[lo:hi], dst[lo:hi] = draw_chunk(np.random.default_rng(streams[i]),
                                            hi - lo)

    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        for f in [pool.submit(fill, i) for i in range(CHUNKS)]:
            f.result()
    return src, dst
