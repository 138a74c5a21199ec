"""Graphs shared by several test files."""
import numpy as np
import pytest

from repro.core import from_edges


@pytest.fixture(scope="session")
def edge_free_block_graph():
    """``(graph, block_size)``: 64 vertices in four blocks of 16, laid out
    so both directions hold the layout's corner cases.  Block 2 (vertices
    32-47) has no arc at all, so it is edge-free in the pull and in the
    push layout; two blocks reach 16 distinct vertices on the compacted
    side (0 and 1 in pull, 0 and 3 in push), so their ``n_local`` equals
    ``local_budget`` (16, a multiple of 8), and the last reaches 5.  Edge
    values come from a fixed seed."""
    i = np.arange(16)
    src = np.concatenate([i, i, 16 + i, 16 + i, 48 + i[:5]])
    dst = np.concatenate([48 + i, 48 + (i + 1) % 16, (3 * i) % 16,
                          (5 * i + 1) % 16, 16 + i[:5]])
    vals = np.random.default_rng(11).random(src.shape[0], dtype=np.float32)
    return from_edges(64, src, dst, vals=vals), 16
