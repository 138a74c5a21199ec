"""The paper's own configuration: the GraphCage graph-algorithm suite.

Mirrors the evaluation setup of the paper (§4): PR / SpMV / BC over a suite
of scale-free graphs, TOCAB block size as the tunable (Fig. 11), plus the
cache-model parameters of the GTX 1080Ti the paper measured on.
"""
from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class GraphCageCfg:
    # graph suite (scaled-down, same generator family as Kron21/Twitter)
    scales: tuple = (14, 15, 16)
    edge_factor: int = 8
    # TOCAB
    block_size: int = 8192  # vertices per subgraph (Fig. 11 sweep default)
    fast_mem_bytes: int = 4 * 1024 * 1024  # TPU VMEM budget for the window
    # paper GPU cache model (Fig. 9/10)
    llc_bytes: int = int(2.75 * 1024 * 1024)
    line_bytes: int = 128
    ways: int = 16
    # algorithms
    pr_damping: float = 0.85
    pr_tol: float = 1e-6
    bfs_alpha: float = 15.0
    # autotuner (repro.tune) — the Fig. 11 sensitivity axes the search
    # sweeps around this config's defaults, and where the DB persists
    tune_block_sizes: tuple = (1024, 2048, 4096, 8192, 16384)
    tune_alphas: tuple = (4.0, 15.0, 64.0)
    tune_impls: tuple = ("slab", "fused")
    tune_db_dir: str = "experiments/tune"
    # resilience (repro.resilience) — retry budget for IO paths and the
    # per-candidate tuner wall-clock bound (None = unbounded)
    retry_attempts: int = 3
    retry_base_delay: float = 0.05
    trial_timeout_s: Optional[float] = None


DEFAULT = GraphCageCfg()
