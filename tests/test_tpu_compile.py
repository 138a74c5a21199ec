"""The TOCAB Pallas kernels compile for a TPU v5e (``interpret=False``).

Interpret mode cannot see what Mosaic refuses — block shapes off the
(8, 128) tiling, vector-indexed gathers, VMEM overflow — so each kernel is
lowered and compiled by the installed TPU compiler for a *described*
``v5e:2x2`` topology at the benchmark suite's ``rmat14`` shapes (the
``benchmarks.common`` suite graph, ``BLOCK_SIZE`` 2048).  The slab pull is
compiled the same way at a GAP kron scale-21 layout, to see what the
compiler adds around its scatters.  Nothing runs and no chip is needed.  The topology is described inside a fixture: only the
worker that runs these tests loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import build_blocked, rmat_graph
from repro.core.partition import BlockedGraph
from repro.core.tocab import _tocab_pull_jit
from repro.kernels.common import LANE
from repro.kernels.tocab_fused.kernel import (
    fused_pull_pallas, fused_push_pallas)
from repro.kernels.tocab_spmm.kernel import tocab_spmm_pallas

SUITE_BLOCK_SIZE = 2048


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one — keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def rmat14():
    g = rmat_graph(14, 8, seed=1, weights=True)
    return {d: build_blocked(g, block_size=SUITE_BLOCK_SIZE, direction=d)
            for d in ("pull", "push")}


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _slabs(sharding, bg):
    i32 = jnp.int32
    nb, eb, lb = bg.num_blocks, bg.edge_budget, bg.local_budget
    return dict(
        widx=_spec(sharding, (nb, eb), i32), cidx=_spec(sharding, (nb, eb), i32),
        ev=_spec(sharding, (nb, eb)), per_block=_spec(sharding, (nb,), i32),
        id_map=_spec(sharding, (nb, lb), i32), eps=_spec(sharding, (1, 2)))


def _compile(fn, *args, **static):
    compiled = fn.lower(*args, **static, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("weighted", [True, False], ids=["spmv", "pagerank"])
def test_fused_pull_compiles_for_v5e(one_chip, rmat14, weighted):
    bg = rmat14["pull"]
    s = _slabs(one_chip, bg)
    values = _spec(one_chip, (bg.num_blocks * bg.block_size, LANE))
    _compile(fused_pull_pallas, values, s["widx"], s["cidx"],
             s["ev"] if weighted else None, s["per_block"], s["per_block"],
             s["id_map"], s["per_block"], s["eps"],
             block_size=bg.block_size, local_budget=bg.local_budget,
             tile_rows=bg.n, weighted=weighted, fuse_epilogue=not weighted)


@pytest.mark.parametrize("reduce", ["sum", "min"])
def test_fused_push_compiles_for_v5e(one_chip, rmat14, reduce):
    bg = rmat14["push"]
    s = _slabs(one_chip, bg)
    values = _spec(one_chip, (bg.n, LANE))
    _compile(fused_push_pallas, values, s["widx"], s["cidx"], s["ev"],
             s["per_block"], s["id_map"], s["per_block"], s["eps"],
             block_size=bg.block_size, local_budget=bg.local_budget,
             reduce=reduce)


@pytest.mark.parametrize("mode", ["onehot", "scatter"])
def test_tocab_spmm_compiles_for_v5e(one_chip, rmat14, mode):
    bg = rmat14["pull"]
    s = _slabs(one_chip, bg)
    values = _spec(one_chip, (bg.num_blocks * bg.block_size, LANE))
    _compile(tocab_spmm_pallas, values, s["widx"], s["cidx"], s["ev"],
             block_size=bg.block_size, local_budget=bg.local_budget,
             chunk=256, mode=mode)


def test_fused_pull_too_big_for_vmem_raises(one_chip):
    """A graph whose resident tile cannot fit VMEM fails before the kernel
    is built, with the reason — it never reaches the compiler."""
    nb, bs, eb, lb, n = 512, 8192, 1024, 1024, 1 << 22
    i32 = jnp.int32
    with pytest.raises(ValueError, match="VMEM"):
        fused_pull_pallas.lower(
            _spec(one_chip, (nb * bs, LANE)), _spec(one_chip, (nb, eb), i32),
            _spec(one_chip, (nb, eb), i32), _spec(one_chip, (nb, eb)),
            _spec(one_chip, (nb,), i32), _spec(one_chip, (nb,), i32),
            _spec(one_chip, (nb, lb), i32), _spec(one_chip, (nb,), i32),
            _spec(one_chip, (1, 2)), block_size=bs, local_budget=lb,
            tile_rows=n, interpret=False)


def test_fused_push_too_big_for_smem_raises(one_chip):
    """The same for SMEM: a block's id_map row that cannot be held there
    (the v5e compiler has 1 MiB) is refused at the entry point."""
    nb, bs, eb, lb, n = 16, 2048, 4096, 131072, 32768
    i32 = jnp.int32
    with pytest.raises(ValueError, match="SMEM"):
        fused_push_pallas.lower(
            _spec(one_chip, (n, LANE)), _spec(one_chip, (nb, eb), i32),
            _spec(one_chip, (nb, eb), i32), _spec(one_chip, (nb, eb)),
            _spec(one_chip, (nb,), i32), _spec(one_chip, (nb, lb), i32),
            _spec(one_chip, (nb,), i32), _spec(one_chip, (1, 2)),
            block_size=bs, local_budget=lb, interpret=False)


def test_tocab_spmm_chunk_too_big_for_smem_raises(one_chip):
    """A ``chunk`` whose double-buffered SMEM index rows (window and edge
    value, plus compact id when scattering) exceed SMEM is refused at the
    entry point, not inside the compiler."""
    nb, bs, eb, lb = 4, 2048, 65536, 1024
    i32 = jnp.int32
    with pytest.raises(ValueError, match="SMEM"):
        tocab_spmm_pallas.lower(
            _spec(one_chip, (nb * bs, LANE)), _spec(one_chip, (nb, eb), i32),
            _spec(one_chip, (nb, eb), i32), _spec(one_chip, (nb, eb)),
            block_size=bs, local_budget=lb, chunk=eb, mode="scatter",
            interpret=False)


def test_slab_pull_phase2_has_no_presort(one_chip):
    """Above a size threshold (between 38.6M and 89.2M updates) the TPU
    compiler sorts a scatter's keys before it scatters, unless they are
    declared sorted.  The slab pull at a GAP kron scale-21 layout (256
    blocks of 348,544 slots, 89.2M phase-2 updates) compiles with no sort:
    phase 2 declares its keys sorted, and phase 3 (38.6M partials into
    2.1M vertices) lies below the threshold.  Abstract shapes: nothing is
    allocated."""
    nb, eb, lb, bs = 256, 348544, 150784, 8192
    n = nb * bs
    i32 = jnp.int32
    slab = _spec(one_chip, (nb, eb), i32)
    per_block = _spec(one_chip, (nb,), i32)
    bg = BlockedGraph(
        n=n, m=63538550, direction="pull", block_size=bs, num_blocks=nb,
        edge_budget=eb, local_budget=lb, window_idx=slab, compact_idx=slab,
        edge_mask=_spec(one_chip, (nb, eb), jnp.bool_),
        id_map=_spec(one_chip, (nb, lb), i32), n_local=per_block,
        n_edges=per_block, edge_perm=slab, n_window=per_block)
    text = _tocab_pull_jit.lower(bg, _spec(one_chip, (n,))).compile().as_text()
    assert text.count("scatter(") == 2
    assert "sort(" not in text
