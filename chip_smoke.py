#!/usr/bin/env python3
"""Chip smoke: the graph engine's main path on one TPU, checked against
independent host references.

    python chip_smoke.py [--seed 0]

Run from the root of a checkout (a plain copy of the files is enough) on a
machine with a TPU.  Without a TPU, or without the repository's sources
beside it, it exits non-zero before any phase and prints no result.

Phases (each prints one line: wall seconds, compile seconds where a second
call separates them, and what was checked):

1. graph    — a GAP ``kron`` graph (Graph500 R-MAT, A=.57 B=.19 C=.19,
              permuted ids, edge factor 16) at scale 22 on the host, then
              the flat ``DeviceGraph`` and the pull and push TOCAB layouts on
              the device.  A scale whose layouts would not fit half the
              device's memory is cut, and the cut is printed.
2. pagerank — ``gc-pull`` slab engine to tol 1e-6 vs a scipy CSR power
              iteration (same damping and dangling handling).
3. bfs      — 4 seeded sources vs a numpy frontier BFS; depths exact.
4. spmv     — ``gc-pull`` and ``gc-push`` vs scipy ``Aᵀ·x``.
5. pallas   — the compiled Pallas kernels at the benchmark suite's sizes
              (the fused kernels cannot hold a scale-22 graph in VMEM):
              fused pull / fused push on rmat14 and rmat16, fused PageRank
              on rmat14, and the balanced schedule's Pallas dense bin on
              rmat14 (every block forced dense) and on the balance-mix
              graph; each against the slab engine on the same chip.
6. checks   — peak device memory.

The last line of stdout is ``{"ok": true, "device": {...}}`` and is printed
only when every phase passed.  JAX's compilation cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, else to ``.jax_cache/`` in the checkout.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

SCALE = 22
EDGE_FACTOR = 16
DAMPING = 0.85
PR_TOL = 1e-6
#: device bytes per nominal edge (``EDGE_FACTOR << scale``) of the flat, pull
#: and push layouts at block size 8192.  Measured on a TPU v5e: scale 22 took
#: 5,239,332,352 B in use once placed, 78.1 B per nominal edge; rounded up.
BYTES_PER_EDGE = 80


def log(phase: str, **fields):
    items = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] {items}", flush=True)


def memory_stat(key: str) -> int:
    import jax

    return jax.devices()[0].memory_stats()[key]


def fits_scale(scale: int, bytes_limit: int) -> int:
    """Largest scale ≤ ``scale`` whose layouts fit half the device."""
    s = scale
    while s > 10 and (EDGE_FACTOR << s) * BYTES_PER_EDGE > bytes_limit // 2:
        s -= 1
    return s


def host_csr(g, weighted: bool):
    import numpy as np
    import scipy.sparse as sp

    data = (g.vals.astype(np.float64) if weighted
            else np.ones(g.m, np.float64))
    return sp.csr_matrix((data, g.colidx, g.rowptr), shape=(g.n, g.n))


def pagerank_ref(a, out_degree, engine_iters: int):
    """Power iteration in float64 with the engine's update.  Returns the rank
    after ``engine_iters`` iterations and the reference's own count."""
    import numpy as np

    n = a.shape[0]
    at = a.T.tocsr()
    dangling_v = out_degree == 0
    safe = np.maximum(out_degree, 1).astype(np.float64)
    rank = np.full(n, 1.0 / n)
    snap, conv, it = None, None, 0
    while it < 200 and (conv is None or snap is None):
        contrib = np.where(dangling_v, 0.0, rank / safe)
        dangling = rank[dangling_v].sum()
        new = (1 - DAMPING) / n + DAMPING * (at @ contrib + dangling / n)
        delta = np.abs(new - rank).sum()
        rank, it = new, it + 1
        if it == engine_iters:
            snap = rank
        if conv is None and delta <= PR_TOL:
            conv = it
    return snap, conv


def bfs_ref(g, source: int):
    """Frontier BFS over the host CSR; -1 marks unreached."""
    import numpy as np

    depth = np.full(g.n, -1, np.int64)
    depth[source] = 0
    frontier = np.array([source], np.int64)
    level = 0
    while frontier.size:
        lo, hi = g.rowptr[frontier], g.rowptr[frontier + 1]
        cnt = hi - lo
        idx = np.repeat(lo - np.cumsum(cnt) + cnt, cnt) + np.arange(cnt.sum())
        nbrs = g.colidx[idx]
        nbrs = np.unique(nbrs[depth[nbrs] < 0])
        level += 1
        depth[nbrs] = level
        frontier = nbrs
    return depth


def timed_twice(fn):
    """(result, first-call seconds, second-call seconds); the difference is
    the compile time of the jitted entry points ``fn`` calls."""
    import jax

    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    t1 = time.perf_counter()
    out = jax.block_until_ready(fn())
    t2 = time.perf_counter()
    return out, t1 - t0, t2 - t1


def check_close(what: str, got, want, rtol: float, atol: float) -> str:
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    assert got.shape == want.shape, f"{what}: shape {got.shape} != {want.shape}"
    assert np.isfinite(got).all(), f"{what}: non-finite values"
    err = np.abs(got - want)
    bad = err > atol + rtol * np.abs(want)
    msg = (f"max_abs_err={err.max():.3e} max_ref={np.abs(want).max():.3e} "
           f"rtol={rtol} atol={atol}")
    assert not bad.any(), f"{what}: {int(bad.sum())} values off; {msg}"
    return msg


def phase_graph(seed: int, bytes_limit: int):
    import jax
    from repro.core import DeviceGraph, build_blocked, rmat_graph

    fit = fits_scale(SCALE, bytes_limit)
    if fit != SCALE:
        log("graph", cut=f"scale {SCALE} -> {fit}",
            reason=f"~{(EDGE_FACTOR << SCALE) * BYTES_PER_EDGE / 1e9:.1f} GB "
                   f"of layouts > half of {bytes_limit / 1e9:.1f} GB")
    t0 = time.perf_counter()
    g = rmat_graph(fit, EDGE_FACTOR, seed=seed, weights=True)
    t_gen = time.perf_counter() - t0
    t0 = time.perf_counter()
    dg = DeviceGraph.from_host(g)
    bg = build_blocked(g, block_size=8192, direction="pull")
    bgp = build_blocked(g, block_size=8192, direction="push")
    jax.block_until_ready((dg, bg, bgp))
    t_build = time.perf_counter() - t0
    used = memory_stat("bytes_in_use")
    assert used <= bytes_limit // 2, (
        f"scale {fit}: layouts took {used} B, more than half of {bytes_limit} B;"
        f" BYTES_PER_EDGE={BYTES_PER_EDGE} underestimates them")
    log("graph", scale=fit, edge_factor=EDGE_FACTOR, n=g.n, m=g.m,
        generate_s=f"{t_gen:.1f}", build_and_place_s=f"{t_build:.1f}",
        blocks=bg.num_blocks, edge_budget=bg.edge_budget,
        device_bytes_in_use=used, check="ok")
    return g, dg, bg, bgp


def phase_pagerank(g, dg, bg):
    import numpy as np
    from repro.core import pagerank

    (rank, iters), first, second = timed_twice(lambda: pagerank(
        dg, bg, variant="gc-pull", tol=PR_TOL, schedule="uniform",
        impl="slab"))
    iters = int(iters)
    t0 = time.perf_counter()
    ref, ref_iters = pagerank_ref(host_csr(g, weighted=False), g.out_degree,
                                  iters)
    t_ref = time.perf_counter() - t0
    assert ref_iters is not None, "reference did not converge in 200 iters"
    assert abs(iters - ref_iters) <= 2, (
        f"pagerank: {iters} iterations vs reference {ref_iters} (allowed ±2)")
    msg = check_close("pagerank", rank, ref, rtol=1e-3, atol=1e-9)
    log("pagerank", wall_s=f"{second:.3f}", compile_s=f"{first - second:.1f}",
        iters=iters, ref_iters=ref_iters, rank_sum=f"{float(np.sum(rank)):.6f}",
        ref_s=f"{t_ref:.1f}", check=f"ok ({msg})")


def phase_bfs(g, dg, bg, seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import INF_DEPTH, bfs

    rng = np.random.default_rng(seed)
    sources = rng.choice(np.flatnonzero(g.out_degree > 0), 4, replace=False)
    walls, levels = [], []
    compile_s = None
    for s in sources:
        def run(s=s):
            return bfs(dg, bg, jnp.int32(int(s)), schedule="uniform",
                       impl="slab")

        if compile_s is None:  # the later sources reuse the compiled BFS
            (depth, lv, _, _), first, wall = timed_twice(run)
            compile_s = first - wall
        else:
            t0 = time.perf_counter()
            depth, lv, _, _ = jax.block_until_ready(run())
            wall = time.perf_counter() - t0
        walls.append(wall)
        ref = bfs_ref(g, int(s))
        got = np.asarray(depth).astype(np.int64)
        got[got >= INF_DEPTH] = -1
        wrong = int((got != ref).sum())
        assert wrong == 0, f"bfs from {s}: {wrong} depths differ"
        levels.append(int(lv))
    log("bfs", sources=[int(s) for s in sources], levels=levels,
        wall_s=",".join(f"{w:.3f}" for w in walls),
        compile_s=f"{compile_s:.1f}", check="ok (depths exact)")


def phase_spmv(g, dg, bg, bgp, seed: int):
    import jax.numpy as jnp
    import numpy as np
    from repro.core import spmv

    x = np.random.default_rng(seed + 1).random(g.n).astype(np.float32)
    ref = host_csr(g, weighted=True).T @ x.astype(np.float64)
    xd = jnp.asarray(x)
    for variant, b in (("gc-pull", bg), ("gc-push", bgp)):
        y, first, second = timed_twice(lambda: spmv(
            dg, b, xd, variant=variant, schedule="uniform", impl="slab"))
        msg = check_close(f"spmv {variant}", y, ref, rtol=1e-4, atol=1e-5)
        log("spmv", variant=variant, wall_s=f"{second:.4f}",
            compile_s=f"{first - second:.1f}", check=f"ok ({msg})")


def _assert_kernel(fn, x):
    """The call lowers to a compiled Pallas kernel, not interpret mode."""
    import jax

    text = jax.jit(fn).lower(x).as_text()
    assert "tpu_custom_call" in text, "no compiled Pallas kernel in lowering"


def phase_pallas(seed: int):
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.common import BLOCK_SIZE, SUITE, balance_mix_graph
    from repro.core import (DeviceGraph, build_blocked, pagerank, tocab_pull,
                            tocab_push)

    def run(label, fused_fn, slab_fn, x, exact=False):
        _assert_kernel(fused_fn, x)
        got, first, second = timed_twice(lambda: fused_fn(x))
        want = slab_fn(x)
        if exact:
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
            msg = "bit-identical"
        else:
            msg = check_close(label, got, want, rtol=1e-5, atol=1e-5)
        log("pallas", case=label, wall_s=f"{second:.4f}",
            compile_s=f"{first - second:.1f}", check=f"ok ({msg})")

    minplus = lambda v, w: v + w  # noqa: E731 — SSSP's semiring
    for name in ("rmat14", "rmat16"):
        g = SUITE[name]()
        pull = build_blocked(g, block_size=BLOCK_SIZE, direction="pull")
        push = build_blocked(g, block_size=BLOCK_SIZE, direction="push")
        x = jnp.asarray(np.random.default_rng(seed).random(g.n, np.float32))
        for label, bgx, fn in ((f"{name} fused pull", pull, tocab_pull),
                               (f"{name} fused push", push, tocab_push)):
            run(label,
                lambda v, b=bgx, f=fn: f(b, v, impl="fused"),
                lambda v, b=bgx, f=fn: f(b, v), x)
            run(label + " min-plus",
                lambda v, b=bgx, f=fn: f(b, v, reduce="min", combine=minplus,
                                         impl="fused"),
                lambda v, b=bgx, f=fn: f(b, v, reduce="min",
                                         combine=minplus), x, exact=True)
        if name != "rmat14":
            continue  # one fused PageRank; each slab PageRank compiles ~1 min
        dg = DeviceGraph.from_host(g)
        (r_f, it_f), first, second = timed_twice(lambda: pagerank(
            dg, pull, tol=PR_TOL, impl="fused"))
        r_s, it_s = pagerank(dg, pull, tol=PR_TOL, impl="slab")
        assert abs(int(it_f) - int(it_s)) <= 1, (int(it_f), int(it_s))
        msg = check_close(f"{name} fused pagerank", r_f, r_s, rtol=1e-5,
                          atol=1e-9)
        log("pallas", case=f"{name} fused pagerank", wall_s=f"{second:.4f}",
            compile_s=f"{first - second:.1f}", iters=int(it_f),
            check=f"ok ({msg})")

    # the balanced schedule's dense bin: rmat14 with every block forced
    # dense, and the balance-mix graph, whose dense bin is real
    for label, g, th in (
            ("rmat14 balanced pallas (all dense)", SUITE["rmat14"](),
             (0.0, 0.0)),
            ("balmix balanced pallas", balance_mix_graph(), None)):
        kw = {} if th is None else {"bin_thresholds": th}
        b = build_blocked(g, block_size=BLOCK_SIZE, direction="pull", **kw)
        assert b.schedule.blocks_per_bin[2] > 0, f"{label}: empty dense bin"
        x = jnp.asarray(np.random.default_rng(seed).random(g.n, np.float32))
        run(label,
            lambda v, b=b: tocab_pull(b, v, schedule="balanced",
                                      dense_impl="pallas"),
            lambda v, b=b: tocab_pull(b, v), x)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(SRC, "repro")):
        sys.exit(f"chip_smoke: the repository's sources are not at {SRC}")
    if os.environ.get("REPRO_CHAOS"):
        sys.exit("chip_smoke: REPRO_CHAOS is set; the smoke runs no fault "
                 "injection")
    sys.path.insert(0, SRC)
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU — jax.devices()[0].platform is "
                 f"{dev.platform!r}; this smoke runs only on the chip")
    from repro.compile_cache import use_compile_cache

    log("setup", device=dev.device_kind, count=len(jax.devices()),
        jax=jax.__version__, compile_cache=use_compile_cache())
    t0 = time.perf_counter()
    g, dg, bg, bgp = phase_graph(args.seed, memory_stat("bytes_limit"))
    phase_pagerank(g, dg, bg)
    phase_bfs(g, dg, bg, args.seed)
    phase_spmv(g, dg, bg, bgp, args.seed)
    del g, dg, bg, bgp
    phase_pallas(args.seed)

    assert "repro.launch.dryrun" not in sys.modules
    log("checks", peak_bytes_in_use=memory_stat("peak_bytes_in_use"),
        total_s=f"{time.perf_counter() - t0:.1f}", check="ok")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
