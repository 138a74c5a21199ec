"""Observability layer: registry determinism, span nesting + JSONL schema,
export fingerprints, report rendering/diffing, and the instrumentation
smoke test (the engines actually populate the expected series)."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import export, trace
from repro.obs.metrics import Registry, registry
from repro.obs.report import diff, render, render_diff


@pytest.fixture(autouse=True)
def _clean_trace():
    trace.clear()
    trace.set_sink(None)
    yield
    trace.clear()
    trace.set_sink(None)


# ------------------------------ metrics ------------------------------ #
def test_counter_gauge_deterministic_snapshot():
    r = Registry()
    for _ in range(3):
        r.counter("edges", "help text").inc(5, engine="pull")
    r.counter("edges").inc(2, engine="push")
    r.gauge("frontier").set(10, algo="bfs")
    r.gauge("frontier").set(7, algo="bfs")  # last write wins
    snap = r.snapshot()
    assert snap["edges"]["kind"] == "counter"
    assert snap["edges"]["help"] == "help text"
    assert snap["edges"]["series"] == [
        {"labels": {"engine": "pull"}, "value": 15.0},
        {"labels": {"engine": "push"}, "value": 2.0},
    ]
    assert snap["frontier"]["series"] == [
        {"labels": {"algo": "bfs"}, "value": 7.0}]
    # identical recording order-insensitivity: label order can't matter
    r2 = Registry()
    r2.counter("edges", "help text").inc(2, engine="push")
    r2.counter("edges").inc(15, engine="pull")
    assert r2.snapshot()["edges"] == snap["edges"]


def test_histogram_aggregation():
    r = Registry()
    h = r.histogram("lat", "latencies")
    for v in (0.5, 1.5, 3.0, 0.0):
        h.observe(v)
    s = h.stats()
    assert s["count"] == 4
    assert s["sum"] == pytest.approx(5.0)
    assert s["min"] == 0.0 and s["max"] == 3.0
    assert s["mean"] == pytest.approx(1.25)
    # log2 buckets: 0.5→2^-1, 1.5→2^1, 3.0→2^2, 0.0→"0"
    assert s["buckets"] == {"0": 1, "2^-1": 1, "2^1": 1, "2^2": 1}
    # snapshot is JSON-serializable and stable under a round-trip
    snap = r.snapshot()
    assert json.loads(json.dumps(snap)) == snap


def test_kind_collision_raises():
    r = Registry()
    r.counter("x")
    with pytest.raises(TypeError):
        r.gauge("x")


# ------------------------------- spans ------------------------------- #
def test_span_nesting_and_jsonl_roundtrip(tmp_path):
    sink = tmp_path / "trace.jsonl"
    trace.set_sink(str(sink))
    with trace.span("outer", phase="bench"):
        with trace.span("inner") as sp:
            sp.block(jnp.ones((4,)))
            sp.set(rows=4)
    evts = trace.events()
    assert [e["name"] for e in evts] == ["inner", "outer"]  # finish order
    inner, outer = evts
    assert inner["parent"] == "outer" and inner["depth"] == 1
    assert outer["parent"] is None and outer["depth"] == 0
    assert inner["attrs"] == {"rows": 4}
    assert outer["attrs"] == {"phase": "bench"}
    assert inner["blocked_s"] >= 0.0
    assert 0.0 <= inner["dur_s"] <= outer["dur_s"]
    # JSONL sink round-trips to the identical events
    lines = [json.loads(l) for l in sink.read_text().splitlines()]
    assert lines == evts
    # span durations also land in the shared registry
    st = registry.histogram("obs.span_seconds").stats(name="inner")
    assert st is not None and st["count"] >= 1


# ------------------------------ export ------------------------------- #
def test_bench_payload_schema_and_atomic_write(tmp_path):
    payload = export.bench_payload(
        "figX", [{"name": "a", "us_per_call": 1.5}])
    assert payload["schema"] == export.BENCH_SCHEMA
    assert payload["name"] == "figX"
    fp = payload["fingerprint"]
    for key in ("jax_version", "backend", "device_count", "git_sha"):
        assert key in fp
    assert fp["device_count"] >= 1
    p = tmp_path / "BENCH_figX.json"
    export.write_json(str(p), payload)
    assert export.read_json(str(p)) == json.loads(json.dumps(payload))
    assert not list(tmp_path.glob("*.tmp"))  # atomic write cleaned up


# ------------------------------ report ------------------------------- #
def _payload(us):
    return export.bench_payload(
        "fig", [{"name": "a", "us_per_call": us, "edges_per_s": 1e6 / us}])


def test_report_render_and_diff():
    new, old = _payload(110.0), _payload(100.0)
    out = render(new)
    assert "us_per_call" in out and "a" in out
    rows = diff(new, old)
    by_metric = {r["metric"]: r for r in rows}
    assert by_metric["us_per_call"]["delta"] == pytest.approx(10.0)
    assert by_metric["us_per_call"]["pct"] == pytest.approx(10.0)
    table = render_diff(rows, only_metric="us_per_call")
    assert "+10.0%" in table


# --------------------- instrumentation smoke test --------------------- #
def test_engines_populate_registry():
    from repro.core import graph as G
    from repro.core.graph import DeviceGraph
    from repro.core.partition import build_blocked
    from repro.core import cache_model, tocab, traversal

    rng = np.random.default_rng(0)
    g = G.from_edges(64, rng.integers(0, 64, 300), rng.integers(0, 64, 300))
    dg = DeviceGraph.from_host(g)
    bg = build_blocked(g, block_size=16, direction="pull")

    # the registry is process-global and other tests run BFS too — count
    # this test's iterations as a delta, not an absolute
    iters = registry.counter("traversal.iterations")

    def bfs_iters():
        return sum(s["value"] for s in iters.snapshot()["series"]
                   if dict(s["labels"]).get("algo") == "bfs")

    before = bfs_iters()
    tocab.tocab_pull(bg, jnp.ones((g.n,), jnp.float32))
    depth, levels, n_push, n_pull = traversal.bfs(dg, bg, jnp.int32(0))
    depth.block_until_ready()
    cache_model.simulate_pagerank_variant(g, "tocab", block_size=16)

    names = registry.names()
    for want in (
        "tocab.engine_traces",
        "traversal.frontier_size", "traversal.frontier_edges",
        "traversal.iterations",
        "cache.miss_rate", "cache.dram_per_edge", "cache.simulations",
    ):
        assert want in names, f"missing metric {want}"
    # BFS ran some iterations and the debug.callback delivered them
    total = bfs_iters() - before
    assert total >= int(levels)
    assert total == int(n_push) + int(n_pull)


def test_push_budget_fill_recorded_once_per_push_level():
    from repro.core import graph as G
    from repro.core.graph import DeviceGraph
    from repro.core.partition import build_blocked
    from repro.core import traversal

    rng = np.random.default_rng(0)
    g = G.from_edges(64, rng.integers(0, 64, 300), rng.integers(0, 64, 300))
    dg = DeviceGraph.from_host(g)
    bg = build_blocked(g, block_size=16, direction="pull")
    fill = registry.histogram("traversal.push_budget_fill")

    def seen():
        return fill.stats(algo="bfs") or {"count": 0, "max": 0.0}

    before = seen()["count"]
    depth, levels, n_push, n_pull = traversal.bfs(dg, bg, jnp.int32(0))
    depth.block_until_ready()
    assert int(n_push) >= 1 and int(n_pull) >= 1
    assert seen()["count"] - before == int(n_push)
    assert 0.0 <= seen()["min"] and seen()["max"] <= 1.0



# ------------------- spans on the profiler's clock ------------------- #
def _host_events(trace_dir) -> list:
    """``(name, start_ns, end_ns)`` of every host-plane event of the one
    profiler trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    [path] = list(trace_dir.rglob("*.xplane.pb"))
    return [(e.name, e.start_ns, e.end_ns)
            for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host")
            for line in plane.lines for e in line.events]


def test_span_lands_on_profiler_host_plane(tmp_path):
    import jax

    with jax.profiler.trace(str(tmp_path)):
        with trace.span("obs.test_outer", rows=3):
            with trace.span("obs.test_inner"):
                jnp.ones((4,)).block_until_ready()
    host = {n: (s, e) for n, s, e in _host_events(tmp_path)}
    assert {"obs.test_outer", "obs.test_inner"} <= host.keys()
    outer, inner = host["obs.test_outer"], host["obs.test_inner"]
    assert outer[0] <= inner[0] <= inner[1] <= outer[1]
    # the in-memory event is still recorded, as without a profiler
    assert [e["name"] for e in trace.events()] == [
        "obs.test_inner", "obs.test_outer"]
    assert trace.events()[1]["attrs"] == {"rows": 3}


SETUP_STEPS = ("build_blocked.sort", "build_blocked.fill",
               "build_blocked.place")


@pytest.mark.parametrize("direction", ["pull", "push"])
def test_build_blocked_spans_its_steps(tmp_path, direction):
    import jax
    from repro.core import graph as G
    from repro.core.partition import build_blocked

    rng = np.random.default_rng(3)
    g = G.from_edges(64, rng.integers(0, 64, 300), rng.integers(0, 64, 300))
    with jax.profiler.trace(str(tmp_path)):
        build_blocked(g, block_size=16, direction=direction)
    evts = trace.events()
    assert [e["name"] for e in evts] == [*SETUP_STEPS, "build_blocked"]
    assert evts[-1]["attrs"] == {"direction": direction, "n": g.n, "m": g.m}
    assert all(e["parent"] == "build_blocked" for e in evts[:-1])
    # on the profiler's clock every step lies inside the call
    host = {n: (s, e) for n, s, e in _host_events(tmp_path)
            if n.startswith("build_blocked")}
    lo, hi = host["build_blocked"]
    steps = [host[name] for name in SETUP_STEPS]
    assert all(lo <= s <= e <= hi for s, e in steps)
    assert all(a[1] <= b[0] for a, b in zip(steps, steps[1:]))


def test_device_graph_place_span():
    from repro.core import graph as G
    from repro.core.graph import DeviceGraph

    rng = np.random.default_rng(4)
    g = G.from_edges(32, rng.integers(0, 32, 100), rng.integers(0, 32, 100))
    dg = DeviceGraph.from_host(g)
    assert dg.m == g.m
    # the placement, then the host's symmetry test: two spans, neither
    # inside the other, so `device_graph.place` times the copies alone
    place, symmetric = trace.events()
    assert place["name"] == "device_graph.place" and place["parent"] is None
    assert symmetric["name"] == "device_graph.symmetric"
    assert symmetric["parent"] is None


# -------------------- named scopes in compiled HLO -------------------- #
SLAB_PHASES = {"tocab.gather", "tocab.partials", "tocab.reduce"}


def _scope_names(compiled_text: str) -> set:
    import re

    return {part for path in re.findall(r'op_name="([^"]*)"', compiled_text)
            for part in re.split(r"[/;]", path)}


def _compiled(entry: str) -> str:
    import jax
    from repro.core import graph as G
    from repro.core import tocab
    from repro.core.graph import DeviceGraph
    from repro.core.pagerank import _pagerank_jit
    from repro.core.partition import build_blocked
    from repro.core.traversal import DEFAULT_ALPHA, _bfs_jit

    rng = np.random.default_rng(5)
    g = G.from_edges(64, rng.integers(0, 64, 400), rng.integers(0, 64, 400))
    x = jnp.ones((g.n,), jnp.float32)
    if entry == "pagerank":
        fn, args = _pagerank_jit, (
            DeviceGraph.from_host(g), build_blocked(g, block_size=16),
            "gc-pull", 0.85, 1e-4, 20, True, "uniform", "slab")
    elif entry == "bfs":
        fn, args = _bfs_jit, (
            DeviceGraph.from_host(g), build_blocked(g, block_size=16),
            jnp.int32(0), 0, DEFAULT_ALPHA, "uniform", "slab")
    elif entry == "push":
        fn, args = jax.jit(tocab.tocab_push), (
            build_blocked(g, block_size=16, direction="push"), x)
    else:
        fn, args = jax.jit(tocab.tocab_edge_reduce), (
            build_blocked(g, block_size=16), jnp.ones((g.m,), jnp.float32))
    return fn.lower(*args).compile().as_text()


@pytest.mark.parametrize("entry,names", [
    ("pagerank", SLAB_PHASES | {"pagerank.step"}),
    ("bfs", SLAB_PHASES | {"traversal.push", "traversal.pull", "bfs.level"}),
    ("push", SLAB_PHASES),
    ("edge_reduce", SLAB_PHASES),
])
def test_compiled_slab_phases_carry_their_scopes(entry, names):
    assert names <= _scope_names(_compiled(entry))


def test_bfs_is_one_span():
    from repro.core import graph as G
    from repro.core.graph import DeviceGraph
    from repro.core.partition import build_blocked
    from repro.core.traversal import bfs

    rng = np.random.default_rng(6)
    g = G.from_edges(64, rng.integers(0, 64, 300), rng.integers(0, 64, 300))
    dg, bg = DeviceGraph.from_host(g), build_blocked(g, block_size=16)
    trace.clear()
    depth, *_ = bfs(dg, bg, jnp.int32(0))
    depth.block_until_ready()
    [ev] = trace.events()
    assert ev["name"] == "bfs" and ev["parent"] is None
