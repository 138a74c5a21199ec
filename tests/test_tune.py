"""repro.tune: fingerprints, DB round-trips, analytic prune, end-to-end
tuning, and the tuned call ``**engine_kwargs(bg, workload)``.

The tuner is allowed to change *where time goes*, never *what comes out*:
a tuned call must be bit-for-bit the engine's output under the resolved
schedule, and numerically the flat baseline's answer.
"""
import os
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    DeviceGraph, baseline_pull, build_blocked, from_edges, graph_fingerprint,
    pagerank, rmat_graph, spmv, tocab_pull,
)
from repro.tune import (
    BUDGETS, Candidate, SearchSpace, Trial, default_candidate, device_key,
    engine_kwargs, entry_key, resolve_plan, resolve_schedule, tune,
)
from repro.tune import analytic, db as tune_db, plan as tune_plan, runner
from repro.tune.space import WORKLOADS


@pytest.fixture
def tune_dir(tmp_path, monkeypatch):
    """Isolated DB dir + cold caches, restored afterwards."""
    monkeypatch.setenv("REPRO_TUNE_DIR", str(tmp_path))
    tune_plan.clear_cache()
    analytic.clear_cache()
    runner.clear_cache()
    yield tmp_path
    tune_plan.clear_cache()
    analytic.clear_cache()
    runner.clear_cache()


def hub_graph(n=512, deg=8, hubs=4, seed=0):
    """Scale-free caricature: most edges point at a few hub destinations."""
    rng = np.random.default_rng(seed)
    src = np.repeat(np.arange(n), deg)
    dst = np.where(rng.random(src.shape[0]) < 0.7,
                   rng.integers(0, hubs, src.shape[0]),
                   rng.integers(0, n, src.shape[0]))
    keep = src != dst
    vals = rng.random(int(keep.sum()), dtype=np.float32)
    return from_edges(n, src[keep], dst[keep], vals=vals, dedup=True)


# --------------------------- fingerprints --------------------------- #

def test_fingerprint_stable_and_discriminating():
    a1 = rmat_graph(8, 8, seed=3, weights=True)
    a2 = rmat_graph(8, 8, seed=3, weights=True)
    b = rmat_graph(8, 8, seed=4, weights=True)
    assert graph_fingerprint(a1) == graph_fingerprint(a2)
    assert graph_fingerprint(a1) != graph_fingerprint(b)
    assert len(graph_fingerprint(a1)) == 16


def test_fingerprint_weight_independent():
    g = rmat_graph(8, 8, seed=3, weights=True)
    unweighted = rmat_graph(8, 8, seed=3, weights=False)
    assert graph_fingerprint(g) == graph_fingerprint(unweighted)


def test_fingerprint_propagates_to_device_and_blocked():
    g = rmat_graph(8, 8, seed=3, weights=True)
    dg = DeviceGraph.from_host(g)
    bg = build_blocked(g, block_size=64)
    assert dg.fingerprint == graph_fingerprint(g)
    assert bg.fingerprint == graph_fingerprint(g)


# ------------------------------- DB -------------------------------- #

def test_db_roundtrip(tune_dir):
    path = tune_db.db_path()
    key = entry_key("deadbeefdeadbeef", dtype="float32", workload="pagerank")
    entry = {"schema": tune_db.DB_SCHEMA, "graph": "toy",
             "chosen": default_candidate().to_json(), "best_us": 12.5}
    tune_db.put_entry(key, entry, path)
    tune_db.clear_cache()
    got = tune_db.get_entry(key, path)
    assert got["graph"] == "toy"
    assert got["best_us"] == 12.5
    assert Candidate.from_json(got["chosen"]) == default_candidate()
    on_disk = json.loads(path.read_text()) if hasattr(path, "read_text") \
        else json.load(open(path))
    assert on_disk["schema"] == tune_db.DB_SCHEMA


def test_db_schema_mismatch_quarantined(tune_dir):
    # hardened load (repro.resilience): a wrong-schema file is moved aside
    # to TUNE_DB.json.corrupt-<ts> and an empty DB served, never an exception
    path = tune_db.db_path()
    tune_db.save({"schema": "repro.tune.db/v999", "entries": {}}, path)
    tune_db.clear_cache()
    db = tune_db.load(path)
    assert db["entries"] == {} and db["schema"] == tune_db.DB_SCHEMA
    assert any(".corrupt-" in n for n in os.listdir(os.path.dirname(path)))


def test_entry_key_shape():
    k = entry_key("abc123", dtype="float32", workload="spmv")
    assert k == f"abc123/{device_key()}/float32/spmv"


# --------------------------- search space --------------------------- #

def test_candidates_valid_and_unique():
    space = SearchSpace()
    for wl in WORKLOADS:
        cands = space.candidates(wl)
        assert len(cands) == len(set(cands))
        for c in cands:
            if c.engine == "cb":
                assert c.direction == "pull" and c.schedule == "uniform"
            if wl == "bfs":
                assert c.direction == "pull"
            if c.schedule == "balanced":
                assert c.engine == "tocab"
            assert c == Candidate.from_json(c.to_json())
    with pytest.raises(ValueError):
        space.candidates("nope")


def test_budget_presets():
    assert set(BUDGETS) == {"smoke", "small", "full"}
    smoke = SearchSpace.for_budget("smoke")
    assert len(smoke.candidates("pagerank")) <= BUDGETS["smoke"].max_trials
    with pytest.raises(ValueError):
        SearchSpace.for_budget("huge")


# --------------------------- analytic prune --------------------------- #

def test_analytic_prune_partitions_candidates(tune_dir):
    g = rmat_graph(9, 8, seed=1, weights=True)
    cands = SearchSpace().candidates("pagerank")
    kept, pruned = analytic.prune(g, cands, prune_ratio=1.0)
    assert sorted(kept + pruned, key=cands.index) == cands
    assert kept  # the best-scoring group always survives
    loose_kept, _ = analytic.prune(g, cands, prune_ratio=1e9)
    assert len(loose_kept) == len(cands)


# ------------------------- end-to-end tuning ------------------------- #

def _tiny_space():
    return SearchSpace(engines=("base", "tocab"), directions=("pull",),
                       schedules=("uniform", "balanced"), block_sizes=(128,))


def test_tune_twice_hits_db(tune_dir):
    g = rmat_graph(9, 8, seed=5, weights=True)
    first = tune({"toy": g}, workloads=("pagerank",), budget="smoke",
                 space=_tiny_space())
    assert first["new_trials"] > 0 and first["db_hits"] == 0
    second = tune({"toy": g}, workloads=("pagerank",), budget="smoke",
                  space=_tiny_space())
    assert second["new_trials"] == 0
    assert second["db_hits"] == len(second["entries"]) == 1
    entry = second["entries"][0]
    assert entry["schema"] == tune_db.DB_SCHEMA
    assert entry["graph_fp"] == graph_fingerprint(g)
    trial = Trial.from_json(entry["trials"][0])
    assert trial.us > 0 and trial.workload == "pagerank"


def _force_plan(g, candidate, workload="pagerank"):
    """Write a DB entry by hand — the read path must honour whatever the
    tuner (or an operator) persisted, so tests can pin the winner."""
    path = tune_db.db_path()
    key = entry_key(graph_fingerprint(g), dtype="float32", workload=workload)
    tune_db.put_entry(key, {"schema": tune_db.DB_SCHEMA, "graph": "forced",
                            "chosen": candidate.to_json(), "best_us": 1.0},
                      path)
    tune_plan.clear_cache()


@pytest.mark.parametrize("make_graph", [
    lambda: rmat_graph(9, 8, seed=2, weights=True),
    lambda: hub_graph(),
], ids=["random", "hub"])
def test_auto_matches_baseline(tune_dir, make_graph):
    g = make_graph()
    dg = DeviceGraph.from_host(g)
    bg = build_blocked(g, block_size=128)
    _force_plan(g, Candidate(engine="tocab", schedule="balanced",
                             block_size=128))
    assert resolve_schedule(bg) == "balanced"
    rank_auto, it_auto = pagerank(dg, bg, variant="gc-pull",
                                  **engine_kwargs(bg, "pagerank"))
    rank_res, it_res = pagerank(dg, bg, variant="gc-pull",
                                schedule="balanced")
    # bit-for-bit: the tuned call IS the resolved schedule
    assert (np.asarray(rank_auto) == np.asarray(rank_res)).all()
    assert int(it_auto) == int(it_res)
    rank_base, _ = pagerank(dg, None, variant="base")
    np.testing.assert_allclose(rank_auto, rank_base, atol=1e-7)

    x = jnp.asarray(np.random.default_rng(0).random(g.n, dtype=np.float32))
    np.testing.assert_allclose(spmv(dg, bg, x, **engine_kwargs(bg, "spmv")),
                               baseline_pull(dg, x), rtol=2e-5, atol=2e-5)


def test_auto_without_db_is_uniform(tune_dir):
    g = rmat_graph(8, 8, seed=6, weights=True)
    bg = build_blocked(g, block_size=64)
    assert resolve_plan(bg) is None
    assert resolve_schedule(bg) == "uniform"
    x = jnp.ones((g.n,), jnp.float32)
    assert engine_kwargs(bg, "spmv") == {"schedule": "uniform", "impl": "slab"}
    out = tocab_pull(bg, x, **engine_kwargs(bg, "spmv"))
    np.testing.assert_array_equal(out, tocab_pull(bg, x, schedule="uniform"))


def test_plan_cache_invalidates_on_db_rewrite(tune_dir):
    g = rmat_graph(8, 8, seed=7, weights=True)
    bg = build_blocked(g, block_size=64)
    assert resolve_schedule(bg) == "uniform"  # cached miss
    _force_plan(g, Candidate(engine="tocab", schedule="balanced",
                             block_size=64))
    # no manual cache clear beyond what _force_plan does: a DB rewrite
    # (new mtime) must be picked up
    assert resolve_schedule(bg) == "balanced"


def test_flat_winner_pins_uniform(tune_dir):
    g = rmat_graph(8, 8, seed=8, weights=True)
    bg = build_blocked(g, block_size=64)
    _force_plan(g, Candidate(engine="base", direction="pull"))
    # caller already committed to a blocked engine; a flat winner means
    # "no balanced dispatch", not "crash"
    assert resolve_schedule(bg) == "uniform"


def test_sibling_workload_borrowed(tune_dir):
    g = rmat_graph(8, 8, seed=9, weights=True)
    bg = build_blocked(g, block_size=64)
    _force_plan(g, Candidate(engine="tocab", schedule="balanced",
                             block_size=64), workload="spmv")
    plan = resolve_plan(bg, workload="pagerank")
    assert plan is not None and plan.source == "db:spmv"
    assert resolve_schedule(bg, workload="pagerank") == "balanced"


