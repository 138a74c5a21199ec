"""Traversal ``schedule=`` plumbing: balanced and tuned dispatch must be
invisible in results, and Beamer α must flow from the tuning DB when the
caller asks for it (``resolve_alpha``), and only then.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    DeviceGraph, bc, bfs, build_blocked, connected_components,
    graph_fingerprint, rmat_graph, sssp,
)
from repro.tune import Candidate, engine_kwargs, entry_key, resolve_alpha
from repro.tune import db as tune_db, plan as tune_plan


@pytest.fixture(scope="module")
def setup():
    g = rmat_graph(scale=8, edge_factor=6, seed=11, weights=True)
    return (g, DeviceGraph.from_host(g),
            DeviceGraph.from_host(g.transpose()),
            build_blocked(g, block_size=64))


@pytest.fixture
def tune_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_DIR", str(tmp_path))
    tune_plan.clear_cache()
    yield tmp_path
    tune_plan.clear_cache()


def _pin(g, candidate, workload="bfs"):
    key = entry_key(graph_fingerprint(g), dtype="float32", workload=workload)
    tune_db.put_entry(key, {"schema": tune_db.DB_SCHEMA, "graph": "pin",
                            "chosen": candidate.to_json(), "best_us": 1.0},
                      tune_db.db_path())
    tune_plan.clear_cache()


def _engine(bg, schedule, alpha=False):
    """Engine keywords of a traversal call: an explicit schedule, or the
    tuned call (``"tuned"``: the DB's engine, and its α where asked)."""
    if schedule != "tuned":
        return {"schedule": schedule}
    kw = engine_kwargs(bg, "bfs")
    if alpha:
        kw["alpha"] = resolve_alpha(bg, workload="bfs")
    return kw


@pytest.mark.parametrize("schedule", ["balanced", "tuned"])
def test_bfs_schedules_agree(setup, tune_dir, schedule):
    g, dg, dgt, bg = setup
    ref, levels, *_ = bfs(dg, bg, jnp.int32(5))
    out, levels2, *_ = bfs(dg, bg, jnp.int32(5),
                           **_engine(bg, schedule, alpha=True))
    assert (np.asarray(ref) == np.asarray(out)).all()
    assert int(levels) == int(levels2)


def test_bc_schedules_agree(setup, tune_dir):
    g, dg, dgt, bg = setup
    ref, depth, sigma = bc(dg, bg, jnp.int32(3))
    for schedule in ("balanced", "tuned"):
        out, d2, s2 = bc(dg, bg, jnp.int32(3),
                         **_engine(bg, schedule, alpha=True))
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
        assert (np.asarray(depth) == np.asarray(d2)).all()
        np.testing.assert_allclose(sigma, s2, rtol=1e-5)


def test_sssp_schedules_agree(setup, tune_dir):
    g, dg, dgt, bg = setup
    ref, it = sssp(dg, bg, jnp.int32(5))
    for schedule in ("balanced", "tuned"):
        out, it2 = sssp(dg, bg, jnp.int32(5), **_engine(bg, schedule))
        assert (np.asarray(ref) == np.asarray(out)).all()
        assert int(it) == int(it2)


def test_cc_schedules_agree(setup, tune_dir):
    g, dg, dgt, bg = setup
    ref, it = connected_components(dg, dgt, bg)
    for schedule in ("balanced", "tuned"):
        out, it2 = connected_components(dg, dgt, bg, **_engine(bg, schedule))
        assert (np.asarray(ref) == np.asarray(out)).all()
        assert int(it) == int(it2)


def test_auto_with_pinned_balanced_plan(setup, tune_dir):
    g, dg, dgt, bg = setup
    _pin(g, Candidate(engine="tocab", schedule="balanced", block_size=64))
    ref, *_ = bfs(dg, bg, jnp.int32(5))
    assert engine_kwargs(bg, "bfs")["schedule"] == "balanced"
    out, *_ = bfs(dg, bg, jnp.int32(5), **_engine(bg, "tuned", alpha=True))
    assert (np.asarray(ref) == np.asarray(out)).all()


def test_alpha_override_flips_direction(setup, tune_dir):
    """α is the push↔pull switch (use_pull ⇔ m_frontier > m/α): α→∞ makes
    the threshold vanish (always pull), α→0⁺ makes it unreachable (always
    push)."""
    g, dg, dgt, bg = setup
    # (a zero-out-degree frontier still goes push: m_frontier = 0 beats no
    # positive threshold — hence ≥ levels-1, not == levels)
    _, levels, n_push, n_pull = bfs(dg, bg, jnp.int32(5), alpha=1e9)
    assert int(n_pull) >= int(levels) - 1
    _, levels2, n_push2, n_pull2 = bfs(dg, bg, jnp.int32(5), alpha=1e-9)
    assert int(n_pull2) == 0 and int(n_push2) == int(levels2)


def test_tuned_alpha_applies_under_auto(setup, tune_dir):
    g, dg, dgt, bg = setup
    _pin(g, Candidate(engine="tocab", block_size=64, alpha=1e-9))
    assert tune_plan.resolve_alpha(bg) == 1e-9
    # the tuned call takes the tuned α → all-push run, bit-identical to
    # spelling alpha=1e-9 explicitly
    d_auto, lv, n_push, n_pull = bfs(dg, bg, jnp.int32(5),
                                     **_engine(bg, "tuned", alpha=True))
    assert int(n_pull) == 0 and int(n_push) == int(lv)
    d_exp, *_ = bfs(dg, bg, jnp.int32(5), alpha=1e-9)
    assert (np.asarray(d_auto) == np.asarray(d_exp)).all()


def test_explicit_schedule_keeps_default_alpha(setup, tune_dir):
    """Without a tuned call, a tuned DB must not silently change
    behaviour."""
    g, dg, dgt, bg = setup
    _pin(g, Candidate(engine="tocab", block_size=64, alpha=1e-9))
    _, _, n_push, n_pull = bfs(dg, bg, jnp.int32(5))
    assert int(n_pull) >= 1  # paper's α=15 still engages pull
