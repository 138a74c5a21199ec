"""Hypothesis property: a tuned call (``**engine_kwargs``) ≡ the flat
baseline, whatever the tuning DB pins.

The plan-resolution layer sits between every engine call and the persisted
DB — for any graph and any persisted winner, it must stay a pure dispatch
decision with no numerical surface.
"""
import os

import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="optional dev dependency (pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.core import (
    DeviceGraph, baseline_pull, build_blocked, from_edges, graph_fingerprint,
    tocab_pull,
)
from repro.tune import Candidate, engine_kwargs, entry_key
from repro.tune import db as tune_db, plan as tune_plan


@st.composite
def small_graph(draw):
    n = draw(st.integers(8, 128))
    m = draw(st.integers(4, 400))
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    src, dst = rng.integers(0, n, m), rng.integers(0, n, m)
    keep = src != dst
    if not keep.any():
        src, dst = np.array([0]), np.array([1])
        keep = np.array([True])
    vals = rng.random(int(keep.sum()), dtype=np.float32)
    return from_edges(n, src[keep], dst[keep], vals=vals, dedup=True)


@given(g=small_graph(), forced=st.sampled_from(["uniform", "balanced"]))
@settings(max_examples=15, deadline=None)
def test_auto_equals_baseline(tmp_path_factory, g, forced):
    tmp = tmp_path_factory.mktemp("tunedb")
    old = os.environ.get("REPRO_TUNE_DIR")
    os.environ["REPRO_TUNE_DIR"] = str(tmp)
    try:
        tune_plan.clear_cache()
        bg = build_blocked(g, block_size=32)
        key = entry_key(graph_fingerprint(g), dtype="float32",
                        workload="pagerank")
        chosen = Candidate(engine="tocab", schedule=forced, block_size=32)
        tune_db.put_entry(
            key, {"schema": tune_db.DB_SCHEMA, "graph": "prop",
                  "chosen": chosen.to_json(), "best_us": 1.0},
            tune_db.db_path())
        tune_plan.clear_cache()
        dg = DeviceGraph.from_host(g)
        x = jnp.asarray(np.linspace(0.0, 1.0, g.n, dtype=np.float32))
        out = tocab_pull(bg, x, **engine_kwargs(bg, "pagerank"))
        np.testing.assert_allclose(out, baseline_pull(dg, x),
                                   rtol=2e-5, atol=2e-5)
    finally:
        tune_plan.clear_cache()
        if old is None:
            os.environ.pop("REPRO_TUNE_DIR", None)
        else:
            os.environ["REPRO_TUNE_DIR"] = old
