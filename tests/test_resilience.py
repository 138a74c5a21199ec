"""Resilience layer: chaos injection, retry/timeout policies, hardened
checkpoint/tune-DB IO, graph structural validation, and engine dispatch
without fallbacks.

The invariant under test throughout: faults change *how often the work
runs* (retry attempt, rebuilt DB), never *what comes out* — IO recovery
must never destroy good data.  Engine dispatch has no recovery path: the
caller's ``impl`` runs, and its failure reaches the caller.
"""
import ast
import json
import os
import pathlib

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    build_blocked, from_edges, graph_fingerprint, rmat_graph,
    tocab_edge_reduce, tocab_pull, tocab_push,
)
from repro.core.graph import Graph, GraphValidationError, validate_graph
from repro.obs.metrics import registry as _obs
from repro.resilience import chaos
from repro.resilience.chaos import ChaosError
from repro.resilience.retry import Policy, call_with_timeout, retry
from repro.train import checkpoint as ckpt
from repro.tune import db as tune_db
from repro.tune import plan as tune_plan
from repro.tune import analytic, runner, tuner
from repro.tune.space import Candidate, SearchSpace, TrialBudget


@pytest.fixture(autouse=True)
def clean_resilience(monkeypatch):
    """Each test starts with chaos disarmed (even under the chaos-smoke CI
    env — these tests inject their own faults)."""
    monkeypatch.delenv(chaos.ENV_SPEC, raising=False)
    monkeypatch.delenv(chaos.ENV_SITES, raising=False)
    chaos.reset()
    yield
    chaos.reset()


def small_graph(seed=0, scale=7):
    return rmat_graph(scale=scale, edge_factor=6, seed=seed, weights=True)


# ------------------------------ chaos -------------------------------- #

def test_chaos_deterministic_by_seed():
    """Same seed → same fault pattern; different seed → different pattern."""

    def pattern(seed):
        chaos.reset()
        chaos.configure(seed=seed, rate=0.3, sites={"s"})
        fired = []
        for _ in range(200):
            try:
                chaos.maybe_raise("s")
                fired.append(False)
            except ChaosError:
                fired.append(True)
        return fired

    p7a, p7b, p8 = pattern(7), pattern(7), pattern(8)
    assert p7a == p7b
    assert p7a != p8
    assert 20 < sum(p7a) < 100  # rate 0.3 over 200 draws


def test_chaos_spec_and_env_parsing(monkeypatch):
    cfg = chaos.configure_spec("42:0.5")
    assert (cfg.seed, cfg.rate) == (42, 0.5)
    assert cfg.sites == chaos.DEFAULT_SITES
    assert chaos.enabled()
    chaos.reset()

    monkeypatch.setenv(chaos.ENV_SPEC, "99:0.25")
    monkeypatch.setenv(chaos.ENV_SITES, "a,b")
    chaos.reset()  # force env re-read
    assert chaos.enabled()
    assert chaos.active_for("a") and chaos.active_for("b")
    assert not chaos.active_for("ckpt.save")

    monkeypatch.setenv(chaos.ENV_SPEC, "nonsense")
    chaos.reset()
    with pytest.raises(ValueError, match="REPRO_CHAOS"):
        chaos.enabled()


def test_chaos_inject_queue():
    chaos.inject("q", times=2)
    for _ in range(2):
        with pytest.raises(ChaosError):
            chaos.maybe_raise("q")
    chaos.maybe_raise("q")  # queue drained, rate not armed

    class Boom(RuntimeError):
        pass

    chaos.inject("q", exc=Boom("custom"))
    with pytest.raises(Boom):
        chaos.maybe_raise("q")


def test_opt_in_sites_not_default():
    """Rate-based injection at the sites that have no recovery path must be
    opt-in, or a chaos run manufactures unhandled crashes.  Kernel dispatch
    has no recovery path at all, so it has no site."""
    assert "tune.trial" not in chaos.DEFAULT_SITES
    assert "tune.trial" in chaos.KNOWN_SITES
    assert set(chaos.DEFAULT_SITES) <= set(chaos.KNOWN_SITES)
    assert not [s for s in chaos.KNOWN_SITES if s.startswith("kernel.")]


# --------------------------- retry / timeout --------------------------- #

def test_retry_recovers_and_counts():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return "ok"

    pol = Policy(max_attempts=3, base_delay=0.001)
    before = _obs.counter("resilience.retries").value(
        site="t", error="OSError") or 0
    assert pol.call(flaky, site="t") == "ok"
    assert len(calls) == 3
    assert _obs.counter("resilience.retries").value(
        site="t", error="OSError") == before + 2


def test_retry_exhaustion_reraises():
    pol = Policy(max_attempts=2, base_delay=0.001)
    before = _obs.counter("resilience.retry_exhausted").value(site="x") or 0
    with pytest.raises(OSError, match="always"):
        pol.call(lambda: (_ for _ in ()).throw(OSError("always")), site="x")
    assert _obs.counter("resilience.retry_exhausted").value(
        site="x") == before + 1


def test_retry_does_not_catch_unlisted():
    pol = Policy(max_attempts=5, base_delay=0.001, retry_on=(OSError,))
    calls = []

    def bug():
        calls.append(1)
        raise ValueError("logic bug, not transient")

    with pytest.raises(ValueError):
        pol.call(bug, site="y")
    assert len(calls) == 1  # no retries for non-transient errors


def test_retry_decorator():
    state = {"n": 0}

    @retry(site="deco", max_attempts=2, base_delay=0.001)
    def fn(x):
        state["n"] += 1
        if state["n"] == 1:
            raise OSError
        return x + 1

    assert fn(1) == 2
    assert fn.policy.max_attempts == 2


def test_call_with_timeout():
    import time

    assert call_with_timeout(lambda: 5, None) == 5
    assert call_with_timeout(lambda: 5, 10.0) == 5
    with pytest.raises(TimeoutError):
        call_with_timeout(time.sleep, 0.05, 5.0)
    with pytest.raises(ZeroDivisionError):  # worker errors re-raise
        call_with_timeout(lambda: 1 / 0, 10.0)


def test_deterministic_jitter():
    pol = Policy(base_delay=0.05)
    assert pol.delay("s", 1) == pol.delay("s", 1)
    assert pol.delay("s", 1) != pol.delay("s", 2)


# --------------------------- engine dispatch --------------------------- #

_ENTRIES = {
    "tocab_pull": ("fused_pull", "pull",
                   lambda bg, g: tocab_pull(bg, jnp.ones((g.n,)), impl="fused")),
    "tocab_push": ("fused_push", "push",
                   lambda bg, g: tocab_push(bg, jnp.ones((g.n,)), impl="fused")),
    "tocab_edge_reduce": ("fused_edge_reduce", "pull",
                          lambda bg, g: tocab_edge_reduce(
                              bg, jnp.ones((g.m,)), impl="fused")),
}


@pytest.mark.parametrize("entry", sorted(_ENTRIES))
def test_explicit_impl_failure_propagates(monkeypatch, entry):
    """A failing kernel reaches the caller as it was raised: no other engine
    runs in its place and nothing is recorded as a fallback."""
    import repro.kernels.tocab_fused as fused_pkg

    kernel, direction, call = _ENTRIES[entry]
    g = small_graph(seed=13)
    bg = build_blocked(g, block_size=32, direction=direction)

    class KernelBroke(RuntimeError):
        pass

    err = KernelBroke("does not lower")

    def boom(*args, **kwargs):
        raise err

    monkeypatch.setattr(fused_pkg, kernel, boom)
    with pytest.raises(KernelBroke) as info:
        call(bg, g)
    assert info.value is err
    fallbacks = _obs.snapshot().get("resilience.fallbacks")
    assert not (fallbacks and fallbacks["series"])


def _imported_modules(path: pathlib.Path, package: str):
    """Absolute names of every module ``path`` imports, and of every name a
    ``from`` import takes (relative imports resolved against ``package``)."""
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            parts = [node.module] if node.module else []
            if node.level:
                base = package.split(".")
                parts = base[:len(base) - node.level + 1] + parts
            module = ".".join(parts)
            yield module
            yield from (f"{module}.{a.name}" for a in node.names)


def test_core_imports_neither_tune_nor_resilience():
    """Imports run tune → core → kernels only: the engines take their
    schedule and impl from the caller, never from the tuning DB, and have
    no fallback or fault-injection hooks."""
    src = pathlib.Path(__file__).resolve().parents[1] / "src"
    files = sorted((src / "repro" / "core").glob("*.py")) + sorted(
        (src / "repro" / "kernels").rglob("*.py"))
    assert len(files) > 10
    bad = []
    for f in files:
        rel = f.relative_to(src).with_suffix("")
        for mod in _imported_modules(f, ".".join(rel.parts[:-1])):
            if mod.split(".")[:2] in (["repro", "tune"],
                                      ["repro", "resilience"]):
                bad.append(f"{rel}: {mod}")
    assert not bad, bad


# ------------------------------- tuner -------------------------------- #

TEST_BUDGET = TrialBudget("test", warmup=0, reps=1, prune_ratio=100.0,
                          max_trials=8)
TEST_SPACE = SearchSpace(engines=("tocab",), directions=("pull",),
                         schedules=("uniform",), impls=("slab",),
                         block_sizes=(32, 64))


@pytest.fixture
def tune_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_DIR", str(tmp_path))
    for mod in (tune_plan, analytic, runner):
        mod.clear_cache()
    yield tmp_path
    for mod in (tune_plan, analytic, runner):
        mod.clear_cache()


def test_poisoned_candidate_skipped(tune_dir):
    g = small_graph(seed=15)
    chaos.inject("tune.trial")  # first trial of the sweep crashes
    e1 = tuner.tune_graph(g, "tg", space=TEST_SPACE, budget=TEST_BUDGET)
    assert len(e1["skipped"]) == 1
    bad_key = Candidate.from_json(e1["skipped"][0]["candidate"]).key()
    key = tune_db.entry_key(graph_fingerprint(g), workload="pagerank")
    assert bad_key in tune_db.poisoned_for(key)

    # re-tune: the poisoned candidate is skipped upfront, not re-run
    e2 = tuner.tune_graph(g, "tg", space=TEST_SPACE, budget=TEST_BUDGET,
                          force=True)
    assert e2["poisoned_skipped"] == [bad_key]
    assert not e2["skipped"]
    assert all(t["candidate"]["block_size"] !=
               e1["skipped"][0]["candidate"]["block_size"]
               for t in e2["trials"])


def test_trial_timeout(tune_dir):
    g = small_graph(seed=16)
    cand = Candidate(engine="tocab", direction="pull", block_size=32)
    with pytest.raises(TimeoutError):
        runner.run_trial(g, cand, budget=TEST_BUDGET, timeout=1e-4)


# ------------------------------ tune DB -------------------------------- #

def test_db_corrupt_json_quarantined(tune_dir):
    path = tune_db.db_path()
    os.makedirs(tune_dir, exist_ok=True)
    with open(path, "w") as f:
        f.write("{definitely not json")
    tune_db.clear_cache()
    before = _obs.counter("tune.db_recovered").value(reason="corrupt") or 0
    db = tune_db.load(path)
    assert db["entries"] == {}
    assert db["schema"] == tune_db.DB_SCHEMA
    quarantined = [n for n in os.listdir(tune_dir) if ".corrupt-" in n]
    assert len(quarantined) == 1
    assert _obs.counter("tune.db_recovered").value(
        reason="corrupt") == before + 1
    # the DB keeps working after recovery
    tune_db.put_entry("k", {"chosen": {}})
    assert tune_db.get_entry("k", path) is not None


def test_db_schema_mismatch_quarantined(tune_dir):
    path = tune_db.db_path()
    with open(path, "w") as f:
        json.dump({"schema": "something/else", "entries": {"k": {}}}, f)
    tune_db.clear_cache()
    assert tune_db.load(path)["entries"] == {}
    assert any(".corrupt-" in n for n in os.listdir(tune_dir))


def test_db_transient_fault_preserves_file(tune_dir):
    """Injected read faults that exhaust retries must NOT quarantine a good
    file — the next clean load sees the original data."""
    tune_db.put_entry("keep-me", {"chosen": {}})
    path = tune_db.db_path()
    tune_db.clear_cache()
    chaos.inject("tune.db_load", times=tune_db.IO_POLICY.max_attempts)
    assert tune_db.load(path)["entries"] == {}  # served empty this call
    assert not any(".corrupt-" in n for n in os.listdir(tune_dir))
    tune_db.clear_cache()
    assert "keep-me" in tune_db.load(path)["entries"]


def test_db_save_fault_retried(tune_dir):
    chaos.inject("tune.db_save")  # one fault < retry budget
    tune_db.put_entry("retried", {"chosen": {}})
    tune_db.clear_cache()
    assert "retried" in tune_db.load(tune_db.db_path())["entries"]


# ----------------------------- checkpoints ----------------------------- #

def _tree():
    return {"w": np.arange(12, dtype=np.float32).reshape(3, 4),
            "b": np.float32(1.5)}


def test_checkpoint_roundtrip_with_checksums(tmp_path):
    d = str(tmp_path)
    ckpt.save(d, 1, _tree())
    with open(os.path.join(d, "step_00000001", "manifest.json")) as f:
        manifest = json.load(f)
    assert len(manifest["checksums"]) == 2
    restored, step, _ = ckpt.restore(d, _tree())
    assert step == 1
    np.testing.assert_array_equal(np.asarray(restored["w"]), _tree()["w"])


def test_torn_checkpoint_falls_back(tmp_path):
    d = str(tmp_path)
    ckpt.save(d, 1, _tree())
    ckpt.save(d, 2, _tree())
    # tear the newest step's arrays mid-file
    with open(os.path.join(d, "step_00000002", "arrays.npz"), "r+b") as f:
        f.seek(64)
        f.write(b"\xde\xad\xbe\xef" * 4)
    assert ckpt.latest_step(d) == 1
    _, step, _ = ckpt.restore(d, _tree())
    assert step == 1
    with pytest.raises(ckpt.CheckpointError):  # explicit bad step raises
        ckpt.restore(d, _tree(), step=2)


def test_checksum_flip_detected(tmp_path):
    """A checkpoint whose npz is loadable but whose bytes changed (bit rot)
    fails the per-leaf crc and is skipped."""
    d = str(tmp_path)
    ckpt.save(d, 1, _tree())
    ckpt.save(d, 2, _tree())
    step2 = os.path.join(d, "step_00000002", "arrays.npz")
    with np.load(step2) as data:
        arrays = {k: data[k].copy() for k in data.files}
    arrays["leaf_0"] = arrays["leaf_0"] + 1  # silent corruption
    np.savez(step2, **arrays)
    assert ckpt._validate_step(d, 2) == "checksum"
    assert ckpt.latest_step(d) == 1


def test_partial_step_skipped(tmp_path):
    d = str(tmp_path)
    ckpt.save(d, 1, _tree())
    os.makedirs(os.path.join(d, "step_00000005"))  # torn: no files inside
    with open(os.path.join(d, "LATEST"), "w") as f:
        f.write("5")
    before = _obs.counter("ckpt.skipped").value(reason="partial") or 0
    assert ckpt.latest_step(d) == 1
    assert _obs.counter("ckpt.skipped").value(reason="partial") == before + 1
    assert ckpt.valid_steps(d) == [1]


def test_checkpoint_save_retried_under_fault(tmp_path):
    d = str(tmp_path)
    chaos.inject("ckpt.save")  # one fault < retry budget
    ckpt.save(d, 3, _tree())
    assert ckpt.latest_step(d) == 3
    chaos.inject("ckpt.restore")
    _, step, _ = ckpt.restore(d, _tree())
    assert step == 3


def test_manager_surfaces_async_error(tmp_path):
    d = str(tmp_path)
    mgr = ckpt.CheckpointManager(d, async_write=True)
    # exhaust the save retry budget on the writer thread
    chaos.inject("ckpt.save", times=ckpt.IO_POLICY.max_attempts)
    before = _obs.counter("ckpt.async_errors").value(error="ChaosError") or 0
    mgr.save(1, _tree())
    with pytest.raises(ChaosError):
        mgr.wait()
    assert _obs.counter("ckpt.async_errors").value(
        error="ChaosError") == before + 1
    # the manager recovers: the next save works and wait() is clean
    mgr.save(2, _tree())
    mgr.wait()
    assert ckpt.latest_step(d) == 2


# ----------------------------- serving -------------------------------- #

def test_serve_batch_step_retried():
    from repro.launch.serve import _resilient_step

    chaos.inject("serve.batch")
    assert _resilient_step(lambda a, b: a + b, 20, 22) == 42


# ------------------------- graph validation ---------------------------- #

def test_validate_graph_accepts_valid():
    g = small_graph(seed=17)
    assert validate_graph(g, "cheap") is g
    assert g.validate("full") is g
    from_edges(4, [0, 1], [1, 2], validate="full")


def test_validate_graph_rejects_each_invariant():
    g = small_graph(seed=18)
    cases = {
        "rowptr_shape": Graph(g.n, g.rowptr[:-1], g.colidx),
        "rowptr_origin": Graph(
            g.n, np.concatenate([[1], g.rowptr[1:]]), g.colidx),
        "rowptr_total": Graph(
            g.n, np.concatenate([g.rowptr[:-1], [g.m + 3]]), g.colidx),
        "colidx_range": Graph(
            g.n, g.rowptr, np.full_like(g.colidx, g.n)),
        "vals_length": Graph(g.n, g.rowptr, g.colidx,
                             vals=np.ones(g.m + 1, np.float32)),
    }
    bad_mono = g.rowptr.copy()
    bad_mono[2] = bad_mono[1] - 1
    bad_mono[-1] = g.m  # keep the total right so monotonicity is what trips
    cases["rowptr_monotone"] = Graph(g.n, bad_mono, g.colidx)
    for check, bad in cases.items():
        with pytest.raises(GraphValidationError) as ei:
            validate_graph(bad, "full")
        assert ei.value.check == check, (check, ei.value.check)


def test_from_edges_validates_coo():
    with pytest.raises(GraphValidationError) as ei:
        from_edges(4, [0, 9], [1, 2], validate="cheap")
    assert ei.value.check == "coo_range"


def test_build_blocked_validates():
    g = small_graph(seed=19)
    bad = Graph(g.n, g.rowptr, np.full_like(g.colidx, g.n))
    with pytest.raises(GraphValidationError):
        build_blocked(bad, block_size=32, validate="full")
    build_blocked(g, block_size=32, validate="cheap")  # valid passes


# ------------------ property test: CSR mutations caught ------------------ #
# hypothesis is an optional dev dependency; only this test skips without it.
try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    st = None

if st is not None:
    @st.composite
    def mutated_csr(draw):
        n = draw(st.integers(4, 64))
        m = draw(st.integers(1, 200))
        seed = draw(st.integers(0, 2**31 - 1))
        rng = np.random.default_rng(seed)
        src = rng.integers(0, n, m)
        dst = rng.integers(0, n, m)
        keep = src != dst
        if not keep.any():
            src, dst = np.array([0]), np.array([1])
        else:
            src, dst = src[keep], dst[keep]
        g = from_edges(n, src, dst, dedup=True)
        mutation = draw(st.sampled_from(
            ["rowptr_shape", "rowptr_origin", "rowptr_total",
             "rowptr_monotone", "colidx_range", "vals_length"]))
        rowptr, colidx, vals = g.rowptr.copy(), g.colidx.copy(), None
        if mutation == "rowptr_shape":
            rowptr = rowptr[:-1]
        elif mutation == "rowptr_origin":
            rowptr[0] = draw(st.integers(1, 5))
        elif mutation == "rowptr_total":
            rowptr[-1] = g.m + draw(st.integers(1, 9))
        elif mutation == "rowptr_monotone":
            i = draw(st.integers(1, n - 1))
            rowptr[i] = -1  # below rowptr[i-1] >= 0 and non-monotone
        elif mutation == "colidx_range":
            i = draw(st.integers(0, g.m - 1))
            colidx[i] = draw(st.sampled_from([-1, n, n + 7]))
        elif mutation == "vals_length":
            vals = np.ones(g.m + draw(st.integers(1, 4)), np.float32)
        return Graph(g.n, rowptr, colidx, vals=vals), mutation

    @given(mutated_csr())
    @settings(max_examples=40, deadline=None)
    def test_csr_mutation_always_caught(case):
        """∀ invariant-violating CSR mutation: full validation raises a
        structured GraphValidationError."""
        bad, mutation = case
        with pytest.raises(GraphValidationError):
            validate_graph(bad, "full")
else:
    @pytest.mark.skip(reason="hypothesis not installed "
                             "(pip install -r requirements-dev.txt)")
    def test_csr_mutation_always_caught():
        pass
