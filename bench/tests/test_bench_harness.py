"""The harness: cells resolve to their files, the run refuses to measure
off the chip, and ``correct`` fails for the control and for each fault
planted in the timed path.  Runs a cell at scale 10 on the CPU, skipping
only the harness's look for a chip."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench import run as bench_run

ROOT = bench_run.ROOT
BENCHMARK = bench_run.read_json(os.path.join(ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
PEAKS = {"hbm_bytes_per_s": 819e9}


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_its_files(name):
    cell = bench_run.resolve_cell(name)
    assert cell.config["name"] == name.split(".")[0]
    for key in ("solve", "warmup_inputs", "steps", "answer", "reference",
                "compare", "least_bytes"):
        assert callable(getattr(cell.algorithm, key))
    # the cell's own checks: what compare reads on a small run is exactly
    # its traffic's limits
    assert cell.traffic["limits"]
    assert set(run_small(small_cell(name))["checks"]) == set(
        cell.traffic["limits"])
    e2e = {m["name"] for m in BENCHMARK["end_to_end"]}
    assert {n for n, _, _ in cell.end_to_end} == e2e
    assert "setup_s" in e2e
    assert cell.per_layer, "every cell reports a per-layer metric"
    for _, _, reader in cell.end_to_end + cell.per_layer:
        assert callable(reader.read)


def test_every_named_file_exists():
    for c in BENCHMARK["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        cfg = bench_run.read_json(os.path.join(ROOT, c["file"]))
        assert set(c["reduced"]) == set(cfg["reduced"])
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert os.path.isfile(os.path.join(
            bench_run.BENCH, "metrics", m["name"] + ".py")), m["name"]
    moved = {m["name"] for m in BENCHMARK["end_to_end"]}
    assert all(m["moves"] in moved for m in BENCHMARK["per_layer"])


RUN_ARGS = ["bench/run.py", "--workload", CELLS[0], "--seed", "3",
            "--seconds", "1", "--trace", "0"]
CALIBRATE_ARGS = ["bench/calibrate.py", "--workload", CELLS[0],
                  "--seeds", "3", "--control-seeds", "4"]


def run_cli(cwd, args=RUN_ARGS):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


@pytest.mark.parametrize("args", [RUN_ARGS, CALIBRATE_ARGS],
                         ids=["run", "calibrate"])
def test_run_without_tpu_exits_nonzero_and_prints_no_result(args):
    p = run_cli(ROOT, args)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not [ln for ln in p.stdout.splitlines()
                if ln.startswith(("{", "calibrate"))]


def test_run_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_cli(tmp_path)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def small_cell(name=CELLS[0]):
    cell = bench_run.resolve_cell(name)
    cell.config = dict(cell.config, scale=10)
    return cell


def run_small(cell, **kw):
    return bench_run.run_cell(cell, seed=2**31 + 11, seconds=0.0,
                              trace=False, peaks=PEAKS,
                              t_start=time.perf_counter(), **kw)


@pytest.mark.parametrize("name", CELLS)
def test_program_is_correct_at_small_size(name, capsys):
    cell = small_cell(name)
    result = run_small(cell)
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    for c in result["checks"].values():
        assert c["value"] <= c["limit"]
    assert set(result["metrics"]) == {"result_s", "setup_s"}  # no HBM on CPU
    json.dumps(result, allow_nan=False)
    err = capsys.readouterr().err
    for key in cell.traffic["limits"]:
        assert f"check {key}=" in err


def test_control_is_not_correct():
    """The reference in bfloat16, in the program's place, fails."""
    result = run_small(small_cell(), control=True)
    assert not result["correct"], result["checks"]


def _half_graph_solve(real):
    """The program run on every other arc of the graph."""
    cache = {}

    def solve(core, dg, layouts, params):
        if "g" not in cache:
            src, dst = np.asarray(dg.src), np.asarray(dg.dst)
            g = core.from_edges(dg.n, src[::2], dst[::2])
            cache["g"] = (core.DeviceGraph.from_host(g),
                          {"pull": core.build_blocked(g, block_size=8192)})
        return real(core, *cache["g"], params)

    return solve


def _altered(fault):
    def wrap(real):
        def solve(core, dg, layouts, params):
            rank, steps = real(core, dg, layouts, params)
            return fault(rank, steps)
        return solve
    return wrap


FAULTS = {
    # the answer altered where it is produced: the top vertex's rank doubled
    "rank_altered": _altered(
        lambda r, s: (r.at[jnp.argmax(r)].multiply(2.0), s)),
    # a step that returns its state unchanged: rank stays at 1/n
    "state_unchanged": _altered(
        lambda r, s: (jnp.full_like(r, 1.0 / r.shape[0]), s)),
    # one step more than the solve took
    "steps_altered": _altered(lambda r, s: (r, s + 1)),
    # half of the graph's arcs left out
    "half_the_arcs": _half_graph_solve,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_in_the_timed_path_is_not_correct(fault):
    cell = small_cell()
    real = cell.algorithm.solve
    calls = []

    def broken(*args):
        calls.append(1)
        return FAULTS[fault](real)(*args)

    cell.algorithm.solve = broken
    result = run_small(cell)
    assert calls, "the fault was not on the timed path"
    assert not result["correct"], (fault, result["checks"])


def test_traced_run_reports_per_layer_metrics():
    """Off the TPU the trace holds no device operation: the metrics read
    from it are left out, the others are reported."""
    cell = small_cell()
    result = bench_run.run_cell(cell, seed=9, seconds=0.0, trace=True,
                                peaks=PEAKS, t_start=time.perf_counter())
    assert result["correct"]
    names = {n for n, _, _ in cell.per_layer}
    assert set(result["metrics"]) == names - {
        "scatter_busy_share", "device_idle_share", "pagerank_roofline",
        "slab_gather_s", "slab_partials_s", "slab_reduce_s"}
    assert result["device"]["busy_s"] == 0
    assert result["device"]["window_s"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(result)[-1] == "checks"
