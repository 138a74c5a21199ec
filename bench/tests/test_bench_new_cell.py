"""A cell of a second algorithm, taken from new files alone: a checkout of
the benchmark with ``tests/data/reach`` laid over ``bench/`` and one more
workload in its ``BENCHMARK.json``, resolved and run by the harness at
scale 10 on the CPU.  Its algorithm has a per-solve input (a source), a
check of its own name and no control."""
import json
import os
import shutil
import time

import numpy as np
import pytest

from bench import calibrate
from bench import run as bench_run
from bench.graph import make_graph, seed_words
from repro.configs.graphcage import GraphCageCfg

NEW = os.path.join(os.path.dirname(__file__), "data", "reach")
CELL = "gap-kron21.reach"
PEAKS = {"hbm_bytes_per_s": 819e9}
SEED = 2**31 + 23
BLOCK = GraphCageCfg().block_size


@pytest.fixture
def cell(tmp_path):
    """The new cell, resolved in a checkout that adds files and one
    workload entry to the benchmark as it stands."""
    shutil.copy(os.path.join(bench_run.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(bench_run.BENCH, tmp_path / "bench", ignore=shutil.
                    ignore_patterns("__pycache__", "tests"))
    shutil.copytree(NEW, tmp_path / "bench", dirs_exist_ok=True)
    bench = bench_run.read_json(tmp_path / "BENCHMARK.json")
    bench["workloads"].append({
        "name": CELL, "config": "gap-kron21", "traffic": "reach", "chips": 1,
        "why": "reachability from a drawn source a solve"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = bench_run.resolve_cell(CELL, root=str(tmp_path))
    cell.config = dict(cell.config, scale=10)
    return cell


def run_small(cell, seconds=0.0, seed=SEED):
    return bench_run.run_cell(cell, seed=seed, seconds=seconds, trace=False,
                              peaks=PEAKS, t_start=time.perf_counter())


def test_new_cell_is_correct_and_its_inputs_reach_the_reference(
        cell, capsys):
    algo = cell.algorithm
    assert not hasattr(algo, "control")
    solved, referred = [], []
    solve, reference = algo.solve, algo.reference

    def traced_solve(core, dg, layouts, params, source):
        solved.append(source)
        return solve(core, dg, layouts, params, source)

    def traced_reference(g, params, at_steps, sources):
        referred.append(list(sources))
        return reference(g, params, at_steps, sources)

    algo.solve, algo.reference = traced_solve, traced_reference
    result = run_small(cell, seconds=0.5)
    assert result["correct"], result["checks"]
    assert set(result["checks"]) == {"depth_mismatches"}
    assert "check depth_mismatches=0.0 limit=0" in capsys.readouterr().err
    # the warm-up solve, then the window's, each from its own source
    window = solved[1:]
    assert result["attempted"] == len(window) >= 8
    draw = algo.solve_inputs(make_graph(cell.config, SEED, BLOCK),
                             cell.traffic["params"])
    assert window == [draw(i) for i in range(len(window))]
    assert len(set(window)) > 1
    # the first, the seed's and the last solve's sources, in that order
    sample = int(np.random.default_rng(seed_words(SEED)).integers(1, 8))
    assert referred == [[window[0], window[sample], window[-1]]]
    assert set(result["metrics"]) == {"result_s", "setup_s"}


def test_seeds_do_the_same_work_for_the_same_solve(cell):
    """Sources are chosen in the drawn graph: under two seeds solve ``i``
    starts at different ids and runs as many levels, with as many vertices
    at each depth."""
    from repro import core

    algo, params = cell.algorithm, cell.traffic["params"]
    runs = []
    for seed in (5, 2**33 + 7):
        hg = make_graph(cell.config, seed, BLOCK)
        draw = algo.solve_inputs(hg, params)
        dg = core.DeviceGraph.from_host(
            core.Graph(n=hg.n, rowptr=hg.rowptr, colidx=hg.colidx))
        outs = [algo.solve(core, dg, {}, params, draw(i)) for i in range(6)]
        runs.append(([draw(i) for i in range(6)],
                     [algo.steps(o) for o in outs],
                     [np.bincount(algo.answer(o) + 1) for o in outs]))
    (ids_a, steps_a, hist_a), (ids_b, steps_b, hist_b) = runs
    assert ids_a != ids_b
    assert steps_a == steps_b
    for x, y in zip(hist_a, hist_b):
        np.testing.assert_array_equal(x, y)


def _depth_altered(real):
    """The answer altered where it is produced: the source's depth moved."""
    def solve(core, dg, layouts, params, source):
        depth, levels = real(core, dg, layouts, params, source)
        return depth.at[source].add(1), levels
    return solve


def _source_shifted(real):
    """The solve run from another vertex than its input."""
    def solve(core, dg, layouts, params, source):
        return real(core, dg, layouts, params, (source + 1) % dg.n)
    return solve


FAULTS = {"depth_altered": _depth_altered, "source_shifted": _source_shifted}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_in_new_cell_is_not_correct(cell, fault):
    algo = cell.algorithm
    algo.solve = FAULTS[fault](algo.solve)
    result = run_small(cell)
    assert not result["correct"], (fault, result["checks"])
    assert result["checks"]["depth_mismatches"]["value"] >= 1


def test_calibrate_takes_the_new_cell_and_refuses_a_control(
        cell, monkeypatch, capsys):
    monkeypatch.setattr(bench_run, "open_cell", lambda name: (cell, PEAKS))
    with pytest.raises(SystemExit, match="no control"):
        calibrate.main(["--workload", CELL, "--seeds", "3",
                        "--control-seeds", "4"])
    assert calibrate.main(["--workload", CELL, "--seeds", "3,4"]) == 0
    [summary] = [ln for ln in capsys.readouterr().out.splitlines()
                 if ln.startswith("calibrate summary")]
    assert json.loads(summary.split(" ", 2)[2]) == {"depth_mismatches": {
        "program_max": 0.0, "control_min": None, "limit": 0}}
