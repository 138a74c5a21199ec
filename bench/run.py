#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once, on the chip of this machine.

    python bench/run.py --workload <config>.<traffic> --seed N \\
        --seconds S --trace 0|1

Run from the root of a checkout that holds the program (``src/repro``).
A cell is a configuration (``bench/configs/<config>.json``: the graph)
under a traffic mix (``bench/traffic/<traffic>.json``: which algorithm,
with which parameters and limits); the algorithm's entry call, reference
and least bytes are in ``bench/algorithms/<algorithm>.py``, and each metric
is read by ``bench/metrics/<metric>.py``.  A cell of another algorithm
needs new files alone: its algorithm module, its traffic mix, its readers
and its entries in ``BENCHMARK.json``.

An algorithm module defines:

- ``LAYOUTS``: the directions of the blocked layouts its entry reads;
- ``solve(core, dg, layouts, params[, x])``: one timed call, on the
  device; ``steps(out)`` and ``answer(out)`` read its loop steps and its
  answer (to the host) from what it returns;
- ``warmup_inputs(dg, layouts)``: ``(dg, layouts[, x])`` of the timed
  call's shapes, on which a solve is short;
- ``reference(hg, params, at_steps[, xs])``: the plain reference on the
  host graph, for the kept solves' step counts;
- ``compare(answers, ref[, xs])``: ``{check: number}`` over the kept
  solves' ``(answer, steps)``; ``correct`` holds where each number is at
  most the traffic's ``limits`` of that name, and the keys are the limits';
- ``least_bytes(hg, steps[, x])``: the bytes a solve must move;
- optionally ``solve_inputs(hg, params)``: a function of the solve's index
  ``i`` (0, 1, ... over the window) that gives that solve's own input
  ``x``, such as a source, chosen in the drawn graph and mapped through
  ``hg.run_id`` (``bench/graph.py``), so that solve ``i`` does isomorphic
  work under every seed.  Only where it is defined do the calls above take
  the bracketed arguments: ``x`` is the solve's input and ``xs`` the kept
  solves' inputs, in the order of ``at_steps`` and ``answers``;
- optionally ``describe(ref)``: fields of the reference for the check log;
- optionally ``control_inputs(hg)`` and ``control(inputs, params[, x])``:
  the reference in a lower precision in the program's place, which
  ``bench/calibrate.py`` runs.

A metric module defines ``read(run)``, which returns the metric's value or
None where the run holds nothing to read.  ``run.scopes`` holds, in a
traced run, the device seconds of every program scope (``jax.named_scope``)
in the window by name (``bench/scopes.py``), and is None untraced.

Set-up (counted in ``setup_s`` from the start of the process): generate
the graph from the seed, build the blocked layouts the algorithm reads,
place them and the flat graph on the device, compile and run one short
warm-up solve.  The window then holds whole solves, back to back, and ends
with the first solve that completes at or after ``--seconds``.  After the
window the device state is freed and the solves' answers are compared with
a plain host reference; each number compared is printed beside its limit.

With ``--trace 0`` the result's metrics are the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  The last line of stdout is the JSON result.  Without a TPU, with
fewer chips than the cell asks for, or without the program's sources, it
exits non-zero before any work and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")
#: JAX's persistent compilation cache, at one fixed path in the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
#: fault injection and silent engine fallback would change what is timed
REFUSED_ENV = ("REPRO_CHAOS", "REPRO_RESILIENCE_FALLBACK")


def log(phase: str, **fields):
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def load_module(path: str):
    """Import a benchmark file by path (metric names may hold dots)."""
    name = "bench_" + os.path.relpath(path, BENCH).replace(os.sep, "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    name: str
    chips: int
    config: dict
    traffic: dict
    algorithm: types.ModuleType
    end_to_end: list  # [(name, unit, reader module)]
    per_layer: list


def resolve_cell(name: str, root: str = ROOT) -> Cell:
    """Find a cell's configuration, traffic mix, algorithm and metric
    readers by the names in ``BENCHMARK.json``, in the checkout at
    ``root``."""
    bench = read_json(os.path.join(root, "BENCHMARK.json"))
    here = os.path.join(root, "bench")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json; "
                         f"there are {sorted(cells)}")
    w = cells[name]
    config = {c["name"]: c for c in bench["configs"]}[w["config"]]
    traffic = read_json(os.path.join(here, "traffic", w["traffic"] + ".json"))

    def readers(metrics):
        return [(m["name"], m["unit"],
                 load_module(os.path.join(here, "metrics", m["name"] + ".py")))
                for m in metrics if name in m.get("workloads", [name])]

    return Cell(
        name=name, chips=int(w["chips"]),
        config=read_json(os.path.join(root, config["file"])),
        traffic=traffic,
        algorithm=load_module(os.path.join(
            here, "algorithms", traffic["algorithm"] + ".py")),
        end_to_end=readers(bench["end_to_end"]),
        per_layer=readers(bench["per_layer"]))


class CompileCounter:
    """Counts XLA compilations (loads from the persistent cache included)
    and the persistent cache's hits and misses, from JAX's monitoring
    events."""

    def __init__(self):
        import jax

        self.count = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_):
        if "backend_compile" in event:
            self.count += 1

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def fallback_series() -> int:
    """Series in the program's ``resilience.fallbacks`` counter (it is
    process-wide): each is an engine that degraded to another rung."""
    from repro.obs.metrics import registry

    snap = registry.snapshot().get("resilience.fallbacks")
    return len(snap["series"]) if snap and snap.get("series") else 0


def memory_peak(device) -> int | None:
    stats = device.memory_stats()
    return None if stats is None else int(stats["peak_bytes_in_use"])


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             peaks: dict, t_start: float, control: bool = False) -> dict:
    """Set up, run the window, check the answers and read the metrics.
    Returns the result object; prints the phase and check lines.

    ``control=True`` puts the algorithm's lower-precision control in the
    program's place (for calibrating limits, never in a benchmark run)."""
    import jax
    import numpy as np
    from repro import core
    from repro.configs.graphcage import GraphCageCfg

    from bench import scopes
    from bench import trace as trace_mod
    from bench.graph import make_graph, seed_words

    algo, params = cell.algorithm, cell.traffic["params"]
    device = jax.devices()[0]
    compiles = CompileCounter()
    fallbacks_before = fallback_series()
    phases, layout_facts = {}, {}

    block_size = GraphCageCfg().block_size
    t = time.perf_counter()
    hg = make_graph(cell.config, seed, block_size)
    phases["generate_s"] = time.perf_counter() - t
    log("setup", generate_s=phases["generate_s"], n=hg.n, arcs=hg.arcs)
    draw = (algo.solve_inputs(hg, params)
            if hasattr(algo, "solve_inputs") else None)

    def input_of(i: int) -> tuple:
        """Solve ``i``'s own input as the trailing arguments of the
        algorithm's calls: none where its solves take none."""
        return () if draw is None else (draw(i),)

    if control:
        inputs = jax.block_until_ready(algo.control_inputs(hg))

        def solve(*x):
            return algo.control(inputs, params, *x)

        def warmup():
            return solve(*input_of(0))
    else:
        g = core.Graph(n=hg.n, rowptr=hg.rowptr, colidx=hg.colidx)
        t = time.perf_counter()
        layouts = {d: core.build_blocked(g, block_size=block_size,
                                         direction=d) for d in algo.LAYOUTS}
        phases["build_blocked_s"] = time.perf_counter() - t
        log("setup", build_blocked_s=phases["build_blocked_s"],
            block_size=block_size)
        t = time.perf_counter()
        dg = core.DeviceGraph.from_host(g)
        jax.block_until_ready((dg, layouts))
        phases["place_s"] = time.perf_counter() - t
        log("setup", place_s=phases["place_s"])
        del g
        for d, b in layouts.items():
            layout_facts[d] = {"arcs": b.m, "slots": b.num_blocks * b.edge_budget}
            log("setup", layout=d, num_blocks=b.num_blocks,
                edge_budget=b.edge_budget, local_budget=b.local_budget,
                padding=b.padding_fraction())

        def solve(*x):
            return algo.solve(core, dg, layouts, params, *x)

        warm = algo.warmup_inputs(dg, layouts)

        def warmup():
            return algo.solve(core, warm[0], warm[1], params, *warm[2:])

    t = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.warmup"):
        jax.block_until_ready(warmup())
    phases["warmup_s"] = time.perf_counter() - t
    setup_s = time.perf_counter() - t_start
    warm = None
    log("setup", warmup_s=phases["warmup_s"], compiles=compiles.count,
        cache_hits=compiles.hits, cache_misses=compiles.misses,
        setup_s=setup_s)

    # keep the first solve, the last, and one drawn from the seed
    sample = int(np.random.default_rng(seed_words(seed)).integers(1, 8))
    kept, steps, xs, failed, dispatch_s = {}, [], [], 0, 0.0
    compiles_before = compiles.count
    tmp = tempfile.TemporaryDirectory(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(tmp.name)
    t0, cpu0 = time.perf_counter(), time.process_time()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            x = input_of(len(steps))
            with jax.profiler.TraceAnnotation("bench.solve"):
                t = time.perf_counter()
                out = solve(*x)
                dispatch_s += time.perf_counter() - t
                out = jax.block_until_ready(out)
            if len(steps) in (0, sample):
                kept[len(steps)] = out
            steps.append(algo.steps(out))
            xs.append(x)
            failed += fallback_series() > fallbacks_before
            t1 = time.perf_counter()
            if t1 - t0 >= seconds:
                break
    window_s, cpu_s = t1 - t0, time.process_time() - cpu0
    if trace:
        jax.profiler.stop_trace()
    compiles_in_window = compiles.count - compiles_before
    peak = memory_peak(device)
    # dispatch_s: host seconds until the solves' calls returned, before
    # their wait; cpu_s: the process's CPU seconds in the window
    log("window", solves=len(steps), window_s=window_s,
        steps=",".join(map(str, sorted(set(steps)))), failed=failed,
        compiles_in_window=compiles_in_window, memory_peak_bytes=peak,
        dispatch_s=dispatch_s, cpu_s=cpu_s)

    kept[len(steps) - 1] = out
    # answers to the host, then free the program's state before the
    # reference runs
    answers = [(algo.answer(o), algo.steps(o)) for o in kept.values()]
    kept_xs = () if draw is None else ([xs[i][0] for i in kept],)
    del out, kept, solve, warmup
    if control:
        del inputs
    else:
        del dg, layouts
    gc.collect()

    summary = scope_s = None
    if trace:
        events = trace_mod.load(tmp.name)
        summary = trace_mod.reduce(events, "bench.window")
        scope_s = scopes.by_scope(scopes.load(tmp.name),
                                  trace_mod.span(events, "bench.window"))
        tmp.cleanup()
        del events

    t = time.perf_counter()
    ref = algo.reference(hg, params, [k for _, k in answers], *kept_xs)
    numbers = algo.compare(answers, ref, *kept_xs)
    limits = cell.traffic["limits"]
    if set(numbers) != set(limits):
        raise RuntimeError(f"bench: {cell.name} compares {sorted(numbers)}, "
                           f"its traffic limits {sorted(limits)}")
    correct = all(v <= limits[k] for k, v in numbers.items())
    described = algo.describe(ref) if hasattr(algo, "describe") else {}
    log("check", reference_s=time.perf_counter() - t, **described,
        correct=correct)

    run = types.SimpleNamespace(
        setup_s=setup_s, phases=phases, window_s=window_s, solves=len(steps),
        steps=steps, memory_peak_bytes=peak, layouts=layout_facts,
        least_bytes=sum(algo.least_bytes(hg, k, *x)
                        for k, x in zip(steps, xs)),
        trace=summary, scopes=scope_s, peaks=peaks)
    metrics = {}
    for name, unit, reader in (cell.per_layer if trace else cell.end_to_end):
        value = reader.read(run)
        if value is not None:
            metrics[name] = {"value": value, "unit": unit}
    result = {
        "correct": bool(correct), "attempted": len(steps), "failed": failed,
        "metrics": metrics,
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": jax.device_count(), "memory_peak_bytes": peak},
    }
    if summary is not None:
        result["device"].update(busy_s=summary.busy_s,
                                window_s=summary.window_s)
        result["breakdown"] = summary.breakdown()
    result["checks"] = {
        k: {"value": v if math.isfinite(v) else None, "limit": limits[k]}
        for k, v in numbers.items()}
    for k, v in numbers.items():
        print(f"check {k}={v!r} limit={limits[k]!r}", file=sys.stderr,
              flush=True)
    return result


def open_cell(name: str) -> tuple[Cell, dict]:
    """Check the environment, resolve the cell and set up JAX on this
    machine's chips; returns the cell and its device's peaks.  Exits
    non-zero, before any work, without the program's sources, without a
    TPU or with fewer chips than the cell asks for."""
    for var in REFUSED_ENV:
        if os.environ.get(var):
            sys.exit(f"bench: {var} is set; the benchmark runs the program "
                     "with no fault injection and no silent fallback")
    if not os.path.isdir(os.path.join(SRC, "repro")):
        sys.exit(f"bench: the program's sources are not at {SRC}")
    sys.path[:0] = [ROOT, SRC]
    cell = resolve_cell(name)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench: no TPU (jax.devices()[0].platform is "
                 f"{devices[0].platform!r}); the benchmark runs on the chip")
    if len(devices) < cell.chips:
        sys.exit(f"bench: {cell.name} needs {cell.chips} chips, JAX finds "
                 f"{len(devices)}")
    peaks = read_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if devices[0].device_kind not in peaks:
        sys.exit(f"bench: no peaks for {devices[0].device_kind!r} in "
                 "bench/peaks.json")
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cell, peaks[devices[0].device_kind]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell, peaks = open_cell(args.workload)
    import jax

    log("setup", workload=cell.name, seed=args.seed,
        device=jax.devices()[0].device_kind, count=jax.device_count(),
        jax=jax.__version__)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      peaks, T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
