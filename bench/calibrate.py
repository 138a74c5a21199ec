#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, in one process.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 \\
        --control-seeds 4,5,6 [--out calibrate.json]

For each of ``--seeds`` it runs the cell as ``run.py`` does, with a window
of one solve, and records each number compared; for each of
``--control-seeds`` it does the same with the algorithm's lower-precision
control in the program's place (refused for an algorithm that has none).
The limit of a number lies above the largest program reading and below the
smallest control reading.  The benchmark's own runs never run this.  Exits
non-zero without a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]

    cell, peaks = bench_run.open_cell(args.workload)
    if control_seeds and not hasattr(cell.algorithm, "control"):
        sys.exit(f"calibrate: {cell.name}'s algorithm has no control; "
                 "run it without --control-seeds")
    import jax

    rows = []
    for control, group in ((False, seeds), (True, control_seeds)):
        for seed in group:
            t = time.perf_counter()
            result = bench_run.run_cell(cell, seed, 0.0, False, peaks, t,
                                        control=control)
            rows.append({"seed": seed, "control": control,
                         "correct": result["correct"],
                         "checks": {k: c["value"] for k, c in
                                    result["checks"].items()}})
            print("calibrate", json.dumps(rows[-1]), flush=True)

    summary = {}
    for key in rows[0]["checks"] if rows else ():
        def readings(control):
            return [float("inf") if r["checks"][key] is None
                    else r["checks"][key]
                    for r in rows if r["control"] == control]

        summary[key] = {"program_max": max(readings(False), default=None),
                        "control_min": min(readings(True), default=None),
                        "limit": cell.traffic["limits"][key]}
    print("calibrate summary", json.dumps(summary), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": cell.name, "rows": rows,
                       "summary": summary, "device": jax.devices()[0].device_kind}, f,
                      indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
