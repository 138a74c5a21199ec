"""Benchmark harness: one function per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--only fig6]``
prints ``name,us_per_call,derived`` CSV rows on stdout and, per figure,
writes a schema-versioned ``BENCH_<fig>.json`` artifact (structured
records + run fingerprint + metric-registry snapshot) under
``experiments/bench/``.  Render or diff those with::

    python -m repro.obs.report experiments/bench/BENCH_fig6_pagerank.json \
        [--baseline old/BENCH_fig6_pagerank.json]

The roofline sweep (§Roofline) is separate — it needs 512 fake devices:
``PYTHONPATH=src python -m benchmarks.roofline``.
"""
import argparse
import os
import sys
import time

from repro.compile_cache import use_compile_cache
from repro.obs import export, trace as obs_trace
from repro.obs.metrics import registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_OUT = os.path.join(ROOT, "experiments", "bench")


def run_one(fn, out_dir: str) -> dict:
    """Run one figure function and write its BENCH_<name>.json artifact."""
    from . import common
    common.drain_records()
    with obs_trace.span(f"bench.{fn.__name__}"):
        fn()
    records = common.drain_records()
    payload = export.bench_payload(fn.__name__, records,
                                   metrics=registry.snapshot())
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"BENCH_{fn.__name__}.json")
    export.write_json(path, payload)
    print(f"# wrote {os.path.relpath(path, ROOT)} "
          f"({len(records)} records)", file=sys.stderr)
    return payload


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on benchmark fn names")
    ap.add_argument("--out-dir", default=DEFAULT_OUT,
                    help="directory for BENCH_<fig>.json artifacts")
    ap.add_argument("--list", action="store_true",
                    help="list benchmark fn names and exit")
    ap.add_argument("--chaos", default=None, metavar="SEED:RATE",
                    help="arm deterministic fault injection at the default "
                         "sites (repro.resilience.chaos) for the whole run")
    args = ap.parse_args()
    use_compile_cache()
    if args.chaos:
        from repro.resilience import chaos
        chaos.configure_spec(args.chaos)
    from . import paper_figs
    if args.list:
        for fn in paper_figs.ALL:
            doc = (fn.__doc__ or fn.__name__).splitlines()[0]
            print(f"{fn.__name__}: {doc}")
        return
    print("name,us_per_call,derived")
    t0 = time.time()
    for fn in paper_figs.ALL:
        if args.only and args.only not in fn.__name__:
            continue
        doc = (fn.__doc__ or fn.__name__).splitlines()[0]
        print(f"# --- {fn.__name__}: {doc}", file=sys.stderr)
        run_one(fn, args.out_dir)
    print(f"# total {time.time() - t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
