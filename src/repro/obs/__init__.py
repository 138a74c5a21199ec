"""Observability layer: process-wide metrics registry, nested tracing spans,
and machine-readable exporters.

The paper makes its whole argument through counters (Fig. 9 L2 miss rate,
Fig. 10 DRAM transactions/edge); this package makes the repo's equivalents —
plus runtime telemetry for every hot path (TOCAB engines, traversal,
training, serving) — first-class and uniformly exportable:

* :mod:`repro.obs.metrics` — labeled counters / gauges / histograms in one
  process-wide :data:`~repro.obs.metrics.registry`.
* :mod:`repro.obs.trace`  — nested span context managers emitting JSONL,
  with ``jax.block_until_ready`` attribution; each span is also a
  ``jax.profiler`` host annotation, on the device trace's clock.
* :mod:`repro.obs.export` — run fingerprint (jax version, backend, device
  count, git SHA) and schema-versioned BENCH JSON writers.
* :mod:`repro.obs.report` — ``python -m repro.obs.report BENCH_x.json
  [--baseline prior.json]`` renders tables and per-metric regression deltas.
"""
from . import export, metrics, trace  # noqa: F401
from .metrics import registry  # noqa: F401
from .trace import span  # noqa: F401
