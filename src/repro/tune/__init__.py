"""`repro.tune` — cache-model-guided autotuner with a persistent tuning DB.

The stack's performance knobs (TOCAB block size, balanced-schedule bins,
engine variant, Beamer α — the paper's Fig. 11 sensitivity axes) are graph-
and device-dependent; this package searches them per (graph fingerprint,
device kind, dtype) the way XLA/Triton autotune kernels:

* :mod:`repro.tune.space`    — declarative search space (:class:`Candidate`,
  :class:`SearchSpace`, trial budgets);
* :mod:`repro.tune.analytic` — cache-model pre-pass pruning candidates by
  predicted DRAM-per-edge before any timing;
* :mod:`repro.tune.runner`   — empirical trials (warmup + median-of-k via
  ``repro.obs`` spans, everything recorded);
* :mod:`repro.tune.db`       — schema-versioned JSON DB under
  ``experiments/tune/`` with an in-process plan cache;
* :mod:`repro.tune.plan`     — read side: the tuned engine arguments a
  caller passes to an entry (:func:`engine_kwargs`), tuned-layout builders
  for callers that can rebuild;
* :mod:`repro.tune.tuner`    — orchestration; ``python -m repro.tune``
  (``tune`` / ``show`` / ``apply``) is the CLI over the benchmark suite.
"""
from .space import (  # noqa: F401
    BUDGETS,
    Candidate,
    SearchSpace,
    TrialBudget,
    WORKLOADS,
    default_candidate,
)
from .db import DB_SCHEMA, db_path, default_dir, device_key, entry_key  # noqa: F401
from .plan import (  # noqa: F401
    TunedPlan,
    blocked_for,
    engine_kwargs,
    resolve_alpha,
    resolve_plan,
    resolve_schedule,
)
from .runner import Trial, run_trial  # noqa: F401
from .tuner import choose, tune, tune_graph  # noqa: F401
