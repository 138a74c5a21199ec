"""Process-wide resilience layer: fault injection and retries on IO paths.

A deployment has to survive a torn checkpoint, a corrupt tuning DB or a
flaky filesystem.  This package turns each such IO assumption into a
tested recovery path:

* :mod:`repro.resilience.chaos` — deterministic, seed-driven fault
  injection (``REPRO_CHAOS=<seed>:<rate>`` or programmatic
  :func:`~repro.resilience.chaos.inject`) with named sites in tuner
  trials, tune-DB and checkpoint IO, and the serve batch path.
* :mod:`repro.resilience.retry` — retry/backoff/timeout policies for
  checkpoint IO, tune-DB persistence, tuner trials, and serving.

Engine dispatch is not among them: the caller's ``schedule`` and ``impl``
are the engine, and a kernel that fails to lower or compile raises.

Everything records into :data:`repro.obs.metrics.registry` under the
``resilience.*`` metric names rather than printing ad-hoc warnings.
"""
from . import chaos, retry  # noqa: F401
from .chaos import ChaosError  # noqa: F401
from .retry import Policy  # noqa: F401
