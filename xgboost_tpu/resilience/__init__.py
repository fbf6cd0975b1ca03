"""Unified failure-handling layer (ISSUE 5 tentpole).

One policy for every fallible path in the package, replacing the ad-hoc
latches that used to live in ``predictor/``, ``data/quantile.py`` and
``tree/hist_kernel.py``:

- ``policy``     — failure classification (transient / resource /
  permanent), ``RetryPolicy`` with bounded retries + exponential backoff
  + deterministic jitter + deadlines, configured via ``XGBTPU_RETRY``;
- ``degrade``    — per-capability health state machine
  (HEALTHY → DEGRADED(retry-after-N) → DISABLED), lock-guarded, exported
  as ``degrade_state{capability}`` / ``faults_total{site,kind}`` metrics
  with trace spans on every transition;
- ``chaos``      — named-site fault injection (``XGBTPU_CHAOS``) with
  seeded deterministic schedules, generalizing ``utils/fault.py``;
- ``checkpoint`` — atomic (tmp+fsync+rename), checksummed checkpoints
  with previous-good fallback, backing ``train(..., resume_from=dir)``;
- ``watchdog``   — deadline guard around collective init / per-round
  dispatch (``XGBTPU_WATCHDOG``) that aborts cleanly instead of wedging.

See ``docs/resilience.md`` for the failure kinds, env grammar, chaos schedule
language and checkpoint format.
"""

from . import chaos, checkpoint, degrade, policy, watchdog  # noqa: F401
from .chaos import ChaosError  # noqa: F401
from .degrade import DEGRADED, DISABLED, HEALTHY  # noqa: F401
from .policy import (  # noqa: F401
    PERMANENT, RESOURCE, TRANSIENT, RetryPolicy, classify,
)
from .watchdog import WatchdogTimeout, watchdog as watchdog_ctx  # noqa: F401

__all__ = [
    "chaos", "checkpoint", "degrade", "policy", "watchdog",
    "ChaosError", "RetryPolicy", "WatchdogTimeout",
    "classify", "HEALTHY", "DEGRADED", "DISABLED",
    "TRANSIENT", "RESOURCE", "PERMANENT",
]
