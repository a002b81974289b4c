"""Fault-tolerance subsystem: classify, retry, or checkpoint — never just die.

The reference Horovod's failure story is a stall inspector that warns and
eventually kills the job (``HOROVOD_STALL_*``, mirrored in our native core).
Elastic Horovod / TorchElastic showed that surviving worker loss and
preemption is what makes data-parallel training production-grade; this
package is that layer for the TPU-native stack:

- :mod:`~horovod_tpu.resilience.health` — a process-wide health state
  machine (``HEALTHY → SUSPECT → DEGRADED → FATAL``) fed by the native
  core's cycle/stall signals and the retry layer, exposed through
  ``basics.health_state()`` and the rank-0 metrics endpoint (``/health``).
- :mod:`~horovod_tpu.resilience.retry` — the shared
  :class:`~horovod_tpu.resilience.retry.RetryPolicy` (exponential backoff +
  seeded jitter + total deadline, instrumented with
  ``resilience_retries``/``resilience_retry_exhausted`` counters) applied to
  rendezvous KV calls, worker restarts, and eager collective dispatch.
- :mod:`~horovod_tpu.resilience.loop` — the preemption-aware training loop
  :func:`~horovod_tpu.resilience.loop.run`: SIGTERM/SIGINT drain in-flight
  collectives, write an emergency checkpoint, and exit with the resumable
  exit code (:data:`RESUMABLE_EXIT_CODE`, 75 = ``EX_TEMPFAIL``) that
  launchers read as "preempted, retry".
- :mod:`~horovod_tpu.resilience.chaos` — the env-gated
  (``HOROVOD_CHAOS=...``) fault-injection harness that makes all of the
  above deterministically testable on CPU in tier-1.
- :mod:`~horovod_tpu.resilience.elastic` — elastic world-size training:
  KV-heartbeat membership with TTL, generation-numbered epochs, in-process
  mesh re-formation, ZeRO-1 state reshard, and rollback to the last
  committed host snapshot — rank loss/join without a job restart
  (:class:`~horovod_tpu.resilience.elastic.ElasticRun` /
  :func:`~horovod_tpu.resilience.elastic.run`).
- :mod:`~horovod_tpu.resilience.numerics` — the value-plane guard: in-jit
  per-step gradient/loss anomaly detection (finiteness + EWMA norm-spike,
  one fused reduction) with atomic step skip, dynamic loss scaling,
  bounded skip/replay via the elastic snapshot, corrupting-rank
  fingerprint quarantine → eviction, and the poison-free weight-publish
  gate. NOT imported here: it needs the data plane (jax) — import it as
  ``from horovod_tpu.resilience import numerics``.

Import hygiene: everything exported here is stdlib-only at import time (no
JAX, no device backend) so the launcher (``run/``) and standalone tools can
use it; :func:`run` imports the data plane lazily on first call.
"""

from __future__ import annotations

from horovod_tpu.resilience import chaos, elastic  # noqa: F401
from horovod_tpu.resilience.health import (  # noqa: F401
    HealthMonitor,
    HealthState,
    MONITOR,
    health_state,
)
from horovod_tpu.resilience.loop import (  # noqa: F401
    Preempted,
    RESUMABLE_EXIT_CODE,
    resume_state,
    run,
)
from horovod_tpu.resilience.retry import (  # noqa: F401
    RetryError,
    RetryPolicy,
    TransientError,
    policy_from_env,
)

__all__ = [
    "HealthMonitor",
    "HealthState",
    "MONITOR",
    "health_state",
    "Preempted",
    "RESUMABLE_EXIT_CODE",
    "resume_state",
    "run",
    "RetryError",
    "RetryPolicy",
    "TransientError",
    "policy_from_env",
    "chaos",
    "elastic",
]
