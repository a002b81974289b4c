"""Tracing/profiling surface — the TPU-native Timeline (SURVEY.md §5.1).

The reference writes a Chrome-tracing JSON from the C++ core's negotiation
and op phases (``common/timeline.{h,cc}``, enabled by ``HOROVOD_TIMELINE``,
coordinator-only). The rebuild has two complementary layers:

- **Negotiation timeline** — the native core (``csrc/``) writes the same
  chrome://tracing JSON for enqueue/negotiate/execute phases when
  ``HOROVOD_TIMELINE`` is set (see ``horovod_tpu/core.py``).
- **Device timeline** (this module) — on TPU the op execution itself lives
  inside XLA, invisible to a host-side tracer; the idiomatic tool is the XLA
  profiler. ``start_timeline``/``stop_timeline`` wrap ``jax.profiler`` so one
  call captures device traces (HLO steps, collective time on ICI, HBM
  transfers) viewable in TensorBoard/Perfetto — the role chrome://tracing
  plays for the reference.
"""

from __future__ import annotations

import contextlib
import os
from typing import Optional

import jax

_active_dir: Optional[str] = None


def start_timeline(log_dir: str) -> None:
    """Begin capturing a device trace into ``log_dir`` (analog of setting
    ``HOROVOD_TIMELINE``; reference ``operations.cc:404-411`` inits the
    Timeline on the coordinator only — call this on rank/process 0)."""
    global _active_dir
    if _active_dir is not None:
        raise RuntimeError(f"timeline already active in {_active_dir}")
    os.makedirs(log_dir, exist_ok=True)
    jax.profiler.start_trace(log_dir)
    _active_dir = log_dir


def stop_timeline() -> str:
    """Stop the capture; returns the trace directory."""
    global _active_dir
    if _active_dir is None:
        raise RuntimeError("no active timeline; call start_timeline first")
    jax.profiler.stop_trace()
    out, _active_dir = _active_dir, None
    return out


@contextlib.contextmanager
def timeline(log_dir: str):
    """Context-manager spelling::

        with hvd.profiler.timeline("/tmp/trace"):
            train_steps()
    """
    start_timeline(log_dir)
    try:
        yield log_dir
    finally:
        stop_timeline()


def annotate(name: str):
    """Named host-span annotation that shows up in the device trace
    (analog of the reference's per-tensor ACTIVITY spans,
    ``common/common.h:31-59``)."""
    return jax.profiler.TraceAnnotation(name)


# Peak bf16 matmul throughput per chip, FLOP/s, keyed by substrings of
# ``jax.Device.device_kind`` (first match wins) — the denominator for MFU
# reporting (used by ``bench.py`` and the benchmark examples). Sources:
# published TPU specs. Kinds with no entry (TPU7x: whether a device is a
# chip or half of one is not settled here) raise on a TPU backend.
_PEAK_BF16_FLOPS = (
    ("v6", 918e12),
    ("trillium", 918e12),
    ("v5e", 197e12),
    ("v5 lite", 197e12),
    ("v5litepod", 197e12),
    ("v5", 459e12),  # v5p reports "TPU v5"; the v5e spellings match above
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)

# Published per-chip HBM bandwidth (bytes/s) — denominator for the MFU
# probe's bandwidth-utilization figure.
_PEAK_HBM_BYTES = (
    ("v6", 1640e9),
    ("trillium", 1640e9),
    ("v5e", 819e9),
    ("v5 lite", 819e9),
    ("v5litepod", 819e9),
    ("v5", 2765e9),
    ("v4", 1228e9),
    ("v3", 900e9),
    ("v2", 700e9),
)


def timed_steps(run_one, n_steps: int, *, lag: int = 2):
    """Time ``n_steps`` calls of ``run_one()`` with a lagged device→host
    read; returns ``(fenced_values, dt_seconds)``.

    ``run_one`` executes one step (keeping its state in a closure) and
    returns a device scalar (typically the loss). Each returned scalar is
    fetched to the host — callers check the values — and transitively
    depends on the previous step's state, so fetching it forces every
    step up to that point; reading with a ``lag``-step delay keeps the
    device pipeline full (steps overlap the host sync) while the final
    drain forces the complete chain before the clock stops.
    """
    import collections
    import time

    fenced = []
    in_flight = collections.deque()
    t0 = time.perf_counter()
    for _ in range(n_steps):
        in_flight.append(run_one())
        if len(in_flight) > lag:
            fenced.append(float(in_flight.popleft()))
    while in_flight:
        fenced.append(float(in_flight.popleft()))
    return fenced, time.perf_counter() - t0


def _lookup_peak(table, device_kind: Optional[str]) -> Optional[float]:
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    kind = device_kind.lower()
    for key, peak in table:
        if key in kind:
            return peak
    if jax.default_backend() == "tpu":
        raise ValueError(
            f"no peak entry for TPU device_kind {device_kind!r}; add it to "
            f"the tables in horovod_tpu/profiler.py (known: "
            f"{', '.join(k for k, _ in table)})")
    return None


def device_peak_flops(device_kind: Optional[str] = None) -> Optional[float]:
    """Peak bf16 FLOP/s for a device kind (default: first local device).
    On a TPU backend an unknown kind raises — a utilization must never
    vanish silently on the hardware it is for; off TPU (e.g. ``cpu``)
    it returns None and callers skip MFU reporting."""
    return _lookup_peak(_PEAK_BF16_FLOPS, device_kind)


def device_peak_hbm_bytes(device_kind: Optional[str] = None) -> Optional[float]:
    """Published per-chip HBM bandwidth in bytes/s, same lookup
    convention as :func:`device_peak_flops`."""
    return _lookup_peak(_PEAK_HBM_BYTES, device_kind)
