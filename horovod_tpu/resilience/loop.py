"""Preemption-aware training loop: drain, checkpoint, exit resumable.

TPU slices get preempted with a SIGTERM and a short grace window. The
reference Horovod dies mid-step and loses everything since the last manual
checkpoint; :func:`run` converts that into a classified, resumable outcome:

1. SIGTERM/SIGINT handlers (installed for the duration of the loop, previous
   handlers restored) set a flag; the loop checks it at every step boundary.
2. On preemption the loop *drains*: waits for the native core's queued
   collectives and blocks on the training state so no in-flight XLA program
   is cut mid-collective. Any registered weight publisher
   (:mod:`horovod_tpu.serving`) then flushes a final generation inside the
   remaining drain budget, so serving subscribers get the last good weights
   across the preemption.
3. It writes an **emergency checkpoint** via ``checkpoint.save`` (wrapped as
   ``{"step": N, "state": ...}``) and raises :class:`Preempted` — a
   ``SystemExit`` subclass whose code is :data:`RESUMABLE_EXIT_CODE` (75 =
   BSD ``EX_TEMPFAIL``), so an unguarded training script exits with the
   code launchers (``run/runner.py`` bounded restarts) read as
   "preempted, retry" rather than "failed".
4. On the next launch, :func:`run` (or :func:`resume_state`) restores the
   newest *valid* checkpoint and continues from the recorded step.

This module is stdlib-importable (the launcher imports
:data:`RESUMABLE_EXIT_CODE` without dragging in JAX); the data plane is
imported lazily inside :func:`run`.
"""

from __future__ import annotations

import logging
import os
import signal
import threading
import time
from typing import Any, Callable, Iterable, Optional, Tuple

from horovod_tpu.observability import metrics as _metrics
from horovod_tpu.resilience import chaos as _chaos, health as _health

__all__ = ["RESUMABLE_EXIT_CODE", "Preempted", "run", "resume_state"]

logger = logging.getLogger("horovod_tpu.resilience")

#: BSD EX_TEMPFAIL: "temporary failure, retry later" — distinct from every
#: code the stack otherwise produces (0 ok, 1/2 errors, 143 SIGTERM-kill),
#: so supervisors can tell "preempted, resume me" from "failed, debug me".
RESUMABLE_EXIT_CODE = 75

#: seconds to wait for the native core's queued collectives while draining
DRAIN_TIMEOUT_S = float(os.environ.get("HOROVOD_PREEMPT_DRAIN_TIMEOUT", "30"))


class Preempted(SystemExit):
    """Raised by :func:`run` after a preemption was drained and emergency-
    checkpointed. Subclasses ``SystemExit`` with :data:`RESUMABLE_EXIT_CODE`
    so an unguarded ``python train.py`` exits resumable; catch it to handle
    preemption in-process instead."""

    def __init__(self, step: int, checkpoint_path: Optional[str] = None,
                 signum: Optional[int] = None):
        super().__init__(RESUMABLE_EXIT_CODE)
        self.step = step
        self.checkpoint_path = checkpoint_path
        self.signum = signum

    def __str__(self):
        sig = (
            f" (signal {self.signum})" if self.signum is not None else ""
        )
        ckpt = (
            f"; emergency checkpoint at {self.checkpoint_path}"
            if self.checkpoint_path
            else "; no emergency checkpoint from this rank"
        )
        return f"preempted at step {self.step}{sig}{ckpt}"


def resume_state(checkpoint_dir: str) -> Optional[Tuple[int, Any]]:
    """``(next_step, state)`` from the newest valid checkpoint under
    `checkpoint_dir` written by :func:`run`, or None when there is none.
    Corrupt/incomplete step directories are skipped (``checkpoint.restore``
    falls back). Collective when ``process_size() > 1``: the root's
    filesystem decides the resume step for every rank, so a rank whose
    local disk lacks the checkpoint still joins the restore broadcast
    instead of silently starting fresh while its peers resume."""
    from horovod_tpu import basics, checkpoint

    multi = basics.is_initialized() and basics.process_size() > 1
    # only the broadcast root pays the CRC sweep of latest_step — every
    # other rank's answer would be discarded by the broadcast anyway
    step = (
        checkpoint.latest_step(checkpoint_dir)
        if not multi or basics.process_rank() == 0
        else None
    )
    if multi:
        from horovod_tpu.ops import collective as C

        step = C.broadcast_object(step, 0)
    if step is None:
        return None
    payload = checkpoint.restore(checkpoint_dir, step)
    # any input-pipeline cursor riding the payload is restored into the
    # loader registry here (pending until the loader registers on a cold
    # restart), so the resumed run draws the exact remaining sample
    # stream — docs/data.md
    payload = checkpoint.detach_data_state(payload)
    if isinstance(payload, dict) and "step" in payload and "state" in payload:
        return int(payload["step"]), payload["state"]
    # a checkpoint not written by run(): resume after its step number
    return step, payload


def _drain(state: Any, timeout_s: float = DRAIN_TIMEOUT_S) -> None:
    """Quiesce the data plane before checkpointing: wait out the native
    core's queued collectives (bounded), then block on the state arrays so
    the snapshot sees completed values, not in-flight buffers."""
    from horovod_tpu import basics

    core = basics._state.core
    if core is not None:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            try:
                if core.pending_count() == 0:
                    break
            except Exception:
                break
            time.sleep(0.01)
    try:
        import jax

        jax.block_until_ready(state)
    except Exception as e:
        # non-array state (or a dead backend) must not block the save
        logger.debug("pre-save state sync skipped: %s", e)


def run(
    step_fn: Callable[[Any, int], Any],
    state: Any,
    *,
    num_steps: int,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 0,
    start_step: Optional[int] = None,
    callbacks: Optional[Iterable] = None,
    signals: Tuple[int, ...] = (signal.SIGTERM, signal.SIGINT),
) -> Any:
    """Drive ``state = step_fn(state, i)`` for ``i in [start, num_steps)``
    with preemption awareness; returns the final state.

    - `checkpoint_dir`: enables resume (newest valid checkpoint is restored
      when `start_step` is None) and emergency checkpoints on preemption.
    - `checkpoint_every`: also checkpoint every N completed steps (0 = only
      on preemption).
    - `callbacks`: :class:`horovod_tpu.callbacks.Callback` objects; the loop
      fires ``on_batch_begin/on_batch_end`` per step and
      ``on_train_begin/on_train_end`` around the run.
    - `signals`: which signals mean "preempted" (default SIGTERM + SIGINT).
      Handlers are only installable on the main thread; elsewhere the loop
      still runs, relying on ``HOROVOD_CHAOS`` or an external flag for
      preemption testing.

    On preemption: drain → emergency checkpoint → raise :class:`Preempted`
    (a ``SystemExit`` carrying :data:`RESUMABLE_EXIT_CODE`). The chaos
    harness (``HOROVOD_CHAOS=sigterm_at_step=K``) delivers a real SIGTERM
    to this process before step K so the whole path is testable in-process.
    """
    first = start_step or 0
    if checkpoint_dir and start_step is None:
        resumed = resume_state(checkpoint_dir)
        if resumed is not None:
            first, state = resumed
            logger.info("resuming from checkpoint at step %d", first)
            if _metrics.enabled():
                _metrics.counter(
                    "resilience_resumes",
                    help="runs resumed from a checkpoint",
                ).inc()

    flag = threading.Event()
    draining = threading.Event()
    received = {"signum": None, "extra": 0}

    def _on_signal(signum, frame):
        # Signal latch: the handler ONLY ever sets flags/counters. Once the
        # drain → emergency-checkpoint sequence has begun, a second SIGTERM
        # (impatient supervisors escalate) must neither re-enter the drain
        # path nor interrupt the in-progress checkpoint write — it is
        # recorded and the first preemption keeps its grace window. The
        # handlers stay installed until _preempt() has completed, so the
        # default action (terminate, truncating the staged npz before its
        # atomic rename) can never fire mid-write.
        if draining.is_set():
            received["extra"] += 1
            return
        received["signum"] = signum
        flag.set()

    previous = {}
    if threading.current_thread() is threading.main_thread():
        for sig in signals:
            try:
                previous[sig] = signal.signal(sig, _on_signal)
            except (ValueError, OSError):  # pragma: no cover
                pass

    from horovod_tpu.callbacks import CallbackList

    cbs = CallbackList(list(callbacks or []))
    chaos_step = _chaos.sigterm_at_step() if _chaos.enabled() else None

    def _preempt(step: int) -> None:
        if draining.is_set():
            # non-reentrant: a second path into preemption (signal during
            # the final-step check, a callback raising) must not drain or
            # checkpoint again over the first pass's in-progress write
            raise Preempted(step, None, received["signum"])
        draining.set()
        drain_deadline = time.monotonic() + DRAIN_TIMEOUT_S
        _drain(state)
        # flush the observability record BEFORE the emergency checkpoint:
        # a preempted run used to keep its weights but lose its spans and
        # flight ring (clean shutdown() was the only flush path — and a
        # supervisor's escalation to SIGKILL never reaches it). Cheap and
        # bounded, so it rides inside the grace window ahead of the
        # checkpoint write.
        try:
            from horovod_tpu import basics as _basics
            from horovod_tpu.observability import flight as _flight

            _flight.record("preempt", step=step)
            _flight.flush()
            _basics.flush_timeline()
        except Exception:
            logger.debug(
                "observability flush during drain failed", exc_info=True)
        # final weight publication (best-effort, inside the remaining drain
        # budget): a preempted trainer's subscribers get the last good
        # generation instead of a staleness gap the length of the restart.
        # Before the emergency checkpoint — the publish is bounded and
        # lossy-safe where the checkpoint is neither.
        try:
            from horovod_tpu import serving as _serving

            if _serving.active_publishers():
                budget = max(0.5, drain_deadline - time.monotonic())
                flushed = _serving.flush_on_preempt(state, step, budget)
                if flushed:
                    logger.warning(
                        "flushed final weight publication from %d "
                        "publisher(s) before the emergency checkpoint",
                        flushed,
                    )
        except Exception:
            logger.warning(
                "final weight publication failed; continuing to the "
                "emergency checkpoint", exc_info=True,
            )
        path = None
        note = "(disabled)"
        state_finite = True
        save_state = state
        if checkpoint_dir:
            # a live state carrying NaN/Inf must NOT become the emergency
            # checkpoint: writing it would displace the newest VALID
            # checkpoint as the resume target (restore skips non-finite
            # checkpoints now, but not writing poison at all preserves
            # the retention budget and the operator's trust in `latest`).
            # ONE device→host snapshot serves both the sweep and the save
            # — the drain window races a supervisor's kill deadline, so
            # the state must not cross the bus twice. Only the WRITER
            # pays it at all: save() no-ops on every other rank, and a
            # non-writer burning its grace window on a full device→host
            # copy shrinks the writer's real budget for nothing.
            try:
                from horovod_tpu import checkpoint as _ckpt
                from horovod_tpu.resilience import numerics as _numerics
                from horovod_tpu.training import host_snapshot

                if _ckpt._is_writer():
                    save_state = host_snapshot(state)
                    if _numerics.checkpoint_finite_check_enabled():
                        state_finite = _numerics.tree_finite(save_state)
            except Exception as e:
                logger.debug("pre-save finiteness sweep skipped: %s", e)
                save_state = state
        if checkpoint_dir and not state_finite:
            note = "(skipped: live state is non-finite; newest valid " \
                   "checkpoint preserved)"
            logger.error(
                "emergency checkpoint at step %d skipped: the live state "
                "carries non-finite values", step,
            )
            if _metrics.enabled():
                _metrics.counter(
                    "resilience_emergency_checkpoint_skipped",
                    help="emergency checkpoints skipped because the live "
                         "state was non-finite",
                ).inc()
        elif checkpoint_dir:
            from horovod_tpu import basics, checkpoint

            # fence=False: on an asymmetric preemption (only this host got
            # SIGTERM) the peers are still training and would never join the
            # save's status broadcast — the grace window must not be spent
            # deadlocked in a collective
            saved = checkpoint.save(
                checkpoint_dir, step,
                checkpoint.attach_data_state(
                    {"step": step, "state": save_state}),
                force=True, fence=False,
            )
            # save() only stages anything on the writer (process rank 0);
            # a preempted non-root rank must not report — or count — a
            # checkpoint it never wrote
            if not basics.is_initialized() or basics.process_rank() == 0:
                path = saved
                note = path
                if _metrics.enabled():
                    _metrics.counter(
                        "resilience_emergency_checkpoints",
                        help="checkpoints written on preemption",
                    ).inc()
                    _metrics.gauge(
                        "resilience_last_checkpoint_step",
                        help="step of the most recent resilience checkpoint",
                    ).set(step)
            else:
                note = "(rank 0 is the writer)"
        if _metrics.enabled():
            _metrics.counter(
                "resilience_preemptions",
                help="preemption signals honored by the training loop",
            ).inc()
            if received["extra"]:
                _metrics.counter(
                    "resilience_extra_preempt_signals",
                    help="signals latched while draining/checkpointing",
                ).inc(received["extra"])
        if received["extra"]:
            logger.warning(
                "latched %d extra signal(s) during drain/checkpoint",
                received["extra"],
            )
        logger.warning(
            "preempted at step %d; emergency checkpoint: %s", step, note,
        )
        raise Preempted(step, path, received["signum"])

    try:
        cbs.on_train_begin()
        step = first
        for step in range(first, num_steps):
            if chaos_step is not None and step >= chaos_step:
                _chaos.consume_sigterm()
                chaos_step = None
                os.kill(os.getpid(), signal.SIGTERM)
                # the Python-level handler runs at the next bytecode
                # boundary; give it one explicit chance before the check
                time.sleep(0)
            if flag.is_set():
                _preempt(step)
            cbs.on_batch_begin(step)
            state = step_fn(state, step)
            _health.beat()
            cbs.on_batch_end(step)
            if (
                checkpoint_dir
                and checkpoint_every
                and (step + 1) % checkpoint_every == 0
                and step + 1 < num_steps
            ):
                from horovod_tpu import checkpoint

                _drain(state)
                checkpoint.save(
                    checkpoint_dir, step + 1,
                    checkpoint.attach_data_state(
                        {"step": step + 1, "state": state}),
                    force=True,
                )
                if _metrics.enabled():
                    _metrics.gauge(
                        "resilience_last_checkpoint_step",
                        help="step of the most recent resilience checkpoint",
                    ).set(step + 1)
        if flag.is_set():
            # the signal landed during the final step: still checkpoint so
            # the restart is a no-op resume instead of a silent rerun
            _preempt(num_steps)
        cbs.on_train_end()
        return state
    finally:
        for sig, handler in previous.items():
            try:
                signal.signal(sig, handler)
            except (ValueError, OSError):  # pragma: no cover
                pass
