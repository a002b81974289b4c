"""Elastic world-size training: survive rank loss/join without a restart.

The launcher's restart-in-place story (PR 2) covers whole-job cycles — every
rank preempted, every slot relaunched into a fresh rendezvous. What it could
not do (``run/runner.py`` said so outright) is *re-form the job at a new
world size* when one rank dies while its peers are still healthy. Elastic
Horovod and TorchElastic showed that preemption-heavy fleets need exactly
that; this module assembles it from the pieces the previous PRs built:

- **membership** rides the rendezvous KV server's heartbeat-scoped TTL keys
  (:class:`~horovod_tpu.run.rendezvous.KVStoreServer`): each rank refreshes
  ``/elastic/hb/<rank>``; a rank that stops (death, preemption) tombstones
  on TTL expiry and readers get
  :class:`~horovod_tpu.run.rendezvous.DeadRankError` instead of a burned
  deadline.
- **epochs** are generation numbers: every membership change bumps the
  generation, publishes the new member list, and fences on a per-generation
  ack barrier (:meth:`ElasticCoordinator.await_acks`) so no rank trains
  under a stale mesh.
- **re-formation** uses the now-idempotent ``hvd.shutdown() → hvd.init()``
  cycle (stale eager-kernel caches are dropped with the old mesh) to build
  a fresh mesh over the surviving ranks' devices — no process relaunch.
- **state** rolls back to the last *committed* step via an in-memory,
  host-offloaded snapshot (:func:`horovod_tpu.training.host_snapshot`) —
  a rank that died mid-step leaves the survivors' in-flight step
  unreproducible at the new size, so the resize replays from the snapshot —
  and the ZeRO-1 optimizer state is re-packed for the new world size with
  :func:`horovod_tpu.checkpoint.consolidate_opt_state`.
- **determinism**: the chaos charges ``rank_fail=N`` /
  ``rank_fail_at_step=K`` / ``rank_join_at_step=K`` drive the whole path on
  the 8-device CPU mesh in tier-1 (``tests/test_elastic.py``), including
  the pinned acceptance trajectory: shrink 8→6, allclose against a fresh
  6-rank run from the same snapshot, grow back 6→8.

Scope: the in-process resize is single-controller SPMD (one process owns
the mesh). Multi-controller jobs get elasticity at the launcher level
(``hvdrun --min-workers/--max-workers``): a permanently lost slot no longer
kills the job while the survivor count stays ≥ ``--min-workers``, and a
blacklisted host is re-admitted after ``HOROVOD_HOST_STRIKE_DECAY``.
"""

from __future__ import annotations

import json
import logging
import os
import time
from typing import Any, Callable, Iterable, List, Optional, Sequence

from horovod_tpu.observability import clock as _obs_clock
from horovod_tpu.observability import metrics as _metrics
from horovod_tpu.observability import straggler as _straggler
from horovod_tpu.resilience import chaos as _chaos, health as _health
from horovod_tpu.resilience import loop as _loop

__all__ = [
    "ElasticCoordinator",
    "ElasticRun",
    "WorldChanged",
    "WorldTooSmall",
    "run",
]

logger = logging.getLogger("horovod_tpu.resilience.elastic")

MIN_WORKERS_ENV = "HOROVOD_ELASTIC_MIN_WORKERS"
MAX_WORKERS_ENV = "HOROVOD_ELASTIC_MAX_WORKERS"

#: seconds the generation ack barrier waits before declaring the epoch dead
BARRIER_TIMEOUT_ENV = "HOROVOD_ELASTIC_BARRIER_TIMEOUT"


class WorldChanged(Exception):
    """Internal control flow: membership changed at `step`'s boundary; the
    elastic driver unwinds the inner training segment, re-forms the mesh
    over `alive`, and resumes. ``lost``/``joined`` carry the delta."""

    def __init__(self, step: int, alive: Sequence[int],
                 lost: Sequence[int] = (), joined: Sequence[int] = ()):
        self.step = step
        self.alive = tuple(alive)
        self.lost = tuple(lost)
        self.joined = tuple(joined)
        super().__init__(
            f"membership changed at step {step}: alive={list(alive)} "
            f"lost={list(lost)} joined={list(joined)}"
        )


class WorldTooSmall(RuntimeError):
    """Surviving ranks fell below ``min_workers``; the job cannot re-form.
    The driver wrote an emergency checkpoint (when a ``checkpoint_dir`` was
    given) before raising, so a relaunch resumes cleanly."""

    def __init__(self, alive: int, min_workers: int, step: int):
        self.alive = alive
        self.min_workers = min_workers
        self.step = step
        super().__init__(
            f"only {alive} rank(s) alive at step {step}, below "
            f"min_workers={min_workers}"
        )


class ElasticCoordinator:
    """Membership over the rendezvous KV plane: heartbeats, liveness,
    generation-numbered epochs with an ack barrier.

    Keys (all under ``/<scope>``):

    - ``/hb/<rank>`` — TTL'd heartbeat; expiry (or an explicit
      :meth:`mark_dead`) tombstones the rank.
    - ``/gen`` — the current epoch record: ``{"generation": G, "ranks":
      [...]}``; every resize rewrites it.
    - ``/ack/<G>/<rank>`` — the epoch barrier: a member acks generation G
      once it has re-formed; :meth:`await_acks` blocks for the full set and
      fails fast with :class:`DeadRankError` when a member dies
      mid-barrier instead of burning the deadline.

    Pass a started :class:`~horovod_tpu.run.rendezvous.KVStoreServer` to
    share the launcher's store; by default the coordinator owns a private,
    non-serving store (direct method calls — the single-controller case).
    """

    def __init__(self, server=None, *, ttl: Optional[float] = None,
                 scope: str = "elastic"):
        from horovod_tpu.run import rendezvous as _rdv

        self._rdv = _rdv
        self._own = server is None
        self._server = server if server is not None else _rdv.KVStoreServer()
        self._scope = "/" + scope.strip("/")
        self._ttl = ttl if ttl is not None else _rdv.default_heartbeat_ttl()
        self._generation = 0

    # ------------------------------------------------------------ liveness

    @property
    def server(self):
        return self._server

    @property
    def generation(self) -> int:
        return self._generation

    def _hb_key(self, rank: int) -> str:
        return f"{self._scope}/hb/{rank}"

    def heartbeat(self, rank: int) -> None:
        """Refresh `rank`'s liveness (also re-admits a tombstoned rank —
        the rejoin signal)."""
        self._server.put(self._hb_key(rank), b"1", ttl=self._ttl)

    def heartbeat_all(self, ranks: Iterable[int]) -> None:
        for r in ranks:
            self.heartbeat(r)

    def mark_dead(self, rank: int) -> None:
        """Explicitly tombstone `rank` (deterministic kill: the chaos path
        and controlled drains use this instead of waiting out the TTL)."""
        self._server.delete(self._hb_key(rank), tombstone=True)

    def alive(self) -> List[int]:
        """Ranks with unexpired heartbeats, ascending."""
        prefix = f"{self._scope}/hb/"
        out = []
        for k in self._server.live_keys(prefix):
            try:
                out.append(int(k[len(prefix):]))
            except ValueError:
                continue
        return sorted(out)

    # -------------------------------------------------------------- epochs

    def begin_generation(self, ranks: Sequence[int]) -> int:
        """Open a new epoch over `ranks`; returns its generation number.
        Mirrored into ``resilience_elastic_generation`` /
        ``resilience_elastic_world_size`` so the transition is observable
        from the metrics endpoint alone. Prior generations' ack-barrier
        keys are retired — every barrier on generation G has resolved
        before G+1 opens, and without the prune the store would grow by
        one key per member per resize forever."""
        if self._generation and hasattr(self._server, "prune"):
            self._server.prune(f"{self._scope}/ack/")
        self._generation += 1
        record = {"generation": self._generation, "ranks": sorted(ranks)}
        self._server.put(
            f"{self._scope}/gen", json.dumps(record).encode())
        if _metrics.enabled():
            _metrics.gauge(
                "resilience_elastic_generation",
                help="current elastic membership epoch",
            ).set(self._generation)
            _metrics.gauge(
                "resilience_elastic_world_size",
                help="ranks in the current elastic epoch",
            ).set(len(record["ranks"]))
        return self._generation

    def membership(self) -> Optional[dict]:
        """The current epoch record, or None before the first epoch."""
        blob = self._server.get(f"{self._scope}/gen")
        return None if blob is None else json.loads(blob)

    def ack(self, generation: int, rank: int) -> None:
        self._server.put(f"{self._scope}/ack/{generation}/{rank}", b"1")

    def await_acks(self, generation: int, ranks: Sequence[int],
                   timeout: Optional[float] = None) -> None:
        """Epoch barrier: block until every rank in `ranks` acked
        `generation`. A member dying mid-barrier raises
        :class:`~horovod_tpu.run.rendezvous.DeadRankError` with its rank id
        immediately (heartbeat-scoped fast-fail), so the caller can drop it
        and open the next epoch rather than waiting out the deadline."""
        if timeout is None:
            timeout = float(os.environ.get(BARRIER_TIMEOUT_ENV, "60"))
        self._server.wait_for(
            [f"{self._scope}/ack/{generation}/{r}" for r in ranks],
            timeout=timeout,
            hb_scope=f"{self._scope}/hb",
        )

    def close(self) -> None:
        if self._own:
            try:
                self._server.close()
            except Exception as e:
                logger.debug("KV server close failed: %s", e)


def _default_reshard(state: Any, new_size: int) -> Any:
    """Re-pack a state pytree for `new_size` ranks: a dict carrying
    ``params`` + ``opt_state`` gets its optimizer state consolidated
    (ZeRO-1 ``[N, shard]`` leaves re-packed, EF residual mass preserved;
    plain states pass through untouched — ``consolidate_opt_state`` is safe
    on any optimizer state). Everything else is returned as-is: replicated
    DP state is world-size-independent by construction."""
    if isinstance(state, dict) and "opt_state" in state and "params" in state:
        from horovod_tpu import checkpoint as _checkpoint

        out = dict(state)
        out["opt_state"] = _checkpoint.consolidate_opt_state(
            out["opt_state"], out["params"], to_size=new_size)
        return out
    return state


class ElasticRun:
    """The elastic driver: wraps :func:`horovod_tpu.resilience.run` in
    membership epochs. Each epoch trains under one world size; a membership
    change unwinds the inner loop, re-forms the mesh, reshards state, and
    re-enters. See :func:`run` for the functional spelling and argument
    docs."""

    def __init__(
        self,
        step_builder: Callable[[int], Callable[[Any, int], Any]],
        *,
        min_workers: Optional[int] = None,
        max_workers: Optional[int] = None,
        snapshot_every: int = 1,
        reshard_fn: Optional[Callable[[Any, int], Any]] = None,
        coordinator: Optional[ElasticCoordinator] = None,
        devices: Optional[Sequence] = None,
        publisher=None,
        publish_every: int = 0,
    ):
        if min_workers is None:
            min_workers = int(os.environ.get(MIN_WORKERS_ENV, "1"))
        self._step_builder = step_builder
        self._min_workers = max(1, min_workers)
        self._max_workers = max_workers
        self._snapshot_every = max(1, snapshot_every)
        self._reshard = reshard_fn or _default_reshard
        self._coord = coordinator
        self._own_coord = coordinator is None
        self._devices = list(devices) if devices is not None else None
        self._publisher = publisher
        self._publish_every = max(0, publish_every)
        self._alive: List[int] = []
        self._failed: List[int] = []
        self._committed_step = 0
        self._committed: Any = None
        #: input-pipeline cursors snapshotted WITH the committed state: a
        #: rollback that rewinds the weights must rewind the sample
        #: stream to the same boundary or the replay consumes the wrong
        #: batches (docs/data.md)
        self._committed_cursors: dict = {}
        self._published_step: Optional[int] = None
        self._has_guard: Optional[bool] = None  # lazily probed once
        #: (step, staged verdict) read one boundary late on non-commit
        #: steps — the guard's observability without fencing every step
        self._staged: Optional[tuple] = None
        self._numerics_rollbacks = 0
        self._recovering_until: Optional[int] = None
        self._warned_unevictable: set = set()

    # ----------------------------------------------------------- internals

    def _form(self, ranks: Sequence[int]) -> None:
        """(Re-)build the mesh over `ranks`' devices on this live process —
        the no-relaunch membership change. Rank r keeps device r, so a
        survivor's device assignment is stable across generations."""
        from horovod_tpu import basics

        if basics.is_initialized():
            if basics.process_size() > 1:
                raise NotImplementedError(
                    "in-process elastic resize is single-controller only; "
                    "multi-process jobs are resized at the launcher "
                    "(hvdrun --min-workers/--max-workers)"
                )
            basics.shutdown()
        basics.init(devices=[self._devices[r] for r in ranks])

    def _poll_membership(self, step: int) -> None:
        """Step-boundary membership sweep: refresh survivors' heartbeats,
        fire any armed chaos charges, and compare the KV liveness view with
        the current epoch. Raises :class:`WorldChanged` on a delta."""
        coord = self._coord
        coord.heartbeat_all(self._alive)
        # quarantine eviction: a rank the numerics cross-check flagged as
        # publishing corrupt gradient fingerprints is tombstoned here —
        # the same 8→7 shrink path a dead rank takes (never rank 0, the
        # driver). Lazy import: this module must stay stdlib at import.
        from horovod_tpu.resilience import numerics as _numerics

        unevictable = []
        retry = []
        for r in _numerics.take_corrupt_ranks():
            if r == 0:
                # the driver cannot tombstone itself — but the publish
                # gate must STAY closed, so the verdict goes back in the
                # quarantine set instead of silently draining
                unevictable.append(r)
            elif r in self._alive:
                logger.warning(
                    "elastic: evicting numerically corrupt rank %d", r)
                try:
                    coord.mark_dead(r)
                except Exception as e:
                    # a transient KV error must NOT lose the verdict: the
                    # publish gate keys on quarantine_pending(), so a
                    # drained-but-unevicted rank would re-open publication
                    # from a fleet that still contains it. Requeue and
                    # retry at the next boundary sweep.
                    retry.append(r)
                    logger.warning(
                        "elastic: eviction of corrupt rank %d failed "
                        "(%s); requeued for the next sweep", r, e)
            # a rank no longer alive was already evicted/dead: drop it
        if retry:
            _numerics.requeue_corrupt_ranks(retry)
        if unevictable:
            _numerics.requeue_corrupt_ranks(unevictable)
            for r in set(unevictable) - self._warned_unevictable:
                self._warned_unevictable.add(r)
                logger.error(
                    "elastic: rank %d flagged numerically corrupt but "
                    "cannot be evicted (single-controller driver); "
                    "weight publication stays gated until "
                    "numerics.clear_quarantine()", r)
        # hung-rank eviction (HOROVOD_HANG_EVICT=1): a rank the hang
        # diagnosis named missing is tombstoned like a corrupt one — the
        # survivors re-form smaller instead of waiting forever
        from horovod_tpu.observability import flight as _flight

        hung_retry = []
        for r in _flight.take_hung_ranks():
            if r != 0 and r in self._alive:
                logger.warning("elastic: evicting hung rank %d", r)
                try:
                    coord.mark_dead(r)
                except Exception as e:
                    # a transient KV error must NOT lose the verdict: the
                    # watchdog will not re-derive it for the same stall
                    # (one firing per episode), so requeue for the next
                    # sweep — the corrupt-rank convention above
                    hung_retry.append(r)
                    logger.warning(
                        "elastic: eviction of hung rank %d failed (%s); "
                        "requeued for the next sweep", r, e)
        if hung_retry:
            _flight.requeue_hung_ranks(hung_retry)
        if _chaos.enabled():
            n_fail = _chaos.take_rank_fail(step)
            if n_fail:
                # highest ranks first, never rank 0 (the driver)
                victims = [r for r in sorted(self._alive) if r != 0][-n_fail:]
                for r in victims:
                    coord.mark_dead(r)
            # check _failed FIRST: take_rank_join pops the charge, and a
            # join armed at/before the fail step must stay armed until
            # there is actually someone to re-admit
            if self._failed and _chaos.take_rank_join(step):
                for r in self._failed:
                    coord.heartbeat(r)  # rejoin = heartbeat resumes
        alive = coord.alive()
        # a heartbeat from a rank this controller has no device for (a
        # shared store serving several parties, a stray key) must be
        # ignored, not crash _form with an IndexError later
        known = [r for r in alive if 0 <= r < len(self._devices)]
        if len(known) < len(alive):
            logger.warning(
                "elastic: ignoring heartbeats for unknown ranks %s "
                "(have %d devices)",
                sorted(set(alive) - set(known)), len(self._devices),
            )
        alive = known
        if self._max_workers is not None:
            alive = alive[: self._max_workers]
        if set(alive) != set(self._alive):
            lost = sorted(set(self._alive) - set(alive))
            joined = sorted(set(alive) - set(self._alive))
            for r in lost:
                _health.record_rank_lost(r)
            raise WorldChanged(step, alive, lost, joined)

    def _sync_observability(self, gen: int) -> None:
        """Re-anchor the fleet-observability layer on an epoch boundary:
        collective correlation keys carry the new generation (keys never
        collide across epochs) and the clock offset vs the KV server is
        re-estimated — a resize is exactly when the host set (and with it
        the skew picture) may have changed. Best-effort: observability
        must never fail a resize."""
        _straggler.set_generation(gen)
        try:
            from horovod_tpu.observability import flight as _flight

            _flight.record(
                "epoch", generation=int(gen), alive=list(self._alive),
            )
        except Exception as e:
            logger.debug("flight epoch event skipped: %s", e)
        try:
            from horovod_tpu import basics as _basics

            rank = (
                _basics.process_rank() if _basics.is_initialized() else 0
            )
            _obs_clock.refresh_from_kv(
                self._coord.server, rank=rank, generation=gen)
        except Exception as e:
            logger.debug("post-resize clock re-sync failed: %s", e)

    def _commit(self, step: int, state: Any) -> None:
        from horovod_tpu.training import host_snapshot

        self._committed_step = step
        self._committed = host_snapshot(state)
        try:
            from horovod_tpu.data import sampler as _data_sampler

            self._committed_cursors = _data_sampler.export_state()
        except Exception as e:
            logger.debug("loader cursor commit skipped: %s", e)

    def _restore_cursors(self) -> None:
        """Rewind every registered loader to the committed boundary (the
        state just rolled back there). Best-effort: a run without a
        registered loader has nothing to rewind."""
        try:
            from horovod_tpu.data import sampler as _data_sampler

            _data_sampler.restore_state(self._committed_cursors)
        except Exception as e:
            logger.debug("loader cursor rollback skipped: %s", e)

    def _wrap(self, step_fn):
        def wrapped(state, step):
            from horovod_tpu.resilience import numerics as _numerics

            # this wrapper owns the fingerprint boundary (authoritative
            # step numbering across resizes/rollbacks); the generic
            # InstrumentedStep hook inside step_fn stands down
            _numerics.claim_boundary()
            self._poll_membership(step)
            out = step_fn(state, step)
            # numerics policy: read the guard verdict carried in the
            # state (probed once — states without a guard never pay the
            # boundary sync), publish/cross-check the fingerprint, and
            # escalate a bad streak to a rollback
            if self._has_guard is None:
                self._has_guard = bool(_numerics.find_guard_states(out))
            v = None
            if self._has_guard:
                committing = (step + 1) % self._snapshot_every == 0
                if _numerics.fingerprint_enabled() or committing:
                    # exact (synchronous) read: the per-step fingerprint
                    # plane needs THIS step's record, and a commit must
                    # be gated on THIS step's verdict (never snapshot
                    # mid-incident). Drain any staged verdict first so
                    # its chaos accounting and gauges are not lost.
                    if self._staged is not None:
                        _numerics.note_step_staged(*self._staged)
                        self._staged = None
                    v = _numerics.note_step(step, out)
                else:
                    # lagged read, one boundary late: fence on the
                    # PREVIOUS step's staged scalars while this step
                    # still runs in the background — a synchronous read
                    # here blocks the host on every step's completion
                    # and destroys async-dispatch pipelining in the hot
                    # loop. The rollback policy already tolerates
                    # MAX_BAD steps of latency, so a one-step-late
                    # verdict is safe.
                    if self._staged is not None:
                        v = _numerics.note_step_staged(*self._staged)
                    self._staged = (step, _numerics.stage_verdict(out))
            if _numerics.fingerprint_enabled():
                _numerics.boundary(step)
            if v is not None and v["bad_streak"] >= \
                    _numerics.max_consecutive_bad():
                raise _numerics.NumericsRollback(step, v["bad_streak"])
            bad_now = v is not None and v["bad_streak"] > 0
            if (step + 1) % self._snapshot_every == 0 and not bad_now:
                # never commit a mid-incident snapshot: rolling back to a
                # state whose guard already counts a bad streak would
                # re-trigger the rollback it is recovering from
                self._commit(step + 1, out)
                if (
                    self._recovering_until is not None
                    and step + 1 > self._recovering_until
                ):
                    # sound progress COMMITTED past the incident that
                    # forced the last rollback: the budget guards against
                    # rollbacks *without* progress, so it resets here —
                    # isolated transient incidents days apart must not
                    # accumulate into a FATAL
                    self._numerics_rollbacks = 0
                    self._recovering_until = None
            self._maybe_publish(step + 1)
            return out

        return wrapped

    def _maybe_publish(self, step: int) -> None:
        """Publish the COMMITTED snapshot on the publish cadence — the
        consolidated state (host-offloaded, reshard-safe), not the live
        device tree, so a publication is always replayable after a resize.
        A fence abort here means a concurrent party resized under us; the
        resize path republishes, so it is not an error."""
        if self._publisher is None or self._publish_every <= 0:
            return
        if step % self._publish_every or self._committed is None:
            return
        if self._committed_step == self._published_step:
            # snapshot_every > publish_every: the committed tree has not
            # moved since the last publication — re-publishing it would
            # mint identical generations and reset subscriber staleness
            # for weights that never changed
            return
        from horovod_tpu import serving as _serving

        try:
            self._publisher.publish(self._committed, self._committed_step)
            self._published_step = self._committed_step
        except _serving.PublishAborted as e:
            logger.warning("publication fenced off mid-resize: %s", e)
        except _serving.PublishError as e:
            logger.warning(
                "weight publication at step %d failed: %s", step, e)

    def _resize(self, wc: WorldChanged):
        """Handle one membership change: rollback to the last committed
        snapshot, mesh re-formation, state reshard, epoch barrier. Returns
        ``(state, next_step)``.

        Both directions resume from the committed snapshot: on a loss the
        interrupted step is unreproducible at the old size, and on a join
        the snapshot IS the boundary state (with ``snapshot_every=1``
        nothing is replayed) — the one source of truth keeps the
        post-resize trajectory bit-deterministic."""
        t0 = time.monotonic()
        alive = list(wc.alive)
        if len(alive) < self._min_workers:
            raise WorldTooSmall(len(alive), self._min_workers, wc.step)
        state = self._committed
        next_step = self._committed_step
        if wc.lost:
            self._failed = sorted(set(self._failed) | set(wc.lost))
        if wc.joined:
            self._failed = [r for r in self._failed if r not in wc.joined]
        if _metrics.enabled() and wc.step > next_step:
            _metrics.counter(
                "resilience_elastic_rollback_steps",
                help="steps replayed after rolling back to the last "
                     "committed snapshot",
            ).inc(wc.step - next_step)
        old_size = len(self._alive)
        self._alive = alive
        self._form(alive)
        state = self._reshard(state, len(alive))
        # the sample stream rolls back WITH the state, and the loaders
        # are fenced on the same generation as the mesh: the survivors
        # repartition the remaining epoch under the new world size with
        # no sample dropped and none double-visited (docs/data.md)
        self._restore_cursors()
        gen = self._coord.begin_generation(alive)
        for r in alive:
            self._coord.ack(gen, r)
        self._coord.await_acks(gen, alive)
        try:
            from horovod_tpu.data import sampler as _data_sampler

            _data_sampler.generation_fence(gen, len(alive))
        except Exception as e:
            logger.debug("loader generation fence skipped: %s", e)
        self._sync_observability(gen)
        dt = time.monotonic() - t0
        if _metrics.enabled():
            _metrics.counter(
                "resilience_elastic_membership_changes",
                help="elastic resizes by direction",
                kind="grow" if len(alive) > old_size else "shrink",
            ).inc()
            _metrics.histogram(
                "resilience_elastic_resize_seconds",
                help="wall time of one membership change (rollback + mesh "
                     "re-formation + reshard + epoch barrier)",
            ).observe(dt)
        logger.warning(
            "elastic: resized to world size %d (generation %d, lost=%s "
            "joined=%s) in %.3fs",
            len(alive), gen, list(wc.lost), list(wc.joined), dt,
        )
        if self._publisher is not None and self._published_step != next_step:
            # republish from the post-resize consolidated state: any
            # generation the fence aborted mid-resize is superseded here,
            # and subscribers see the exact weights the replayed steps
            # start from (off-cadence on purpose — the resize IS the
            # event; skipped only when this exact committed step already
            # published, e.g. a resize landing right on the cadence)
            from horovod_tpu import serving as _serving

            try:
                self._publisher.publish(state, next_step)
                self._published_step = next_step
            except _serving.PublishError as e:
                logger.warning(
                    "post-resize weight publication failed: %s", e)
        return state, next_step

    def _numerics_rollback(self, nr):
        """Handle one :class:`numerics.NumericsRollback`: replay from the
        last committed snapshot with a FRESH data epoch (the replay salt
        data pipelines fold into batch selection), bounded by
        ``HOROVOD_NUMERICS_MAX_ROLLBACKS``. Exhausting the budget is
        FATAL — the run cannot make numerically sound progress."""
        from horovod_tpu.resilience import numerics as _numerics

        self._numerics_rollbacks += 1
        if self._numerics_rollbacks > _numerics.max_rollbacks():
            _health.record_fatal(
                f"numerics rollback budget exhausted "
                f"({self._numerics_rollbacks - 1} rollbacks)"
            )
            raise _numerics.NumericsError(
                f"still seeing {nr.streak} consecutive bad steps after "
                f"{self._numerics_rollbacks - 1} rollback(s); giving up"
            ) from nr
        if self._committed is None:
            _health.record_fatal("numerics rollback with no snapshot")
            raise _numerics.NumericsError(
                "consecutive bad steps before any committed snapshot"
            ) from nr
        self._recovering_until = nr.step + 1
        epoch = _numerics.bump_replay_epoch()
        if _metrics.enabled():
            _metrics.counter(
                "numerics_rollbacks",
                help="rollbacks to the committed snapshot forced by "
                     "consecutive bad steps",
            ).inc()
            if nr.step >= self._committed_step:
                _metrics.counter(
                    "numerics_rollback_steps",
                    help="steps replayed after a numerics rollback",
                ).inc(nr.step + 1 - self._committed_step)
        logger.warning(
            "numerics: %d consecutive bad steps at step %d; rolling back "
            "to committed step %d (replay epoch %d)",
            nr.streak, nr.step, self._committed_step, epoch,
        )
        # rewind the sample cursors to the committed boundary; the bumped
        # replay epoch (folded into batch selection by the loader) makes
        # the replayed steps draw FRESH batches from that same cursor
        self._restore_cursors()
        return self._committed, self._committed_step

    # -------------------------------------------------------------- driver

    def run(
        self,
        state: Any,
        *,
        num_steps: int,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: int = 0,
        callbacks: Optional[Iterable] = None,
    ) -> Any:
        import jax

        from horovod_tpu import basics

        if self._devices is None:
            self._devices = list(jax.devices())
        cap = self._max_workers or int(
            os.environ.get(MAX_WORKERS_ENV, "0")
        ) or len(self._devices)
        self._max_workers = min(cap, len(self._devices))
        if self._coord is None:
            self._coord = ElasticCoordinator()
        if self._publisher is not None and self._publisher.fence_fn is None:
            # the elastic generation IS the publish fence: a resize bumps
            # it, aborting any in-flight generation before it can commit
            self._publisher.fence_fn = lambda: self._coord.generation

        # everything past coordinator creation sits inside the try: a
        # failed initial formation or a bad checkpoint dir must not leak
        # the owned coordinator's bound socket
        try:
            # initial formation at full strength (bounded by max_workers);
            # the admissible band applies from step 0, not just on
            # resizes — a host that cannot field min_workers must error,
            # not silently train below the floor for the whole run
            self._alive = list(range(self._max_workers))
            if len(self._alive) < self._min_workers:
                raise WorldTooSmall(
                    len(self._alive), self._min_workers, 0)
            if not (
                basics.is_initialized()
                and basics.size() == len(self._alive)
            ):
                self._form(self._alive)
            self._coord.heartbeat_all(self._alive)
            gen = self._coord.begin_generation(self._alive)
            for r in self._alive:
                self._coord.ack(gen, r)
            self._coord.await_acks(gen, self._alive)
            try:
                from horovod_tpu.data import sampler as _data_sampler

                _data_sampler.generation_fence(gen, len(self._alive))
            except Exception as e:
                logger.debug("loader generation fence skipped: %s", e)
            self._sync_observability(gen)

            next_step = 0
            if checkpoint_dir:
                resumed = _loop.resume_state(checkpoint_dir)
                if resumed is not None:
                    next_step, state = resumed
                    state = self._reshard(state, len(self._alive))
                    logger.info(
                        "elastic: resumed from checkpoint at step %d",
                        next_step)
            self._commit(next_step, state)

            from horovod_tpu.resilience import numerics as _numerics

            built_for: Optional[tuple] = None  # membership the fn targets
            step_fn = None
            while True:
                # key the cache on MEMBERSHIP, not count: a simultaneous
                # loss+join keeps the size but re-forms the mesh over a
                # different device set — only a numerics rollback (same
                # membership, replay) may reuse the compiled step
                membership = tuple(self._alive)
                if step_fn is None or built_for != membership:
                    step_fn = self._step_builder(len(self._alive))
                    built_for = membership
                try:
                    return _loop.run(
                        self._wrap(step_fn),
                        state,
                        num_steps=num_steps,
                        checkpoint_dir=checkpoint_dir,
                        checkpoint_every=checkpoint_every,
                        start_step=next_step,
                        callbacks=callbacks,
                    )
                except WorldChanged as wc:
                    # a staged verdict from the broken mesh / abandoned
                    # trajectory must not be read against the new one
                    self._staged = None
                    state, next_step = self._resize(wc)
                except _numerics.NumericsRollback as nr:
                    self._staged = None
                    state, next_step = self._numerics_rollback(nr)
        except WorldTooSmall:
            # _committed is None when the floor broke before any snapshot
            # (initial formation): nothing to save, just surface the error
            if checkpoint_dir and self._committed is not None:
                from horovod_tpu import checkpoint as _checkpoint

                _checkpoint.save(
                    checkpoint_dir, self._committed_step,
                    _checkpoint.attach_data_state(
                        {"step": self._committed_step,
                         "state": self._committed},
                        cursors=self._committed_cursors,
                    ),
                    force=True, fence=False,
                )
            raise
        finally:
            # hand the fingerprint boundary back: a standalone
            # InstrumentedStep loop after this run must publish again
            from horovod_tpu.resilience import numerics as _numerics

            if self._staged is not None:
                # the LAST step's lagged verdict has no next boundary —
                # drain it so its gauges/chaos accounting land (best
                # effort: the mesh may be the thing that just died)
                try:
                    _numerics.note_step_staged(*self._staged)
                except Exception as e:
                    logger.debug("staged verdict drain failed: %s", e)
                self._staged = None
            _numerics.release_boundary()
            if self._own_coord and self._coord is not None:
                self._coord.close()


def run(
    step_builder: Callable[[int], Callable[[Any, int], Any]],
    state: Any,
    *,
    num_steps: int,
    min_workers: Optional[int] = None,
    max_workers: Optional[int] = None,
    snapshot_every: int = 1,
    reshard_fn: Optional[Callable[[Any, int], Any]] = None,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 0,
    callbacks: Optional[Iterable] = None,
    coordinator: Optional[ElasticCoordinator] = None,
    devices: Optional[Sequence] = None,
    publisher=None,
    publish_every: int = 0,
) -> Any:
    """Drive elastic training: ``state = step_fn(state, i)`` where
    ``step_fn = step_builder(world_size)`` is rebuilt every time membership
    changes. Returns the final state.

    - `step_builder(world_size)`: called after each mesh (re-)formation —
      ``hvd.mesh()`` is the fresh mesh — and must return a ``(state, step)
      -> state`` step callable for that world size.
    - `min_workers` / `max_workers` (env ``HOROVOD_ELASTIC_MIN_WORKERS`` /
      ``HOROVOD_ELASTIC_MAX_WORKERS``): the admissible world-size band.
      Falling below `min_workers` raises :class:`WorldTooSmall` after an
      emergency checkpoint of the last committed snapshot.
    - `snapshot_every`: commit a host-offloaded rollback snapshot every N
      completed steps (default 1). On a rank loss the run rolls back to
      the last committed step — a death detected at step k replays steps
      ``[committed, k)`` at the new world size.
    - `reshard_fn(state, new_size)`: state re-packing across world sizes;
      the default consolidates ZeRO-1 optimizer state for dicts carrying
      ``params`` + ``opt_state`` and passes everything else through.
    - `checkpoint_dir` / `checkpoint_every` / `callbacks`: forwarded to the
      inner :func:`horovod_tpu.resilience.run` — periodic checkpoints,
      SIGTERM preemption (drain → emergency checkpoint → exit 75), and
      resume all keep working inside each epoch.
    - `coordinator`: a shared :class:`ElasticCoordinator` (multi-party
      setups); by default the run owns a private one.
    - `publisher` / `publish_every`: a
      :class:`horovod_tpu.serving.WeightPublisher` to stream consolidated
      weights from every Nth committed snapshot. The elastic generation is
      wired up as its fence (a resize aborts any in-flight publication) and
      every resize republishes from the post-resize consolidated state.

    The numerics guard composes (:mod:`horovod_tpu.resilience.numerics`):
    when the carried state holds a guarded optimizer, the driver reads
    the per-step verdict — ``HOROVOD_NUMERICS_MAX_BAD`` consecutive bad
    steps roll back to the committed snapshot with a bumped replay epoch
    (bounded by ``HOROVOD_NUMERICS_MAX_ROLLBACKS``, then FATAL) — and a
    rank the fingerprint cross-check quarantined is evicted on the next
    membership sweep exactly like a dead one.

    Membership faults are injectable deterministically:
    ``HOROVOD_CHAOS="rank_fail=2,rank_fail_at_step=3,rank_join_at_step=6"``
    kills the two highest ranks at step 3's boundary and re-admits them at
    step 6's.
    """
    return ElasticRun(
        step_builder,
        min_workers=min_workers,
        max_workers=max_workers,
        snapshot_every=snapshot_every,
        reshard_fn=reshard_fn,
        coordinator=coordinator,
        devices=devices,
        publisher=publisher,
        publish_every=publish_every,
    ).run(
        state,
        num_steps=num_steps,
        checkpoint_dir=checkpoint_dir,
        checkpoint_every=checkpoint_every,
        callbacks=callbacks,
    )
