"""Elastic world-size training (ISSUE 4): KV heartbeats with TTL,
generation-numbered epochs, in-process mesh re-formation, ZeRO-1 state
reshard, rollback to the last committed snapshot, and the launcher's
min/max-workers band.

The acceptance pin: an 8-rank CPU-mesh run under
``HOROVOD_CHAOS=rank_fail=2`` continues at world size 6 without relaunch,
its post-resize trajectory matches a fresh 6-rank run restored from the
rollback snapshot (allclose), a later rejoin restores world size 8, and the
``resilience_elastic_*`` metrics record both transitions. Tier-1: single
process, deterministic chaos, no sleeps > 0.2s.
"""

import os
import signal
import threading
import time
from unittest import mock

import numpy as np
import pytest

from horovod_tpu.observability import metrics
from horovod_tpu.resilience import chaos, elastic, health, loop
from horovod_tpu.resilience.health import HealthState
from horovod_tpu.run.rendezvous import (
    DeadRankError,
    KVStoreClient,
    KVStoreServer,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_resilience():
    metrics.reset()
    metrics.set_enabled(True)
    health.reset()
    chaos.configure(None)
    yield
    metrics.reset()
    metrics.set_enabled(True)
    health.reset()
    chaos.reset()


# ------------------------------------------- KV heartbeat TTL / dead ranks


class TestKVHeartbeats:
    def test_ttl_key_expires_to_tombstone(self):
        s = KVStoreServer()
        s.put("/e/hb/3", b"1", ttl=0.05)
        assert s.get("/e/hb/3") == b"1"
        time.sleep(0.08)
        assert s.get("/e/hb/3") is None
        assert "/e/hb/3" in s.dead_keys()

    def test_refresh_clears_tombstone(self):
        s = KVStoreServer()
        s.put("/e/hb/2", b"1", ttl=0.05)
        time.sleep(0.08)
        assert "/e/hb/2" in s.dead_keys()
        s.put("/e/hb/2", b"1", ttl=5.0)  # the rank rejoined
        assert "/e/hb/2" not in s.dead_keys()
        assert s.get("/e/hb/2") == b"1"

    def test_wait_for_dead_heartbeat_fast_fails(self):
        """The satellite fix: a key owned by a dead rank must surface
        DeadRankError with the rank id immediately — not burn the whole
        deadline."""
        s = KVStoreServer()
        s.put("/e/hb/5", b"1", ttl=0.05)
        time.sleep(0.08)
        t0 = time.monotonic()
        with pytest.raises(DeadRankError) as ei:
            s.wait_for(["/e/ack/7/5"], timeout=30, hb_scope="/e/hb")
        assert ei.value.rank == 5
        assert time.monotonic() - t0 < 5  # nowhere near the 30s deadline

    def test_wait_for_tombstoned_key_itself(self):
        s = KVStoreServer()
        s.put("/e/hb/4", b"1", ttl=0.05)
        time.sleep(0.08)
        with pytest.raises(DeadRankError) as ei:
            s.wait_for(["/e/hb/4"], timeout=30)
        assert ei.value.rank == 4

    def test_wait_for_mid_wait_death(self):
        """A rank dying WHILE others wait on its key also fails fast: TTL
        expiry is re-swept on every wakeup."""
        s = KVStoreServer()
        s.put("/e/hb/6", b"1", ttl=0.15)
        err = []

        def waiter():
            try:
                s.wait_for(["/e/ack/1/6"], timeout=30, hb_scope="/e/hb")
            except BaseException as e:
                err.append(e)

        t = threading.Thread(target=waiter)
        t.start()
        t.join(timeout=5)
        assert not t.is_alive()
        assert isinstance(err[0], DeadRankError) and err[0].rank == 6

    def test_wait_for_plain_timeout_unchanged(self):
        s = KVStoreServer()
        with pytest.raises(TimeoutError):
            s.wait_for(["/never"], timeout=0.1)

    def test_wait_for_satisfied_returns_values(self):
        s = KVStoreServer()
        s.put("/a/1", b"x")
        s.put("/a/2", b"y")
        got = s.wait_for(["/a/1", "/a/2"], timeout=1)
        assert got == {"/a/1": b"x", "/a/2": b"y"}

    def test_client_wait_for_raises_dead_rank_over_http(self):
        """End-to-end over the wire: GET on an expired heartbeat key
        answers 410 Gone with the owner rank; the client maps it to
        DeadRankError."""
        server = KVStoreServer()
        server.start()
        try:
            server.put("/e/hb/2", b"1", ttl=0.05)
            time.sleep(0.08)
            client = KVStoreClient("127.0.0.1", server.port)
            t0 = time.monotonic()
            with pytest.raises(DeadRankError) as ei:
                client.wait_for("/e/hb/2", timeout=30)
            assert ei.value.rank == 2
            assert time.monotonic() - t0 < 5
        finally:
            server.stop()

    def test_client_put_with_ttl(self):
        server = KVStoreServer()
        server.start()
        try:
            client = KVStoreClient("127.0.0.1", server.port)
            client.heartbeat(3, scope="e/hb", ttl=0.05)
            assert server.get("/e/hb/3") == b"1"
            time.sleep(0.08)
            assert server.get("/e/hb/3") is None
            assert "/e/hb/3" in server.dead_keys()
        finally:
            server.stop()


# ------------------------------------------------------ elastic coordinator


class TestElasticCoordinator:
    def test_liveness_mark_dead_rejoin(self):
        c = elastic.ElasticCoordinator(ttl=5.0)
        try:
            c.heartbeat_all(range(4))
            assert c.alive() == [0, 1, 2, 3]
            c.mark_dead(3)
            c.mark_dead(2)
            assert c.alive() == [0, 1]
            c.heartbeat(2)  # rejoin = heartbeat resumes
            assert c.alive() == [0, 1, 2]
        finally:
            c.close()

    def test_generation_record_and_metrics(self):
        c = elastic.ElasticCoordinator(ttl=5.0)
        try:
            c.heartbeat_all(range(3))
            g = c.begin_generation([0, 1, 2])
            assert g == 1
            rec = c.membership()
            assert rec == {"generation": 1, "ranks": [0, 1, 2]}
            assert metrics.value("resilience_elastic_generation") == 1.0
            assert metrics.value("resilience_elastic_world_size") == 3.0
            g2 = c.begin_generation([0, 1])
            assert g2 == 2
            assert metrics.value("resilience_elastic_world_size") == 2.0
        finally:
            c.close()

    def test_barrier_completes_on_full_acks(self):
        c = elastic.ElasticCoordinator(ttl=5.0)
        try:
            c.heartbeat_all(range(3))
            g = c.begin_generation([0, 1, 2])
            for r in (0, 1, 2):
                c.ack(g, r)
            c.await_acks(g, [0, 1, 2], timeout=2)  # returns, no raise
        finally:
            c.close()

    def test_begin_generation_prunes_prior_ack_keys(self):
        """Ack-barrier keys are per-generation names: opening G+1 retires
        G's acks so the store does not grow by world_size keys per
        resize forever."""
        c = elastic.ElasticCoordinator(ttl=5.0)
        try:
            c.heartbeat_all(range(3))
            g1 = c.begin_generation([0, 1, 2])
            for r in (0, 1, 2):
                c.ack(g1, r)
            g2 = c.begin_generation([0, 1])
            acks = c.server.live_keys("/elastic/ack/")
            assert acks == []  # g1's barrier resolved; its keys retired
            c.ack(g2, 0)
            assert c.server.live_keys("/elastic/ack/") == [
                f"/elastic/ack/{g2}/0"]
        finally:
            c.close()

    def test_barrier_fast_fails_on_dead_member(self):
        """A member dying mid-barrier surfaces DeadRankError with its rank
        instead of the barrier timing out."""
        c = elastic.ElasticCoordinator(ttl=5.0)
        try:
            c.heartbeat_all(range(3))
            g = c.begin_generation([0, 1, 2])
            c.ack(g, 0)
            c.ack(g, 1)
            c.mark_dead(2)
            t0 = time.monotonic()
            with pytest.raises(DeadRankError) as ei:
                c.await_acks(g, [0, 1, 2], timeout=30)
            assert ei.value.rank == 2
            assert time.monotonic() - t0 < 5
        finally:
            c.close()


# ----------------------------------------------------- chaos rank charges


class TestElasticChaos:
    def test_parse_rank_keys(self):
        cfg = chaos.parse_spec(
            "rank_fail=2,rank_fail_at_step=3,rank_join_at_step=6")
        assert cfg == {
            "rank_fail": 2, "rank_fail_at_step": 3, "rank_join_at_step": 6,
        }

    @pytest.mark.chaos
    def test_rank_fail_fires_at_its_step_once(self):
        chaos.configure("rank_fail=2,rank_fail_at_step=3")
        assert chaos.take_rank_fail(0) == 0
        assert chaos.take_rank_fail(2) == 0
        assert chaos.take_rank_fail(3) == 2
        assert chaos.take_rank_fail(3) == 0  # consumed
        assert chaos.take_rank_fail(4) == 0
        assert metrics.value(
            "resilience_chaos_injected", site="rank_fail") == 1.0

    @pytest.mark.chaos
    def test_rank_fail_defaults_to_step_one(self):
        chaos.configure("rank_fail=1")
        assert chaos.take_rank_fail(0) == 0
        assert chaos.take_rank_fail(1) == 1

    @pytest.mark.chaos
    def test_rank_join_consumed_once(self):
        chaos.configure("rank_join_at_step=5")
        assert not chaos.take_rank_join(4)
        assert chaos.take_rank_join(6)
        assert not chaos.take_rank_join(7)
        assert metrics.value(
            "resilience_chaos_injected", site="rank_join_at_step") == 1.0


# --------------------------------------------- double-SIGTERM signal latch


@pytest.mark.chaos
def test_double_sigterm_single_drain_valid_checkpoint(hvd, tmp_path):
    """Satellite fix: a second SIGTERM landing DURING the emergency
    checkpoint write must be latched — no drain re-entry, no torn npz. The
    second signal is delivered from inside the save itself (the worst
    window), and the checkpoint must still validate."""
    from horovod_tpu import checkpoint as ckpt

    d = str(tmp_path / "ck")
    real_save = ckpt.save
    drains = []

    def noisy_save(directory, step, state, **kw):
        os.kill(os.getpid(), signal.SIGTERM)  # supervisor escalates mid-save
        time.sleep(0)  # give the handler its bytecode boundary
        return real_save(directory, step, state, **kw)

    def counting_drain(state, timeout_s=None):
        drains.append(1)

    chaos.configure("sigterm_at_step=2")
    with mock.patch.object(loop, "_drain", counting_drain), \
            mock.patch("horovod_tpu.checkpoint.save", noisy_save):
        with pytest.raises(loop.Preempted) as ei:
            loop.run(
                lambda st, i: {"w": st["w"] + 1}, {"w": np.zeros(2)},
                num_steps=5, checkpoint_dir=d,
            )
    assert ei.value.step == 2
    assert len(drains) == 1  # no re-entry into the drain path
    assert ckpt.latest_step(d) == 2  # the npz survived, CRC-valid
    assert metrics.value("resilience_preemptions") == 1.0
    assert metrics.value("resilience_extra_preempt_signals") == 1.0


def test_preempt_is_not_reentrant():
    """The drain/checkpoint sequence runs exactly once per preemption even
    when the loop has multiple paths into _preempt."""
    chaos.configure("sigterm_at_step=1")
    with pytest.raises(loop.Preempted):
        loop.run(lambda st, i: st, {}, num_steps=3)
    assert metrics.value("resilience_preemptions") == 1.0
    chaos.configure(None)


# ------------------------------------------- shutdown -> init idempotence


def test_reinit_on_new_mesh_clears_stale_kernel_caches():
    """Satellite fix: a live-process shutdown() → init() cycle is
    idempotent — re-init on an EQUAL mesh keeps the compiled-eager-kernel
    caches warm, while re-init on a DIFFERENT mesh (the elastic resize)
    drops the old mesh's stale entries. This is the primitive the elastic
    resize stands on."""
    import jax

    import horovod_tpu as hvd
    from horovod_tpu.ops import collective as C

    def cached_kernels():
        return sum(
            f.cache_info().currsize
            for f in (C._eager_allreduce_fn, C._eager_fused_allreduce_fn,
                      C._eager_allgather_fn, C._eager_broadcast_fn,
                      C._eager_reducescatter_fn)
        )

    hvd.init()
    try:
        assert hvd.size() == 8
        out = hvd.allreduce(np.ones((4,), np.float32))
        np.testing.assert_allclose(np.asarray(out), 1.0)
        assert cached_kernels() >= 1

        # same-mesh cycle: the caches stay warm (no recompile per cycle)
        hvd.shutdown()
        warm = cached_kernels()
        assert warm >= 1
        hvd.init()
        assert cached_kernels() == warm

        # different mesh: the stale-keyed entries are dropped at init
        hvd.shutdown()
        hvd.init(devices=jax.devices()[:6])
        assert cached_kernels() == 0
        assert hvd.size() == 6
        out = hvd.allreduce(np.full((4,), 2.0, np.float32))
        np.testing.assert_allclose(np.asarray(out), 2.0)

        hvd.shutdown()
        hvd.init()
        assert hvd.size() == 8
    finally:
        hvd.shutdown()


def test_atexit_registered_once():
    import horovod_tpu as hvd
    from horovod_tpu import basics

    registered = []
    with mock.patch.object(
        basics.atexit, "register",
        side_effect=lambda fn: registered.append(fn),
    ):
        was = basics._atexit_registered
        try:
            basics._atexit_registered = False
            hvd.init()
            hvd.shutdown()
            hvd.init()
            hvd.shutdown()
        finally:
            basics._atexit_registered = was
    assert len(registered) == 1  # one handler per process, not per init


def test_stale_collective_name_does_not_poison_reinit():
    import horovod_tpu as hvd
    from horovod_tpu.ops.collective import _register_name, _outstanding_names

    hvd.init()
    try:
        _register_name("grad/w0")  # an async op left outstanding at death
        hvd.shutdown()
        assert "grad/w0" not in _outstanding_names
        hvd.init()
        _register_name("grad/w0")  # must not raise DUPLICATE_NAME
        from horovod_tpu.ops.collective import _release_name

        _release_name("grad/w0")
    finally:
        hvd.shutdown()


# -------------------------------------------------- health feed


def test_record_rank_lost_strikes_and_counts():
    health.record_rank_lost(5)
    assert health.health_state() == HealthState.SUSPECT
    assert "rank 5" in health.snapshot()["reason"]
    assert metrics.value("resilience_rank_lost") == 1.0
    health.beat()
    assert health.health_state() == HealthState.HEALTHY


# -------------------------------------------------- launcher elastic band


def test_host_strike_decay_readmits():
    from horovod_tpu.run.runner import HostStrikes

    s = HostStrikes(limit=1, decay_s=0.05)
    s.strike("h1")
    assert s.blacklisted("h1")
    time.sleep(0.08)
    assert not s.blacklisted("h1")  # strikes decayed: re-admitted
    # permanent by default
    s2 = HostStrikes(limit=1, decay_s=0)
    s2.strike("h2")
    time.sleep(0.08)
    assert s2.blacklisted("h2")


def test_parse_args_min_max_workers():
    from horovod_tpu.run.runner import parse_args

    args = parse_args([
        "-np", "4", "--min-workers", "2", "--max-workers", "6",
        "--", "python", "train.py",
    ])
    assert args.min_workers == 2
    assert args.max_workers == 6


def test_launch_job_min_workers_tolerates_dead_slot(monkeypatch):
    """The elastic floor: a permanently failed slot is abandoned — the
    survivors run to completion instead of being SIGTERMed."""
    from horovod_tpu.run import hosts, runner

    monkeypatch.setenv("HOROVOD_RETRY_WORKER_RESTART_BASE_DELAY", "0.01")
    monkeypatch.setenv("HOROVOD_RETRY_WORKER_RESTART_MAX_DELAY", "0.02")
    slots = hosts.allocate(hosts.parse_hosts("localhost:2"), 2)

    def fake_execute(argv, env=None, stdout_handler=None,
                     stderr_handler=None, event=None, shell=False):
        if env.get("HOROVOD_RANK") == "1":
            return 1  # permanent death
        # the survivor outlives the failure and completes
        time.sleep(0.1)
        return 0 if not (event and event.is_set()) else 143

    with mock.patch.object(runner.safe_exec, "execute", fake_execute):
        codes = runner.launch_job(
            slots, ["python", "train.py"], {"JAX_PLATFORMS": "cpu"},
            min_workers=1)
    assert codes == [0, 1]  # survivor finished; dead slot recorded
    assert metrics.value(
        "resilience_elastic_slots_abandoned", host="localhost") == 1.0


def test_launch_job_below_min_workers_still_kills(monkeypatch):
    from horovod_tpu.run import hosts, runner

    slots = hosts.allocate(hosts.parse_hosts("localhost:2"), 2)

    def fake_execute(argv, env=None, stdout_handler=None,
                     stderr_handler=None, event=None, shell=False):
        if env.get("HOROVOD_RANK") == "1":
            return 1
        # survivor blocks until the teardown event fires
        if event:
            event.wait(5)
        return 143 if (event and event.is_set()) else 0

    with mock.patch.object(runner.safe_exec, "execute", fake_execute):
        codes = runner.launch_job(
            slots, ["python", "train.py"], {"JAX_PLATFORMS": "cpu"},
            min_workers=2)
    assert codes[1] == 1
    assert codes[0] == 143  # torn down: the floor was broken


def test_launch_job_exports_elastic_band(monkeypatch):
    from horovod_tpu.run import hosts, runner

    slots = hosts.allocate(hosts.parse_hosts("localhost:1"), 1)
    seen = {}

    def fake_execute(argv, env=None, stdout_handler=None,
                     stderr_handler=None, event=None, shell=False):
        seen.update(env)
        return 0

    with mock.patch.object(runner.safe_exec, "execute", fake_execute):
        runner.launch_job(
            slots, ["python", "t.py"], {}, min_workers=1, max_workers=4)
    assert seen.get("HOROVOD_ELASTIC_MIN_WORKERS") == "1"
    assert seen.get("HOROVOD_ELASTIC_MAX_WORKERS") == "4"

    # an operator-exported cap is honored, not clobbered by the default
    seen.clear()
    with mock.patch.object(runner.safe_exec, "execute", fake_execute):
        runner.launch_job(
            slots, ["python", "t.py"],
            {"HOROVOD_ELASTIC_MAX_WORKERS": "2"})
    assert seen.get("HOROVOD_ELASTIC_MAX_WORKERS") == "2"


@pytest.mark.elastic
def test_unknown_rank_heartbeat_is_ignored():
    """A heartbeat for a rank this controller has no device for (shared
    store, stray key) must be ignored — not IndexError the resize."""
    import horovod_tpu as hvd

    coord = elastic.ElasticCoordinator(ttl=5.0)
    hvd.init()
    try:
        coord.heartbeat(40)  # no such device
        out = elastic.run(
            lambda world: (lambda st, i: {"w": st["w"] + 1}),
            {"w": np.zeros(1)}, num_steps=3, coordinator=coord)
        np.testing.assert_allclose(out["w"], 3.0)
        assert hvd.size() == 8  # the stray rank never joined
    finally:
        hvd.shutdown()
        coord.close()


# ---------------------------------------------------- elastic training e2e


def _tiny_model():
    import flax.linen as nn

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            return nn.Dense(2)(x)

    return Tiny()


def _batch_for(step, n=48):
    rng = np.random.RandomState(step)
    x = rng.rand(n, 8).astype(np.float32)
    y = (x.sum(axis=1) > 4).astype(np.int64)
    return x, y


def _make_builder(model):
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.training import (
        make_shardmap_train_step, shard_batch, softmax_xent,
    )

    def step_builder(world):
        tx = hvd.DistributedOptimizer(optax.adam(1e-2), shard_optimizer=True)
        step = make_shardmap_train_step(
            model, tx, loss_fn=softmax_xent, shard_optimizer=True,
            instrument=False)

        def step_fn(state, i):
            x, y = _batch_for(i)
            p, _, os_, loss = step(
                state["params"], {}, state["opt_state"],
                shard_batch(x), shard_batch(y))
            return {"params": p, "opt_state": os_}

        return step_fn

    return step_builder


def _fresh_state(model):
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.training import replicate

    params0 = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8)))["params"]
    tx = hvd.DistributedOptimizer(optax.adam(1e-2), shard_optimizer=True)
    params = replicate(jax.tree_util.tree_map(jnp.array, params0))
    return {"params": params, "opt_state": tx.init(params)}


@pytest.mark.elastic
@pytest.mark.chaos
def test_elastic_shrink_matches_fresh_run_then_rejoins():
    """THE acceptance pin. 8-rank run, ``rank_fail=2`` at step 3's
    boundary: continues at world size 6 in the same process, the
    post-resize trajectory matches a fresh 6-rank run restored from the
    rollback snapshot, ``rank_join_at_step=6`` grows back to 8, and the
    generation/membership metrics record both transitions."""
    import jax

    import horovod_tpu as hvd
    from horovod_tpu import checkpoint as ckpt
    from horovod_tpu.training import host_snapshot

    model = _tiny_model()
    builder = _make_builder(model)

    chaos.configure(
        "rank_fail=2,rank_fail_at_step=3,rank_join_at_step=6")
    hvd.init()
    try:
        state = _fresh_state(model)
        final = elastic.run(
            builder, state, num_steps=9, snapshot_every=1)
        assert hvd.size() == 8  # rejoined
        p_elastic = np.asarray(
            jax.tree_util.tree_leaves(final["params"])[0])

        # metrics recorded both transitions
        assert metrics.value("resilience_elastic_generation") == 3.0
        assert metrics.value(
            "resilience_elastic_membership_changes", kind="shrink") == 1.0
        assert metrics.value(
            "resilience_elastic_membership_changes", kind="grow") == 1.0
        assert metrics.value("resilience_elastic_world_size") == 8.0
        assert metrics.value("resilience_rank_lost") == 2.0
        hist = metrics.value("resilience_elastic_resize_seconds")
        assert hist["count"] == 2
        assert metrics.value(
            "resilience_chaos_injected", site="rank_fail") == 1.0

        # reference: the same schedule driven by hand — 8-rank steps 0..3,
        # snapshot, fresh 6-rank formation restored from it for 3..6,
        # snapshot, back to 8 for 6..9
        chaos.configure(None)
        hvd.shutdown()
        hvd.init()
        st = _fresh_state(model)
        fn8 = builder(8)
        for i in range(3):
            st = fn8(st, i)
        snap = host_snapshot(st)
        hvd.shutdown()
        hvd.init(devices=jax.devices()[:6])
        st6 = dict(snap)
        st6["opt_state"] = ckpt.consolidate_opt_state(
            st6["opt_state"], st6["params"], to_size=6)
        fn6 = builder(6)
        for i in range(3, 6):
            st6 = fn6(st6, i)
        snap6 = host_snapshot(st6)
        hvd.shutdown()
        hvd.init()
        st8 = dict(snap6)
        st8["opt_state"] = ckpt.consolidate_opt_state(
            st8["opt_state"], st8["params"], to_size=8)
        fn8b = builder(8)
        for i in range(6, 9):
            st8 = fn8b(st8, i)
        p_ref = np.asarray(jax.tree_util.tree_leaves(st8["params"])[0])
        np.testing.assert_allclose(p_elastic, p_ref, rtol=1e-5, atol=1e-6)
    finally:
        hvd.shutdown()


@pytest.mark.elastic
@pytest.mark.chaos
def test_elastic_world_too_small_checkpoints_and_raises(tmp_path):
    """Falling below min_workers is not survivable: the driver writes an
    emergency checkpoint of the last committed snapshot and raises."""
    import horovod_tpu as hvd
    from horovod_tpu import checkpoint as ckpt

    model = _tiny_model()
    builder = _make_builder(model)
    d = str(tmp_path / "ck")

    chaos.configure("rank_fail=3,rank_fail_at_step=2")
    hvd.init()
    try:
        state = _fresh_state(model)
        with pytest.raises(elastic.WorldTooSmall) as ei:
            elastic.run(
                builder, state, num_steps=6, min_workers=7,
                checkpoint_dir=d)
        assert ei.value.alive == 5
        assert ei.value.min_workers == 7
        # last committed snapshot (step 2) was emergency-checkpointed
        assert ckpt.latest_step(d) == 2
    finally:
        hvd.shutdown()


@pytest.mark.elastic
def test_min_workers_enforced_at_initial_formation():
    """The admissible band applies from step 0: a host that cannot field
    min_workers errors immediately instead of silently training small."""
    import horovod_tpu as hvd

    hvd.init()
    try:
        with pytest.raises(elastic.WorldTooSmall) as ei:
            elastic.run(
                lambda world: (lambda st, i: st), {"w": np.zeros(1)},
                num_steps=3, min_workers=9)  # only 8 devices exist
        assert ei.value.alive == 8
        assert ei.value.min_workers == 9
    finally:
        hvd.shutdown()


@pytest.mark.elastic
def test_elastic_no_faults_is_a_plain_run():
    """Without chaos/membership churn, elastic.run degrades to the plain
    loop: one generation, full world, correct arithmetic."""
    import horovod_tpu as hvd

    hvd.init()
    try:
        calls = []

        def builder(world):
            calls.append(world)

            def fn(st, i):
                return {"w": st["w"] + world}

            return fn

        out = elastic.run(builder, {"w": np.zeros(2)}, num_steps=4)
        np.testing.assert_allclose(out["w"], 32.0)  # 4 steps x world 8
        assert calls == [8]
        assert metrics.value("resilience_elastic_generation") == 1.0
    finally:
        hvd.shutdown()


@pytest.mark.elastic
@pytest.mark.chaos
def test_elastic_rollback_replays_uncommitted_steps():
    """With snapshot_every=2, a death detected at step 3 rolls back to the
    last committed step 2 and replays — the rollback metric records it."""
    import horovod_tpu as hvd

    seen = []

    def builder(world):
        def fn(st, i):
            seen.append((world, i))
            return {"w": st["w"] + 1}

        return fn

    chaos.configure("rank_fail=1,rank_fail_at_step=3")
    hvd.init()
    try:
        out = elastic.run(
            builder, {"w": np.zeros(1)}, num_steps=5, snapshot_every=2)
        # 8-world ran steps 0,1,2; death at step-3 boundary rolled back to
        # committed step 2, so 7-world replays 2 then runs 3,4
        assert (8, 2) in seen and (7, 2) in seen
        np.testing.assert_allclose(out["w"], 5.0)  # exactly-once effect
        assert metrics.value("resilience_elastic_rollback_steps") == 1.0
    finally:
        hvd.shutdown()


@pytest.mark.elastic
@pytest.mark.chaos
def test_join_charge_survives_until_someone_failed():
    """Regression: rank_join armed at (or before) the fail step must not
    be consumed while nobody has failed yet — the charge waits for the
    shrink, then fires on the next boundary and regrows the world."""
    import horovod_tpu as hvd

    chaos.configure("rank_fail=1,rank_fail_at_step=2,rank_join_at_step=2")
    hvd.init()
    try:
        out = elastic.run(
            lambda world: (lambda st, i: {"w": st["w"] + 1}),
            {"w": np.zeros(1)}, num_steps=5)
        assert hvd.size() == 8  # shrank to 7, then the join charge fired
        np.testing.assert_allclose(out["w"], 5.0)
        assert metrics.value(
            "resilience_elastic_membership_changes", kind="shrink") == 1.0
        assert metrics.value(
            "resilience_elastic_membership_changes", kind="grow") == 1.0
    finally:
        hvd.shutdown()


@pytest.mark.elastic
@pytest.mark.chaos
def test_elastic_sigterm_preemption_still_exits_resumable(tmp_path):
    """The preemption protocol composes: SIGTERM inside an elastic run
    still drains, emergency-checkpoints, and raises Preempted (exit 75)."""
    import horovod_tpu as hvd
    from horovod_tpu import checkpoint as ckpt

    d = str(tmp_path / "ck")
    chaos.configure("sigterm_at_step=2")
    hvd.init()
    try:
        def builder(world):
            return lambda st, i: {"w": st["w"] + 1}

        with pytest.raises(loop.Preempted) as ei:
            elastic.run(
                builder, {"w": np.zeros(1)}, num_steps=5,
                checkpoint_dir=d)
        assert ei.value.code == loop.RESUMABLE_EXIT_CODE
        assert ckpt.latest_step(d) == 2
    finally:
        hvd.shutdown()


@pytest.mark.elastic
@pytest.mark.chaos
def test_clock_reestimated_after_elastic_resize():
    """Satellite (ISSUE 14): the elastic driver re-estimates the clock
    offset against the coordinator's KV at every epoch boundary — pinned
    end to end here: after a rank_fail shrink, the stored estimate
    carries the POST-resize generation, a real error bound, and the
    mirrored clock gauges (previously asserted nowhere end-to-end)."""
    import horovod_tpu as hvd
    from horovod_tpu.observability import clock

    model = _tiny_model()
    builder = _make_builder(model)
    chaos.configure("rank_fail=2,rank_fail_at_step=2")
    clock.reset()
    hvd.init()
    try:
        state = _fresh_state(model)
        elastic.run(builder, state, num_steps=4, snapshot_every=1)
        assert hvd.size() == 6  # the shrink happened (48 % 6 == 0)
        info = clock.info()
        # formation is generation 1; the post-shrink epoch re-estimated
        # under generation 2 (a resize is exactly when the host set — and
        # the skew picture — may have changed)
        assert info["generation"] == 2
        assert clock.error_bound() is not None
        assert info["age_s"] is not None
        assert metrics.value(
            "observability_clock_offset_seconds") is not None
        assert metrics.value(
            "observability_clock_error_seconds") is not None
    finally:
        hvd.shutdown()
        clock.reset()
