"""Elastic-aware deterministic input pipeline (ISSUE 15): the global
sample index's purity contract, cursor checkpoint/resume, NumericsRollback
fresh-batch replay, elastic exactly-once resharding, shard-store CRC
quarantine, prefetch-watchdog stall detection, and input-side straggler
attribution — all driven deterministically on the 8-device CPU mesh
(``pytest -m data``). Semantics: docs/data.md."""

import os
import re

import numpy as np
import pytest

from horovod_tpu.data import (
    ArrayShardStore,
    DataUnavailableError,
    GlobalSampleIndex,
    ResumableLoader,
    mix_seed,
    sampler,
    shard_indices,
)
from horovod_tpu.observability import metrics, straggler
from horovod_tpu.resilience import chaos, health, numerics

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.data


@pytest.fixture(autouse=True)
def _fresh_data_plane():
    metrics.reset()
    metrics.set_enabled(True)
    health.reset()
    chaos.configure(None)
    numerics.reset()
    straggler.reset()
    sampler.reset()
    yield
    metrics.reset()
    metrics.set_enabled(True)
    health.reset()
    chaos.reset()
    numerics.reset()
    straggler.reset()
    sampler.reset()


def _xy(n, feat=4, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.rand(n, feat).astype(np.float32)
    y = np.arange(n, dtype=np.int32)  # labels ARE indices: draws visible
    return x, y


# -------------------------------------------------------- seed mixing


def test_mix_seed_no_epoch_seed_collision():
    """Satellite regression: RandomState(seed + epoch) made (seed=0,
    epoch=1) and (seed=1, epoch=0) identical streams; the hash mix must
    not."""
    assert mix_seed(0, 1) != mix_seed(1, 0)
    assert mix_seed(0, 0, 1) != mix_seed(0, 1, 0)
    assert mix_seed(0, 0, 1) != mix_seed(1, 0, 0)
    # and the fix reaches shard_indices / the epoch permutation
    a = shard_indices(101, rank=0, size=4, seed=0, epoch=1)
    b = shard_indices(101, rank=0, size=4, seed=1, epoch=0)
    assert not np.array_equal(a, b)
    # replay_epoch reshuffles the SAME epoch
    r0 = shard_indices(101, rank=0, size=4, seed=0, epoch=0)
    r1 = shard_indices(101, rank=0, size=4, seed=0, epoch=0,
                       replay_epoch=1)
    assert not np.array_equal(r0, r1)
    assert sorted(set(np.concatenate([
        shard_indices(101, rank=r, size=4, seed=0, epoch=0,
                      replay_epoch=1) for r in range(4)
    ]).tolist())) == list(range(101))


def test_mix_seed_deterministic():
    assert mix_seed(7, 3, 2) == mix_seed(7, 3, 2)
    assert 0 <= mix_seed(7, 3, 2) < 2 ** 32


# -------------------------------------------------- global sample index


def test_global_sample_index_purity_and_partition():
    gsi = GlobalSampleIndex(96, 24, seed=3)
    assert gsi.steps_per_epoch == 4
    # pure + deterministic
    np.testing.assert_array_equal(
        gsi.batch_indices(1, 2), GlobalSampleIndex(
            96, 24, seed=3).batch_indices(1, 2))
    # steps partition the selected epoch window
    allv = np.concatenate([gsi.batch_indices(0, s) for s in range(4)])
    assert sorted(allv.tolist()) == list(range(96))
    # rank slices partition each batch, at EVERY world size that divides
    b = gsi.batch_indices(0, 1)
    for size in (2, 3, 4, 6, 8, 12, 24):
        parts = [gsi.rank_indices(0, 1, r, size) for r in range(size)]
        assert sorted(np.concatenate(parts).tolist()) == sorted(b.tolist())
    # the GLOBAL batch never depends on the world size — the elastic
    # repartition invariant
    with pytest.raises(ValueError, match="divide"):
        gsi.rank_indices(0, 0, 0, 5)
    with pytest.raises(IndexError):
        gsi.batch_indices(0, 4)


def test_global_sample_index_replay_epoch_diverges():
    gsi = GlobalSampleIndex(64, 16, seed=0)
    a = gsi.batch_indices(2, 1, replay_epoch=0)
    b = gsi.batch_indices(2, 1, replay_epoch=1)
    assert not np.array_equal(a, b)
    # both still draw from the full epoch
    for replay in (0, 1):
        allv = np.concatenate(
            [gsi.batch_indices(2, s, replay) for s in range(4)])
        assert sorted(allv.tolist()) == list(range(64))


def test_global_sample_index_stream_and_advance():
    gsi = GlobalSampleIndex(32, 16, seed=1)
    keys = [(e, s) for e, s, _ in gsi.stream(0, 1, num_steps=4)]
    assert keys == [(0, 1), (1, 0), (1, 1), (2, 0)]
    assert gsi.advance(0, 1) == (1, 0)


# ------------------------------------------------------ resumable loader


def test_resumable_loader_matches_pure_index(hvd):
    n, bs = 96, 24
    x, y = _xy(n)
    gsi = GlobalSampleIndex(n, bs, seed=3)
    ref = [idx.tolist() for _, _, idx in gsi.stream(0, 0, num_steps=6)]
    loader = ResumableLoader((x, y), bs, seed=3, prefetch=2, name="pure")
    try:
        seen = []
        for _ in range(6):
            xb, yb = loader.next_batch()
            assert xb.shape == (bs, 4)
            assert xb.sharding.spec[0] is not None  # sharded over data
            idx = np.asarray(yb).tolist()
            np.testing.assert_array_equal(np.asarray(xb), x[idx])
            seen.append(idx)
        assert seen == ref
        # cursor crossed the epoch boundary: 4 steps/epoch
        assert loader.state()["epoch"] == 1
        assert loader.state()["step"] == 2
        # metrics moved
        assert metrics.value("input_batches") == 6.0
        assert metrics.value("data_cursor_epoch") == 1.0
    finally:
        loader.close()


def test_resumable_loader_restore_is_exact(hvd):
    """Cold restart: a FRESH loader restored to a mid-epoch cursor draws
    the identical remaining stream."""
    n, bs = 64, 16
    x, y = _xy(n)
    gsi = GlobalSampleIndex(n, bs, seed=11)
    ref = [idx.tolist() for _, _, idx in gsi.stream(0, 0, num_steps=8)]
    a = ResumableLoader((x, y), bs, seed=11, prefetch=2, name="a")
    head = [np.asarray(a.next_batch()[1]).tolist() for _ in range(5)]
    cursor = a.state()
    a.close()
    b = ResumableLoader((x, y), bs, seed=11, prefetch=0, name="b")
    b.restore(cursor)
    tail = [np.asarray(b.next_batch()[1]).tolist() for _ in range(3)]
    b.close()
    assert head + tail == ref


def test_resumable_loader_per_rank_mode_partitions():
    n, bs = 48, 12
    x, y = _xy(n)
    loaders = [
        ResumableLoader((x, y), bs, seed=2, rank=r, size=3, prefetch=0,
                        name=f"r{r}", register=False)
        for r in range(3)
    ]
    gsi = GlobalSampleIndex(n, bs, seed=2)
    for s in range(4):
        slices = []
        for ld in loaders:
            _, yb = ld.next_batch()
            assert yb.shape == (bs // 3,)
            slices.append(np.asarray(yb))
        assert sorted(np.concatenate(slices).tolist()) == \
            sorted(gsi.batch_indices(0, s).tolist())
    for ld in loaders:
        ld.close()


def test_resumable_loader_reshard_mid_epoch_exactly_once():
    """The per-rank repartition drill: 2 ranks consume half the epoch,
    then 'resize' to 1 survivor that re-binds (same cursor) and consumes
    the rest — union == epoch, no duplicates."""
    n, bs = 64, 16
    x, y = _xy(n)
    l0 = ResumableLoader((x, y), bs, seed=9, rank=0, size=2, prefetch=0,
                         name="re0", register=False)
    l1 = ResumableLoader((x, y), bs, seed=9, rank=1, size=2, prefetch=0,
                         name="re1", register=False)
    visited = []
    for _ in range(2):  # steps 0..1 at world 2
        for ld in (l0, l1):
            visited.extend(np.asarray(ld.next_batch()[1]).tolist())
    l0.reshard(rank=0, size=1, generation=2)
    for _ in range(2):  # steps 2..3 at world 1: full batches
        _, yb = l0.next_batch()
        assert yb.shape == (bs,)
        visited.extend(np.asarray(yb).tolist())
    assert sorted(visited) == list(range(n))
    with pytest.raises(RuntimeError, match="per-rank"):
        ResumableLoader((x, y), bs, prefetch=0, name="glob",
                        register=False).reshard(rank=0, size=1)
    l0.close()
    l1.close()


def test_loader_registry_pending_cursor_applies_on_register():
    """Cold-restart ordering: restore the checkpoint FIRST, build the
    loader after — the pending cursor applies at register time."""
    sampler.restore_state({"late": {"epoch": 2, "step": 1, "seed": 5}})
    x, y = _xy(32)
    ld = ResumableLoader((x, y), 16, seed=5, prefetch=0, name="late")
    try:
        assert ld.cursor() == (2, 1)
        assert sampler.export_state()["late"]["epoch"] == 2
    finally:
        ld.close()


# ------------------------------------------- acceptance: kill/resume


@pytest.mark.chaos
def test_kill_resume_mid_epoch_identical_remaining_stream(hvd, tmp_path):
    """Acceptance drill (ISSUE 15): train with checkpointing, SIGTERM
    mid-epoch, cold-restart resume — the remaining sample stream is
    IDENTICAL to an uninterrupted run, by exact index comparison."""
    from horovod_tpu.resilience import loop as rloop

    n, bs = 64, 16  # 4 steps/epoch; kill at step 5 = epoch 1, step 1
    x, y = _xy(n)
    ckpt = str(tmp_path / "ckpt")
    gsi = GlobalSampleIndex(n, bs, seed=11)
    ref = [idx.tolist() for _, _, idx in gsi.stream(0, 0, num_steps=8)]

    seen = []
    ld = ResumableLoader((x, y), bs, seed=11, prefetch=2, name="resume")
    chaos.configure("sigterm_at_step=5")

    def step_fn(state, i):
        _, yb = ld.next_batch()
        seen.append(np.asarray(yb).tolist())
        return state + 1

    with pytest.raises(SystemExit) as ei:
        rloop.run(step_fn, np.zeros(1), num_steps=8, checkpoint_dir=ckpt)
    assert ei.value.code == rloop.RESUMABLE_EXIT_CODE
    ld.close()
    assert seen == ref[:5]

    # cold restart: fresh registry, fresh loader, cursor restored from
    # the emergency checkpoint's data_cursor payload
    sampler.reset()
    chaos.configure(None)
    resumed = rloop.resume_state(ckpt)
    assert resumed is not None and resumed[0] == 5
    ld2 = ResumableLoader((x, y), bs, seed=11, prefetch=2, name="resume")
    assert ld2.cursor() == (1, 1)
    seen2 = []

    def step_fn2(state, i):
        _, yb = ld2.next_batch()
        seen2.append(np.asarray(yb).tolist())
        return state + 1

    rloop.run(step_fn2, np.zeros(1), num_steps=8, start_step=resumed[0])
    ld2.close()
    assert seen2 == ref[5:], "resumed stream diverged from the reference"


# ------------------------------- acceptance: numerics rollback replay


@pytest.mark.chaos
@pytest.mark.numerics
def test_numerics_rollback_replays_with_fresh_batches(hvd, monkeypatch):
    """Acceptance drill (ISSUE 15): a PR-9 NumericsRollback bumps the
    replay epoch; the replayed steps draw DIFFERENT (fresh) batches than
    the poisoned attempt — both pinned by exact index comparison — while
    the cursor rewinds with the committed snapshot."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu.resilience import elastic
    from horovod_tpu.training import (
        make_shardmap_train_step, replicate, softmax_xent,
    )

    monkeypatch.setenv("HOROVOD_NUMERICS_MAX_BAD", "2")

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            return nn.Dense(2)(x)

    n, bs = 96, 16
    x, y = _xy(n, feat=8)
    y = (y % 2).astype(np.int32)
    ld = ResumableLoader((x, y), bs, seed=5, prefetch=2, name="numerics")
    model = Tiny()
    draws = []  # (step, replay_epoch, indices)

    def builder(world):
        tx = hvd.DistributedOptimizer(
            optax.adam(1e-2), shard_optimizer=True, numerics_guard=True)
        step = make_shardmap_train_step(
            model, tx, loss_fn=softmax_xent, shard_optimizer=True,
            instrument=False, donate=False)

        def step_fn(state, i):
            xb, yb = ld.next_batch()
            replay = ld.last_key[2]
            draws.append((i, replay, ld.last_indices.tolist()))
            xh = np.asarray(xb)
            if replay == 0 and i >= 3:
                xh = xh * np.nan  # the poisoned-data incident
            p, _, st, _ = step(state["params"], {}, state["opt_state"],
                               jnp.asarray(xh), yb)
            return {"params": p, "opt_state": st}

        return step_fn

    try:
        params0 = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8)))["params"]
        tx0 = hvd.DistributedOptimizer(
            optax.adam(1e-2), shard_optimizer=True, numerics_guard=True)
        params = replicate(jax.tree_util.tree_map(jnp.array, params0))
        state = {"params": params, "opt_state": tx0.init(params)}
        out = elastic.run(builder, state, num_steps=6, snapshot_every=1)
        assert numerics.replay_epoch() == 1
        poisoned = {i: idx for i, r, idx in draws if r == 0}
        replayed = {i: idx for i, r, idx in draws if r == 1}
        # the rollback replayed the bad steps...
        assert 3 in poisoned and 3 in replayed
        # ...with genuinely FRESH batches (exact index comparison)...
        for i in replayed:
            if i in poisoned:
                assert replayed[i] != poisoned[i], i
        # ...that still come from the same epoch's sample set
        gsi = GlobalSampleIndex(n, bs, seed=5)
        assert replayed[3] == gsi.batch_indices(
            0, 3, replay_epoch=1).tolist()
        assert numerics.tree_finite(out["params"])
    finally:
        ld.close()


# ------------------------------------ acceptance: elastic exactly-once


@pytest.mark.chaos
@pytest.mark.elastic
def test_elastic_resize_mid_epoch_exactly_once(hvd):
    """Acceptance drill (ISSUE 15): 8→6 resize mid-epoch under
    HOROVOD_CHAOS=rank_fail=2 — the committed sample stream's union over
    the epoch equals the full epoch with no duplicates, the replayed
    step re-draws IDENTICAL indices (same replay epoch), the stream is
    pinned against a fresh same-seed run, and the loader is generation-
    fenced with the mesh."""
    from horovod_tpu.resilience import elastic

    chaos.configure("rank_fail=2,rank_fail_at_step=2")
    n, bs = 96, 24  # divides by 8 AND 6; 4 steps = one epoch
    x, y = _xy(n)
    ld = ResumableLoader((x, y), bs, seed=7, prefetch=2, name="elastic")
    draws = []   # every raw draw (step, indices, world)
    final = {}   # last draw per step = the committed logical stream

    def builder(world):
        def step_fn(state, i):
            _, yb = ld.next_batch()
            idx = np.asarray(yb).tolist()
            draws.append((i, idx, world))
            final[i] = idx
            return {"w": state["w"] + 1.0}

        return step_fn

    try:
        # snapshot_every=2: the resize at step 2's boundary rolls back to
        # committed step 2 == the boundary — and a second drill variant
        # below exercises a real replay
        elastic.run(builder, {"w": np.zeros(1)}, num_steps=4,
                    snapshot_every=1)
        worlds = sorted({w for _, _, w in draws})
        assert worlds == [6, 8], "resize did not happen"
        # exactly-once over the epoch on the committed stream
        allv = [v for i in range(4) for v in final[i]]
        assert sorted(allv) == list(range(n))
        # pinned against a fresh same-seed run
        gsi = GlobalSampleIndex(n, bs, seed=7)
        for i in range(4):
            assert final[i] == gsi.batch_indices(0, i).tolist()
        # any replayed step re-drew the SAME indices (no replay salt)
        from collections import Counter

        for i, k in Counter(i for i, _, _ in draws).items():
            if k > 1:
                assert len({tuple(idx) for j, idx, _ in draws
                            if j == i}) == 1
        # generation fence: loader moved with the mesh epoch
        assert ld.state()["generation"] == 2
        assert metrics.value("data_generation") == 2.0
    finally:
        ld.close()


@pytest.mark.chaos
@pytest.mark.elastic
def test_elastic_rollback_replay_redraws_identical_batches(hvd):
    """With sparse commits the resize REPLAYS steps: the loader cursor
    rewinds with the snapshot, so the replayed draw is bit-identical to
    the original (same (epoch, step, replay) key) — the exactly-once
    guarantee is over the logical stream, not raw read counts."""
    from collections import Counter

    from horovod_tpu.resilience import elastic

    chaos.configure("rank_fail=2,rank_fail_at_step=3")
    n, bs = 96, 24
    x, y = _xy(n)
    ld = ResumableLoader((x, y), bs, seed=13, prefetch=2, name="replay")
    draws = []

    def builder(world):
        def step_fn(state, i):
            _, yb = ld.next_batch()
            draws.append((i, np.asarray(yb).tolist()))
            return {"w": state["w"] + 1.0}

        return step_fn

    try:
        elastic.run(builder, {"w": np.zeros(1)}, num_steps=4,
                    snapshot_every=2)
        counts = Counter(i for i, _ in draws)
        replayed = [i for i, k in counts.items() if k > 1]
        assert replayed, "expected a replay with snapshot_every=2"
        for i in replayed:
            assert len({tuple(idx) for j, idx in draws if j == i}) == 1, \
                "replayed step drew different indices"
    finally:
        ld.close()


# ------------------------------------------------- shard store / chaos


def test_shard_store_roundtrip_and_crc(tmp_path):
    x, y = _xy(50)
    manifest = ArrayShardStore.write(str(tmp_path), (x, y), 16)
    assert [s["rows"] for s in manifest["shards"]] == [16, 16, 16, 2]
    store = ArrayShardStore(str(tmp_path))
    assert store.n_rows == 50 and store.n_shards == 4
    xs, ys = store.gather([0, 17, 33, 49])
    np.testing.assert_array_equal(ys, [0, 17, 33, 49])
    np.testing.assert_array_equal(xs, x[[0, 17, 33, 49]])
    assert store.shard_of(15) == 0 and store.shard_of(16) == 1
    with pytest.raises(IndexError):
        store.gather([50])
    # a loader runs straight off the store (host mode)
    ld = ResumableLoader(store, 10, seed=1, prefetch=0, device=False,
                         name="store", register=False)
    xb, yb = ld.next_batch()
    np.testing.assert_array_equal(xb, x[np.asarray(yb)])
    ld.close()


@pytest.mark.chaos
def test_shard_corrupt_quarantine_drill(tmp_path, hvd):
    """Acceptance drill (ISSUE 15): shard_corrupt → CRC mismatch →
    retries → quarantine; training CONTINUES past the shard with the
    substitution surfaced in metrics and health — never silently
    ignored, never a crash."""
    from horovod_tpu.observability import flight

    n, bs = 96, 24
    x, y = _xy(n)
    ArrayShardStore.write(str(tmp_path), (x, y), 16)
    chaos.configure("shard_corrupt=2:0")
    store = ArrayShardStore(str(tmp_path))
    ld = ResumableLoader(store, bs, seed=4, prefetch=2, name="corrupt")
    try:
        seen = []
        for _ in range(4):  # the full epoch: training continues
            xb, yb = ld.next_batch()
            assert xb.shape == (bs, 4)
            seen.extend(np.asarray(yb).tolist())
        assert store.quarantined() == [2]
        # the shard's rows [32, 48) were substituted, not served
        assert not (set(range(32, 48)) & set(seen))
        assert len(seen) == n  # static batch shapes held
        # surfaced: metrics + health SUSPECT naming the shard + flight
        # (>=: the prefetch thread speculates past the consumed batches)
        assert metrics.value("data_samples_substituted") >= 16.0
        assert metrics.value(
            "resilience_chaos_injected", site="shard_corrupt") >= 1.0
        assert metrics.value("data_quarantined_shards") == 1.0
        assert metrics.value("data_shard_retries", shard=2) >= 2.0
        assert health.health_state() >= health.HealthState.SUSPECT
        assert "shard-00002" in health.MONITOR.reason()
        assert any(
            e.get("event") == "shard_quarantined"
            for e in flight.events() if e["kind"] == "data"
        )
        # deterministic: the same epoch re-drawn substitutes identically
        ld2 = ResumableLoader(store, bs, seed=4, prefetch=0,
                              name="corrupt2", register=False)
        seen2 = []
        for _ in range(4):
            _, yb = ld2.next_batch()
            seen2.extend(np.asarray(yb).tolist())
        assert seen2 == seen
        ld2.close()
    finally:
        ld.close()


def test_all_shards_quarantined_raises(tmp_path):
    x, y = _xy(16)
    ArrayShardStore.write(str(tmp_path), (x, y), 16)  # ONE shard
    chaos.configure("shard_corrupt=0:0")
    store = ArrayShardStore(str(tmp_path))
    with pytest.raises(DataUnavailableError):
        store.gather([0, 1])


# --------------------------------------- data_stall drill + attribution


@pytest.mark.chaos
def test_data_stall_drill_names_rank_input_bound(hvd, monkeypatch):
    """Acceptance drill (ISSUE 15): HOROVOD_CHAOS=data_stall=3:1.0 —
    straggler attribution names rank 3 as *input-bound* (not compute),
    the flight recorder carries the stall event, and health goes
    SUSPECT."""
    from horovod_tpu.observability import flight

    monkeypatch.setenv("HOROVOD_DATA_WATCHDOG", "0.3")
    chaos.configure("data_stall=3:1.0")
    n, bs = 96, 24
    x, y = _xy(n, feat=8)
    ld = ResumableLoader((x, y), bs, seed=0, prefetch=1, name="stall")
    try:
        out = None
        for step in range(3):
            straggler.set_step(step)
            ld.next_batch()
            np.asarray(hvd.allreduce(
                np.ones((8, 8), np.float32), hvd.Sum))
            out = straggler.attribute()
        assert out is not None
        assert out["rank"] == 3
        assert out["cause"] == "input", out
        assert out["spread_seconds"] >= 0.5
        # health: SUSPECT (or DEGRADED if the stall strikes accumulated)
        # with the input-bound cause in the reason
        assert health.health_state() >= health.HealthState.SUSPECT
        assert "rank 3" in health.MONITOR.reason()
        assert "input-bound" in health.MONITOR.reason()
        # watchdog detected the stall (0.3s watchdog vs 1.0s stall)
        assert metrics.value("data_prefetch_stalls") >= 1.0
        assert metrics.value("resilience_input_stalls") >= 1.0
        assert metrics.value(
            "resilience_chaos_injected", site="data_stall") >= 1.0
        # flight recorder carries the stall event
        assert any(
            e.get("event") == "input_stall"
            for e in flight.events() if e["kind"] == "data"
        )
        # wait metrics fed the fleet signal
        assert metrics.value("data_wait_seconds_recent") is not None
    finally:
        ld.close()


def test_compute_bound_straggler_stays_compute(hvd):
    """rank_slow (a slow CHIP) must not be classified input-bound: the
    cause distinction is the whole point."""
    chaos.configure("rank_slow=2:0.08")
    out = None
    for step in range(3):
        straggler.set_step(step)
        np.asarray(hvd.allreduce(np.ones((4, 4), np.float32), hvd.Sum))
        out = straggler.attribute()
    assert out is not None and out["rank"] == 2
    assert out["cause"] == "compute"


def test_fleet_attribution_consumes_published_data_waits():
    """The fleet path: per-rank waits extracted from published snapshots
    classify the straggler input-bound on rank 0 (no local loader)."""
    records = []
    for q in range(3):
        records.append({
            "key": [0, 0, q], "op": "allreduce",
            "arrivals": {"0": 10.0 + q, "1": 10.3 + q},
        })
    merged = straggler.merge_arrival_exports([records])
    out = straggler.attribute(
        merged, expected_ranks=2, data_waits={1: 0.28})
    assert out is not None and out["rank"] == 1
    assert out["cause"] == "input"


# ------------------------------------------------ ShardedLoader fixes


def test_sharded_loader_set_epoch_mid_iteration_raises(hvd):
    from horovod_tpu.data import ShardedLoader

    x = np.ones((32, 2), np.float32)
    loader = ShardedLoader(x, 8, shuffle=False)
    it = iter(loader)
    next(it)
    with pytest.raises(RuntimeError, match="iterator is live"):
        loader.set_epoch(1)
    it.close()
    loader.set_epoch(1)  # fine once the iterator closed


def test_sharded_loader_epoch_snapshot_at_iter(hvd):
    from horovod_tpu.data import ShardedLoader

    x = np.zeros((32, 2), np.float32)
    y = np.arange(32, dtype=np.int32)
    loader = ShardedLoader((x, y), 8, seed=1)
    first = [np.asarray(b[1]).tolist() for b in loader]
    loader.set_epoch(1)
    second = [np.asarray(b[1]).tolist() for b in loader]
    assert first != second
    assert sorted(sum(first, [])) == sorted(sum(second, []))
    # the seed/epoch collision fix reaches ShardedLoader's order too
    a = ShardedLoader((x, y), 8, seed=0)
    a.set_epoch(1)
    b = ShardedLoader((x, y), 8, seed=1)
    assert [np.asarray(t[1]).tolist() for t in a] != \
        [np.asarray(t[1]).tolist() for t in b]


# ---------------------------------------------------------------- hvd_top


def test_hvd_top_input_pane_renders():
    import sys

    sys.path.insert(0, os.path.join(_REPO, "tools"))
    import hvd_top

    fleet = {
        "ranks": [0, 1], "dead_ranks": [], "straggler": None,
        "metrics": {
            "data_wait_seconds_recent": {
                "type": "gauge", "help": "", "samples": {"": {
                    "ranks": {"0": 0.001, "1": 0.25},
                    "min": 0.001, "mean": 0.125, "max": 0.25, "p99": 0.25,
                }},
            },
            "input_examples_per_second": {
                "type": "gauge", "help": "", "samples": {"": {
                    "ranks": {"0": 9000.0, "1": 120.0},
                    "min": 120.0, "mean": 4560.0, "max": 9000.0,
                    "p99": 9000.0,
                }},
            },
            "data_quarantined_shards": {
                "type": "gauge", "help": "", "samples": {"": {
                    "ranks": {"0": 1.0}, "min": 1.0, "mean": 1.0,
                    "max": 1.0, "p99": 1.0,
                }},
            },
        },
    }
    text = hvd_top.render(fleet)
    assert "INPUT:" in text
    assert "quarantined shards 1" in text
    assert "per-rank wait" in text
    # and an input-free fleet renders no pane
    assert "INPUT:" not in hvd_top.render(
        {"ranks": [0], "dead_ranks": [], "straggler": None, "metrics": {}})


# --------------------------------------------------- CI/tooling guards


def test_data_env_knobs_documented():
    """Every HOROVOD_DATA_* / HOROVOD_PREFETCH_* env knob named in the
    source must appear in docs/data.md's knob table (the metric-catalog
    guard pattern, PR 7/9/10)."""
    knob_re = re.compile(
        r"HOROVOD_(?:DATA|PREFETCH)_[A-Z]+(?:_[A-Z]+)*")
    knobs = set()
    for dirpath, _dirnames, filenames in os.walk(
            os.path.join(_REPO, "horovod_tpu")):
        for fn in filenames:
            if not fn.endswith(".py"):
                continue
            with open(os.path.join(dirpath, fn)) as f:
                knobs |= set(knob_re.findall(f.read()))
    assert {"HOROVOD_DATA_WATCHDOG", "HOROVOD_PREFETCH_BATCHES",
            "HOROVOD_DATA_CACHE_SHARDS"} <= knobs
    with open(os.path.join(_REPO, "docs", "data.md")) as f:
        doc = f.read()
    missing = sorted(k for k in knobs if k not in doc)
    assert not missing, (
        f"env knobs named in code but absent from the docs/data.md "
        f"knob table: {missing}"
    )


def test_data_chaos_charges_parse():
    spec = chaos.parse_spec("data_stall=3:0.5,shard_corrupt=2:1")
    assert spec["data_stall"] == (3, 0.5)
    assert spec["shard_corrupt"] == (2, 1)
    # shard_corrupt's read index defaults to 0
    assert chaos.parse_spec("shard_corrupt=4")["shard_corrupt"] == (4, 0)
    with pytest.raises(ValueError):
        chaos.parse_spec("data_stall=3")
