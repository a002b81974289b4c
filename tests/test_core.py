"""Native control-plane core tests (reference analog: the C++ core is
exercised through the Python bindings, SURVEY.md §4)."""

import os
import time

import numpy as np
import pytest
import jax
from jax.sharding import NamedSharding, PartitionSpec as P


@pytest.fixture()
def hvd_core(monkeypatch, tmp_path):
    """init with the native core attached (single-process local controller)."""
    import horovod_tpu as hvd

    monkeypatch.setenv("HOROVOD_CYCLE_TIME", "2")
    hvd.shutdown()
    hvd.init(native_core=True)
    yield hvd
    hvd.shutdown()


def stacked(hvd, x):
    return jax.device_put(x, NamedSharding(hvd.mesh(), P(hvd.data_axis())))


def test_core_allreduce_roundtrip(hvd_core):
    hvd = hvd_core
    n = hvd.size()
    x = np.arange(n * 4, dtype=np.float32).reshape(n, 4)
    h = hvd.allreduce_async(stacked(hvd, x), op=hvd.Sum, name="core.g0")
    out = hvd.synchronize(h)
    np.testing.assert_allclose(np.asarray(out), x.sum(axis=0))


def test_core_many_tensors_one_cycle(hvd_core):
    """Multiple small tensors negotiated in one cycle get fused into one
    grouped collective; results must still be per-tensor correct."""
    hvd = hvd_core
    n = hvd.size()
    xs = [
        np.random.RandomState(i).randn(n, 8).astype(np.float32)
        for i in range(6)
    ]
    handles = [
        hvd.allreduce_async(stacked(hvd, x), op=hvd.Sum, name=f"core.f{i}")
        for i, x in enumerate(xs)
    ]
    for h, x in zip(handles, xs):
        np.testing.assert_allclose(
            np.asarray(hvd.synchronize(h)), x.sum(axis=0), rtol=1e-5
        )


def test_core_steady_state_cache(hvd_core):
    """Same named tensor over multiple steps rides the response cache."""
    hvd = hvd_core
    n = hvd.size()
    for step in range(5):
        x = np.full((n, 2), float(step), dtype=np.float32)
        h = hvd.allreduce_async(stacked(hvd, x), op=hvd.Sum, name="core.grad")
        out = hvd.synchronize(h)
        np.testing.assert_allclose(np.asarray(out), x.sum(axis=0))


def test_core_duplicate_name(hvd_core):
    hvd = hvd_core
    from horovod_tpu.basics import _state

    _state.core.cycle_time_ms = 500  # hold the cycle open
    n = hvd.size()
    x = stacked(hvd, np.ones((n, 2), dtype=np.float32))
    # the loop reads the cycle time as it starts a sleep: let the 2 ms one
    # it is in end, or a loaded host can see the first name negotiated and
    # done before the second is enqueued
    time.sleep(0.05)
    h = hvd.allreduce_async(x, op=hvd.Sum, name="core.dup")
    with pytest.raises(ValueError, match="Duplicate tensor name"):
        hvd.allreduce_async(x, op=hvd.Sum, name="core.dup")
    _state.core.cycle_time_ms = 2
    hvd.synchronize(h)


def test_core_broadcast_and_allgather(hvd_core):
    hvd = hvd_core
    n = hvd.size()
    xb = np.stack([np.full((3,), r, dtype=np.float32) for r in range(n)])
    hb = hvd.broadcast_async(stacked(hvd, xb), root_rank=2, name="core.b")
    np.testing.assert_array_equal(
        np.asarray(hvd.synchronize(hb)), np.full((3,), 2.0)
    )
    xg = np.arange(n * 2, dtype=np.float32).reshape(n, 2)
    hg = hvd.allgather_async(stacked(hvd, xg), name="core.ag")
    np.testing.assert_array_equal(
        np.asarray(hvd.synchronize(hg)), xg.reshape(-1)
    )


def test_core_knobs(hvd_core):
    from horovod_tpu.basics import _state

    core = _state.core
    assert core.fusion_threshold == 64 * 1024 * 1024
    core.fusion_threshold = 1024
    assert core.fusion_threshold == 1024
    assert core.pending_count() == 0


def test_core_timeline(monkeypatch, tmp_path):
    """Two sessions in one process: the library's globals outlive
    init/shutdown, and a second session's file once opened with the
    separator of the first's (invalid JSON) — which session this test got
    depended on the files its xdist worker had run before."""
    import json

    import horovod_tpu as hvd

    monkeypatch.setenv("HOROVOD_CYCLE_TIME", "2")
    hvd.shutdown()
    for session in range(2):
        tl = tmp_path / f"timeline{session}.json"
        monkeypatch.setenv("HOROVOD_TIMELINE", str(tl))
        hvd.init(native_core=True)
        n = hvd.size()
        x = stacked(hvd, np.ones((n, 2), dtype=np.float32))
        for i in range(3):
            hvd.synchronize(
                hvd.allreduce_async(x, op=hvd.Sum, name=f"tl.{i}")
            )
        hvd.shutdown()
        content = tl.read_text()
        assert "NEGOTIATE" in content
        assert "ALLREDUCE" in content
        assert "CYCLE_START" in content
        events = json.loads(content)
        assert isinstance(events, list) and len(events) > 5


def test_core_prescale_postscale(hvd_core):
    hvd = hvd_core
    n = hvd.size()
    x = np.ones((n, 2), dtype=np.float32)
    h = hvd.allreduce_async(
        stacked(hvd, x), op=hvd.Sum, name="core.scale",
        prescale_factor=2.0, postscale_factor=0.5,
    )
    np.testing.assert_allclose(
        np.asarray(hvd.synchronize(h)), np.full((2,), float(n))
    )


def test_core_multiprocess_requires_coordinator():
    from horovod_tpu.core import NativeCore

    with pytest.raises(ValueError, match="coordinator"):
        NativeCore(rank=0, size=2, coordinator_host=None)


def test_core_allgather_fusion(hvd_core):
    """Two named allgathers ready in one cycle fuse into ONE response (the
    reference fuses allgathers too, controller.cc:700-755) and launch as one
    grouped XLA program; per-rank size blocks concatenate on the wire."""
    hvd = hvd_core
    from horovod_tpu import core as core_mod

    core = hvd.basics._state.core
    core.cycle_time_ms = 150  # widen the window so both land in one cycle

    plans = []
    orig = core_mod.NativeCore._execute_one

    def spy(self, resp, handles):
        plans.append(
            (resp.response_type, list(resp.tensor_names),
             list(resp.tensor_sizes))
        )
        return orig(self, resp, handles)

    core_mod.NativeCore._execute_one = spy
    try:
        for attempt in range(4):
            ha = hvd.allgather_async(
                np.ones((2, 3), np.float32), name=f"ag{attempt}_a"
            )
            hb = hvd.allgather_async(
                np.full((1, 3), 2.0, np.float32), name=f"ag{attempt}_b"
            )
            out_a = np.asarray(hvd.synchronize(ha))
            out_b = np.asarray(hvd.synchronize(hb))
            if any(
                t == core_mod.REQUEST_ALLGATHER and len(names) == 2
                for t, names, _ in plans
            ):
                break
    finally:
        core_mod.NativeCore._execute_one = orig

    # replicated input on the 8-chip mesh: every chip contributes the array
    assert out_a.shape == (2 * hvd.size(), 3)
    assert out_b.shape == (1 * hvd.size(), 3)
    np.testing.assert_allclose(out_b, 2.0)
    fused = [
        sizes for t, names, sizes in plans
        if t == core_mod.REQUEST_ALLGATHER and len(names) == 2
    ]
    assert fused, f"allgather responses never fused: {plans}"
    # one per-rank size block per tensor (size_ entries each, single proc)
    assert len(fused[0]) == 2


def test_grouped_allgather_matches_per_tensor(hvd_core):
    hvd = hvd_core
    n = hvd.size()
    rng = np.random.RandomState(0)
    xs = [
        stacked(hvd, rng.randn(n, 2, 3).astype(np.float32)),
        stacked(hvd, rng.randn(n, 1, 3).astype(np.float32)),
    ]
    outs = hvd.grouped_allgather(xs)
    for x, o in zip(xs, outs):
        np.testing.assert_allclose(np.asarray(o), np.asarray(hvd.allgather(x)))
