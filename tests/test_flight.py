"""Black-box flight recorder + cross-rank hang forensics (ISSUE 14).

The acceptance pin: under ``HOROVOD_CHAOS=rank_hang_at_step=K`` on the
8-device CPU mesh, the live hang detector AND the offline
``tools/hvd_blackbox.py`` analysis of sidecar files alone both name the
hung rank and the exact collective signature ``(step, gen, seq)``; a
variant that SIGKILLs the hung process still diagnoses from the surviving
ranks' records. Plus unit coverage of the ring, the torn-tail-tolerant
sidecar, the verdict classes, and the env-knob doc guard."""

import json
import os
import re
import signal
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from horovod_tpu.observability import flight, metrics, straggler
from horovod_tpu.run.rendezvous import InProcessKVStore
from horovod_tpu.resilience import chaos, health

_TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_TESTS_DIR)


@pytest.fixture(autouse=True)
def _iso(monkeypatch):
    """Flight/chaos/health/metrics state is module-global: every test
    starts clean and leaves nothing armed (a stray watchdog thread or
    chaos charge would poison later tests)."""
    for var in ("HOROVOD_FLIGHT", "HOROVOD_FLIGHT_DIR",
                "HOROVOD_FLIGHT_MAX_EVENTS", "HOROVOD_FLIGHT_FLUSH_EVERY",
                "HOROVOD_FLIGHT_MAX_BYTES", "HOROVOD_HANG_TIMEOUT",
                "HOROVOD_HANG_TAIL", "HOROVOD_HANG_EVICT"):
        monkeypatch.delenv(var, raising=False)
    metrics.reset()
    metrics.set_enabled(True)
    flight.reset()
    chaos.configure(None)
    health.reset()
    straggler.reset()
    yield
    flight.reset()
    chaos.reset()
    health.reset()
    straggler.reset()
    metrics.reset()


# ------------------------------------------------------------- ring basics


def test_ring_is_bounded(monkeypatch):
    monkeypatch.setenv("HOROVOD_FLIGHT_MAX_EVENTS", "16")
    flight.reset()
    for i in range(40):
        flight.record("note", i=i)
    evs = flight.events()
    assert len(evs) == 16
    assert evs[0]["i"] == 24 and evs[-1]["i"] == 39  # oldest dropped
    assert metrics.value("flight_events", kind="note") == 40


def test_disabled_records_nothing(monkeypatch):
    monkeypatch.setenv("HOROVOD_FLIGHT", "0")
    flight.reset()
    assert flight.record("note") is None
    flight.collective_begin("allreduce", (0, 0, 0))
    flight.step_boundary(0)
    assert flight.events() == []


def test_collective_end_once_per_key():
    flight.collective_begin("allreduce", (0, 0, 0))
    flight.collective_end()
    flight.collective_end()  # grouped launches: one end per begin
    kinds = [(e.get("ph"), e.get("seq")) for e in flight.events()
             if e["kind"] == "collective"]
    assert kinds == [("b", 0), ("e", 0)]


# ------------------------------------------------------- sidecar durability


def test_sidecar_roundtrip_and_torn_tail(tmp_path):
    d = str(tmp_path)
    flight.configure(dir=d)
    for s in range(2):
        flight.step_boundary(s)
        for q in range(3):
            flight.collective_begin("allreduce", (s, 0, q))
            flight.collective_end()
    path = flight.flush()
    assert path == os.path.join(d, "flight-rank0.jsonl")
    # SIGKILL mid-write: a torn half line at the tail must not poison the
    # record (the rendezvous-WAL discipline)
    with open(path, "a") as f:
        f.write('{"t": 1.0, "kind": "collective", "ph": "b", "st')
    side = flight.load_sidecar(path)
    assert side["skipped"] == 1
    assert side["ranks"] == [0]
    colls = [e for e in side["events"] if e["kind"] == "collective"]
    assert len(colls) == 12  # 2 steps x 3 collectives x (b + e)
    verdict = flight.analyze_dir(d)
    assert verdict["verdict"] == "progressing"


def test_sidecar_compaction_bounds_the_file(tmp_path, monkeypatch):
    monkeypatch.setenv("HOROVOD_FLIGHT_MAX_EVENTS", "32")
    monkeypatch.setenv("HOROVOD_FLIGHT_FLUSH_EVERY", "1")
    monkeypatch.setenv("HOROVOD_FLIGHT_MAX_BYTES", "4000")
    flight.reset()
    flight.configure(dir=str(tmp_path))
    for s in range(100):
        flight.collective_begin("allreduce", (s, 0, 0))
        flight.collective_end()
    flight.flush()
    path = flight.sidecar_path()
    assert os.path.getsize(path) < 2 * 4000  # bounded, not unbounded-append
    assert metrics.value("flight_sidecar_compactions") >= 1
    side = flight.load_sidecar(path)
    assert side["events"]  # still a loadable record after compaction
    assert flight.analyze_dir(str(tmp_path))["verdict"] == "progressing"


# -------------------------------------------------------- verdict classes


def _stream(keys, *, end_last=True, op="allreduce", ops=None):
    """[(step, seq), ...] -> b/e event stream; the last begin is left
    unended when end_last=False (the parked state)."""
    out = []
    for i, (s, q) in enumerate(keys):
        o = ops[i] if ops else op
        out.append({"t": float(i), "kind": "collective", "ph": "b",
                    "op": o, "step": s, "gen": 0, "seq": q})
        if end_last or i < len(keys) - 1:
            out.append({"t": float(i) + 0.5, "kind": "collective",
                        "ph": "e", "op": o, "step": s, "gen": 0, "seq": q})
    return out


def test_analyze_rank_missing_names_signature():
    evs = {
        0: _stream([(0, 0), (0, 1), (1, 0)], end_last=False),
        1: _stream([(0, 0), (0, 1), (1, 0)], end_last=False),
        2: _stream([(0, 0), (0, 1)]),  # never arrived at (1, 0, 0)
    }
    v = flight.analyze(evs, expected=[0, 1, 2])
    assert v["verdict"] == "rank_missing"
    assert v["hung_ranks"] == [2]
    assert v["key"] == [1, 0, 0] and v["op"] == "allreduce"
    assert v["waiting"] == [0, 1]
    assert "rank(s) [2] missing" in flight.describe(v)


def test_analyze_missing_rank_with_no_record_at_all():
    evs = {0: _stream([(0, 0)], end_last=False)}
    v = flight.analyze(evs, expected=[0, 1])
    assert v["verdict"] == "rank_missing" and v["hung_ranks"] == [1]
    assert v["key"] == [0, 0, 0]


def test_analyze_missing_rank_after_survivors_moved_on():
    """Offline after an eviction/release: survivors progressed past the
    stuck collective — the verdict still names the FIRST signature the
    missing rank never joined, not the end-of-run frontier."""
    evs = {
        0: _stream([(0, 0), (0, 1), (1, 0), (1, 1), (2, 0)]),
        1: _stream([(0, 0), (0, 1)]),  # stopped before (1, 0, 0)
    }
    v = flight.analyze(evs, expected=[0, 1])
    assert v["verdict"] == "rank_missing" and v["hung_ranks"] == [1]
    assert v["key"] == [1, 0, 0]


def test_analyze_schedule_divergence_by_sched_hash():
    a = _stream([(0, 0), (1, 0)], end_last=False)
    b = _stream([(0, 0), (1, 0)], end_last=False)
    a.append({"t": 9.0, "kind": "sched", "step": 0, "hash": "aaaa", "n": 1})
    b.append({"t": 9.0, "kind": "sched", "step": 0, "hash": "bbbb", "n": 1})
    v = flight.analyze({0: a, 1: b}, expected=[0, 1])
    assert v["verdict"] == "schedule_divergence"
    assert v["hung_ranks"] == [1]
    assert "diverged" in flight.describe(v)


def test_analyze_schedule_divergence_by_forked_op():
    """Ranks parked at the SAME seq on DIFFERENT collectives: the
    schedules forked — stronger evidence than the (one-step-lagged)
    hashes."""
    a = _stream([(0, 0), (0, 1)], end_last=False,
                ops=["allreduce", "allreduce"])
    b = _stream([(0, 0), (0, 1)], end_last=False,
                ops=["allreduce", "allgather"])
    v = flight.analyze({0: a, 1: b}, expected=[0, 1])
    assert v["verdict"] == "schedule_divergence"
    assert v["hung_ranks"] == [1]


def test_analyze_all_parked_and_progressing():
    parked = {r: _stream([(0, 0)], end_last=False) for r in range(3)}
    v = flight.analyze(parked, expected=[0, 1, 2])
    assert v["verdict"] == "all_parked" and v["hung_ranks"] == []
    done = {r: _stream([(0, 0)]) for r in range(3)}
    assert flight.analyze(done, expected=[0, 1, 2])["verdict"] == \
        "progressing"
    assert flight.analyze({}, expected=[0])["verdict"] == "no_data"


def test_health_record_hang_goes_degraded_with_signature():
    health.record_hang(5, [3, 1, 7])
    snap = health.snapshot()
    assert snap["state"] == "DEGRADED"
    assert "rank 5" in snap["reason"] and "(3, 1, 7)" in snap["reason"]
    assert metrics.value("resilience_hangs", rank=5) == 1
    # flight ring mirrored the transition
    hs = [e for e in flight.events() if e["kind"] == "health"]
    assert hs and hs[-1]["dst"] == "DEGRADED"


# ------------------------------------------- the deterministic live drill


@pytest.mark.chaos
def test_rank_hang_drill_live_and_offline(tmp_path, monkeypatch):
    """THE acceptance pin (single-controller half). 8-device mesh,
    ``rank_hang_at_step=1``: rank 7 stops dispatching mid-step — the live
    watchdog names rank 7 and the exact ``(step, gen, seq)``, health goes
    DEGRADED with the signature in its reason, and the offline
    ``hvd_blackbox`` analysis of the sidecar files alone reaches the SAME
    verdict after the process state is gone."""
    d = str(tmp_path / "flight")
    monkeypatch.setenv("HOROVOD_FLIGHT_DIR", d)
    monkeypatch.setenv("HOROVOD_HANG_TIMEOUT", "0.25")
    flight.reset()
    chaos.configure("rank_hang_at_step=1,rank_hang_hold=8.0")

    import horovod_tpu as hvd
    from horovod_tpu.training import instrument_step

    hvd.init()
    try:
        def raw_step(x, n=3):
            for _ in range(n):
                x = hvd.allreduce(x)
            return x

        step = instrument_step(raw_step, examples_per_step=8)
        x = np.ones((8,), np.float32)
        t0 = time.monotonic()
        for _ in range(3):
            x = step(x)
        # the hold was released by the live diagnosis, not the 8 s budget
        assert time.monotonic() - t0 < 6.0
        for _ in range(100):  # the diagnosing watchdog is a thread
            if flight.last_hang() is not None:
                break
            time.sleep(0.02)
        v = flight.last_hang()
        assert v is not None and v["verdict"] == "rank_missing"
        assert v["hung_ranks"] == [7]
        assert v["key"][0] == 1 and v["key"][1] == 0  # step 1, gen 0
        assert v["key"][2] >= 1  # mid-step: the drill fires from seq 1 on
        assert v["op"] == "allreduce"
        assert v["waiting"] == [0, 1, 2, 3, 4, 5, 6]
        snap = health.snapshot()
        assert snap["state"] == "DEGRADED"
        assert "rank 7" in snap["reason"] and "missing" in snap["reason"]
        assert metrics.value("hang_watchdog_fired") >= 1
        assert metrics.value("hang_diagnosed", verdict="rank_missing") >= 1
        assert metrics.value(
            "resilience_chaos_injected", site="rank_hang_at_step") == 1
        live_key = list(v["key"])
    finally:
        hvd.shutdown()
        # this drill warms the shape-independent eager-kernel caches on
        # the full 8-mesh; later tests assert cold-cache compile counts
        from horovod_tpu.ops.collective import clear_eager_caches

        clear_eager_caches()

    # offline: the SAME verdict from the sidecar files alone
    off = flight.analyze_dir(d)
    assert off["verdict"] == "rank_missing"
    assert off["hung_ranks"] == [7]
    assert off["key"] == live_key and off["op"] == "allreduce"
    # and through the CLI (exit 3 = hang found, scriptable)
    out = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "hvd_blackbox.py"),
         d],
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 3, out.stderr
    assert "rank(s) [7] missing" in out.stdout
    assert f"(step, gen, seq)=({live_key[0]}, {live_key[1]}, " \
           f"{live_key[2]})" in out.stdout
    out_json = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "hvd_blackbox.py"),
         d, "--json"],
        capture_output=True, text=True, timeout=120,
    )
    assert json.loads(out_json.stdout)["hung_ranks"] == [7]


# ------------------------------------- the SIGKILL (dead-process) variant


_KILL_WORKER = textwrap.dedent("""
    import os, sys, time
    sys.path.insert(0, {repo!r})
    from horovod_tpu.observability import flight

    rank = int(sys.argv[1])
    flight.configure(dir={flight_dir!r}, rank=rank, world=2)
    for step in range(3):
        flight.step_boundary(step)
        for seq in range(3):
            if rank == 1 and step == 1 and seq == 1:
                # "hangs": never begins (1, 0, 1); SIGKILLed while parked
                flight.flush()
                print("PARKED", flush=True)
                time.sleep(60)
            flight.collective_begin("allreduce", (step, 0, seq))
            flight.collective_end()
        flight.flush()
    print("DONE", flush=True)
""")


@pytest.mark.chaos
def test_sigkill_variant_diagnoses_from_surviving_records(tmp_path):
    """THE acceptance pin (dead-process half): the hung process is
    SIGKILLed mid-drill — no shutdown, no flush of anything after the
    park — and the offline analysis still names it and the exact
    signature from whatever its crash-durable sidecar (plus the
    survivors') retained."""
    d = str(tmp_path / "flight")
    os.makedirs(d)
    script = tmp_path / "worker.py"
    script.write_text(_KILL_WORKER.format(repo=_REPO, flight_dir=d))
    env = dict(os.environ)
    env.pop("HOROVOD_FLIGHT_DIR", None)
    p1 = subprocess.Popen(
        [sys.executable, str(script), "1"], env=env,
        stdout=subprocess.PIPE, text=True,
    )
    assert p1.stdout.readline().strip() == "PARKED"
    p1.kill()  # SIGKILL: no handlers, no flush path — the sidecar is all
    p1.wait(timeout=60)
    assert p1.returncode == -signal.SIGKILL
    p0 = subprocess.run(
        [sys.executable, str(script), "0"], env=env, timeout=120,
        capture_output=True, text=True,
    )
    assert "DONE" in p0.stdout

    v = flight.analyze_dir(d)
    assert v["verdict"] == "rank_missing"
    assert v["hung_ranks"] == [1]
    assert v["key"] == [1, 0, 1] and v["op"] == "allreduce"
    out = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "hvd_blackbox.py"),
         d],
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 3
    assert "rank(s) [1] missing" in out.stdout
    assert "(step, gen, seq)=(1, 0, 1)" in out.stdout


# --------------------------------------------- preemption drain satellite


@pytest.mark.chaos
def test_preempt_drain_flushes_flight_ring(tmp_path):
    """Satellite (ISSUE 14): the SIGTERM drain flushes the flight ring
    (and the trace sidecars) BEFORE the emergency checkpoint — a
    preempted run keeps its record, not only its weights."""
    from horovod_tpu.resilience import loop

    d = str(tmp_path / "flight")
    flight.configure(dir=d)
    flight.collective_begin("allreduce", (0, 0, 0))
    flight.collective_end()
    chaos.configure("sigterm_at_step=1")
    with pytest.raises(loop.Preempted):
        loop.run(lambda s, i: s, np.zeros(2), num_steps=4)
    side = flight.load_sidecar(os.path.join(d, "flight-rank0.jsonl"))
    kinds = [e["kind"] for e in side["events"]]
    assert "preempt" in kinds  # the drain reached the flight flush
    assert "collective" in kinds


# ----------------------------------------------------- watchdog lifecycle


def test_watchdog_does_not_fire_while_progressing():
    kv = InProcessKVStore()
    flight.configure(kv=kv, world=2)
    flight.arm_watchdog(timeout=0.15)
    try:
        for i in range(8):
            flight.collective_begin("allreduce", (0, 0, i))
            flight.collective_end()
            time.sleep(0.04)  # well under the timeout
        assert flight.last_hang() is None
        assert metrics.value("hang_watchdog_fired") is None
    finally:
        flight.disarm_watchdog()


def test_watchdog_fires_once_per_stall_and_rearms():
    kv = InProcessKVStore()
    flight.configure(kv=kv, world=2)
    flight.arm_watchdog(timeout=0.1)
    try:
        flight.collective_begin("allreduce", (0, 0, 0))
        flight.collective_end()
        time.sleep(0.5)  # stall >> timeout: exactly one firing
        assert metrics.value("hang_watchdog_fired") == 1
        # progress resumes -> the watchdog re-arms -> a second stall fires
        flight.collective_begin("allreduce", (0, 0, 1))
        flight.collective_end()
        time.sleep(0.5)
        assert metrics.value("hang_watchdog_fired") == 2
    finally:
        flight.disarm_watchdog()


def test_hang_evict_queues_rank(monkeypatch, tmp_path):
    """HOROVOD_HANG_EVICT=1: a diagnosed missing rank lands in the
    eviction queue the elastic membership sweep drains."""
    monkeypatch.setenv("HOROVOD_HANG_EVICT", "1")
    kv = InProcessKVStore()
    # rank pinned: this process pushes ONLY its own tail (the
    # multi-process convention), so the planted rank-1 tail survives
    flight.configure(kv=kv, world=2, rank=0)
    # rank 1's tail is behind rank 0's -> missing at (0, 0, 1)
    flight.step_boundary(0)  # the progress baseline the stall is against
    for seq in range(2):
        flight.collective_begin("allreduce", (0, 0, seq))
    kv.put(f"{flight.TAIL_SCOPE}/1", json.dumps({
        "rank": 1, "world": 2, "offset_s": 0.0, "generation": 0,
        "events": _stream([(0, 0)]),
    }).encode())
    flight.arm_watchdog(timeout=0.1)
    try:
        for _ in range(100):
            if flight.last_hang() is not None:
                break
            time.sleep(0.02)
        v = flight.last_hang()
        assert v is not None and v["hung_ranks"] == [1]
        assert flight.take_hung_ranks() == [1]
        assert flight.take_hung_ranks() == []  # drained
    finally:
        flight.disarm_watchdog()


# ------------------------------------------------------------- doc guards


def test_flight_env_knobs_documented():
    """CI guard (ISSUE 14 satellite): every HOROVOD_FLIGHT_* /
    HOROVOD_HANG_* literal in horovod_tpu/ must appear in the
    docs/observability.md knob table (metric-catalog-guard pattern); the
    flight_*/hang_* metric names are covered by
    test_metric_catalog_covers_every_emitted_name."""
    knob_re = re.compile(r"HOROVOD_(?:FLIGHT|HANG)(?:_[A-Z]+)*")
    knobs = set()
    for dirpath, _dirnames, filenames in os.walk(
            os.path.join(_REPO, "horovod_tpu")):
        for fn in filenames:
            if not fn.endswith(".py"):
                continue
            with open(os.path.join(dirpath, fn)) as f:
                knobs |= set(knob_re.findall(f.read()))
    assert {"HOROVOD_FLIGHT", "HOROVOD_FLIGHT_DIR", "HOROVOD_HANG_TIMEOUT",
            "HOROVOD_HANG_EVICT"} <= knobs
    with open(os.path.join(_REPO, "docs", "observability.md")) as f:
        doc = f.read()
    missing = sorted(k for k in knobs if k not in doc)
    assert not missing, (
        f"flight/hang env knobs named in code but absent from the "
        f"docs/observability.md knob table: {missing}"
    )
