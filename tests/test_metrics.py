"""Observability layer: metrics registry semantics, exporter formats,
instrumentation hooks (eager ops, native-core cycle callback), the merged
host+native chrome-trace timeline, and the import-side-effect guard — plus
the ISSUE 7 fleet plane: cross-rank snapshot aggregation over the
rendezvous KV, clock-offset estimation, correlated per-rank collective
traces, and deterministic straggler attribution.

No reference analog — upstream Horovod's only observability surface is the
chrome Timeline; the queryable registry is this rebuild's addition
(ISSUE 1). Tier-1: everything here runs on the 8-device CPU mesh."""

import json
import os
import re
import subprocess
import sys
import threading
import urllib.request

import numpy as np
import pytest

from horovod_tpu.observability import (
    aggregate,
    clock,
    exporters,
    metrics,
    straggler,
    trace,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_registry():
    """Every test sees an empty default registry, a clean trace buffer, and
    an unsynchronized fleet layer."""
    metrics.reset()
    metrics.set_enabled(True)
    trace.reset()
    straggler.reset()
    clock.reset()
    aggregate.set_aggregator(None)
    yield
    metrics.reset()
    metrics.set_enabled(True)
    trace.reset()
    straggler.reset()
    clock.reset()
    aggregate.set_aggregator(None)


# ------------------------------------------------------------ registry


def test_counter_semantics():
    c = metrics.counter("requests")
    c.inc()
    c.inc(4)
    assert metrics.counter("requests").value == 5.0
    with pytest.raises(ValueError, match=">= 0"):
        c.inc(-1)


def test_labeled_children_are_distinct():
    metrics.counter("allreduce_bytes", rank=0).inc(100)
    metrics.counter("allreduce_bytes", rank=1).inc(7)
    metrics.counter("allreduce_bytes").inc(1)  # unlabeled child coexists
    snap = metrics.snapshot()["allreduce_bytes"]
    assert snap["type"] == "counter"
    assert snap["samples"]["rank=0"] == 100.0
    assert snap["samples"]["rank=1"] == 7.0
    assert snap["samples"][""] == 1.0
    assert metrics.value("allreduce_bytes", rank=1) == 7.0
    assert metrics.value("allreduce_bytes", rank=9) is None


def test_gauge_set_inc():
    g = metrics.gauge("util")
    g.set(0.5)
    g.inc(0.25)
    g.dec(0.5)
    assert abs(metrics.value("util") - 0.25) < 1e-12


def test_histogram_buckets():
    h = metrics.histogram("lat", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.05, 0.5, 5.0):
        h.observe(v)
    s = metrics.value("lat")
    assert s["count"] == 5
    assert abs(s["sum"] - 5.605) < 1e-9
    # cumulative, prometheus-style, with the implicit +Inf tail
    assert s["buckets"]["0.01"] == 1
    assert s["buckets"]["0.1"] == 3
    assert s["buckets"]["1.0"] == 4
    assert s["buckets"]["+Inf"] == 5
    h.observe(float("nan"))  # must not poison sum/count
    assert metrics.value("lat")["count"] == 5


def test_kind_conflict_raises():
    metrics.counter("x").inc()
    with pytest.raises(ValueError, match="already registered"):
        metrics.gauge("x")


def test_disabled_is_noop():
    metrics.set_enabled(False)
    c = metrics.counter("never")
    c.inc(100)
    h = metrics.histogram("never_h")
    h.observe(1.0)
    metrics.set_enabled(True)
    assert "never" not in metrics.snapshot()
    assert metrics.value("never") is None


def test_thread_safety_smoke():
    n_threads, n_inc = 8, 2000

    def worker():
        for _ in range(n_inc):
            metrics.counter("contended").inc()
            metrics.histogram("contended_h", buckets=(1, 2)).observe(1)

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert metrics.value("contended") == n_threads * n_inc
    assert metrics.value("contended_h")["count"] == n_threads * n_inc


def test_summary_renders():
    metrics.counter("a").inc(2)
    metrics.histogram("b").observe(0.01)
    out = metrics.summary()
    assert "a" in out and "b" in out and "count=1" in out


# ------------------------------------------------------------ exporters

_PROM_LINE = re.compile(
    r"^(# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* .+"
    r"|[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? -?[0-9.e+-]+(inf|nan)?)$"
)


def test_prometheus_exposition_parses():
    metrics.counter("allreduce_count").inc(3)
    metrics.counter("allreduce_bytes", rank=0).inc(1024)
    metrics.gauge("train_mfu").set(0.41)
    metrics.histogram("cycle", buckets=(0.5, 1.5)).observe(1.0)
    text = exporters.to_prometheus()
    assert text.endswith("\n")
    for line in text.rstrip("\n").splitlines():
        assert _PROM_LINE.match(line), f"bad exposition line: {line!r}"
    assert "allreduce_count 3" in text
    assert 'allreduce_bytes{rank="0"} 1024' in text
    assert 'cycle_bucket{le="+Inf"} 1' in text
    assert "cycle_sum 1" in text
    assert "cycle_count 1" in text
    assert "# TYPE cycle histogram" in text


def test_prometheus_nonfinite_samples_render():
    """inf/nan samples must render as exposition spellings, not crash the
    scrape handler (int(inf) raises)."""
    metrics.gauge("pos").set(float("inf"))
    metrics.gauge("neg").set(float("-inf"))
    metrics.gauge("nan").set(float("nan"))
    metrics.histogram("h", buckets=(1.0,)).observe(float("inf"))
    text = exporters.to_prometheus()
    assert "pos +Inf" in text
    assert "neg -Inf" in text
    assert "nan NaN" in text
    assert "h_sum +Inf" in text


def test_trace_recording_gate():
    """set_recording(False) (what init() applies on ranks != 0) silences
    span/instant recording even with HOROVOD_TIMELINE set; the buffer cap
    drops rather than grows past MAX_BUFFERED_EVENTS."""
    os.environ["HOROVOD_TIMELINE"] = "/tmp/_never_written.json"
    try:
        trace.reset()
        trace.set_recording(False)
        with trace.span("t", "x"):
            pass
        trace.instant("t", "y")
        assert trace.events() == []
        trace.set_recording(True)
        with trace.span("t", "x"):
            pass
        assert len(trace.events()) == 1
    finally:
        del os.environ["HOROVOD_TIMELINE"]
        trace.reset()


def test_json_exporter_roundtrips():
    metrics.counter("c", job="x").inc(2)
    data = json.loads(exporters.to_json())
    assert data["c"]["samples"]["job=x"] == 2.0


def test_http_endpoint_serves_both_formats():
    metrics.counter("served").inc(9)
    server = exporters.start_http_server(0, host="127.0.0.1")
    try:
        port = server.server_port
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ) as r:
            body = r.read().decode()
            assert "served 9" in body
            assert r.headers["Content-Type"].startswith("text/plain")
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics.json", timeout=10
        ) as r:
            assert json.load(r)["served"]["samples"][""] == 9.0
    finally:
        exporters.stop_http_server()


# ------------------------------------------- instrumentation: eager ops


def test_eager_allreduce_feeds_registry(hvd):
    # the compiled-kernel cache outlives init/shutdown on an equal mesh: an
    # earlier test file on this worker may have left this very kernel in it
    from horovod_tpu.ops import collective

    collective._eager_allreduce_fn.cache_clear()
    out = hvd.allreduce(np.ones((8, 4), np.float32), op=hvd.Sum)
    out2 = hvd.allreduce(np.ones((8, 4), np.float32), op=hvd.Sum)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(out))
    assert metrics.value("allreduce_count") == 2
    assert metrics.value("allreduce_bytes") == 2 * 8 * 4 * 4
    # same (mesh, axis, shape) twice: first lookup compiles, second hits
    assert metrics.value("eager_compile_cache_misses", kind="allreduce") >= 1
    assert metrics.value("eager_compile_cache_hits", kind="allreduce") >= 1


def test_grouped_and_other_ops_feed_registry(hvd):
    hvd.grouped_allreduce(
        [np.ones((4,), np.float32), np.ones((2, 2), np.float32)], hvd.Sum
    )
    hvd.allgather(np.ones((2, 3), np.float32))
    hvd.reducescatter(np.ones((8, 2), np.float32), hvd.Sum)
    assert metrics.value("allreduce_tensors") == 2
    assert metrics.value("allreduce_bytes") == 4 * 4 + 4 * 4
    assert metrics.value("allgather_count") == 1
    assert metrics.value("reducescatter_count") == 1


def test_train_step_instrumentation(hvd):
    import optax

    from horovod_tpu import models
    from horovod_tpu.training import (
        init_model, make_jit_train_step, replicate, shard_batch,
    )

    model = models.MLP(features=(8, 4))
    tx = optax.sgd(0.1)
    import jax
    import jax.numpy as jnp

    params, batch_stats = init_model(
        model, jax.random.PRNGKey(0), jnp.zeros((1, 6), jnp.float32)
    )
    params = replicate(params)
    opt_state = replicate(tx.init(params))
    step = make_jit_train_step(model, tx)
    images = shard_batch(np.random.RandomState(0).rand(16, 6).astype("f"))
    labels = shard_batch(np.random.RandomState(1).randint(0, 4, 16))
    for _ in range(3):
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state, images, labels
        )
    assert metrics.value("train_steps") == 3
    assert metrics.value("train_examples") == 3 * 16
    # interval histogram needs 2+ calls
    assert metrics.value("train_step_seconds")["count"] == 2
    assert metrics.value("train_examples_per_sec") > 0


# -------------------------------- instrumentation: native-core cycle path


def test_core_cycle_metrics_and_merged_timeline(monkeypatch, tmp_path):
    """The acceptance loop of ISSUE 1 in-process: named async allreduces
    through the native core populate the cycle-latency histogram and cache
    counters, and shutdown merges host spans into the native chrome-trace
    file — one valid-JSON Perfetto load with both pid lanes."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd

    timeline = str(tmp_path / "merged_timeline.json")
    monkeypatch.setenv("HOROVOD_TIMELINE", timeline)
    monkeypatch.setenv("HOROVOD_CYCLE_TIME", "2")
    hvd.shutdown()
    trace.reset()  # re-read HOROVOD_TIMELINE under the monkeypatch
    hvd.init(native_core=True)
    try:
        x = jax.device_put(
            np.ones((hvd.size(), 4), np.float32),
            NamedSharding(hvd.mesh(), P(hvd.data_axis())),
        )
        for step in range(4):
            h = hvd.allreduce_async(x, op=hvd.Sum, name="grad")
            out = hvd.synchronize(h)
        np.testing.assert_allclose(np.asarray(out), np.full((4,), 8.0))
    finally:
        hvd.shutdown()

    hist = metrics.value("core_cycle_latency_seconds")
    assert hist is not None and hist["count"] >= 1 and hist["sum"] > 0
    assert metrics.value("core_enqueued_tensors") == 4
    # steps 2..4 of the same name ride the response cache
    assert metrics.value("core_cache_hits") >= 1
    assert metrics.value("core_cycles") >= 1

    with open(timeline) as f:
        events = json.load(f)  # valid JSON or this throws
    pids = {str(e.get("pid")) for e in events}
    assert trace.HOST_PID in pids, pids  # host spans present
    assert "0" in pids, pids  # native-core events present
    host = [e for e in events if e.get("pid") == trace.HOST_PID]
    assert any(e.get("tid") == "enqueue" for e in host)
    assert any(e.get("tid") == "cycle" for e in host)


# -------------------------------------------------- import side effects


def test_metrics_import_has_no_jax_side_effects():
    """The registry must stay importable from collection-time contexts
    (pytest collecting under ``JAX_PLATFORMS=cpu``): importing it — even
    through the ``horovod_tpu`` package, which imports jax the library —
    must not initialize any JAX device backend, and using the registry and
    exporters must not either."""
    code = (
        "import horovod_tpu.observability.metrics as m\n"
        "import horovod_tpu.observability.exporters as e\n"
        "import horovod_tpu.observability.trace as t\n"
        "m.counter('x', rank=0).inc(3)\n"
        "m.histogram('h').observe(0.1)\n"
        "e.to_prometheus(); e.to_json()\n"
        "import sys\n"
        "jax = sys.modules.get('jax')\n"
        "if jax is not None:\n"
        "    from jax._src import xla_bridge\n"
        "    backends = getattr(xla_bridge, '_backends', None)\n"
        "    assert not backends, (\n"
        "        'observability import initialized a JAX backend: %r'\n"
        "        % backends)\n"
        "print('CLEAN')\n"
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, cwd=_REPO, env=env, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "CLEAN" in out.stdout


# ------------------------------------------------- satellite: trace ring


def test_trace_ring_caps_and_counts_drops(monkeypatch):
    """The span buffer is a capped ring (HOROVOD_TRACE_MAX_SPANS): when
    full the OLDEST events are evicted (a soak keeps its newest window),
    the trace_spans_dropped counter records the loss, and flush appends a
    visible marker."""
    monkeypatch.setenv("HOROVOD_TIMELINE", "/tmp/_ring_never.json")
    monkeypatch.setenv("HOROVOD_TRACE_MAX_SPANS", "10")
    trace.reset()  # re-read both env knobs
    for i in range(15):
        trace.instant("t", f"ev{i}")
    evs = trace.events()
    assert len(evs) == 10
    names = [e["name"] for e in evs]
    assert "ev0" not in names and "ev4" not in names  # oldest gone
    assert "ev14" in names  # newest kept
    assert trace.dropped() == 5
    assert metrics.value("trace_spans_dropped") == 5
    out = str(trace.flush("/tmp/_ring_flush.json"))
    try:
        with open(out) as f:
            flushed = json.load(f)
        assert any("5 oldest events dropped" in e.get("name", "")
                   for e in flushed)
    finally:
        os.unlink(out)


# ------------------------------------------ satellite: exporter escaping


def test_prometheus_label_escaping():
    """Backslash/quote/newline in label values must render per the
    exposition format — a raw newline would terminate the sample line
    mid-way and corrupt every series after it."""
    metrics.counter("esc", path="a\\b").inc()
    metrics.counter("esc", msg='say "hi"').inc(2)
    metrics.counter("esc", txt="line1\nline2").inc(3)
    metrics.histogram("esc_h", buckets=(1.0,), q='x"y').observe(0.5)
    text = exporters.to_prometheus()
    assert "\n\n" not in text  # no sample line got split by a raw newline
    for line in text.rstrip("\n").splitlines():
        assert _PROM_LINE.match(line), f"bad exposition line: {line!r}"
    assert r'esc{path="a\\b"} 1' in text
    assert r'esc{msg="say \"hi\""} 2' in text
    assert r'esc{txt="line1\nline2"} 3' in text
    # labeled histogram keeps its explicit TYPE line + labeled expansion
    assert "# TYPE esc_h histogram" in text
    assert r'esc_h_bucket{q="x\"y",le="1.0"} 1' in text


# --------------------------------------------------- fleet: clock offsets


def test_clock_offset_estimation_synthetic():
    """A remote clock running 5s ahead estimates to offset ~= 5 with the
    half-RTT error bound."""
    import time as _time

    off, err = clock.estimate_offset(lambda: _time.monotonic() + 5.0)
    assert abs(off - 5.0) <= max(err, 1e-3)
    assert 0 <= err < 0.1


def test_clock_refresh_against_kv_server_and_http_client():
    """In-process and HTTP-probed offsets against the SAME KV server are
    both ~0 (same host clock), gauges land, and the trace clock_sync
    metadata is attached for the merge tool."""
    from horovod_tpu.run.rendezvous import KVStoreClient, KVStoreServer

    server = KVStoreServer()
    try:
        off, err = clock.refresh_from_kv(server, rank=0)
        assert abs(off) < 0.05 and err < 0.05
        assert metrics.value("observability_clock_offset_seconds") == off
        assert metrics.value("observability_clock_error_seconds") == err
        server.start()
        client = KVStoreClient("127.0.0.1", server.port)
        off2, err2 = clock.refresh_from_kv(client, rank=1)
        assert abs(off2) < 0.5 and err2 < 0.5
        assert clock.info()["offset_s"] == off2
    finally:
        server.close()


def test_merge_rank_traces_applies_offsets(tmp_path):
    """Two rank files whose clock_sync metadata says their epochs are 1s
    apart merge onto one timebase: equal local ts land 1s apart, host
    lanes are renamed per rank, and correlation args survive."""
    def write(path, rank, epoch_ns, offset_s):
        events = [
            {"ph": "i", "pid": trace.HOST_PID, "tid": "meta",
             "name": "clock_sync", "ts": 0.0,
             "args": {"rank": rank, "epoch_monotonic_ns": epoch_ns,
                      "offset_s": offset_s, "error_s": 0.001}},
            {"ph": "X", "pid": f"rank{rank}", "tid": "allreduce",
             "name": "allreduce s0.0", "ts": 100.0, "dur": 5.0,
             "args": {"step": 0, "gen": 0, "seq": 0, "rank": rank}},
            {"ph": "X", "pid": trace.HOST_PID, "tid": "eager",
             "name": "allreduce:", "ts": 100.0, "dur": 5.0},
        ]
        with open(path, "w") as f:
            json.dump(events, f)

    p0 = tmp_path / "t0.json"
    p1 = tmp_path / "t1.json"
    write(p0, 0, epoch_ns=0, offset_s=0.0)
    write(p1, 1, epoch_ns=1_000_000_000, offset_s=0.0)  # epoch 1s later
    out = tmp_path / "merged.json"
    merged = clock.merge_rank_traces([str(p0), str(p1)], str(out))
    with open(out) as f:
        assert json.load(f) == merged
    assert not any(e.get("name") == "clock_sync" for e in merged)
    r0 = [e for e in merged if e.get("pid") == "rank0"][0]
    r1 = [e for e in merged if e.get("pid") == "rank1"][0]
    assert r1["ts"] - r0["ts"] == pytest.approx(1e6)  # the 1s skew
    assert {e.get("pid") for e in merged} >= {
        "rank0", "rank1", "rank0-host", "rank1-host"}
    assert r1["args"]["seq"] == r0["args"]["seq"] == 0


# ----------------------------------------------- fleet: aggregation plane


def _rank_payload(rank, count, hist=None):
    snap = {
        "allreduce_count": {
            "type": "counter", "help": "", "samples": {"": count}},
    }
    if hist is not None:
        snap["lat"] = {"type": "histogram", "help": "", "samples": {"": hist}}
    return json.dumps({
        "rank": rank, "clock": None, "metrics": snap, "arrivals": [],
    }).encode()


def test_fleet_aggregation_stats_and_rank_series():
    """Rank snapshots in the KV merge into min/mean/max/p99 fleet series
    plus rank-labeled raw series; histograms merge bucket-wise with an
    estimated p99."""
    from horovod_tpu.run.rendezvous import KVStoreServer

    server = KVStoreServer()
    try:
        h0 = {"buckets": {"0.1": 9, "1.0": 10, "+Inf": 10},
              "sum": 1.0, "count": 10}
        h1 = {"buckets": {"0.1": 0, "1.0": 90, "+Inf": 90},
              "sum": 50.0, "count": 90}
        server.put("/obs/snap/0", _rank_payload(0, 10, h0), ttl=30)
        server.put("/obs/snap/1", _rank_payload(1, 30, h1), ttl=30)
        server.put("/obs/snap/2", _rank_payload(2, 20), ttl=30)
        agg = aggregate.FleetAggregator(server)
        out = agg.collect()
        assert out["ranks"] == [0, 1, 2] and out["dead_ranks"] == []
        s = out["metrics"]["allreduce_count"]["samples"][""]
        assert s["min"] == 10 and s["max"] == 30 and s["mean"] == 20
        assert s["p99"] == pytest.approx(29.8)  # interpolated over 3 ranks
        assert s["ranks"] == {"0": 10.0, "1": 30.0, "2": 20.0}
        hl = out["metrics"]["lat"]["samples"][""]
        assert hl["count"] == 100 and hl["sum"] == 51.0
        assert hl["buckets"]["1.0"] == 100
        assert hl["p99"] == 1.0  # 99th falls in the merged 1.0 bucket
        prom = aggregate.to_prometheus_fleet(out)
        assert 'fleet_allreduce_count{stat="max"} 30' in prom
        assert 'allreduce_count{rank="1"} 30' in prom
        assert "# TYPE fleet_lat histogram" in prom
        assert 'fleet_lat_bucket{le="1.0"} 100' in prom
        assert 'fleet_rank_alive{rank="2"} 1' in prom
        # registry mirrors
        assert metrics.value("fleet_ranks") == 3
        assert metrics.value("fleet_aggregations") == 1
    finally:
        server.close()


def test_fleet_prometheus_help_lines():
    """Satellite (ISSUE 14): the fleet exporter emits a # HELP line beside
    every # TYPE — the merged families (carrying the per-process help text
    through) AND the fleet synthetics — so a Prometheus UI explains fleet
    series exactly like local ones."""
    from horovod_tpu.run.rendezvous import KVStoreServer

    server = KVStoreServer()
    try:
        snap = {
            "steps": {"type": "counter", "help": "steps dispatched",
                      "samples": {"": 7}},
            "lat": {"type": "histogram", "help": "step latency",
                    "samples": {"": {"buckets": {"+Inf": 1}, "sum": 0.1,
                                     "count": 1}}},
        }
        server.put("/obs/snap/0", json.dumps(
            {"rank": 0, "clock": None, "metrics": snap, "arrivals": [
                {"key": [0, 0, q], "op": "allreduce",
                 "arrivals": {"0": 1.0 + q, "1": 2.0 + q}}
                for q in range(3)
            ]}).encode(), ttl=30)
        server.put("/obs/snap/1", json.dumps(
            {"rank": 1, "clock": None, "metrics": snap, "arrivals": []}
        ).encode(), ttl=30)
        agg = aggregate.FleetAggregator(server, world=2)
        prom = aggregate.to_prometheus_fleet(agg.collect())
        # every # TYPE line has a # HELP sibling for the same family
        typed = re.findall(r"^# TYPE (\S+)", prom, re.M)
        helped = set(re.findall(r"^# HELP (\S+)", prom, re.M))
        missing = [n for n in typed if n not in helped]
        assert not missing, f"# TYPE families without # HELP: {missing}"
        # the per-process help text rides through, suffixed for the fleet
        assert "# HELP fleet_steps steps dispatched " \
               "(min/mean/max/p99 across ranks)" in prom
        assert "# HELP fleet_lat step latency (fleet-merged across ranks)" \
            in prom
        assert "# HELP steps steps dispatched" in prom
        # synthetics documented too (straggler block present: the arrival
        # spread above is attributed to rank 1)
        assert "# HELP fleet_rank_alive " in prom
        assert "# HELP fleet_straggler_detected_rank " in prom
        assert "# HELP fleet_straggler_detected_spread_seconds " in prom
    finally:
        from horovod_tpu.resilience import health

        health.reset()
        server.close()


def test_fleet_dead_rank_surfaced_not_dropped():
    """A rank whose snapshot lease expired shows up DEAD (surfaced, with
    fleet_rank_alive 0), never silently absent — both through the server
    store and through a probing HTTP client."""
    import time as _time

    from horovod_tpu.run.rendezvous import KVStoreClient, KVStoreServer

    server = KVStoreServer()
    try:
        server.put("/obs/snap/0", _rank_payload(0, 5), ttl=30)
        server.put("/obs/snap/1", _rank_payload(1, 7), ttl=0.05)
        agg = aggregate.FleetAggregator(server)
        assert agg.collect()["ranks"] == [0, 1]
        _time.sleep(0.15)
        out = agg.collect()
        assert out["ranks"] == [0]
        assert out["dead_ranks"] == [1]
        assert metrics.value("fleet_dead_ranks") == 1
        prom = aggregate.to_prometheus_fleet(out)
        assert 'fleet_rank_alive{rank="1"} 0' in prom
        # client path: probe ranks 0..world-1, 410 Gone -> dead
        server.start()
        client = KVStoreClient("127.0.0.1", server.port)
        out2 = aggregate.FleetAggregator(
            client, world=2, register=False).collect()
        assert out2["ranks"] == [0] and out2["dead_ranks"] == [1]
    finally:
        server.close()


def test_publisher_payload_roundtrip(hvd):
    """MetricsPublisher ships this process's registry + arrival ring; the
    aggregator reconstructs rank-labeled values from it."""
    from horovod_tpu.run.rendezvous import KVStoreServer

    hvd.allreduce(np.ones((4,), np.float32), hvd.Sum)
    server = KVStoreServer()
    try:
        pub = aggregate.MetricsPublisher(server, rank=0, interval=5.0)
        pub.publish_once()
        assert metrics.value("fleet_snapshots_published") == 1
        out = aggregate.FleetAggregator(server).collect()
        s = out["metrics"]["allreduce_count"]["samples"][""]
        assert s["ranks"]["0"] == 1.0
        # the arrival ring rode along (1 collective, 8 simulated ranks)
        assert out["straggler"] is None  # no spread without chaos
    finally:
        server.close()


def test_fleet_http_endpoint(hvd):
    """/fleet and /fleet.json serve the registered aggregator's merged
    view; 404 without one."""
    from horovod_tpu.run.rendezvous import KVStoreServer

    server = KVStoreServer()
    http = exporters.start_http_server(0, host="127.0.0.1")
    try:
        port = http.server_port
        with pytest.raises(urllib.request.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/fleet", timeout=10)
        pub = aggregate.MetricsPublisher(server, rank=0, interval=5.0)
        metrics.counter("served_fleet").inc(4)
        pub.publish_once()
        aggregate.FleetAggregator(server)  # registers as default
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/fleet", timeout=10
        ) as r:
            body = r.read().decode()
            assert 'served_fleet{rank="0"} 4' in body
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/fleet.json", timeout=10
        ) as r:
            data = json.load(r)
            assert data["ranks"] == [0]
    finally:
        exporters.stop_http_server()
        server.close()


# --------------------------------- straggler attribution (ISSUE 7 e2e)


def test_rank_slow_chaos_parse():
    from horovod_tpu.resilience import chaos

    assert chaos.parse_spec("rank_slow=3:0.2") == {"rank_slow": (3, 0.2)}
    with pytest.raises(ValueError, match="rank_slow"):
        chaos.parse_spec("rank_slow=3")
    with pytest.raises(ValueError, match="unknown chaos site"):
        chaos.parse_spec("rank_sloow=3:0.2")
    chaos.configure("rank_slow=3:0.2")
    try:
        assert chaos.rank_slow() == (3, 0.2)
        assert chaos.rank_slow() == (3, 0.2)  # persistent, not consumed
    finally:
        chaos.configure(None)


def test_straggler_e2e_deterministic(hvd, monkeypatch, tmp_path):
    """ISSUE 7 acceptance: under HOROVOD_CHAOS=rank_slow=3:0.2 on the
    8-device CPU mesh, the aggregator's straggler_rank names rank 3 within
    2 steps, health transitions to SUSPECT, and the merged skew-corrected
    trace contains the same collective's spans from >= 2 ranks sharing one
    (step, seq) correlation key."""
    from horovod_tpu.resilience import chaos, health
    from horovod_tpu.run.rendezvous import KVStoreServer

    timeline = str(tmp_path / "fleet_timeline.json")
    monkeypatch.setenv("HOROVOD_TIMELINE", timeline)
    monkeypatch.setenv("HOROVOD_CHAOS", "rank_slow=3:0.2")
    trace.reset()  # re-read HOROVOD_TIMELINE under the monkeypatch
    chaos.reset()  # re-read HOROVOD_CHAOS under the monkeypatch
    health.reset()
    server = KVStoreServer()
    try:
        clock.refresh_from_kv(server, rank=0)
        pub = aggregate.MetricsPublisher(server, rank=0, interval=60.0)
        agg = aggregate.FleetAggregator(server, register=False)
        detected_at = None
        for step in range(2):
            straggler.set_step(step)
            hvd.allreduce(np.ones((4,), np.float32), hvd.Sum)
            hvd.allreduce(np.ones((8,), np.float32), hvd.Sum)
            pub.publish_once()
            out = agg.collect()
            if out["straggler"] is not None and detected_at is None:
                detected_at = step
                assert out["straggler"]["rank"] == 3
                assert out["straggler"]["spread_seconds"] >= 0.15
        assert detected_at is not None and detected_at <= 1
        assert metrics.value("straggler_rank") == 3
        assert metrics.value(
            "collective_arrival_spread_seconds")["count"] == 4
        assert metrics.value("straggler_collectives", rank=3) == 4
        # persistent straggler fed the health machine: SUSPECT, rank named;
        # collectives 3 and 4 of the streak each strike (re-strike per
        # collective so step-completion beats cannot hide a persistent but
        # progressing straggler)
        assert health.health_state() == health.HealthState.SUSPECT
        assert "rank 3 straggling" in health.MONITOR.reason()
        assert metrics.value("resilience_stragglers") == 2
        assert metrics.value(
            "resilience_chaos_injected", site="rank_slow") == 4
    finally:
        chaos.configure(None)  # never leak the charge into later tests
        health.reset()
        server.close()

    # the flushed + merged trace: one collective -> a row per rank, tied
    # together by the (step, gen, seq) args, skew-correction applied
    flushed = trace.flush(timeline)
    assert flushed == timeline
    merged_path = str(tmp_path / "merged.json")
    merged = clock.merge_rank_traces([timeline], merged_path)
    by_key = {}
    for e in merged:
        a = e.get("args") or {}
        pid = str(e.get("pid", ""))
        if "seq" in a and pid.startswith("rank") and "-host" not in pid:
            by_key.setdefault(
                (a["step"], a["gen"], a["seq"]), set()).add(pid)
    assert by_key, "no correlated collective spans in the merged trace"
    assert all(len(pids) == 8 for pids in by_key.values())
    assert len(by_key) == 4  # 2 steps x 2 collectives, seq reset per step
    assert {k[2] for k in by_key} == {0, 1}
    # rank 3's bar is the short one: it arrived last, everyone else waited
    r3 = [e for e in merged if e.get("pid") == "rank3"
          and "seq" in (e.get("args") or {})]
    r0 = [e for e in merged if e.get("pid") == "rank0"
          and "seq" in (e.get("args") or {})]
    assert max(e["dur"] for e in r3) < 1e3  # rank3 waits ~nothing (us)
    assert min(e["dur"] for e in r0) > 0.15e6  # others wait >= the delay


def test_straggler_below_threshold_is_quiet(hvd):
    """No chaos, simulated arrivals are equal: spread ~0, nobody flagged,
    health untouched."""
    from horovod_tpu.resilience import health

    health.reset()
    straggler.set_step(0)
    hvd.allreduce(np.ones((4,), np.float32), hvd.Sum)
    assert straggler.attribute() is None
    assert metrics.value("straggler_rank") == -1
    assert health.health_state() == health.HealthState.HEALTHY


# ------------------------------------------------ satellite: hvd_top view


def _load_hvd_top():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "hvd_top", os.path.join(_REPO, "tools", "hvd_top.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_hvd_top_renders_fleet_and_straggler():
    top = _load_hvd_top()
    fleet = {
        "ranks": [0, 1], "dead_ranks": [2],
        "metrics": {
            "train_steps": {"type": "counter", "samples": {"": {
                "ranks": {"0": 10, "1": 12},
                "min": 10, "mean": 11, "max": 12, "p99": 12}}},
            "lat": {"type": "histogram", "samples": {"": {
                "buckets": {"+Inf": 3}, "sum": 0.3, "count": 3,
                "p99": 0.1}}},
        },
        "straggler": {"rank": 1, "spread_seconds": 0.2, "op": "allreduce",
                      "key": [3, 0, 1], "streak": 4},
    }
    out = top.render(fleet)
    assert "2 rank(s) reporting" in out and "DEAD: [2]" in out
    assert "STRAGGLER: rank 1 trailing by 200.0 ms" in out
    assert "train_steps" in out and "12" in out
    assert "lat" in out and "n=3" in out
    # filter narrows the table
    assert "train_steps" not in top.render(fleet, name_filter="lat")


def test_hvd_top_serving_pane():
    """Satellite (ISSUE 14): hvd_top renders a serving pane — subscriber
    lag/staleness, queue depth, admission rejections, per-arm request
    outcomes — from the fleet metrics, and omits it when no serving
    series exist."""
    top = _load_hvd_top()

    def g(v):
        return {"samples": {"": {"ranks": {"0": v}, "min": v, "mean": v,
                                 "max": v, "p99": v}}, "type": "gauge",
                "help": ""}

    def c(samples):
        return {
            "type": "counter", "help": "",
            "samples": {
                k: {"ranks": {"0": v}, "min": v, "mean": v, "max": v,
                    "p99": v}
                for k, v in samples.items()
            },
        }

    fleet = {
        "collected_at": 0.0, "ranks": [0], "dead_ranks": [],
        "straggler": None,
        "metrics": {
            "serving_subscriber_lag": g(2),
            "serving_staleness_seconds": g(7.5),
            "serving_queue_depth": g(5),
            "serving_admission_rejected": c({"reason=queue_full": 4}),
            "serving_requests": c({
                "arm=stable,outcome=ok": 90,
                "arm=canary,outcome=ok": 9,
                "arm=canary,outcome=error": 1,
            }),
        },
    }
    out = top.render(fleet)
    assert "SERVING:" in out
    assert "lag 2 gen(s)" in out
    assert "staleness 7.5s" in out
    assert "queue depth 5" in out
    assert "rejected 4 (queue_full=4)" in out
    assert "requests arm=canary: error=1 ok=9" in out
    assert "requests arm=stable: ok=90" in out
    # no serving series -> no pane
    assert "SERVING:" not in top.render(
        {"ranks": [0], "dead_ranks": [], "straggler": None,
         "metrics": {"train_steps": g(3)}})


def test_hvd_top_scrapes_live_endpoint(hvd):
    """--once --json against the real rank-0 endpoint (fleet registered ->
    fleet view; else single-process fallback)."""
    from horovod_tpu.run.rendezvous import KVStoreServer

    top = _load_hvd_top()
    server = KVStoreServer()
    http = exporters.start_http_server(0, host="127.0.0.1")
    try:
        url = f"http://127.0.0.1:{http.server_port}"
        metrics.counter("topped").inc(3)
        fleet, is_fleet = top.fetch(url)
        assert not is_fleet  # no aggregator yet: /metrics.json fallback
        assert fleet["metrics"]["topped"]["samples"][""]["ranks"]["0"] == 3
        pub = aggregate.MetricsPublisher(server, rank=0, interval=5.0)
        pub.publish_once()
        aggregate.FleetAggregator(server)
        fleet, is_fleet = top.fetch(url)
        assert is_fleet
        assert "topped" in top.render(fleet)
    finally:
        exporters.stop_http_server()
        server.close()


# ------------------------------- satellite: metric-catalog drift guard


_METRIC_LITERAL_RE = re.compile(
    r'\b(?:metrics|_metrics)\s*\.\s*(?:counter|gauge|histogram)\(\s*'
    r'"([A-Za-z_][A-Za-z0-9_]*)"'
)


def test_metric_catalog_covers_every_emitted_name():
    """Every metric name emitted as a literal through
    counter(/gauge(/histogram( anywhere under horovod_tpu/ must appear in
    the docs/observability.md catalog — the catalog cannot silently drift
    from the code again. (f-string-templated families like train_* are
    documented by pattern and exempt by construction.)"""
    names = set()
    for dirpath, _dirnames, filenames in os.walk(
        os.path.join(_REPO, "horovod_tpu")
    ):
        for fn in filenames:
            if not fn.endswith(".py"):
                continue
            with open(os.path.join(dirpath, fn)) as f:
                names |= set(_METRIC_LITERAL_RE.findall(f.read()))
    assert len(names) > 40, "guard regex found suspiciously few metrics"
    with open(os.path.join(_REPO, "docs", "observability.md")) as f:
        catalog = f.read()
    missing = sorted(n for n in names if n not in catalog)
    assert not missing, (
        "metric names emitted in code but absent from the "
        f"docs/observability.md catalog: {missing}"
    )


def test_straggler_partial_arrivals_deferred_until_complete():
    """Fleet attribution must not score a key while a rank's arrival —
    most likely the straggler's own — is still in flight: the partial set
    is deferred (not remembered as seen), and the SAME key attributes
    correctly once the late snapshot lands."""
    early = [{"key": [0, 0, 0], "op": "allreduce", "arrivals": {"0": 10.0}}]
    late = [{"key": [0, 0, 0], "op": "allreduce",
             "arrivals": {"1": 10.3}}]
    # first pass: only rank 0's snapshot arrived -> deferred, no verdict
    assert straggler.attribute(
        straggler.merge_arrival_exports([early]), expected_ranks=2
    ) is None
    assert metrics.value("collective_arrival_spread_seconds") is None
    # second pass: rank 1's (straggling) arrival landed -> attributed
    out = straggler.attribute(
        straggler.merge_arrival_exports([early, late]), expected_ranks=2
    )
    assert out is not None and out["rank"] == 1
    assert out["spread_seconds"] == pytest.approx(0.3)
    # and the finalized key never double-counts on a repeated pass
    straggler.attribute(
        straggler.merge_arrival_exports([early, late]), expected_ranks=2
    )
    assert metrics.value(
        "collective_arrival_spread_seconds")["count"] == 1


def test_attribution_processes_records_in_temporal_order():
    """Post-resize keys (gen bumped, step rolled back) sort temporally
    AFTER leftover pre-resize keys: an old healthy key in the same pass
    must not wipe the attribution the newer straggling keys build."""
    recs = []
    # pre-resize healthy key: gen 0, step 5 — temporally OLDEST
    recs.append({"key": [5, 0, 0], "op": "allreduce",
                 "arrivals": {"0": 1.0, "1": 1.0}})
    # post-resize: rank 1 trails 0.3s at 3 consecutive gen-1 collectives
    for q in range(3):
        recs.append({"key": [0, 1, q], "op": "allreduce",
                     "arrivals": {"0": 10.0 + q, "1": 10.3 + q}})
    out = straggler.attribute(
        straggler.merge_arrival_exports([recs]), expected_ranks=2)
    assert out is not None and out["rank"] == 1 and out["streak"] == 3
    assert metrics.value("straggler_rank") == 1  # not wiped to -1
    from horovod_tpu.resilience import health

    try:
        assert health.health_state() == health.HealthState.SUSPECT
    finally:
        health.reset()


def test_merge_uses_newest_clock_sync(tmp_path):
    """trace.flush appends one clock_sync per flush; a sidecar reused
    across shutdown/init cycles must be shifted by the NEWEST epoch, not
    the first run's stale one."""
    events = [
        {"ph": "i", "pid": trace.HOST_PID, "tid": "meta",
         "name": "clock_sync", "ts": 0.0,
         "args": {"rank": 1, "epoch_monotonic_ns": 0, "offset_s": 0.0}},
        {"ph": "i", "pid": trace.HOST_PID, "tid": "meta",
         "name": "clock_sync", "ts": 0.0,
         "args": {"rank": 1, "epoch_monotonic_ns": 100_000_000_000,
                  "offset_s": 0.0}},
        {"ph": "X", "pid": "rank1", "tid": "allreduce", "name": "x",
         "ts": 50.0, "dur": 1.0},
    ]
    p = tmp_path / "t.json"
    with open(p, "w") as f:
        json.dump(events, f)
    ref = [{"ph": "i", "pid": trace.HOST_PID, "tid": "meta",
            "name": "clock_sync", "ts": 0.0,
            "args": {"rank": 0, "epoch_monotonic_ns": 100_000_000_000,
                     "offset_s": 0.0}},
           {"ph": "X", "pid": "rank0", "tid": "allreduce", "name": "y",
            "ts": 50.0, "dur": 1.0}]
    p0 = tmp_path / "t0.json"
    with open(p0, "w") as f:
        json.dump(ref, f)
    merged = clock.merge_rank_traces([str(p0), str(p)])
    r0 = [e for e in merged if e.get("pid") == "rank0"][0]
    r1 = [e for e in merged if e.get("pid") == "rank1"][0]
    # same epoch under the NEWEST meta -> aligned; the stale first meta
    # would have shifted rank1 by the full 100s inter-run gap
    assert r1["ts"] == pytest.approx(r0["ts"])


def test_aggregator_defers_keys_until_full_world_reported():
    """With world known, a collect() racing the straggler's own (late)
    snapshot must defer the key — not finalize it against the
    published-so-far subset and then skip the decisive arrival forever."""
    from horovod_tpu.run.rendezvous import KVStoreServer

    def payload(rank, arrivals):
        return json.dumps({
            "rank": rank, "clock": None, "metrics": {},
            "arrivals": [{"key": [0, 0, 0], "op": "allreduce",
                          "arrivals": arrivals}],
        }).encode()

    server = KVStoreServer()
    try:
        server.put("/obs/snap/0", payload(0, {"0": 10.0}), ttl=30)
        server.put("/obs/snap/1", payload(1, {"1": 10.01}), ttl=30)
        agg = aggregate.FleetAggregator(server, world=3, register=False)
        assert agg.collect()["straggler"] is None  # deferred, not scored
        server.put("/obs/snap/2", payload(2, {"2": 10.3}), ttl=30)
        out = agg.collect()
        assert out["straggler"] is not None
        assert out["straggler"]["rank"] == 2
        assert out["straggler"]["spread_seconds"] == pytest.approx(0.3)
    finally:
        server.close()
