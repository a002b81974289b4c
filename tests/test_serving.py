"""Streaming weight publication (ISSUE 6): KV write-ahead log + restart,
background sweep, commit-last publish protocol, int8 delta chains with
keyframe resync, staleness contract, elastic composition, preemption-drain
final flush.

The acceptance pin: a trainer publishing 5+ generations of int8 deltas
under ``HOROVOD_CHAOS=publish_fail=1,kv_restart_at_step=3`` with a mid-run
8→6 elastic shrink never exposes a torn generation — the subscriber
reconstructs the trainer's consolidated weights allclose, including a
keyframe re-root + resync after the KV restart. Tier-1: single process,
deterministic chaos, no sleeps > 0.2s; the >=20-generation soaks are
``slow``.
"""

import json
import os
import time

import numpy as np
import pytest

from horovod_tpu.observability import metrics
from horovod_tpu.resilience import chaos, health, loop
from horovod_tpu.run.rendezvous import KVStoreClient, KVStoreServer
from horovod_tpu.serving import (
    ChainError,
    PublishAborted,
    WeightPublisher,
    WeightSubscriber,
    subscribe_weights,
)
from horovod_tpu.serving import protocol

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh():
    from horovod_tpu.serving import publisher as _pub_mod

    metrics.reset()
    metrics.set_enabled(True)
    health.reset()
    chaos.configure(None)
    with _pub_mod._ACTIVE_LOCK:
        _pub_mod._ACTIVE.clear()  # no flush-registry leakage across tests
    yield
    metrics.reset()
    metrics.set_enabled(True)
    health.reset()
    chaos.reset()
    with _pub_mod._ACTIVE_LOCK:
        _pub_mod._ACTIVE.clear()


def _tree(seed=0, big=2048, small=7):
    rng = np.random.RandomState(seed)
    return {
        "dense": {"kernel": rng.randn(big).astype(np.float32).reshape(-1, 64)},
        "bias": rng.randn(small).astype(np.float32),
        "step_count": np.int32(seed),
    }


def _drift(tree, seed, scale=0.01):
    rng = np.random.RandomState(seed)

    def one(x):
        x = np.asarray(x)
        if x.dtype.kind == "f":
            return x + scale * rng.randn(*x.shape).astype(x.dtype)
        return x

    import jax

    return jax.tree_util.tree_map(one, tree)


# ------------------------------------------------------------ wire protocol


@pytest.mark.serving
class TestProtocol:
    def test_keyframe_roundtrip_exact(self):
        t = _tree(0)
        payload, info = protocol.encode(t)
        assert info["kind"] == "key"
        out = protocol.decode(payload)
        import jax

        for got, want in zip(jax.tree_util.tree_leaves(out),
                             jax.tree_util.tree_leaves(t)):
            np.testing.assert_array_equal(got, want)

    def test_delta_chain_tracks_reconstruction_exactly(self):
        """The EF argument: each delta is measured against the decode of
        the previous wire, so publisher and subscriber reconstructions are
        bit-identical at ANY chain length, and quantization error never
        accumulates (stays within one delta's quantization of the truth)."""
        truth = _tree(0)
        payload, _ = protocol.encode(truth)
        recon_pub = protocol.decode(payload)
        recon_sub = protocol.decode(payload)
        for s in range(1, 8):
            truth = _drift(truth, s)
            payload, info = protocol.encode(truth, recon_pub)
            assert info["kind"] == "delta"
            recon_pub = protocol.decode(payload, recon_pub)
            recon_sub = protocol.decode(payload, recon_sub)
            np.testing.assert_array_equal(
                recon_sub["dense"]["kernel"], recon_pub["dense"]["kernel"])
            # bounded by ONE quantization error, not s of them
            np.testing.assert_allclose(
                recon_sub["dense"]["kernel"], truth["dense"]["kernel"],
                atol=2e-4)

    def test_delta_quantize_floor_and_int_passthrough(self):
        """Sub-floor leaves (the 7-elt bias) and integer leaves ride raw —
        the delta is then EXACT for them, same floor rule as the
        collective wire."""
        base = _tree(0)
        new = _drift(base, 1)
        payload, info = protocol.encode(new, base)
        out = protocol.decode(payload, base)
        np.testing.assert_array_equal(out["bias"], new["bias"])  # raw delta
        assert out["step_count"] == new["step_count"]
        # the big leaf IS quantized: close but not exact
        k = out["dense"]["kernel"] - new["dense"]["kernel"]
        assert 0 < np.abs(k).max() < 2e-4

    def test_bool_leaf_rides_full_in_delta(self):
        """numpy bool subtraction raises; a bool mask leaf (and any other
        non-subtractable dtype) must ride as its FULL value inside a
        delta instead of crashing the encode."""
        base = {"w": np.ones(2048, np.float32),
                "mask": np.array([True, False, True])}
        new = {"w": base["w"] + 0.1,
               "mask": np.array([False, False, True])}
        payload, _ = protocol.encode(new, base)
        out = protocol.decode(payload, base)
        np.testing.assert_array_equal(out["mask"], new["mask"])
        np.testing.assert_allclose(out["w"], new["w"], atol=2e-3)

    def test_delta_base_treedef_mismatch(self):
        with pytest.raises(ValueError, match="treedef"):
            protocol.encode(_tree(0), {"other": np.zeros(3)})
        payload, _ = protocol.encode(_tree(0))
        with pytest.raises(ChainError):
            protocol.decode(
                protocol.encode(_tree(1), _tree(0))[0], base=None)

    def test_chunks_and_crc(self):
        payload = os.urandom(1000)
        chunks = protocol.split_chunks(payload, 256)
        assert len(chunks) == 4 and b"".join(chunks) == payload
        assert protocol.split_chunks(b"", 256) == [b""]
        m = protocol.parse_manifest(protocol.build_manifest(
            generation=3, step=30, kind="delta", keyframe=1,
            chunks=chunks, payload=payload, wire_bytes=900,
            elastic_generation=None, published_at=time.time()))
        assert m["generation"] == 3 and m["base"] == 2
        assert m["chunk_crc"][1] == protocol.crc(chunks[1])
        assert m["payload_crc"] == protocol.crc(payload)
        with pytest.raises(ChainError):
            protocol.parse_manifest(b"not json")
        with pytest.raises(ChainError):
            protocol.parse_manifest(json.dumps({"version": 99}).encode())

    def test_wire_bytes_match_analytic_model(self):
        """Model == gauge: the encoder's wire accounting equals
        scaling_projection.publish_bytes leaf for leaf."""
        import sys

        sys.path.insert(0, os.path.join(_REPO, "tools"))
        from scaling_projection import publish_bytes

        shapes = [(784, 512), (512,), (512, 512), (512,), (512, 10), (10,)]
        rng = np.random.RandomState(0)
        tree = [rng.randn(*s).astype(np.float32) for s in shapes]
        model = publish_bytes(shapes, keyframe_every=8)
        _, key_info = protocol.encode(tree)
        assert key_info["wire_bytes"] == model["keyframe_bytes"]
        base = protocol.decode(protocol.encode(tree)[0])
        _, delta_info = protocol.encode(
            [t + 0.01 for t in tree], base)
        assert delta_info["wire_bytes"] == model["delta_bytes"]
        assert model["delta_ratio_vs_checkpoint"] < 0.3  # the ~4x win


# ------------------------------------------------------- KV durability (WAL)


@pytest.mark.serving
class TestKVWal:
    def test_restart_replays_state(self, tmp_path):
        s = KVStoreServer(wal_path=str(tmp_path / "kv.wal"))
        s.put("/elastic/gen", b'{"generation": 3}')
        s.put("/serving/head", b"7")
        s.put("/hb/2", b"1", ttl=30.0)
        s.delete("/hb/5", tombstone=True)
        s.restart()
        assert s.get("/elastic/gen") == b'{"generation": 3}'
        assert s.get("/serving/head") == b"7"
        assert s.get("/hb/2") == b"1"  # TTL lease re-armed
        assert "/hb/5" in s.dead_keys()  # tombstone survived
        assert metrics.value("rendezvous_wal_replayed") > 0
        assert metrics.value("rendezvous_restarts") == 1.0
        s.close()

    def test_fresh_server_on_same_wal(self, tmp_path):
        wal = str(tmp_path / "kv.wal")
        s = KVStoreServer(wal_path=wal)
        s.put("/a", b"x")
        s.prune("/gone")  # prune of nothing: no record
        s.put("/gone/1", b"y")
        s.prune("/gone")
        s.close()
        s2 = KVStoreServer(wal_path=wal)
        assert s2.get("/a") == b"x"
        assert s2.get("/gone/1") is None
        s2.close()

    def test_restart_without_replay_truncates(self, tmp_path):
        s = KVStoreServer(wal_path=str(tmp_path / "kv.wal"))
        s.put("/a", b"x")
        s.restart(replay=False)  # the disk died with the process
        assert s.get("/a") is None
        s.put("/b", b"y")
        s.restart()  # the new WAL reflects only post-loss state
        assert s.get("/a") is None and s.get("/b") == b"y"
        s.close()

    def test_torn_tail_record_tolerated(self, tmp_path):
        wal = str(tmp_path / "kv.wal")
        s = KVStoreServer(wal_path=wal)
        s.put("/a", b"x")
        s.put("/b", b"y")
        s.close()
        with open(wal, "ab") as f:
            f.write(b'{"op": "put", "k": "/c", "v"')  # died mid-append
        s2 = KVStoreServer(wal_path=wal)
        assert s2.get("/a") == b"x" and s2.get("/b") == b"y"
        assert s2.get("/c") is None
        s2.close()

    def test_compaction_bounds_the_log(self, tmp_path):
        wal = str(tmp_path / "kv.wal")
        s = KVStoreServer(wal_path=wal)
        for i in range(50):
            s.put("/hot", str(i).encode())  # 50 records, 1 live key
        s.close()
        s2 = KVStoreServer(wal_path=wal)  # open compacts
        assert s2.get("/hot") == b"49"
        assert s2._wal_records == 1
        s2.close()

    def test_second_server_on_live_wal_fails_fast(self, tmp_path):
        """Found by the 3-process drive: a second server on the same WAL
        (operator error, a restart racing the old process) compacted the
        LIVE server's log before its port bind even failed — silently
        truncating committed generations. The WAL lock makes the loser
        fail fast instead."""
        wal = str(tmp_path / "kv.wal")
        s = KVStoreServer(wal_path=wal)
        s.put("/serving/head", b"9")
        with pytest.raises(RuntimeError, match="locked by another"):
            KVStoreServer(wal_path=wal)
        # the live server's log was never touched
        s.put("/a", b"x")
        s.close()
        s2 = KVStoreServer(wal_path=wal)  # lock released on close
        assert s2.get("/serving/head") == b"9" and s2.get("/a") == b"x"
        s2.close()

    def test_no_wal_restart_loses_everything(self):
        s = KVStoreServer()
        s.put("/a", b"x")
        s.restart()
        assert s.get("/a") is None
        s.close()

    def test_restart_preserves_port_and_http(self):
        s = KVStoreServer(secret="sek")
        port = s.start()
        c = KVStoreClient("127.0.0.1", port, secret="sek")
        c.put("k1", b"v1")
        s.restart()
        assert s.port == port
        c.put("k2", b"v2")  # same address keeps working
        assert c.get("k2") == b"v2"
        assert c.get("k1") is None  # no WAL: lost
        s.close()

    def test_client_delete_tombstone_over_http(self):
        from horovod_tpu.run.rendezvous import DeadRankError

        s = KVStoreServer(secret="sek")
        port = s.start()
        c = KVStoreClient("127.0.0.1", port, secret="sek")
        c.put("/serving/manifest/3", b"m")
        assert c.delete("/serving/manifest/3", tombstone=True)
        with pytest.raises(DeadRankError):
            c.get("/serving/manifest/3")
        assert not c.delete("/never")  # 404 → False, no raise
        s.close()


@pytest.mark.serving
class TestKVSweep:
    def test_background_sweep_expires_without_access(self):
        s = KVStoreServer(sweep_interval=0.03, tombstone_ttl=300)
        s.put("/hb/1", b"1", ttl=0.05)
        time.sleep(0.15)  # nobody reads the key; the timer must reap it
        with s._lock:
            gone = "/hb/1" not in s._store
            dead = "/hb/1" in s._dead
        assert gone and dead
        assert metrics.value("rendezvous_keys_swept", kind="expired") == 1.0
        s.close()

    def test_tombstone_gc_bounds_memory(self):
        s = KVStoreServer(sweep_interval=0.03, tombstone_ttl=0.05)
        for i in range(5):
            s.delete(f"/hb/{i}", tombstone=True)
        time.sleep(0.2)
        assert s.dead_keys() == []
        assert metrics.value(
            "rendezvous_keys_swept", kind="tombstone") == 5.0
        s.close()

    def test_lazy_access_never_drops_tombstones(self):
        s = KVStoreServer(tombstone_ttl=0.01)  # no sweep timer
        s.delete("/hb/9", tombstone=True)
        time.sleep(0.05)
        assert "/hb/9" in s.dead_keys()  # access sweeps TTLs, not stones
        s.close()


# -------------------------------------------------------------- publisher


@pytest.mark.serving
class TestPublisher:
    def test_commit_last_ordering(self):
        """chunks → manifest → head, never any other order."""
        order = []
        s = KVStoreServer()
        real_put = s.put

        def spy(key, value, ttl=None):
            order.append(key)
            real_put(key, value, ttl=ttl)

        s.put = spy
        pub = WeightPublisher(s, chunk_bytes=512, register=False)
        pub.publish({"params": _tree(0)}, 1)
        assert order[-1] == "/serving/head"
        assert order[-2] == "/serving/manifest/1"
        assert all("/chunks/" in k for k in order[:-2]) and len(order) > 3
        s.close()

    @pytest.mark.chaos
    def test_publish_fail_retries_and_never_tears(self):
        """With publish_fail armed, chunk 0 lands and the attempt dies; a
        subscriber polling at that exact torn moment sees NOTHING (head
        unmoved), and the retried attempt commits the full generation."""
        from unittest import mock

        s = KVStoreServer()
        pub = WeightPublisher(s, register=False)
        sub = WeightSubscriber(s)
        chaos.configure("publish_fail=1")

        seen_mid_failure = []
        real_inject = chaos.inject_failure

        def probing_inject(site, exc_factory=None):
            try:
                real_inject(site, exc_factory)
            except BaseException:
                seen_mid_failure.append(sub.poll())  # torn moment: poll now
                raise

        with mock.patch(
                "horovod_tpu.resilience.chaos.inject_failure",
                probing_inject):
            gen = pub.publish({"params": _tree(0)}, 1)
        assert gen == 1
        assert seen_mid_failure == [None]  # the tear was never visible
        assert sub.generation == 0
        assert metrics.value(
            "resilience_chaos_injected", site="publish_fail") == 1.0
        assert sub.poll() is not None and sub.generation == 1
        s.close()
        chaos.configure(None)

    def test_gc_retires_back_to_keyframe(self):
        s = KVStoreServer()
        pub = WeightPublisher(s, keyframe_every=3, register=False)
        t = _tree(0)
        for i in range(1, 8):  # keyframes at 1, 4, 7
            t = _drift(t, i)
            pub.publish({"params": t}, i)
        assert pub.keyframe_generation == 7
        live = s.live_keys("/serving/manifest/")
        assert live == ["/serving/manifest/7"]
        # GC'd manifests are tombstoned, not vanished
        assert "/serving/manifest/4" in s.dead_keys()
        assert s.live_keys("/serving/chunks/1/") == []
        assert metrics.value("serving_generations_gc") == 6.0
        s.close()

    def test_fence_abort_is_clean(self):
        s = KVStoreServer()
        calls = {"n": 0}

        def fence():
            calls["n"] += 1
            return 1 if calls["n"] == 1 else 2

        pub = WeightPublisher(s, register=False, fence_fn=fence)
        with pytest.raises(PublishAborted):
            pub.publish({"params": _tree(0)}, 1)
        assert pub.generation == 0
        assert s.get("/serving/head") is None
        assert s.live_keys("/serving/chunks/") == []
        assert metrics.value("serving_publish_aborts") == 1.0
        # next publish with a stable fence commits normally
        pub.fence_fn = lambda: 2
        assert pub.publish({"params": _tree(0)}, 2) == 1
        s.close()

    def test_kv_restart_chaos_rearms_keyframe(self):
        """kv_restart_at_step fires inside publish(); without a WAL the
        store comes back empty and the publisher re-roots the chain with a
        keyframe instead of emitting an unchainable delta."""
        s = KVStoreServer()
        pub = WeightPublisher(s, keyframe_every=100, register=False)
        t = _tree(0)
        pub.publish({"params": t}, 1)
        t = _drift(t, 1)
        pub.publish({"params": t}, 2)  # a delta
        chaos.configure("kv_restart_at_step=3")
        t = _drift(t, 2)
        pub.publish({"params": t}, 3)
        assert metrics.value(
            "resilience_chaos_injected", site="kv_restart_at_step") == 1.0
        assert pub.keyframe_generation == 3  # re-rooted
        sub = WeightSubscriber(s)
        out = sub.poll()
        assert out is not None and sub.generation == 3
        np.testing.assert_allclose(
            out["dense"]["kernel"], t["dense"]["kernel"], atol=2e-4)
        s.close()
        chaos.configure(None)

    def test_kv_restart_with_wal_keeps_the_chain(self, tmp_path):
        """Same chaos charge with a WAL'd KV: the generations survive the
        restart, the chain continues with deltas (no re-root)."""
        s = KVStoreServer(wal_path=str(tmp_path / "kv.wal"))
        pub = WeightPublisher(s, keyframe_every=100, register=False)
        t = _tree(0)
        pub.publish({"params": t}, 1)
        chaos.configure("kv_restart_at_step=2")
        t = _drift(t, 1)
        pub.publish({"params": t}, 2)
        assert pub.keyframe_generation == 1  # still the original keyframe
        assert metrics.value(
            "serving_publish_generations", kind="delta") == 1.0
        sub = WeightSubscriber(s)
        sub.poll()
        assert sub.generation == 2
        s.close()
        chaos.configure(None)

    def test_trainer_restart_new_publisher_never_corrupts_base(self):
        """Found by the 3-process drive: a restarted trainer's FRESH
        publisher re-used generation numbers over the same KV, and a
        surviving subscriber applied its deltas against the OLD chain's
        trees — silently wrong weights. Pin the fix: the new publisher
        adopts the head (monotonic numbers) and stamps a new chain id, so
        the subscriber resyncs onto the new chain instead."""
        s = KVStoreServer()
        pub1 = WeightPublisher(s, keyframe_every=100, register=False)
        t = _tree(0)
        for i in (1, 2, 3):
            t = _drift(t, i)
            pub1.publish({"params": t}, i)
        sub = WeightSubscriber(s)
        sub.poll()
        assert sub.generation == 3

        # the trainer restarts: new publisher instance, DIVERGED state
        # (resumed from a checkpoint two steps back)
        t2 = _drift(_tree(0), 99)
        pub2 = WeightPublisher(s, keyframe_every=100, register=False)
        pub2.publish({"params": t2}, 10)
        assert pub2.generation == 4  # adopted head 3, not restarted at 1
        t2 = _drift(t2, 100)
        pub2.publish({"params": t2}, 11)  # a delta on the NEW chain

        out = sub.poll()
        assert out is not None and sub.generation == 5
        # bit-identical to the NEW publisher's reconstruction — the old
        # chain's trees never contaminated the result
        np.testing.assert_array_equal(
            out["dense"]["kernel"],
            np.asarray(pub2.reconstruction()["dense"]["kernel"]))
        # and the DEAD chain was GC'd, not leaked: gens 1-3 retired once
        # the new keyframe (gen 4) superseded them
        assert s.live_keys("/serving/manifest/") == [
            "/serving/manifest/4", "/serving/manifest/5"]
        assert s.live_keys("/serving/chunks/1/") == []
        s.close()

    def test_maybe_publish_cadence_and_swallow(self):
        s = KVStoreServer()
        pub = WeightPublisher(s, publish_every=3, register=False)
        assert pub.maybe_publish({"params": _tree(0)}, 1) is None
        assert pub.maybe_publish({"params": _tree(0)}, 3) == 1
        assert pub.maybe_publish({"params": _tree(0)}, 3) is None  # dedup
        s.close()
        # a dead KV makes maybe_publish log-and-continue, not raise
        from horovod_tpu.resilience.retry import RetryPolicy

        dead = KVStoreClient("127.0.0.1", 1, retry_policy=RetryPolicy(
            max_attempts=1, base_delay=0.0, deadline=0.2))
        pub2 = WeightPublisher(
            dead, publish_every=1, register=False,
            retry_policy=RetryPolicy(
                max_attempts=1, base_delay=0.0, deadline=0.2))
        assert pub2.maybe_publish({"params": _tree(0)}, 1) is None
        assert metrics.value("serving_publish_failures") == 1.0


# ------------------------------------------------------------- subscriber


@pytest.mark.serving
class TestSubscriber:
    def _published(self, n=3, keyframe_every=8, server=None):
        s = server or KVStoreServer()
        pub = WeightPublisher(
            s, keyframe_every=keyframe_every, register=False)
        t = _tree(0)
        trees = []
        for i in range(1, n + 1):
            t = _drift(t, i)
            pub.publish({"params": t}, i)
            trees.append(t)
        return s, pub, trees

    def test_poll_semantics(self):
        s, pub, trees = self._published(3)
        sub = WeightSubscriber(s)
        out = sub.poll()
        assert out is not None and sub.generation == 3 and sub.step == 3
        assert sub.poll() is None  # nothing new
        assert sub.lag() == 0
        assert sub.weights() is out
        s.close()

    def test_no_publication_yet(self):
        s = KVStoreServer()
        sub = WeightSubscriber(s)
        assert sub.poll() is None
        assert sub.generation == 0 and sub.staleness_seconds() is None
        s.close()

    def test_corrupt_chunk_never_applied_then_recovers(self):
        """A CRC-failing chunk (torn read, bitrot) is NEVER applied: the
        poll degrades to the old generation; once the bytes read clean
        again (transient corruption) the next poll advances normally."""
        s, pub, trees = self._published(2)
        sub = WeightSubscriber(s)
        sub.poll()
        t = _drift(trees[-1], 3)
        pub.publish({"params": t}, 3)
        key = "/serving/chunks/3/0"
        orig = s.get(key)
        s.put(key, b"garbage" + orig)
        assert sub.poll() is None
        assert sub.generation == 2 and sub.lag() == 1  # degraded, not torn
        assert metrics.value("serving_subscribe_errors") == 1.0
        s.put(key, orig)  # the re-read comes back clean
        out = sub.poll()
        assert out is not None and sub.generation == 3
        np.testing.assert_array_equal(
            out["dense"]["kernel"],
            np.asarray(pub.reconstruction()["dense"]["kernel"]))
        s.close()

    def test_lagging_past_gc_resyncs(self):
        """A subscriber that stalls while GC retires its position recovers
        through the keyframe — and serves bit-identical state."""
        s, pub, trees = self._published(2, keyframe_every=3)
        sub = WeightSubscriber(s)
        sub.poll()
        assert sub.generation == 2
        t = trees[-1]
        for i in range(3, 9):  # keyframes at 4, 7; GC retires 2,3
            t = _drift(t, i)
            pub.publish({"params": t}, i)
        out = sub.poll()
        assert out is not None and sub.generation == 8
        np.testing.assert_array_equal(
            out["dense"]["kernel"],
            np.asarray(pub.reconstruction()["dense"]["kernel"]))
        s.close()

    def test_partial_apply_still_returns_progress(self):
        """Review-found: gen2 applies, gen3 is corrupt and resync fails —
        the poll must hand the caller the gen2 tree it COMMITTED (the
        watermark already moved to gen2's publish time), not None."""
        s, pub, trees = self._published(1)
        sub = WeightSubscriber(s)
        sub.poll()
        t2 = _drift(trees[-1], 2)
        pub.publish({"params": t2}, 2)
        t3 = _drift(t2, 3)
        pub.publish({"params": t3}, 3)
        # corrupt gen 3 AND the keyframe so resync cannot win either
        s.put("/serving/chunks/3/0", b"xx")
        s.delete("/serving/chunks/1/0")
        out = sub.poll()
        assert out is not None  # gen 2 committed during this poll
        assert sub.generation == 2 and sub.lag() == 1
        np.testing.assert_allclose(
            out["dense"]["kernel"], t2["dense"]["kernel"], atol=2e-4)
        assert sub.poll() is None  # no further progress possible
        s.close()

    def test_publish_error_contract_covers_encode(self):
        """Review-found: a state whose published tree STRUCTURE changed
        between publishes must not escape maybe_publish as a raw
        TypeError/ValueError — the publisher re-roots with a keyframe (a
        delta against a mismatched base is meaningless)."""
        s = KVStoreServer()
        pub = WeightPublisher(s, keyframe_every=100, register=False)
        pub.publish({"params": {"w": np.ones(2048, np.float32)}}, 1)
        # the tree gains a leaf: delta encode fails → keyframe re-root
        grown = {"w": np.ones(2048, np.float32),
                 "b": np.zeros(4, np.float32)}
        gen = pub.publish({"params": grown}, 2)
        assert gen == 2 and pub.keyframe_generation == 2
        sub = WeightSubscriber(s)
        sub.poll()
        assert sub.generation == 2
        np.testing.assert_array_equal(sub.weights()["b"], grown["b"])
        s.close()

    def test_keyframe_unreachable_keeps_serving_stale(self):
        """Even the resync path failing must not crash the serving
        process: the old generation keeps serving and staleness grows."""
        s, pub, trees = self._published(2)
        sub = WeightSubscriber(s)
        sub.poll()
        t = _drift(trees[-1], 9)
        pub.publish({"params": t}, 3)
        # destroy the chain AND the keyframe: delta 3 corrupt, keyframe gone
        s.put("/serving/chunks/3/0", b"xx")
        s.delete("/serving/chunks/1/0")
        assert sub.poll() is None
        assert sub.generation == 2  # still serving the old weights
        assert sub.lag() == 1
        assert metrics.value("serving_subscribe_errors") == 1.0
        s.close()

    def test_staleness_watermark(self):
        s, pub, trees = self._published(1)
        sub = WeightSubscriber(s, stale_after=0.05)
        assert sub.stale()  # nothing applied yet
        sub.poll()
        assert not sub.stale()
        time.sleep(0.08)
        assert sub.stale()  # trainer went quiet past the watermark
        assert sub.staleness_seconds() > 0.05
        # a fresh publication un-stales on the next poll
        pub.publish({"params": _drift(trees[-1], 5)}, 2)
        sub.poll()
        assert not sub.stale()
        s.close()

    @pytest.mark.chaos
    def test_subscriber_stall_chaos_delays_poll(self):
        s, pub, trees = self._published(1)
        sub = WeightSubscriber(s)
        chaos.configure("subscriber_stall=0.05")
        t0 = time.monotonic()
        sub.poll()
        assert time.monotonic() - t0 >= 0.05
        assert metrics.value(
            "resilience_chaos_injected", site="subscriber_stall") >= 1.0
        s.close()
        chaos.configure(None)

    def test_http_transport_roundtrip(self):
        """The real deployment shape: subscriber in another process via
        HTTP + HMAC, served by the launcher's KV server."""
        s = KVStoreServer(secret="sek")
        port = s.start()
        client = KVStoreClient("127.0.0.1", port, secret="sek")
        pub = WeightPublisher(client, chunk_bytes=1024, register=False)
        t = _tree(0)
        pub.publish({"params": t}, 1)
        t2 = _drift(t, 1)
        pub.publish({"params": t2}, 2)
        sub = subscribe_weights("127.0.0.1", port, secret="sek")
        out = sub.wait_for_generation(2, timeout=10)
        np.testing.assert_allclose(
            out["dense"]["kernel"], t2["dense"]["kernel"], atol=2e-4)
        assert sub.step == 2
        s.close()

    def test_subscribe_weights_arg_validation(self):
        with pytest.raises(ValueError):
            subscribe_weights()
        with pytest.raises(ValueError):
            subscribe_weights("h", 1, store=KVStoreServer())


# ------------------------------------------------- preemption drain flush


@pytest.mark.serving
@pytest.mark.chaos
class TestPreemptFlush:
    def test_sigterm_drain_flushes_final_generation(self):
        """The satellite: SIGTERM → drain → final publication → emergency
        checkpoint. Subscribers hold the last good weights across the
        restart gap."""
        s = KVStoreServer()
        pub = WeightPublisher(s, publish_every=10)  # registered
        try:
            chaos.configure("sigterm_at_step=3")

            def step_fn(state, i):
                return {"params": {"w": state["params"]["w"] + 1.0}}

            with pytest.raises(loop.Preempted) as ei:
                loop.run(
                    step_fn, {"params": {"w": np.zeros(3, np.float32)}},
                    num_steps=100)
            assert ei.value.step == 3
            sub = WeightSubscriber(s)
            out = sub.poll()
            assert out is not None
            np.testing.assert_array_equal(out["w"], [3.0, 3.0, 3.0])
            assert metrics.value("serving_final_flushes") == 1.0
        finally:
            chaos.configure(None)
            s.close()

    def test_flush_failure_never_blocks_checkpoint(self, tmp_path):
        """A dead serving KV must not eat the preemption grace window or
        the emergency checkpoint."""
        from horovod_tpu.resilience.retry import RetryPolicy

        dead = KVStoreClient("127.0.0.1", 1, retry_policy=RetryPolicy(
            max_attempts=1, base_delay=0.0, deadline=0.2))
        from horovod_tpu.serving import active_publishers

        pub = WeightPublisher(
            dead, retry_policy=RetryPolicy(
                max_attempts=1, base_delay=0.0, deadline=0.2))
        assert pub in active_publishers()
        chaos.configure("sigterm_at_step=2")

        def step_fn(state, i):
            return {"params": {"w": state["params"]["w"] + 1.0}}

        ckpt = str(tmp_path / "ck")
        t0 = time.monotonic()
        with pytest.raises(loop.Preempted) as ei:
            loop.run(
                step_fn, {"params": {"w": np.zeros(2, np.float32)}},
                num_steps=100, checkpoint_dir=ckpt)
        assert time.monotonic() - t0 < 10
        assert ei.value.checkpoint_path is not None  # checkpoint still won
        assert metrics.value("serving_final_flushes") is None
        chaos.configure(None)


# ------------------------------------------------------------ fit callback


@pytest.mark.serving
def test_publish_callback_cadence_and_train_end():
    from horovod_tpu.callbacks import PublishCallback

    s = KVStoreServer()
    pub = WeightPublisher(s, register=False)
    cb = PublishCallback(pub, every=2)

    class Trainer:
        params = {"w": np.arange(4, dtype=np.float32)}

    cb.set_trainer(Trainer())
    for b in range(5):  # publishes after batches 2 and 4
        cb.on_batch_end(b)
        Trainer.params = {"w": Trainer.params["w"] + 1}
    assert pub.generation == 2
    cb.on_train_end()  # batch 5 unpublished → final flush
    assert pub.generation == 3
    sub = WeightSubscriber(s)
    out = sub.poll()
    np.testing.assert_array_equal(out["w"], np.arange(4) + 5.0)
    with pytest.raises(ValueError):
        PublishCallback(pub, every=0)
    s.close()


# --------------------------------------------------- e2e acceptance (mesh)


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


@pytest.mark.serving
@pytest.mark.chaos
@pytest.mark.elastic
def test_publish_subscribe_roundtrip_with_chaos_and_shrink():
    """THE acceptance pin. An 8-rank trainer publishes every committed
    step under ``publish_fail=1,kv_restart_at_step=3`` with an elastic
    8→6 shrink at step 3's boundary. The KV has no WAL, so the restart
    wipes it — the publisher re-roots with a keyframe and the subscriber
    resyncs. Every generation the subscriber applies reconstructs the
    trainer's consolidated weights; the final one is allclose to the final
    trained params."""
    import jax

    import horovod_tpu as hvd
    from horovod_tpu.resilience import elastic
    from horovod_tpu.training import host_snapshot

    model = _tiny_model()
    builder = _make_builder(model)
    server = KVStoreServer()
    pub = WeightPublisher(server, keyframe_every=100, register=False)
    sub = WeightSubscriber(server)
    coord = elastic.ElasticCoordinator(server=server)

    chaos.configure(
        "publish_fail=1,kv_restart_at_step=3,rank_fail=2,rank_fail_at_step=3")
    hvd.init()
    try:
        state = _fresh_state(model)
        final = elastic.run(
            builder, state, num_steps=5, snapshot_every=1,
            coordinator=coord, publisher=pub, publish_every=1)
        assert hvd.size() == 6  # shrunk, no rejoin armed

        # every armed charge fired exactly once
        for site in ("publish_fail", "kv_restart_at_step", "rank_fail"):
            assert metrics.value(
                "resilience_chaos_injected", site=site) == 1.0, site

        # >= 5 generations: steps 1..5 plus the post-resize republish
        assert pub.generation >= 5
        assert metrics.value(
            "serving_publish_generations", kind="delta") >= 2.0
        # the restart re-rooted the chain mid-run
        assert 1 < pub.keyframe_generation <= pub.generation

        tree = sub.wait_for_generation(pub.generation, timeout=10)
        assert sub.lag() == 0
        want = host_snapshot(final["params"])
        for got, w in zip(jax.tree_util.tree_leaves(tree),
                          jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(got, w, atol=1e-3)
        # bit-identical to the publisher's tracked reconstruction
        for got, w in zip(
                jax.tree_util.tree_leaves(tree),
                jax.tree_util.tree_leaves(pub.reconstruction())):
            np.testing.assert_array_equal(got, w)
    finally:
        hvd.shutdown()
        coord.close()
        server.close()
        chaos.configure(None)


@pytest.mark.serving
@pytest.mark.slow
def test_twenty_generation_soak_with_wal_restarts():
    """Soak: 24 generations with a WAL'd KV restarted every 8 publishes;
    the chain survives every restart (no re-root needed) and a subscriber
    polling at arbitrary cadence ends bit-identical to the publisher."""
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        s = KVStoreServer(wal_path=os.path.join(d, "kv.wal"))
        pub = WeightPublisher(s, keyframe_every=5, register=False)
        sub = WeightSubscriber(s)
        t = _tree(0)
        for i in range(1, 25):
            t = _drift(t, i)
            pub.publish({"params": t}, i)
            if i % 8 == 0:
                s.restart()
            if i % 3 == 0:
                sub.poll()
        sub.poll()
        assert sub.generation == pub.generation == 24
        import jax

        for got, w in zip(
                jax.tree_util.tree_leaves(sub.weights()),
                jax.tree_util.tree_leaves(pub.reconstruction())):
            np.testing.assert_array_equal(got, w)
        s.close()
