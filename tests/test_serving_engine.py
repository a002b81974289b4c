"""Serving engine (ISSUE 13): continuous-batching inference plane on
subscribed weights — paged KV cache, chunked prefill, admission
backpressure, int8 wire ingest on device, staleness → /health 503, and the
canary/promotion/rollback generation rollout.

The acceptance pin: train a tiny transformer LM on the 8-device mesh under
a numerics guard → publish generations → the engine serves them under
continuous batching → a ``grad_spike`` trips the publish gate (the
poisoned generation never reaches the KV) and a gate-less trainer's
poisoned generation is caught by the serving-metrics canary instead —
auto-rollback to G−1 with the engine's weights allclose to the last
healthy commit, and the training step's collective-schedule fingerprint
byte-identical before and after serving (the engine adds no
training-side collectives; the full pinned 20-cell matrix is re-verified
every tier-1 run by ``test_schedule.py``).

Tier-1: deterministic, no sleeps > 0.2s; ``serving`` marker.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402

from horovod_tpu.models.transformer import TransformerLM, generate  # noqa: E402
from horovod_tpu.observability import metrics  # noqa: E402
from horovod_tpu.resilience import chaos, health  # noqa: E402
from horovod_tpu.run.rendezvous import KVStoreServer  # noqa: E402
from horovod_tpu.serving import (  # noqa: E402
    GenerationRollout,
    InferenceEngine,
    QueueFull,
    WeightPublisher,
    WeightSubscriber,
    protocol,
)
from horovod_tpu.serving.engine import note_subscriber_health  # noqa: E402

pytestmark = pytest.mark.serving


@pytest.fixture(autouse=True)
def _fresh():
    from horovod_tpu.serving import publisher as _pub_mod

    metrics.reset()
    metrics.set_enabled(True)
    health.reset()
    chaos.configure(None)
    with _pub_mod._ACTIVE_LOCK:
        _pub_mod._ACTIVE.clear()
    yield
    metrics.reset()
    metrics.set_enabled(True)
    health.reset()
    chaos.reset()
    with _pub_mod._ACTIVE_LOCK:
        _pub_mod._ACTIVE.clear()


def _model(depth=2, vocab=97, dim=32, heads=4, max_len=64):
    return TransformerLM(vocab=vocab, dim=dim, depth=depth, heads=heads,
                         mlp_ratio=2, max_len=max_len, dtype=jnp.float32)


def _params(model, seed=0):
    return model.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"]


def _ragged_prompts(seed, lens, vocab=97):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, vocab, size=l).astype(np.int32) for l in lens]


def _reference_generate(model, params, prompts, max_new):
    """generate() over one right-padded ragged batch; returns each row's
    generated run."""
    tp = max(len(p) for p in prompts)
    pad = np.zeros((len(prompts), tp), np.int32)
    for i, p in enumerate(prompts):
        pad[i, :len(p)] = p
    lens = np.asarray([len(p) for p in prompts], np.int32)
    out = np.asarray(generate(
        model, params, pad, max_new_tokens=max_new, prompt_lens=lens))
    return [out[i, lens[i]:lens[i] + max_new] for i in range(len(prompts))]


# ------------------------------------------------------------ paged cache


class TestPagedAttention:
    def test_paged_gather_matches_contiguous_decode(self):
        from horovod_tpu.ops.flash_attention import (
            decode_attention,
            paged_decode_attention,
        )

        rng = np.random.RandomState(0)
        b, h, hkv, d, page = 2, 4, 2, 8, 4
        n_pages, per_seq = 9, 3
        L = per_seq * page
        q = jnp.asarray(rng.randn(b, 1, h, d).astype(np.float32))
        cache_k = rng.randn(b, L, hkv, d).astype(np.float32)
        cache_v = rng.randn(b, L, hkv, d).astype(np.float32)
        # scatter the contiguous cache into a shuffled page pool
        k_pages = np.zeros((n_pages, page, hkv, d), np.float32)
        v_pages = np.zeros((n_pages, page, hkv, d), np.float32)
        table = np.array([[5, 2, 7], [1, 8, 3]], np.int32)
        for row in range(b):
            for j in range(per_seq):
                pg = table[row, j]
                k_pages[pg] = cache_k[row, j * page:(j + 1) * page]
                v_pages[pg] = cache_v[row, j * page:(j + 1) * page]
        start = jnp.asarray([5, 9], jnp.int32)
        ref = decode_attention(
            q, jnp.asarray(cache_k), jnp.asarray(cache_v), start)
        got = paged_decode_attention(
            q, jnp.asarray(k_pages), jnp.asarray(v_pages),
            jnp.asarray(table), start, page_size=page)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_models_decode_attention_alias_still_works(self):
        from horovod_tpu.models.transformer import _decode_attention

        rng = np.random.RandomState(1)
        q = jnp.asarray(rng.randn(1, 1, 2, 4).astype(np.float32))
        k = jnp.asarray(rng.randn(1, 8, 2, 4).astype(np.float32))
        v = jnp.asarray(rng.randn(1, 8, 2, 4).astype(np.float32))
        out = _decode_attention(q, k, v, jnp.asarray([3], jnp.int32))
        assert out.shape == (1, 1, 2, 4)
        assert np.all(np.isfinite(np.asarray(out)))


# ------------------------------------------------- engine ↔ generate parity


class TestEngineParity:
    def test_greedy_token_identical_to_generate_ragged(self):
        """Acceptance: greedy decode through the paged engine is
        token-identical to models.transformer.generate for a ragged batch
        that overflows the slot count (5 requests through 3 slots —
        sequences join and leave mid-flight by construction)."""
        model = _model()
        params = _params(model)
        prompts = _ragged_prompts(42, (5, 11, 3, 8, 14))
        max_new = 6
        want = _reference_generate(model, params, prompts, max_new)
        eng = InferenceEngine(model, page_size=8, num_pages=40, max_batch=3,
                              prefill_chunk=8, max_seq_len=32)
        eng.set_weights(params, generation=1)
        reqs = [eng.submit(p, max_new, rid=f"r{i}")
                for i, p in enumerate(prompts)]
        eng.run_until_idle()
        for r, w in zip(reqs, want):
            assert r.error is None
            np.testing.assert_array_equal(np.asarray(r.generated), w)
            np.testing.assert_array_equal(
                r.tokens, np.concatenate([r.prompt, w]))
        # everything freed afterwards
        assert eng.scheduler.idle()
        assert eng.scheduler.pages_in_use() == 0

    def test_staggered_joins_leave_tokens_unchanged(self):
        """Sequences submitted while others are mid-decode produce the
        same tokens as the all-at-once reference — batch composition is
        not observable per row."""
        model = _model(depth=1)
        params = _params(model)
        prompts = _ragged_prompts(7, (9, 4, 13, 6))
        max_new = 5
        want = _reference_generate(model, params, prompts, max_new)
        eng = InferenceEngine(model, page_size=8, num_pages=40, max_batch=4,
                              prefill_chunk=8, max_seq_len=32)
        eng.set_weights(params, generation=1)
        first = [eng.submit(p, max_new, rid=f"a{i}")
                 for i, p in enumerate(prompts[:2])]
        for _ in range(3):  # first pair mid-flight
            eng.step()
        late = [eng.submit(p, max_new, rid=f"b{i}")
                for i, p in enumerate(prompts[2:])]
        eng.run_until_idle()
        for r, w in zip(first + late, want):
            assert r.error is None
            np.testing.assert_array_equal(np.asarray(r.generated), w)

    def test_long_prompt_prefill_is_chunked(self):
        """A prompt longer than prefill_chunk takes several prefill
        iterations and still matches generate()."""
        model = _model(depth=1)
        params = _params(model)
        prompts = _ragged_prompts(3, (21,))
        max_new = 4
        want = _reference_generate(model, params, prompts, max_new)
        eng = InferenceEngine(model, page_size=8, num_pages=16, max_batch=2,
                              prefill_chunk=8, max_seq_len=32)
        eng.set_weights(params, generation=1)
        req = eng.submit(prompts[0], max_new, rid="long")
        eng.run_until_idle()
        np.testing.assert_array_equal(np.asarray(req.generated), want[0])
        assert metrics.value("serving_engine_steps", kind="prefill") >= 3
        assert metrics.value(
            "serving_prefill_tokens") == float(len(prompts[0]))

    def test_engine_adds_no_training_side_collectives(self):
        """The compiled engine step contains ZERO collectives — serving
        shares a host with training without perturbing any schedule
        fingerprint."""
        from horovod_tpu.analysis.schedule import collective_schedule

        model = _model(depth=1)
        params = _params(model)
        eng = InferenceEngine(model, page_size=8, num_pages=16, max_batch=2,
                              prefill_chunk=8, max_seq_len=32)
        eng.set_weights(params, generation=1)
        b, c = eng.max_batch, eng.prefill_chunk
        sched = collective_schedule(
            lambda *a: eng._apply(*a),
            eng.arm_params("stable"), eng._cache,
            jnp.zeros((b, c), jnp.int32), jnp.zeros((b, c), jnp.int32),
            jnp.zeros((b, eng.pages_per_seq), jnp.int32))
        assert len(sched.ops) == 0


# --------------------------------------------------- admission / backpressure


class TestAdmission:
    def test_page_pool_exhaustion_backpressures_until_free(self):
        """A head-of-line request that cannot reserve its worst-case pages
        waits in the queue (never evicts an admitted sequence) and admits
        the moment the finishing sequence frees them."""
        model = _model(depth=1)
        params = _params(model)
        # pool: 5 allocatable pages of 8; each request needs 3
        eng = InferenceEngine(model, page_size=8, num_pages=6, max_batch=2,
                              prefill_chunk=8, max_seq_len=24)
        eng.set_weights(params, generation=1)
        prompts = _ragged_prompts(11, (10, 10))
        r1 = eng.submit(prompts[0], 8, rid="one")
        r2 = eng.submit(prompts[1], 8, rid="two")
        eng.step()
        # only one fits: 3 + 3 > 5 pages
        assert eng.scheduler.pages_in_use() == 3
        assert eng.scheduler.queue_depth() == 1
        assert metrics.value("serving_queue_depth") == 1.0
        eng.run_until_idle()
        assert r1.error is None and r2.error is None
        assert eng.scheduler.pages_in_use() == 0
        assert metrics.value("serving_sequences_admitted") == 2.0

    def test_queue_full_rejects_with_metric(self):
        model = _model(depth=1)
        params = _params(model)
        eng = InferenceEngine(model, page_size=8, num_pages=16, max_batch=1,
                              prefill_chunk=8, max_seq_len=16, max_queue=2)
        eng.set_weights(params, generation=1)
        p = _ragged_prompts(5, (4, 4, 4))
        eng.submit(p[0], 2, rid="q0")
        eng.submit(p[1], 2, rid="q1")
        with pytest.raises(QueueFull):
            eng.submit(p[2], 2, rid="q2")
        assert metrics.value(
            "serving_admission_rejected", reason="queue_full") == 1.0
        eng.run_until_idle()

    def test_oversized_request_rejected_loudly(self):
        model = _model(depth=1)
        eng = InferenceEngine(model, page_size=8, num_pages=16, max_batch=1,
                              prefill_chunk=8, max_seq_len=16)
        eng.set_weights(_params(model), generation=1)
        with pytest.raises(ValueError, match="max_seq_len"):
            eng.submit(np.ones(14, np.int32), 8, rid="big")

    @pytest.mark.chaos
    def test_request_burst_charge_overflows_queue_once(self):
        """``HOROVOD_CHAOS=request_burst=N``: N synthetic requests hit the
        queue at one iteration boundary; the overflow is counted, the
        charge fires exactly once, and the engine drains the admitted
        remainder without error."""
        model = _model(depth=1)
        params = _params(model)
        eng = InferenceEngine(model, page_size=8, num_pages=40, max_batch=2,
                              prefill_chunk=8, max_seq_len=16, max_queue=3)
        eng.set_weights(params, generation=1)
        chaos.configure("request_burst=6")
        eng.step()
        assert metrics.value(
            "resilience_chaos_injected", site="request_burst") == 1.0
        assert metrics.value(
            "serving_admission_rejected", reason="queue_full") == 3.0
        eng.run_until_idle()
        # a second boundary does not re-fire the consumed charge
        eng.step()
        assert metrics.value(
            "resilience_chaos_injected", site="request_burst") == 1.0


# -------------------------------------------- wire ingest / device decode


class TestDeviceDecode:
    def test_device_decode_bit_identical_to_host(self):
        """protocol.decode(device=True) lands int8 delta leaves on device
        (scale + int8 buffers, dequant-accumulate in XLA) and the result
        is BIT-identical to the host decode — the publisher-reconstruction
        contract survives the engine's ingest mode."""
        rng = np.random.RandomState(0)
        t0 = {"w": rng.randn(4096).astype(np.float32).reshape(64, 64),
              "b": rng.randn(7).astype(np.float32),
              "n": np.int32(3)}
        t1 = {"w": t0["w"] + 0.01 * rng.randn(64, 64).astype(np.float32),
              "b": t0["b"] + 0.1, "n": np.int32(4)}
        key_payload, _ = protocol.encode(t0)
        base_host = protocol.decode(key_payload)
        base_dev = protocol.decode(key_payload, device=True)
        delta_payload, info = protocol.encode(t1, base_host)
        assert info["kind"] == "delta"
        host = protocol.decode(delta_payload, base_host)
        dev = protocol.decode(delta_payload, base_dev, device=True)
        assert isinstance(dev["w"], jax.Array)
        for k in ("w", "b", "n"):
            np.testing.assert_array_equal(np.asarray(dev[k]),
                                          np.asarray(host[k]))

    def test_poisoned_chain_reroots_with_keyframe_on_next_publish(
            self, monkeypatch):
        """Once a non-finite generation is on the chain (gate disabled),
        a delta against it could never recover (NaN absorbs deltas) — the
        next healthy publish must re-root with a keyframe so subscribers
        escape the poison."""
        monkeypatch.setenv("HOROVOD_PUBLISH_NUMERICS_GATE", "0")
        s = KVStoreServer()
        try:
            pub = WeightPublisher(s, keyframe_every=8, register=False)
            sub = WeightSubscriber(s)
            w = np.arange(2048, dtype=np.float32)
            pub.publish({"params": {"w": w}}, 1)
            pub.publish({"params": {"w": w * np.nan}}, 2)
            gen = pub.publish({"params": {"w": w + 1}}, 3)
            assert gen == 3
            assert pub.keyframe_generation == 3  # re-rooted, not a delta
            sub.poll()
            np.testing.assert_array_equal(sub.weights()["w"], w + 1)
        finally:
            s.close()

    def test_device_subscriber_matches_publisher_reconstruction(self):
        s = KVStoreServer()
        try:
            pub = WeightPublisher(s, keyframe_every=4, register=False)
            sub = WeightSubscriber(s, device=True)
            rng = np.random.RandomState(1)
            w = rng.randn(2048).astype(np.float32)
            for step in range(3):
                w = w + rng.randn(2048).astype(np.float32) * 0.01
                pub.publish({"params": {"w": w}}, step)
                sub.poll()
            assert sub.generation == 3
            got = sub.weights()["w"]
            assert isinstance(got, jax.Array)
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(pub.reconstruction()["w"]))
        finally:
            s.close()


# ------------------------------------------------- staleness → health plane


class TestStalenessHealth:
    def test_stale_subscriber_degrades_health_with_lag_in_reason(self):
        s = KVStoreServer()
        try:
            pub = WeightPublisher(s, register=False)
            sub = WeightSubscriber(s, stale_after=0.05)
            pub.publish({"params": {"w": np.ones(4, np.float32)}}, 1)
            sub.poll()
            note_subscriber_health(sub)
            assert health.health_state() == health.HealthState.HEALTHY
            # age the served generation past the watermark and open a lag
            sub._published_at -= 10.0
            pub.publish({"params": {"w": np.ones(4, np.float32) * 2}}, 2)
            sub._head_seen = 2  # observed head without applying
            note_subscriber_health(sub)
            snap = health.snapshot()
            assert snap["value"] >= int(health.HealthState.DEGRADED)
            assert "stale" in snap["reason"]
            assert "1 generation" in snap["reason"]
            assert metrics.value("serving_subscriber_lag") == 1.0
            assert metrics.value("serving_staleness_seconds") > 5.0
            assert metrics.value("resilience_serving_stale") == 1.0
            # catching up clears the condition IMMEDIATELY (observable
            # state, not stall evidence)
            sub.poll()
            note_subscriber_health(sub)
            assert health.health_state() == health.HealthState.HEALTHY
        finally:
            s.close()

    def test_serving_fresh_never_clears_foreign_degradation(self):
        health.record_retry_exhausted("kv")
        assert health.health_state() == health.HealthState.DEGRADED
        health.record_serving_fresh()
        assert health.health_state() == health.HealthState.DEGRADED

    def test_beat_recovery_drops_staleness_ownership(self):
        """Review pin: once beats recover a staleness-owned DEGRADED, the
        ownership claim is gone — a later foreign degradation must not be
        clearable by record_serving_fresh, and a FATAL keeps its own
        reason on /health even while the weights stay stale."""
        health.record_serving_stale(2, 60.0)
        for _ in range(health.MONITOR.recovery_beats):
            health.beat()
        assert health.health_state() == health.HealthState.HEALTHY
        health.record_retry_exhausted("kv")
        health.record_serving_fresh()
        assert health.health_state() == health.HealthState.DEGRADED
        health.record_fatal("publisher chain corrupt")
        health.record_serving_stale(3, 120.0)
        assert health.MONITOR.reason() == "publisher chain corrupt"

    @pytest.mark.chaos
    def test_subscriber_stall_serves_g_minus_k_without_dropping_sequences(
            self):
        """Acceptance (satellite): under ``subscriber_stall`` the engine
        keeps serving G−k per the degrade-don't-crash contract, in-flight
        sequences complete, and the lag clears on catch-up."""
        model = _model(depth=1)
        params = _params(model)
        s = KVStoreServer()
        try:
            pub = WeightPublisher(s, keyframe_every=8, register=False)
            pub.publish({"params": params}, 1)
            chaos.configure("subscriber_stall=0.05")
            sub = WeightSubscriber(s, device=True)
            eng = InferenceEngine(model, page_size=8, num_pages=24,
                                  max_batch=2, prefill_chunk=8,
                                  max_seq_len=24, subscriber=sub)
            assert eng.poll_weights() == 1
            # trainer races ahead; the engine does NOT poll mid-request
            p2 = jax.tree_util.tree_map(lambda a: np.asarray(a) * 1.01,
                                        jax.device_get(params))
            pub.publish({"params": p2}, 2)
            pub.publish({"params": p2}, 3)
            prompts = _ragged_prompts(9, (6, 9))
            reqs = [eng.submit(p, 4, rid=f"s{i}")
                    for i, p in enumerate(prompts)]
            eng.run_until_idle()
            for r in reqs:
                assert r.error is None and len(r.generated) == 4
            assert eng.arm_generation("stable") == 1  # still G−k
            assert eng.poll_weights() == 3  # catch-up applies the chain
            assert metrics.value(
                "resilience_chaos_injected", site="subscriber_stall") >= 1.0
        finally:
            s.close()


# ------------------------------------------------------------- the rollout


def _canary_rid(roll, i):
    return f"canary-seed-{i}"


class TestRollout:
    def _serve_stack(self, model, params, *, fraction=1.0, min_requests=2):
        s = KVStoreServer()
        pub = WeightPublisher(s, keyframe_every=8, register=False)
        sub = WeightSubscriber(s, device=True)
        eng = InferenceEngine(model, page_size=8, num_pages=40, max_batch=2,
                              prefill_chunk=8, max_seq_len=24)
        events = []
        roll = GenerationRollout(
            eng, sub, canary_fraction=fraction,
            min_canary_requests=min_requests, max_latency_ratio=None,
            on_event=lambda e, g: events.append((e, g)))
        pub.publish({"params": params}, 1)
        roll.poll()
        assert roll.stable_generation == 1
        return s, pub, sub, eng, roll, events

    def test_healthy_generation_canaries_then_promotes(self):
        model = _model(depth=1)
        params = _params(model)
        s, pub, sub, eng, roll, events = self._serve_stack(model, params)
        try:
            p2 = jax.tree_util.tree_map(
                lambda a: np.asarray(a) * 1.01, jax.device_get(params))
            pub.publish({"params": p2}, 2)
            roll.poll()
            assert roll.canary_generation == 2
            assert metrics.value("serving_rollout_state") == 1.0
            prompts = _ragged_prompts(21, (5, 7, 4))
            reqs = [roll.submit(_canary_rid(roll, i), p, 3)
                    for i, p in enumerate(prompts)]
            roll.drain()
            assert all(r.error is None for r in reqs)
            assert roll.stable_generation == 2
            assert roll.canary_generation is None
            assert eng.arm_generation("stable") == 2
            assert eng.arm_generation("canary") is None
            assert ("canary_started", 2) in events
            assert ("promoted", 2) in events
            assert metrics.value(
                "serving_rollouts", outcome="promoted") == 1.0
        finally:
            s.close()

    def test_poisoned_generation_rolls_back_to_stable(self, monkeypatch):
        """A generation a gate-less trainer shipped (non-finite weights)
        errors every canary request → auto-rollback to G−1, generation
        vetoed forever, stable arm untouched and allclose to the last
        healthy commit."""
        monkeypatch.setenv("HOROVOD_PUBLISH_NUMERICS_GATE", "0")
        model = _model(depth=1)
        params = _params(model)
        s, pub, sub, eng, roll, events = self._serve_stack(model, params)
        try:
            healthy = jax.device_get(pub.reconstruction())
            poisoned = jax.tree_util.tree_map(
                lambda a: np.asarray(a) * np.nan, jax.device_get(params))
            pub.publish({"params": poisoned}, 2)
            roll.poll()
            assert roll.canary_generation == 2
            prompts = _ragged_prompts(31, (5, 6))
            reqs = [roll.submit(_canary_rid(roll, i), p, 3)
                    for i, p in enumerate(prompts)]
            roll.drain()
            assert all(r.error == "non-finite logits" for r in reqs)
            assert roll.stable_generation == 1
            assert 2 in roll.vetoed
            assert ("rolled_back", 2) in events
            assert metrics.value(
                "serving_rollouts", outcome="rolled_back") == 1.0
            # stable params ARE the last healthy commit
            for got, want in zip(
                jax.tree_util.tree_leaves(eng.arm_params("stable")),
                jax.tree_util.tree_leaves(healthy),
            ):
                np.testing.assert_array_equal(np.asarray(got),
                                              np.asarray(want))
            # the vetoed generation never re-canaries; the next healthy
            # one does, and serving still works end to end
            roll.poll()
            assert roll.canary_generation is None
            p3 = jax.tree_util.tree_map(
                lambda a: np.asarray(a) * 1.01, healthy)
            pub.publish({"params": p3}, 3)
            roll.poll()
            assert roll.canary_generation == 3
            reqs = [roll.submit(_canary_rid(roll, 10 + i), p, 2)
                    for i, p in enumerate(prompts)]
            roll.drain()
            assert all(r.error is None for r in reqs)
            assert roll.stable_generation == 3
        finally:
            s.close()

    def test_promotion_mid_flight_drains_old_stable_coherently(self):
        """Review pin: promoting a canary while a STABLE sequence is
        mid-decode must not swap its weights — the in-flight sequence
        parks on a drain arm and its tokens stay identical to generate()
        under the OLD generation."""
        model = _model(depth=1)
        p1 = _params(model, seed=0)
        p2 = jax.tree_util.tree_map(
            lambda a: np.asarray(a) * 1.5, jax.device_get(p1))
        prompts = _ragged_prompts(13, (9,))
        want_old = _reference_generate(model, p1, prompts, 8)
        eng = InferenceEngine(model, page_size=8, num_pages=24, max_batch=2,
                              prefill_chunk=8, max_seq_len=24)
        eng.set_weights(p1, generation=1, arm="stable")
        req = eng.submit(prompts[0], 8, rid="inflight")
        for _ in range(4):  # mid-decode
            eng.step()
        eng.set_weights(p2, generation=2, arm="canary")
        eng.promote_canary()
        assert eng.arm_generation("stable") == 2
        eng.run_until_idle()
        assert req.error is None
        np.testing.assert_array_equal(np.asarray(req.generated), want_old[0])
        assert not [a for a in eng._arms if "drain" in a]  # released

    def test_run_until_idle_without_weights_raises_loudly(self):
        model = _model(depth=1)
        eng = InferenceEngine(model, page_size=8, num_pages=16, max_batch=1,
                              prefill_chunk=8, max_seq_len=16)
        eng.submit(np.asarray([1, 2], np.int32), 2, rid="w0")
        with pytest.raises(RuntimeError, match="no weights installed"):
            eng.run_until_idle()

    def test_route_is_deterministic_split(self):
        model = _model(depth=1)
        params = _params(model)
        s, pub, sub, eng, roll, _ = self._serve_stack(
            model, params, fraction=0.5)
        try:
            p2 = jax.tree_util.tree_map(
                lambda a: np.asarray(a) * 1.01, jax.device_get(params))
            pub.publish({"params": p2}, 2)
            roll.poll()
            arms = {roll.route(f"rid-{i}") for i in range(64)}
            assert arms == {"stable", "canary"}
            for i in range(64):  # same rid → same arm, always
                assert roll.route(f"rid-{i}") == roll.route(f"rid-{i}")
        finally:
            s.close()


# ----------------------------------------------------------- acceptance e2e


@pytest.mark.chaos
def test_e2e_train_publish_serve_canary_rollback(hvd, monkeypatch):
    """THE acceptance drill: train on the 8-device mesh under the numerics
    guard → publish generations → serve under continuous batching →
    (a) a grad_spike trips the publish gate so the poisoned generation
    never arrives (PublishRejected — gate leg), (b) a gate-less trainer's
    poisoned generation is caught by the serving-metrics canary and
    auto-rolled back to G−1 with the engine allclose to the last healthy
    commit (metrics leg), and the training step's collective schedule is
    byte-identical before and after serving (the engine adds no
    training-side collectives; the pinned 20-cell fingerprint matrix is
    separately re-verified by test_schedule.py every run)."""
    from horovod_tpu.analysis.schedule import collective_schedule
    from horovod_tpu.resilience import numerics
    from horovod_tpu.serving import PublishRejected
    from horovod_tpu.training import (
        make_shardmap_train_step,
        replicate,
        shard_batch,
        token_xent,
    )

    monkeypatch.setenv("HOROVOD_NUMERICS_WARMUP", "1")
    monkeypatch.setenv("HOROVOD_NUMERICS_SPIKE_FACTOR", "5.0")
    model = _model(depth=1, vocab=64, dim=32, heads=2, max_len=32)
    params0 = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    # the spike charge compiles INTO the guarded step at trace time
    chaos.configure("grad_spike_at_step=3:500")
    tx = numerics.guard(optax.adam(1e-2))
    step = make_shardmap_train_step(
        model, tx, loss_fn=token_xent, instrument=False, donate=False)
    rng = np.random.RandomState(0)
    toks = rng.randint(1, 64, size=(16, 9)).astype(np.int32)
    xs, ys = shard_batch(toks[:, :-1]), shard_batch(toks[:, 1:])
    params = replicate(jax.tree_util.tree_map(jnp.array, params0))
    opt_state = tx.init(params)

    server = KVStoreServer()
    try:
        pub = WeightPublisher(server, keyframe_every=8, register=False)
        sub = WeightSubscriber(server, device=True)
        eng = InferenceEngine(model, page_size=8, num_pages=24, max_batch=2,
                              prefill_chunk=8, max_seq_len=24)
        roll = GenerationRollout(eng, sub, canary_fraction=1.0,
                                 min_canary_requests=2,
                                 max_latency_ratio=None)

        def train_one():
            nonlocal params, opt_state
            params, _, opt_state, loss = step(params, {}, opt_state, xs, ys)
            return loss

        fp_before = collective_schedule(
            step, params, {}, opt_state, xs, ys).fingerprint()

        # healthy steps 0..2 → G1 (keyframe) + G2 (int8 delta) commit
        train_one()
        assert pub.publish(
            {"params": params, "opt_state": opt_state}, 1) == 1
        roll.poll()
        assert roll.stable_generation == 1
        train_one()
        assert pub.publish(
            {"params": params, "opt_state": opt_state}, 2) == 2
        roll.poll()
        assert roll.canary_generation == 2
        prompts = _ragged_prompts(5, (6, 9), vocab=64)
        reqs = [roll.submit(f"e2e-{i}", p, 4)
                for i, p in enumerate(prompts)]
        roll.drain()
        assert all(r.error is None for r in reqs)
        assert roll.stable_generation == 2  # promoted under traffic
        train_one()

        # the spike: guard step 3 goes BAD in-jit → publish gate refuses,
        # the poisoned generation NEVER reaches the KV head
        train_one()
        assert numerics.verdict(opt_state)["bad_streak"] >= 1
        with pytest.raises(PublishRejected) as ei:
            pub.publish({"params": params, "opt_state": opt_state}, 4)
        assert ei.value.reason == "bad_step"
        roll.poll()
        assert roll.stable_generation == 2  # nothing new arrived
        assert metrics.value(
            "serving_publish_rejected", reason="bad_step") == 1.0

        # streak clears → G3 commits; capture the last healthy commit
        train_one()
        assert numerics.verdict(opt_state)["bad_streak"] == 0
        assert pub.publish(
            {"params": params, "opt_state": opt_state}, 5) == 3
        roll.poll()
        reqs = [roll.submit(f"e2e2-{i}", p, 4)
                for i, p in enumerate(prompts)]
        roll.drain()
        assert roll.stable_generation == 3
        healthy = jax.device_get(pub.reconstruction())

        # metrics leg: a GATE-LESS trainer ships the poison → the canary
        # catches it and auto-rolls back to G−1
        monkeypatch.setenv("HOROVOD_PUBLISH_NUMERICS_GATE", "0")
        poisoned = jax.tree_util.tree_map(
            lambda a: np.asarray(a) * np.nan, jax.device_get(params))
        assert pub.publish({"params": poisoned}, 6) == 4
        roll.poll()
        assert roll.canary_generation == 4
        reqs = [roll.submit(f"e2e3-{i}", p, 3)
                for i, p in enumerate(prompts)]
        roll.drain()
        assert all(r.error == "non-finite logits" for r in reqs)
        assert roll.stable_generation == 3
        assert 4 in roll.vetoed
        assert metrics.value(
            "serving_rollouts", outcome="rolled_back") == 1.0
        for got, want in zip(
            jax.tree_util.tree_leaves(eng.arm_params("stable")),
            jax.tree_util.tree_leaves(healthy),
        ):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=0, atol=0)

        # the engine added no training-side collectives: the training
        # step's schedule fingerprint is byte-identical after serving
        fp_after = collective_schedule(
            step, params, {}, opt_state, xs, ys).fingerprint()
        assert fp_after == fp_before
    finally:
        server.close()
