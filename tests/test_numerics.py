"""Numerics guard (ISSUE 9): in-jit gradient/loss anomaly detection with
atomic step skip, dynamic loss scaling, bounded skip/replay, corrupting-rank
fingerprint quarantine + elastic eviction, and the poison-free publish gate.

Acceptance pins (all on the 8-device CPU mesh, deterministic chaos):

- ``grad_nan_at_step=3``: the step is skipped with weights AND
  error-feedback residuals bit-identical to pre-step, training resumes,
  and the trajectory matches a clean run that never saw the batch.
- ``grad_corrupt_rank=5:4``: rank 5 is named within one step, goes
  SUSPECT, and is evicted via the elastic 8→7 path.
- ``grad_spike`` during an active publish: the publisher rejects the
  generation and the subscriber's ``reconstruction`` still matches the
  last healthy commit.
"""

import os
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax

from horovod_tpu.compression import Compression
from horovod_tpu.observability import metrics
from horovod_tpu.resilience import chaos, health, loop, numerics
from horovod_tpu.resilience.health import HealthState

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_numerics():
    from horovod_tpu.analysis import sanitizer

    metrics.reset()
    metrics.set_enabled(True)
    health.reset()
    chaos.configure(None)
    numerics.reset()
    sanitizer.reset()  # the fingerprint plane's fallback store
    yield
    metrics.reset()
    metrics.set_enabled(True)
    health.reset()
    chaos.reset()
    numerics.reset()
    sanitizer.reset()


def _params():
    return {"w": jnp.ones(4, jnp.float32)}


def _g(v):
    return {"w": jnp.full(4, v, jnp.float32)}


# ------------------------------------------------------------- guard unit


@pytest.mark.numerics
class TestGuard:
    def test_good_step_matches_unguarded(self):
        tx = numerics.guard(optax.adam(1e-2))
        plain = optax.adam(1e-2)
        p = _params()
        sg, sp = tx.init(p), plain.init(p)
        for v in (0.5, -0.25, 0.1):
            ug, sg = tx.update(_g(v), sg, p)
            up, sp = plain.update(_g(v), sp, p)
            np.testing.assert_array_equal(
                np.asarray(ug["w"]), np.asarray(up["w"]))
        v = numerics.verdict(sg)
        assert v["count"] == 3 and v["bad_count"] == 0

    @pytest.mark.parametrize("poison", [np.nan, np.inf, -np.inf])
    def test_nonfinite_grads_skip_atomically(self, poison):
        tx = numerics.guard(optax.adam(1e-2))
        p = _params()
        st = tx.init(p)
        _, st = tx.update(_g(0.5), st, p)
        before = [np.asarray(l).copy()
                  for l in jax.tree_util.tree_leaves(st.inner)]
        u, st = tx.update(_g(poison), st, p)
        np.testing.assert_array_equal(np.asarray(u["w"]), 0.0)
        after = jax.tree_util.tree_leaves(st.inner)
        for a, b in zip(before, after):
            np.testing.assert_array_equal(a, np.asarray(b))
        v = numerics.verdict(st)
        assert v["bad_count"] == 1 and v["bad_streak"] == 1
        assert v["last_bad"] and not v["last_finite"]

    def test_nonfinite_loss_marks_bad(self):
        tx = numerics.guard(optax.sgd(0.1))
        p = _params()
        st = tx.init(p)
        u, st = tx.update(_g(0.5), st, p, loss=jnp.float32(np.nan))
        np.testing.assert_array_equal(np.asarray(u["w"]), 0.0)
        assert numerics.verdict(st)["bad_count"] == 1

    def test_spike_detected_after_warmup_only(self):
        tx = numerics.guard(optax.sgd(0.1), warmup=3, spike_factor=5.0)
        p = _params()
        st = tx.init(p)
        # a 100x "spike" INSIDE warmup passes (and is absorbed)
        u, st = tx.update(_g(0.5), st, p)
        u, st = tx.update(_g(50.0), st, p)
        assert numerics.verdict(st)["bad_count"] == 0
        for _ in range(3):
            u, st = tx.update(_g(0.5), st, p)
        ewma_before = numerics.verdict(st)["ewma"]
        u, st = tx.update(_g(500.0), st, p)
        v = numerics.verdict(st)
        assert v["bad_count"] == 1 and v["last_bad"]
        np.testing.assert_array_equal(np.asarray(u["w"]), 0.0)
        # the spike did NOT raise its own bar
        assert v["ewma"] == pytest.approx(ewma_before)
        # and a normal step afterwards resumes cleanly
        u, st = tx.update(_g(0.5), st, p)
        assert numerics.verdict(st)["bad_streak"] == 0
        assert np.all(np.asarray(u["w"]) != 0)

    def test_ewma_seeds_on_first_good_step_after_bad_start(self):
        """Review hardening: a BAD step 0 (chaos, loss-scale hunting)
        must not strand the EWMA baseline near 0 — the seed fires on the
        first GOOD norm, so the spike bar at warmup is the full
        spike_factor x baseline, not a fraction of it."""
        tx = numerics.guard(optax.sgd(0.1), warmup=2, spike_factor=10.0)
        p = _params()
        st = tx.init(p)
        _, st = tx.update(_g(np.nan), st, p)  # bad step 0
        _, st = tx.update(_g(0.5), st, p)     # first good: seeds EWMA
        assert numerics.verdict(st)["ewma"] == pytest.approx(1.0)
        # 3x the baseline after warmup is ordinary fluctuation, not a
        # spike (with a count==0-keyed seed the bar would sit far lower)
        _, st = tx.update(_g(0.5), st, p)
        u, st = tx.update(_g(1.5), st, p)
        assert numerics.verdict(st)["last_bad"] is False
        assert np.all(np.asarray(u["w"]) != 0)

    def test_bad_step_preserves_negative_zero_params(self):
        """Review hardening: the builders apply the discarded update as
        ``p + u``, and IEEE gives ``-0.0 + (+0.0) = +0.0`` — a sign-bit
        flip that breaks the bit-identical-skip contract. The guard
        discards with NEGATIVE zero (``p + (-0.0) = p`` for every p)."""
        tx = numerics.guard(optax.sgd(0.1))
        p = {"w": jnp.array([-0.0, 0.0, 1.0], jnp.float32)}
        st = tx.init(p)
        u, st = tx.update(
            {"w": jnp.full(3, np.nan, jnp.float32)}, st, p)
        got = np.asarray(optax.apply_updates(p, u)["w"])
        np.testing.assert_array_equal(got, np.asarray(p["w"]))
        assert np.signbit(got[0]) and not np.signbit(got[1])
        # a GOOD step still applies real updates
        u, st = tx.update(_g(0.5), st, p)
        assert np.all(np.asarray(u["w"]) != 0)

    def test_standalone_hook_feeds_gauges_without_fingerprint(self):
        """Review hardening: the troubleshooting contract is that
        HOROVOD_NUMERICS_GUARD=1 *alone* feeds the numerics_guard_*
        gauges and consumes fired chaos charges — without the elastic
        wrapper or the fingerprint plane. The standalone hook reads the
        verdict LAGGED (staged async copy, noted one boundary late) so a
        plain jitted loop keeps its dispatch pipeline."""
        numerics.configure(fingerprint=False)
        chaos.configure("grad_nan_at_step=1")
        tx = numerics.guard(optax.sgd(0.1))
        p = _params()
        st = tx.init(p)
        _, st = tx.update(_g(0.5), st, p)
        assert numerics.maybe_note_output(0, st) is None  # staged only
        _, st = tx.update(_g(0.5), st, p)  # count==1: injection fires
        v = numerics.maybe_note_output(1, st)
        assert v is not None and v["count"] == 1  # step 0, one late
        assert metrics.value("numerics_guard_bad_steps") == 0.0
        v = numerics.flush_staged()  # the last boundary's verdict
        assert v is not None and v["bad_count"] == 1
        assert metrics.value("numerics_guard_bad_steps") == 1.0
        assert chaos.grad_nan_step() is None  # consumed via the hook
        assert metrics.value(
            "resilience_chaos_injected", site="grad_nan_at_step") == 1.0
        assert numerics.flush_staged() is None  # drained

    def test_warmup_counts_good_steps_only(self):
        """Review hardening: the documented contract is `warmup` GOOD
        steps — bad steps don't feed the EWMA, so they must not count
        toward its baseline either. Two good steps after a bad start is
        still inside warmup=3: the 50x norm is absorbed, not flagged."""
        tx = numerics.guard(optax.sgd(0.1), warmup=3, spike_factor=5.0)
        p = _params()
        st = tx.init(p)
        _, st = tx.update(_g(np.nan), st, p)  # bad: not a warmup sample
        _, st = tx.update(_g(0.5), st, p)
        _, st = tx.update(_g(0.5), st, p)
        # total count is 3 (>= warmup) but only 2 good samples: unarmed
        u, st = tx.update(_g(25.0), st, p)
        v = numerics.verdict(st)
        assert v["bad_count"] == 1  # only the NaN step
        assert np.all(np.asarray(u["w"]) != 0)  # the 50x step applied
        # one more good sample arms it; the next blow-up is flagged
        _, st = tx.update(_g(0.5), st, p)
        u, st = tx.update(_g(500.0), st, p)
        v = numerics.verdict(st)
        assert v["last_bad"] and v["bad_count"] == 2
        np.testing.assert_array_equal(np.asarray(u["w"]), 0.0)

    def test_streak_counts_consecutive_bad(self):
        tx = numerics.guard(optax.sgd(0.1))
        p = _params()
        st = tx.init(p)
        for _ in range(3):
            _, st = tx.update(_g(np.nan), st, p)
        v = numerics.verdict(st)
        assert v["bad_streak"] == 3 and v["bad_count"] == 3
        _, st = tx.update(_g(0.5), st, p)
        assert numerics.verdict(st)["bad_streak"] == 0

    def test_int_leaves_ride_through(self):
        """Integer leaves are excluded from the norm (they cannot be
        non-finite) and the guarded update matches the unguarded one."""
        tx = numerics.guard(optax.sgd(1.0))
        plain = optax.sgd(1.0)
        p = {"w": jnp.ones(4), "steps": jnp.zeros((2,), jnp.int32)}
        sg, sp = tx.init(p), plain.init(p)
        g = {"w": jnp.full(4, 0.5), "steps": jnp.ones((2,), jnp.int32)}
        ug, sg = tx.update(g, sg, p)
        up, sp = plain.update(g, sp, p)
        for k in p:
            np.testing.assert_array_equal(
                np.asarray(ug[k]), np.asarray(up[k]))
        v = numerics.verdict(sg)
        assert v["bad_count"] == 0
        # only the float dtype contributes to the norm
        assert v["last_norm"] == pytest.approx(1.0)

    def test_per_dtype_norms_recorded(self):
        tx = numerics.guard(optax.sgd(1.0))
        p = {"a": jnp.ones((3,), jnp.float32), "b": jnp.ones((2,), jnp.bfloat16)}
        st = tx.init(p)
        g = {"a": jnp.full((3,), 2.0, jnp.float32),
             "b": jnp.full((2,), 1.0, jnp.bfloat16)}
        _, st = tx.update(g, st, p)
        v = numerics.verdict(st)
        assert set(v["per_dtype"]) == {"float32", "bfloat16"}
        assert v["per_dtype"]["float32"] == pytest.approx(np.sqrt(12.0))
        assert v["per_dtype"]["bfloat16"] == pytest.approx(np.sqrt(2.0))

    def test_loss_scale_unscales_and_backs_off(self):
        tx = numerics.guard(optax.sgd(0.1), loss_scale=16.0)
        p = _params()
        st = tx.init(p)
        assert float(np.asarray(numerics.current_scale(st))) == 16.0
        # gradients arrive scaled by 16 (the builder scaled the loss);
        # the applied update must be the UNSCALED sgd step
        u, st = tx.update(_g(16.0 * 0.5), st, p)
        np.testing.assert_allclose(np.asarray(u["w"]), -0.05, rtol=1e-6)
        # a bad step halves the scale
        _, st = tx.update(_g(np.inf), st, p)
        assert numerics.verdict(st)["loss_scale"] == 8.0

    def test_loss_scale_grows_after_interval(self):
        tx = numerics.guard(
            optax.sgd(0.1), loss_scale=4.0, growth_interval=3)
        p = _params()
        st = tx.init(p)
        for i in range(3):
            _, st = tx.update(_g(4.0 * 0.5), st, p)
        assert numerics.verdict(st)["loss_scale"] == 8.0
        # streak resets after growth: two more good steps keep it at 8
        for i in range(2):
            _, st = tx.update(_g(8.0 * 0.5), st, p)
        assert numerics.verdict(st)["loss_scale"] == 8.0

    def test_unguarded_state_has_no_verdict(self):
        st = optax.adam(1e-2).init(_params())
        assert numerics.verdict(st) is None
        assert numerics.note_step(0, st) is None
        assert float(np.asarray(numerics.current_scale(st))) == 1.0

    def test_distributed_optimizer_wraps_and_env_enables(
            self, hvd, monkeypatch):
        tx = hvd.DistributedOptimizer(optax.adam(1e-2), numerics_guard=True)
        assert numerics.is_guarded(tx)
        monkeypatch.setenv("HOROVOD_NUMERICS_GUARD", "1")
        assert numerics.is_guarded(hvd.DistributedOptimizer(optax.sgd(0.1)))
        monkeypatch.delenv("HOROVOD_NUMERICS_GUARD")
        assert not numerics.is_guarded(
            hvd.DistributedOptimizer(optax.sgd(0.1)))
        # loss_scale implies the guard
        assert numerics.is_guarded(
            hvd.DistributedOptimizer(optax.sgd(0.1), loss_scale="dynamic"))


# ------------------------------------------------- chaos charge accounting


@pytest.mark.numerics
@pytest.mark.chaos
class TestChaosCharges:
    def test_parse_grammar(self):
        cfg = chaos.parse_spec(
            "grad_nan_at_step=3,grad_spike_at_step=7:100.0,"
            "grad_corrupt_rank=5:4")
        assert cfg == {
            "grad_nan_at_step": 3,
            "grad_spike_at_step": (7, 100.0),
            "grad_corrupt_rank": (5, 4),
        }
        # scale defaults when omitted
        assert chaos.parse_spec("grad_spike_at_step=2")[
            "grad_spike_at_step"] == (2, 1e3)
        with pytest.raises(ValueError):
            chaos.parse_spec("grad_corrupt_rank=5")

    def test_nan_charge_fires_exactly_once(self):
        chaos.configure("grad_nan_at_step=1")
        tx = numerics.guard(optax.sgd(0.1))
        p = _params()
        st = tx.init(p)
        for i in range(4):
            _, st = tx.update(_g(0.5), st, p)
            numerics.note_step(i, st)
        v = numerics.verdict(st)
        assert v["bad_count"] == 1  # exactly one injection
        assert chaos.grad_nan_step() is None  # consumed
        # non-sticky evidence: the bit marks only the firing step, so a
        # checkpointed later state can never replay it into a fresh run
        assert v["chaos_fired"] == 0
        assert metrics.value(
            "resilience_chaos_injected", site="grad_nan_at_step") == 1.0

    def test_spike_charge_fires_exactly_once(self):
        chaos.configure("grad_spike_at_step=4:1000")
        tx = numerics.guard(optax.sgd(0.1), warmup=2)
        p = _params()
        st = tx.init(p)
        for i in range(6):
            _, st = tx.update(_g(0.5), st, p)
            numerics.note_step(i, st)
        v = numerics.verdict(st)
        assert v["bad_count"] == 1
        assert chaos.grad_spike() is None
        assert metrics.value(
            "resilience_chaos_injected", site="grad_spike_at_step") == 1.0

    def test_overlapping_nan_and_spike_charges_compose(self):
        """Review hardening: grad_nan and grad_spike armed at the SAME
        step compose (NaN × scale stays NaN). With a where-select
        overwrite the gradients came out a finite ×scale — inside the
        default warmup that is not even a BAD step — while the fired
        bitmask still told note_step the NaN path was exercised."""
        chaos.configure("grad_nan_at_step=1,grad_spike_at_step=1:100")
        tx = numerics.guard(optax.sgd(0.1))
        p = _params()
        st = tx.init(p)
        for i in range(3):
            _, st = tx.update(_g(0.5), st, p)
            numerics.note_step(i, st)
        v = numerics.verdict(st)
        # the step really went non-finite: the finiteness detector fired
        assert v["bad_count"] == 1
        assert chaos.grad_nan_step() is None  # both charges consumed
        assert chaos.grad_spike() is None
        assert metrics.value(
            "resilience_chaos_injected", site="grad_nan_at_step") == 1.0
        assert metrics.value(
            "resilience_chaos_injected", site="grad_spike_at_step") == 1.0

    def test_unfired_charge_stays_armed(self):
        """A charge whose step never arrives is NOT consumed — mirrors
        the PR-8 hardening."""
        chaos.configure("grad_nan_at_step=50")
        tx = numerics.guard(optax.sgd(0.1))
        p = _params()
        st = tx.init(p)
        for i in range(3):
            _, st = tx.update(_g(0.5), st, p)
            numerics.note_step(i, st)
        assert chaos.grad_nan_step() == 50  # still armed
        assert metrics.value(
            "resilience_chaos_injected", site="grad_nan_at_step") is None

    def test_restored_state_past_k_never_counts_a_phantom_injection(self):
        """Review hardening: a guard state restored with its counter
        already past K can never execute the traced `count == K`
        injection — note_step must NOT consume the charge or count an
        injection that never ran (chaos_fired is the evidence)."""
        tx = numerics.guard(optax.sgd(0.1))
        p = _params()
        st = tx.init(p)
        for i in range(5):
            _, st = tx.update(_g(0.5), st, p)  # no chaos armed: count=5
        chaos.configure("grad_nan_at_step=3")  # armed AFTER count passed 3
        _, st = tx.update(_g(0.5), st, p)
        numerics.note_step(5, st)
        assert chaos.grad_nan_step() == 3  # still armed
        assert metrics.value(
            "resilience_chaos_injected", site="grad_nan_at_step") is None
        assert numerics.verdict(st)["chaos_fired"] == 0

    def test_boundary_dedupes_consecutive_same_step(self):
        """Review hardening: an instrumented step inside the elastic
        wrapper drives the boundary twice per step — the second call for
        the same step must be a no-op (one publish, one cross-check),
        while a later (or rolled-back earlier) step still runs."""
        store = _Store()
        numerics.configure(fingerprint=True, kv=store)
        with _world(4):
            numerics.boundary(0)
            n0 = metrics.value("numerics_fingerprints_checked")
            numerics.boundary(0)  # duplicate: deduped
            assert metrics.value("numerics_fingerprints_checked") == n0
            numerics.boundary(1)
            assert metrics.value("numerics_fingerprints_checked") == n0 + 1
            numerics.boundary(0)  # rollback revisits step 0: runs again
            assert metrics.value("numerics_fingerprints_checked") == n0 + 2

    def test_republish_keeps_chaos_perturbation_sticky(self):
        """Review hardening: a second publish of the SAME step (two
        boundary hooks with diverged counters) must keep the perturbed
        victim record instead of overwriting it clean after the charge
        was consumed."""
        import json

        store = _Store()
        numerics.configure(fingerprint=True, kv=store)
        chaos.configure("grad_corrupt_rank=2:0")
        with _world(4):
            numerics.publish_fingerprint(0)
            assert chaos.grad_corrupt() is None  # consumed
            numerics.publish_fingerprint(0)  # republish, charge gone
        rec = json.loads(store.get(numerics.fingerprint_key(0, 2)))
        assert rec["finite"] == 0  # still perturbed, not overwritten

    def test_corrupt_rank_stays_armed_in_one_rank_world(self):
        """grad_corrupt_rank targets a peer; a 1-rank world has none, so
        the charge must stay armed instead of counting a perturbation
        that cannot exist."""
        chaos.configure("grad_corrupt_rank=5:0")
        numerics.configure(fingerprint=True)
        numerics.publish_fingerprint(0)
        assert chaos.grad_corrupt() == (5, 0)  # world=1: still armed
        assert metrics.value(
            "resilience_chaos_injected", site="grad_corrupt_rank") is None
        assert numerics.cross_check_fingerprints(0) is None


# ------------------------------------------------- fingerprint plane


@pytest.mark.numerics
class TestFingerprints:
    def test_publish_perturbs_chaos_victim_and_cross_check_names_it(self):
        """Single-controller publish writes one record per rank; the
        armed grad_corrupt_rank charge perturbs ONLY the victim's copy
        (consumed on perturb), and the cross-check names it."""
        store = _Store()
        numerics.configure(fingerprint=True, kv=store)
        chaos.configure("grad_corrupt_rank=3:2")
        with _world(4):
            numerics.publish_fingerprint(
                2, {"step": 2, "finite": 1, "norm": 1.5, "per_dtype": {}})
            assert chaos.grad_corrupt() is None  # consumed by the perturb
            found = numerics.cross_check_fingerprints(2)
        assert found is not None and found[0]["rank"] == 3
        assert not found[0]["finite"]
        assert metrics.value(
            "resilience_chaos_injected", site="grad_corrupt_rank") == 1.0
        assert metrics.value("numerics_fingerprints_checked") == 1.0
        assert numerics.take_corrupt_ranks() == [3]

    def test_cross_check_flags_outlier_and_feeds_health(self):
        store = _Store()
        numerics.configure(fingerprint=True, kv=store)
        for r in range(8):
            rec = {"step": 1, "finite": 1, "norm": 1.0, "per_dtype": {}}
            if r == 5:
                rec["norm"] = 1e6  # SDC-flavored outlier, still finite
            store.put(
                numerics.fingerprint_key(1, r),
                __import__("json").dumps(rec).encode())
        with _world(8):
            found = numerics.cross_check_fingerprints(1)
        assert found is not None and found[0]["rank"] == 5
        assert numerics.take_corrupt_ranks() == [5]
        assert numerics.take_corrupt_ranks() == []  # popped
        assert health.health_state() == HealthState.SUSPECT
        assert "rank 5" in health.snapshot()["reason"]
        assert metrics.value("numerics_corrupt_ranks", rank=5) == 1.0
        assert metrics.value("resilience_numeric_corruptions") == 1.0

    def test_garbled_blob_is_a_verdict_not_an_absence(self):
        """Review hardening: a rank whose published fingerprint is
        unparseable bytes is judged like a non-finite record — garbled
        output often comes from the exact corrupt host this plane hunts,
        and dropping it would mark the step fully checked with the
        most-broken rank never examined."""
        import json

        store = _Store()
        numerics.configure(fingerprint=True, kv=store)
        for r in range(4):
            blob = (
                b"\xff\x00 not json \xfe" if r == 2 else
                json.dumps(
                    {"step": 1, "finite": 1, "norm": 1.0}).encode()
            )
            store.put(numerics.fingerprint_key(1, r), blob)
        with _world(4):
            found = numerics.cross_check_fingerprints(1)
        assert found is not None and found[0]["rank"] == 2
        assert not found[0]["finite"]
        assert numerics.take_corrupt_ranks() == [2]
        # all 4 records were present (garbled ≠ missing): no deferral
        assert metrics.value("numerics_fingerprints_checked") == 1.0

    def test_schedule_divergence_defers_to_sanitizer(self):
        """A rank the PR-8 sanitizer already named at the same step is a
        control-flow bug, not data corruption — no numerics verdict."""
        from horovod_tpu.analysis import sanitizer

        store = _Store()
        numerics.configure(fingerprint=True, kv=store)
        for r in range(4):
            rec = {"step": 3, "finite": 1 if r != 2 else 0,
                   "norm": 1.0 if r != 2 else None, "per_dtype": {}}
            store.put(
                numerics.fingerprint_key(3, r),
                __import__("json").dumps(rec).encode())
        old = sanitizer._last_divergence
        sanitizer._last_divergence = {"step": 3, "rank": 2, "op": "x"}
        try:
            with _world(4):
                assert numerics.cross_check_fingerprints(3) is None
        finally:
            sanitizer._last_divergence = old
        assert not numerics.quarantine_pending()

    def test_low_side_outlier_flagged_but_zero_sentinel_is_not(self):
        """Review hardening: a stuck-at-zero SDC rank (norm far BELOW the
        family median) is quarantined like a blow-up; an exact 0.0 is the
        default record's no-signal sentinel and never a verdict."""
        import json

        store = _Store()
        numerics.configure(fingerprint=True, kv=store)
        norms = {0: 1.0, 1: 1.1, 2: 1e-9, 3: 0.9}
        for r, n in norms.items():
            store.put(
                numerics.fingerprint_key(1, r),
                json.dumps({"step": 1, "finite": 1, "norm": n}).encode())
        with _world(4):
            found = numerics.cross_check_fingerprints(1)
        assert found is not None and found[0]["rank"] == 2
        assert numerics.take_corrupt_ranks() == [2]
        # exact-zero sentinel: not flagged
        store2 = _Store()
        numerics.configure(kv=store2)
        for r, n in {0: 1.0, 1: 1.1, 2: 0.0, 3: 0.9}.items():
            store2.put(
                numerics.fingerprint_key(2, r),
                json.dumps({"step": 2, "finite": 1, "norm": n}).encode())
        with _world(4):
            assert numerics.cross_check_fingerprints(2) is None

    def test_set_step_first_call_does_not_preempt_real_record(self):
        """Review hardening: the very first set_step(0) fires BEFORE step
        0 executes — it must not publish a premature default record whose
        boundary dedupe then suppresses the real (possibly corrupt)
        step-0 fingerprint."""
        import json

        store = _Store()
        numerics.configure(fingerprint=True, kv=store)
        numerics.set_step(0)  # InstrumentedStep's first call, pre-step
        assert store.get(numerics.fingerprint_key(0, 0)) is None
        # the step runs, goes non-finite; the policy layer notes it and
        # the elastic wrapper drives the boundary with the REAL record
        tx = numerics.guard(optax.sgd(0.1))
        p = _params()
        st = tx.init(p)
        _, st = tx.update(_g(np.nan), st, p)
        numerics.note_step(0, st)
        numerics.boundary(0)
        rec = json.loads(store.get(numerics.fingerprint_key(0, 0)))
        assert rec["finite"] == 0  # the real record, not the default

    def test_deferred_recheck_reports_each_finding_once(self):
        """Review hardening: a step kept pending by a missing peer must
        not re-strike health / re-quarantine the SAME finding on every
        retry boundary."""
        import json

        store = _Store()
        numerics.configure(fingerprint=True, kv=store)
        with _world(4):
            for r in range(3):  # rank 3 never publishes (dead peer)
                store.put(
                    numerics.fingerprint_key(0, r),
                    json.dumps({
                        "step": 0, "finite": 1 if r != 2 else 0,
                        "norm": 1.0 if r != 2 else None}).encode())
            first = numerics.cross_check_fingerprints(0)
            assert first is not None and first[0]["rank"] == 2
            assert numerics.take_corrupt_ranks() == [2]
            # retries while rank 3 stays missing: no duplicate findings
            for b in range(1, 4):
                numerics.boundary(b)
        assert metrics.value("numerics_corrupt_ranks", rank=2) == 1.0
        assert metrics.value("resilience_numeric_corruptions") == 1.0
        assert not numerics.quarantine_pending()  # not re-quarantined
        # deferred rechecks do NOT inflate "steps checked": steps 1..3
        # each completed once (+3); step 0's four partial attempts
        # (initial + three rechecks, rank 3 still missing) added nothing
        assert metrics.value("numerics_fingerprints_checked") == 3.0

    def test_deferred_partial_family_defers_norm_verdict(self):
        """Review hardening: a median over a PARTIAL record set must not
        indict a healthy rank (2 of 8 landed — one corrupt at 600, one
        healthy at 0.5 → median 300 puts the HEALTHY rank below
        med/factor, and _flagged would then mute the real culprit
        forever); the norm-relative verdict waits for the complete
        check, which names the true outlier."""
        import json

        store = _Store()
        numerics.configure(fingerprint=True, kv=store)
        with _world(8):
            for r, n in {2: 600.0, 5: 0.5}.items():
                store.put(
                    numerics.fingerprint_key(0, r),
                    json.dumps(
                        {"step": 0, "finite": 1, "norm": n}).encode())
            assert numerics.cross_check_fingerprints(0) is None
            assert not numerics.quarantine_pending()  # nobody misjudged
            for r in range(8):
                if r in (2, 5):
                    continue
                store.put(
                    numerics.fingerprint_key(0, r),
                    json.dumps(
                        {"step": 0, "finite": 1, "norm": 0.5}).encode())
            found = numerics.cross_check_fingerprints(0)
        assert found is not None and [f["rank"] for f in found] == [2]
        assert numerics.take_corrupt_ranks() == [2]

    def test_exhausted_budget_partial_family_never_convicts(self):
        """Review hardening: when the deferral budget runs out with only
        a sliver of the family landed (flaky KV), the norm-relative
        verdict must STAY silent — a 2-record "majority" of an 8-rank
        world has a partial median that can indict the healthy rank.
        Norm-relative verdicts require every expected record; only
        family-independent non-finite verdicts run on a partial set."""
        import json

        store = _Store()
        numerics.configure(fingerprint=True, kv=store)
        with _world(8):
            for r, n in {2: 0.5, 5: 600.0}.items():
                store.put(
                    numerics.fingerprint_key(0, r),
                    json.dumps(
                        {"step": 0, "finite": 1, "norm": n}).encode())
            # burn the whole retry budget and one exhausted check on top
            for _ in range(numerics.PENDING_CHECK_ATTEMPTS + 1):
                assert numerics.cross_check_fingerprints(0) is None
        assert not numerics.quarantine_pending()
        assert health.health_state() == HealthState.HEALTHY

    def test_claimed_boundary_silences_instrumented_hook(self):
        """Review hardening: once the elastic wrapper claims the
        boundary, InstrumentedStep's set_step hook must not publish —
        two hooks with diverged counters double-publish every step."""
        store = _Store()
        numerics.configure(fingerprint=True, kv=store)
        numerics.claim_boundary()
        numerics.set_step(0)
        numerics.set_step(1)  # would publish boundary(0) if not claimed
        assert store.get(numerics.fingerprint_key(0, 0)) is None
        with _world(2):
            numerics.boundary(0)  # the owner still publishes
        assert store.get(numerics.fingerprint_key(0, 0)) is not None

    def test_boundary_noop_when_disabled(self):
        numerics.configure(fingerprint=False)
        assert numerics.boundary(0) is None
        numerics.set_step(1)  # must not publish anything either
        assert numerics._store().get(numerics.fingerprint_key(0, 0)) is None

    def test_multi_device_process_publishes_owned_device_ranks(self):
        """Pass-5 hardening: with several devices per process (a 2-host
        × 4-chip topology) each process publishes one record per OWNED
        device rank, indexed by DEVICE rank — keying by process rank
        misattributed a corrupt chip's norm to the wrong record and left
        the cross-check scanning process-rank keys."""
        import json
        from unittest import mock

        from horovod_tpu import basics

        store = _Store()
        numerics.configure(fingerprint=True, kv=store)
        rec = {"step": 0, "finite": 1, "norm": 1.0, "per_dtype": {},
               "rank_norms": [float(r) + 1.0 for r in range(8)]}

        def _proc(prank):
            return [
                mock.patch.object(
                    basics, "is_initialized", return_value=True),
                mock.patch.object(basics, "size", return_value=8),
                mock.patch.object(basics, "process_size", return_value=2),
                mock.patch.object(
                    basics, "process_rank", return_value=prank),
            ]

        ps = _proc(1)
        for p in ps:
            p.start()
        try:
            numerics.publish_fingerprint(0, dict(rec))
        finally:
            for p in ps:
                p.stop()
        # process 1 owns device ranks 4..7 and publishes exactly those,
        # each carrying ITS OWN pre-reduction norm
        for r in range(4):
            assert store.get(numerics.fingerprint_key(0, r)) is None
        for r in range(4, 8):
            got = json.loads(store.get(numerics.fingerprint_key(0, r)))
            assert got["norm"] == float(r) + 1.0
        ps = _proc(0)
        for p in ps:
            p.start()
        try:
            numerics.publish_fingerprint(0, dict(rec))
            # rank 0 cross-checks all 8 DEVICE ranks, not 2 process ranks
            assert numerics.cross_check_fingerprints(0) is None
        finally:
            for p in ps:
                p.stop()
        assert metrics.value("numerics_fingerprints_checked") == 1.0

    def test_corrupt_charge_consumed_by_owning_process_only(self):
        """The grad_corrupt_rank victim is a DEVICE rank: only the
        process that owns it perturbs (and consumes the charge); other
        processes leave it armed."""
        import json
        from unittest import mock

        from horovod_tpu import basics

        store = _Store()
        numerics.configure(fingerprint=True, kv=store)
        chaos.configure("grad_corrupt_rank=5:0")

        def _publish(prank):
            ps = [
                mock.patch.object(
                    basics, "is_initialized", return_value=True),
                mock.patch.object(basics, "size", return_value=8),
                mock.patch.object(basics, "process_size", return_value=2),
                mock.patch.object(
                    basics, "process_rank", return_value=prank),
            ]
            for p in ps:
                p.start()
            try:
                numerics.publish_fingerprint(0)
            finally:
                for p in ps:
                    p.stop()

        _publish(0)  # device rank 5 belongs to process 1, not 0
        assert chaos.grad_corrupt() == (5, 0)  # still armed
        _publish(1)
        assert chaos.grad_corrupt() is None  # consumed by the owner
        rec = json.loads(store.get(numerics.fingerprint_key(0, 5)))
        assert rec["finite"] == 0

    def test_release_boundary_restores_instrumented_hook(self):
        """Review hardening: a driver's boundary claim must be released
        when its run ends — a later standalone InstrumentedStep loop in
        the same process otherwise silently publishes nothing."""
        store = _Store()
        numerics.configure(fingerprint=True, kv=store)
        numerics.claim_boundary()
        numerics.set_step(0)
        numerics.set_step(1)
        assert store.get(numerics.fingerprint_key(0, 0)) is None
        numerics.release_boundary()
        numerics.set_step(2)  # publishes boundary(1) again
        assert store.get(numerics.fingerprint_key(1, 0)) is not None

    def test_impossible_corrupt_charge_warns_loudly(self, caplog):
        """Review hardening: grad_corrupt_rank=0 (the driver) or an
        out-of-range rank can never fire in a multi-rank world — warn
        loudly once instead of silently injecting nothing."""
        import logging

        store = _Store()
        numerics.configure(fingerprint=True, kv=store)
        chaos.configure("grad_corrupt_rank=0:0")
        with _world(4), caplog.at_level(
                logging.WARNING,
                logger="horovod_tpu.resilience.numerics"):
            numerics.publish_fingerprint(0)
            numerics.publish_fingerprint(1)
        assert chaos.grad_corrupt() == (0, 0)  # armed, nothing fired
        hits = [r for r in caplog.records
                if "can never fire" in r.getMessage()]
        assert len(hits) == 1  # loud, and only once

    def test_multiprocess_corrupt_rank0_never_perturbed(self):
        """Review hardening: the MULTI-PROCESS branch must honor the
        never-rank-0 invariant too — process 0 perturbing its own record
        would quarantine the un-evictable driver and gate publication
        forever."""
        import json
        from unittest import mock

        from horovod_tpu import basics

        store = _Store()
        numerics.configure(fingerprint=True, kv=store)
        chaos.configure("grad_corrupt_rank=0:0")
        ps = [
            mock.patch.object(basics, "is_initialized", return_value=True),
            mock.patch.object(basics, "size", return_value=8),
            mock.patch.object(basics, "process_size", return_value=2),
            mock.patch.object(basics, "process_rank", return_value=0),
        ]
        for p in ps:
            p.start()
        try:
            numerics.publish_fingerprint(0)
        finally:
            for p in ps:
                p.stop()
        assert chaos.grad_corrupt() == (0, 0)  # still armed
        rec = json.loads(store.get(numerics.fingerprint_key(0, 0)))
        assert rec["finite"] == 1  # NOT perturbed

    def test_rank0_quarantine_keeps_gate_closed(self):
        """Review hardening: a corrupt rank the coordinator cannot evict
        (rank 0, the driver) must stay quarantined — draining it would
        re-open publication of a corrupt trainer's weights."""
        from unittest import mock

        from horovod_tpu.resilience import elastic as _elastic

        er = _elastic.ElasticRun(lambda w: (lambda s, i: s))
        er._alive = [0, 1, 2, 3]
        er._devices = [object()] * 4
        er._coord = mock.Mock()
        er._coord.alive.return_value = [0, 1, 2, 3]
        numerics.requeue_corrupt_ranks([0])
        er._poll_membership(0)  # no WorldChanged, nothing evicted
        er._coord.mark_dead.assert_not_called()
        assert numerics.quarantine_pending()  # gate stays closed
        assert numerics.publish_gate_reason(
            None, {"w": np.ones(2)}) == "quarantine"
        er._poll_membership(1)  # idempotent: still gated, still no evict
        er._coord.mark_dead.assert_not_called()
        assert numerics.quarantine_pending()

    def test_evict_failure_requeues_quarantine(self):
        """Review hardening: a transient KV failure in mark_dead must
        not drain the verdict — the publish gate keys on
        quarantine_pending(), so a drained-but-unevicted rank would
        re-open publication from a fleet that still contains it. The
        eviction retries at the next boundary sweep."""
        from unittest import mock

        from horovod_tpu.resilience import elastic as _elastic

        er = _elastic.ElasticRun(lambda w: (lambda s, i: s))
        er._alive = [0, 1, 2, 3]
        er._devices = [object()] * 4
        er._coord = mock.Mock()
        er._coord.alive.return_value = [0, 1, 2, 3]
        er._coord.mark_dead.side_effect = OSError("kv down")
        numerics.requeue_corrupt_ranks([2])
        er._poll_membership(0)
        assert numerics.quarantine_pending()  # verdict preserved
        assert numerics.publish_gate_reason(
            None, {"w": np.ones(2)}) == "quarantine"
        # the KV heals: the next sweep evicts and drains the quarantine
        er._coord.mark_dead.side_effect = None
        er._poll_membership(1)
        er._coord.mark_dead.assert_called_with(2)
        assert not numerics.quarantine_pending()

    def test_instrumented_step_standalone_publishes_real_record(self):
        """Pass-5 hardening: an InstrumentedStep loop WITHOUT the
        elastic wrapper (nobody runs note_step) must publish each step's
        real verdict at the next boundary, not the 0.0-norm default."""
        import json

        from horovod_tpu import training

        store = _Store()
        numerics.configure(fingerprint=True, kv=store)
        tx = numerics.guard(optax.sgd(0.1))

        def step(params, opt_state, i):
            u, st = tx.update(_g(2.0), opt_state, params)
            return optax.apply_updates(params, u), st

        wrapped = training.InstrumentedStep(step)
        p, st = _params(), tx.init(_params())
        for i in range(3):
            p, st = wrapped(p, st, i)
        numerics.boundary(2)  # flush the final step
        for s in range(3):
            rec = json.loads(store.get(numerics.fingerprint_key(s, 0)))
            assert rec["step"] == s
            assert rec["norm"] == pytest.approx(4.0)  # ||2.0 * ones(4)||


class _Store:
    """Minimal put/get KV (the sanitizer _LocalStore surface)."""

    def __init__(self):
        self._d = {}

    def put(self, key, value, ttl=None):
        self._d[key] = value

    def get(self, key):
        return self._d.get(key)


class _world:
    """Pretend basics.is_initialized()/size() report an n-rank world
    without bringing up a mesh (fingerprint-plane unit tests)."""

    def __init__(self, n):
        self.n = n

    def __enter__(self):
        from unittest import mock

        from horovod_tpu import basics

        self._p = [
            mock.patch.object(basics, "is_initialized", return_value=True),
            mock.patch.object(basics, "size", return_value=self.n),
            mock.patch.object(basics, "process_rank", return_value=0),
            mock.patch.object(basics, "process_size", return_value=1),
        ]
        for p in self._p:
            p.start()
        return self

    def __exit__(self, *exc):
        for p in self._p:
            p.stop()
        return False


# ------------------------------------------- checkpoint + emergency gating


@pytest.mark.numerics
class TestCheckpointFiniteness:
    def test_is_valid_checkpoint_rejects_nonfinite(self, tmp_path):
        from horovod_tpu import checkpoint as ckpt

        d = str(tmp_path)
        ckpt.save(d, 1, {"w": np.ones(4, np.float32)})
        ckpt.save(d, 2, {"w": np.array([1, np.nan, 3, 4], np.float32)})
        assert ckpt.is_valid_checkpoint(os.path.join(d, "step_1"))
        assert not ckpt.is_valid_checkpoint(os.path.join(d, "step_2"))
        # resume falls back to the newest VALID (finite) checkpoint
        assert ckpt.latest_step(d) == 1
        assert ckpt.valid_steps(d) == [1]

    def test_finite_check_env_optout(self, tmp_path, monkeypatch):
        """A state that LEGITIMATELY carries non-finite leaves (an
        additive -inf attention-mask buffer) must not invalidate every
        checkpoint the run writes: HOROVOD_CHECKPOINT_FINITE_CHECK=0
        opts the poison sweep out while CRC validation still runs."""
        from horovod_tpu import checkpoint as ckpt

        d = str(tmp_path)
        ckpt.save(d, 1, {"mask": np.full(4, -np.inf, np.float32),
                         "w": np.ones(2, np.float32)})
        assert not ckpt.is_valid_checkpoint(os.path.join(d, "step_1"))
        monkeypatch.setenv(numerics.CKPT_FINITE_ENV, "0")
        assert ckpt.is_valid_checkpoint(os.path.join(d, "step_1"))
        assert ckpt.latest_step(d) == 1

    def test_all_nonfinite_escalates_loudly(self, tmp_path, caplog):
        """Review hardening: when EVERY checkpoint is rejected solely by
        the finiteness sweep, that is a config problem (a model that
        legitimately stores non-finite leaves invalidates everything it
        writes) — resume names the escape hatch at ERROR instead of
        silently restarting from scratch."""
        import logging

        from horovod_tpu import checkpoint as ckpt

        d = str(tmp_path)
        ckpt.save(d, 1, {"m": np.full(2, -np.inf, np.float32)})
        ckpt.save(d, 2, {"m": np.array([np.nan, 1.0], np.float32)})
        with caplog.at_level(logging.ERROR, logger="horovod_tpu"):
            assert ckpt.valid_steps(d) == []
            assert ckpt.latest_step(d) is None
        loud = [r for r in caplog.records
                if "HOROVOD_CHECKPOINT_FINITE_CHECK=0" in r.getMessage()]
        assert len(loud) == 2  # once per walk, not per checkpoint

    def test_mixed_corruption_does_not_blame_the_sweep(self, tmp_path,
                                                       caplog):
        """A directory holding torn archives alongside non-finite ones is
        real corruption territory — the config-problem escalation must
        not fire and point the operator at the wrong knob."""
        import logging

        from horovod_tpu import checkpoint as ckpt

        d = str(tmp_path)
        ckpt.save(d, 1, {"m": np.array([np.nan], np.float32)})
        ckpt.save(d, 2, {"m": np.ones(2, np.float32)})
        with open(os.path.join(d, "step_2", "arrays.npz"), "wb") as f:
            f.write(b"torn")
        with caplog.at_level(logging.ERROR, logger="horovod_tpu"):
            assert ckpt.latest_step(d) is None
        assert not [r for r in caplog.records
                    if "FINITE_CHECK" in r.getMessage()]

    def test_finite_optout_streams_without_materializing(self, tmp_path,
                                                         monkeypatch):
        """Review hardening: with HOROVOD_CHECKPOINT_FINITE_CHECK=0 only
        the streaming CRC check runs — validation must not np.load a
        multi-GB member onto a small-RAM resume host."""
        from horovod_tpu import checkpoint as ckpt

        d = str(tmp_path)
        ckpt.save(d, 1, {"w": np.ones(8, np.float32)})
        monkeypatch.setenv(numerics.CKPT_FINITE_ENV, "0")

        def boom(*a, **k):
            raise AssertionError("np.load materialized a member")

        monkeypatch.setattr(ckpt.np, "load", boom)
        assert ckpt.is_valid_checkpoint(os.path.join(d, "step_1"))
        # a torn archive still fails the streamed CRC
        with open(os.path.join(d, "step_1", "arrays.npz"), "r+b") as f:
            f.truncate(40)
        assert not ckpt.is_valid_checkpoint(os.path.join(d, "step_1"))

    def test_integer_and_object_leaves_unaffected(self, tmp_path):
        from horovod_tpu import checkpoint as ckpt

        d = str(tmp_path)
        ckpt.save(d, 3, {"i": np.arange(4), "s": "meta", "f": np.ones(2)})
        assert ckpt.latest_step(d) == 3
        out = ckpt.restore(d, 3)
        assert out["s"] == "meta"

    def test_emergency_checkpoint_skips_nonfinite_state(self, tmp_path):
        """The live state going NaN right before a preemption must NOT
        displace the newest valid checkpoint."""
        from horovod_tpu import checkpoint as ckpt

        d = str(tmp_path / "ck")

        def step_fn(st, i):
            if i == 2:
                return {"w": st["w"] * np.nan}
            return {"w": st["w"] + 1}

        chaos.configure("sigterm_at_step=3")
        with pytest.raises(loop.Preempted) as ei:
            loop.run(step_fn, {"w": np.zeros(2)}, num_steps=6,
                     checkpoint_dir=d, checkpoint_every=2)
        assert ei.value.step == 3
        assert ei.value.checkpoint_path is None  # nothing was written
        # the periodic step-2 checkpoint (still finite) is the newest valid
        assert ckpt.latest_step(d) == 2
        assert metrics.value(
            "resilience_emergency_checkpoint_skipped") == 1.0

    def test_emergency_checkpoint_still_written_when_finite(self, tmp_path):
        from horovod_tpu import checkpoint as ckpt

        d = str(tmp_path / "ck")
        chaos.configure("sigterm_at_step=2")
        with pytest.raises(loop.Preempted):
            loop.run(lambda st, i: {"w": st["w"] + 1}, {"w": np.zeros(2)},
                     num_steps=5, checkpoint_dir=d)
        assert ckpt.latest_step(d) == 2
        assert metrics.value(
            "resilience_emergency_checkpoint_skipped") is None


# ------------------------------------------------------ publish gate


@pytest.mark.numerics
@pytest.mark.serving
class TestPublishGate:
    def _pub(self):
        from horovod_tpu.run.rendezvous import KVStoreServer
        from horovod_tpu.serving import WeightPublisher

        s = KVStoreServer()
        return s, WeightPublisher(s, publish_every=0, register=False)

    def test_nonfinite_tree_rejected(self):
        from horovod_tpu.serving import PublishRejected

        s, pub = self._pub()
        try:
            pub.publish({"params": {"w": np.ones(4, np.float32)}}, 1)
            with pytest.raises(PublishRejected) as ei:
                pub.publish(
                    {"params": {"w": np.array([np.nan], np.float32)}}, 2)
            assert ei.value.reason == "nonfinite"
            assert pub.generation == 1
            assert metrics.value(
                "serving_publish_rejected", reason="nonfinite") == 1.0
        finally:
            s.close()

    def test_quarantine_blocks_until_cleared(self):
        from horovod_tpu.serving import PublishRejected

        s, pub = self._pub()
        try:
            numerics._quarantine.add(5)
            with pytest.raises(PublishRejected) as ei:
                pub.publish({"params": {"w": np.ones(2, np.float32)}}, 1)
            assert ei.value.reason == "quarantine"
            numerics.clear_quarantine()
            assert pub.publish(
                {"params": {"w": np.ones(2, np.float32)}}, 1) == 1
        finally:
            s.close()

    def test_gate_disabled_by_env(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_PUBLISH_NUMERICS_GATE", "0")
        s, pub = self._pub()
        try:
            assert pub.publish(
                {"params": {"w": np.array([np.nan], np.float32)}}, 1) == 1
        finally:
            s.close()

    def test_spike_mid_publish_keeps_subscriber_on_last_healthy(self):
        """Acceptance: a grad_spike marking the trainer's step BAD makes
        the publisher reject the next generation; the subscriber's view
        still matches the last healthy commit; publication resumes once
        the streak clears."""
        from horovod_tpu.serving import PublishRejected, WeightSubscriber

        s, pub = self._pub()
        try:
            tx = numerics.guard(optax.sgd(0.1), warmup=1, spike_factor=5.0)
            p = {"w": jnp.ones(4, jnp.float32)}
            st = tx.init(p)
            for _ in range(3):
                u, st = tx.update(_g(0.5), st, p)
                p = optax.apply_updates(p, u)
            state = {"params": p, "opt_state": st}
            assert pub.publish(state, 3) == 1
            sub = WeightSubscriber(s, scope=pub.scope)
            assert sub.poll() is not None
            np.testing.assert_array_equal(
                np.asarray(sub.weights()["w"]),
                np.asarray(pub.reconstruction()["w"]))
            healthy = np.asarray(sub.weights()["w"]).copy()

            # the spike: step goes BAD, update skipped, streak = 1
            u, st = tx.update(_g(500.0), st, p)
            p = optax.apply_updates(p, u)
            state = {"params": p, "opt_state": st}
            assert numerics.verdict(st)["bad_streak"] == 1
            with pytest.raises(PublishRejected) as ei:
                pub.publish(state, 4)
            assert ei.value.reason == "bad_step"
            sub.poll()
            assert sub.generation == 1  # still the last healthy commit
            np.testing.assert_array_equal(
                np.asarray(sub.weights()["w"]), healthy)
            assert metrics.value(
                "serving_publish_rejected", reason="bad_step") == 1.0

            # streak clears -> publication resumes
            u, st = tx.update(_g(0.5), st, p)
            p = optax.apply_updates(p, u)
            assert pub.publish({"params": p, "opt_state": st}, 5) == 2
            sub.poll()
            assert sub.generation == 2
        finally:
            s.close()


# --------------------------------------------------- in-step acceptance e2e


def _batch_for(step, n=48, epoch=0):
    rng = np.random.RandomState(1000 * epoch + step)
    x = rng.rand(n, 8).astype(np.float32)
    y = (x.sum(axis=1) > 4).astype(np.int64)
    return x, y


def _tiny_model():
    import flax.linen as nn

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            return nn.Dense(2)(x)

    return Tiny()


def _guarded_step(hvd, model):
    from horovod_tpu.training import make_shardmap_train_step, softmax_xent

    tx = hvd.DistributedOptimizer(
        optax.adam(1e-2), shard_optimizer=True,
        compression=Compression.fp16, error_feedback=True,
        numerics_guard=True)
    step = make_shardmap_train_step(
        model, tx, loss_fn=softmax_xent, shard_optimizer=True,
        instrument=False, donate=False)
    return tx, step


@pytest.mark.numerics
@pytest.mark.chaos
def test_grad_nan_step_skipped_bit_identical_and_trajectory_matches(hvd):
    """THE acceptance pin: under ``grad_nan_at_step=3`` the poisoned step
    leaves params AND error-feedback residuals bit-identical, training
    resumes, and the final trajectory matches a clean run that never saw
    the bad batch."""
    from horovod_tpu.training import replicate, shard_batch

    model = _tiny_model()
    params0 = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8)))["params"]

    def run(inject, batch_steps):
        chaos.configure("grad_nan_at_step=3" if inject else None)
        tx, step = _guarded_step(hvd, model)
        params = replicate(jax.tree_util.tree_map(jnp.array, params0))
        st = tx.init(params)
        snap = {}
        for i, bstep in enumerate(batch_steps):
            x, y = _batch_for(bstep)
            if inject and i == 3:
                snap["params"] = [
                    np.asarray(l).copy()
                    for l in jax.tree_util.tree_leaves(params)]
                snap["residual"] = {
                    k: np.asarray(v).copy()
                    for k, v in st.inner.residual.items()}
            params, _, st, loss = step(
                params, {}, st, shard_batch(x), shard_batch(y))
            numerics.note_step(i, st)
            if inject and i == 3:
                # bit-identical skip: params AND EF residuals untouched
                for a, b in zip(snap["params"],
                                jax.tree_util.tree_leaves(params)):
                    np.testing.assert_array_equal(a, np.asarray(b))
                for k, v in st.inner.residual.items():
                    np.testing.assert_array_equal(
                        snap["residual"][k], np.asarray(v))
                assert numerics.verdict(st)["last_bad"]
        return params, st

    p_chaos, st_chaos = run(True, [0, 1, 2, 3, 4, 5])
    v = numerics.verdict(st_chaos)
    assert v["bad_count"] == 1 and v["count"] == 6
    assert metrics.value(
        "resilience_chaos_injected", site="grad_nan_at_step") == 1.0

    # a clean run that never saw batch 3 lands on the same weights
    p_clean, _ = run(False, [0, 1, 2, 4, 5])
    for a, b in zip(jax.tree_util.tree_leaves(p_chaos),
                    jax.tree_util.tree_leaves(p_clean)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7)


@pytest.mark.numerics
def test_real_single_rank_corruption_localized_from_local_norms(hvd):
    """Review hardening (the big one): localization must work on REAL
    per-rank corruption, not just the chaos-perturbed record. One rank's
    batch shard carries NaN: the guard skips the step globally (the
    verdict is pmean-agreed), its gathered PRE-reduction local norms
    single out that rank, and the cross-check quarantines it alone —
    while a globally-bad step (every shard poisoned) quarantines NOBODY
    (majority-family rule: no healthy family to deviate from)."""
    from horovod_tpu.training import replicate, shard_batch

    model = _tiny_model()
    params0 = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8)))["params"]
    store = _Store()
    numerics.configure(fingerprint=True, kv=store)
    tx, step = _guarded_step(hvd, model)
    params = replicate(jax.tree_util.tree_map(jnp.array, params0))
    st = tx.init(params)
    n = hvd.size()

    def poisoned(ranks):
        x, y = _batch_for(0, n=6 * n)
        x = x.copy()
        per = x.shape[0] // n
        for r in ranks:
            x[r * per:(r + 1) * per] = np.nan
        return shard_batch(x), shard_batch(y)

    # step 0: only rank 5's shard is poisoned
    xs, ys = poisoned([5])
    params, _, st, _ = step(params, {}, st, xs, ys)
    v = numerics.note_step(0, st)
    assert v["last_bad"]  # globally agreed skip
    assert v["rank_norms"][5] == -1.0  # the local view singles out 5
    assert all(rn > 0 for i, rn in enumerate(v["rank_norms"]) if i != 5)
    found = numerics.boundary(0)
    assert found is not None and [f["rank"] for f in found] == [5]
    assert numerics.take_corrupt_ranks() == [5]

    # step 1: EVERY shard poisoned — a bad batch, not rank corruption
    xs, ys = poisoned(list(range(n)))
    params, _, st, _ = step(params, {}, st, xs, ys)
    v = numerics.note_step(1, st)
    assert v["last_bad"]
    assert all(rn == -1.0 for rn in v["rank_norms"])
    assert numerics.boundary(1) is None
    assert not numerics.quarantine_pending()  # no 8->1 mass eviction


@pytest.mark.numerics
def test_cross_check_defers_missing_peer_then_flags_late_record(hvd):
    """Review hardening: a peer whose fingerprint has not landed must be
    re-checked at later boundaries, not silently dropped — the corrupt
    rank is often the slow one."""
    import json

    store = _Store()
    numerics.configure(fingerprint=True, kv=store)
    with _world(4):
        # ranks 0-2 published; rank 3 (the slow, corrupt one) has not
        for r in range(3):
            store.put(
                numerics.fingerprint_key(0, r),
                json.dumps(
                    {"step": 0, "finite": 1, "norm": 1.0}).encode())
        assert numerics.cross_check_fingerprints(0) is None
        # next boundary: rank 3's corrupt record finally lands
        store.put(
            numerics.fingerprint_key(0, 3),
            json.dumps({"step": 0, "finite": 0, "norm": None}).encode())
        found = numerics.boundary(1)
    assert found is not None and found[0] == {
        "step": 0, "rank": 3, "norm": None, "finite": False,
        "median_norm": 1.0,
    }
    assert numerics.take_corrupt_ranks() == [3]


@pytest.mark.numerics
@pytest.mark.chaos
@pytest.mark.elastic
def test_grad_corrupt_rank_quarantined_and_evicted():
    """THE acceptance pin: under ``grad_corrupt_rank=5:4`` rank 5 is
    named within one step, goes SUSPECT, and is evicted via the elastic
    8→7 path."""
    import horovod_tpu as hvd
    from horovod_tpu.resilience import elastic

    chaos.configure("grad_corrupt_rank=5:4")
    hvd.init()
    try:
        out = elastic.run(
            lambda world: (lambda st, i: {"w": st["w"] + 1}),
            {"w": np.zeros(1)}, num_steps=8)
        assert hvd.size() == 7  # rank 5 evicted, no relaunch
        np.testing.assert_allclose(out["w"], 8.0)
        assert metrics.value("numerics_corrupt_ranks", rank=5) == 1.0
        assert metrics.value("resilience_numeric_corruptions") == 1.0
        assert metrics.value(
            "resilience_chaos_injected", site="grad_corrupt_rank") == 1.0
        assert metrics.value(
            "resilience_elastic_membership_changes", kind="shrink") == 1.0
        # SUSPECT was entered naming the rank (beats may have recovered it)
        assert metrics.value(
            "resilience_health_transitions",
            **{"from": "HEALTHY", "to": "SUSPECT"}) >= 1.0
    finally:
        hvd.shutdown()


@pytest.mark.numerics
@pytest.mark.elastic
def test_same_size_membership_change_rebuilds_step():
    """Review hardening: the step cache keys on MEMBERSHIP, not world
    size — a quarantine eviction landing on the same sweep as a chaos
    rejoin keeps the count but re-forms the mesh over a different device
    set, so the step must be rebuilt (and the boundary claim released
    when the run ends)."""
    import horovod_tpu as hvd
    from horovod_tpu.resilience import elastic

    chaos.configure("rank_fail=1,rank_fail_at_step=2,rank_join_at_step=5")
    builds = []
    hvd.init()
    try:
        def builder(world):
            builds.append(world)

            def step_fn(st, i):
                if i == 4:
                    # flagged here so step 5's sweep evicts rank 3 in
                    # the SAME boundary the failed rank 7 rejoins
                    numerics.requeue_corrupt_ranks([3])
                return {"w": st["w"] + 1}

            return step_fn

        out = elastic.run(builder, {"w": np.zeros(1)}, num_steps=8)
        np.testing.assert_allclose(out["w"], 8.0)
        # 8 -> 7 (rank 7 fails) -> 7 (rank 3 out, rank 7 back): the last
        # transition keeps the size but MUST rebuild the step
        assert builds == [8, 7, 7]
        assert numerics._external_boundary is False  # claim released
    finally:
        hvd.shutdown()


@pytest.mark.numerics
@pytest.mark.elastic
def test_bad_streak_rolls_back_with_fresh_data(monkeypatch):
    """K consecutive bad steps trigger a bounded rollback to the
    committed snapshot; the replay draws FRESH batches via the bumped
    replay epoch and completes."""
    import horovod_tpu as hvd
    from horovod_tpu.resilience import elastic
    from horovod_tpu.training import replicate, shard_batch

    monkeypatch.setenv("HOROVOD_NUMERICS_MAX_BAD", "2")
    model = _tiny_model()
    params0 = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8)))["params"]
    seen = []

    hvd.init()
    builds = []
    try:
        def builder(world):
            builds.append(world)
            tx, step = _guarded_step(hvd, model)

            def step_fn(state, i):
                epoch = numerics.replay_epoch()
                seen.append((i, epoch))
                x, y = _batch_for(i, epoch=epoch)
                if epoch == 0 and i >= 3:
                    x = x * np.nan  # a poisoned data shard
                p, _, st, _ = step(
                    state["params"], {}, state["opt_state"],
                    shard_batch(x), shard_batch(y))
                return {"params": p, "opt_state": st}

            return step_fn

        tx0, _ = _guarded_step(hvd, model)
        params = replicate(jax.tree_util.tree_map(jnp.array, params0))
        state = {"params": params, "opt_state": tx0.init(params)}
        out = elastic.run(builder, state, num_steps=6, snapshot_every=1)
        assert numerics.replay_epoch() == 1
        assert metrics.value("numerics_rollbacks") == 1.0
        # steps 3,4 went bad in epoch 0 -> rollback -> replay 3.. in epoch 1
        assert (3, 0) in seen and (4, 0) in seen and (3, 1) in seen
        assert numerics.tree_finite(out["params"])
        v = numerics.verdict(out["opt_state"])
        assert v["bad_streak"] == 0
        # pass-5 hardening: the rollback replays at the SAME world size,
        # so the compiled step is reused — not rebuilt (and recompiled)
        assert len(builds) == 1
    finally:
        hvd.shutdown()


@pytest.mark.numerics
@pytest.mark.elastic
def test_lagged_verdict_rolls_back_with_sparse_commits(monkeypatch):
    """Review hardening: with snapshot_every > 1 the elastic wrapper
    reads the guard verdict LAGGED on non-commit boundaries (staged
    async copy — the synchronous per-step device→host read fenced every
    step of the hot loop). The bad-streak rollback still fires (one step
    late at most) and commits stay gated on an EXACT same-step verdict."""
    import horovod_tpu as hvd
    from horovod_tpu.resilience import elastic
    from horovod_tpu.training import replicate, shard_batch

    monkeypatch.setenv("HOROVOD_NUMERICS_MAX_BAD", "2")
    model = _tiny_model()
    params0 = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8)))["params"]
    seen = []

    hvd.init()
    try:
        def builder(world):
            tx, step = _guarded_step(hvd, model)

            def step_fn(state, i):
                epoch = numerics.replay_epoch()
                seen.append((i, epoch))
                x, y = _batch_for(i, epoch=epoch)
                if epoch == 0 and i >= 3:
                    x = x * np.nan
                p, _, st, _ = step(
                    state["params"], {}, state["opt_state"],
                    shard_batch(x), shard_batch(y))
                return {"params": p, "opt_state": st}

            return step_fn

        tx0, _ = _guarded_step(hvd, model)
        params = replicate(jax.tree_util.tree_map(jnp.array, params0))
        state = {"params": params, "opt_state": tx0.init(params)}
        out = elastic.run(builder, state, num_steps=6, snapshot_every=4)
        assert numerics.replay_epoch() == 1
        assert metrics.value("numerics_rollbacks") == 1.0
        # bad steps 3,4 in epoch 0; the replay re-runs them with fresh data
        assert (3, 0) in seen and (3, 1) in seen
        assert numerics.tree_finite(out["params"])
        assert numerics.verdict(out["opt_state"])["bad_streak"] == 0
    finally:
        hvd.shutdown()


@pytest.mark.numerics
@pytest.mark.elastic
def test_rollback_budget_exhaustion_is_fatal(monkeypatch):
    """Bad steps that survive every replay (the data is poisoned in every
    epoch) exhaust the rollback budget: FATAL + NumericsError."""
    import horovod_tpu as hvd
    from horovod_tpu.resilience import elastic

    monkeypatch.setenv("HOROVOD_NUMERICS_MAX_BAD", "1")
    monkeypatch.setenv("HOROVOD_NUMERICS_MAX_ROLLBACKS", "1")
    model = _tiny_model()
    params0 = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8)))["params"]

    hvd.init()
    try:
        from horovod_tpu.training import replicate, shard_batch

        def builder(world):
            tx, step = _guarded_step(hvd, model)

            def step_fn(state, i):
                x, y = _batch_for(i)
                if i >= 1:
                    x = x * np.nan  # poisoned in EVERY epoch
                p, _, st, _ = step(
                    state["params"], {}, state["opt_state"],
                    shard_batch(x), shard_batch(y))
                return {"params": p, "opt_state": st}

            return step_fn

        tx0, _ = _guarded_step(hvd, model)
        params = replicate(jax.tree_util.tree_map(jnp.array, params0))
        state = {"params": params, "opt_state": tx0.init(params)}
        with pytest.raises(numerics.NumericsError):
            elastic.run(builder, state, num_steps=5, snapshot_every=1)
        assert health.health_state() == HealthState.FATAL
    finally:
        hvd.shutdown()


@pytest.mark.numerics
def test_jit_builder_loss_scaling_matches_unscaled(hvd):
    """make_jit_train_step with a guarded, loss-scaled optimizer: the
    loss is scaled inside the differentiated fn and the guard divides
    the grads back, so the trajectory matches the unguarded builder and
    the reported loss is the UNSCALED one."""
    from horovod_tpu.training import (
        make_jit_train_step, replicate, shard_batch, softmax_xent,
    )

    model = _tiny_model()
    params0 = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8)))["params"]

    def run(guarded):
        if guarded:
            tx = hvd.DistributedOptimizer(
                optax.adam(1e-2), numerics_guard=True, loss_scale=64.0)
        else:
            tx = hvd.DistributedOptimizer(optax.adam(1e-2))
        step = make_jit_train_step(
            model, tx, loss_fn=softmax_xent, instrument=False,
            donate=False)
        params = replicate(jax.tree_util.tree_map(jnp.array, params0))
        st = tx.init(params)
        for i in range(5):
            x, y = _batch_for(i)
            params, _, st, loss = step(
                params, {}, st, shard_batch(x), shard_batch(y))
        return params, float(loss), st

    p_g, l_g, st_g = run(True)
    p_u, l_u, _ = run(False)
    assert l_g == pytest.approx(l_u, rel=1e-4)  # reported loss unscaled
    for a, b in zip(jax.tree_util.tree_leaves(p_g),
                    jax.tree_util.tree_leaves(p_u)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6)
    v = numerics.verdict(st_g)
    assert v["loss_scale"] == 64.0 and v["bad_count"] == 0


# -------------------------------------------------- reshard / broadcast


@pytest.mark.numerics
def test_loss_scale_with_guard_disabled_raises(hvd):
    """Review hardening: loss_scale lives in the guard state; an explicit
    numerics_guard=False alongside it would silently train unscaled."""
    with pytest.raises(ValueError, match="loss_scale"):
        hvd.DistributedOptimizer(
            optax.sgd(0.1), numerics_guard=False, loss_scale="dynamic")


@pytest.mark.numerics
@pytest.mark.elastic
def test_rollback_budget_resets_on_sound_progress(monkeypatch):
    """Review hardening: the rollback budget guards against rollbacks
    WITHOUT sound progress — two isolated incidents, each fully recovered
    with committed steps in between, must both be survivable even with a
    budget of 1."""
    import horovod_tpu as hvd
    from horovod_tpu.resilience import elastic
    from horovod_tpu.training import replicate, shard_batch

    monkeypatch.setenv("HOROVOD_NUMERICS_MAX_BAD", "1")
    monkeypatch.setenv("HOROVOD_NUMERICS_MAX_ROLLBACKS", "1")
    model = _tiny_model()
    params0 = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8)))["params"]

    hvd.init()
    try:
        def builder(world):
            tx, step = _guarded_step(hvd, model)

            def step_fn(state, i):
                epoch = numerics.replay_epoch()
                x, y = _batch_for(i, epoch=epoch)
                # two isolated transient incidents: steps 2 and 6 are
                # poisoned only on their first serving (epoch-specific)
                if (i == 2 and epoch == 0) or (i == 6 and epoch == 1):
                    x = x * np.nan
                p, _, st, _ = step(
                    state["params"], {}, state["opt_state"],
                    shard_batch(x), shard_batch(y))
                return {"params": p, "opt_state": st}

            return step_fn

        tx0, _ = _guarded_step(hvd, model)
        params = replicate(jax.tree_util.tree_map(jnp.array, params0))
        state = {"params": params, "opt_state": tx0.init(params)}
        out = elastic.run(builder, state, num_steps=9, snapshot_every=1)
        assert metrics.value("numerics_rollbacks") == 2.0
        assert numerics.tree_finite(out["params"])
    finally:
        hvd.shutdown()


@pytest.mark.numerics
def test_tree_finite():
    assert numerics.tree_finite({"a": np.ones(3), "b": "meta", "c": 7})
    assert not numerics.tree_finite({"a": np.array([1.0, np.inf])})
    assert not numerics.tree_finite(
        {"a": {"b": jnp.array([np.nan], jnp.float32)}})
    # integer arrays cannot be non-finite
    assert numerics.tree_finite({"i": np.arange(5)})


# ------------------------------------------------------- CI/tooling guards


def test_every_chaos_charge_documented_in_fault_tolerance_table():
    """Tier-1 guard (satellite): every HOROVOD_CHAOS charge name parsed
    in chaos.py must appear in docs/fault_tolerance.md's chaos table —
    the drill catalog cannot silently drift from the harness (the same
    pattern as the PR-7 metric-catalog guard)."""
    keys = set(
        chaos._COUNT_KEYS + chaos._FLOAT_KEYS + chaos._INT_KEYS
        + chaos._STRUCT_KEYS
    )
    assert len(keys) >= 14, "suspiciously few chaos charges parsed"
    with open(os.path.join(_REPO, "docs", "fault_tolerance.md")) as f:
        doc = f.read()
    missing = sorted(k for k in keys if f"`{k}" not in doc)
    assert not missing, (
        "chaos charges parsed in chaos.py but absent from the "
        f"docs/fault_tolerance.md chaos table: {missing}"
    )


def test_numerics_env_knobs_documented():
    """Every HOROVOD_NUMERICS_* env knob the module defines appears in
    the docs (fault_tolerance.md or troubleshooting.md)."""
    knobs = sorted(
        v for k, v in vars(numerics).items()
        if k.endswith("_ENV") and isinstance(v, str)
        and v.startswith("HOROVOD_")
    )
    docs = ""
    for name in ("fault_tolerance.md", "troubleshooting.md", "serving.md"):
        with open(os.path.join(_REPO, "docs", name)) as f:
            docs += f.read()
    missing = [k for k in knobs if k not in docs]
    assert not missing, f"undocumented numerics env knobs: {missing}"
