"""Serving hot-path (ISSUE 18): automatic prefix caching, speculative
decoding, and prefix-affinity fleet routing.

The acceptance pins:

- prefix-hit and speculative outputs are BIT-identical to ``generate()``
  for ragged batches with mid-flight joins — caching and speculation are
  pure memory/scheduling optimisations, never sampling changes;
- measured prefill-token savings and draft proposal/acceptance counts
  match the analytic ``tools/scaling_projection.py`` models EXACTLY on
  deterministic A/B workloads (a full-depth draft accepts 100% by
  construction);
- a page-aliasing churn soak never strands or double-frees a refcount,
  never mutates a shared page, and never leaks stale KV through a
  recycled page;
- the ``cache_evict_at_pass`` chaos charge forces victims to re-prefill
  with tokens bit-identical to the uninterrupted run;
- the fleet router prefers cache-warm replicas only BELOW the
  staleness/backpressure tiers.

Tier-1: deterministic, no sleeps; ``serving`` marker.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402

from horovod_tpu.models.transformer import TransformerLM  # noqa: E402
from horovod_tpu.observability import metrics, reqtrace  # noqa: E402
from horovod_tpu.resilience import chaos, health  # noqa: E402
from horovod_tpu.run.rendezvous import KVStoreServer  # noqa: E402
from horovod_tpu.serving import (  # noqa: E402
    GenerationRollout,
    InferenceEngine,
    WeightPublisher,
    WeightSubscriber,
)
from horovod_tpu.serving.scheduler import (  # noqa: E402
    PrefixCache,
    Request,
    prefix_digests,
)

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


def _engine(model, params, *, generation=1, **kw):
    kw.setdefault("page_size", 8)
    kw.setdefault("num_pages", 64)
    kw.setdefault("max_batch", 4)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("max_seq_len", 48)
    eng = InferenceEngine(model, **kw)
    eng.set_weights(params, generation=generation)
    return eng


def _serve(eng, prompts, max_new, tag, **kw):
    reqs = [eng.submit(p, max_new, rid=f"{tag}-{i}", **kw)
            for i, p in enumerate(prompts)]
    eng.run_until_idle()
    return [np.asarray(r.generated) for r in reqs], reqs


# -------------------------------------------------------- digests + cache


class TestPrefixDigests:
    def test_chain_is_prefix_closed_and_content_keyed(self):
        rng = np.random.RandomState(0)
        p = rng.randint(1, 97, size=32).astype(np.int32)
        d32 = prefix_digests(p, 8)
        assert len(d32) == 4
        # a prompt sharing the first 16 tokens shares the first 2 digests
        q = np.concatenate([p[:16], rng.randint(1, 97, size=16)
                            ]).astype(np.int32)
        d_q = prefix_digests(q, 8)
        assert d_q[:2] == d32[:2] and d_q[2] != d32[2]
        # the chain keys CONTENT + POSITION: same block after a
        # different block hashes differently (no cross-prompt aliasing
        # of identical-but-shifted blocks)
        r = np.concatenate([p[8:16], p[8:16]]).astype(np.int32)
        d_r = prefix_digests(r, 8)
        assert d_r[0] != d32[1] and d_r[1] != d32[1]
        # partial trailing block contributes no digest
        assert len(prefix_digests(p[:19], 8)) == 2

    def test_cache_alignment_and_cap(self):
        c = PrefixCache(page_size=8, prefill_chunk=8)
        assert c.align_tokens == 8
        # the LAST prompt token must always prefill (it produces the
        # first-token logits): a fully-resident prompt still caps at
        # (len-1) // align pages
        assert c.max_hit_pages(16) == 1
        assert c.max_hit_pages(17) == 2
        assert c.max_hit_pages(8) == 0
        # lcm alignment: chunk 12 x page 8 -> hits in 24-token units
        c2 = PrefixCache(page_size=8, prefill_chunk=12)
        assert c2.align_tokens == 24 and c2.align_pages == 3
        assert c2.max_hit_pages(25) == 3
        assert c2.max_hit_pages(24) == 0

    def test_refcount_lru_and_acquire_pins(self):
        c = PrefixCache(page_size=8, prefill_chunk=8)
        assert c.insert(1, "a", 10) and c.insert(1, "b", 11)
        assert not c.insert(1, "a", 12)  # duplicate content
        assert c.evictable() == 2
        c.acquire([10])
        assert c.evictable() == 1  # pinned pages never evict
        assert c.evict(5) == [11]
        c.release([10])
        assert c.evict(5) == [10]
        assert c.resident_pages() == 0

    def test_lookup_is_longest_resident_run(self):
        c = PrefixCache(page_size=8, prefill_chunk=8)
        c.insert(1, "a", 10)
        c.insert(1, "c", 12)
        assert c.lookup(1, ["a", "b", "c"]) == [10]  # stops at the hole
        assert c.lookup(2, ["a"]) == []  # namespaced: other generation


# ------------------------------------------------------------- engine hits


class TestPrefixCacheParity:
    def test_warm_pass_bit_identical_with_exact_prefill_savings(self):
        from tools.scaling_projection import prefix_prefill_flops

        model = _model()
        params = _params(model)
        lens = (19, 8, 27, 12, 33)
        prompts = _ragged_prompts(3, lens)
        eng = _engine(model, params, prefix_cache=True)
        cold, _ = _serve(eng, prompts, 8, "cold")
        t_cold = metrics.value("serving_prefill_tokens")
        assert t_cold == sum(lens)
        warm, _ = _serve(eng, prompts, 8, "warm")
        for a, b in zip(warm, cold):
            np.testing.assert_array_equal(a, b)
        m = prefix_prefill_flops(list(lens), list(lens), page_size=8,
                                 prefill_chunk=8)
        assert metrics.value("serving_prefill_tokens") - t_cold \
            == m["cached_prefill_tokens"]
        assert m["saved_tokens"] > 0
        assert metrics.value("serving_prefix_hits") == sum(
            1 for h in m["hit_tokens_per_request"] if h)
        assert metrics.value("serving_prefix_pages_shared") is None \
            or metrics.value("serving_prefix_pages_shared") == 0  # idle

    def test_mid_flight_joins_hit_and_stay_identical(self):
        model = _model()
        params = _params(model)
        prompts = _ragged_prompts(7, (21, 9, 26, 17))
        eng = _engine(model, params, prefix_cache=True)
        base, _ = _serve(eng, prompts, 8, "cold")
        # resubmit with STAGGERED joins: two up front, two joining while
        # the first pair is mid-decode — hits alias live-traffic pages
        reqs = [eng.submit(p, 8, rid=f"j{i}")
                for i, p in enumerate(prompts[:2])]
        for _ in range(4):
            eng.step()
        reqs += [eng.submit(p, 8, rid=f"j{i+2}")
                 for i, p in enumerate(prompts[2:])]
        eng.run_until_idle()
        for r, want in zip(reqs, base):
            np.testing.assert_array_equal(np.asarray(r.generated), want)
        assert metrics.value("serving_prefix_hits") >= 3  # len-9 misses

    def test_prefix_cache_off_never_indexes(self):
        model = _model(depth=1)
        params = _params(model)
        prompts = _ragged_prompts(1, (17, 17))
        eng = _engine(model, params, prefix_cache=False)
        _serve(eng, prompts, 4, "a")
        _serve(eng, prompts, 4, "b")
        assert eng.scheduler.cached_page_count() == 0
        assert metrics.value("serving_prefix_hits") is None

    def test_generation_namespace_isolates_hits(self):
        """New weights must never serve KV computed by old weights: the
        index is keyed by generation, so a bump turns hits to misses."""
        model = _model(depth=1)
        params = _params(model)
        prompts = _ragged_prompts(2, (19,))
        eng = _engine(model, params, prefix_cache=True)
        base, _ = _serve(eng, prompts, 6, "g1")
        eng.set_weights(params, generation=2)
        warm, _ = _serve(eng, prompts, 6, "g2")
        np.testing.assert_array_equal(warm[0], base[0])  # same params
        assert metrics.value("serving_prefix_hits") is None
        assert metrics.value("serving_prefix_misses") == 2


# ------------------------------------------------------- admission credit


class TestAdmissionCredit:
    def test_fully_cached_prompt_admits_on_tight_pool_without_eviction(
            self):
        model = _model(depth=1)
        params = _params(model)
        prompt = _ragged_prompts(4, (24,))[0]
        # 5 allocatable pages; worst-case bill is 4 (24 prompt + 8 new)
        eng = _engine(model, params, num_pages=6, max_batch=1,
                      max_seq_len=32, prefix_cache=True)
        cold, _ = _serve(eng, [prompt], 8, "cold")
        assert eng.scheduler.cached_page_count() == 3  # full prompt pages
        assert eng.scheduler.free_page_count() == 2
        # worst 4 > free 2: only the 2-page prefix credit lets this in
        # without touching the LRU — no eviction may fire
        warm, _ = _serve(eng, [prompt], 8, "warm")
        np.testing.assert_array_equal(warm[0], cold[0])
        assert metrics.value("serving_prefix_hits") == 1
        assert metrics.value("serving_prefix_evictions") is None

    def test_backpressure_hint_scales_by_post_credit_reservation(self):
        model = _model(depth=1)
        params = _params(model)
        prompts = _ragged_prompts(9, (25, 25), vocab=97)
        eng = _engine(model, params, prefix_cache=True)
        _serve(eng, [prompts[0]], 6, "seed")  # caches 3 full pages
        sched = eng.scheduler
        # a real backlog (nothing stepped yet): the base hint is
        # queue-depth x TPOT, and only then can the credit bite
        backlog = [eng.submit(prompts[1], 6, rid=f"q{i}")
                   for i in range(4)]
        cached = Request("h-hit", prompts[0], 6)
        cold = Request("h-miss", prompts[1], 6)
        hinted = sched.backpressure_hint(cached)
        unhinted = sched.backpressure_hint(cold)
        eng.run_until_idle()
        assert all(r.error is None for r in backlog)
        assert hinted < unhinted  # credit shrinks the retry-after
        assert hinted > 0.0  # floored at one TPOT: it still needs a slot


# ---------------------------------------------------------- churn + aliasing


class TestAliasingChurnSoak:
    def _pool_invariants(self, eng):
        """Idle-engine page accounting: every page is exactly one of
        {free, cached-resident}; refcounts all zero; nothing stranded."""
        sched = eng.scheduler
        pc = sched._prefix
        free = set(sched._free_pages)
        resident = set(pc._key_of)
        assert not (free & resident), "page both free and cached"
        assert len(free) + len(resident) == eng.num_pages - 1, \
            "page leaked or double-freed"
        assert sched.pages_in_use() == 0
        assert all(v == 0 for v in pc._ref.values()), "stranded refcount"
        assert set(pc._lru) == resident, "LRU out of sync with index"

    def test_churn_soak_refcounts_cow_and_recycling(self):
        model = _model(depth=1)
        params = _params(model)
        rng = np.random.RandomState(11)
        # a TIGHT pool (11 allocatable, up to 10 held by live traffic) +
        # prompts sharing prefixes: every round mixes hits, misses,
        # LRU evictions under admission pressure, and page recycling
        eng = _engine(model, params, num_pages=12, max_batch=2,
                      max_seq_len=40, prefix_cache=True)
        stems = _ragged_prompts(12, (32, 32, 32))
        expected = {}
        for rnd in range(12):
            batch, rids = [], []
            for j in range(3):
                stem = stems[rng.randint(len(stems))]
                cut = int(rng.choice((9, 17, 25, 32)))
                p = stem[:cut]
                batch.append(p)
                rids.append(f"soak-{rnd}-{j}")
            # snapshot every cached page before the round, keyed by its
            # content digest: aliasing is copy-on-write by construction,
            # so a digest still mapped to the same page after the round
            # must hold byte-identical KV (an evicted page may be
            # recycled under a NEW digest — that is reuse, not mutation)
            pc = eng.scheduler._prefix
            mapping = dict(pc._by_key)
            resident = sorted(pc._key_of)
            before = {
                p: [np.asarray(leaf)[p]
                    for leaf in jax.tree_util.tree_leaves(eng._cache)]
                for p in resident}
            reqs = [eng.submit(p, 6, rid=r) for p, r in zip(batch, rids)]
            eng.run_until_idle()
            for p, r in zip(batch, reqs):
                key = p.tobytes()
                got = np.asarray(r.generated)
                if key not in expected:
                    expected[key] = got
                # recycled pages never leak stale KV: a repeat prompt
                # decodes bit-identically regardless of churn history
                np.testing.assert_array_equal(got, expected[key])
            leaves = jax.tree_util.tree_leaves(eng._cache)
            for key, page in mapping.items():
                if pc._by_key.get(key) != page:
                    continue  # evicted (and maybe recycled) — not shared
                for leaf, old in zip(leaves, before[page]):
                    np.testing.assert_array_equal(
                        np.asarray(leaf)[page], old)
            self._pool_invariants(eng)
        assert metrics.value("serving_prefix_hits", ) > 0
        assert metrics.value("serving_prefix_evictions") > 0  # pool churned


# ------------------------------------------------------- speculative decode


class TestSpeculativeDecoding:
    def test_full_depth_draft_pins_counters_and_parity(self):
        from tools.scaling_projection import spec_decode_tokens

        model = _model()
        params = _params(model)
        lens = (19, 8, 27, 12, 5)
        prompts = _ragged_prompts(3, lens)
        plain = _engine(model, params, prefix_cache=False)
        base, _ = _serve(plain, prompts, 10, "p")
        spec = _engine(model, params, prefix_cache=False,
                       draft_depth=model.depth, spec_lookahead=3)
        out, _ = _serve(spec, prompts, 10, "s")
        for a, b in zip(out, base):
            np.testing.assert_array_equal(a, b)
        # full-depth draft == target: acceptance is 100% and the
        # counters land EXACTLY on the analytic model
        m = spec_decode_tokens(10, 3, acceptance_rate=1.0,
                               n_requests=len(prompts))
        assert metrics.value("spec_proposed") == m["proposed"]
        assert metrics.value("spec_accepted") == m["accepted"]
        assert metrics.value("spec_rollbacks") is None

    def test_shallow_draft_parity_with_mid_flight_joins(self):
        model = _model()
        params = _params(model)
        prompts = _ragged_prompts(5, (21, 9, 26, 17, 6, 13))
        plain = _engine(model, params)
        base, _ = _serve(plain, prompts, 9, "p")
        spec = _engine(model, params, draft_depth=1, spec_lookahead=4)
        reqs = [spec.submit(p, 9, rid=f"s-{i}")
                for i, p in enumerate(prompts[:3])]
        for _ in range(5):
            spec.step()
        reqs += [spec.submit(p, 9, rid=f"s-{i+3}")
                 for i, p in enumerate(prompts[3:])]
        spec.run_until_idle()
        for r, want in zip(reqs, base):
            np.testing.assert_array_equal(np.asarray(r.generated), want)
        assert metrics.value("spec_proposed") > 0
        assert metrics.value("spec_rollbacks") > 0  # a 1-layer draft errs

    def test_spec_rides_prefix_cache_bit_identically(self):
        model = _model()
        params = _params(model)
        prompts = _ragged_prompts(8, (19, 25, 11))
        plain = _engine(model, params, prefix_cache=False)
        base, _ = _serve(plain, prompts, 10, "p")
        spec = _engine(model, params, prefix_cache=True,
                       draft_depth=1, spec_lookahead=3)
        cold, _ = _serve(spec, prompts, 10, "c")
        warm, _ = _serve(spec, prompts, 10, "w")
        for a, b, c in zip(warm, cold, base):
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(b, c)
        assert metrics.value("serving_prefix_hits") >= 2

    def test_sampled_rows_fall_back_to_plain_decode(self):
        model = _model(depth=1)
        params = _params(model)
        prompts = _ragged_prompts(6, (12, 15))
        plain = _engine(model, params)
        base, _ = _serve(plain, prompts, 8, "t", temperature=0.7)
        spec = _engine(model, params, draft_depth=1, spec_lookahead=3)
        out, _ = _serve(spec, prompts, 8, "t", temperature=0.7)
        # rid-seeded sampling: plain and spec engines draw identically
        # BECAUSE temperature rows never speculate
        for a, b in zip(out, base):
            np.testing.assert_array_equal(a, b)
        assert metrics.value("spec_proposed") is None

    def test_stale_draft_generation_fences_off_speculation(self):
        model = _model(depth=1)
        params = _params(model)
        prompts = _ragged_prompts(2, (14,))
        plain = _engine(model, params)
        base, _ = _serve(plain, prompts, 8, "p")
        spec = _engine(model, params, draft_depth=1, spec_lookahead=3)
        # overwrite the auto-derived draft with a STALE generation: the
        # fence must fall back to plain decode, not verify old proposals
        spec.set_draft_weights(spec._subset_draft_params(
            jax.device_get(params)), generation=99, arm="stable")
        out, _ = _serve(spec, prompts, 8, "s")
        np.testing.assert_array_equal(out[0], base[0])
        assert metrics.value("spec_proposed") is None
        assert metrics.value(
            "serving_engine_steps", kind="spec_verify") is None

    def test_draft_must_be_truncation_of_target(self):
        model = _model(depth=2)
        with pytest.raises(ValueError, match="draft"):
            InferenceEngine(model, page_size=8, num_pages=16, max_batch=1,
                            prefill_chunk=8, max_seq_len=16, draft_depth=3)
        other = _model(depth=1, dim=16, heads=2)
        eng = _engine(model, _params(model), num_pages=16, max_batch=1,
                      max_seq_len=16, draft_depth=1)
        with pytest.raises(ValueError, match="truncation"):
            eng.set_draft_weights(
                jax.device_get(_params(other)), generation=1)


# ------------------------------------------------------------- chaos drill


@pytest.mark.chaos
class TestCacheEvictChaos:
    def test_forced_eviction_revictims_reprefill_bit_identical(self):
        model = _model()
        params = _params(model)
        prompts = _ragged_prompts(3, (19, 8, 27, 12))
        eng = _engine(model, params, prefix_cache=True)
        base, _ = _serve(eng, prompts, 10, "b")
        # fire the charge a few passes into the WARM run: hits are
        # aliased and mid-decode, so the drill hits live victims
        chaos.configure(f"cache_evict_at_pass={eng._step_count + 6}")
        out, _ = _serve(eng, prompts, 10, "v")
        for a, b in zip(out, base):
            np.testing.assert_array_equal(a, b)
        assert metrics.value("resilience_chaos_injected",
                             site="cache_evict_at_pass") == 1.0
        assert metrics.value("serving_prefix_hits") == 3  # len-8 misses
        assert metrics.value("serving_prefix_evictions") > 0
        assert eng.scheduler.pages_in_use() == 0
        # the charge is consumed: an idle follow-up run stays clean
        again, _ = _serve(eng, prompts, 10, "w")
        for a, b in zip(again, base):
            np.testing.assert_array_equal(a, b)
        assert metrics.value("resilience_chaos_injected",
                             site="cache_evict_at_pass") == 1.0

    def test_reqtrace_attributes_cached_tokens_and_spec_counts(self):
        model = _model(depth=1)
        params = _params(model)
        prompts = _ragged_prompts(4, (19,))
        eng = _engine(model, params, prefix_cache=True,
                      draft_depth=1, spec_lookahead=3)
        seen = []

        def _obs(req, summary):
            seen.append(summary)

        reqtrace.add_completion_observer(_obs)
        try:
            _serve(eng, prompts, 8, "a")
            _serve(eng, prompts, 8, "b")
        finally:
            reqtrace.remove_completion_observer(_obs)
        recs = [s for s in seen if str(s["rid"]).startswith("b-")]
        assert recs and recs[0]["cached_tokens"] == 16
        assert recs[0]["spec_proposed"] >= 3
        assert recs[0]["spec_accepted"] >= 0
        cold = [s for s in seen if str(s["rid"]).startswith("a-")]
        assert cold[0]["cached_tokens"] == 0


# ----------------------------------------------------------- fleet affinity


class TestFleetPrefixAffinity:
    def _router(self, model, params, n=3):
        from horovod_tpu.serving.fleet import FleetRouter

        router = FleetRouter()
        for i in range(n):
            router.add_replica(f"r{i}", _engine(model, params))
        return router

    def test_warm_replica_wins_the_tie(self):
        model = _model(depth=1)
        params = _params(model)
        prompt = _ragged_prompts(5, (19,))[0]
        router = self._router(model, params)
        try:
            warm = router.replica("r1")
            warm.engine.submit(prompt, 4, rid="seed")
            warm.engine.run_until_idle()
            order = [r.index for r in router.candidates(prompt=prompt)]
            assert order[0] == 1  # affinity breaks the load tie
            # no prompt -> stable index order (affinity never invents load)
            assert [r.index for r in router.candidates()] == [0, 1, 2]
        finally:
            router.close()

    def test_affinity_is_demoted_below_staleness(self):
        model = _model(depth=1)
        params = _params(model)
        prompt = _ragged_prompts(5, (19,))[0]
        router = self._router(model, params, n=2)
        try:
            warm = router.replica("r1")
            warm.engine.submit(prompt, 4, rid="seed")
            warm.engine.run_until_idle()
            warm.stale = lambda: True  # cache-warm but stale
            order = [r.index for r in router.candidates(prompt=prompt)]
            assert order == [0, 1]  # staleness dominates affinity
        finally:
            router.close()

    def test_status_blob_carries_block_summary(self):
        model = _model(depth=1)
        params = _params(model)
        prompt = _ragged_prompts(5, (19,))[0]
        router = self._router(model, params, n=1)
        try:
            r = router.replica("r0")
            r.engine.submit(prompt, 4, rid="seed")
            r.engine.run_until_idle()
            st = r.status()
            assert st["prefix_page_size"] == 8
            assert len(st["prefix_blocks"]) == 2
            # the summary is CONTENT digests — generation-free, so a
            # router can match prompts without knowing replica arms
            assert set(st["prefix_blocks"]) == set(
                prefix_digests(prompt, 8, limit=2))
        finally:
            router.close()


# ----------------------------------------------------------- analytic models


class TestScalingModels:
    def test_prefix_prefill_flops_properties(self):
        from tools.scaling_projection import prefix_prefill_flops

        m = prefix_prefill_flops([24, 8, 17], [24, 8, 17], page_size=8,
                                 prefill_chunk=8)
        # len 24 -> 2 pages (last token prefills); len 8 -> 0; 17 -> 2
        assert m["hit_tokens_per_request"] == [16, 0, 16]
        assert m["cold_prefill_tokens"] == 49
        assert m["cached_prefill_tokens"] == 17
        assert m["saved_tokens"] == 32
        assert m["prefill_token_ratio"] == pytest.approx(49 / 17)
        # chunk misalignment rounds DOWN to the lcm grid
        m2 = prefix_prefill_flops([32], [32], page_size=8,
                                  prefill_chunk=12)
        assert m2["alignment_tokens"] == 24
        assert m2["hit_tokens_per_request"] == [24]
        # partial residency never exceeds what is actually cached
        m3 = prefix_prefill_flops([32], [10], page_size=8,
                                  prefill_chunk=8)
        assert m3["hit_tokens_per_request"] == [8]
        f = prefix_prefill_flops([24], [24], page_size=8, prefill_chunk=8,
                                 params_per_token=1000)
        assert f["cold_prefill_flops"] == 2 * 1000 * 24

    def test_spec_decode_tokens_properties(self):
        from tools.scaling_projection import spec_decode_tokens

        m = spec_decode_tokens(10, 3, acceptance_rate=1.0, n_requests=5)
        # 9 decoded tokens per request (the first comes from prefill):
        # 2 spec iterations of 4, then 1 plain decode — fleet totals x5
        assert m["spec_iterations"] == 10 and m["plain_decodes"] == 5
        assert m["proposed"] == 30 and m["accepted"] == 30
        assert m["target_passes_spec"] == 15 < m["target_passes_plain"] == 45
        assert m["draft_passes"] == 40  # K proposals + 1 backfill, x2 x5
        # free drafts + full acceptance -> ratio = 9/3
        free = spec_decode_tokens(10, 3, acceptance_rate=1.0,
                                  draft_cost=0.0)
        assert free["decode_goodput_ratio"] == pytest.approx(3.0)
        # a draft as expensive as the target can only break even per
        # EXTRA forward: ratio stays below the free-draft bound
        costly = spec_decode_tokens(10, 3, acceptance_rate=1.0,
                                    draft_cost=1.0)
        assert costly["decode_goodput_ratio"] < 3.0
        part = spec_decode_tokens(10, 3, acceptance_rate=0.5)
        assert part["accepted"] < part["proposed"]
        assert part["expected_tokens_per_iteration"] == pytest.approx(
            1 + 0.5 + 0.25 + 0.125)
        with pytest.raises(ValueError):
            spec_decode_tokens(10, 0)


# ------------------------------------------------------------ e2e + bench


@pytest.mark.chaos
def test_e2e_canary_promote_with_caching_and_speculation(hvd, monkeypatch):
    """The ISSUE 18 drill: train on the 8-device mesh → publish G1/G2 →
    the fleet-side rollout canaries G2 on an engine running with BOTH the
    prefix cache and a draft-speculating decode → promotion under live
    traffic, tokens bit-identical to a plain engine on the same weights,
    and the training step's collective schedule byte-identical before and
    after (the hot-path machinery adds no training-side collectives)."""
    from horovod_tpu.analysis.schedule import collective_schedule
    from horovod_tpu.training import (
        make_shardmap_train_step,
        replicate,
        shard_batch,
        token_xent,
    )

    model = _model(depth=2, vocab=64, dim=32, heads=2, max_len=32)
    params0 = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    tx = optax.adam(1e-2)
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
        eng = InferenceEngine(model, page_size=8, num_pages=32,
                              max_batch=2, prefill_chunk=8, max_seq_len=24,
                              prefix_cache=True, draft_depth=1,
                              spec_lookahead=3)
        roll = GenerationRollout(eng, sub, canary_fraction=1.0,
                                 min_canary_requests=2,
                                 max_latency_ratio=None)
        fp_before = collective_schedule(
            step, params, {}, opt_state, xs, ys).fingerprint()

        params, _, opt_state, _ = step(params, {}, opt_state, xs, ys)
        assert pub.publish({"params": params}, 1) == 1
        roll.poll()
        assert roll.stable_generation == 1
        params, _, opt_state, _ = step(params, {}, opt_state, xs, ys)
        assert pub.publish({"params": params}, 2) == 2
        roll.poll()
        assert roll.canary_generation == 2

        prompts = _ragged_prompts(5, (9, 14), vocab=64)
        reqs = [roll.submit(f"d-{i}", p, 6)
                for i, p in enumerate(prompts)]
        roll.drain()
        assert all(r.error is None for r in reqs)
        assert roll.stable_generation == 2  # promoted under traffic
        # a SECOND wave hits the canary-generation cache AND speculates;
        # a plain engine on the same weights must emit the same bits
        wave = [roll.submit(f"d2-{i}", p, 6)
                for i, p in enumerate(prompts)]
        roll.drain()
        assert metrics.value("serving_prefix_hits") >= 1
        assert metrics.value("spec_proposed") > 0
        plain = InferenceEngine(model, page_size=8, num_pages=32,
                                max_batch=2, prefill_chunk=8,
                                max_seq_len=24, prefix_cache=False)
        plain.set_weights(eng.arm_params("stable"), generation=2)
        want, _ = _serve(plain, prompts, 6, "ref")
        for r, w in zip(wave, want):
            np.testing.assert_array_equal(np.asarray(r.generated), w)

        fp_after = collective_schedule(
            step, params, {}, opt_state, xs, ys).fingerprint()
        assert fp_after == fp_before
    finally:
        server.close()


def test_hvd_top_serving_pane_shows_hit_and_acceptance_rates():
    import importlib

    hvd_top = importlib.import_module("tools.hvd_top")
    model = _model(depth=1)
    params = _params(model)
    prompts = _ragged_prompts(4, (19, 19))
    eng = _engine(model, params, prefix_cache=True,
                  draft_depth=1, spec_lookahead=3)
    _serve(eng, prompts, 8, "a")
    _serve(eng, prompts, 8, "b")
    lines = hvd_top.serving_pane(
        hvd_top._single_rank_fleet(metrics.snapshot()))
    joined = "\n".join(lines)
    assert "prefix cache: hit rate" in joined
    assert "spec decode: acceptance" in joined
