"""Serving router contracts (bigdl_tpu/serving/; ISSUE 6).

The load-bearing invariants, all CPU-pinned on a tiny model:

- a 2-replica router run over a mixed long-prefill / short-decode
  workload returns EXACTLY the single-batcher (and hence per-prompt
  greedy) results — zero dropped, zero duplicated responses;
- repeated prompts route sticky through the prefix cache and skip
  prefill (measured on both the router and replica counters);
- admission control parks overflow at the router under saturation and
  sheds with ``RouterSaturated`` past ``max_pending``;
- ``drain()`` finishes a replica's in-flight requests while the other
  replica keeps serving, and flips that replica's /readyz check
  (``?check=serving_replica_<name>`` on the live MetricsServer);
- ``drain(migrate=True)`` exports in-flight KV mid-decode and resumes
  on a survivor, bitwise.
"""
import numpy as np
import pytest

import jax

from bigdl_tpu.models import TransformerLM
from bigdl_tpu.models.transformer.generate import (GenerationConfig,
                                                   generate)
from bigdl_tpu.models.transformer.serving import ContinuousBatcher
from bigdl_tpu.observability.exporter import (HealthRegistry,
                                              MetricsServer)
from bigdl_tpu.observability.registry import MetricRegistry
from bigdl_tpu.observability.request_trace import RequestTracker
from bigdl_tpu.serving import (PrefixCache, ReplicaPool, Router,
                               RouterSaturated, SLOConfig)

V = 32


@pytest.fixture(scope="module")
def model():
    m = TransformerLM(V, d_model=32, num_heads=4, num_layers=2,
                      max_len=64)
    m.materialize(jax.random.PRNGKey(6))
    m.evaluate()
    return m


def _prompts(lengths, seed=4):
    rs = np.random.RandomState(seed)
    return [list(rs.randint(1, V + 1, size=(n,))) for n in lengths]


def _greedy(model, prompt, n_new=6):
    cfg = GenerationConfig(max_new_tokens=n_new, temperature=0.0)
    return np.asarray(generate(model, np.asarray([prompt], np.int32),
                               cfg))[0]


GEO = dict(max_batch=2, num_pages=64, page_size=4, max_new_tokens=6,
           max_burst=4)


def _plane(model, *, slo=None, prefix=None, n=2, geo=None, **router_kw):
    """An isolated (health registry, metric registry, pool, router)
    quadruple; callers close router then pool."""
    health = HealthRegistry()
    reg = MetricRegistry()
    geo = geo or GEO
    pool = ReplicaPool(model, n, health=health,
                       burst=min(4, geo["max_burst"]), **geo)
    # NB: `prefix or ...` would discard an EMPTY cache (len 0 is falsy)
    if prefix is None:
        prefix = PrefixCache(min_tokens=4)
    router = Router(pool, slo=slo or SLOConfig(long_prefill_tokens=32),
                    prefix_cache=prefix,
                    registry=reg, health=health, **router_kw)
    return health, reg, pool, router


class TestEquivalence:
    def test_two_replicas_match_single_batcher(self, model):
        """ISSUE 6 acceptance: mixed long-prefill/short-decode workload
        through 2 replicas == the same request set through one batcher,
        with zero dropped or duplicated responses and at least one
        measured prefix-cache prefill skip."""
        lens = [40, 3, 5, 40, 7, 2, 40, 6]
        prompts = _prompts(lens)
        # single-batcher reference
        cb = ContinuousBatcher(model, registry=MetricRegistry(),
                               health=HealthRegistry(), **GEO)
        for i, p in enumerate(prompts):
            cb.submit(i, p)
        single = dict(cb.run_to_completion(burst=4))

        health, reg, pool, router = _plane(model)
        try:
            # two waves: the second re-submits wave 1's long prompt, so
            # the prefix cache provably skips a prefill
            for i in range(4):
                router.submit(i, prompts[i])
            router.wait_all(timeout=120)
            for i in range(4, 8):
                router.submit(i, prompts[i])
            router.wait_all(timeout=120)
            res = dict(router.finished())
            # zero drops, zero duplicates
            assert sorted(res) == list(range(8))
            assert router.inflight_count == 0
            assert all(rep.step_error is None for rep in pool)
            for i, p in enumerate(prompts):
                np.testing.assert_array_equal(res[i], single[i],
                                              err_msg=f"req {i}")
                np.testing.assert_array_equal(res[i], _greedy(model, p),
                                              err_msg=f"req {i}")
            # prompts[3] == prompts[0] content-wise? They are distinct
            # random draws; the repeated prompt is the wave-2 re-use of
            # an identical token sequence below
            router.submit("again", prompts[0])
            router.wait_all(timeout=60)
            again = dict(router.finished())["again"]
            np.testing.assert_array_equal(again, res[0])
            assert reg.get("router_prefix_hits_total").value() >= 1
            skips = sum(r.stats().prefill_skips for r in pool)
            assert skips >= 1, "no measured prefill skip"
        finally:
            router.close()
            pool.close()

    def test_every_replica_served(self, model):
        """Load actually spreads: with enough simultaneous requests
        both replicas admit some."""
        health, reg, pool, router = _plane(model)
        try:
            prompts = _prompts([5] * 8, seed=9)
            placed = []
            # freeze both drivers so placement is decided while every
            # slot is still free (deterministic spread)
            with pool["r0"].lock, pool["r1"].lock:
                for i, p in enumerate(prompts):
                    placed.append(router.submit(i, p))
            router.wait_all(timeout=120)
            res = dict(router.finished())
            assert sorted(res) == list(range(8))
            assert {"r0", "r1"} <= set(p for p in placed if p)
        finally:
            router.close()
            pool.close()


class TestPrefixRouting:
    def test_sticky_hit_skips_prefill(self, model):
        health, reg, pool, router = _plane(model)
        try:
            p = _prompts([24], seed=11)[0]
            first = router.submit("a", p)
            router.wait_all(timeout=60)
            entry = router.prefix.lookup(p)
            assert entry is not None and entry.replica == first
            second = router.submit("b", p)
            router.wait_all(timeout=60)
            res = dict(router.finished())
            np.testing.assert_array_equal(res["a"], res["b"])
            np.testing.assert_array_equal(res["a"], _greedy(model, p))
            # sticky: the hit routed to the replica that prefilled it
            assert second == first
            assert reg.get("router_prefix_hits_total").value() == 1
            assert pool[second].stats().prefill_skips >= 1
        finally:
            router.close()
            pool.close()

    def test_short_prompts_not_captured(self, model):
        health, reg, pool, router = _plane(
            model, prefix=PrefixCache(min_tokens=16))
        try:
            p = _prompts([5], seed=12)[0]
            router.submit("a", p)
            router.wait_all(timeout=60)
            assert router.prefix.lookup(p) is None
            router.submit("b", p)
            router.wait_all(timeout=60)
            assert reg.get("router_prefix_hits_total").value() == 0
            res = dict(router.finished())
            np.testing.assert_array_equal(res["a"], res["b"])
        finally:
            router.close()
            pool.close()


class TestLongestPrefixRouting:
    """ISSUE 18: the radix index at the router — partial hits adopt a
    truncated snapshot and prefill only the suffix, and the capture
    hook no longer pollutes the cache telemetry."""

    def test_capture_does_not_pollute_counters(self, model):
        """The capture hook uses ``peek``: hit/miss counters and LRU
        order reflect only real dispatch lookups."""
        health, reg, pool, router = _plane(
            model, prefix=PrefixCache(min_tokens=4, page_size=4))
        try:
            p = _prompts([24], seed=21)[0]
            router.submit("a", p)
            router.wait_all(timeout=60)
            # dispatch looked up once (miss); the capture hook's
            # presence probe counted NOTHING
            assert (router.prefix.hits, router.prefix.misses) == (0, 1)
            assert len(router.prefix) == 1
            router.submit("b", p)
            router.wait_all(timeout=60)
            assert (router.prefix.hits, router.prefix.misses) == (1, 1)
            assert reg.get("router_prefix_hits_total").value() == 1
        finally:
            router.close()
            pool.close()

    @pytest.mark.slow
    def test_partial_hits_suffix_prefill_and_drain(self, model):
        """End-to-end drill: prompts sharing a 3-page prefix with
        distinct suffixes produce greedy results identical to fresh
        prefills while the router counts partial hits and reused
        tokens; a queued suffix job survives a drain by re-dispatching
        as its full prompt on the survivor."""
        prefix = PrefixCache(min_tokens=4, page_size=4)
        health, reg, pool, router = _plane(model, prefix=prefix)
        try:
            rs = np.random.RandomState(22)
            shared = list(rs.randint(1, V + 1, size=(12,)))
            sfx = [list(rs.randint(1, V + 1, size=(6,)))
                   for _ in range(4)]
            seeded = router.submit("seed", shared + sfx[0])
            router.wait_all(timeout=60)
            for i in (1, 2):
                router.submit(f"q{i}", shared + sfx[i])
            router.wait_all(timeout=60)
            res = dict(router.finished())
            for rid, p in [("seed", shared + sfx[0]),
                           ("q1", shared + sfx[1]),
                           ("q2", shared + sfx[2])]:
                np.testing.assert_array_equal(res[rid],
                                              _greedy(model, p),
                                              err_msg=rid)
            assert reg.get(
                "router_prefix_partial_hits_total").value() == 2
            assert reg.get(
                "router_prefix_tokens_reused_total").value() == 24
            lat = router.latency_summary()
            assert lat["prefix_partial_hits"] == 2
            assert lat["prefix_tokens_reused"] == 24
            assert 0.0 < lat["prefix_tokens_reused_fraction"] < 1.0
            suffix_prefills = sum(
                int(r.batcher._m_suffix.value()) for r in pool)
            assert suffix_prefills == 2

            # queued suffix job across a drain: freeze the sticky
            # replica so the job parks in ITS queue, then drain — it
            # must re-dispatch as a full prompt and reuse the prefix
            # on the survivor
            with pool[seeded].lock:
                router.submit("q3", shared + sfx[3])
                router.drain(seeded)
            router.wait_all(timeout=60)
            out = dict(router.finished())["q3"]
            np.testing.assert_array_equal(
                out, _greedy(model, shared + sfx[3]))
            assert reg.get(
                "router_prefix_partial_hits_total").value() >= 3
        finally:
            router.close()
            pool.close()


class TestAdmission:
    def test_saturation_parks_then_completes(self, model):
        """With both drivers frozen and per-replica queue depth capped,
        a burst of submissions fills each replica's queue and the rest
        PARK at the router; everything still completes correctly once
        the drivers run."""
        slo = SLOConfig(long_prefill_tokens=32, max_queue_depth=1,
                        max_pending=100)
        health, reg, pool, router = _plane(model, slo=slo)
        try:
            prompts = _prompts([4] * 10, seed=13)
            placed = []
            with pool["r0"].lock, pool["r1"].lock:
                for i, p in enumerate(prompts):
                    placed.append(router.submit(i, p))
                # each replica accepted exactly max_queue_depth
                assert sum(p is not None for p in placed) == 2
                assert router.pending_count == 8
                assert reg.get("router_pending_depth").value() == 8
            router.wait_all(timeout=120)
            res = dict(router.finished())
            assert sorted(res) == list(range(10))
            for i in range(10):
                np.testing.assert_array_equal(
                    res[i], _greedy(model, prompts[i]),
                    err_msg=f"req {i}")
        finally:
            router.close()
            pool.close()

    def test_sheds_past_max_pending(self, model):
        slo = SLOConfig(long_prefill_tokens=32, max_queue_depth=0,
                        max_pending=0)
        health, reg, pool, router = _plane(model, slo=slo)
        try:
            with pytest.raises(RouterSaturated):
                router.submit("x", _prompts([4])[0])
            assert reg.get("router_rejected_total").value() == 1
            # the shed request leaves no residue
            assert router.inflight_count == 0
        finally:
            router.close()
            pool.close()

    def test_duplicate_request_id_raises(self, model):
        health, reg, pool, router = _plane(model)
        try:
            p = _prompts([4])[0]
            with pool["r0"].lock, pool["r1"].lock:
                router.submit("dup", p)
                with pytest.raises(ValueError, match="duplicate"):
                    router.submit("dup", p)
            router.wait_all(timeout=60)
            assert [rid for rid, _ in router.finished()] == ["dup"]
        finally:
            router.close()
            pool.close()

    def test_cancel_parked_request(self, model):
        slo = SLOConfig(long_prefill_tokens=32, max_queue_depth=0,
                        max_pending=10)
        health, reg, pool, router = _plane(model, slo=slo)
        try:
            assert router.submit("park", _prompts([4])[0]) is None
            assert router.pending_count == 1
            assert router.cancel("park") is True
            assert router.pending_count == 0
            assert router.inflight_count == 0
            assert router.cancel("park") is False
        finally:
            router.close()
            pool.close()

    def test_session_sticky(self, model):
        health, reg, pool, router = _plane(model)
        try:
            p = _prompts([6], seed=14)[0]
            first = router.submit("s1", p, session="sess")
            router.wait_all(timeout=60)
            second = router.submit("s2", _prompts([7], seed=15)[0],
                                   session="sess")
            router.wait_all(timeout=60)
            assert first == second
            router.finished()
        finally:
            router.close()
            pool.close()


class TestDrain:
    def test_drain_finishes_inflight_other_replica_serves(self, model):
        """ISSUE 6 acceptance: drain(r) finishes r's in-flight requests
        while the other replica keeps serving, and flips r's /readyz
        check on the live MetricsServer."""
        health, reg, pool, router = _plane(model)
        server = MetricsServer(port=0, registry=reg,
                               health=health).start()
        try:
            prompts = _prompts([9, 8, 7, 6], seed=16)
            placed = []
            r0 = pool["r0"]
            with r0.lock, pool["r1"].lock:
                for i, p in enumerate(prompts):
                    placed.append(router.submit(i, p))
                # both replicas took work (drivers frozen: nothing ran)
                assert {"r0", "r1"} <= set(placed)
                # manually admit + decode ONE burst on r0 while its
                # driver is frozen: its rows now sit mid-decode (5 of 6
                # tokens), so the drain below must finish real
                # in-flight work
                r0.batcher.step(burst=4)
                inflight = [s for s in r0.batcher.slots if s is not None]
                assert inflight and all(1 <= len(s[2]) < 6
                                        for s in inflight)
            summary = router.drain("r0", timeout=120)
            assert summary["replica"] == "r0"
            assert r0.batcher.idle          # everything it owned is done
            # its in-flight rows RETIRED here (not migrated/requeued)
            assert r0.registry.get(
                "serving_retirements_total").value() >= len(inflight)
            # /readyz: full verdict fails, r0's check not ok, r1's ok
            from urllib.request import urlopen
            from urllib.error import HTTPError
            import json as _json
            try:
                with urlopen(f"{server.url}/readyz", timeout=10) as r:
                    body = _json.loads(r.read())
                    status = r.status
            except HTTPError as e:
                body = _json.loads(e.read())
                status = e.code
            assert status == 503
            assert body["checks"]["serving_replica_r0"]["ok"] is False
            assert body["checks"]["serving_router"]["ok"] is True
            with urlopen(f"{server.url}/readyz?"
                         "check=serving_replica_r1", timeout=10) as r:
                assert r.status == 200
            # the drained replica admits nothing; the other serves on
            after = router.submit("after", prompts[0])
            assert after == "r1"
            router.wait_all(timeout=120)
            res = dict(router.finished())
            assert sorted(res, key=str) == sorted(
                list(range(4)) + ["after"], key=str)
            for i, p in enumerate(prompts):
                np.testing.assert_array_equal(
                    res[i], _greedy(model, p), err_msg=f"req {i}")
            np.testing.assert_array_equal(res["after"], res[0])
            router.resume("r0")
            ok, _ = health.run("readiness")
            assert ok
        finally:
            server.close()
            router.close()
            pool.close()

    def test_drain_migrates_mid_decode_bitwise(self, model):
        """migrate=True exports an in-flight request's KV mid-decode
        and resumes it on the survivor — result bitwise equal to the
        uninterrupted greedy continuation."""
        geo = dict(max_batch=2, num_pages=64, page_size=4,
                   max_new_tokens=12, max_burst=2)
        health, reg, pool, router = _plane(model, geo=geo)
        try:
            p = _prompts([10], seed=17)[0]
            router.drain("r1", timeout=60)      # force placement on r0
            r0 = pool["r0"]
            with r0.lock:                       # freeze r0's driver
                assert router.submit("mg", p) == "r0"
                r0.batcher.step(burst=2)        # admit + decode 1 burst
                slot = [s for s in r0.batcher.slots if s is not None]
                assert slot and slot[0][0] == "mg"
                assert 1 <= len(slot[0][2]) < 12    # genuinely mid-way
                router.resume("r1")
                summary = router.drain("r0", migrate=True, timeout=60)
            assert summary["migrated"] == 1
            assert reg.get("router_migrations_total").value() == 1
            router.wait_all(timeout=120)
            res = dict(router.finished())
            np.testing.assert_array_equal(res["mg"],
                                          _greedy(model, p, 12))
            assert pool["r1"].stats().prefill_skips >= 1
        finally:
            router.close()
            pool.close()

    def test_drain_requeues_queued_requests(self, model):
        """Requests still QUEUED on the drained replica re-dispatch to
        survivors (none lost, none doubled)."""
        slo = SLOConfig(long_prefill_tokens=32, max_queue_depth=4)
        health, reg, pool, router = _plane(model, slo=slo)
        try:
            prompts = _prompts([4] * 6, seed=18)
            with pool["r0"].lock, pool["r1"].lock:
                placed = [router.submit(i, p)
                          for i, p in enumerate(prompts)]
                assert placed.count("r0") >= 2   # slots + queue on r0
                # drain r0 while its driver is frozen: everything it
                # holds is still queued (nothing admitted yet), so all
                # of it must requeue
                summary = router.drain("r0", migrate=True, timeout=60)
            assert summary["requeued"] + summary["migrated"] == \
                placed.count("r0")
            router.wait_all(timeout=120)
            res = dict(router.finished())
            assert sorted(res) == list(range(6))
            for i in range(6):
                np.testing.assert_array_equal(
                    res[i], _greedy(model, prompts[i]),
                    err_msg=f"req {i}")
        finally:
            router.close()
            pool.close()


class TestStepFailure:
    def test_wait_all_raises_the_replicas_exception(self, model):
        """A step that fails on the replica's driver thread must not
        read as slowness: ``wait_all`` raises at once, chained to the
        replica's own exception, instead of sitting out its timeout
        and raising a bare TimeoutError."""
        import time
        health, reg, pool, router = _plane(model, n=1)
        try:
            boom = ValueError("Mosaic refused the block shape")

            def failing_step(burst=None):
                raise boom
            pool["r0"].batcher.step = failing_step
            router.submit("doomed", _prompts([5])[0])
            t0 = time.monotonic()
            with pytest.raises(RuntimeError,
                               match="replica r0 step failed.*Mosaic "
                                     "refused") as ei:
                router.wait_all(timeout=60)
            assert time.monotonic() - t0 < 10
            assert ei.value.__cause__ is boom
            assert pool["r0"].step_error is boom
        finally:
            router.close()
            pool.close()


class TestDisaggregation:
    def test_long_prefill_handed_to_decode_replica(self, model):
        slo = SLOConfig(long_prefill_tokens=16)
        health, reg, pool, router = _plane(
            model, slo=slo, prefix=PrefixCache(min_tokens=999),
            capture_prefixes=False, prefill_replica="r0")
        try:
            p = _prompts([40], seed=19)[0]
            placed = router.submit("d", p)
            assert placed == "r1"       # decode lands off the prefill
            router.wait_all(timeout=60)
            res = dict(router.finished())
            np.testing.assert_array_equal(res["d"], _greedy(model, p))
            assert reg.get("router_disagg_prefills_total").value() == 1
            assert pool["r1"].stats().prefill_skips == 1
        finally:
            router.close()
            pool.close()

    def test_single_replica_skips_disagg(self, model):
        slo = SLOConfig(long_prefill_tokens=16)
        health, reg, pool, router = _plane(model, slo=slo, n=1)
        try:
            p = _prompts([40], seed=20)[0]
            assert router.submit("d", p) == "r0"
            router.wait_all(timeout=60)
            res = dict(router.finished())
            np.testing.assert_array_equal(res["d"], _greedy(model, p))
            assert reg.get("router_disagg_prefills_total").value() == 0
        finally:
            router.close()
            pool.close()


class TestValidation:
    def test_pool_rejects_bad_config(self, model):
        with pytest.raises(ValueError, match="replica"):
            ReplicaPool(model, 0, health=HealthRegistry(), **GEO)
        with pytest.raises(ValueError, match="distinct"):
            ReplicaPool(model, 2, names=["a", "a"],
                        health=HealthRegistry(), **GEO)

    def test_router_rejects_unknown_prefill_replica(self, model):
        health = HealthRegistry()
        pool = ReplicaPool(model, 1, health=health, **GEO)
        try:
            with pytest.raises(ValueError, match="prefill"):
                Router(pool, prefill_replica="nope", health=health,
                       registry=MetricRegistry())
        finally:
            pool.close()

    def test_slo_validation(self):
        with pytest.raises(ValueError):
            SLOConfig(ttft_p99_s=0)
        with pytest.raises(ValueError):
            SLOConfig(max_kv_utilization=1.5)

    def test_health_filter_names_missing_check_fails(self):
        h = HealthRegistry()
        h.register("present", lambda: True)
        ok, res = h.run("readiness", names=["present", "absent"])
        assert not ok
        assert res["present"]["ok"] and not res["absent"]["ok"]
        ok, res = h.run("readiness", names=["present"])
        assert ok

    def test_stopped_pool_unregisters_health(self, model):
        health = HealthRegistry()
        pool = ReplicaPool(model, 1, health=health, **GEO)
        assert any(c.name == "serving_replica_r0"
                   for c in health.checks("readiness"))
        pool.close()
        assert health.checks("readiness") == []


class TestRequestTimelines:
    """ISSUE 19: every request through the router leaves ONE causal
    timeline spanning admission -> placement -> prefill -> decode ->
    completion, the router_queue_wait_seconds histogram sees EVERY
    request, and churn (drain migrate=True) never forks or drops a
    timeline."""

    def test_end_to_end_timeline_and_queue_wait(self, model):
        tracker = RequestTracker(sample_every=1)
        health, reg, pool, router = _plane(model, tracker=tracker)
        try:
            prompts = _prompts([5, 7, 4, 6], seed=23)
            for i, p in enumerate(prompts):
                router.submit(i, p)
            router.wait_all(timeout=120)
            res = dict(router.finished())
            assert sorted(res) == list(range(4))
            st = tracker.stats()
            assert (st["started"], st["finished"], st["in_flight"]) \
                == (4, 4, 0)
            # the aggregate queue-wait clock saw EVERY request,
            # independent of sampling, and rides latency_summary()
            qw = reg.get("router_queue_wait_seconds").snapshot()
            assert qw["count"] == 4
            summ = router.latency_summary()
            assert summ["queue_wait_count"] == 4
            assert summ["queue_wait_p99_s"] >= summ["queue_wait_p50_s"]
            assert summ["attribution"]["requests"] == 4
            # one causal timeline per request: milestones in order
            for i in range(4):
                tl = tracker.timeline(i)
                names = [e["event"] for e in tl["timeline"]]
                assert names[0] == "submit" and names[-1] == "finish"
                for a, b in (("submit", "place"),
                             ("place", "first_token"),
                             ("first_token", "complete")):
                    assert names.index(a) < names.index(b), (i, names)
                assert names.count("finish") == 1
                assert tl["status"] == "ok"
                assert tl["tokens"] == len(res[i])
                assert tl["replicas"], "no replica attributed"
                ts = [e["t"] for e in tl["timeline"]]
                assert ts == sorted(ts)
        finally:
            router.close()
            pool.close()

    def test_tracker_false_disables_timelines_keeps_queue_wait(
            self, model):
        health, reg, pool, router = _plane(model, tracker=False)
        try:
            router.submit("r", _prompts([5], seed=24)[0])
            router.wait_all(timeout=60)
            router.finished()
            assert reg.get("router_queue_wait_seconds") \
                .snapshot()["count"] == 1
            assert router.latency_summary()["attribution"] is None
        finally:
            router.close()
            pool.close()

    def test_queue_wait_exemplar_links_to_timeline(self, model):
        """The histogram's OpenMetrics exemplar is a live trace id:
        the scrape can jump from the bucket to /requests/<id>."""
        tracker = RequestTracker(sample_every=1)
        health, reg, pool, router = _plane(model, tracker=tracker)
        try:
            router.submit("ex1", _prompts([5], seed=25)[0])
            router.wait_all(timeout=60)
            router.finished()
            text = reg.expose()
            assert '# {trace_id="ex1"}' in text
            assert tracker.timeline("ex1") is not None
        finally:
            router.close()
            pool.close()

    def test_router_teaches_tracker_the_slo(self, model):
        tracker = RequestTracker()          # no SLO of its own
        slo = SLOConfig(long_prefill_tokens=32, ttft_p99_s=1.25)
        health, reg, pool, router = _plane(model, slo=slo,
                                           tracker=tracker)
        try:
            assert tracker.slo is slo
            assert tracker.ttft_slo_s == 1.25
        finally:
            router.close()
            pool.close()

    def test_drain_migrate_keeps_one_timeline(self, model):
        """Exactly-once under churn: a request migrated mid-decode has
        ONE timeline spanning both replicas — the migration hop is
        recorded (and booked as migration_s), never a second submit or
        a forked finish."""
        tracker = RequestTracker(sample_every=1)
        geo = dict(max_batch=2, num_pages=64, page_size=4,
                   max_new_tokens=12, max_burst=2)
        health, reg, pool, router = _plane(model, geo=geo,
                                           tracker=tracker)
        try:
            p = _prompts([10], seed=17)[0]
            router.drain("r1", timeout=60)   # force placement on r0
            r0 = pool["r0"]
            with r0.lock:                    # freeze r0's driver
                assert router.submit("mg", p) == "r0"
                r0.batcher.step(burst=2)     # admit + decode 1 burst
                router.resume("r1")
                summary = router.drain("r0", migrate=True, timeout=60)
            assert summary["migrated"] == 1
            router.wait_all(timeout=120)
            res = dict(router.finished())
            assert sorted(res) == ["mg"]     # exactly once
            st = tracker.stats()
            assert (st["started"], st["finished"]) == (1, 1)
            tl = tracker.timeline("mg")
            names = [e["event"] for e in tl["timeline"]]
            assert names.count("submit") == 1
            assert names.count("finish") == 1
            assert "migrate" in names and "adopt" in names
            # the re-placement books migration, not queue wait
            hops = [e for e in tl["timeline"] if e["event"] == "place"]
            assert [h["cause"] for h in hops] == ["submit", "migrate"]
            assert tl["replicas"] == ["r0", "r1"]
            assert tl["components"]["migration_s"] > 0.0
            # the queue-wait histogram counted both placements
            assert reg.get("router_queue_wait_seconds") \
                .snapshot()["count"] == 2
        finally:
            router.close()
            pool.close()
