"""Multi-tenant counting service: caches, adaptive stopping, group
batching equivalence, and ledger-based resume.

Every sample is a deterministic function of (seed, iteration id), so
service-level invariants are exact: shared groups and resumed services
reproduce solo runs bit-for-bit, not just statistically.
"""

import math
import os

import numpy as np
import pytest

from repro.core import build_engine, count_subgraphs_exact, get_template
from repro.graph import erdos_renyi
from repro.service import (CountingService, CountRequest, EngineCache,
                           EstimateCache, RequestStatus, RunningStat)


def _graph(n=30, deg=4.0, seed=0):
    return erdos_renyi(n, deg, seed=seed)


def _svc(tmp_path, name="svc", **kw):
    kw.setdefault("round_size", 8)
    kw.setdefault("default_max_iters", 64)
    return CountingService(ledger_root=str(tmp_path / name), **kw)


class TestRunningStat:
    def test_matches_numpy(self):
        xs = [3.0, 1.5, 4.25, -2.0, 7.5, 0.0]
        st = RunningStat()
        for x in xs:
            st.update(x)
        arr = np.asarray(xs)
        assert st.mean == pytest.approx(arr.mean())
        assert st.variance == pytest.approx(arr.var(ddof=1))
        assert st.stderr == pytest.approx(arr.std(ddof=1) / math.sqrt(len(xs)))
        lo, hi = st.ci95
        assert lo < st.mean < hi

    def test_degenerate_cases(self):
        st = RunningStat()
        assert st.rel_stderr == float("inf")
        st.update(0.0)
        st.update(0.0)
        # zero mean must not report a met target
        assert st.rel_stderr == float("inf")


class TestEngineCache:
    def test_hit_miss_and_content_keying(self, tmp_path):
        cache = EngineCache()
        g1 = _graph(seed=1)
        g2 = _graph(seed=1)     # same content, different object
        g3 = _graph(seed=2)     # different content
        e1 = cache.get(g1, "u3")
        assert cache.stats() == {"hits": 0, "misses": 1, "builds": 1,
                                 "evictions": 0, "resident": 1,
                                 "engines": [e1.execution_report()]}
        assert cache.get(g2, "u3") is e1          # content hash, not identity
        assert cache.get(g1, "u3", plan="plain") is not e1
        assert cache.get(g3, "u3") is not e1
        assert cache.hits == 1 and cache.builds == 3

    def test_lru_eviction(self):
        cache = EngineCache(max_entries=2)
        g = _graph()
        e_u3 = cache.get(g, "u3")
        cache.get(g, "path4")
        cache.get(g, "u3")              # refresh u3
        cache.get(g, "u5")              # evicts u4 (least recent)
        assert len(cache) == 2
        assert cache.get(g, "u3") is e_u3
        cache.get(g, "path4")              # miss again -> rebuild
        assert cache.builds == 4

    def test_service_builds_once_for_repeats(self, tmp_path):
        svc = _svc(tmp_path)
        svc.add_graph("g", _graph())
        for _ in range(3):
            svc.submit(CountRequest("g", "u3", max_iters=4))
        svc.run()
        assert svc.engine_cache.stats()["builds"] == 1
        assert svc.stats()["groups"] == 1

    def test_idle_groups_release_engine_device_state(self, tmp_path):
        """Retired groups keep their sample history (late joiners) but must
        not pin device arrays of engines the bounded cache evicted; engines
        still cache-resident stay warm for repeated requests."""
        svc = _svc(tmp_path, engine_cache=EngineCache(max_entries=1))
        svc.add_graph("g", _graph())
        r1 = svc.submit(CountRequest("g", "u3", max_iters=4))
        svc.run()
        (grp_u3,) = svc._groups.values()
        # cache-resident: idle group must NOT release (warm repeats)
        assert not grp_u3.engine._released
        r2 = svc.submit(CountRequest("g", "path4", max_iters=4))
        svc.run()
        assert svc._requests[r2].status is RequestStatus.DONE
        # u3 engine was evicted by the 1-entry cache; its idle group must
        # not keep it resident
        assert grp_u3.engine._released
        # a late joiner to the idle group still gets a correct answer
        # (history serves the first 4 samples; the engine re-materializes
        # lazily for the 4 fresh iterations)
        r3 = svc.submit(CountRequest("g", "u3", max_iters=8))
        svc.run()
        assert svc.result(r3).iterations == 8
        assert svc.result(r1).estimate == pytest.approx(
            np.mean(grp_u3.history[:4]))


class TestEstimateCache:
    def test_persistent_roundtrip_serves_without_engine_build(self, tmp_path):
        cache_path = str(tmp_path / "estimates.json")
        g = _graph()
        svc1 = _svc(tmp_path, "a", estimate_cache=cache_path)
        svc1.add_graph("g", g)
        rid = svc1.submit(CountRequest("g", "u3", max_iters=8))
        first = svc1.run()[rid]
        assert os.path.isfile(cache_path)

        svc2 = _svc(tmp_path, "b", estimate_cache=cache_path)
        svc2.add_graph("other-name", g)   # keyed by content, not name
        rid2 = svc2.submit(CountRequest("other-name", "u3", max_iters=8))
        assert svc2.status(rid2) is RequestStatus.DONE
        res = svc2.result(rid2)
        assert res.from_cache
        assert res.estimate == first.estimate
        assert res.iterations == first.iterations
        assert svc2.engine_cache.stats()["builds"] == 0

    def test_insufficient_precision_is_a_miss(self, tmp_path):
        cache = EstimateCache()
        g = _graph()
        svc1 = _svc(tmp_path, "a", estimate_cache=cache)
        svc1.add_graph("g", g)
        rid = svc1.submit(CountRequest("g", "u3", max_iters=6))
        done = svc1.run()[rid]
        svc2 = _svc(tmp_path, "b", estimate_cache=cache)
        svc2.add_graph("g", g)
        # demands more iterations than cached -> must recompute
        rid2 = svc2.submit(CountRequest("g", "u3", max_iters=12))
        assert svc2.status(rid2) is RequestStatus.PENDING
        res = svc2.run()[rid2]
        assert not res.from_cache and res.iterations == 12
        # the tighter answer replaced the cached one
        assert done.iterations < 12 <= cache.get(list(
            cache._mem)[0])["iterations"]

    def test_min_iters_guard_applies_to_cache_hits(self, tmp_path):
        cache = EstimateCache()
        g = _graph()
        svc1 = _svc(tmp_path, "a", estimate_cache=cache)
        svc1.add_graph("g", g)
        # 2 lucky samples can cache a tiny rel_stderr...
        svc1.submit(CountRequest("g", "u3", max_iters=2))
        svc1.run()
        # ...but a request whose own guard demands >= 4 samples must not be
        # answered by that entry
        svc2 = _svc(tmp_path, "b", estimate_cache=cache)
        svc2.add_graph("g", g)
        rid = svc2.submit(CountRequest("g", "u3", rel_stderr=0.9,
                                       min_iters=4))
        assert svc2.status(rid) is RequestStatus.PENDING
        res = svc2.run()[rid]
        assert res.iterations >= 4 and not res.from_cache


class TestAdaptiveStopping:
    def test_tighter_target_runs_longer_same_stream(self, tmp_path):
        g = _graph(40, 4.0, seed=3)
        svc = _svc(tmp_path, round_size=16, default_max_iters=600)
        svc.add_graph("g", g)
        rid_loose = svc.submit(CountRequest("g", "u3", rel_stderr=0.2))
        rid_tight = svc.submit(CountRequest("g", "u3", rel_stderr=0.05))
        res = svc.run()
        loose, tight = res[rid_loose], res[rid_tight]
        assert loose.target_met and tight.target_met
        assert tight.rel_stderr <= 0.05
        assert tight.iterations > loose.iterations
        # both are prefix means of the same deterministic sample stream:
        # same estimator, different stopping points -> estimates agree in
        # expectation; check both against the exact count
        exact = count_subgraphs_exact(g, get_template("u3"))
        assert tight.estimate == pytest.approx(exact, rel=0.2)
        assert loose.estimate == pytest.approx(exact, rel=0.6)

    def test_estimate_is_prefix_mean_of_engine_samples(self, tmp_path):
        g = _graph(seed=4)
        svc = _svc(tmp_path)
        svc.add_graph("g", g)
        rid = svc.submit(CountRequest("g", "u3", rel_stderr=0.1, seed=5))
        res = svc.run()[rid]
        eng = build_engine(g, get_template("u3"), "pgbsc")
        est = eng.estimate(n_iters=res.iterations, seed=5)
        manual = np.asarray(est["samples"])
        assert res.estimate == pytest.approx(float(manual.mean()), rel=1e-6)
        want_se = float(manual.std(ddof=1)) / math.sqrt(len(manual))
        assert res.stderr == pytest.approx(want_se, rel=1e-6)
        assert res.stderr > 0.0

    def test_cap_bounds_adaptive_requests(self, tmp_path):
        # cap deliberately not a round_size multiple: the final round must
        # shrink to the remaining budget, not overshoot with wasted dispatch
        g = _graph(seed=6)
        svc = _svc(tmp_path, default_max_iters=12, round_size=8)
        svc.add_graph("g", g)
        # unreachable target -> runs to the cap, reported as target unmet
        rid = svc.submit(CountRequest("g", "u3", rel_stderr=1e-9))
        res = svc.run()[rid]
        assert res.iterations == 12
        assert not res.target_met
        assert svc.stats()["unique_iterations"] == 12


class TestGroupBatching:
    def test_shared_group_equals_solo_run_with_no_extra_device_work(
            self, tmp_path):
        g = _graph(36, 4.0, seed=7)
        req = dict(template="path4", rel_stderr=0.15, seed=2)

        solo_cache = EngineCache()
        solo = _svc(tmp_path, "solo", engine_cache=solo_cache)
        solo.add_graph("g", g)
        rid = solo.submit(CountRequest("g", **req))
        solo_res = solo.run()[rid]
        solo_eng = solo_cache.get(g, "path4")
        solo_cols = solo_eng.n_colorings_dispatched

        shared_cache = EngineCache()
        shared = _svc(tmp_path, "shared", engine_cache=shared_cache)
        shared.add_graph("g", g)
        rids = [shared.submit(CountRequest("g", **req)) for _ in range(3)]
        shared_res = shared.run()
        shared_eng = shared_cache.get(g, "path4")

        for r in rids:
            assert shared_res[r].estimate == solo_res.estimate
            assert shared_res[r].stderr == solo_res.stderr
            assert shared_res[r].iterations == solo_res.iterations
        # 3 tenants, 1 group, exactly the solo run's device work
        assert shared_eng.n_colorings_dispatched == solo_cols
        assert shared.stats()["groups"] == 1

    def test_different_seeds_do_not_share(self, tmp_path):
        svc = _svc(tmp_path)
        svc.add_graph("g", _graph())
        svc.submit(CountRequest("g", "u3", max_iters=4, seed=0))
        svc.submit(CountRequest("g", "u3", max_iters=4, seed=1))
        svc.run()
        assert svc.stats()["groups"] == 2
        # but one engine serves both groups
        assert svc.engine_cache.stats()["builds"] == 1


class TestLifecycleAndResume:
    def test_status_transitions_and_cancel(self, tmp_path):
        svc = _svc(tmp_path)
        svc.add_graph("g", _graph())
        rid = svc.submit(CountRequest("g", "u3", max_iters=32))
        dead = svc.submit(CountRequest("g", "path4", max_iters=32))
        assert svc.status(rid) is RequestStatus.PENDING
        svc.cancel(dead)
        assert svc.status(dead) is RequestStatus.CANCELLED
        svc.step()
        svc.run()
        assert svc.status(rid) is RequestStatus.DONE
        assert svc.status(dead) is RequestStatus.CANCELLED
        with pytest.raises(RuntimeError):
            svc.result(dead)

    def test_unknown_engine_fails_request_not_service(self, tmp_path):
        svc = _svc(tmp_path)
        svc.add_graph("g", _graph())
        bad = svc.submit(CountRequest("g", "u3", max_iters=4,
                                      engine="nonsense"))
        ok = svc.submit(CountRequest("g", "u3", max_iters=4))
        res = svc.run()
        assert svc.status(bad) is RequestStatus.FAILED
        assert bad not in res and ok in res

    def test_precision_contract_required(self, tmp_path):
        svc = _svc(tmp_path)
        svc.add_graph("g", _graph())
        with pytest.raises(ValueError):
            svc.submit(CountRequest("g", "u3"))
        with pytest.raises(KeyError):
            svc.submit(CountRequest("nograph", "u3", max_iters=4))

    def test_cancel_mid_dispatch_flushes_ledger_and_drains_group(
            self, tmp_path):
        """A cancel landing while a dispatch is in flight must not lose
        the dispatched samples (the ledger checkpoint still flushes; they
        serve future joiners) and must drain the group before the next
        round — not one round late."""
        g = _graph(seed=9)
        cache = EngineCache()
        eng = cache.get(g, "u3")
        inner = eng.count_iterations_batch
        dispatched: list[int] = []
        svc = CountingService(ledger_root=str(tmp_path / "led"),
                              engine_cache=cache, round_size=4)

        def spy(iterations, **kw):
            dispatched.extend(int(i) for i in iterations)
            svc.cancel(rid)          # lands while this dispatch is running
            return inner(iterations, **kw)

        eng.count_iterations_batch = spy
        svc.add_graph("g", g)
        rid = svc.submit(CountRequest("g", "u3", max_iters=12))
        svc.step()                   # dispatches one round; cancel mid-call
        assert svc.status(rid) is RequestStatus.CANCELLED
        assert dispatched == [0, 1, 2, 3]
        (grp,) = svc._groups.values()
        # in-flight samples were flushed to the ledger and group history
        assert sorted(grp.runner.completed_iterations()) == [0, 1, 2, 3]
        assert len(grp.history) == 4
        # the drained group never costs another device dispatch
        svc.step()
        svc.run()
        assert dispatched == [0, 1, 2, 3]
        # and a future joiner consumes the flushed samples for free
        r2 = svc.submit(CountRequest("g", "u3", max_iters=4))
        svc.run()
        assert svc.result(r2).iterations == 4
        assert dispatched == [0, 1, 2, 3]

    def test_resume_after_kill_reuses_ledger(self, tmp_path):
        g = _graph(seed=8)
        cache = EngineCache()
        eng = cache.get(g, "u3")
        fresh_ids: list[int] = []
        inner = eng.count_iterations_batch

        def spy(iterations, **kw):
            fresh_ids.extend(int(i) for i in iterations)
            return inner(iterations, **kw)

        eng.count_iterations_batch = spy
        ledger_root = str(tmp_path / "led")

        svc1 = CountingService(ledger_root=ledger_root, engine_cache=cache,
                               round_size=4)
        svc1.add_graph("g", g)
        svc1.submit(CountRequest("g", "u3", max_iters=12))
        svc1.step()          # one round = 4 iterations, then "killed"
        assert sorted(fresh_ids) == [0, 1, 2, 3]

        svc2 = CountingService(ledger_root=ledger_root, engine_cache=cache,
                               round_size=4)
        svc2.add_graph("g", g)
        rid = svc2.submit(CountRequest("g", "u3", max_iters=12))
        res = svc2.run()[rid]
        # the restarted service computed only the missing iterations
        assert sorted(fresh_ids) == list(range(12))
        assert res.iterations == 12

        # and matches a never-killed service exactly
        svc3 = _svc(tmp_path, "straight", engine_cache=EngineCache())
        svc3.add_graph("g", g)
        rid3 = svc3.submit(CountRequest("g", "u3", max_iters=12))
        straight = svc3.run()[rid3]
        assert res.estimate == straight.estimate
        assert res.stderr == straight.stderr
