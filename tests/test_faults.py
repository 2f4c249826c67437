"""Chaos suite: the failure-containment subsystem under injected faults.

Three layers of assertions:

* **primitives** — the fault harness is deterministic, the watchdog
  abandons hung work, the ladder/breaker state machines transition as
  documented, corrupt state files quarantine instead of raising;
* **containment** — injected dispatch/build/loop/handler faults never
  orphan a request (every admitted request reaches a terminal status)
  and never deadlock the service;
* **invariance** — requests that survive chaos produce *bitwise-identical*
  estimates to a clean run, because samples are pure functions of
  ``(seed, iteration id)`` and containment only ever re-runs them.
"""

import json
import os
import threading
import time
import types
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro.graph.structure import Graph
from repro.obs import metrics as _metrics
from repro.resilience import faults, recovery
from repro.resilience.degradation import (BreakerBoard, CircuitBreaker,
                                          DegradationState)
from repro.resilience.retry import (DispatchTimeout, RetryPolicy,
                                    run_with_timeout)
from repro.service.async_loop import (TERMINAL_STATUSES,
                                      AsyncCountingService)
from repro.service.cache import EstimateCache
from repro.service.requests import CountRequest, RequestStatus
from repro.service.scheduler import (WATCHDOG_FLOOR_EDGE_ROWS_PER_S,
                                     CountingService)


@pytest.fixture(autouse=True)
def _no_leftover_plan():
    """A test that dies mid-chaos must not poison the rest of the run."""
    yield
    faults.clear_plan()


@pytest.fixture(scope="module")
def graph():
    rng = np.random.default_rng(0)
    n = 48
    edges = set()
    for _ in range(140):
        a, b = rng.integers(0, n, 2)
        if a != b:
            edges.add((min(a, b), max(a, b)))
    return Graph.from_edges(n, sorted(edges))


def _service(tmp_path, **kw):
    kw.setdefault("round_size", 4)
    kw.setdefault("default_max_iters", 8)
    kw.setdefault("ledger_root", str(tmp_path / "ledgers"))
    return CountingService(**kw)


def _run_one(svc, graph, template="path3", **req_kw):
    svc.add_graph("g", graph)
    req_kw.setdefault("max_iters", 8)
    rid = svc.submit(CountRequest("g", template, **req_kw))
    svc.run()
    return rid, svc._requests[rid]


def _counter_total(prefix: str) -> float:
    snap = _metrics.snapshot()
    return sum(v for k, v in snap["counters"].items()
               if k.split("{")[0] == prefix)


# ---------------------------------------------------------------- harness
class TestHarness:
    def test_same_seed_same_schedule(self):
        fires = []
        for _ in range(2):
            plan = faults.FaultPlan.parse("kernel.dispatch:raise:0.5",
                                          seed=42)
            pattern = []
            with faults.active_plan(plan):
                for _ in range(40):
                    try:
                        faults.inject("kernel.dispatch")
                        pattern.append(0)
                    except faults.InjectedFault:
                        pattern.append(1)
            fires.append(pattern)
        assert fires[0] == fires[1]
        assert 0 < sum(fires[0]) < 40        # rate actually partial

    def test_times_budget_and_after(self):
        plan = faults.FaultPlan(
            [faults.FaultSpec("kernel.dispatch", times=2, after=1)])
        raised = []
        with faults.active_plan(plan):
            for _ in range(6):
                try:
                    faults.inject("kernel.dispatch")
                    raised.append(0)
                except faults.InjectedFault:
                    raised.append(1)
        # first hit skipped, then exactly `times` firings
        assert raised == [0, 1, 1, 0, 0, 0]

    def test_match_scopes_to_one_context(self):
        plan = faults.FaultPlan(
            [faults.FaultSpec("kernel.dispatch", match="poison")])
        with faults.active_plan(plan):
            faults.inject("kernel.dispatch", context="healthy-group")
            with pytest.raises(faults.InjectedFault):
                faults.inject("kernel.dispatch", context="poison-group")

    def test_no_plan_is_noop(self):
        faults.clear_plan()
        faults.inject("kernel.dispatch")     # must not raise

    def test_parse_rejects_unknown_point_and_mode(self):
        with pytest.raises(ValueError):
            faults.FaultPlan.parse("not.a.point:raise")
        with pytest.raises(ValueError):
            faults.FaultPlan.parse("kernel.dispatch:explode")

    def test_parse_json_file(self, tmp_path):
        p = tmp_path / "plan.json"
        p.write_text(json.dumps({"seed": 9, "faults": [
            {"point": "ledger.write", "mode": "corrupt", "rate": 1.0}]}))
        plan = faults.FaultPlan.parse(str(p))
        assert plan.seed == 9
        assert plan.specs[0].mode == "corrupt"

    def test_corrupt_bytes_truncates_deterministically(self):
        payload = b"x" * 256
        cuts = []
        for _ in range(2):
            plan = faults.FaultPlan(
                [faults.FaultSpec("ledger.write", mode="corrupt")], seed=5)
            with faults.active_plan(plan):
                cuts.append(faults.corrupt_bytes("ledger.write", payload))
        assert cuts[0] == cuts[1]
        assert 0 < len(cuts[0]) < len(payload)

    def test_injected_fault_is_plain_runtime_error(self):
        # containment code must not (and cannot meaningfully) special-case
        assert issubclass(faults.InjectedFault, RuntimeError)


# ----------------------------------------------------------- retry/watchdog
class TestWatchdog:
    def test_no_timeout_runs_inline(self):
        assert run_with_timeout(lambda c: 7, None) == 7
        assert threading.active_count() < 50

    def test_timeout_abandons_hung_worker(self):
        woke = threading.Event()

        def hang(cancelled):
            time.sleep(1.5)
            if cancelled.is_set():
                woke.set()               # abandoned worker: no side effects
                return None
            return "too late"

        t0 = time.monotonic()
        with pytest.raises(DispatchTimeout):
            run_with_timeout(hang, 0.2, name="test")
        assert time.monotonic() - t0 < 1.0   # did not wait the full sleep
        assert woke.wait(3.0)                # worker saw its cancel flag

    def test_worker_exception_reraises(self):
        with pytest.raises(KeyError):
            run_with_timeout(lambda c: {}["missing"], 1.0)

    def test_backoff_shape(self):
        pol = RetryPolicy(base_delay_s=0.1, max_delay_s=0.5, jitter=0.0)
        assert [pol.delay(a) for a in (1, 2, 3, 4)] == \
            [0.1, 0.2, 0.4, 0.5]
        with pytest.raises(ValueError):
            RetryPolicy(max_attempts=0)


# -------------------------------------------------------- ladder / breaker
class TestDegradation:
    def test_ladder_steps_and_applies(self):
        clk = [0.0]
        lad = DegradationState(step_after=2, cooldown_s=10.0,
                               clock=lambda: clk[0])
        base = {"fuse_spmm_ema": True, "autotune_blocks": True,
                "spmm_method": "bsr"}
        assert lad.apply(base) == base           # level 0: untouched
        assert not lad.on_failure()
        assert lad.on_failure()                  # 2nd consecutive: step
        assert lad.level_name == "unfused"
        kw = lad.apply(base)
        assert "fuse_spmm_ema" not in kw and "autotune_blocks" not in kw
        assert lad.on_failure() is False and lad.on_failure()
        assert lad.level_name == "xla"
        assert lad.apply(base)["spmm_method"] == "segment"

    def test_ladder_promotes_one_rung_per_cooldown(self):
        clk = [0.0]
        lad = DegradationState(step_after=1, cooldown_s=5.0,
                               clock=lambda: clk[0])
        lad.on_failure(); lad.on_failure()
        assert lad.level == 2
        assert not lad.maybe_promote()           # cooldown not elapsed
        clk[0] = 6.0
        assert lad.maybe_promote() and lad.level == 1
        assert not lad.maybe_promote()           # one rung per cooldown
        clk[0] = 12.0
        assert lad.maybe_promote() and lad.level == 0

    def test_breaker_state_machine(self):
        clk = [0.0]
        br = CircuitBreaker(threshold=2, cooldown_s=5.0,
                            clock=lambda: clk[0])
        assert br.allow()
        br.on_failure()
        assert br.state == br.CLOSED and br.allow()
        br.on_failure()
        assert br.state == br.OPEN and not br.allow()
        clk[0] = 6.0
        assert br.allow()                        # half-open trial admitted
        assert br.state == br.HALF_OPEN and not br.allow()
        br.on_success()
        assert br.state == br.CLOSED and br.allow()

    def test_board_snapshot_reports_unhealthy(self):
        board = BreakerBoard(threshold=1, cooldown_s=60.0)
        board.get(("k",), label="grp").on_failure()
        snap = board.snapshot()
        assert snap["counts"]["open"] == 1
        assert snap["unhealthy"]["grp"]["state"] == "open"


# ---------------------------------------------------------------- recovery
class TestRecovery:
    def test_roundtrip(self, tmp_path):
        p = str(tmp_path / "state.json")
        recovery.write_checked(p, {"a": 1})
        payload, status = recovery.load_checked(p, kind="t")
        assert (payload, status) == ({"a": 1}, "ok")

    def test_missing_is_clean_cold_start(self, tmp_path):
        payload, status = recovery.load_checked(
            str(tmp_path / "nope.json"), kind="t")
        assert payload is None and status == "missing"

    @pytest.mark.parametrize("content,reason", [
        (b"{\"envelope\": 1, \"crc\": 0, \"payl", "json"),   # torn write
        (b"\x00\x01garbage", "json"),
        (b"[1, 2, 3]", "schema"),
        (b"{\"envelope\": 1, \"crc\": 123, \"payload\": {}}", "crc"),
    ])
    def test_bad_state_quarantined_not_raised(self, tmp_path,
                                              content, reason):
        p = tmp_path / "state.json"
        p.write_bytes(content)
        payload, status = recovery.load_checked(str(p), kind="t")
        assert payload is None and status == reason
        assert not p.exists()                    # moved aside...
        assert p.with_suffix(".json.corrupt").exists()   # ...as evidence

    def test_legacy_pre_envelope_dict_loads(self, tmp_path):
        p = tmp_path / "old.json"
        p.write_text(json.dumps({"completed": {"0": 1.0}, "seed": 0}))
        payload, status = recovery.load_checked(str(p), kind="t")
        assert status == "ok" and payload["completed"] == {"0": 1.0}

    def test_injected_corrupt_write_quarantines_on_next_load(self,
                                                             tmp_path):
        p = str(tmp_path / "state.json")
        plan = faults.FaultPlan(
            [faults.FaultSpec("ledger.write", mode="corrupt", times=1)])
        with faults.active_plan(plan):
            recovery.write_checked(p, {"a": 1}, fault_point="ledger.write")
        payload, status = recovery.load_checked(p, kind="t")
        assert payload is None and status == "json"


# --------------------------------------------------- state-file containment
class TestStateContainment:
    def test_torn_ledger_restarts_cold(self, tmp_path, graph):
        """A ledger torn mid-checkpoint must cost recomputation, never a
        crash — and the recomputed estimate is bitwise-identical."""
        clean = _service(tmp_path / "clean")
        _, st_clean = _run_one(clean, graph)
        base = st_clean.result.estimate

        root = tmp_path / "torn"
        plan = faults.FaultPlan(
            [faults.FaultSpec("ledger.write", mode="corrupt", after=1,
                              times=1)], seed=3)
        with faults.active_plan(plan):
            svc = _service(root)
            _, st = _run_one(svc, graph)
        assert st.result.estimate == base
        # the torn file is found (and quarantined) by the next process
        svc2 = _service(root)
        _, st2 = _run_one(svc2, graph)
        assert st2.result.estimate == base
        corrupt = [f for _, _, fs in os.walk(root / "ledgers") for f in fs
                   if f.endswith(".corrupt")]
        assert corrupt, "torn ledger was not quarantined"

    def test_garbage_estimate_cache_starts_cold(self, tmp_path):
        p = tmp_path / "estimates.json"
        p.write_bytes(b"\x00not json at all")
        cache = EstimateCache(str(p))
        assert cache.get("anything") is None     # no raise, cold start
        assert p.with_suffix(".json.corrupt").exists()
        cache.put("k", {"estimate": 1.0, "stderr": 0.1,
                        "rel_stderr": 0.1, "iterations": 4})
        assert EstimateCache(str(p)).get("k")["estimate"] == 1.0

    def test_truncated_estimate_cache_starts_cold(self, tmp_path):
        p = tmp_path / "estimates.json"
        cache = EstimateCache(str(p))
        cache.put("k", {"estimate": 1.0, "stderr": 0.1,
                        "rel_stderr": 0.1, "iterations": 4})
        p.write_bytes(p.read_bytes()[:10])       # torn write
        assert EstimateCache(str(p)).get("k") is None


# ------------------------------------------------------- scheduler containment
class TestSchedulerChaos:
    def test_retried_dispatch_is_bitwise_identical(self, tmp_path, graph):
        clean = _service(tmp_path / "clean")
        _, st_clean = _run_one(clean, graph)
        base = st_clean.result.estimate

        plan = faults.FaultPlan.parse("kernel.dispatch:raise:1.0:2", seed=7)
        before = _counter_total("dispatch_retries_total")
        with faults.active_plan(plan):
            svc = _service(tmp_path / "chaos")
            _, st = _run_one(svc, graph)
        assert st.status is RequestStatus.DONE
        assert st.result.estimate == base
        assert plan.stats()["kernel.dispatch:raise"]["fired"] == 2
        assert _counter_total("dispatch_retries_total") > before

    def test_exhausted_budget_fails_with_structured_error(self, tmp_path,
                                                          graph):
        plan = faults.FaultPlan.parse("kernel.dispatch:raise:1.0", seed=7)
        with faults.active_plan(plan):
            svc = _service(tmp_path / "x",
                           retry_policy=RetryPolicy(max_attempts=2,
                                                    base_delay_s=0.01))
            _, st = _run_one(svc, graph)
        assert st.status is RequestStatus.FAILED
        assert st.error_class == "InjectedFault"
        assert "kernel.dispatch" in st.error

    def test_ladder_steps_down_under_repeated_failure(self, tmp_path,
                                                      graph):
        plan = faults.FaultPlan.parse("kernel.dispatch:raise:1.0:2", seed=7)
        with faults.active_plan(plan):
            svc = _service(tmp_path / "lad", degrade_after=2)
            _, st = _run_one(svc, graph)
        assert st.status is RequestStatus.DONE
        state = svc.resilience_state()
        assert state["degraded_ladders"], "ladder never stepped"
        (snap,) = state["degraded_ladders"].values()
        assert snap["level_name"] == "unfused"

    def test_breaker_quarantines_poison_group(self, tmp_path, graph):
        plan = faults.FaultPlan.parse("kernel.dispatch:raise:1.0", seed=7)
        svc = _service(tmp_path / "br",
                       retry_policy=RetryPolicy(max_attempts=1),
                       breaker_threshold=2, breaker_cooldown_s=300.0)
        svc.add_graph("g", graph)
        with faults.active_plan(plan):
            statuses = []
            for _ in range(3):
                rid = svc.submit(CountRequest("g", "path3", max_iters=8))
                svc.run()
                st = svc._requests[rid]
                statuses.append(st.error_class)
        # two real failures open the circuit; the third fails *fast*
        assert statuses[:2] == ["InjectedFault", "InjectedFault"]
        assert statuses[2] == "CircuitOpen"
        assert svc.resilience_state()["breakers"]["counts"]["open"] == 1
        # rewind the open timestamp (= cooldown elapsed): a clean
        # half-open trial dispatch closes the circuit
        (br,) = svc._breakers._breakers.values()
        br._opened_at -= 600.0
        rid = svc.submit(CountRequest("g", "path3", max_iters=8))
        svc.run()
        assert svc._requests[rid].status is RequestStatus.DONE
        assert svc.resilience_state()["breakers"]["counts"]["closed"] == 1

    def test_hung_dispatch_caught_by_watchdog(self, tmp_path, graph):
        clean = _service(tmp_path / "clean")
        _, st_clean = _run_one(clean, graph)
        base = st_clean.result.estimate

        plan = faults.FaultPlan(
            [faults.FaultSpec("dispatch.hang", mode="hang", hang_s=5.0,
                              times=1)], seed=1)
        with faults.active_plan(plan):
            svc = _service(tmp_path / "hang",
                           retry_policy=RetryPolicy(max_attempts=3,
                                                    base_delay_s=0.01,
                                                    timeout_s=0.5))
            t0 = time.monotonic()
            _, st = _run_one(svc, graph)
        assert st.status is RequestStatus.DONE
        assert st.result.estimate == base
        assert time.monotonic() - t0 < 5.0       # did not sit out the hang

    def test_watchdog_scales_with_dispatch_work(self, tmp_path, graph):
        """The policy's timeout is slack on top of the dispatch's modeled
        SpMM time, so a big graph's healthy dispatch is not taken for hung:
        at Graph500 RMAT scale 20 a u7 dispatch of 8 colorings runs for
        minutes on a TPU v5e, past the 120 s serve default."""
        svc = _service(tmp_path / "w", retry_policy=RetryPolicy(
            max_attempts=1, timeout_s=120.0))
        _run_one(svc, graph, template="u5")
        (grp,) = svc._groups.values()
        rows = grp.engine.spmm_cols_per_coloring
        assert rows > 0
        assert svc.dispatch_watchdog_s(grp, 8) == pytest.approx(
            120.0 + 8 * rows * graph.m / WATCHDOG_FLOOR_EDGE_ROWS_PER_S)
        # u7 (42 SpMM rows per coloring) on RMAT-20's 31.4M edge slots:
        # the watchdog stands well past the 172 s such a dispatch took
        rmat20_u7 = types.SimpleNamespace(engine=types.SimpleNamespace(
            spmm_cols_per_coloring=42, g=types.SimpleNamespace(m=31_400_000)))
        assert svc.dispatch_watchdog_s(rmat20_u7, 8) > 3 * 172.0
        assert svc.dispatch_watchdog_s(grp, 0) == 120.0
        no_dog = _service(tmp_path / "off")
        assert no_dog.dispatch_watchdog_s(grp, 8) is None

    def test_unaffected_group_untouched_by_scoped_chaos(self, tmp_path,
                                                        graph):
        clean = _service(tmp_path / "clean")
        clean.add_graph("g", graph)
        r1 = clean.submit(CountRequest("g", "path3", max_iters=8))
        r2 = clean.submit(CountRequest("g", "star4", max_iters=8))
        clean.run()
        base3 = clean._requests[r1].result.estimate
        base_s = clean._requests[r2].result.estimate

        svc = _service(tmp_path / "scoped",
                       retry_policy=RetryPolicy(max_attempts=1))
        svc.add_graph("g", graph)
        # poison only the path3 group (match on its template hash prefix)
        from repro.core.templates import TemplateSpec
        h3 = TemplateSpec.of("path3").canonical_hash[:8]
        plan = faults.FaultPlan([faults.FaultSpec(
            "kernel.dispatch", match=h3)], seed=7)
        with faults.active_plan(plan):
            r1 = svc.submit(CountRequest("g", "path3", max_iters=8))
            r2 = svc.submit(CountRequest("g", "star4", max_iters=8))
            svc.run()
        assert svc._requests[r1].status is RequestStatus.FAILED
        assert svc._requests[r2].status is RequestStatus.DONE
        assert svc._requests[r2].result.estimate == base_s
        assert plan.stats()["kernel.dispatch:raise"]["fired"] >= 1
        assert base3 != base_s                   # the two groups differ


# ---------------------------------------------------------- async containment
class TestAsyncChaos:
    def _async(self, tmp_path, **kw):
        kw.setdefault("round_size", 4)
        kw.setdefault("default_max_iters", 8)
        kw.setdefault("idle_wait_s", 0.01)
        kw.setdefault("warm_pool", False)
        kw.setdefault("ledger_root", str(tmp_path / "ledgers"))
        return AsyncCountingService(**kw)

    def test_dispatcher_crash_restarts_and_finishes(self, tmp_path, graph):
        sync = _service(tmp_path / "sync")
        _, st_sync = _run_one(sync, graph)
        base = st_sync.result.estimate

        plan = faults.FaultPlan.parse("dispatch.loop:raise:1.0:2", seed=3)
        with faults.active_plan(plan):
            svc = self._async(tmp_path / "crash")
            svc.add_graph("g", graph)
            with svc:
                rid = svc.submit(CountRequest("g", "path3", max_iters=8))
                assert svc.wait([rid], timeout=90)
                res = svc.result(rid)
        assert res.estimate == base
        assert svc.stats()["dispatcher_crashes"] == 2

    def test_restart_budget_exhaustion_orphans_nothing(self, tmp_path,
                                                       graph):
        plan = faults.FaultPlan.parse("dispatch.loop:raise:1.0", seed=3)
        with faults.active_plan(plan):
            svc = self._async(tmp_path / "dead", max_dispatcher_restarts=2)
            svc.add_graph("g", graph)
            with svc:
                rids = [svc.submit(CountRequest("g", "path3", max_iters=8))
                        for _ in range(3)]
                assert svc.wait(rids, timeout=30)
            for rid in rids:
                st = svc._requests[rid]
                assert st.status in TERMINAL_STATUSES
                if st.status is RequestStatus.FAILED:
                    assert st.error_class == "DispatcherDead"
            # the dead service sheds instead of silently queueing
            rid = svc.submit(CountRequest("g", "path3", max_iters=8))
            assert svc._requests[rid].status in TERMINAL_STATUSES
        assert not svc.resilience_state()["dispatcher"]["alive"]

    def test_mixed_chaos_every_request_terminal(self, tmp_path, graph):
        """The headline containment contract: a request admitted under
        multi-point chaos always reaches a terminal status — and every
        DONE answer matches the clean run bitwise."""
        sync = _service(tmp_path / "sync")
        sync.add_graph("g", graph)
        base = {}
        for tpl in ("path3", "star3"):
            r = sync.submit(CountRequest("g", tpl, max_iters=8, seed=1))
            sync.run()
            base[tpl] = sync._requests[r].result.estimate

        plan = faults.FaultPlan.parse(
            "kernel.dispatch:raise:0.25,engine.build:raise:0.3:2,"
            "dispatch.loop:raise:1.0:1,http.handler:raise:0.5:2", seed=13)
        with faults.active_plan(plan):
            svc = self._async(tmp_path / "mixed", degrade_after=1,
                              retry_policy=RetryPolicy(
                                  max_attempts=3, base_delay_s=0.01,
                                  timeout_s=30.0))
            svc.add_graph("g", graph)
            with svc:
                rids = {}
                for i in range(8):
                    tpl = ("path3", "star3")[i % 2]
                    rids[svc.submit(CountRequest(
                        "g", tpl, max_iters=8, seed=1))] = tpl
                assert svc.wait(list(rids), timeout=120), \
                    "chaos deadlocked the service"
        for rid, tpl in rids.items():
            st = svc._requests[rid]
            assert st.status in TERMINAL_STATUSES, f"{rid} orphaned"
            if st.status is RequestStatus.DONE and not st.from_cache:
                assert st.result.estimate == base[tpl]
        fired = sum(v["fired"] for v in plan.stats().values())
        assert fired > 0, "chaos plan never fired — test is vacuous"


# -------------------------------------------------------------- HTTP hardening
class TestHttpContainment:
    def test_structured_500_and_resilient_healthz(self, tmp_path, graph):
        from repro.service.frontend import serve_forever

        svc = AsyncCountingService(
            round_size=4, default_max_iters=8, idle_wait_s=0.01,
            warm_pool=False, ledger_root=str(tmp_path / "ledgers"))
        svc.add_graph("g", graph)
        httpd = serve_forever(svc, port=0)
        port = httpd.server_address[1]
        base = f"http://127.0.0.1:{port}"
        try:
            plan = faults.FaultPlan(
                [faults.FaultSpec("http.handler", match="POST", times=1)],
                seed=2)
            with faults.active_plan(plan):
                req = urllib.request.Request(
                    f"{base}/count",
                    data=json.dumps({"graph": "g", "templates": ["path3"],
                                     "max_iters": 8}).encode(),
                    headers={"Content-Type": "application/json"})
                with pytest.raises(urllib.error.HTTPError) as ei:
                    urllib.request.urlopen(req, timeout=30)
                assert ei.value.code == 500
                body = json.loads(ei.value.read())
                assert body["error_class"] == "InjectedFault"
                assert body["request_id"].startswith("h")
                # the pool survived: same request now succeeds
                with urllib.request.urlopen(
                        urllib.request.Request(
                            f"{base}/count", data=req.data,
                            headers=dict(req.headers)), timeout=60) as r:
                    assert r.status == 200
            with urllib.request.urlopen(f"{base}/healthz", timeout=10) as r:
                h = json.loads(r.read())
            assert h["ok"] is True
            assert h["resilience"]["dispatcher"]["alive"] is True
            assert "breakers" in h["resilience"]
            assert "degraded_ladders" in h["resilience"]
        finally:
            httpd.shutdown()
            svc.close()

    def test_wait_clamped_by_server_budget(self, tmp_path, graph):
        from repro.service.frontend import make_server
        import threading as th

        svc = AsyncCountingService(
            round_size=4, default_max_iters=8, idle_wait_s=0.01,
            warm_pool=False, ledger_root=str(tmp_path / "ledgers"))
        svc.add_graph("g", graph)
        svc.start()
        httpd = make_server(svc, port=0, max_wait_s=0.2)
        th.Thread(target=httpd.serve_forever, daemon=True).start()
        port = httpd.server_address[1]
        # hang every dispatch: without the clamp this request would park
        # the handler thread for the client's full 600s ask
        plan = faults.FaultPlan(
            [faults.FaultSpec("dispatch.hang", mode="hang", hang_s=60.0)],
            seed=2)
        try:
            with faults.active_plan(plan):
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/count",
                    data=json.dumps({"graph": "g", "templates": ["path3"],
                                     "max_iters": 8,
                                     "timeout_s": 600}).encode(),
                    headers={"Content-Type": "application/json"})
                t0 = time.monotonic()
                with urllib.request.urlopen(req, timeout=30) as r:
                    assert r.status == 202       # accepted, not finished
                assert time.monotonic() - t0 < 10.0
        finally:
            httpd.shutdown()
            svc.close(timeout=1.0)


# ------------------------------------------------------------- metrics audit
def test_containment_metrics_are_labeled(tmp_path, graph):
    """Every fault class fired in a chaos run is accounted for by a
    labeled containment metric (the ISSUE acceptance criterion)."""
    plan = faults.FaultPlan.parse("kernel.dispatch:raise:1.0:2", seed=7)
    before_inj = _counter_total("fault_injections_total")
    before_ret = _counter_total("dispatch_retries_total")
    with faults.active_plan(plan):
        svc = _service(tmp_path / "m")
        _, st = _run_one(svc, graph)
    assert st.status is RequestStatus.DONE
    snap = _metrics.snapshot()
    inj = {k: v for k, v in snap["counters"].items()
           if k.startswith("fault_injections_total")}
    assert any("kernel.dispatch" in k for k in inj)
    assert _counter_total("fault_injections_total") - before_inj == 2
    assert _counter_total("dispatch_retries_total") - before_ret >= 1
    assert any(k.startswith("degradation_level") for k in snap["gauges"])
