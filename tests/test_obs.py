"""Observability subsystem: tracing, metrics, export, and the memory-model
watermark validation.

The obs layer is pure stdlib, so most tests run with no device work; the
watermark and kernel-counter tests drive real engines/kernels to check the
instrumentation fires on the paths it claims to cover.
"""

import collections
import glob
import json
import re
import threading
import time
import urllib.request

import numpy as np
import pytest

from repro.core import build_engine
from repro.graph import erdos_renyi
from repro.obs import metrics, tracing
from repro.obs.validate import validate_snapshot
from repro.service import CountingService, CountRequest, EstimateCache


@pytest.fixture
def tracer():
    """Fresh enabled tracer for one test; restores the disabled default."""
    t = tracing.set_tracer(tracing.Tracer(enabled=True))
    yield t
    tracing.set_tracer(tracing.Tracer(enabled=False))


@pytest.fixture
def registry():
    """Fresh registry for one test; restores a clean default after."""
    r = metrics.set_registry(metrics.MetricsRegistry())
    yield r
    metrics.set_registry(metrics.MetricsRegistry())


def _graph(n=30, deg=4.0, seed=0):
    return erdos_renyi(n, deg, seed=seed)


# --------------------------------------------------------------- tracing
class TestTracing:
    def test_nesting_and_timing(self, tracer):
        with tracing.span("outer", kind="test") as outer:
            time.sleep(0.002)
            with tracing.span("inner") as inner:
                time.sleep(0.002)
            inner2 = tracing.span("inner")
            with inner2:
                pass
        assert len(tracer.roots) == 1
        root = tracer.roots[0]
        assert root is outer
        assert [c.name for c in root.children] == ["inner", "inner"]
        assert root.children[0] is inner and root.children[1] is inner2
        assert root.seconds >= inner.seconds >= 0.002
        assert root.attrs == {"kind": "test"}
        d = root.to_dict()
        assert d["name"] == "outer" and len(d["children"]) == 2

    def test_set_attrs_mid_span(self, tracer):
        with tracing.span("s") as sp:
            sp.set(result=7)
        assert tracer.roots[0].attrs["result"] == 7

    def test_breakdown_aggregates(self, tracer):
        for _ in range(3):
            with tracing.span("a"):
                with tracing.span("b"):
                    pass
        agg = tracer.breakdown()
        assert agg["a"]["count"] == 3 and agg["b"]["count"] == 3
        assert agg["a"]["seconds"] >= agg["b"]["seconds"] >= 0.0

    def test_disabled_is_shared_noop(self):
        assert not tracing.enabled()
        s1 = tracing.span("x", a=1)
        s2 = tracing.span("y")
        assert s1 is s2                     # one shared null span
        with s1 as got:
            assert got.set(z=3) is got
        assert tracing.get_tracer().roots == []

    def test_disabled_overhead_bound(self):
        """50k disabled spans must stay well under half a second — the
        micro-scale version of the <2% bench_engines regression budget."""
        assert not tracing.enabled()
        t0 = time.perf_counter()
        for _ in range(50_000):
            with tracing.span("hot", i=1):
                pass
        dt = time.perf_counter() - t0
        assert dt < 0.5, f"disabled-span overhead too high: {dt:.3f}s"

    def test_reset_and_max_roots(self, tracer):
        tracer.max_roots = 5
        for _ in range(9):
            with tracing.span("r"):
                pass
        assert len(tracer.roots) == 5
        tracer.reset()
        assert tracer.roots == []

    def test_profile_puts_spans_on_the_profiler_clock(self, tmp_path):
        """``profile`` writes one trace whose host plane holds the
        program's spans, attributes included, and restores the tracer."""
        import jax.numpy as jnp
        from jax.profiler import ProfileData

        assert not tracing.enabled()
        with tracing.profile(str(tmp_path)) as t:
            assert t.enabled and t.profiler
            with tracing.span("test.profiled", rids="r000001 r000002"):
                jnp.ones(8).block_until_ready()
        assert not tracing.enabled()
        assert not tracing.get_tracer().profiler
        (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True)
        events = [(ev.name, dict(ev.stats))
                  for plane in ProfileData.from_file(path).planes
                  if plane.name.startswith("/host:")
                  for line in plane.lines for ev in line.events]
        assert ("test.profiled", {"rids": "r000001 r000002"}) in events


# ---------------------------------------------------------- device scopes
_SCOPED_OP = re.compile(r" = .*?\s(gather|scatter|reduce)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# a transform may wrap a scope's name, as in ``vmap(plan.node3)``
_SCOPE = re.compile(r"(?<![\w.])(plan\.node\d+|kernel\.[a-z]+)")


class TestDeviceScopes:
    @pytest.mark.parametrize("engine", ["pgbsc", "pfascia"])
    def test_table_ops_sit_in_kernel_and_plan_node_scopes(self, engine):
        """Every gather, scatter and reduce of a compiled u5 dispatch is
        named by a kernel scope; those of a plan node's kernels sit inside
        that node's scope (the ``op_name`` a device trace reports)."""
        import jax
        import jax.numpy as jnp

        eng = build_engine(_graph(40), "u5", engine)
        eng.warm(2)
        hlo = eng._seeded().lower(
            eng._operands(), jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((2,), jnp.int32)).compile().as_text()
        per_kernel = collections.Counter()
        for line in hlo.splitlines():
            if not _SCOPED_OP.search(line):
                continue
            m = _OP_NAME.search(line)
            assert m, line
            scopes = _SCOPE.findall(m.group(1))
            kernels = [i for i, sc in enumerate(scopes)
                       if sc.startswith("kernel.")]
            assert kernels, m.group(1)
            kernel = scopes[kernels[-1]]
            per_kernel[kernel] += 1
            if kernel in (tracing.KERNEL_SPMM, tracing.KERNEL_EMA,
                          tracing.KERNEL_FUSED):
                assert any(sc.startswith(tracing.PLAN_NODE)
                           for sc in scopes[:kernels[-1]]), m.group(1)
        assert per_kernel[tracing.KERNEL_SPMM] >= 1
        assert per_kernel[tracing.KERNEL_EMA] >= 1
        assert per_kernel[tracing.KERNEL_ROOT] >= 1


# --------------------------------------------------------------- metrics
class TestMetrics:
    def test_counter_gauge_identity(self, registry):
        c = metrics.counter("c_total", kind="a")
        c.inc()
        c.inc(2.5)
        assert metrics.counter("c_total", kind="a") is c
        assert metrics.counter("c_total", kind="b") is not c
        assert c.value == 3.5
        g = metrics.gauge("g_bytes")
        g.set(42)
        assert metrics.gauge("g_bytes").value == 42.0

    def test_histogram_percentiles_vs_numpy(self, registry, rng):
        """Interpolated percentile error is bounded by the bucket width."""
        width = 0.01
        buckets = tuple(np.arange(width, 1.0 + width, width))
        h = metrics.histogram("lat_seconds", buckets=buckets)
        xs = rng.uniform(0.0, 1.0, size=2000)
        for x in xs:
            h.observe(float(x))
        for q in (0.50, 0.95, 0.99):
            got = h.percentile(q)
            want = float(np.quantile(xs, q))
            assert abs(got - want) <= 2 * width, (q, got, want)

    def test_histogram_overflow_and_empty(self, registry):
        h = metrics.histogram("h", buckets=(1.0, 2.0))
        assert h.percentile(0.5) == 0.0
        h.observe(100.0)
        assert h.bucket_counts == [0, 0, 1]
        assert h.percentile(0.5) == 2.0     # clamped to the last edge
        assert h.count == 1 and h.sum == 100.0

    def test_snapshot_schema_and_validation(self, registry):
        metrics.counter("req_total", status="done").inc(3)
        metrics.gauge("mem_bytes").set(1024)
        metrics.histogram("t_seconds").observe(0.05)
        snap = metrics.snapshot()
        validate_snapshot(snap)             # must not raise
        assert snap["schema"] == metrics.SNAPSHOT_SCHEMA
        assert snap["counters"]['req_total{status="done"}'] == 3.0
        assert snap["gauges"]["mem_bytes"] == 1024.0
        h = snap["histograms"]["t_seconds"]
        assert h["count"] == 1 and sum(h["bucket_counts"]) == 1
        assert set(h) >= {"le", "bucket_counts", "p50", "p95", "p99", "sum"}
        # the snapshot is JSON round-trippable and stays valid
        validate_snapshot(json.loads(json.dumps(snap)))

    def test_validate_rejects_corruption(self, registry):
        metrics.histogram("t_seconds").observe(0.05)
        snap = metrics.snapshot()
        bad = json.loads(json.dumps(snap))
        bad["schema"] = 99
        with pytest.raises(ValueError, match="schema"):
            validate_snapshot(bad)
        bad = json.loads(json.dumps(snap))
        bad["histograms"]["t_seconds"]["bucket_counts"][0] += 1
        with pytest.raises(ValueError, match="count"):
            validate_snapshot(bad)
        bad = json.loads(json.dumps(snap))
        bad["counters"]["x"] = float("inf")
        with pytest.raises(ValueError, match="finite"):
            validate_snapshot(bad)

    def test_prometheus_text(self, registry):
        metrics.counter("req_total", status="done").inc(2)
        metrics.histogram("t_seconds", buckets=(0.1, 1.0)).observe(0.05)
        text = metrics.to_prometheus()
        assert "# TYPE req_total counter" in text
        assert 'req_total{status="done"} 2' in text
        assert "# TYPE t_seconds histogram" in text
        assert 't_seconds_bucket{le="0.1"} 1' in text
        assert 't_seconds_bucket{le="+Inf"} 1' in text
        assert "t_seconds_count 1" in text


# -------------------------------------------------------- kernel counters
class TestKernelCounters:
    def test_ema_dtype_fallback_and_paths(self, registry):
        import jax.numpy as jnp
        from repro.kernels.ema import ops as ema_ops
        m_a = jnp.ones((6, 4), jnp.int32)
        y_p = jnp.ones((6, 4), jnp.int32)
        ia = jnp.zeros((3, 2), jnp.int32)
        ip = jnp.zeros((3, 2), jnp.int32)
        ema_ops.ema(m_a, y_p, ia, ip, use_pallas=True, interpret=True)
        snap = metrics.snapshot()["counters"]
        assert snap['kernel_fallbacks_total{kernel="ema",'
                    'reason="dtype_unsupported"}'] >= 1
        assert snap['kernel_launches_total{kernel="ema",path="xla"}'] >= 1

    def test_ema_vmem_fallback(self, registry):
        import jax.numpy as jnp
        from repro.kernels.ema import ops as ema_ops
        # rows >> VMEM budget at the default block sizes -> vmem_overflow
        m_a = jnp.ones((40_000, 8), jnp.float32)
        y_p = jnp.ones((40_000, 8), jnp.float32)
        ia = jnp.zeros((4, 2), jnp.int32)
        ip = jnp.zeros((4, 2), jnp.int32)
        ema_ops.ema(m_a, y_p, ia, ip, use_pallas=True, interpret=True)
        snap = metrics.snapshot()["counters"]
        assert snap['kernel_fallbacks_total{kernel="ema",'
                    'reason="vmem_overflow"}'] >= 1

    def test_spmm_dtype_fallback(self, registry):
        import jax.numpy as jnp
        from repro.kernels.spmm import ops as spmm_ops
        g = _graph()
        prep = spmm_ops.prepare(g, "pallas_gather", interpret=True)
        out = spmm_ops.spmm(jnp.ones((3, g.n), jnp.int32), prep)
        assert out.shape == (3, g.n)
        snap = metrics.snapshot()["counters"]
        assert snap['kernel_fallbacks_total{kernel="spmm",'
                    'reason="dtype_unsupported"}'] >= 1
        assert snap['kernel_launches_total{kernel="spmm",path="xla"}'] >= 1

    def test_fusion_report_and_counters(self, registry):
        eng = build_engine(_graph(60), "u5", "pgbsc", fuse_spmm_ema=True)
        allowed = {"admitted", "admitted_shared", "dtype_unsupported",
                   "multi_consumer", "vmem_overflow"}
        assert eng.fusion_report                      # every internal node
        assert set(eng.fusion_report.values()) <= allowed
        snap = metrics.snapshot()["counters"]
        fusion = {k: v for k, v in snap.items()
                  if k.startswith("fusion_admissions_total")}
        assert sum(fusion.values()) == len(eng.fusion_report)


# ------------------------------------------------- memory-model watermark
class TestWatermark:
    @pytest.mark.parametrize("tpl", ["u5", "u7", "u10"])
    def test_measured_peak_within_model(self, registry, tpl):
        """The traced live-table watermark never exceeds the PR 3 analytic
        peak prediction that drives budget-based batching."""
        eng = build_engine(_graph(50), tpl, "pgbsc", batch_size=4)
        eng.count_iterations_batch(list(range(4)), seed=0)
        assert 0 < eng.measured_peak_bytes <= eng.peak_table_bytes
        gauges = metrics.snapshot()["gauges"]
        meas = [v for k, v in gauges.items()
                if k.startswith("memory_measured_peak_bytes")]
        model = [v for k, v in gauges.items()
                 if k.startswith("memory_model_peak_bytes")]
        assert meas and model and meas[0] <= model[0]


# ------------------------------- executor decision and compiled program
def _lower_seeded(eng, width):
    import jax
    import jax.numpy as jnp
    return eng._seeded().lower(
        eng._operands(), jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((width,), jnp.int32))


class TestExecutionGauges:
    def test_decision_gauges_equal_pick_execution(self, registry):
        from repro.core import executor as pexec
        g = _graph(64)
        eng = build_engine(g, "u12", "pgbsc", plan="optimized")
        choice = pexec.pick_execution(eng.plan, 12, g.n)
        gauges = metrics.snapshot()["gauges"]
        labels = '{engine="pgbsc",k="12",template="u12"}'
        assert gauges["engine_batch_size" + labels] == choice.batch_size
        assert gauges["engine_chunked_nodes" + labels] == \
            len(choice.schedule.chunks) == 0
        assert gauges["engine_modeled_peak_bytes" + labels] == \
            choice.peak_bytes

    @pytest.mark.parametrize("tpl,budget,want", [
        # u12 at the benchmark's ratio of gather budget to edge slots
        # (4 GiB over 1.8M slots: 592-row steps): its 792-column passive
        # tables take 3 steps at 2 colorings, every other table one
        ("u12", None, {1: 1, 3: 1, 5: 3, 7: 3, 9: 1, 11: 1}),
        # u13 squeezed into colorset chunks: one SpMM per chunk
        ("u13", 500_000, {1: 1, 3: 1, 5: 1716, 6: 2, 7: 6}),
    ], ids=["u12", "u13-chunked"])
    def test_segment_steps_equal_row_chunks(self, registry, monkeypatch,
                                            tpl, budget, want):
        from math import comb

        from repro.kernels.spmm import ops as spmm_ops
        g = _graph(64)
        monkeypatch.setattr(spmm_ops, "_SEGMENT_GATHER_BUDGET_BYTES",
                            596 * g.m * 4)
        eng = build_engine(g, tpl, "pgbsc", plan="optimized",
                           memory_budget_bytes=budget)
        chunks = eng.schedule.chunk_map
        steps = eng.segment_spmm_steps(2)
        assert steps == want
        for idx, n in steps.items():
            q = chunks.get(idx, 1)
            node = eng.plan.nodes[idx]
            rows = 2 * -(-comb(eng.k, eng.plan.nodes[node.passive].size)
                         // q)
            per_call = -(-rows // spmm_ops._segment_row_chunk(rows, g.m, 4))
            assert n == q * per_call
        # the traced program's SpMMs take those steps: one traced call
        # per y-cached table or chunked node (a chunk scan runs it q times)
        orig, traced = spmm_ops._segment_row_chunk, []

        def spy(c, e, itemsize):
            rc = orig(c, e, itemsize)
            traced.append(-(-c // rc))
            return rc

        monkeypatch.setattr(spmm_ops, "_segment_row_chunk", spy)
        _lower_seeded(eng, 2)
        assert sorted(traced) == sorted(
            n // chunks.get(i, 1) for i, n in steps.items())

    def test_cache_stats_and_spans_carry_them(self, registry, tracer):
        from repro.service import EngineCache
        cache = EngineCache()
        eng = cache.get(_graph(64), "u12")
        eng.warm(2)
        rep = eng.execution_report()
        assert cache.stats()["engines"] == [rep]
        assert rep["template"] == "u12" and rep["width"] == 2
        assert rep["batch_size"] == eng.batch_size
        assert rep["modeled_peak_bytes"] == eng.peak_table_bytes
        assert rep["spmm_steps"] == eng.segment_spmm_steps(2)
        # the CPU backend gives a memory analysis
        assert rep["compiled_temp_bytes"] > 0
        gauges = metrics.snapshot()["gauges"]
        labels = '{engine="pgbsc",k="12",template="u12"}'
        assert gauges["engine_compiled_temp_bytes" + labels] == \
            rep["compiled_temp_bytes"]
        assert gauges['engine_spmm_steps{engine="pgbsc",k="12",node="5",'
                      'template="u12"}'] == rep["spmm_steps"][5]
        spans = {}

        def walk(sp):
            spans[sp.name] = sp.attrs
            for c in sp.children:
                walk(c)

        for r in tracer.roots:
            walk(r)
        assert spans["engine_cache.build"]["batch_size"] == eng.batch_size
        assert spans["engine_cache.build"]["chunked_nodes"] == 0
        assert spans["engine.warm"]["spmm_steps"] == rep["spmm_steps"]
        assert spans["engine.warm"]["compiled_temp_bytes"] == \
            rep["compiled_temp_bytes"]

    def test_recording_leaves_u7_hlo_unchanged(self, registry,
                                               monkeypatch):
        from repro.core.engines import CountingEngine
        g = _graph(64)
        eng = build_engine(g, "u7", "pgbsc", plan="optimized")
        eng.warm(2)
        with_rec = _lower_seeded(eng, 2).as_text()
        monkeypatch.setattr(CountingEngine, "_record_compiled",
                            lambda self, width, compiled: None)
        monkeypatch.setattr(CountingEngine, "execution_decision",
                            lambda self: {})
        bare = build_engine(g, "u7", "pgbsc", plan="optimized")
        bare.warm(2)
        assert bare.compiled_report == {}
        assert _lower_seeded(bare, 2).as_text() == with_rec


# ------------------------------------------------------- service plumbing
class TestServiceObservability:
    def test_estimate_cache_stats_contract(self, registry):
        cache = EstimateCache()
        assert cache.stats() == {"hits": 0, "misses": 0, "writes": 0,
                                 "invalidations": 0, "resident": 0}
        assert cache.satisfies("k", 0.1, None) is None
        cache.put("k", {"estimate": 1.0, "stderr": 0.01,
                        "rel_stderr": 0.01, "iterations": 32})
        assert cache.satisfies("k", 0.1, None) is not None
        st = cache.stats()
        assert st["hits"] == 1 and st["misses"] == 1
        assert st["writes"] == 1 and st["resident"] == 1
        snap = metrics.snapshot()["counters"]
        assert snap['estimate_cache_lookups_total{result="hit"}'] == 1
        assert snap['estimate_cache_lookups_total{result="miss"}'] == 1
        assert snap["estimate_cache_writes_total"] == 1

    def test_estimate_cache_schema_invalidation(self, registry, tmp_path):
        p = tmp_path / "est.json"
        p.write_text(json.dumps({"old_key": {"estimate": 1.0}}))
        cache = EstimateCache(str(p))
        assert len(cache) == 0
        assert cache.stats()["invalidations"] == 1

    def test_scheduler_stats_and_breakdown(self, registry, tmp_path):
        svc = CountingService(ledger_root=str(tmp_path / "svc"),
                              round_size=8, default_max_iters=16)
        svc.add_graph("g", _graph())
        rid = svc.submit(CountRequest("g", "u3", max_iters=8))
        svc.run()
        res = svc.result(rid)

        st = svc.stats()
        assert st["estimate_cache"]["writes"] == 1
        assert st["engine_cache"]["builds"] == 1

        b = res.breakdown
        assert b is not None
        assert set(b) == {"queue_s", "compile_s", "execute_s", "total_s"}
        accounted = b["queue_s"] + b["compile_s"] + b["execute_s"]
        assert b["total_s"] > 0
        assert accounted >= 0.95 * b["total_s"]
        assert res.to_dict()["breakdown"] == b

        snap = metrics.snapshot()
        c = snap["counters"]
        assert c['service_requests_total{status="done"}'] == 1
        assert c["service_dispatches_total"] >= 1
        assert c["runner_checkpoints_total"] >= 1
        h = snap["histograms"]["service_request_total_seconds"]
        assert h["count"] == 1 and h["sum"] == pytest.approx(
            b["total_s"], rel=0.05)

    def test_cached_request_counted(self, registry, tmp_path):
        svc = CountingService(ledger_root=str(tmp_path / "svc"),
                              round_size=8, default_max_iters=16)
        svc.add_graph("g", _graph())
        svc.submit(CountRequest("g", "u3", max_iters=8))
        svc.run()
        rid2 = svc.submit(CountRequest("g", "u3", max_iters=8))
        res = svc.result(rid2)
        assert res.from_cache and res.breakdown is None
        c = metrics.snapshot()["counters"]
        assert c['service_requests_total{status="cached"}'] == 1

    def test_service_round_spans(self, registry, tracer, tmp_path):
        svc = CountingService(ledger_root=str(tmp_path / "svc"),
                              round_size=8, default_max_iters=8)
        svc.add_graph("g", _graph())
        svc.submit(CountRequest("g", "u3", max_iters=8))
        svc.run()
        agg = tracer.breakdown()
        assert agg["service.round"]["count"] >= 1
        assert agg["service.dispatch"]["count"] >= 1
        assert agg["engine_cache.build"]["count"] == 1
        assert agg["runner.checkpoint"]["count"] >= 1

    def test_http_request_spans_share_its_id(self, registry, tracer,
                                            tmp_path):
        """One ``POST /count`` through the front end and the async
        dispatcher: the front end's phases nest under its request span,
        every span of the request carries its service id, and the
        dispatcher's retire and idle time have spans of their own."""
        from repro.service import AsyncCountingService
        from repro.service.frontend import make_server

        svc = AsyncCountingService(ledger_root=str(tmp_path / "http"),
                                   round_size=4, default_max_iters=8,
                                   idle_wait_s=0.01)
        svc.add_graph("g", _graph())
        svc.start()
        httpd = make_server(svc, "127.0.0.1", 0)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{httpd.server_address[1]}/count",
                data=json.dumps({"graph": "g", "templates": ["u3"],
                                 "max_iters": 4}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as resp:
                (ent,) = json.load(resp)["requests"]
            # the handler closes its request span after the reply is sent
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline and not any(
                    r.name == "frontend.request" for r in tracer.roots):
                time.sleep(0.01)
        finally:
            httpd.shutdown()
            svc.close()
        rid = ent["id"]
        by_name = collections.defaultdict(list)

        def walk(sp):
            by_name[sp.name].append(sp)
            for c in sp.children:
                walk(c)

        for r in list(tracer.roots):
            walk(r)
        (request,) = by_name["frontend.request"]
        assert [c.name for c in request.children] == [
            "frontend.parse", "frontend.wait", "frontend.respond"]
        assert request.attrs["request_id"].startswith("h")
        for name in ("frontend.request", "frontend.wait",
                     "frontend.respond", "service.attach",
                     "service.dispatch"):
            assert any(rid in sp.attrs.get("rids", "").split()
                       for sp in by_name[name]), name
        assert by_name["service.retire"] and by_name["service.idle"]
