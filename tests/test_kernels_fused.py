"""Fused SpMM->eMA kernel: oracle equivalence, fallbacks, memory model.

The fused kernel must be indistinguishable (to float reassociation) from the
unfused pair ``ema(m_a, spmm(m_p), ia, ip)`` at the kernel level, and a
``fuse_spmm_ema=True`` engine must reproduce the unfused engine's counts on
u5/u7/u10 for single and batched colorings. The executor's peak-memory model
must charge fused nodes no y-table, so the same budget admits at least as
large a coloring batch.
"""

from math import comb

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_engine, executor as pexec, get_template
from repro.core.colorsets import split_tables
from repro.graph import Graph, erdos_renyi, grid_2d, rmat, star
from repro.graph.coloring import coloring_numpy
from repro import kernels
from repro.kernels import autotune
from repro.kernels.ema.ops import ema_xla
from repro.kernels.fused import (fused_group_fits_vmem, fused_spmm_ema,
                                 fused_spmm_ema_shared, prepare_fused)
from repro.kernels.fused.pallas_fused import pick_group_batch_block
from repro.kernels.spmm.ref import spmm_dense


def _rand_table(rng, shape, dtype=np.float32):
    return jnp.asarray(rng.integers(0, 4, size=shape).astype(dtype))


def _oracle(g, m_a, m_p, ia, ip):
    y = spmm_dense(m_p, jnp.asarray(g.to_dense()).astype(m_p.dtype))
    return ema_xla(m_a, y, ia, ip)


GRAPHS = {
    "er_uneven": lambda: erdos_renyi(130, 7.0, seed=1),   # n % 128 != 0
    "grid": lambda: grid_2d(12, 11),
    "star_skew": lambda: star(150),
    "rmat": lambda: rmat(8, 8, seed=2),
    "empty": lambda: Graph.from_edges(100, np.zeros((0, 2), np.int64)),
}


class TestFusedKernel:
    @pytest.mark.parametrize("gname", sorted(GRAPHS))
    @pytest.mark.parametrize("k,t,ta", [(5, 3, 1), (7, 4, 2)])
    def test_matches_oracle(self, gname, k, t, ta):
        g = GRAPHS[gname]()
        ia, ip = split_tables(k, t, ta)
        ia, ip = jnp.asarray(ia), jnp.asarray(ip)
        rng = np.random.default_rng(k * 10 + ta)
        m_a = _rand_table(rng, (comb(k, ta), g.n))
        m_p = _rand_table(rng, (comb(k, t - ta), g.n))
        prep = prepare_fused(g)
        got = fused_spmm_ema(m_a, m_p, ia, ip, prep)
        want = _oracle(g, m_a, m_p, ia, ip)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6)

    def test_empty_graph_is_zero(self):
        g = GRAPHS["empty"]()
        ia, ip = split_tables(5, 3, 1)
        rng = np.random.default_rng(0)
        m_a = _rand_table(rng, (5, g.n))
        m_p = _rand_table(rng, (10, g.n))
        got = fused_spmm_ema(m_a, m_p, jnp.asarray(ia), jnp.asarray(ip),
                             prepare_fused(g))
        assert not np.asarray(got).any()

    @pytest.mark.parametrize("b", [1, 3, 5])  # 5 exercises batch padding
    def test_batched(self, b):
        g = GRAPHS["er_uneven"]()
        ia, ip = split_tables(7, 4, 2)
        ia, ip = jnp.asarray(ia), jnp.asarray(ip)
        rng = np.random.default_rng(b)
        m_a = _rand_table(rng, (b, comb(7, 2), g.n))
        m_p = _rand_table(rng, (b, comb(7, 2), g.n))
        prep = prepare_fused(g)
        got = fused_spmm_ema(m_a, m_p, ia, ip, prep)
        assert got.shape == (b, comb(7, 4), g.n)
        for i in range(b):
            want = _oracle(g, m_a[i], m_p[i], ia, ip)
            np.testing.assert_allclose(np.asarray(got[i]),
                                       np.asarray(want), rtol=1e-6)

    def test_batch_blocking_smaller_than_batch(self, monkeypatch):
        # force bb < B so the grid walks multiple batch blocks
        monkeypatch.setattr(kernels, "VMEM_BUDGET_BYTES", 1 << 16)
        g = GRAPHS["rmat"]()
        ia, ip = split_tables(5, 3, 1)
        ia, ip = jnp.asarray(ia), jnp.asarray(ip)
        rng = np.random.default_rng(9)
        m_a = _rand_table(rng, (4, 5, g.n))
        m_p = _rand_table(rng, (4, 10, g.n))
        assert pick_group_batch_block(4, (5,), 10, (16,), (ia.shape[1],),
                                      128, jnp.float32) < 4
        got = fused_spmm_ema(m_a, m_p, ia, ip, prepare_fused(g))
        for i in range(4):
            want = _oracle(g, m_a[i], m_p[i], ia, ip)
            np.testing.assert_allclose(np.asarray(got[i]),
                                       np.asarray(want), rtol=1e-6)

    def test_float64(self, x64):
        g = GRAPHS["grid"]()
        ia, ip = split_tables(5, 3, 2)
        ia, ip = jnp.asarray(ia), jnp.asarray(ip)
        rng = np.random.default_rng(5)
        m_a = _rand_table(rng, (comb(5, 2), g.n), np.float64)
        m_p = _rand_table(rng, (comb(5, 1), g.n), np.float64)
        got = fused_spmm_ema(m_a, m_p, ia, ip, prepare_fused(g))
        assert got.dtype == jnp.float64
        want = _oracle(g, m_a, m_p, ia, ip)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0)

    def test_unsupported_dtype_falls_back_exactly(self):
        # float16 is outside the kernel's dtype set -> explicit XLA fallback,
        # never a silent cast; small ints are exact in f16
        g = GRAPHS["rmat"]()
        ia, ip = split_tables(5, 2, 1)
        ia, ip = jnp.asarray(ia), jnp.asarray(ip)
        rng = np.random.default_rng(6)
        m_a = _rand_table(rng, (5, g.n), np.float16)
        m_p = _rand_table(rng, (5, g.n), np.float16)
        got = fused_spmm_ema(m_a, m_p, ia, ip, prepare_fused(g))
        assert got.dtype == jnp.float16
        want = _oracle(g, m_a.astype(jnp.float32),
                       m_p.astype(jnp.float32), ia, ip)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want), rtol=1e-3)

    def test_vmem_overflow_falls_back(self, monkeypatch):
        assert not fused_group_fits_vmem((4000,), 4000, (8000,), (100,))
        # shrink the budget so dispatch takes the XLA fallback, and verify
        # the kernel is really bypassed (it would raise if called)
        from repro.kernels.fused import ops as fops
        monkeypatch.setattr(kernels, "VMEM_BUDGET_BYTES", 1)
        monkeypatch.setattr(
            fops, "fused_spmm_ema_pallas",
            lambda *a, **k: (_ for _ in ()).throw(
                AssertionError("kernel path taken")))
        g = GRAPHS["rmat"]()
        ia, ip = split_tables(5, 3, 1)
        rng = np.random.default_rng(7)
        m_a = _rand_table(rng, (5, g.n))
        m_p = _rand_table(rng, (10, g.n))
        got = fused_spmm_ema(m_a, m_p, jnp.asarray(ia), jnp.asarray(ip),
                             prepare_fused(g))
        want = _oracle(g, m_a, m_p, jnp.asarray(ia), jnp.asarray(ip))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6)


class TestFusedEngine:
    @pytest.mark.parametrize("tname", ["u5", "u7", "u10"])
    def test_matches_unfused_single_and_batched(self, tname):
        g = erdos_renyi(60, 4.0, seed=3)
        t = get_template(tname)
        base = build_engine(g, t, "pgbsc")
        fused = build_engine(g, t, "pgbsc", fuse_spmm_ema=True)
        assert fused.schedule.fused, "expected fused-eligible nodes"
        colors = coloring_numpy(0, 0, g.n, t.k)
        want, _ = base.count_colorful(colors)
        got, _ = fused.count_colorful(colors)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
        batch = np.stack([coloring_numpy(0, i, g.n, t.k) for i in range(3)])
        want_b, _ = base.count_colorful_batch(jnp.asarray(batch))
        got_b, _ = fused.count_colorful_batch(jnp.asarray(batch))
        np.testing.assert_allclose(np.asarray(got_b), np.asarray(want_b),
                                   rtol=1e-6)

    def test_fusion_ignored_off_pgbsc(self):
        g = erdos_renyi(30, 3.0, seed=1)
        e = build_engine(g, "u5", "pfascia", fuse_spmm_ema=True)
        assert not e.fuse_spmm_ema and not e.schedule.fused

    def test_budget_admits_larger_batch(self):
        g = erdos_renyi(200, 5.0, seed=2)
        base = build_engine(g, "u10", "pgbsc")
        fused = build_engine(g, "u10", "pgbsc", fuse_spmm_ema=True)
        assert fused.exec_choice.peak_bytes_per_coloring < \
            base.exec_choice.peak_bytes_per_coloring
        budget = 8 * base.exec_choice.peak_bytes_per_coloring
        e0 = build_engine(g, "u10", "pgbsc", memory_budget_bytes=budget)
        e1 = build_engine(g, "u10", "pgbsc", memory_budget_bytes=budget,
                          fuse_spmm_ema=True)
        assert e1.batch_size > e0.batch_size

    def test_f64_counts_match_f32_engine(self, x64):
        # counts are integer-valued; f64 fused path must agree exactly
        g = erdos_renyi(40, 3.5, seed=8)
        colors = coloring_numpy(0, 0, g.n, 5)
        want, _ = build_engine(g, "u5", "pgbsc").count_colorful(colors)
        e = build_engine(g, "u5", "pgbsc", dtype=jnp.float64,
                         fuse_spmm_ema=True)
        assert e.schedule.fused
        got, _ = e.count_colorful(colors)
        assert float(got) == float(want)


class TestExecutorFusedModel:
    def _plan(self, tname):
        return get_template(tname).plan_dedup

    def test_fused_peak_not_higher(self):
        plan = self._plan("u7")
        k = 7
        fused_nodes = tuple(
            i for i, nd in enumerate(plan.nodes) if not nd.is_leaf)
        s0 = pexec.compute_schedule(plan, k)
        s1 = pexec.compute_schedule(plan, k, fused=fused_nodes)
        p0 = pexec.simulate_peak_rows(plan, k, s0)
        p1 = pexec.simulate_peak_rows(plan, k, s1)
        assert p1 <= p0
        assert s1.fused_set == set(fused_nodes)

    def test_chunking_beats_fusion_on_conflict(self):
        # a node assigned both chunking and fusion must execute chunked:
        # the engine dispatch checks packs first, and the schedule keeps
        # both markers
        g = erdos_renyi(60, 4.0, seed=3)
        e = build_engine(g, "u10", "pgbsc", fuse_spmm_ema=True,
                         memory_budget_bytes=1 << 20)
        for idx in e.schedule.chunk_map:
            assert e.schedule.chunk_map[idx] >= 1
        colors = coloring_numpy(0, 0, g.n, 10)
        want, _ = build_engine(g, "u10", "pgbsc",
                               memory_budget_bytes=1 << 20
                               ).count_colorful(colors)
        got, _ = e.count_colorful(colors)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-6)


class TestAutotune:
    def test_ema_blocks_from_candidates(self):
        autotune.clear_cache()
        rng = np.random.default_rng(0)
        m_a = _rand_table(rng, (5, 256))
        y_p = _rand_table(rng, (10, 256))
        ia, ip = split_tables(5, 3, 1)
        ia, ip = jnp.asarray(ia), jnp.asarray(ip)
        blocks = autotune.ema_blocks(m_a, y_p, ia, ip, interpret=True)
        assert blocks in autotune.EMA_BLOCK_CANDIDATES
        # second call is a cache hit
        n_timed = len(autotune.cache_info())
        assert autotune.ema_blocks(m_a, y_p, ia, ip,
                                   interpret=True) == blocks
        assert len(autotune.cache_info()) == n_timed

    def test_autotuned_ema_matches_ref(self):
        from repro.kernels.ema.ops import ema
        from repro.kernels.ema.ref import ema_ref
        rng = np.random.default_rng(1)
        m_a = _rand_table(rng, (10, 300))
        y_p = _rand_table(rng, (10, 300))
        ia, ip = split_tables(5, 4, 2)
        ia, ip = jnp.asarray(ia), jnp.asarray(ip)
        got = ema(m_a, y_p, ia, ip, use_pallas=True, autotune=True)
        want = ema_ref(m_a, y_p, ia, ip)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=0)

    def test_engine_autotune_matches(self):
        g = erdos_renyi(50, 4.0, seed=4)
        colors = coloring_numpy(0, 0, g.n, 5)
        want, _ = build_engine(g, "u5", "pgbsc").count_colorful(colors)
        e = build_engine(g, "u5", "pgbsc", use_pallas_ema=True,
                         autotune_blocks=True)
        got, _ = e.count_colorful(colors)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-6)


class TestSharedPassiveKernel:
    """One launch, one SpMM leg, N consumers reading the same y tiles."""

    def _inputs(self, g, dtype=np.float32):
        # two consumers of one passive: (k=5, t=5, ta=3) and (k=5, t=4,
        # ta=2) — same c_p = C(5,2), different c_a/s/l per consumer
        rng = np.random.default_rng(11)
        m_p = _rand_table(rng, (comb(5, 2), g.n), dtype)
        m_as, ias, ips = [], [], []
        for t, ta in ((5, 3), (4, 2)):
            ia, ip = split_tables(5, t, ta)
            ias.append(jnp.asarray(ia))
            ips.append(jnp.asarray(ip))
            m_as.append(_rand_table(rng, (comb(5, ta), g.n), dtype))
        return m_as, m_p, ias, ips

    @pytest.mark.parametrize("gname", ["er_uneven", "grid", "empty"])
    def test_matches_oracle_per_consumer(self, gname):
        g = GRAPHS[gname]()
        m_as, m_p, ias, ips = self._inputs(g)
        outs = fused_spmm_ema_shared(m_as, m_p, ias, ips, prepare_fused(g))
        assert len(outs) == 2
        for m_a, ia, ip, got in zip(m_as, ias, ips, outs):
            want = _oracle(g, m_a, m_p, ia, ip)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-6)

    def test_batched(self):
        g = GRAPHS["er_uneven"]()
        m_as, m_p, ias, ips = self._inputs(g)
        b = 3
        m_p_b = jnp.stack([m_p * (i + 1) for i in range(b)])
        m_as_b = [jnp.stack([m * (i + 1) for i in range(b)]) for m in m_as]
        outs = fused_spmm_ema_shared(m_as_b, m_p_b, ias, ips,
                                     prepare_fused(g))
        for m_a, ia, ip, got in zip(m_as, ias, ips, outs):
            assert got.shape[0] == b
            for i in range(b):
                want = _oracle(g, m_a * (i + 1), m_p * (i + 1), ia, ip)
                np.testing.assert_allclose(np.asarray(got[i]),
                                           np.asarray(want), rtol=1e-6)

    def test_bf16_within_tolerance(self):
        g = GRAPHS["er_uneven"]()
        m_as, m_p, ias, ips = self._inputs(g)
        prep16 = prepare_fused(g, dtype=jnp.bfloat16)
        outs = fused_spmm_ema_shared(
            [m.astype(jnp.bfloat16) for m in m_as],
            m_p.astype(jnp.bfloat16), ias, ips, prep16)
        for m_a, ia, ip, got in zip(m_as, ias, ips, outs):
            want = np.asarray(_oracle(g, m_a, m_p, ia, ip), np.float64)
            err = np.abs(np.asarray(got, np.float64) - want)
            rel = err / np.maximum(np.abs(want), 1.0)
            assert rel.max() <= 1e-2

    def test_vmem_overflow_falls_back_exactly(self, monkeypatch):
        monkeypatch.setattr(kernels, "VMEM_BUDGET_BYTES", 1 << 12)
        g = GRAPHS["er_uneven"]()
        m_as, m_p, ias, ips = self._inputs(g)
        outs = fused_spmm_ema_shared(m_as, m_p, ias, ips, prepare_fused(g))
        for m_a, ia, ip, got in zip(m_as, ias, ips, outs):
            want = _oracle(g, m_a, m_p, ia, ip)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-6)


def _shared_passive_bundle():
    """Two k=5 trees (the same unrooted 'fork', rooted differently) whose
    dedup plan shares a path2 passive between T1's root and an interior
    node of T2 — the groupable shape: neither consumer's active is in the
    pair and T2's root runs after both."""
    from repro.core.templates import TreeTemplate
    t1 = TreeTemplate([(0, 1), (1, 2), (0, 3), (0, 4)], root=0,
                      name="sharedp_a")
    t2 = TreeTemplate([(0, 1), (1, 2), (2, 3), (1, 4)], root=0,
                      name="sharedp_b")
    return (t1, t2)


class TestSharedPassiveEngine:
    def test_group_forms_and_counts_match(self):
        g = erdos_renyi(80, 6.0, seed=9)
        bundle = _shared_passive_bundle()
        base = build_engine(g, bundle, "pgbsc", plan="dedup")
        shared = build_engine(g, bundle, "pgbsc", plan="dedup",
                              fuse_spmm_ema=True)
        assert shared.schedule.fused_groups, "expected a shared group"
        grp = shared.schedule.fused_groups[0]
        assert len(grp) == 2
        assert all(shared.fusion_report[m] == "admitted_shared"
                   for m in grp)
        # both group members consume the same passive child
        passives = {shared.plan.nodes[m].passive for m in grp}
        assert len(passives) == 1
        batch = jnp.stack(
            [jnp.asarray(coloring_numpy(0, i, g.n, 5)) for i in range(3)])
        want, _ = base.count_colorful_batch(batch)
        got, _ = shared.count_colorful_batch(batch)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6)

    def test_cols_drop_vs_per_consumer_fusion(self):
        g = erdos_renyi(80, 6.0, seed=9)
        shared = build_engine(g, _shared_passive_bundle(), "pgbsc",
                              plan="dedup", fuse_spmm_ema=True)
        cols = shared.spmm_cols_per_coloring
        # per-consumer fusion would re-run the shared passive's SpMM once
        # per extra member; the group pays it exactly once
        per_consumer = cols + sum(
            (len(grp) - 1) * comb(
                shared.k,
                shared.plan.nodes[shared.plan.nodes[grp[0]].passive].size)
            for grp in shared.schedule.fused_groups)
        assert cols < per_consumer
        # dispatch accounting follows the model
        batch = jnp.stack(
            [jnp.asarray(coloring_numpy(0, i, g.n, 5)) for i in range(2)])
        shared.count_colorful_batch(batch)
        assert shared.n_spmm_cols_dispatched == 2 * cols

    def test_cols_not_worse_than_ycache(self):
        # full-coverage admission: grouping must never dispatch more SpMM
        # columns than the unfused y-cache walk of the same plan
        g = erdos_renyi(80, 6.0, seed=9)
        bundle = _shared_passive_bundle()
        base = build_engine(g, bundle, "pgbsc", plan="dedup")
        shared = build_engine(g, bundle, "pgbsc", plan="dedup",
                              fuse_spmm_ema=True)
        assert shared.spmm_cols_per_coloring <= base.spmm_cols_per_coloring

    def test_chain_consumers_stay_on_ycache(self):
        # path-like shared passives are consumed through active chains: a
        # single launch cannot consume its own outputs, so no group forms
        g = erdos_renyi(60, 5.0, seed=10)
        e = build_engine(g, ("u5", "path5", "star5"), "pgbsc",
                         plan="dedup", fuse_spmm_ema=True)
        assert not e.schedule.fused_groups
        assert "admitted_shared" not in e.fusion_report.values()

    def test_bf16_group_engine_within_tolerance(self):
        g = erdos_renyi(80, 6.0, seed=9)
        bundle = _shared_passive_bundle()
        base = build_engine(g, bundle, "pgbsc", plan="dedup")
        e16 = build_engine(g, bundle, "pgbsc", plan="dedup",
                           fuse_spmm_ema=True, dtype=jnp.bfloat16,
                           reorder="rcm")
        assert e16.schedule.fused_groups
        batch = jnp.stack(
            [jnp.asarray(coloring_numpy(0, i, g.n, 5)) for i in range(2)])
        want, _ = base.count_colorful_batch(batch)
        got, _ = e16.count_colorful_batch(batch)
        want = np.asarray(want, np.float64)
        rel = np.abs(np.asarray(got, np.float64) - want) \
            / np.maximum(np.abs(want), 1.0)
        assert rel.max() <= 1e-2


class TestBf16Engine:
    @pytest.mark.parametrize("tname", ["u5", "u7"])
    @pytest.mark.parametrize("engine", ["fascia", "pfascia", "pgbsc"])
    def test_counts_within_tolerance(self, tname, engine):
        g = erdos_renyi(70, 5.0, seed=12)
        t = get_template(tname)
        base = build_engine(g, t, engine)
        e16 = build_engine(g, t, engine, dtype=jnp.bfloat16)
        batch = jnp.stack(
            [jnp.asarray(coloring_numpy(0, i, g.n, t.k)) for i in range(2)])
        want, _ = base.count_colorful_batch(batch)
        got, _ = e16.count_colorful_batch(batch)
        want = np.asarray(want, np.float64)
        rel = np.abs(np.asarray(got, np.float64) - want) \
            / np.maximum(np.abs(want), 1.0)
        assert rel.max() <= 1e-2

    def test_fused_bf16_matches_f32_within_tolerance(self):
        g = erdos_renyi(70, 5.0, seed=12)
        base = build_engine(g, "u5", "pgbsc")
        e16 = build_engine(g, "u5", "pgbsc", dtype=jnp.bfloat16,
                           fuse_spmm_ema=True)
        assert e16.schedule.fused, "bf16 must stay kernel-eligible"
        colors = coloring_numpy(0, 0, g.n, 5)
        want = float(base.count_colorful(colors)[0])
        got = float(e16.count_colorful(colors)[0])
        assert abs(got - want) / max(abs(want), 1.0) <= 1e-2
