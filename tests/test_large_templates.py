"""Coverage for the paper's larger templates (u10-u17): plan consistency,
engine agreement across plan variants, estimator self-consistency, and
served estimates against the benchmark's plain reference."""

import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_engine, get_template
from repro.graph import erdos_renyi
from repro.graph.coloring import coloring_numpy
from repro.graph.generators import rmat
from repro.service import AsyncCountingService, CountRequest

ROOT = Path(__file__).resolve().parents[1]

BIG = ["u10", "u12", "u13", "u14", "u15-1", "u15-2", "u16", "u17"]


class TestLargeTemplatePlans:
    @pytest.mark.parametrize("name", BIG)
    def test_plan_variants_cover_template(self, name):
        t = get_template(name)
        for plan in (t.plan, t.plan_dedup, t.plan_optimized):
            assert plan.nodes[-1].size == t.k
            # every internal node partitions exactly
            for nd in plan.nodes:
                if not nd.is_leaf:
                    a = plan.nodes[nd.active]
                    p = plan.nodes[nd.passive]
                    assert a.size + p.size == nd.size

    @pytest.mark.parametrize("name", BIG)
    def test_optimized_plan_work_not_worse(self, name):
        from math import comb
        t = get_template(name)

        def ema_work(plan):
            w = 0
            for nd in plan.nodes:
                if nd.is_leaf:
                    continue
                ta = plan.nodes[nd.active].size
                w += comb(t.k, nd.size) * comb(nd.size, ta)
            return w

        assert ema_work(t.plan_optimized) <= ema_work(t.plan_dedup)


class TestLargeTemplateCounting:
    @pytest.mark.parametrize("name", ["u10", "u12"])
    def test_plan_variants_agree_exactly(self, name):
        # small graph so the run is quick; counts stay < 2^24 (exact f32)
        g = erdos_renyi(60, 3.0, seed=12)
        t = get_template(name)
        colors = coloring_numpy(8, 0, g.n, t.k)
        vals = []
        for plan in ("plain", "dedup", "optimized"):
            e = build_engine(g, t, "pgbsc", plan=plan)
            vals.append(float(e.count_colorful(colors)[0]))
        assert vals[0] == vals[1] == vals[2], (name, vals)

    def test_u13_binary_tree_runs(self):
        g = erdos_renyi(40, 3.0, seed=13)
        t = get_template("u13")
        e = build_engine(g, t, "pgbsc", plan="optimized")
        colors = coloring_numpy(9, 0, g.n, t.k)
        total, root = e.count_colorful(colors)
        assert np.isfinite(float(total))
        assert root.shape == (1, g.n)  # C(13,13) = 1 combo at the root

    def test_dedup_shrinks_all_big_plans(self):
        for name in BIG:
            t = get_template(name)
            assert t.plan_dedup.n_nodes < t.plan.n_nodes, name


# Served estimates against the plain reference of ``bench/harness``: the
# comparison that decides a benchmark run's ``correct``. The reference's
# colour-set DP shares nothing with the program (``core/oracle.py`` is
# exact but does not finish one u12 coloring on a 60-vertex graph in 90 s).
# On a Graph500 Kronecker graph of scale 7 (128 vertices, 1,896 slots) the
# float32 path reassociates sums only, and stays under 1e-7 (both
# templates, three seeds); bf16 storage rounds every table to 8 bits of
# mantissa and reads 1.2e-4 to 4.8e-4 at the worst seed. The tolerance is
# the benchmark's limit on ``estimate_rel_gap``, between the two.
SERVED_TOL = 1e-5
SERVED_SEEDS = (11, 12, 13)
SERVED_COLORINGS = 2


@pytest.fixture(scope="module")
def kron7():
    """A seeded Graph500 Kronecker graph and the reference built apart
    from the program's ``Graph`` (its own simple adjacency)."""
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from bench.harness.graph import simple_adjacency
    from bench.harness.reference import Reference
    g = rmat(7, 16, a=0.57, b=0.19, c=0.19, seed=5)
    src, dst = simple_adjacency(g.n, np.stack(g.edges_by_dst, axis=1))
    return g, Reference(g.n, src, dst)


class TestServedAgainstReference:
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("name", ["u7", "u12"])
    def test_served_estimates_match_reference(self, kron7, tmp_path, name,
                                              dtype):
        g, ref = kron7
        t = get_template(name)
        svc = AsyncCountingService(
            ledger_root=str(tmp_path / "ledger"), round_size=2,
            idle_wait_s=0.01, engine_kw={"dtype": getattr(jnp, dtype)})
        svc.add_graph("g", g)
        with svc:
            rids = [svc.submit(CountRequest("g", template=name, seed=s,
                                            max_iters=SERVED_COLORINGS))
                    for s in SERVED_SEEDS]
            assert svc.drain(timeout=300.0)
        gaps = []
        for rid, seed in zip(rids, SERVED_SEEDS):
            res = svc.result(rid)
            assert res.iterations == SERVED_COLORINGS
            want = (ref.counts(t.edges, t.root, seed, SERVED_COLORINGS)
                    .mean() * ref.scale(t.edges))
            assert want > 0
            gaps.append(abs(res.estimate - want) / want)
        if dtype == "float32":
            assert max(gaps) <= SERVED_TOL, gaps
        else:
            # the control: a narrower storage must fail the same limit
            assert max(gaps) > SERVED_TOL, gaps
