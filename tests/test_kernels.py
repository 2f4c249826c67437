"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracles,
swept over shapes, graph families, and block sizes."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.graph import erdos_renyi, grid_2d, rmat, star
from repro.graph.reorder import apply_order, degree_order, rcm_order
from repro.graph.structure import Graph
from repro.kernels.ema.ops import ema, ema_xla
from repro.kernels.ema.pallas_ema import ema_pallas
from repro.kernels.ema.ref import ema_ref
from repro.kernels.fused.ops import prepare_fused
from repro.kernels.spmm import ops as spmm_ops
from repro.kernels.spmm.pallas_bsr import spmm_bsr_pallas
from repro.kernels.spmm.pallas_gather import spmm_gather_pallas
from repro.kernels.spmm.ref import spmm_dense, spmm_segment_ref


def _rand_table(rng, c, n, dtype=np.float32):
    return jnp.asarray(rng.integers(0, 4, size=(c, n)).astype(dtype))


GRAPHS = {
    "er_small": lambda: erdos_renyi(96, 4.0, seed=0),
    "er_uneven": lambda: erdos_renyi(130, 7.0, seed=1),   # n % 128 != 0
    "grid": lambda: grid_2d(12, 11),
    "star_skew": lambda: star(150),
    "rmat": lambda: rmat(8, 8, seed=2),
}


class TestSpmmXlaBackends:
    @pytest.mark.parametrize("gname", sorted(GRAPHS))
    @pytest.mark.parametrize("method", ["segment", "ell"])
    @pytest.mark.parametrize("c", [1, 5, 33])
    def test_matches_dense_oracle(self, gname, method, c):
        g = GRAPHS[gname]()
        rng = np.random.default_rng(42)
        m = _rand_table(rng, c, g.n)
        want = spmm_dense(m, jnp.asarray(g.to_dense()))
        prep = spmm_ops.prepare(g, method)
        got = spmm_ops.spmm(m, prep)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0)

    def test_segment_ref_matches_dense(self):
        g = GRAPHS["er_small"]()
        rng = np.random.default_rng(0)
        m = _rand_table(rng, 7, g.n)
        src, dst = g.edges_by_dst
        got = spmm_segment_ref(m, jnp.asarray(src), jnp.asarray(dst), g.n)
        want = spmm_dense(m, jnp.asarray(g.to_dense()))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0)


def _isolated_graph():
    """A path and a triangle among 40 vertices: most vertices have no edge,
    so many dst segments are empty (leading, interior and trailing)."""
    return Graph.from_edges(40, np.array([[3, 4], [4, 5], [5, 6],
                                          [20, 21], [21, 22], [22, 20]]))


SEGMENT_GRAPHS = {
    "er_small": GRAPHS["er_small"],
    "star_hub": GRAPHS["star_skew"],     # one vertex holds half the slots
    "isolated": _isolated_graph,
}


class TestSpmmSegment:
    """``_spmm_segment``: sorted scatter, rows in one step or in budgeted
    chunks, exact against the unchunked reference and the dense oracle."""

    @pytest.mark.parametrize("gname", sorted(SEGMENT_GRAPHS))
    @pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    # (rows, rows the patched budget admits): below, at and across it, a
    # ragged last chunk, and a budget under 8 rows
    @pytest.mark.parametrize("c,fit", [(3, 8), (8, 8), (20, 8), (11, 11),
                                       (12, 11), (5, 3), (40, None)])
    def test_matches_ref_and_dense(self, monkeypatch, gname, dtype, c, fit):
        g = SEGMENT_GRAPHS[gname]()
        src, dst = g.edges_by_dst
        if fit is not None:     # acc dtype is f32 for both storages
            monkeypatch.setattr(spmm_ops, "_SEGMENT_GATHER_BUDGET_BYTES",
                                fit * src.size * 4)
        rng = np.random.default_rng(c)
        m = _rand_table(rng, c, g.n).astype(dtype)
        got = spmm_ops._spmm_segment(m, jnp.asarray(src), jnp.asarray(dst),
                                     g.n)
        assert got.dtype == m.dtype and got.shape == m.shape
        m32 = m.astype(jnp.float32)
        ref = spmm_segment_ref(m32, jnp.asarray(src), jnp.asarray(dst), g.n)
        dense = spmm_dense(m32, jnp.asarray(g.to_dense()))
        # integer tables: f32 sums are exact, one cast back to the storage
        for want in (ref, dense):
            np.testing.assert_allclose(
                np.asarray(got, np.float32),
                np.asarray(want.astype(dtype), np.float32), rtol=0)

    @pytest.mark.parametrize("c,e,itemsize,budget,want", [
        (70, 7_600_000, 4, None, 70),         # g500-s18 u7 node 3: one step
        (14, 7_600_000, 4, None, 14),         # its node 1
        (280, 31_403_422, 4, None, 32),       # RMAT-20 at 8 colorings
        (280, 31_403_422, 2, None, 64),
        (20, 100, 4, 8 * 100 * 4, 8),
        (20, 100, 4, 13 * 100 * 4, 8),        # largest multiple of 8
        (20, 100, 4, 5 * 100 * 4, 5),         # under 8 rows fit
        (20, 100, 4, 10, 1),                  # not one row fits
        (3, 0, 4, 1, 1),                      # no edges
    ])
    def test_row_chunk(self, monkeypatch, c, e, itemsize, budget, want):
        if budget is not None:
            monkeypatch.setattr(spmm_ops, "_SEGMENT_GATHER_BUDGET_BYTES",
                                budget)
        assert spmm_ops._segment_row_chunk(c, e, itemsize) == want

    def test_no_edges(self):
        g = Graph.from_edges(9, np.zeros((0, 2), np.int64))
        m = _rand_table(np.random.default_rng(0), 4, g.n)
        got = spmm_ops.spmm(m, spmm_ops.prepare(g, "segment"))
        np.testing.assert_array_equal(np.asarray(got), 0)

    @pytest.mark.parametrize("method", ["segment", "pallas_gather",
                                        "pallas_bsr", "fused"])
    def test_prepare_refuses_unsorted_dst(self, monkeypatch, method):
        g = GRAPHS["er_small"]()
        src, dst = g.edges_by_dst
        perm = np.random.default_rng(0).permutation(src.size)
        monkeypatch.setattr(Graph, "edges_by_dst",
                            property(lambda self: (src[perm], dst[perm])))
        with pytest.raises(ValueError, match="sorted by dst"):
            if method == "fused":
                prepare_fused(g)
            else:
                spmm_ops.prepare(g, method)

    @pytest.mark.parametrize("gname", sorted(GRAPHS) + ["rmat_rcm",
                                                        "rmat_degree"])
    def test_edges_by_dst_sorted(self, gname):
        orders = {"rmat_rcm": rcm_order, "rmat_degree": degree_order}
        if gname in orders:
            g = GRAPHS["rmat"]()
            g = apply_order(g, orders[gname](g))
        else:
            g = GRAPHS[gname]()
        src, dst = g.edges_by_dst
        assert src.size == dst.size == g.m
        assert np.all(np.diff(dst) >= 0)


class TestSpmmPallas:
    @pytest.mark.parametrize("gname", sorted(GRAPHS))
    @pytest.mark.parametrize("method", ["pallas_gather", "pallas_bsr"])
    @pytest.mark.parametrize("c", [3, 20])
    def test_matches_dense_oracle(self, gname, method, c):
        g = GRAPHS[gname]()
        rng = np.random.default_rng(7)
        m = _rand_table(rng, c, g.n)
        want = spmm_dense(m, jnp.asarray(g.to_dense()))
        prep = spmm_ops.prepare(g, method)
        got = spmm_ops.spmm(m, prep)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0)

    @pytest.mark.parametrize("tile,chunk", [(128, 128), (128, 512), (256, 256)])
    def test_gather_tile_chunk_sweep(self, tile, chunk):
        g = erdos_renyi(100, 6.0, seed=3)
        gp = g.padded(tile)
        ch = gp.edge_chunks(tile=tile, chunk_size=chunk)
        rng = np.random.default_rng(1)
        m = _rand_table(rng, 9, gp.n)
        got = spmm_gather_pallas(
            m, jnp.asarray(ch.src), jnp.asarray(ch.dst_local),
            jnp.asarray(ch.mask), jnp.asarray(ch.src_tile),
            jnp.asarray(ch.dst_tile), n_tiles=ch.n_tiles, tile=tile,
            c_block=8)
        want = spmm_dense(m, jnp.asarray(gp.to_dense()))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0)

    @pytest.mark.parametrize("method", ["pallas_gather", "pallas_bsr"])
    def test_c_smaller_than_c_block(self, method):
        g = GRAPHS["er_small"]()
        rng = np.random.default_rng(11)
        m = _rand_table(rng, 3, g.n)
        got = spmm_ops.spmm(m, spmm_ops.prepare(g, method), c_block=64)
        want = spmm_dense(m, jnp.asarray(g.to_dense()))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0)

    def test_bsr_after_rcm_has_fewer_blocks(self):
        g = grid_2d(32, 32)
        base = g.bsr(tile=128)
        rcm = apply_order(g, rcm_order(g)).bsr(tile=128)
        assert rcm.n_blocks <= base.n_blocks

    def test_bsr_kernel_direct(self):
        g = erdos_renyi(300, 5.0, seed=5).padded(128)
        bs = g.bsr(tile=128)
        rng = np.random.default_rng(2)
        m = _rand_table(rng, 16, g.n)
        got = spmm_bsr_pallas(m, jnp.asarray(bs.blocks),
                              jnp.asarray(bs.src_tile),
                              jnp.asarray(bs.dst_tile),
                              n_tiles=bs.n_tiles, tile=128, c_block=16)
        want = spmm_dense(m, jnp.asarray(g.to_dense()))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0)


class TestEma:
    @pytest.mark.parametrize("k,t,ta", [(5, 2, 1), (5, 3, 1), (7, 4, 2),
                                        (9, 5, 2)])
    @pytest.mark.parametrize("n", [64, 130, 512])
    def test_xla_matches_ref(self, k, t, ta, n):
        from repro.core.colorsets import split_tables
        from math import comb
        ia, ip = split_tables(k, t, ta)
        rng = np.random.default_rng(k * 100 + t)
        m_a = _rand_table(rng, comb(k, ta), n)
        y_p = _rand_table(rng, comb(k, t - ta), n)
        want = ema_ref(m_a, y_p, jnp.asarray(ia), jnp.asarray(ip))
        got = ema_xla(m_a, y_p, jnp.asarray(ia), jnp.asarray(ip))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0)

    @pytest.mark.parametrize("k,t,ta", [(5, 3, 1), (7, 4, 2)])
    @pytest.mark.parametrize("n", [128, 300])
    @pytest.mark.parametrize("s_block", [4, 8])
    def test_pallas_matches_ref(self, k, t, ta, n, s_block):
        from repro.core.colorsets import split_tables
        from math import comb
        ia, ip = split_tables(k, t, ta)
        rng = np.random.default_rng(k * 10 + ta)
        m_a = _rand_table(rng, comb(k, ta), n)
        y_p = _rand_table(rng, comb(k, t - ta), n)
        want = ema_ref(m_a, y_p, jnp.asarray(ia), jnp.asarray(ip))
        got = ema_pallas(m_a, y_p, jnp.asarray(ia), jnp.asarray(ip),
                         s_block=s_block, n_block=256)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0)

    def test_dispatch_fallback(self):
        # huge tables skip the pallas path but remain correct
        from repro.core.colorsets import split_tables
        ia, ip = split_tables(5, 3, 1)
        rng = np.random.default_rng(3)
        m_a = _rand_table(rng, 5, 64)
        y_p = _rand_table(rng, 10, 64)
        want = ema_ref(m_a, y_p, jnp.asarray(ia), jnp.asarray(ip))
        got = ema(m_a, y_p, jnp.asarray(ia), jnp.asarray(ip), use_pallas=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0)


def _split_pair(k, t, ta):
    from repro.core.colorsets import split_tables
    ia, ip = split_tables(k, t, ta)
    return jnp.asarray(ia), jnp.asarray(ip)


class TestBatchedKernels:
    """The Pallas kernels fold leading batch dims into the grid — no
    ``lax.map`` loop over colorings."""

    @pytest.mark.parametrize("b", [1, 3])
    @pytest.mark.parametrize("n", [130, 300])
    def test_ema_pallas_batched(self, b, n):
        from math import comb
        ia, ip = _split_pair(7, 4, 2)
        rng = np.random.default_rng(b * 10 + n)
        m_a = jnp.asarray(
            rng.integers(0, 4, size=(b, comb(7, 2), n)).astype(np.float32))
        y_p = jnp.asarray(
            rng.integers(0, 4, size=(b, comb(7, 2), n)).astype(np.float32))
        got = ema_pallas(m_a, y_p, ia, ip, s_block=8, n_block=256)
        assert got.shape == (b, comb(7, 4), n)
        for i in range(b):
            want = ema_ref(m_a[i], y_p[i], ia, ip)
            np.testing.assert_allclose(np.asarray(got[i]),
                                       np.asarray(want), rtol=0)

    def test_ema_dispatch_batched(self):
        ia, ip = _split_pair(5, 3, 1)
        rng = np.random.default_rng(4)
        m_a = jnp.asarray(
            rng.integers(0, 4, size=(2, 5, 200)).astype(np.float32))
        y_p = jnp.asarray(
            rng.integers(0, 4, size=(2, 10, 200)).astype(np.float32))
        got = ema(m_a, y_p, ia, ip, use_pallas=True)
        for i in range(2):
            want = ema_ref(m_a[i], y_p[i], ia, ip)
            np.testing.assert_allclose(np.asarray(got[i]),
                                       np.asarray(want), rtol=0)

    def test_ema_chunked_batched(self):
        from math import comb
        from repro.kernels.ema.ops import ema_chunked, pack_chunked_splits
        from repro.kernels.spmm.ref import spmm_dense
        g = GRAPHS["er_uneven"]()
        ia, ip = _split_pair(5, 3, 2)
        pack = pack_chunked_splits(np.asarray(ia), np.asarray(ip),
                                   comb(5, 1), 2)
        rng = np.random.default_rng(5)
        m_a = jnp.asarray(
            rng.integers(0, 4, size=(3, comb(5, 2), g.n)).astype(np.float32))
        m_p = jnp.asarray(
            rng.integers(0, 4, size=(3, comb(5, 1), g.n)).astype(np.float32))
        adj = jnp.asarray(g.to_dense())
        got = ema_chunked(m_a, m_p, pack, lambda m: spmm_dense(m, adj))
        for i in range(3):
            want = ema_ref(m_a[i], spmm_dense(m_p[i], adj), ia, ip)
            np.testing.assert_allclose(np.asarray(got[i]),
                                       np.asarray(want), rtol=0)


class TestKernelDtypes:
    """dtype is threaded through out_shape, accumulators, and casts —
    unsupported dtypes take the XLA path explicitly, never a silent
    float32 downcast."""

    def test_ema_pallas_float64(self, x64):
        ia, ip = _split_pair(5, 3, 2)
        rng = np.random.default_rng(1)
        m_a = jnp.asarray(
            rng.integers(0, 4, size=(10, 200)).astype(np.float64))
        y_p = jnp.asarray(
            rng.integers(0, 4, size=(5, 200)).astype(np.float64))
        got = ema_pallas(m_a, y_p, ia, ip, s_block=8, n_block=256)
        assert got.dtype == jnp.float64
        want = ema_ref(m_a, y_p, ia, ip)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0)

    @pytest.mark.parametrize("method", ["pallas_gather", "pallas_bsr"])
    def test_spmm_pallas_float64(self, x64, method):
        g = GRAPHS["er_uneven"]()
        rng = np.random.default_rng(2)
        m = jnp.asarray(rng.integers(0, 4, size=(9, g.n)).astype(np.float64))
        prep = spmm_ops.prepare(g, method)
        got = spmm_ops.spmm(m, prep)
        assert got.dtype == jnp.float64
        want = spmm_dense(m, jnp.asarray(g.to_dense()).astype(jnp.float64))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0)

    @pytest.mark.parametrize("method", ["pallas_gather", "pallas_bsr"])
    def test_spmm_unsupported_dtype_falls_back(self, method):
        # float16 is outside the interpret dtype set: dispatch must use the
        # segment-sum fallback and preserve the dtype
        g = GRAPHS["er_small"]()
        rng = np.random.default_rng(3)
        m = jnp.asarray(rng.integers(0, 4, size=(5, g.n)).astype(np.float16))
        got = spmm_ops.spmm(m, spmm_ops.prepare(g, method))
        assert got.dtype == jnp.float16
        want = spmm_dense(m.astype(jnp.float32), jnp.asarray(g.to_dense()))
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want), rtol=1e-3)

    def test_pallas_supports_dtype_sets(self):
        from repro.kernels.ema.ops import pallas_supports_dtype
        assert pallas_supports_dtype(jnp.float32, True)
        assert pallas_supports_dtype(jnp.float64, True)
        assert pallas_supports_dtype(jnp.bfloat16, True)
        assert not pallas_supports_dtype(jnp.float16, True)
        # the compiled TPU path is f32-only until widened deliberately
        assert pallas_supports_dtype(jnp.float32, False)
        assert not pallas_supports_dtype(jnp.float64, False)

    def test_engine_f64_pallas_matches_xla(self, x64):
        # the headline regression: a dtype=float64 engine on the Pallas
        # kernel paths must agree with the XLA path at f64 — before the
        # fix the kernels silently downcast to f32
        from repro.core import build_engine
        from repro.graph.coloring import coloring_numpy
        g = GRAPHS["er_small"]()
        colors = coloring_numpy(0, 0, g.n, 5)
        xla = build_engine(g, "u5", "pgbsc", dtype=jnp.float64)
        pal = build_engine(g, "u5", "pgbsc", dtype=jnp.float64,
                           spmm_method="pallas_bsr", use_pallas_ema=True)
        want, _ = xla.count_colorful(colors)
        got, _ = pal.count_colorful(colors)
        assert want.dtype == got.dtype == jnp.float64
        assert float(got) == float(want)
