"""Least bytes agree with the program's fused byte model per plan node."""

from math import comb

import pytest

from bench.harness import least
from repro.analysis.roofline import spmm_ema_hbm_bytes

U7 = [[0, 1], [0, 2], [1, 3], [1, 4], [2, 5], [2, 6]]


@pytest.mark.parametrize("k,sa,sp", [(7, 1, 1), (7, 1, 3), (7, 3, 3),
                                     (7, 4, 3), (5, 2, 2), (12, 5, 6)])
@pytest.mark.parametrize("itemsize", [4, 2])
def test_node_bytes_is_the_fused_model(k, sa, sp, itemsize):
    n, adj = 4096, 4 * 96444 + 4 * 4097
    s = sa + sp
    assert least.node_bytes(n, comb(k, sa), comb(k, sp), comb(k, s), adj,
                            itemsize) == spmm_ema_hbm_bytes(
        1, n, comb(k, sa), comb(k, sp), comb(k, s), adj, itemsize,
        fused=True)


def test_u7_plan_bytes():
    n, e = 1000, 20000
    b, f = least.least_per_coloring(U7, 0, n, e, 4)
    # u7 = root with two identical 3-vertex subtrees: its one plan has the
    # distinct nodes (1+1 -> 2), (2+1 -> 3), (1+3 -> 4) and (4+3 -> 7),
    # and two distinct passive pieces (a vertex, the 3-vertex subtree)
    adj = 4 * e + 4 * (n + 1)
    tables = (7 + 7 + 21) + (21 + 7 + 35) + (7 + 35 + 35) + (35 + 35 + 1)
    assert b == n * tables * 4 + 2 * adj
    assert f > 0


def test_least_seconds_takes_the_larger_bound():
    peaks = {"hbm_bytes_per_s": 1e9, "flops_per_s": 1e9}
    b, f = least.least_per_coloring(U7, 0, 100, 400, 4)
    assert least.least_seconds(U7, 0, 100, 400, 4, peaks) == max(b, f) / 1e9
