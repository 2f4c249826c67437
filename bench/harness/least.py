"""Least work of one coloring: the bytes and operations any plan must spend.

A plan splits a rooted template at one child edge of its root into an
*active* piece (the root's side) and a *passive* piece (the child's
subtree), recursively, down to single vertices. Each split is one plan node:
it reads the active table ``C(k, s_a) x n`` and the passive table
``C(k, s_p) x n`` once and writes its output ``C(k, s) x n`` once, fused,
with no neighbour-sum table round trip; the adjacency is read once per
distinct passive piece as CSR (4-byte column ids and row pointers). Pieces
that are the same rooted tree are counted once. The bytes of one node are
:func:`node_bytes`, which is ``analysis/roofline.spmm_ema_hbm_bytes`` at
``fused=True`` and batch 1.

The least bytes and the least operations are each minimised over every
plan, so ``max(bytes / HBM peak, ops / compute peak)`` is a lower bound on
one coloring's device time whatever plan or kernel the program uses.
"""

from __future__ import annotations

from functools import lru_cache
from math import comb


def rooted(edges, root: int) -> tuple:
    """Canonical rooted tree: a sorted tuple of the children's forms."""
    nbr: dict[int, list[int]] = {}
    for u, v in edges:
        nbr.setdefault(u, []).append(v)
        nbr.setdefault(v, []).append(u)

    def form(x: int, parent: int) -> tuple:
        return tuple(sorted(form(y, x) for y in nbr.get(x, []) if y != parent))

    return form(root, -1)


def size(t: tuple) -> int:
    return 1 + sum(size(c) for c in t)


def node_bytes(n: int, c_a: int, c_p: int, s: int, adj_bytes: int,
               itemsize: int) -> int:
    """One fused plan node over one coloring: tables in, table out, plus the
    adjacency stream charged to this node."""
    return n * (c_a + c_p + s) * itemsize + adj_bytes


def node_flops(n: int, e: int, c_p: int, s: int, splits: int) -> int:
    """One add and one multiply per edge slot per passive color set, and per
    vertex per (output set, split)."""
    return 2 * e * c_p + 2 * n * s * splits


@lru_cache(maxsize=None)
def _plans(t: tuple) -> frozenset:
    """Every plan of rooted tree ``t`` as a frozenset of its distinct nodes
    ``(active, passive)``."""
    if not t:
        return frozenset([frozenset()])
    out = set()
    for i, child in enumerate(t):
        active = t[:i] + t[i + 1:]
        for pa in _plans(active):
            for pp in _plans(child):
                out.add(frozenset({(active, child)}) | pa | pp)
    return frozenset(out)


def least_per_coloring(edges, root: int, n: int, e: int,
                       itemsize: int) -> tuple[int, int]:
    """``(least bytes, least operations)`` of one coloring of the template
    on a graph of ``n`` vertices and ``e`` directed edge slots."""
    t = rooted(edges, root)
    k = size(t)
    adj = 4 * e + 4 * (n + 1)
    best_b = best_f = None
    for plan in _plans(t):
        b = f = 0
        for active, passive in plan:
            sa, sp = size(active), size(passive)
            s = sa + sp
            b += node_bytes(n, comb(k, sa), comb(k, sp), comb(k, s), 0,
                            itemsize)
            f += node_flops(n, e, comb(k, sp), comb(k, s), comb(s, sa))
        b += adj * len({p for _, p in plan})
        best_b = b if best_b is None else min(best_b, b)
        best_f = f if best_f is None else min(best_f, f)
    return best_b, best_f


def least_seconds(edges, root: int, n: int, e: int, itemsize: int,
                  peaks: dict) -> float:
    """Least device seconds of one coloring on a chip with ``peaks``."""
    b, f = least_per_coloring(edges, root, n, e, itemsize)
    return max(b / peaks["hbm_bytes_per_s"], f / peaks["flops_per_s"])
