"""The plain reference against brute force, and the configurations'
template edge lists against the program's registry."""

import itertools
import json
import time

import jax
import numpy as np
import pytest

from bench.harness import reference
from bench.harness.cell import BENCH_DIR
from bench.harness.graph import kronecker_edges, simple_adjacency
from bench.harness.reference import Reference, automorphisms

U7 = [[0, 1], [0, 2], [1, 3], [1, 4], [2, 5], [2, 6]]
# the registry's u12: a spine of 7 vertices with a leaf on each of 1..5
U12 = [[i, i + 1] for i in range(6)] + [[s, 6 + s] for s in range(1, 6)]
U17 = [[i, i + 1] for i in range(10)] + [[s, 10 + s] for s in range(1, 7)]


def brute_force(n, src, dst, edges, colors):
    """Colorful maps of the template by trying every vertex tuple."""
    adj = set(zip(src.tolist(), dst.tolist()))
    k = len(edges) + 1
    total = 0
    for m in itertools.permutations(range(n), k):
        if len({int(colors[v]) for v in m}) < k:
            continue
        if all((m[u], m[v]) in adj for u, v in edges):
            total += 1
    return total


@pytest.mark.parametrize("edges", [
    [[0, 1], [1, 2]],
    [[0, 1], [0, 2], [0, 3]],
    [[0, 1], [1, 2], [2, 3], [1, 4]],
    [[0, 1], [1, 2], [0, 3], [3, 4]],
])
def test_counts_match_brute_force(edges):
    cfg = {"scale": 4, "edgefactor": 4, "edges": 20, "A": .57, "B": .19,
           "C": .19}
    n, e = kronecker_edges(cfg, 3)
    src, dst = simple_adjacency(n, e)
    ref = Reference(n, src, dst, edge_block=7)
    k = len(edges) + 1
    got = ref.counts(edges, 0, 11, 3)
    for it in range(3):
        colors = np.asarray(jax.random.randint(
            jax.random.fold_in(jax.random.PRNGKey(11), it), (n,), 0, k))
        assert got[it] == brute_force(n, src, dst, edges, colors)


def automorphisms_by_permutation(edges) -> int:
    """Automorphisms of the unrooted tree, by trying every permutation."""
    k = len(edges) + 1
    es = {frozenset(e) for e in edges}
    return sum(all(frozenset((p[u], p[v])) in es for u, v in edges)
               for p in itertools.permutations(range(k)))


def prufer_tree(seq) -> list[list[int]]:
    """The labelled tree on ``len(seq) + 2`` vertices with Prufer code
    ``seq``."""
    k = len(seq) + 2
    degree = [1] * k
    for x in seq:
        degree[x] += 1
    edges = []
    for x in seq:
        leaf = min(v for v in range(k) if degree[v] == 1)
        edges.append([leaf, x])
        degree[leaf] -= 1
        degree[x] -= 1
    u, v = [v for v in range(k) if degree[v] == 1]
    return edges + [[u, v]]


def unlabelled_trees(k: int) -> list[list[list[int]]]:
    """One labelled tree of each isomorphism class on ``k`` vertices: every
    Prufer code, kept where its least rooted form over all roots is new."""
    def form(kids, x, parent):
        return "(" + "".join(sorted(form(kids, y, x) for y in kids[x]
                                    if y != parent)) + ")"

    seen = {}
    for seq in itertools.product(range(k), repeat=k - 2):
        edges = prufer_tree(seq)
        kids = {v: [] for v in range(k)}
        for u, v in edges:
            kids[u].append(v)
            kids[v].append(u)
        seen.setdefault(min(form(kids, r, -1) for r in range(k)), edges)
    return list(seen.values())


SMALL_TREES = [t for k in range(2, 8) for t in unlabelled_trees(k)]
_rng = np.random.default_rng(1600)
RANDOM_TREES = [prufer_tree(_rng.integers(0, k, k - 2).tolist())
                for k in [8] * 20 + [9] * 20]


def test_small_trees_are_every_tree():
    # unlabelled trees on 2..7 vertices (OEIS A000055)
    assert [len(unlabelled_trees(k)) for k in range(2, 8)] == [
        1, 1, 2, 3, 6, 11]


@pytest.mark.parametrize("edges", SMALL_TREES + RANDOM_TREES,
                         ids=lambda e: "-".join(f"{u}.{v}" for u, v in e))
def test_automorphisms_match_permutations(edges):
    assert automorphisms(edges) == automorphisms_by_permutation(edges)


@pytest.mark.parametrize("edges, aut", [
    ([], 1),
    ([[0, 1]], 2),
    ([[0, 1], [1, 2]], 2),
    ([[0, 1], [0, 2], [0, 3]], 6),
    ([[0, i] for i in range(1, 8)], 5040),                  # star of 8
    ([[i, i + 1] for i in range(16)], 2),                   # path of 17
    (U7, 8),
    (U12, 8),
    (U17, 2),
    ([[(i - 1) // 2, i] for i in range(1, 13)], 16),        # u13
    ([[(i - 1) // 2, i] for i in range(1, 15)], 128),       # u15-2
    # two centres whose halves differ: a spine 0..3, two leaves on 1
    ([[0, 1], [1, 2], [2, 3], [1, 4]], 2),
    # two centres whose halves are alike: two leaves on each of 0 and 1
    ([[0, 1], [0, 2], [0, 3], [1, 4], [1, 5]], 8),
])
def test_automorphisms(edges, aut):
    assert automorphisms(edges) == aut


def test_large_template_scale_is_quick():
    t = time.perf_counter()
    assert automorphisms(U17) == 2
    assert time.perf_counter() - t < 0.1
    t = time.perf_counter()
    Reference.scale(U12)
    assert time.perf_counter() - t < 1.0


def _scale6_graph():
    cfg = {"scale": 6, "edgefactor": 16, "edges": 400, "A": .57, "B": .19,
           "C": .19}
    n, e = kronecker_edges(cfg, 5)
    return (n, *simple_adjacency(n, e))


def test_u12_counts_do_not_depend_on_the_root():
    ref = Reference(*_scale6_graph())
    at0 = ref.counts(U12, 0, 7, 3)
    assert np.all(at0 > 2 ** 24)       # past float32's exact integers
    np.testing.assert_array_equal(at0, ref.counts(U12, 6, 7, 3))


def test_u12_counts_do_not_depend_on_the_budget(monkeypatch):
    graph = _scale6_graph()
    want = Reference(*graph).counts(U12, 0, 7, 3)
    # 16 KiB: 4 edge slots a step for 792 columns, one vertex a step for
    # the 5,544 splits, and at least two of each for every table
    monkeypatch.setattr(reference, "_BLOCK_BUDGET_BYTES", 1 << 14)
    np.testing.assert_array_equal(Reference(*graph).counts(U12, 0, 7, 3),
                                  want)


def test_adjacency_is_simple_and_symmetric():
    n, e = kronecker_edges({"scale": 6, "edgefactor": 8, "edges": 200,
                            "A": .57, "B": .19, "C": .19}, 5)
    src, dst = simple_adjacency(n, e)
    pairs = set(zip(src.tolist(), dst.tolist()))
    assert len(pairs) == len(src)
    assert all((d, s) in pairs and s != d for s, d in pairs)
    assert np.all(np.diff(dst) >= 0)


@pytest.mark.parametrize("config", ["g500-s18"])
def test_named_templates_are_the_registry_trees(config):
    from repro.core.templates import TemplateSpec

    cfg = json.loads((BENCH_DIR / "configs" / f"{config}.json").read_text())
    for name, t in cfg["templates"].items():
        if "send" in t:
            mine = TemplateSpec.of([tuple(e) for e in t["edges"]])
            assert mine.canonical_hash == TemplateSpec.of(
                t["send"]).canonical_hash, name
