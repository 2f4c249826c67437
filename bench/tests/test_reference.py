"""The plain reference against brute force, and the configurations'
template edge lists against the program's registry."""

import itertools
import json

import jax
import numpy as np
import pytest

from bench.harness.cell import BENCH_DIR
from bench.harness.graph import kronecker_edges, simple_adjacency
from bench.harness.reference import Reference, automorphisms


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


def test_automorphisms():
    assert automorphisms([[0, 1], [1, 2]]) == 2
    assert automorphisms([[0, 1], [0, 2], [0, 3]]) == 6
    assert automorphisms([[0, 1], [0, 2], [1, 3], [1, 4], [2, 5],
                          [2, 6]]) == 8


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
