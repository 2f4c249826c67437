"""The benchmark's graph: a Graph500 Kronecker (R-MAT) edge list.

The generator follows the Graph500 specification (one uniform draw per edge
per level picks the quadrant; vertex labels randomly permuted) and is kept
here so that the data the reference reads comes from the yardstick and not
from the system under test: the same seed gives the same edges, and the
service builds its own graph from them (``Graph.from_edges``) while the
reference builds its own simple undirected adjacency with
:func:`simple_adjacency`.
"""

from __future__ import annotations

import numpy as np


def _draw(rng: np.random.Generator, scale: int, m: int, a: float, b: float,
          c: float) -> np.ndarray:
    """``m`` Kronecker edges: one uniform draw per edge per level picks the
    quadrant, as in the Graph500 specification's generator."""
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    for level in range(scale):
        r = rng.random(m, dtype=np.float32)
        go_right = (r >= a) & (r < a + b) | (r >= a + b + c)
        go_down = r >= a + b
        src |= go_down.astype(np.int64) << level
        dst |= go_right.astype(np.int64) << level
    return np.stack([src, dst], axis=1)


def kronecker_edges(cfg: dict, seed: int) -> tuple[int, np.ndarray]:
    """``(n, (edges, 2) int64)``: the first ``cfg["edges"]`` distinct
    undirected non-loop edges, in the order drawn, of Kronecker edge lists
    of ``edgefactor * n`` edges each, drawn from ``seed`` (more lists only
    if one falls short), with the vertex labels permuted at random as the
    specification asks. Every seed thus gives a graph of the same size, and
    the service the same compiled shapes."""
    scale, ef = int(cfg["scale"]), int(cfg["edgefactor"])
    a, b, c = float(cfg["A"]), float(cfg["B"]), float(cfg["C"])
    n = 1 << scale
    want = int(cfg["edges"])
    rng = np.random.default_rng(seed)
    keys = np.zeros(0, dtype=np.int64)
    while True:
        e = _draw(rng, scale, n * ef, a, b, c)
        lo, hi = np.minimum(e[:, 0], e[:, 1]), np.maximum(e[:, 0], e[:, 1])
        k = np.concatenate([keys, (lo * n + hi)[lo != hi]])
        _, first = np.unique(k, return_index=True)
        keys = k[np.sort(first)]
        if keys.size >= want:
            keys = keys[:want]
            perm = rng.permutation(n)
            return n, perm[np.stack([keys // n, keys % n], axis=1)]


def simple_adjacency(n: int, edges: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Directed edge slots ``(src, dst)`` of the simple undirected graph on
    ``edges``: both directions, no self-loops, no duplicates, sorted by
    ``dst`` then ``src``. Written apart from the program's ``Graph``."""
    e = np.asarray(edges, dtype=np.int64)
    both = np.concatenate([e, e[:, ::-1]])
    both = both[both[:, 0] != both[:, 1]]
    key = np.unique(both[:, 1] * n + both[:, 0])
    return (key % n).astype(np.int32), (key // n).astype(np.int32)
