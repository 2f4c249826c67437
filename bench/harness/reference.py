"""Plain color-coding reference, written apart from the program.

For a tree template T with k vertices and a coloring c of the graph's
vertices with k colors, it counts the colorful maps of T into G: maps that
send template edges to graph edges and use every color once. That number is
``aut(T)`` times the colorful copies of T, so one sample of the estimator is
``count / (aut(T) * k! / k^k)`` (Alon, Yuster and Zwick's color coding).

The count is a dynamic program over the rooted template. A table holds, for
every graph vertex and every set of ``s`` colors (a k-bit mask with ``s``
bits), the maps of a rooted piece with ``s`` vertices whose root lands on
that vertex. Adding a child's subtree to a piece sums the child's table over
each vertex's neighbours, then multiplies over every split of a color set.
Color sets are plain bitmasks here, not the program's combination indices,
and the neighbour sum is a segment sum over the edge list in blocks.

Colorings follow the service's documented sample rule: sample ``i`` of a
request with seed ``s`` colors vertex ``v`` with
``jax.random.randint(fold_in(PRNGKey(s), i), (n,), 0, k)[v]``.
"""

from __future__ import annotations

import itertools
import math
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

EDGE_BLOCK = 1 << 20


def automorphisms(edges) -> int:
    """Automorphisms of the unrooted tree, by trying every permutation."""
    k = len(edges) + 1
    es = {frozenset(e) for e in edges}
    return sum(all(frozenset((p[u], p[v])) in es for u, v in edges)
               for p in itertools.permutations(range(k)))


def colorful_probability(k: int) -> float:
    return math.factorial(k) / k ** k


def _children(edges, root: int) -> dict[int, list[int]]:
    nbr: dict[int, list[int]] = {}
    for u, v in edges:
        nbr.setdefault(u, []).append(v)
        nbr.setdefault(v, []).append(u)
    kids, seen, stack = {}, {root}, [root]
    while stack:
        x = stack.pop()
        kids[x] = sorted(y for y in nbr.get(x, []) if y not in seen)
        seen.update(kids[x])
        stack.extend(kids[x])
    return kids


def _shapes(kids: dict[int, list[int]], root: int) -> dict[int, str]:
    """A canonical name of the rooted piece below each template vertex:
    two vertices get one name exactly when their pieces are isomorphic."""
    out: dict[int, str] = {}

    def name(x: int) -> str:
        out[x] = "(" + "".join(sorted(name(c) for c in kids[x])) + ")"
        return out[x]

    name(root)
    return out


@lru_cache(maxsize=None)
def _masks(k: int, s: int) -> tuple[int, ...]:
    return tuple(m for m in range(1 << k) if bin(m).count("1") == s)


@lru_cache(maxsize=None)
def _splits(k: int, sa: int, sb: int) -> tuple[np.ndarray, np.ndarray]:
    """Column indices ``(ia, ib)``, each ``(C(k, sa+sb), C(sa+sb, sa))``:
    row ``j`` lists every way to split output set ``j`` into a set of
    ``sa`` colors (column of the piece's table) and the rest (column of the
    child's neighbour-summed table)."""
    pos_a = {m: i for i, m in enumerate(_masks(k, sa))}
    pos_b = {m: i for i, m in enumerate(_masks(k, sb))}
    ia, ib = [], []
    for m in _masks(k, sa + sb):
        bits = [1 << i for i in range(k) if m >> i & 1]
        ra, rb = [], []
        for sub in itertools.combinations(bits, sa):
            ma = sum(sub)
            ra.append(pos_a[ma])
            rb.append(pos_b[m ^ ma])
        ia.append(ra)
        ib.append(rb)
    return np.asarray(ia, np.int32), np.asarray(ib, np.int32)


class Reference:
    """Colorful-map counts on one graph, computed on the default device in
    float32 (no matrix products: sums and elementwise products only)."""

    def __init__(self, n: int, src: np.ndarray, dst: np.ndarray,
                 edge_block: int = EDGE_BLOCK):
        self.n = int(n)
        e = int(src.shape[0])
        blk = max(1, min(edge_block, e))
        nb = -(-e // blk)
        pad = nb * blk - e
        # padded slots add into a spare segment ``n`` that is dropped
        s = np.concatenate([src, np.zeros(pad, np.int32)]).reshape(nb, blk)
        d = np.concatenate([dst, np.full(pad, n, np.int32)]).reshape(nb, blk)
        self._src, self._dst = jnp.asarray(s), jnp.asarray(d)
        self._fns: dict = {}
        # colorings per device call: keep (batch x n) near a million
        self.batch = max(1, (1 << 20) // max(self.n, 1))

    def _neighbour_sum(self, t: jnp.ndarray, src: jnp.ndarray,
                       dst: jnp.ndarray) -> jnp.ndarray:
        """``y[v] = sum of t[u] over edge slots (u, v)``; t is (n, c), the
        slots ``(src, dst)`` in blocks."""
        n = self.n

        def body(acc, blk):
            s, d = blk
            return acc + jax.ops.segment_sum(
                t[s], d, num_segments=n + 1, indices_are_sorted=True), None

        acc0 = jnp.zeros((n + 1, t.shape[1]), t.dtype)
        acc, _ = jax.lax.scan(body, acc0, (src, dst))
        return acc[:n]

    def _program(self, edges: tuple, root: int):
        k = len(edges) + 1
        kids = _children(edges, root)
        shape = _shapes(kids, root)

        # the edge arrays are arguments, not constants: the compiled program
        # then does not depend on the graph, and the compile cache keeps it
        def totals(src, dst, seed_key, iters):
            def one(it):
                colors = jax.random.randint(
                    jax.random.fold_in(seed_key, it), (self.n,), 0, k,
                    dtype=jnp.int32)
                # pieces of one shape have one table: each is computed once
                memo: dict[str, tuple[jnp.ndarray, int]] = {}

                def table(x: int) -> tuple[jnp.ndarray, int]:
                    if shape[x] in memo:
                        return memo[shape[x]]
                    t = (colors[:, None] == jnp.arange(k, dtype=colors.dtype)
                         ).astype(jnp.float32)            # sets of size 1
                    size = 1
                    for c in kids[x]:
                        tc, sc = table(c)
                        y = self._neighbour_sum(tc, src, dst)
                        ia, ib = _splits(k, size, sc)
                        t = jnp.sum(t[:, ia] * y[:, ib], axis=-1)
                        size += sc
                    memo[shape[x]] = (t, size)
                    return t, size

                t, _ = table(root)
                return jnp.sum(t[:, 0])
            return jax.lax.map(one, iters)

        return jax.jit(totals)

    def counts(self, edges, root: int, seed: int, n_iters: int) -> np.ndarray:
        """Colorful-map counts of samples ``0 .. n_iters-1`` of ``seed``."""
        key = (tuple(tuple(e) for e in edges), int(root))
        fn = self._fns.get(key)
        if fn is None:
            fn = self._fns[key] = self._program(*key)
        seed_key = jax.random.PRNGKey(int(seed))
        # a power of two up to ``self.batch``: few distinct programs
        b = min(self.batch, 1 << max(0, int(n_iters) - 1).bit_length())
        out = []
        for base in range(0, n_iters, b):
            ids = np.arange(base, base + b, dtype=np.int32)
            out.append(np.asarray(fn(self._src, self._dst, seed_key,
                                     jnp.asarray(ids))))
        return np.concatenate(out)[:n_iters].astype(np.float64)

    @staticmethod
    def scale(edges) -> float:
        """One sample of the estimator is ``count * scale``."""
        return 1.0 / (automorphisms(edges)
                      * colorful_probability(len(edges) + 1))
