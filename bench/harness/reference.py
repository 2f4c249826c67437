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
# Bytes of one gathered float32 operand: the ``(block, columns)`` edge block
# of a neighbour-sum step, and the ``(vertices, splits)`` factor of a split
# product. Every u7 table (35 columns, 140 splits at most) stays in one piece
# at the benchmark's scale 18; a k = 12 table of 792 columns takes 2^16 edge
# slots a step, and its 5,544 splits 2^13 vertices a step.
_BLOCK_BUDGET_BYTES = 256 << 20


def automorphisms(edges) -> int:
    """Automorphisms of the unrooted tree, from canonical forms: the tree
    is rooted at its center, or cut at its central edge into two rooted
    halves, which a map may swap where they are alike."""
    centers = _centers(edges)
    if len(centers) == 1:
        c = centers[0]
        return _pieces(_children(edges, c), c)[c][1]
    a, b = centers
    halves = [e for e in edges if {e[0], e[1]} != {a, b}]
    form_a, aut_a = _pieces(_children(halves, a), a)[a]
    form_b, aut_b = _pieces(_children(halves, b), b)[b]
    return aut_a * aut_b * (2 if form_a == form_b else 1)


def _centers(edges) -> list[int]:
    """The one or two vertices left after stripping leaves layer by layer."""
    nbr = _neighbours(edges) or {0: []}       # k = 1: no edges
    deg = {x: len(ys) for x, ys in nbr.items()}
    layer = [x for x, d in deg.items() if d <= 1]
    left = len(nbr)
    while left > 2:
        left -= len(layer)
        nxt = []
        for x in layer:
            for y in nbr[x]:
                deg[y] -= 1
                if deg[y] == 1:
                    nxt.append(y)
        layer = nxt
    return sorted(layer)


def colorful_probability(k: int) -> float:
    return math.factorial(k) / k ** k


def _neighbours(edges) -> dict[int, list[int]]:
    nbr: dict[int, list[int]] = {}
    for u, v in edges:
        nbr.setdefault(u, []).append(v)
        nbr.setdefault(v, []).append(u)
    return nbr


def _children(edges, root: int) -> dict[int, list[int]]:
    nbr = _neighbours(edges)
    kids, seen, stack = {}, {root}, [root]
    while stack:
        x = stack.pop()
        kids[x] = sorted(y for y in nbr.get(x, []) if y not in seen)
        seen.update(kids[x])
        stack.extend(kids[x])
    return kids


def _pieces(kids: dict[int, list[int]],
            root: int) -> dict[int, tuple[str, int]]:
    """``(canonical form, automorphisms)`` of the rooted piece below each
    template vertex: two vertices get one form exactly when their pieces
    are isomorphic, and a piece's automorphisms are its children's times
    ``m!`` for each class of ``m`` children whose pieces are alike."""
    out: dict[int, tuple[str, int]] = {}

    def walk(x: int) -> tuple[str, int]:
        below = sorted(walk(c) for c in kids[x])
        aut = 1
        for _, alike in itertools.groupby(below, key=lambda fa: fa[0]):
            alike = [a for _, a in alike]
            aut *= math.factorial(len(alike)) * math.prod(alike)
        out[x] = "(" + "".join(f for f, _ in below) + ")", aut
        return out[x]

    walk(root)
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
        slots ``(src, dst)`` in blocks, cut into smaller power-of-two blocks
        where a block of ``c`` gathered columns would pass the budget."""
        n = self.n
        fit = _BLOCK_BUDGET_BYTES // (4 * t.shape[1])
        if src.shape[1] > fit:
            step = 1 << max(0, fit.bit_length() - 1)
            pad = -src.size % step
            # padded slots add into the spare segment ``n``; every block is
            # a slice of the dst-sorted slots, so it stays sorted
            src = jnp.pad(src.reshape(-1), (0, pad)).reshape(-1, step)
            dst = jnp.pad(dst.reshape(-1), (0, pad),
                          constant_values=n).reshape(-1, step)

        def body(acc, blk):
            s, d = blk
            return acc + jax.ops.segment_sum(
                t[s], d, num_segments=n + 1, indices_are_sorted=True), None

        acc0 = jnp.zeros((n + 1, t.shape[1]), t.dtype)
        acc, _ = jax.lax.scan(body, acc0, (src, dst))
        return acc[:n]

    def _split_product(self, t: jnp.ndarray, y: jnp.ndarray, ia: np.ndarray,
                       ib: np.ndarray) -> jnp.ndarray:
        """``out[v, j] = sum over i of t[v, ia[j, i]] * y[v, ib[j, i]]``, in
        power-of-two chunks of vertices where one gathered factor of all
        ``n`` would pass the budget (each vertex's sum is its own)."""
        fit = _BLOCK_BUDGET_BYTES // (4 * ia.size)
        if self.n <= fit:
            return jnp.sum(t[:, ia] * y[:, ib], axis=-1)
        rows = 1 << max(0, fit.bit_length() - 1)
        pad = ((0, -self.n % rows), (0, 0))
        out = jax.lax.map(
            lambda ty: jnp.sum(ty[0][:, ia] * ty[1][:, ib], axis=-1),
            (jnp.pad(t, pad).reshape(-1, rows, t.shape[1]),
             jnp.pad(y, pad).reshape(-1, rows, y.shape[1])))
        return out.reshape(-1, ia.shape[0])[:self.n]

    def _program(self, edges: tuple, root: int):
        k = len(edges) + 1
        kids = _children(edges, root)
        shape = {x: form for x, (form, _) in _pieces(kids, root).items()}

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
                        t = self._split_product(t, y, ia, ib)
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
        return _scale(tuple(tuple(int(x) for x in e) for e in edges))


@lru_cache(maxsize=None)
def _scale(edges: tuple) -> float:
    return 1.0 / (automorphisms(edges) * colorful_probability(len(edges) + 1))
