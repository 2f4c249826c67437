"""Jitted SpMM dispatch over backends.

``prepare(graph, method)`` lifts a host Graph into the device arrays each
backend needs; ``spmm(m, prep)`` applies Y = M @ A. All backends agree with
``ref.spmm_dense`` / ``ref.spmm_segment_ref`` (tests sweep shapes and dtypes).

Backends:
  segment       gather + segment_sum over edges in dst order, the scatter told
                its indices are sorted (XLA; the served default): a whole
                table per step, rows chunked only past a byte budget
                (``_spmm_segment``)
  ell           padded neighbor-list gather (XLA; good for low max-degree)
  dense         dense matmul (tiny graphs / oracle)
  pallas_gather on-the-fly densified edge chunks on the MXU (TPU target)
  pallas_bsr    pre-densified 128x128 block-sparse MXU path (TPU target)
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.structure import Graph
from repro.kernels import resolve_interpret
from repro.kernels import autotune as _autotune
from repro.kernels.ema import ops as ema_ops
from repro.kernels.spmm.pallas_bsr import spmm_bsr_pallas
from repro.kernels.spmm.pallas_gather import spmm_gather_pallas
from repro.obs import metrics as _metrics
from repro.obs import tracing as _tracing

__all__ = ["prepare", "spmm", "spmm_row_chunks", "segment_steps",
           "SpmmPrep", "METHODS"]

METHODS = ("segment", "ell", "dense", "pallas_gather", "pallas_bsr")

# Bytes of the (rows, E) gathered operand, in the accumulator dtype, that one
# step of the segment SpMM may hold. A u7 table at Graph500 scale 18 (70 rows
# x 7.6M slots, 2.1 GB) goes through in one step; RMAT-20's 280 rows x 31.4M
# slots take 32-row steps. It bounds the program where XLA writes the
# operand out: the CPU backend, and a TPU v5e at RMAT-20. At scale 18 the
# v5e fuses the sorted gather into the scatter and writes nothing.
_SEGMENT_GATHER_BUDGET_BYTES = 4 << 30


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SpmmPrep:
    """Device-side graph operand for a given backend (a pytree)."""

    method: str
    n: int
    arrays: dict[str, Any]
    static: dict[str, Any]

    def tree_flatten(self):
        keys = sorted(self.arrays)
        return [self.arrays[k] for k in keys], (self.method, self.n, keys,
                                                tuple(sorted(self.static.items())))

    @classmethod
    def tree_unflatten(cls, aux, children):
        method, n, keys, static = aux
        return cls(method, n, dict(zip(keys, children)), dict(static))


def prepare(g: Graph, method: str = "segment", *, tile: int = 128,
            chunk_size: int = 512, interpret: bool | None = None,
            dtype=jnp.float32, reorder: str = "") -> SpmmPrep:
    """``dtype`` is the table *storage* dtype: the Pallas backends store
    their adjacency operand (dense BSR blocks / gather masks) in it, so a
    bf16 engine streams half the adjacency bytes; kernels still accumulate
    in the (storage, accum) pair's accumulator. ``reorder`` tags the prep
    with the vertex-ordering choice the graph was built under — it rides
    ``static`` into the autotune cache key so timings never cross block
    streams with different locality."""
    if method not in METHODS:
        raise ValueError(f"unknown spmm method {method!r}")
    interpret = resolve_interpret(interpret)
    if method == "segment":
        src, dst = edges_sorted_by_dst(g)
        return SpmmPrep(method, g.n,
                        {"src": jnp.asarray(src), "dst": jnp.asarray(dst)}, {})
    if method == "ell":
        nbr, mask = g.ell()
        return SpmmPrep(method, g.n,
                        {"nbr": jnp.asarray(nbr), "mask": jnp.asarray(mask)}, {})
    if method == "dense":
        return SpmmPrep(method, g.n, {"a": jnp.asarray(g.to_dense())}, {})
    # Pallas backends also carry the raw edge lists so a dtype the kernel
    # does not support can fall back to the XLA segment path explicitly
    # (never a silent downcast).
    fb_src, fb_dst = edges_sorted_by_dst(g)
    fb = {"fb_src": jnp.asarray(fb_src), "fb_dst": jnp.asarray(fb_dst)}
    adj_dtype = jnp.dtype(dtype)
    if method == "pallas_gather":
        gp = g.padded(tile)
        ch = gp.edge_chunks(tile=tile, chunk_size=chunk_size)
        return SpmmPrep(
            method, g.n,
            {"src": jnp.asarray(ch.src), "dst_local": jnp.asarray(ch.dst_local),
             "mask": jnp.asarray(ch.mask, adj_dtype),
             "src_tile": jnp.asarray(ch.src_tile),
             "dst_tile": jnp.asarray(ch.dst_tile), **fb},
            {"tile": tile, "n_tiles": ch.n_tiles, "interpret": interpret,
             "reorder": reorder},
        )
    # pallas_bsr
    gp = g.padded(tile)
    bs = gp.bsr(tile=tile)
    return SpmmPrep(
        method, g.n,
        {"blocks": jnp.asarray(bs.blocks, adj_dtype),
         "src_tile": jnp.asarray(bs.src_tile),
         "dst_tile": jnp.asarray(bs.dst_tile), **fb},
        {"tile": tile, "n_tiles": bs.n_tiles, "interpret": interpret,
         "reorder": reorder},
    )


def edges_sorted_by_dst(g: Graph) -> tuple[np.ndarray, np.ndarray]:
    """``g.edges_by_dst``, checked on the host to be in non-decreasing dst
    order: ``_spmm_segment`` tells XLA its scatter indices are sorted, and
    unsorted indices would give wrong sums without an error."""
    src, dst = g.edges_by_dst
    if not np.all(dst[1:] >= dst[:-1]):
        raise ValueError("segment SpMM edge lists must be sorted by dst "
                         "(as Graph.edges_by_dst builds them)")
    return src, dst


def _segment_row_chunk(c: int, e: int, itemsize: int) -> int:
    """Rows per step: all ``c`` when the gathered (c, e) operand fits
    ``_SEGMENT_GATHER_BUDGET_BYTES``, else the largest multiple of 8 that
    fits (at least one row)."""
    fit = _SEGMENT_GATHER_BUDGET_BYTES // (max(e, 1) * itemsize)
    if c <= fit:
        return c
    return fit // 8 * 8 if fit >= 8 else max(fit, 1)


def segment_steps(rows: int, e: int, dtype) -> int:
    """Steps the segment SpMM takes over a ``(rows, N)`` table of storage
    ``dtype`` on ``e`` edge slots: one unless the gathered operand passes
    ``_SEGMENT_GATHER_BUDGET_BYTES`` (:func:`_segment_row_chunk`)."""
    itemsize = jnp.dtype(ema_ops.accum_dtype(dtype)).itemsize
    return -(-rows // _segment_row_chunk(rows, e, itemsize))


def _spmm_segment(m: jnp.ndarray, src, dst, n: int) -> jnp.ndarray:
    """Y = M @ A for a (C, N) table as a gather of ``m[:, src]`` and a
    ``segment_sum`` by ``dst``.

    Precondition: ``dst`` is non-decreasing, as ``Graph.edges_by_dst`` builds
    it (``edges_sorted_by_dst`` checks it once per prep). The scatter is
    told its indices are sorted, so XLA neither sorts them nor permutes the
    updates. Rows go through in one step unless the gathered (C, E) operand
    would exceed ``_SEGMENT_GATHER_BUDGET_BYTES``; past it, a scan over row
    chunks (a ragged last chunk zero-padded). So each edge index is gathered
    and scattered once per table, or per chunk past the budget; on a TPU v5e
    at Graph500 scale 18 the gather fuses into the scatter and the operand
    is never written. Each vertex sums its edges in edge order; sub-f32
    storage accumulates in f32 (the kernels' storage/accum contract) and
    casts back at the end.
    """
    store = m.dtype
    acc_dt = ema_ops.accum_dtype(store)
    c = m.shape[0]

    def rows(chunk):
        contrib = chunk[:, src].astype(acc_dt)                      # (rc, E)
        out = jax.ops.segment_sum(contrib.T, dst, num_segments=n,
                                  indices_are_sorted=True)          # (N, rc)
        return out.T.astype(store)

    row_chunk = _segment_row_chunk(c, int(src.shape[0]),
                                   jnp.dtype(acc_dt).itemsize)
    if row_chunk >= c:
        return rows(m)
    n_chunks = -(-c // row_chunk)
    c_pad = n_chunks * row_chunk
    m_p = jnp.pad(m, ((0, c_pad - c), (0, 0))) if c_pad != c else m
    m_p = m_p.reshape(n_chunks, row_chunk, m.shape[1])
    _, out = jax.lax.scan(lambda _, chunk: (None, rows(chunk)), None, m_p)
    return out.reshape(c_pad, m.shape[1])[:c]


def _spmm_ell(m: jnp.ndarray, nbr, mask) -> jnp.ndarray:
    # Y[:, i] = sum_d m[:, nbr[i, d]] * mask[i, d]
    def body(acc, nd):
        col_ids, msk = nd
        return acc + m[:, col_ids] * msk[None, :], None

    acc0 = jnp.zeros_like(m)
    acc, _ = jax.lax.scan(body, acc0, (nbr.T, mask.T))
    return acc


def spmm(m: jnp.ndarray, prep: SpmmPrep, *, c_block: int | None = None,
         autotune: bool = False) -> jnp.ndarray:
    """Y = M @ A for count table m of shape (..., C, N).

    Leading (batch) dimensions are folded into the combination rows: every
    backend treats rows independently, so a (B, C, N) batched table is one
    (B*C, N) SpMM — a single kernel launch for the whole coloring batch.
    A dtype the Pallas kernels do not support in the current mode runs the
    XLA segment path on the prep's fallback edge lists instead (explicit
    fallback, never a downcast). ``c_block`` overrides the Pallas row-block
    heuristic; ``autotune=True`` sweeps candidates once per (shape, dtype).
    Every backend's device ops run under the ``kernel.spmm`` scope.
    """
    with _tracing.device_scope(_tracing.KERNEL_SPMM):
        return _spmm(m, prep, c_block, autotune)


def _spmm(m: jnp.ndarray, prep: SpmmPrep, c_block: int | None,
          autotune: bool) -> jnp.ndarray:
    if m.ndim > 2:
        lead = m.shape[:-1]
        out = _spmm(m.reshape(-1, m.shape[-1]), prep, c_block, autotune)
        return out.reshape(lead + (out.shape[-1],))
    a = prep.arrays
    if prep.method == "segment":
        return _spmm_segment(m, a["src"], a["dst"], prep.n)
    if prep.method == "ell":
        return _spmm_ell(m, a["nbr"], a["mask"])
    if prep.method == "dense":
        return m @ a["a"].astype(m.dtype)
    st = prep.static
    if not ema_ops.pallas_supports_dtype(m.dtype, st["interpret"]):
        # explicit XLA fallback — count it so "asked for Pallas, got XLA"
        # is observable (incremented once per traced shape under jit)
        _metrics.counter("kernel_fallbacks_total", kernel="spmm",
                         reason="dtype_unsupported").inc()
        _metrics.counter("kernel_launches_total", kernel="spmm",
                         path="xla").inc()
        return _spmm_segment(m, a["fb_src"], a["fb_dst"], prep.n)
    _metrics.counter("kernel_launches_total", kernel="spmm",
                     path=prep.method).inc()
    n_pad = st["n_tiles"] * st["tile"]
    m_pad = jnp.pad(m, ((0, 0), (0, n_pad - m.shape[1]))) if n_pad != m.shape[1] else m

    def run(cb: int) -> jnp.ndarray:
        if prep.method == "pallas_gather":
            return spmm_gather_pallas(
                m_pad, a["src"], a["dst_local"], a["mask"], a["src_tile"],
                a["dst_tile"], n_tiles=st["n_tiles"], tile=st["tile"],
                c_block=cb, interpret=st["interpret"],
            )
        return spmm_bsr_pallas(
            m_pad, a["blocks"], a["src_tile"], a["dst_tile"],
            n_tiles=st["n_tiles"], tile=st["tile"],
            c_block=cb, interpret=st["interpret"],
        )

    if c_block is None:
        if autotune:
            c_block = _autotune.spmm_c_block(
                m_pad, run, kind=prep.method, interpret=st["interpret"],
                reorder=st.get("reorder", ""))
        else:
            c_block = _pick_c_block(m.shape[0])
    return run(c_block)[:, : m.shape[1]]


def spmm_row_chunks(m: jnp.ndarray, n_chunks: int) -> jnp.ndarray:
    """Split the combination-row axis for the colorset-chunked executor path.

    Returns ``(n_chunks, rows_per_chunk, N)`` with zero-padded tail rows;
    each chunk is a self-contained SpMM operand (rows are independent), so
    the chunked eMA can scan ``spmm(chunk, prep)`` without ever holding the
    full ``C(k, t_p) x N`` neighbor-sum table.
    """
    c, n = m.shape[-2], m.shape[-1]
    r = -(-c // n_chunks)
    pad = n_chunks * r - c
    if pad:
        width = [(0, 0)] * (m.ndim - 2) + [(0, pad), (0, 0)]
        m = jnp.pad(m, width)
    return m.reshape(m.shape[:-2] + (n_chunks, r, n))


def _pick_c_block(c: int) -> int:
    for cand in (256, 128, 64, 32, 16, 8):
        if c >= cand:
            return cand
    return 8


def spmm_flops(g: Graph, rows: int) -> int:
    """Useful work: one add per (edge, row)."""
    return g.m * rows
