"""Jitted SpMM dispatch over backends.

``prepare(graph, method)`` lifts a host Graph into the device arrays each
backend needs; ``spmm(m, prep)`` applies Y = M @ A. All backends agree with
``ref.spmm_dense`` / ``ref.spmm_segment_ref`` (tests sweep shapes and dtypes).

Backends:
  segment       chunked gather + segment_sum over edges (XLA; default on CPU)
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

from repro.graph.structure import Graph
from repro.kernels import resolve_interpret
from repro.kernels import autotune as _autotune
from repro.kernels.ema import ops as ema_ops
from repro.kernels.spmm.pallas_bsr import spmm_bsr_pallas
from repro.kernels.spmm.pallas_gather import spmm_gather_pallas
from repro.obs import metrics as _metrics
from repro.obs import tracing as _tracing

__all__ = ["prepare", "spmm", "spmm_row_chunks", "SpmmPrep", "METHODS"]

METHODS = ("segment", "ell", "dense", "pallas_gather", "pallas_bsr")

# Target elements for the (rows x edges) gather intermediate of the segment
# backend; keeps peak memory bounded while amortizing scan overhead.
_SEGMENT_TARGET_ELEMS = 1 << 24


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
        src, dst = g.edges_by_dst
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
    fb_src, fb_dst = g.edges_by_dst
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


def _spmm_segment(m: jnp.ndarray, src, dst, n: int) -> jnp.ndarray:
    store = m.dtype
    acc_dt = ema_ops.accum_dtype(store)
    c = m.shape[0]
    e = max(int(src.shape[0]), 1)
    row_chunk = max(1, min(c, _SEGMENT_TARGET_ELEMS // e))
    n_chunks = -(-c // row_chunk)
    c_pad = n_chunks * row_chunk
    m_p = jnp.pad(m, ((0, c_pad - c), (0, 0))) if c_pad != c else m
    m_p = m_p.reshape(n_chunks, row_chunk, m.shape[1])

    def body(_, chunk):
        # sub-f32 storage accumulates its edge sums in f32 (same
        # storage/accum contract as the kernels) and casts back at the end
        contrib = chunk[:, src].astype(acc_dt)                    # (rc, E)
        out = jax.ops.segment_sum(contrib.T, dst, num_segments=n)  # (N, rc)
        return None, out.T.astype(store)

    _, out = jax.lax.scan(body, None, m_p)
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
