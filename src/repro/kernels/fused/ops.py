"""Dispatch for the fused SpMM -> eMA kernel.

``prepare_fused(graph)`` lifts the adjacency into the destination-sorted BSR
block stream the kernel walks (plus raw edge lists for the explicit XLA
fallback); ``fused_spmm_ema(m_a, m_p, ia, ip, prep)`` computes

    out = ema(m_a, m_p @ A, ia, ip)

without materializing the ``(B, C(k,t_p), N)`` neighbor-sum table in HBM —
the whole point of the fusion (see pallas_fused.py). Unsupported dtypes or
tables too large for VMEM run the unfused XLA pair (segment SpMM + scan eMA)
explicitly; the kernel path never downcasts. Sub-f32 storage dtypes (bf16)
stream half the table/adjacency bytes while the kernels accumulate in the
(storage, accum) pair's f32 member.

``fused_spmm_ema_shared`` is the group form: several consumers of ONE
passive child computed by a single launch whose SpMM leg runs once into
shared VMEM scratch (see ``fused_spmm_ema_shared_pallas``). Its fallback
preserves the sharing: one XLA segment SpMM, then one eMA per consumer.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.graph.structure import Graph
from repro.kernels import resolve_interpret
from repro.kernels.ema.ops import ema_xla, pallas_supports_dtype
from repro.kernels.fused.pallas_fused import (fused_spmm_ema_pallas,
                                              fused_spmm_ema_shared_pallas,
                                              group_batch_block_fits)
from repro.obs import metrics as _metrics

__all__ = ["FusedPrep", "prepare_fused", "fused_spmm_ema",
           "fused_spmm_ema_shared", "fused_group_fits_vmem"]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class FusedPrep:
    """Device-side adjacency operand for the fused kernel (a pytree)."""

    n: int
    arrays: dict[str, Any]
    static: dict[str, Any]

    def tree_flatten(self):
        keys = sorted(self.arrays)
        return [self.arrays[k] for k in keys], (
            self.n, keys, tuple(sorted(self.static.items())))

    @classmethod
    def tree_unflatten(cls, aux, children):
        n, keys, static = aux
        return cls(n, dict(zip(keys, children)), dict(static))

    @property
    def n_blocks(self) -> int:
        return int(self.arrays["blocks"].shape[0])


def prepare_fused(g: Graph, *, tile: int = 128,
                  interpret: bool | None = None,
                  dtype=jnp.float32, reorder: str = "") -> FusedPrep:
    """BSR block stream (every dst tile populated, sorted by dst tile) plus
    the raw edge lists for the XLA fallback path. ``dtype`` is the storage
    dtype the adjacency blocks are held in (bf16 halves their HBM bytes);
    ``reorder`` tags the prep with the vertex-ordering choice for the
    autotune cache key, same as ``spmm.ops.prepare``."""
    gp = g.padded(tile)
    bs = gp.bsr(tile=tile)
    src, dst = g.edges_by_dst
    return FusedPrep(
        g.n,
        {"blocks": jnp.asarray(bs.blocks, jnp.dtype(dtype)),
         "src_tile": jnp.asarray(bs.src_tile),
         "dst_tile": jnp.asarray(bs.dst_tile),
         "fb_src": jnp.asarray(src), "fb_dst": jnp.asarray(dst)},
        {"tile": tile, "n_tiles": bs.n_tiles,
         "interpret": resolve_interpret(interpret),
         "reorder": reorder},
    )


def fused_group_fits_vmem(c_as, c_p: int, ss, ls, *, tile: int = 128,
                          dtype=jnp.float32) -> bool:
    """Whether one single-coloring step of the shared-passive group kernel
    (a group of one is the solo kernel) fits VMEM, by the kernel's own
    working-set model, ``pallas_fused.group_vmem_bytes``."""
    return group_batch_block_fits(1, tuple(c_as), c_p,
                                  tuple(-(-s // 8) * 8 for s in ss),
                                  tuple(ls), tile, dtype)


def _fallback(m_a, m_p, ia, ip, prep: FusedPrep) -> jnp.ndarray:
    """Unfused XLA pair — the explicit escape hatch for unsupported dtypes
    or VMEM-oversized tables (matches the kernel to float reassociation)."""
    from repro.kernels.spmm.ops import _spmm_segment
    _metrics.counter("kernel_launches_total", kernel="fused",
                     path="xla").inc()
    lead = m_p.shape[:-2]
    flat = m_p.reshape((-1, m_p.shape[-1]))
    y = _spmm_segment(flat, prep.arrays["fb_src"], prep.arrays["fb_dst"],
                      prep.n)
    y = y.reshape(lead + (m_p.shape[-2], m_p.shape[-1]))
    return ema_xla(m_a, y, ia, ip)


def fused_spmm_ema(m_a: jnp.ndarray, m_p: jnp.ndarray,
                   ia: jnp.ndarray, ip: jnp.ndarray,
                   prep: FusedPrep) -> jnp.ndarray:
    """``ema(m_a, m_p @ A, ia, ip)`` for tables of shape (..., C, N).

    Rank-polymorphic over one optional leading batch dimension (folded into
    the kernel grid — one launch for the whole coloring batch). The vertex
    axis is padded to the tile multiple on the way in (padding vertices are
    isolated, so their neighbor sums and output columns are exact zeros) and
    sliced on the way out.
    """
    st = prep.static
    dtype = jnp.promote_types(m_a.dtype, m_p.dtype)
    # every fallback decision is reason-counted (once per traced shape),
    # so "asked for the fused kernel, got the XLA pair" is never silent
    if not pallas_supports_dtype(dtype, st["interpret"]):
        _metrics.counter("kernel_fallbacks_total", kernel="fused",
                         reason="dtype_unsupported").inc()
        return _fallback(m_a, m_p, ia, ip, prep)
    if not fused_group_fits_vmem((m_a.shape[-2],), m_p.shape[-2],
                                 (ia.shape[0],), (ia.shape[1],),
                                 tile=st["tile"], dtype=dtype):
        _metrics.counter("kernel_fallbacks_total", kernel="fused",
                         reason="vmem_overflow").inc()
        return _fallback(m_a, m_p, ia, ip, prep)
    _metrics.counter("kernel_launches_total", kernel="fused",
                     path="pallas").inc()
    batched = m_a.ndim > 2
    lead = m_a.shape[:-2]
    n = m_a.shape[-1]
    m_a3 = m_a.reshape((-1,) + m_a.shape[-2:])
    m_p3 = m_p.reshape((-1,) + m_p.shape[-2:])
    n_pad = st["n_tiles"] * st["tile"]
    if n_pad != n:
        m_a3 = jnp.pad(m_a3, ((0, 0), (0, 0), (0, n_pad - n)))
        m_p3 = jnp.pad(m_p3, ((0, 0), (0, 0), (0, n_pad - n)))
    out = fused_spmm_ema_pallas(
        m_a3, m_p3, ia, ip, prep.arrays["blocks"], prep.arrays["src_tile"],
        prep.arrays["dst_tile"], n_tiles=st["n_tiles"], tile=st["tile"],
        interpret=st["interpret"])[:, :, :n]
    return out.reshape(lead + out.shape[-2:]) if batched else out[0]


def _fallback_shared(m_as, m_p, ias, ips, prep: FusedPrep) -> tuple:
    """Shared fallback: the SpMM still runs ONCE (the sharing survives the
    escape hatch), then one XLA eMA per consumer."""
    from repro.kernels.spmm.ops import _spmm_segment
    _metrics.counter("kernel_launches_total", kernel="fused_shared",
                     path="xla").inc()
    lead = m_p.shape[:-2]
    flat = m_p.reshape((-1, m_p.shape[-1]))
    y = _spmm_segment(flat, prep.arrays["fb_src"], prep.arrays["fb_dst"],
                      prep.n)
    y = y.reshape(lead + (m_p.shape[-2], m_p.shape[-1]))
    return tuple(ema_xla(m_a, y, ia, ip)
                 for m_a, ia, ip in zip(m_as, ias, ips))


def fused_spmm_ema_shared(m_as, m_p: jnp.ndarray, ias, ips,
                          prep: FusedPrep) -> tuple:
    """Per-consumer ``ema(m_a_i, m_p @ A, ia_i, ip_i)`` tuple for a group of
    consumers sharing one passive child. The Pallas path runs the SpMM leg
    once into shared VMEM scratch; tables have shape (..., C, N) with one
    optional shared leading batch dimension.
    """
    st = prep.static
    m_as, ias, ips = tuple(m_as), tuple(ias), tuple(ips)
    dtype = m_p.dtype
    for m_a in m_as:
        dtype = jnp.promote_types(dtype, m_a.dtype)
    c_as = tuple(m.shape[-2] for m in m_as)
    ss = tuple(ia.shape[0] for ia in ias)
    ls = tuple(ia.shape[1] for ia in ias)
    if not pallas_supports_dtype(dtype, st["interpret"]):
        _metrics.counter("kernel_fallbacks_total", kernel="fused_shared",
                         reason="dtype_unsupported").inc()
        return _fallback_shared(m_as, m_p, ias, ips, prep)
    if not fused_group_fits_vmem(c_as, m_p.shape[-2], ss, ls,
                                 tile=st["tile"], dtype=dtype):
        _metrics.counter("kernel_fallbacks_total", kernel="fused_shared",
                         reason="vmem_overflow").inc()
        return _fallback_shared(m_as, m_p, ias, ips, prep)
    _metrics.counter("kernel_launches_total", kernel="fused_shared",
                     path="pallas").inc()
    batched = m_p.ndim > 2
    lead = m_p.shape[:-2]
    n = m_p.shape[-1]
    m_p3 = m_p.reshape((-1,) + m_p.shape[-2:])
    m_as3 = tuple(m.reshape((-1,) + m.shape[-2:]) for m in m_as)
    n_pad = st["n_tiles"] * st["tile"]
    if n_pad != n:
        pad = ((0, 0), (0, 0), (0, n_pad - n))
        m_p3 = jnp.pad(m_p3, pad)
        m_as3 = tuple(jnp.pad(m, pad) for m in m_as3)
    outs = fused_spmm_ema_shared_pallas(
        m_as3, m_p3, ias, ips, prep.arrays["blocks"],
        prep.arrays["src_tile"], prep.arrays["dst_tile"],
        n_tiles=st["n_tiles"], tile=st["tile"], interpret=st["interpret"])
    outs = tuple(out[:, :, :n] for out in outs)
    if batched:
        return tuple(out.reshape(lead + out.shape[-2:]) for out in outs)
    return tuple(out[0] for out in outs)
