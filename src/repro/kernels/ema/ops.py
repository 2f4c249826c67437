"""Jitted eMA dispatch: XLA scan path + Pallas kernel path.

The XLA path scans over the L splits; each step is two row-gathers plus a
fused multiply-add over the full (S, N) tile — the direct JAX transcription of
paper Algorithm 4 line 7. The Pallas path keeps child tables resident in VMEM
(see pallas_ema.py) and is selected when (a) the caller asked for it, (b) the
table dtype is supported by the kernel in the current mode, and (c) the
resident tables fit the VMEM budget at the actual block sizes chosen. A dtype
the kernel does not support falls back to the XLA path *explicitly* — the
Pallas path never downcasts.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import kernels
from repro.kernels import accum_dtype, resolve_interpret
from repro.kernels import autotune as _autotune
from repro.kernels.ema.pallas_ema import ema_pallas, ema_vmem_bytes
from repro.obs import metrics as _metrics
from repro.obs import tracing as _tracing

__all__ = ["ema", "ema_xla", "ema_chunked", "pack_chunked_splits",
           "ChunkedSplits", "ema_flops", "pallas_supports_dtype",
           "pallas_dtype_pair", "accum_dtype"]

_PALLAS_S_BLOCK = 8
_PALLAS_N_BLOCK = 512

# (storage dtype) -> (storage, accumulator) pairs the Pallas kernels run
# without *losing* precision relative to the storage contract. bf16 tables
# are admitted in BOTH modes because every kernel accumulates partial
# products in an f32 VMEM accumulator and casts only at the final store —
# halving HBM table traffic without bf16 accumulation error. f64 stays
# interpret-only (the TPU vector unit has no f64).
_INTERPRET_PAIRS = {
    np.dtype(jnp.float32): np.dtype(jnp.float32),
    np.dtype(jnp.float64): np.dtype(jnp.float64),
    np.dtype(jnp.bfloat16): np.dtype(jnp.float32),
}
_COMPILED_PAIRS = {
    np.dtype(jnp.float32): np.dtype(jnp.float32),
    np.dtype(jnp.bfloat16): np.dtype(jnp.float32),
}


def pallas_dtype_pair(dtype, interpret: bool | None = None
                      ) -> tuple[np.dtype, np.dtype] | None:
    """(storage, accumulator) dtype pair for the Pallas kernels, or None.

    None means the kernels cannot run this dtype in this mode without
    downcasting — the dispatch layers fall back to XLA explicitly. The
    mode is ``resolve_interpret(interpret)``.
    """
    dt = np.dtype(dtype)
    table = (_INTERPRET_PAIRS if resolve_interpret(interpret)
             else _COMPILED_PAIRS)
    acc = table.get(dt)
    return None if acc is None else (dt, acc)


def pallas_supports_dtype(dtype, interpret: bool | None = None) -> bool:
    """Whether the Pallas kernels can run this dtype *without* downcasting."""
    return pallas_dtype_pair(dtype, interpret) is not None


def ema_xla(m_a: jnp.ndarray, y_p: jnp.ndarray,
            ia: jnp.ndarray, ip: jnp.ndarray) -> jnp.ndarray:
    """Child tables (..., C, N); gathers run on axis -2 so an optional
    leading batch dimension broadcasts through the scan untouched.
    Sub-f32 tables accumulate in f32 and cast back at the end, matching
    the kernel path's storage/accumulator contract."""
    store = m_a.dtype
    acc_dt = accum_dtype(store)

    def body(acc, idx):
        ia_l, ip_l = idx
        term = jnp.take(m_a, ia_l, axis=-2).astype(acc_dt) \
            * jnp.take(y_p, ip_l, axis=-2).astype(acc_dt)
        return acc + term, None

    acc0 = jnp.zeros(m_a.shape[:-2] + (ia.shape[0], m_a.shape[-1]), acc_dt)
    acc, _ = jax.lax.scan(body, acc0, (ia.T, ip.T))
    return acc.astype(store)


def ema(m_a: jnp.ndarray, y_p: jnp.ndarray, ia: jnp.ndarray, ip: jnp.ndarray,
        *, use_pallas: bool = False, interpret: bool | None = None,
        s_block: int | None = None, n_block: int | None = None,
        autotune: bool = False) -> jnp.ndarray:
    """eMA dispatch. ``use_pallas`` selects the kernel path when the dtype is
    supported and the tables fit VMEM at the chosen block sizes; a batched
    (B, C, N) input runs as ONE kernel launch (batch on the grid). Explicit
    ``s_block``/``n_block`` override the defaults; ``autotune=True`` sweeps
    :data:`repro.kernels.autotune.EMA_BLOCK_CANDIDATES` once per shape.
    Either path's device ops run under the ``kernel.ema`` scope."""
    with _tracing.device_scope(_tracing.KERNEL_EMA):
        return _ema(m_a, y_p, ia, ip, use_pallas, interpret, s_block,
                    n_block, autotune)


def _ema(m_a, y_p, ia, ip, use_pallas, interpret, s_block, n_block,
         autotune) -> jnp.ndarray:
    dtype = jnp.promote_types(m_a.dtype, y_p.dtype)
    if use_pallas:
        interpret = resolve_interpret(interpret)
        if not pallas_supports_dtype(dtype, interpret):
            _metrics.counter("kernel_fallbacks_total", kernel="ema",
                             reason="dtype_unsupported").inc()
        else:
            if autotune and (s_block is None or n_block is None):
                s_block, n_block = _autotune.ema_blocks(m_a, y_p, ia, ip,
                                                        interpret=interpret)
            sb = s_block or _PALLAS_S_BLOCK
            nb = n_block or _PALLAS_N_BLOCK
            if _fits_vmem(m_a, y_p, n_block=nb, s_block=sb):
                _metrics.counter("kernel_launches_total", kernel="ema",
                                 path="pallas").inc()
                return ema_pallas(m_a, y_p, ia, ip, s_block=sb, n_block=nb,
                                  interpret=interpret)
            _metrics.counter("kernel_fallbacks_total", kernel="ema",
                             reason="vmem_overflow").inc()
    _metrics.counter("kernel_launches_total", kernel="ema", path="xla").inc()
    return ema_xla(m_a, y_p, ia, ip)


def _fits_vmem(m_a, y_p, *, n_block: int = _PALLAS_N_BLOCK,
               s_block: int = _PALLAS_S_BLOCK) -> bool:
    """VMEM residency check at the *actual* block sizes and dtype: the
    kernel's whole per-step working set (:func:`ema_vmem_bytes`)."""
    dtype = jnp.promote_types(m_a.dtype, y_p.dtype)
    return ema_vmem_bytes(m_a.shape[-2], y_p.shape[-2], s_block, n_block,
                          dtype) < kernels.VMEM_BUDGET_BYTES


# ------------------------------------------------------------------ chunked
@dataclasses.dataclass(frozen=True)
class ChunkedSplits:
    """Static pair tables for the colorset-chunked eMA of one plan node.

    The (color set, split) pairs of the node's ``(IA, IP)`` tables are
    grouped by which passive-axis chunk their ``IP`` rank falls in, so each
    chunk's pairs can be applied the moment that slice of the SpMM output
    exists. All arrays are ``(n_chunks, pairs_pad)`` with ``pairs_pad`` a
    multiple of ``pair_block`` (padding pairs have mask 0).
    """

    out_idx: np.ndarray    # output color-set rank of each pair
    a_idx: np.ndarray      # active-child rank
    p_loc: np.ndarray      # passive rank, local to the chunk
    mask: np.ndarray       # 1.0 for real pairs
    n_chunks: int
    chunk_rows: int        # passive rows per chunk (last chunk padded)
    n_out_rows: int        # C(k, t)
    pair_block: int


def pack_chunked_splits(ia, ip, n_passive_rows: int, n_chunks: int,
                        pair_block: int = 128) -> ChunkedSplits:
    """Host-side regrouping of split tables for :func:`ema_chunked`."""
    ia = np.asarray(ia)
    ip = np.asarray(ip)
    s, l = ia.shape
    r = -(-n_passive_rows // n_chunks)
    jj = np.repeat(np.arange(s, dtype=np.int32), l)
    aa = ia.ravel().astype(np.int32)
    pp = ip.ravel().astype(np.int32)
    q_of = pp // r
    counts = np.bincount(q_of, minlength=n_chunks)
    p_max = int(counts.max()) if len(counts) else 1
    p_pad = max(pair_block, -(-p_max // pair_block) * pair_block)
    out_idx = np.zeros((n_chunks, p_pad), np.int32)
    a_idx = np.zeros((n_chunks, p_pad), np.int32)
    p_loc = np.zeros((n_chunks, p_pad), np.int32)
    mask = np.zeros((n_chunks, p_pad), np.float32)
    order = np.argsort(q_of, kind="stable")
    offs = np.concatenate([[0], np.cumsum(counts)])
    for q in range(n_chunks):
        sel = order[offs[q]: offs[q + 1]]
        m = len(sel)
        out_idx[q, :m] = jj[sel]
        a_idx[q, :m] = aa[sel]
        p_loc[q, :m] = pp[sel] - q * r
        mask[q, :m] = 1.0
    return ChunkedSplits(out_idx=out_idx, a_idx=a_idx, p_loc=p_loc,
                         mask=mask, n_chunks=n_chunks, chunk_rows=r,
                         n_out_rows=s, pair_block=pair_block)


def ema_chunked(m_a: jnp.ndarray, m_p: jnp.ndarray, pack: ChunkedSplits,
                spmm_fn) -> jnp.ndarray:
    """eMA that never materializes the full passive SpMM output.

    ``spmm_fn(chunk)`` maps a ``(..., chunk_rows, N)`` slice of the passive
    table to its neighbor sums; the scan walks the ``C(k, t_p)`` axis one
    chunk at a time, applying that chunk's (active, passive, out) pairs in
    ``pair_block``-sized scatter-adds. A leading (B,) batch dimension rides
    through every step natively (gathers on axis -2, scatter-adds under an
    ellipsis) — one scan for the whole coloring batch, no per-element
    serialization. Peak extra memory is one passive chunk + one pair block
    instead of the whole ``C(k, t_p) x N`` table. Matches the unchunked path
    to float reassociation (~1e-6 relative). Its device ops run under the
    ``kernel.ema`` scope; those of ``spmm_fn`` may name their own.
    """
    with _tracing.device_scope(_tracing.KERNEL_EMA):
        return _ema_chunked(m_a, m_p, pack, spmm_fn)


def _ema_chunked(m_a, m_p, pack: ChunkedSplits, spmm_fn) -> jnp.ndarray:
    n = m_a.shape[-1]
    lead = m_a.shape[:-2]
    from repro.kernels.spmm.ops import spmm_row_chunks
    m_p_chunks = spmm_row_chunks(m_p, pack.n_chunks)    # (..., Q, R, N)
    # scan iterates the chunk axis, which must lead
    m_p_chunks = jnp.moveaxis(m_p_chunks, -3, 0)        # (Q, ..., R, N)
    pb = pack.pair_block
    n_blocks = pack.out_idx.shape[1] // pb
    oj = jnp.asarray(pack.out_idx)
    ai = jnp.asarray(pack.a_idx)
    pl = jnp.asarray(pack.p_loc)
    mk = jnp.asarray(pack.mask, m_a.dtype)

    def chunk_body(acc, xs):
        m_p_c, oj_c, ai_c, pl_c, mk_c = xs
        y = spmm_fn(m_p_c)                              # (..., R, N)

        def pair_body(acc2, ys):
            o, a, p, w = ys
            term = jnp.take(m_a, a, axis=-2) * jnp.take(y, p, axis=-2) \
                * w[:, None]
            return acc2.at[..., o, :].add(term), None

        acc, _ = jax.lax.scan(
            pair_body, acc,
            (oj_c.reshape(n_blocks, pb), ai_c.reshape(n_blocks, pb),
             pl_c.reshape(n_blocks, pb), mk_c.reshape(n_blocks, pb)))
        return acc, None

    acc0 = jnp.zeros(lead + (pack.n_out_rows, n), m_a.dtype)
    acc, _ = jax.lax.scan(chunk_body, acc0, (m_p_chunks, oj, ai, pl, mk))
    return acc


def ema_flops(n: int, s: int, l: int) -> int:
    """2 flops (mul + add) per (vertex, color set, split)."""
    return 2 * n * s * l
