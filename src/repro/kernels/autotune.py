"""Block-size autotuning for the Pallas kernels: a small cached sweep.

Fused plans dispatch only a handful of distinct ``(B, C, N)`` table shapes
per engine, so exhaustive per-shape timing is cheap: each candidate block
configuration is compiled once and timed over a few repetitions, and the
winner is cached in-process keyed by (kernel kind, shape signature, dtype,
interpret flag, vertex-reorder choice). Subsequent dispatches with the same
signature pay a dict lookup; ``autotune_cache_{hits,misses}_total`` counters
in the obs registry make the reuse rate observable.

``measure=False`` (the default for :func:`ema_blocks` callers that pass
``autotune=False``) never runs the sweep — dispatch falls back to the static
heuristics — so tests and cold paths stay deterministic and compile-light.
"""

from __future__ import annotations

import time
from typing import Callable, Hashable, Sequence

import jax
import numpy as np

from repro.obs import metrics as _metrics
from repro.obs import tracing as _tracing

__all__ = ["autotune", "ema_blocks", "spmm_c_block", "cache_info",
           "clear_cache", "EMA_BLOCK_CANDIDATES", "SPMM_C_BLOCK_CANDIDATES"]

# (s_block, n_block) candidates for the eMA kernel sweep.
EMA_BLOCK_CANDIDATES: tuple[tuple[int, int], ...] = (
    (4, 256), (8, 256), (8, 512), (16, 512), (8, 1024),
)
# c_block candidates for the SpMM MXU kernels.
SPMM_C_BLOCK_CANDIDATES: tuple[int, ...] = (32, 64, 128, 256)

_CACHE: dict[Hashable, object] = {}


def clear_cache() -> None:
    _CACHE.clear()


def cache_info() -> dict:
    """Snapshot of tuned choices (for benchmarks / debugging)."""
    return dict(_CACHE)


def _time_once(fn: Callable[[], object], reps: int = 3) -> float:
    out = fn()                      # compile + warm
    jax.block_until_ready(out)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def autotune(key: Hashable, candidates: Sequence, make_fn: Callable,
             reps: int = 3):
    """Return the candidate minimizing median runtime of ``make_fn(cand)()``.

    ``make_fn(cand)`` must return a zero-arg callable running the kernel with
    that candidate; candidates that fail to trace/compile are skipped. The
    winner is cached under ``key``; on total failure the first candidate is
    cached so the sweep never repeats.
    """
    kind = str(key[0]) if isinstance(key, tuple) and key else "unknown"
    if key in _CACHE:
        _metrics.counter("autotune_cache_hits_total", kind=kind).inc()
        return _CACHE[key]
    _metrics.counter("autotune_cache_misses_total", kind=kind).inc()
    best, best_t = None, float("inf")
    with _tracing.span("autotune.sweep", kind=kind,
                       candidates=len(candidates)):
        for cand in candidates:
            try:
                t = _time_once(make_fn(cand), reps=reps)
            except Exception:
                continue
            if t < best_t:
                best, best_t = cand, t
    if best is None:
        best = candidates[0]
    _CACHE[key] = best
    return best


def ema_blocks(m_a, y_p, ia, ip, *, interpret: bool | None = None,
               candidates: Sequence[tuple[int, int]] = EMA_BLOCK_CANDIDATES
               ) -> tuple[int, int]:
    """Tuned (s_block, n_block) for :func:`..ema.pallas_ema.ema_pallas`.

    The key carries the backend kind, both table dtypes, and the interpret
    flag alongside the shapes — a bf16 sweep never reuses f32 timings. (The
    eMA kernel has no graph operand, so no reorder component here.)"""
    from repro.kernels import resolve_interpret
    from repro.kernels.ema.pallas_ema import ema_pallas
    interpret = resolve_interpret(interpret)
    key = ("ema", m_a.shape, y_p.shape, ia.shape, str(m_a.dtype),
           str(y_p.dtype), interpret)

    def make(cand):
        sb, nb = cand
        return lambda: ema_pallas(m_a, y_p, ia, ip, s_block=sb, n_block=nb,
                                  interpret=interpret)

    return autotune(key, tuple(candidates), make)


def spmm_c_block(m, run_with_c_block: Callable[[int], object], *,
                 kind: str, interpret: bool, reorder: str = "",
                 candidates: Sequence[int] = SPMM_C_BLOCK_CANDIDATES) -> int:
    """Tuned c_block for the Pallas SpMM kernels (gather / bsr / fused).

    ``run_with_c_block(c)`` runs the kernel with that block size; candidates
    larger than the (padded) row count are skipped up front. The cache key
    is (backend kind, shape, dtype, interpret, reorder): a tuned block for
    the RCM-reordered BSR stream is a different entry than the identity
    order's — the block stream, and thus the winner, differs.
    """
    rows = m.shape[-2] if m.ndim >= 2 else 1
    cands = tuple(c for c in candidates if c <= max(rows, min(candidates)))
    if not cands:
        cands = (min(candidates),)
    key = (kind, m.shape, str(m.dtype), interpret, reorder)
    return autotune(key, cands, lambda c: (lambda: run_with_c_block(c)))
