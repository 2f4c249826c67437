"""Pallas TPU kernels for the PGBSC hot path (SpMM, eMA, fused SpMM->eMA).

Every kernel entry point takes ``interpret: bool | None = None``; ``None``
resolves through :func:`resolve_interpret`, so the execution mode follows
the JAX backend and never a default flag.
"""

from __future__ import annotations

import jax
import numpy as np

__all__ = ["resolve_interpret", "accum_dtype", "vmem_bytes",
           "VMEM_BUDGET_BYTES", "HIGHEST"]


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Whether the Pallas kernels run in interpret mode.

    ``None`` follows the default backend: compiled by Mosaic on a TPU,
    interpreted everywhere else (the CPU test backend). An explicit
    ``interpret=True`` on a TPU raises: an interpreted kernel on the chip
    runs correctly but orders of magnitude slower, and would pass for a
    working, slow server.
    """
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("interpret=True on a TPU backend: Pallas kernels "
                         "run compiled on the chip; pass interpret=None")
    return bool(interpret)


# Mosaic's default f32 matmul contracts in bf16 passes; counts need every
# bit of the f32 tables (the adjacency and selection one-hots are exact)
HIGHEST = jax.lax.Precision.HIGHEST


# VMEM working-set budget shared by every kernel's fit model: below the
# 16 MiB scoped-VMEM default of a TPU v5e core, leaving headroom for the
# temporaries Mosaic spills while lowering the kernel bodies. Fit models
# read it at call time, so tests can shrink it.
VMEM_BUDGET_BYTES = 12 * 2 ** 20


def vmem_bytes(rows: int, cols: int, dtype, copies: int = 1) -> int:
    """VMEM bytes of ``copies`` ``(rows, cols)`` tiles as Mosaic lays them
    out: lanes padded to 128, sublanes to 8 rows of 32 bits (16 rows for
    bf16). Pipelined block operands pass ``copies=2`` per block — Pallas
    double-buffers every input and output block."""
    itemsize = np.dtype(dtype).itemsize
    sub = 8 * max(1, 4 // itemsize)
    return (copies * (-(-rows // sub) * sub) * (-(-cols // 128) * 128)
            * itemsize)


def accum_dtype(dtype) -> np.dtype:
    """Accumulator dtype for a storage dtype: sub-f32 storage accumulates
    in f32 (kernel scratch AND the XLA fallback paths), wider passes
    through. This is the \"final reductions in f32\" half of the bf16
    contract — storage is narrow, arithmetic is not."""
    dt = np.dtype(dtype)
    return np.dtype(np.float32) if dt.itemsize < 4 else dt
