"""Pallas eMA kernel (paper §4.5 Algorithm 4 line 7).

Layout (C, N): color combinations on sublanes, vertices on lanes. The static
split tables IA/IP select rows of the resident child tables; each step is a
vector FMA over a block of vertex lanes:

    out[j, v_blk] = sum_l m_a[IA[j, l], v_blk] * y_p[IP[j, l], v_blk]

Grid: (batch, s_blocks, n_blocks) — a batched (B, C, N) coloring table is one
kernel launch with the batch folded into the leading (parallel) grid axis.
The child tables keep their full combination dimension resident in VMEM and
are blocked over vertices only — valid for k <= ~13 (C(13,6) * 512 lanes *
4 B ≈ 3.5 MB per table); larger templates fall back to the XLA path in
ops.py. Row gathers are sublane-dynamic indexing, which Mosaic lowers to
vector loads with a dynamic base — cheap relative to the lane-dynamic gathers
the naive layout would need (that asymmetry is the whole point of the paper's
column-major layout, transposed to TPU lanes).

Tables of any admitted float dtype pass through with output dtype = promoted
input dtype; rows accumulate in the (storage, accum) pair's accumulator
dtype (f32 for bf16 tables — see ``ops.pallas_dtype_pair``) and cast only at
the final store; the padded tail of the split-table axis is masked, so
padded rows cost no FMAs and write exact zeros. Runs interpreted on CPU and
compiled (parallel dimension semantics) on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import accum_dtype, resolve_interpret, vmem_bytes

__all__ = ["ema_pallas", "ema_vmem_bytes"]


def _scratch_shapes(c_a: int, c_p: int, s_block: int, n_block: int, dtype):
    """Row accumulator, plus the widened copies of both resident child
    blocks for sub-32-bit storage (see ``_kernel``)."""
    acc = accum_dtype(dtype)
    shapes = [pltpu.VMEM((s_block, n_block), acc)]
    if jnp.dtype(dtype).itemsize < 4:
        shapes += [pltpu.VMEM((c_a, n_block), acc),
                   pltpu.VMEM((c_p, n_block), acc)]
    return shapes


def ema_vmem_bytes(c_a: int, c_p: int, s_block: int, n_block: int,
                   dtype) -> int:
    """VMEM working set of one grid step: both child blocks and the output
    block double-buffered in the storage dtype, plus the scratch of
    :func:`_scratch_shapes`."""
    blocks = sum(vmem_bytes(r, n_block, dtype, copies=2)
                 for r in (c_a, c_p, s_block))
    return blocks + sum(vmem_bytes(*sh.shape, sh.dtype)
                        for sh in _scratch_shapes(c_a, c_p, s_block,
                                                  n_block, dtype))


def _kernel(ia_ref, ip_ref, ma_ref, yp_ref, out_ref, rows_ref, *widened,
            s_block: int, l: int, s_total: int):
    sb = pl.program_id(1)
    acc_dtype = rows_ref.dtype
    n_blk = rows_ref.shape[-1]
    if widened:
        # packed sub-32-bit tables (bf16 holds two rows per 32-bit sublane):
        # Mosaic loads a single dynamic row only at 32-bit granularity, so
        # the resident blocks are widened once per grid step into
        # accumulator-dtype scratch and the rows are gathered from there
        ma_w, yp_w = widened
        ma_w[...] = ma_ref[0].astype(acc_dtype)
        yp_w[...] = yp_ref[0].astype(acc_dtype)

        def a_row(i):
            return ma_w[pl.dslice(i, 1), :]

        def p_row(i):
            return yp_w[pl.dslice(i, 1), :]
    else:
        def a_row(i):
            return ma_ref[0, pl.dslice(i, 1), :]

        def p_row(i):
            return yp_ref[0, pl.dslice(i, 1), :]

    def s_body(s, _):
        s_global = sb * s_block + s

        def compute_row():
            # rows accumulate in acc_dtype (f32 for bf16 storage) and cast
            # at the store, so narrow tables never pay accumulation error
            def l_body(j, row):
                a = a_row(ia_ref[s_global, j]).astype(acc_dtype)
                p = p_row(ip_ref[s_global, j]).astype(acc_dtype)
                return row + a * p

            return jax.lax.fori_loop(0, l, l_body,
                                     jnp.zeros((1, n_blk), acc_dtype))

        # padded split rows (s_global >= s_total) skip the FMA loop entirely
        # and store zeros, so padding costs no work and no garbage values
        rows_ref[pl.dslice(s, 1), :] = jax.lax.cond(
            s_global < s_total, compute_row,
            lambda: jnp.zeros((1, n_blk), acc_dtype))
        return 0

    jax.lax.fori_loop(0, s_block, s_body, 0)
    # one aligned store of the whole (s_block, n_block) output block
    out_ref[0] = rows_ref[...].astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("s_block", "n_block", "interpret")
)
def ema_pallas(
    m_a: jnp.ndarray,   # (Ca, N) or (B, Ca, N)
    y_p: jnp.ndarray,   # (Cp, N) or (B, Cp, N)
    ia: jnp.ndarray,    # (S, L) int32
    ip: jnp.ndarray,    # (S, L) int32
    *,
    s_block: int = 8,
    n_block: int = 512,
    interpret: bool | None = None,
) -> jnp.ndarray:
    interpret = resolve_interpret(interpret)
    s, l = ia.shape
    batched = m_a.ndim > 2
    if m_a.ndim != y_p.ndim:
        raise ValueError(f"rank mismatch: {m_a.shape} vs {y_p.shape}")
    if not batched:
        m_a = m_a[None]
        y_p = y_p[None]
    if m_a.ndim != 3:
        # collapse any extra leading dims into one batch axis
        lead = m_a.shape[:-2]
        out = ema_pallas(m_a.reshape((-1,) + m_a.shape[-2:]),
                         y_p.reshape((-1,) + y_p.shape[-2:]), ia, ip,
                         s_block=s_block, n_block=n_block,
                         interpret=interpret)
        return out.reshape(lead + out.shape[-2:])
    dtype = jnp.promote_types(m_a.dtype, y_p.dtype)
    m_a = m_a.astype(dtype)
    y_p = y_p.astype(dtype)
    b, _, n = m_a.shape
    assert y_p.shape[-1] == n
    s_pad = -(-s // s_block) * s_block
    n_pad = -(-n // n_block) * n_block
    if s_pad != s:
        # padded rows are masked inside the kernel (index 0 is a placeholder
        # that is never dereferenced); sliced away afterwards
        ia = jnp.pad(ia, ((0, s_pad - s), (0, 0)))
        ip = jnp.pad(ip, ((0, s_pad - s), (0, 0)))
    if n_pad != n:
        m_a = jnp.pad(m_a, ((0, 0), (0, 0), (0, n_pad - n)))
        y_p = jnp.pad(y_p, ((0, 0), (0, 0), (0, n_pad - n)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, s_pad // s_block, n_pad // n_block),
        in_specs=[
            pl.BlockSpec((1, m_a.shape[1], n_block),
                         lambda bb, sb, nb, IA, IP: (bb, 0, nb)),
            pl.BlockSpec((1, y_p.shape[1], n_block),
                         lambda bb, sb, nb, IA, IP: (bb, 0, nb)),
        ],
        out_specs=pl.BlockSpec((1, s_block, n_block),
                               lambda bb, sb, nb, IA, IP: (bb, sb, nb)),
        scratch_shapes=_scratch_shapes(m_a.shape[1], y_p.shape[1], s_block,
                                       n_block, dtype),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, s_block=s_block, l=l, s_total=s),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, s_pad, n_pad), dtype),
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
    )(ia, ip, m_a, y_p)
    out = out[:, :s, :n]
    return out if batched else out[0]
