"""Fused SpMM -> eMA Pallas kernel (paper Algorithm 4 lines 3+7, one pass).

The unfused PGBSC walk materializes each plan node's passive neighbor-sum
table ``y_p = m_p @ A`` (shape ``(B, C(k,t_p), N)``) in HBM, then reads it
back for the eMA. This kernel keeps the whole exchange in VMEM: the grid
walks the destination-tile-sorted BSR block stream of the adjacency, and for
each destination vertex tile

    1. accumulates ``y[:, :, tile] += m_p[:, :, src_tile] @ block`` into a
       VMEM scratch accumulator (MXU matmuls over the tile's block run),
    2. on the tile's last block, applies the (IA, IP) split combination —
       expressed as one-hot selection matmuls per split, the MXU-friendly
       form of the row gathers — against the resident active table block and
       writes ONLY the ``(bb, C(k,t), tile)`` output block; y never exists
       outside VMEM.

Grid: (batch_blocks, n_blocks). The coloring batch is tiled into blocks of
``bb`` colorings that ride INSIDE the kernel block shapes (largest ``bb``
whose working set fits VMEM) rather than as bare grid steps — per-step
overhead is paid once per ``bb`` colorings, and the MXU matmuls see
``bb``-fold taller operands. The batch-block axis is parallel; the BSR block
axis is "arbitrary" (the scratch accumulator and output block carry state
across consecutive steps of one destination tile). ``Graph.bsr()``
guarantees every destination tile has at least one block (zero blocks are
inserted for empty tiles), so every output block is written. Padded output
rows (combination axis rounded up to the sublane multiple) select nothing
and come out exact zeros; padded batch rows see zero tables.

Correct under interpret mode on CPU; ``dimension_semantics`` set for the
compiled TPU path (the batched ``dot_general`` contractions need a Mosaic
with batched-dot support). Dtypes the dispatch layer admits (see
``ema.ops.pallas_dtype_pair``) split into a (storage, accumulator) pair:
tables and adjacency blocks stream in the storage dtype (bf16 halves their
HBM traffic), while the y scratch and the split-combination accumulator run
in the pair's accumulator dtype (f32 for bf16) and cast only at the output
store.

``fused_spmm_ema_shared_pallas`` generalizes the launch to a GROUP of
consumers sharing one passive child: the SpMM leg runs ONCE into the shared
y scratch, then each consumer's split combination reads it and writes its
own output table — the shared sub-templates a fused multi-template plan
creates cost one SpMM for the whole group instead of one per consumer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels
from repro.kernels import HIGHEST, accum_dtype, resolve_interpret, vmem_bytes

__all__ = ["fused_spmm_ema_pallas", "fused_spmm_ema_shared_pallas",
           "group_vmem_bytes", "group_batch_block_fits",
           "pick_group_batch_block"]


def group_vmem_bytes(bb: int, c_as: tuple[int, ...], c_p: int,
                     s_pads: tuple[int, ...], ls: tuple[int, ...],
                     tile: int, dtype) -> int:
    """VMEM working set of one grid step of the shared-passive group kernel
    (a group of one is the solo kernel) with a ``bb``-coloring batch block.

    Double-buffered block operands in the storage dtype: the adjacency
    tile, the passive block, and per consumer its active block, output
    block and two selection stacks. Single-buffered, in the accumulator
    dtype: the y scratch, and the split-combination temporaries of the
    largest consumer (accumulator, selected active rows, selected passive
    rows, transposed result, each ``(s_pad, bb, tile)``, plus the widened
    active block) — consumers combine one after another.
    """
    acc = accum_dtype(dtype)
    total = (vmem_bytes(tile, tile, dtype, copies=2)
             + bb * vmem_bytes(c_p, tile, dtype, copies=2)
             + bb * vmem_bytes(c_p, tile, acc))
    temps = 0
    for c_a, s_pad, l in zip(c_as, s_pads, ls):
        total += (bb * vmem_bytes(c_a, tile, dtype, copies=2)
                  + bb * vmem_bytes(s_pad, tile, dtype, copies=2)
                  + l * vmem_bytes(s_pad, c_a, dtype, copies=2)
                  + l * vmem_bytes(s_pad, c_p, dtype, copies=2))
        temps = max(temps, 4 * s_pad * vmem_bytes(bb, tile, acc)
                    + bb * vmem_bytes(c_a, tile, acc))
    return total + temps


def group_batch_block_fits(bb: int, c_as: tuple[int, ...], c_p: int,
                           s_pads: tuple[int, ...], ls: tuple[int, ...],
                           tile: int, dtype) -> bool:
    """Whether a ``bb``-coloring group step fits the VMEM budget."""
    return group_vmem_bytes(bb, c_as, c_p, s_pads, ls, tile,
                            dtype) < kernels.VMEM_BUDGET_BYTES


def pick_group_batch_block(b: int, c_as: tuple[int, ...], c_p: int,
                           s_pads: tuple[int, ...], ls: tuple[int, ...],
                           tile: int, dtype) -> int:
    """Largest batch block whose group working set fits VMEM; floors at 1."""
    bb = max(1, b)
    while bb > 1 and not group_batch_block_fits(bb, c_as, c_p, s_pads, ls,
                                                tile, dtype):
        bb = -(-bb // 2)
    return bb


def _kernel(dst_tile_ref, src_tile_ref,                   # scalar prefetch
            blocks_ref, ma_ref, mp_ref, sela_ref, selp_ref,  # inputs
            out_ref,                                      # output
            y_ref,                                        # VMEM scratch
            *, l: int):
    b = pl.program_id(1)
    nb = pl.num_programs(1)
    acc_dtype = y_ref.dtype      # accumulator pair member (f32 for bf16)

    # --- SpMM leg: accumulate this destination tile's neighbor sums in VMEM
    is_first = jnp.logical_or(
        b == 0, dst_tile_ref[b] != dst_tile_ref[jnp.maximum(b - 1, 0)]
    )

    @pl.when(is_first)
    def _zero():
        y_ref[...] = jnp.zeros_like(y_ref)

    # (bb, Cp, tile) @ (tile, tile): fold the batch block into matmul rows
    bb, c_p, tile = y_ref.shape
    mp_flat = mp_ref[...].reshape(bb * c_p, tile).astype(acc_dtype)
    y_ref[...] += jax.lax.dot(
        mp_flat, blocks_ref[0].astype(acc_dtype),
        preferred_element_type=acc_dtype, precision=HIGHEST,
    ).reshape(bb, c_p, tile)

    # --- eMA leg: on the tile's last block, combine and write the output.
    # The (IA, IP) row gathers are expressed as one-hot selection matmuls
    # (MXU-friendly; TPU Pallas has no dynamic sublane gather): per split i,
    #   out[b] += (sel_a[i] @ m_a[b]) * (sel_p[i] @ y[b]).
    # Padded output rows have all-zero selection rows, so they come out
    # exact zeros without a separate masking pass.
    is_last = jnp.logical_or(
        b == nb - 1, dst_tile_ref[b] != dst_tile_ref[jnp.minimum(b + 1, nb - 1)]
    )

    @pl.when(is_last)
    def _combine():
        s_pad = out_ref.shape[1]
        contract = (((1,), (1,)), ((), ()))   # sel (S,C) x table (bb,C,tile)
        ma = ma_ref[...].astype(acc_dtype)

        def body(i, acc):
            sel_a = sela_ref[pl.dslice(i, 1)][0].astype(acc_dtype)  # (S_pad, Ca)
            sel_p = selp_ref[pl.dslice(i, 1)][0].astype(acc_dtype)  # (S_pad, Cp)
            a_rows = jax.lax.dot_general(
                sel_a, ma, contract, preferred_element_type=acc_dtype,
                precision=HIGHEST)
            p_rows = jax.lax.dot_general(
                sel_p, y_ref[...], contract, preferred_element_type=acc_dtype,
                precision=HIGHEST)
            return acc + a_rows * p_rows                  # (S_pad, bb, tile)

        acc = jax.lax.fori_loop(
            0, l, body, jnp.zeros((s_pad, bb, tile), acc_dtype))
        out_ref[...] = acc.transpose(1, 0, 2).astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("n_tiles", "tile", "interpret")
)
def fused_spmm_ema_pallas(
    m_a: jnp.ndarray,        # (B, Ca, N) float, N = n_tiles * tile
    m_p: jnp.ndarray,        # (B, Cp, N) float
    ia: jnp.ndarray,         # (S, L) int32
    ip: jnp.ndarray,         # (S, L) int32
    blocks: jnp.ndarray,     # (n_blocks, tile, tile) {0,1} adjacency tiles
    src_tile: jnp.ndarray,   # (n_blocks,) int32
    dst_tile: jnp.ndarray,   # (n_blocks,) int32, sorted ascending, all tiles
    *,
    n_tiles: int,
    tile: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """-> (B, S, N): ``ema(m_a, m_p @ A, ia, ip)`` without materializing
    the ``(B, Cp, N)`` neighbor-sum table. Inputs must be 3-D (batched);
    the ops-layer wrapper handles rank/padding/dtype dispatch."""
    interpret = resolve_interpret(interpret)
    s, l = ia.shape
    b, _, n = m_a.shape
    assert n == n_tiles * tile, (n, n_tiles, tile)
    assert m_p.shape[0] == b and m_p.shape[2] == n
    dtype = jnp.promote_types(m_a.dtype, m_p.dtype)
    acc_dt = jnp.dtype(accum_dtype(dtype))
    m_a = m_a.astype(dtype)
    m_p = m_p.astype(dtype)
    c_a, c_p = m_a.shape[1], m_p.shape[1]
    s_pad = -(-s // 8) * 8          # sublane multiple for the output block
    bb = pick_group_batch_block(b, (c_a,), c_p, (s_pad,), (l,), tile, dtype)
    b_pad = -(-b // bb) * bb
    if b_pad != b:
        m_a = jnp.pad(m_a, ((0, b_pad - b), (0, 0), (0, 0)))
        m_p = jnp.pad(m_p, ((0, b_pad - b), (0, 0), (0, 0)))
    # one-hot selection matrices per split: sel[i, j, c] = 1 iff split i of
    # output row j reads table row c. Padded rows (>= s) select nothing.
    sel_a = (ia.T[:, :, None] == jnp.arange(c_a)).astype(dtype)  # (L, S, Ca)
    sel_p = (ip.T[:, :, None] == jnp.arange(c_p)).astype(dtype)  # (L, S, Cp)
    if s_pad != s:
        pad = ((0, 0), (0, s_pad - s), (0, 0))
        sel_a = jnp.pad(sel_a, pad)
        sel_p = jnp.pad(sel_p, pad)
    n_blocks = blocks.shape[0]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b_pad // bb, n_blocks),
        in_specs=[
            pl.BlockSpec((1, tile, tile),
                         lambda g, blk, dt, st: (blk, 0, 0)),
            pl.BlockSpec((bb, c_a, tile),
                         lambda g, blk, dt, st: (g, 0, dt[blk])),
            pl.BlockSpec((bb, c_p, tile),
                         lambda g, blk, dt, st: (g, 0, st[blk])),
            pl.BlockSpec((l, s_pad, c_a),
                         lambda g, blk, dt, st: (0, 0, 0)),
            pl.BlockSpec((l, s_pad, c_p),
                         lambda g, blk, dt, st: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bb, s_pad, tile),
                               lambda g, blk, dt, st: (g, 0, dt[blk])),
        scratch_shapes=[pltpu.VMEM((bb, c_p, tile), acc_dt)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, l=l),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b_pad, s_pad, n), dtype),
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(dst_tile, src_tile, blocks, m_a, m_p, sel_a, sel_p)
    return out[:b, :s, :]


# ---------------------------------------------------------------------------
# Shared-passive group launch: one SpMM leg, many consumers
# ---------------------------------------------------------------------------

def _shared_kernel(dst_tile_ref, src_tile_ref,            # scalar prefetch
                   *refs, n_cons: int, ls: tuple[int, ...]):
    # refs layout: blocks, mp, (ma_i, sela_i, selp_i) x n_cons,
    #              out_i x n_cons, y scratch
    blocks_ref, mp_ref = refs[0], refs[1]
    cons = [refs[2 + 3 * i: 5 + 3 * i] for i in range(n_cons)]
    outs = refs[2 + 3 * n_cons: 2 + 4 * n_cons]
    y_ref = refs[-1]
    b = pl.program_id(1)
    nb = pl.num_programs(1)
    acc_dtype = y_ref.dtype

    is_first = jnp.logical_or(
        b == 0, dst_tile_ref[b] != dst_tile_ref[jnp.maximum(b - 1, 0)]
    )

    @pl.when(is_first)
    def _zero():
        y_ref[...] = jnp.zeros_like(y_ref)

    bb, c_p, tile = y_ref.shape
    mp_flat = mp_ref[...].reshape(bb * c_p, tile).astype(acc_dtype)
    y_ref[...] += jax.lax.dot(
        mp_flat, blocks_ref[0].astype(acc_dtype),
        preferred_element_type=acc_dtype, precision=HIGHEST,
    ).reshape(bb, c_p, tile)

    is_last = jnp.logical_or(
        b == nb - 1, dst_tile_ref[b] != dst_tile_ref[jnp.minimum(b + 1, nb - 1)]
    )

    @pl.when(is_last)
    def _combine():
        contract = (((1,), (1,)), ((), ()))
        # the consumer loop unrolls at trace time; every consumer reads the
        # SAME resident y scratch — the SpMM leg was paid once above
        for ci in range(n_cons):
            ma_ref, sela_ref, selp_ref = cons[ci]
            out_ref = outs[ci]
            s_pad = out_ref.shape[1]
            ma = ma_ref[...].astype(acc_dtype)

            def body(i, acc, sela_ref=sela_ref, selp_ref=selp_ref, ma=ma):
                sel_a = sela_ref[pl.dslice(i, 1)][0].astype(acc_dtype)
                sel_p = selp_ref[pl.dslice(i, 1)][0].astype(acc_dtype)
                a_rows = jax.lax.dot_general(
                    sel_a, ma, contract, preferred_element_type=acc_dtype,
                precision=HIGHEST)
                p_rows = jax.lax.dot_general(
                    sel_p, y_ref[...], contract,
                    preferred_element_type=acc_dtype, precision=HIGHEST)
                return acc + a_rows * p_rows

            acc = jax.lax.fori_loop(
                0, ls[ci], body, jnp.zeros((s_pad, bb, tile), acc_dtype))
            out_ref[...] = acc.transpose(1, 0, 2).astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("n_tiles", "tile", "interpret")
)
def fused_spmm_ema_shared_pallas(
    m_as: tuple,             # per-consumer (B, Ca_i, N) float
    m_p: jnp.ndarray,        # (B, Cp, N) float — the shared passive table
    ias: tuple,              # per-consumer (S_i, L_i) int32
    ips: tuple,              # per-consumer (S_i, L_i) int32
    blocks: jnp.ndarray,     # (n_blocks, tile, tile) adjacency tiles
    src_tile: jnp.ndarray,   # (n_blocks,) int32
    dst_tile: jnp.ndarray,   # (n_blocks,) int32, sorted ascending, all tiles
    *,
    n_tiles: int,
    tile: int = 128,
    interpret: bool | None = None,
) -> tuple:
    """-> per-consumer (B, S_i, N) tuple: every consumer's
    ``ema(m_a_i, m_p @ A, ia_i, ip_i)`` from ONE launch whose SpMM leg runs
    once into shared VMEM scratch. Inputs must be 3-D (batched)."""
    interpret = resolve_interpret(interpret)
    n_cons = len(m_as)
    assert n_cons == len(ias) == len(ips) and n_cons >= 1
    b, _, n = m_as[0].shape
    assert n == n_tiles * tile, (n, n_tiles, tile)
    assert m_p.shape[0] == b and m_p.shape[2] == n
    dtype = m_p.dtype
    for ma in m_as:
        dtype = jnp.promote_types(dtype, ma.dtype)
    acc_dt = jnp.dtype(accum_dtype(dtype))
    m_p = m_p.astype(dtype)
    m_as = tuple(ma.astype(dtype) for ma in m_as)
    c_p = m_p.shape[1]
    c_as = tuple(ma.shape[1] for ma in m_as)
    ss = tuple(ia.shape[0] for ia in ias)
    ls = tuple(ia.shape[1] for ia in ias)
    s_pads = tuple(-(-s // 8) * 8 for s in ss)
    bb = pick_group_batch_block(b, c_as, c_p, s_pads, ls, tile, dtype)
    b_pad = -(-b // bb) * bb
    if b_pad != b:
        pad = ((0, b_pad - b), (0, 0), (0, 0))
        m_p = jnp.pad(m_p, pad)
        m_as = tuple(jnp.pad(ma, pad) for ma in m_as)
    sel_as, sel_ps = [], []
    for ia, ip, c_a, s, s_pad in zip(ias, ips, c_as, ss, s_pads):
        sa = (ia.T[:, :, None] == jnp.arange(c_a)).astype(dtype)  # (L, S, Ca)
        sp = (ip.T[:, :, None] == jnp.arange(c_p)).astype(dtype)  # (L, S, Cp)
        if s_pad != s:
            pad = ((0, 0), (0, s_pad - s), (0, 0))
            sa, sp = jnp.pad(sa, pad), jnp.pad(sp, pad)
        sel_as.append(sa)
        sel_ps.append(sp)
    n_blocks = blocks.shape[0]

    in_specs = [
        pl.BlockSpec((1, tile, tile), lambda g, blk, dt, st: (blk, 0, 0)),
        pl.BlockSpec((bb, c_p, tile), lambda g, blk, dt, st: (g, 0, st[blk])),
    ]
    operands = [blocks, m_p]
    for ci in range(n_cons):
        in_specs.append(pl.BlockSpec(
            (bb, c_as[ci], tile), lambda g, blk, dt, st: (g, 0, dt[blk])))
        in_specs.append(pl.BlockSpec(
            (ls[ci], s_pads[ci], c_as[ci]), lambda g, blk, dt, st: (0, 0, 0)))
        in_specs.append(pl.BlockSpec(
            (ls[ci], s_pads[ci], c_p), lambda g, blk, dt, st: (0, 0, 0)))
        operands += [m_as[ci], sel_as[ci], sel_ps[ci]]
    out_specs = [
        pl.BlockSpec((bb, sp, tile), lambda g, blk, dt, st: (g, 0, dt[blk]))
        for sp in s_pads
    ]
    out_shape = [jax.ShapeDtypeStruct((b_pad, sp, n), dtype) for sp in s_pads]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b_pad // bb, n_blocks),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((bb, c_p, tile), acc_dt)],
    )
    outs = pl.pallas_call(
        functools.partial(_shared_kernel, n_cons=n_cons, ls=ls),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(dst_tile, src_tile, *operands)
    return tuple(out[:b, :s, :] for out, s in zip(outs, ss))
