"""Pallas TPU SpMM — edge-chunk gather path with on-the-fly densification.

TPU adaptation of the paper's CSC SpMM (§4.5): random column gathers do not
map onto the TPU memory hierarchy, so each destination-tile-sorted edge chunk
is *densified on the fly* inside VMEM via one-hot outer products and applied
as a 128x128 MXU matmul:

    P        = (onehot(src_local) * mask) @ onehot(dst_local)^T   # (T, T)
    out_tile += m_src_tile @ P                                    # MXU

The chunk stream is sorted by destination tile, so the output block stays
resident in VMEM across consecutive grid steps (revisiting pattern) and is
zero-initialized on first visit. ``src_tile``/``dst_tile`` ride the scalar
prefetch channel and drive the BlockSpec index maps (the TPU analogue of the
paper's propagation blocking).

Grid: (c_blocks, n_chunks). VMEM per step:
    m block   (C_BLK, T)     e.g. 512x128x4B = 256 KB
    out block (C_BLK, T)     256 KB
    one-hot scratch / P      (T, E_CHUNK) + (T, T) ≈ 320 KB
comfortably inside the ~16 MB VMEM budget; C_BLK and E_CHUNK are tunable.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import HIGHEST, accum_dtype, resolve_interpret

__all__ = ["spmm_gather_pallas"]

# edge chunks per block of the src / dst_local / mask arrays
_CHUNK_ROWS = 8


def _kernel(src_tile_ref, dst_tile_ref,        # scalar prefetch (SMEM)
            src_ref, dstl_ref, mask_ref, m_ref,  # inputs
            out_ref,                            # output
            acc_ref):                           # VMEM accumulator scratch
    t = pl.program_id(1)
    nc = pl.num_programs(1)
    tile = out_ref.shape[1]
    acc_dtype = acc_ref.dtype

    # Zero the accumulator on the first chunk of each destination tile.
    is_first = jnp.logical_or(
        t == 0, dst_tile_ref[t] != dst_tile_ref[jnp.maximum(t - 1, 0)]
    )

    @pl.when(is_first)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # this chunk's row of the 8-chunk edge block
    row = pl.dslice(t % _CHUNK_ROWS, 1)
    src = src_ref[row, :]          # (1, E) global src ids of this chunk
    dstl = dstl_ref[row, :]        # (1, E) local dst offsets
    mask = mask_ref[row, :].astype(acc_dtype)  # (1, E) {0,1}

    src_local = src - src_tile_ref[t] * tile
    lane = jax.lax.broadcasted_iota(jnp.int32, (tile, src.shape[1]), 0)
    onehot_src = jnp.where(lane == src_local, mask, jnp.zeros((), acc_dtype))
    onehot_dst = (lane == dstl).astype(acc_dtype)
    p = jax.lax.dot_general(
        onehot_src, onehot_dst,
        (((1,), (1,)), ((), ())),
        preferred_element_type=acc_dtype, precision=HIGHEST,
    )                               # (T, T) densified adjacency block
    # partial sums in the accumulator dtype (f32 for bf16 storage); the
    # output block is written once, on the tile's last chunk
    acc_ref[...] += jax.lax.dot(
        m_ref[...].astype(acc_dtype), p, preferred_element_type=acc_dtype,
        precision=HIGHEST
    )

    is_last = jnp.logical_or(
        t == nc - 1, dst_tile_ref[t] != dst_tile_ref[jnp.minimum(t + 1, nc - 1)]
    )

    @pl.when(is_last)
    def _store():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("n_tiles", "tile", "c_block", "interpret"),
)
def spmm_gather_pallas(
    m: jnp.ndarray,            # (C, N) float, N = n_tiles * tile
    src: jnp.ndarray,          # (n_chunks, E) int32 global src ids
    dst_local: jnp.ndarray,    # (n_chunks, E) int32
    mask: jnp.ndarray,         # (n_chunks, E) {0,1}, cast to m's dtype
    src_tile: jnp.ndarray,     # (n_chunks,) int32
    dst_tile: jnp.ndarray,     # (n_chunks,) int32  (sorted ascending)
    *,
    n_tiles: int,
    tile: int = 128,
    c_block: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    interpret = resolve_interpret(interpret)
    c, n = m.shape
    assert n == n_tiles * tile, (n, n_tiles, tile)
    dtype = m.dtype
    c_pad = -(-c // c_block) * c_block
    if c_pad != c:
        m = jnp.pad(m, ((0, c_pad - c), (0, 0)))
    n_chunks, e = src.shape
    # Mosaic blocks span 8 rows at least, so the edge arrays are read 8
    # chunks per block (one DMA per 8 grid steps) and the kernel picks its
    # chunk's row; the mask rides in the 32-bit accumulator dtype, whose
    # single rows load at any offset
    r_pad = -(-n_chunks // _CHUNK_ROWS) * _CHUNK_ROWS - n_chunks
    src, dst_local, mask = (
        jnp.pad(x, ((0, r_pad), (0, 0))) for x in
        (src, dst_local, mask.astype(accum_dtype(dtype))))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(c_pad // c_block, n_chunks),
        in_specs=[
            pl.BlockSpec((_CHUNK_ROWS, e),
                         lambda cb, t, st, dt: (t // _CHUNK_ROWS, 0)),
            pl.BlockSpec((_CHUNK_ROWS, e),
                         lambda cb, t, st, dt: (t // _CHUNK_ROWS, 0)),
            pl.BlockSpec((_CHUNK_ROWS, e),
                         lambda cb, t, st, dt: (t // _CHUNK_ROWS, 0)),
            pl.BlockSpec((c_block, tile), lambda cb, t, st, dt: (cb, st[t])),
        ],
        out_specs=pl.BlockSpec((c_block, tile), lambda cb, t, st, dt: (cb, dt[t])),
        scratch_shapes=[pltpu.VMEM((c_block, tile), accum_dtype(dtype))],
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((c_pad, n), dtype),
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
    )(src_tile, dst_tile, src, dst_local, mask, m)
    return out[:c]
