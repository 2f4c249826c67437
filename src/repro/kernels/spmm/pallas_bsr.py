"""Pallas TPU SpMM — pre-densified block-sparse (BSR) MXU path.

The adjacency is stored as dense 128x128 tiles for nonempty blocks only
(`Graph.bsr()`); after RCM reordering the nonzeros concentrate near the
diagonal so the number of stored blocks approaches E / (tile * avg_deg_local).
Each grid step is a single MXU matmul:

    out[:, dst_tile] += m[:, src_tile] @ block

Blocks are sorted by destination tile (consecutive output revisiting);
src/dst tile ids ride the scalar-prefetch channel into the BlockSpec index
maps. Compared to the gather path this trades HBM footprint
(tile^2 * 4B per nonempty block) for zero densification work per step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import HIGHEST, accum_dtype, resolve_interpret

__all__ = ["spmm_bsr_pallas"]


def _kernel(src_tile_ref, dst_tile_ref, blocks_ref, m_ref, out_ref, acc_ref):
    b = pl.program_id(1)
    nb = pl.num_programs(1)
    acc_dtype = acc_ref.dtype
    is_first = jnp.logical_or(
        b == 0, dst_tile_ref[b] != dst_tile_ref[jnp.maximum(b - 1, 0)]
    )

    @pl.when(is_first)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # partial sums live in the accumulator scratch (f32 for bf16 storage);
    # the output block is written once, on the tile's last block
    acc_ref[...] += jax.lax.dot(
        m_ref[...].astype(acc_dtype), blocks_ref[0].astype(acc_dtype),
        preferred_element_type=acc_dtype, precision=HIGHEST,
    )

    is_last = jnp.logical_or(
        b == nb - 1, dst_tile_ref[b] != dst_tile_ref[jnp.minimum(b + 1, nb - 1)]
    )

    @pl.when(is_last)
    def _store():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("n_tiles", "tile", "c_block", "interpret")
)
def spmm_bsr_pallas(
    m: jnp.ndarray,          # (C, N) float, N = n_tiles * tile
    blocks: jnp.ndarray,     # (n_blocks, tile, tile) {0,1}, cast to m's dtype
    src_tile: jnp.ndarray,   # (n_blocks,) int32
    dst_tile: jnp.ndarray,   # (n_blocks,) int32, sorted ascending
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
    blocks = blocks.astype(dtype)
    c_pad = -(-c // c_block) * c_block
    if c_pad != c:
        m = jnp.pad(m, ((0, c_pad - c), (0, 0)))
    n_blocks = blocks.shape[0]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(c_pad // c_block, n_blocks),
        in_specs=[
            pl.BlockSpec((1, tile, tile), lambda cb, b, st, dt: (b, 0, 0)),
            pl.BlockSpec((c_block, tile), lambda cb, b, st, dt: (cb, st[b])),
        ],
        out_specs=pl.BlockSpec((c_block, tile), lambda cb, b, st, dt: (cb, dt[b])),
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
    )(src_tile, dst_tile, blocks, m)
    return out[:c]
