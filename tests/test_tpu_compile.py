"""Ahead-of-time compiles of the counting kernels for a TPU v5e chip.

Each test lowers one kernel at a deployment-sized plan-node shape and
compiles it with the TPU compiler against a described (not attached)
``v5e:2x2`` topology, so Mosaic's block-alignment and VMEM checks and
XLA's device-memory check run on every test pass without a chip. Nothing
executes: these tests say that the kernels compile, not what they compute
or how fast (the kernel tests and ``chip_smoke.py`` cover that).

Shapes are the ``u7`` template's plan nodes at a coloring batch of 8:

* the segment SpMM and the eMA at Graph500 RMAT scale 20 (``N = 2**20``
  vertices, 31,403,422 edge slots);
* the gather and BSR SpMMs and the fused kernels at RMAT scale 14. These
  kernels prefetch one source and one destination tile id per edge chunk
  or adjacency block into the core's 1 MiB SMEM, which caps the stream at
  about 120k entries: RMAT-20's 6,263,363 chunks of 128 edge slots need
  50 MB and the compiler refuses them. RMAT-14 has 12,176 chunks of 512
  slots and 12,037 occupied 128x128 blocks.
"""

import os
from math import comb

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ema.pallas_ema import ema_pallas
from repro.kernels.fused.pallas_fused import (fused_spmm_ema_pallas,
                                              fused_spmm_ema_shared_pallas)
from repro.kernels.spmm.ops import _spmm_segment
from repro.kernels.spmm.pallas_bsr import spmm_bsr_pallas
from repro.kernels.spmm.pallas_gather import spmm_gather_pallas

HBM_BYTES = 16 * 10 ** 9          # one TPU v5e chip
TILE = 128
BATCH = 8
K = 7
N20 = 1 << 20                     # RMAT scale 20
EDGES20 = 31_403_422              # its directed edge slots (seed 0)
N14 = 1 << 14                     # RMAT scale 14
CHUNKS14 = 12_176                 # its 512-slot edge chunks
BLOCKS14 = 12_037                 # its BSR blocks (no empty tile)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, used
    return compiled


def _is_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_gather_spmm_rmat14(one_chip):
    rows = BATCH * comb(K, 3)
    e = 512
    compiled = _compile(
        lambda m, s, d, mk, st, dt: spmm_gather_pallas(
            m, s, d, mk, st, dt, n_tiles=N14 // TILE, tile=TILE,
            interpret=False),
        one_chip, ((rows, N14), jnp.float32),
        ((CHUNKS14, e), jnp.int32), ((CHUNKS14, e), jnp.int32),
        ((CHUNKS14, e), jnp.float32), ((CHUNKS14,), jnp.int32),
        ((CHUNKS14,), jnp.int32))
    assert _is_kernel(compiled)


def test_segment_spmm_rmat20(one_chip):
    rows = BATCH * comb(K, 3)
    _compile(lambda m, s, d: _spmm_segment(m, s, d, N20), one_chip,
             ((rows, N20), jnp.float32), ((EDGES20,), jnp.int32),
             ((EDGES20,), jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_ema_rmat20(one_chip, dtype):
    # u7 node (t=4, t_a=1): 7 active rows, 35 passive, 35 outputs x 4 splits
    c_a, c_p, s, l = comb(K, 1), comb(K, 3), comb(K, 4), comb(4, 1)
    compiled = _compile(
        lambda a, p, ia, ip: ema_pallas(a, p, ia, ip, interpret=False),
        one_chip, ((BATCH, c_a, N20), dtype), ((BATCH, c_p, N20), dtype),
        ((s, l), jnp.int32), ((s, l), jnp.int32))
    assert _is_kernel(compiled)


def test_bsr_spmm_rmat14(one_chip):
    rows = BATCH * comb(K, 3)
    compiled = _compile(
        lambda m, b, st, dt: spmm_bsr_pallas(
            m, b, st, dt, n_tiles=N14 // TILE, tile=TILE, interpret=False),
        one_chip, ((rows, N14), jnp.float32),
        ((BLOCKS14, TILE, TILE), jnp.float32), ((BLOCKS14,), jnp.int32),
        ((BLOCKS14,), jnp.int32))
    assert _is_kernel(compiled)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_fused_rmat14(one_chip, dtype):
    c_a, c_p, s, l = comb(K, 1), comb(K, 3), comb(K, 4), comb(4, 1)
    compiled = _compile(
        lambda a, p, ia, ip, b, st, dt: fused_spmm_ema_pallas(
            a, p, ia, ip, b, st, dt, n_tiles=N14 // TILE, tile=TILE,
            interpret=False),
        one_chip, ((BATCH, c_a, N14), dtype), ((BATCH, c_p, N14), dtype),
        ((s, l), jnp.int32), ((s, l), jnp.int32),
        ((BLOCKS14, TILE, TILE), dtype), ((BLOCKS14,), jnp.int32),
        ((BLOCKS14,), jnp.int32))
    assert _is_kernel(compiled)


def test_fused_shared_rmat14(one_chip):
    # the k=5 two-template bundle's group: two consumers of one passive
    # child, (t=5, t_a=3) and (t=4, t_a=2), 10 passive rows each
    k = 5
    dims = [(comb(k, 3), comb(k, 5), comb(5, 3)),
            (comb(k, 2), comb(k, 4), comb(4, 2))]
    c_p = comb(k, 2)
    shapes = [((BATCH, c_a, N14), jnp.float32) for c_a, _, _ in dims]
    shapes.append(((BATCH, c_p, N14), jnp.float32))
    shapes += [((s, l), jnp.int32) for _, s, l in dims for _ in range(2)]
    shapes += [((BLOCKS14, TILE, TILE), jnp.float32),
               ((BLOCKS14,), jnp.int32), ((BLOCKS14,), jnp.int32)]

    def fn(a1, a2, p, ia1, ip1, ia2, ip2, b, st, dt):
        return fused_spmm_ema_shared_pallas(
            (a1, a2), p, (ia1, ia2), (ip1, ip2), b, st, dt,
            n_tiles=N14 // TILE, tile=TILE, interpret=False)

    assert _is_kernel(_compile(fn, one_chip, *shapes))
