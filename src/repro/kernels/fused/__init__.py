"""Fused SpMM -> eMA Pallas kernel: one plan node, one kernel, no HBM
y-cache intermediate (paper §4.5's bandwidth argument taken to its limit).
The shared variant runs the SpMM leg once for a GROUP of consumers of the
same passive child, keeping the y tiles in VMEM scratch across them."""

from repro.kernels.fused.ops import (FusedPrep, fused_group_fits_vmem,
                                     fused_spmm_ema, fused_spmm_ema_shared,
                                     prepare_fused)

__all__ = ["FusedPrep", "fused_group_fits_vmem",
           "fused_spmm_ema", "fused_spmm_ema_shared", "prepare_fused"]
