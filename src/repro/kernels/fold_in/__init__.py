"""Pallas fold-in kernel for the serving hot path (DESIGN.md §10a).

Same package shape as ``kernels/fused_sweep``:
    fold_in.py — pl.pallas_call kernel (doc-axis grid, φ rows by DMA)
    ops.py     — public wrapper (draw precompute, interpret/VMEM guard)
    ref.py     — pure-jnp oracle on the same precomputed draws
"""
from repro.kernels.fold_in.ops import (fold_in_draws,  # noqa: F401
                                       fold_in_fused, fold_in_smem_bytes,
                                       fold_in_vmem_bytes)
from repro.kernels.fold_in.ref import fold_in_kernel_ref  # noqa: F401
