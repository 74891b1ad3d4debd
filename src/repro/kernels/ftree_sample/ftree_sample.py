"""Batched F+tree sampling kernel (paper Alg. 1, TPU-adapted).

Layout (DESIGN.md §3): a scalar O(log T) walk wastes the 8×128 VPU, so the
walk is *batched across tokens*: each grid program loads the whole tree
(2T f32 — ≤128 KiB for T=16384, comfortably VMEM-resident) plus one tile of
``N_BLK`` uniforms, and performs the log₂T traversal as unrolled steps of
vectorized gather + select over the full tile.  Depth stays O(log T); every
step is lane-parallel over tokens.

The tree is replicated to every program via a constant index_map; uniforms
and outputs tile the batch axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

N_BLK = 1024  # tokens per grid program (8×128 lanes)


def _kernel(depth: int, f_ref, u_ref, z_ref):
    F = f_ref[...]                       # (2T,) in VMEM
    u = u_ref[...] * F[1]                # scale uniforms by the root
    i = jnp.ones(u.shape, jnp.int32)     # all walks start at the root
    for _ in range(depth):               # unrolled log₂T vector steps
        left = F[2 * i]                  # vectorized VMEM gather
        # zero-mass right subtrees are never entered — same edge guard as
        # ftree.sample_batch (u01→1 can round u up to F[1] in f32, which
        # would otherwise walk onto a zero-probability padded leaf)
        go_right = (u >= left) & (F[2 * i + 1] > 0)
        i = 2 * i + go_right.astype(jnp.int32)
        u = jnp.where(go_right, u - left, u)
    T = F.shape[0] // 2
    z_ref[...] = i - T


@functools.partial(jax.jit, static_argnames=("interpret",))
def ftree_sample_pallas(F: jax.Array, u01: jax.Array,
                        *, interpret: bool) -> jax.Array:
    """z[k] = F.sample(u01[k]); F: (2T,) f32, u01: (N,) f32, N % N_BLK == 0."""
    two_t = F.shape[0]
    T = two_t // 2
    depth = T.bit_length() - 1
    n = u01.shape[0]
    grid = (n // N_BLK,)
    return pl.pallas_call(
        functools.partial(_kernel, depth),
        grid=grid,
        in_specs=[
            pl.BlockSpec((two_t,), lambda b: (0,)),      # tree: replicated
            pl.BlockSpec((N_BLK,), lambda b: (b,)),      # uniforms: tiled
        ],
        out_specs=pl.BlockSpec((N_BLK,), lambda b: (b,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.int32),
        interpret=interpret,
        name="ftree_sample",
    )(F, u01)
