"""Batched F+tree maintenance kernel (paper Alg. 2, TPU-adapted).

Applies K single-parameter updates p_{t_k} += δ_k to the tree in one pass.
Instead of K serial bottom-up walks (Alg. 2), the kernel processes the tree
**level by level**: at level ℓ every update touches exactly one node
(leaf index >> ℓ), so each level is one vectorized scatter-add of the K
deltas — duplicate paths accumulate naturally.  Depth stays O(log T); work
per level is lane-parallel over the update batch.

The whole tree and the update batch live in VMEM (tree ≤ 128 KiB at
T=16384; batch tiles at 1024).  Single grid program with an inner loop over
batch tiles keeps the scatter target resident.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(depth: int, f_ref, t_ref, d_ref, out_ref):
    out_ref[...] = f_ref[...]
    T = f_ref.shape[0] // 2
    leaf = t_ref[...] + T                    # (K,) heap leaf indices
    delta = d_ref[...]                       # (K,)
    for lvl in range(depth + 1):             # leaf .. root, unrolled
        node = leaf >> lvl
        cur = out_ref[...]
        out_ref[...] = cur.at[node].add(delta)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ftree_update_pallas(F: jax.Array, ts: jax.Array, deltas: jax.Array,
                        *, interpret: bool) -> jax.Array:
    two_t = F.shape[0]
    T = two_t // 2
    depth = T.bit_length() - 1
    k = ts.shape[0]
    return pl.pallas_call(
        functools.partial(_kernel, depth),
        in_specs=[
            pl.BlockSpec((two_t,), lambda: (0,)),
            pl.BlockSpec((k,), lambda: (0,)),
            pl.BlockSpec((k,), lambda: (0,)),
        ],
        out_specs=pl.BlockSpec((two_t,), lambda: (0,)),
        out_shape=jax.ShapeDtypeStruct((two_t,), F.dtype),
        interpret=interpret,
        name="ftree_update",
    )(F, ts, deltas)
