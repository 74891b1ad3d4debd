"""Fused CGS conditional + inverse-CDF draw kernel.

For a tile of tokens, computes the paper's conditional (2)

    p_t = (n_td + α)(n_tw + β)/(n_t + β̄)

from gathered count rows, cumulative-sums along T, and draws the new topic —
all in one VMEM-resident pass.  This is the dense-vectorized TPU alternative
(DESIGN.md §3) the F+tree path is compared against in the roofline analysis:
arithmetic intensity is low (3 reads of T + O(T) flops per token), so the
kernel's job is purely to avoid materializing (N, T) intermediates in HBM.

Tiling: tokens tile the grid at ``N_BLK`` rows; each program holds
(N_BLK, T) count rows + the shared (T,) global counts in VMEM.
T is expected MXU/VPU-aligned (multiple of 128; T=1024 in the paper's runs).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

N_BLK = 256


def _kernel(alpha: float, beta: float, beta_bar: float,
            ntd_ref, nwt_ref, nt_ref, u_ref, z_ref, norm_ref):
    ntd = ntd_ref[...].astype(jnp.float32)        # (N_BLK, T)
    nwt = nwt_ref[...].astype(jnp.float32)        # (N_BLK, T)
    nt = nt_ref[...].astype(jnp.float32)          # (T,)
    p = (ntd + alpha) * (nwt + beta) / (nt[None, :] + beta_bar)
    c = jnp.cumsum(p, axis=-1)                    # (N_BLK, T)
    norm = c[:, -1]
    u = u_ref[...] * norm
    z_ref[...] = jnp.sum(c <= u[:, None], axis=-1).astype(jnp.int32)
    norm_ref[...] = norm


@functools.partial(jax.jit, static_argnames=("alpha", "beta", "beta_bar",
                                             "interpret"))
def lda_scores_pallas(n_td_rows: jax.Array, n_wt_rows: jax.Array,
                      n_t: jax.Array, u01: jax.Array, *,
                      alpha: float, beta: float, beta_bar: float,
                      interpret: bool):
    n, T = n_td_rows.shape
    grid = (n // N_BLK,)
    return pl.pallas_call(
        functools.partial(_kernel, alpha, beta, beta_bar),
        grid=grid,
        in_specs=[
            pl.BlockSpec((N_BLK, T), lambda b: (b, 0)),
            pl.BlockSpec((N_BLK, T), lambda b: (b, 0)),
            pl.BlockSpec((T,), lambda b: (0,)),
            pl.BlockSpec((N_BLK,), lambda b: (b,)),
        ],
        out_specs=[
            pl.BlockSpec((N_BLK,), lambda b: (b,)),
            pl.BlockSpec((N_BLK,), lambda b: (b,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.float32),
        ],
        interpret=interpret,
        name="lda_scores",
    )(n_td_rows, n_wt_rows, n_t, u01)
