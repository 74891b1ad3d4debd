"""Public wrapper for the fused CGS conditional + draw kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.fused_sweep.ops import default_interpret
from repro.kernels.lda_scores.lda_scores import N_BLK, lda_scores_pallas


def lda_scores_draw(n_td_rows: jax.Array, n_wt_rows: jax.Array,
                    n_t: jax.Array, u01: jax.Array, *,
                    alpha: float, beta: float, beta_bar: float,
                    interpret: bool | None = None):
    """(z, norm) for a batch of tokens; batch padded to the tile size."""
    n = n_td_rows.shape[0]
    n_pad = -n % N_BLK
    if n_pad:
        n_td_rows = jnp.pad(n_td_rows, ((0, n_pad), (0, 0)))
        n_wt_rows = jnp.pad(n_wt_rows, ((0, n_pad), (0, 0)))
        u01 = jnp.pad(u01, (0, n_pad))
    if interpret is None:
        interpret = default_interpret()
    z, norm = lda_scores_pallas(
        n_td_rows, n_wt_rows, n_t, u01.astype(jnp.float32),
        alpha=float(alpha), beta=float(beta), beta_bar=float(beta_bar),
        interpret=interpret)
    return z[:n], norm[:n]
