"""Public wrapper: padding + dtype plumbing for the ftree_sample kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ftree_sample.ftree_sample import N_BLK, ftree_sample_pallas
from repro.kernels.fused_sweep.ops import default_interpret


def ftree_sample(F: jax.Array, u01: jax.Array, *,
                 interpret: bool | None = None) -> jax.Array:
    """Batched F+tree draws; any N (internally padded to the tile size)."""
    n = u01.shape[0]
    n_pad = -n % N_BLK
    u = jnp.pad(u01.astype(jnp.float32), (0, n_pad))
    if interpret is None:
        interpret = default_interpret()
    z = ftree_sample_pallas(F.astype(jnp.float32), u, interpret=interpret)
    return z[:n]
