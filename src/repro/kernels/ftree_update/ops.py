"""Public wrapper for the batched F+tree update kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ftree_update.ftree_update import ftree_update_pallas
from repro.kernels.fused_sweep.ops import default_interpret


def ftree_update_batch(F: jax.Array, ts: jax.Array, deltas: jax.Array, *,
                       interpret: bool | None = None) -> jax.Array:
    """F+tree after p[ts[k]] += deltas[k] for all k (duplicates accumulate)."""
    if interpret is None:
        interpret = default_interpret()
    return ftree_update_pallas(
        F.astype(jnp.float32), ts.astype(jnp.int32),
        deltas.astype(jnp.float32), interpret=interpret)
