"""Pure-jnp oracle for the Pallas fold-in kernel.

Consumes the same precomputed ``(z0, u)`` draw arrays as the kernel
(``ops.fold_in_draws``) and replays the identical per-token chain as a
vmapped ``lax.scan`` — the bridge that factors the tentpole equality
into two independently testable halves:

* ``fold_in_kernel_ref == fold_in_pallas`` — the kernel replays the
  chain faithfully (tests sweep shapes/padding);
* ``fold_in_kernel_ref == core/heldout.py:fold_in_batch`` — the draw
  precompute is bit-identical to the reference's internal derivation
  (same counter-mode ``fold_in`` chains, reorganized).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.prefix import prefix_sum
from repro.core.samplers import lsearch_guarded


def fold_in_kernel_ref(word_ids, valid, z0, u, alpha, phi):
    """Reference fold-in on precomputed draws.

    ``word_ids``/``valid``/``z0``: (D, L); ``u``: (D, sweeps, L) f32;
    returns (D, T) i32 counts — same contract as ``fold_in_pallas``
    (which takes the same ``(D, sweeps, L)`` uniforms).
    """
    T = phi.shape[1]
    L = word_ids.shape[1]
    pos = jnp.arange(L, dtype=jnp.int32)

    def one_doc(words, mask, z_init, u_doc):
        v = mask.astype(jnp.int32)
        n_td = jnp.zeros((T,), jnp.int32).at[z_init].add(v)

        def sweep(carry, u_row):
            z, n_td = carry

            def step(c, inp):
                z, n_td = c
                i, u01, vi = inp
                w, t_old = words[i], z[i]
                n_td = n_td.at[t_old].add(-vi)
                p = (n_td.astype(jnp.float32) + alpha) * phi[w]
                cdf = prefix_sum(p)
                t_new = lsearch_guarded(cdf, u01 * cdf[-1])
                t_new = jnp.where(vi > 0, t_new, t_old)
                n_td = n_td.at[t_new].add(vi)
                z = z.at[i].set(t_new)
                return (z, n_td), None

            (z, n_td), _ = lax.scan(step, (z, n_td), (pos, u_row, v))
            return (z, n_td), None

        (_, n_td), _ = lax.scan(sweep, (z_init, n_td), u_doc)
        return n_td

    return jax.vmap(one_doc)(word_ids, valid.astype(jnp.int32), z0, u)
