"""Pallas fold-in kernel: the φ-frozen per-document sweep, VMEM-resident.

The serving hot path (DESIGN.md §10) answers a θ query by Gibbs fold-in
against a frozen φ snapshot — ``core/heldout.py:fold_in_batch`` runs it
as a vmapped ``lax.scan``.  This kernel is its Pallas twin on a
``(D, sweeps)`` grid: one document per row of programs, one sweep per
program.  The document's tokens, its per-sweep uniforms and its topic
assignments ``z`` are read and written one scalar at a time, so they sit
in SMEM; the ``(R, C)`` topic-count tile lives in VMEM scratch for the
whole multi-sweep chain; φ stays in ANY/HBM as ``(J, R, C)`` row tiles
(:func:`repro.core.prefix.topic_tile` — one row per leading index, so a
row DMA needs no sublane alignment), and the current token's row is
gathered by explicit DMA (``pltpu.make_async_copy``) into an ``(R, C)``
VMEM scratch — the §7 doc-slab machinery specialized to one row.

**Bit-exactness contract:** all randomness is precomputed outside the
kernel (``ops.fold_in_draws``) by the identical counter-mode
``doc_fold_key`` chains ``fold_in_batch`` derives internally — the
kernel consumes ``z0`` (initial assignments) and ``u`` (per-sweep
LSearch uniforms) as plain arrays and replays the exact per-token op
order of the reference: decrement, ``(n_td+α)·φ[w]``,
:func:`repro.core.prefix.prefix_sum`, guarded LSearch, masked
re-assign, increment.  Counts change by iota-masked selects and scalars
leave a row by masked sums (exact: one nonzero term).  Padded positions
are inert by construction (their draws are consumed and discarded, their
count updates are ±0), so a kernel row is bit-identical to the serial
``fold_in`` on that document alone.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.prefix import (prefix_sum_tiles, topic_iota, topic_tile,
                               tpu_roll)

F32 = jnp.float32


def _row_copy(phi_ref, w, row, sem):
    """DMA φ row ``w`` (ANY/HBM ``(J, R, C)``) into the ``(R, C)`` VMEM
    scratch."""
    cp = pltpu.make_async_copy(phi_ref.at[w], row, sem)
    cp.start()
    cp.wait()


def _kernel(T: int, L: int, *refs):
    (w_ref, v_ref, z0_ref, u_ref, alpha_ref, phi_ref,
     ntd_out, z, n_td, phi_row, sem) = refs
    k = pl.program_id(1)
    tile = topic_tile(T)
    topic = topic_iota(tile)

    # First sweep: z ← z0 and n_td[z0[p]] += v[p].  Integer adds are
    # order-independent, so this matches the reference's vector
    # `.at[z].add(v)` bit-for-bit.
    @pl.when(k == 0)
    def _init():
        def init_count(p, ntd):
            t = z0_ref[0, 0, p]
            z[p] = t
            return ntd + jnp.where(topic == t, v_ref[0, 0, p], 0)

        n_td[...] = jax.lax.fori_loop(0, L, init_count,
                                      jnp.zeros(tile, jnp.int32))

    alpha = alpha_ref[0, 0]

    def tok_step(p, ntd):
        w, vi, t_old = w_ref[0, 0, p], v_ref[0, 0, p], z[p]
        ntd = ntd - jnp.where(topic == t_old, vi, 0)
        _row_copy(phi_ref, w, phi_row, sem)
        cdf = prefix_sum_tiles((ntd.astype(F32) + alpha) * phi_row[...],
                               roll=tpu_roll)
        total = jnp.sum(jnp.where(topic == T - 1, cdf, 0.0))   # cdf[-1]
        u_val = u_ref[0, 0, p] * total
        # lsearch_guarded(cdf, u_val)
        last = jnp.sum((cdf < total).astype(jnp.int32))
        t_new = jnp.minimum(jnp.sum((cdf <= u_val).astype(jnp.int32)), last)
        t_new = jnp.where(vi > 0, t_new, t_old)
        z[p] = t_new
        return ntd + jnp.where(topic == t_new, vi, 0)

    n_td[...] = jax.lax.fori_loop(0, L, tok_step, n_td[...])

    @pl.when(k == pl.num_programs(1) - 1)
    def _emit():
        ntd_out[0] = n_td[...]


@functools.partial(jax.jit, static_argnames=("sweeps", "interpret"))
def fold_in_pallas(word_ids: jax.Array, valid: jax.Array, z0: jax.Array,
                   u: jax.Array, alpha: jax.Array, phi: jax.Array, *,
                   sweeps: int, interpret: bool) -> jax.Array:
    """One fused multi-sweep fold-in over a padded doc batch.

    Shapes: ``word_ids``/``valid``/``z0`` are ``(D, L)`` i32;
    ``u`` is ``(D, sweeps, L)`` f32 (the ``ops.fold_in_draws`` output);
    ``alpha`` a ``(1, 1)`` f32; ``phi`` ``(J, T)`` f32, HBM-resident.
    Returns ``(D, T)`` i32 fold-in counts, row-for-row bit-identical to
    ``fold_in_batch``.
    """
    D, L = word_ids.shape
    J, T = phi.shape
    tile = topic_tile(T)
    smem = lambda idx: pl.BlockSpec((1, 1, L), idx, memory_space=pltpu.SMEM)
    doc = lambda d, k: (d, 0, 0)
    row3 = lambda a: a.reshape(D, 1, L)
    out = pl.pallas_call(
        functools.partial(_kernel, T, L),
        grid=(D, int(sweeps)),
        in_specs=[
            smem(doc), smem(doc), smem(doc),                 # words/valid/z0
            smem(lambda d, k: (d * sweeps + k, 0, 0)),       # uniforms
            pl.BlockSpec(memory_space=pltpu.SMEM),           # alpha
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),  # φ (HBM)
        ],
        out_specs=pl.BlockSpec((1, *tile), lambda d, k: (d, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((D, *tile), jnp.int32),
        scratch_shapes=[pltpu.SMEM((L,), jnp.int32),       # z
                        pltpu.VMEM(tile, jnp.int32),       # n_td
                        pltpu.VMEM(tile, F32),             # φ row
                        pltpu.SemaphoreType.DMA],
        interpret=interpret,
        name="fold_in",
    )(row3(word_ids), row3(valid), row3(z0),
      u.reshape(D * sweeps, 1, L), alpha, phi.reshape(J, *tile))
    return out.reshape(D, T)
