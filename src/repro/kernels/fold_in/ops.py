"""Public wrapper for the Pallas fold-in kernel: draw precompute, shape
validation, interpret default and VMEM budgeting.

:func:`fold_in_fused` is a drop-in for ``core/heldout.py:fold_in_batch``
(same signature + ``interpret``), bit-identical per document.  The RNG
split is the one piece of the reference that cannot run inside a Pallas
body — ``jax.random`` key ops don't lower to Mosaic — so
:func:`fold_in_draws` precomputes every draw *outside* the kernel by the
identical counter-mode ``doc_fold_key`` chains the reference derives
internally (same ``fold_in``/``randint``/``uniform`` callsites, so the
bits agree), and the kernel replays the chain on plain arrays.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.heldout import _ROLE_INIT, _ROLE_SWEEP
from repro.kernels.fold_in.fold_in import fold_in_pallas
from repro.kernels.fused_sweep.ops import (SMEM_BUDGET_BYTES,
                                           VMEM_SCOPED_DEFAULT_BYTES,
                                           default_interpret,
                                           topic_row_bytes)


def fold_in_vmem_bytes(L: int, T: int, sweeps: int) -> int:
    """VMEM bytes of one compiled fold-in kernel program (DESIGN.md §10a):
    the i32 count output tile (double-buffered), the count scratch and
    the f32 φ-row scratch — four topic rows.  φ stays in HBM — only one
    row is ever resident — and the token streams live in SMEM
    (:func:`fold_in_smem_bytes`), so neither ``L`` nor ``sweeps``
    enters."""
    del L, sweeps
    return 4 * topic_row_bytes(T)


def fold_in_smem_bytes(L: int) -> int:
    """SMEM bytes of one compiled fold-in kernel program: the words, mask
    and ``z0`` rows plus one sweep's uniforms, each double-buffered, and
    the ``(L,)`` assignment scratch."""
    return 4 * (2 * 4 * L + L)


def fold_in_draws(doc_keys, L: int, T: int, sweeps: int):
    """Precompute the kernel's draws: ``(z0, u)`` of shapes ``(D, L)``
    i32 and ``(D, sweeps, L)`` f32.

    Bit-identical to the draws ``fold_in_batch`` derives internally:
    position ``p``'s init assignment comes from
    ``fold_in(fold_in(dk, _ROLE_INIT), p)`` and sweep ``k``'s uniform
    from ``fold_in(fold_in(fold_in(dk, _ROLE_SWEEP), k), p)`` — pure
    functions of the key bits, so hoisting them out of the sweep loop
    changes nothing.
    """
    pos = jnp.arange(L, dtype=jnp.int32)

    def per_doc(dk):
        ik = jax.random.fold_in(dk, _ROLE_INIT)
        tk = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(ik, pos)
        z0 = jax.vmap(
            lambda kk: jax.random.randint(kk, (), 0, T,
                                          dtype=jnp.int32))(tk)
        sk = jax.random.fold_in(dk, _ROLE_SWEEP)

        def sweep_u(k):
            ks = jax.random.fold_in(sk, k)
            uk = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(ks, pos)
            return jax.vmap(jax.random.uniform)(uk)

        u = jax.vmap(sweep_u)(jnp.arange(sweeps, dtype=jnp.int32))
        return z0, u

    return jax.vmap(per_doc)(doc_keys)


def fold_in_fused(word_ids, valid, phi, alpha, doc_keys,
                  sweeps: int = 20, *, interpret: bool | None = None):
    """Pallas twin of ``fold_in_batch``: (D, L) padded batch → (D, T)
    i32 fold-in counts, bit-identical per document.

    ``interpret=None`` → :func:`default_interpret` (compiled on TPU,
    interpreted elsewhere); the compiled path is guarded by the §7 VMEM
    budget — oversized ``(L, sweeps)`` must fall back to
    ``inner_mode="scan"`` rather than fail in Mosaic.  Fully jittable
    (validation is shape-only; ``alpha`` may be traced).
    """
    if word_ids.ndim != 2 or word_ids.shape != valid.shape:
        raise ValueError(
            f"word_ids/valid must be matching (D, L) arrays; got "
            f"{word_ids.shape} and {valid.shape}")
    if doc_keys.shape[0] != word_ids.shape[0]:
        raise ValueError(
            f"doc_keys carries {doc_keys.shape[0]} keys for "
            f"{word_ids.shape[0]} rows")
    if sweeps < 1:
        raise ValueError(
            f"fold_in_fused needs sweeps >= 1, got {sweeps} (sweeps=0 is "
            f"the init counts — use fold_in_batch)")
    D, L = word_ids.shape
    T = phi.shape[1]
    if interpret is None:
        interpret = default_interpret()
    if not interpret:
        vmem = fold_in_vmem_bytes(L, T, int(sweeps))
        smem = fold_in_smem_bytes(L)
        if vmem > VMEM_SCOPED_DEFAULT_BYTES or smem > SMEM_BUDGET_BYTES:
            raise ValueError(
                f"fold-in kernel state ({vmem / 2**20:.1f} MiB VMEM, "
                f"{smem / 2**10:.0f} KiB SMEM) exceeds the VMEM/SMEM "
                f"budget; lower the length bucket L={L} or use "
                f"inner_mode='scan'")
    z0, u = fold_in_draws(doc_keys, L, T, int(sweeps))
    return fold_in_pallas(
        word_ids.astype(jnp.int32), valid.astype(jnp.int32), z0, u,
        jnp.asarray(alpha, jnp.float32).reshape(1, 1),
        phi.astype(jnp.float32), sweeps=int(sweeps),
        interpret=bool(interpret))
