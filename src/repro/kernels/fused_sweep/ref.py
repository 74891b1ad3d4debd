"""Pure-jnp oracle for the fused F+LDA sweep kernel.

A ``lax.scan`` over the token stream with exactly the kernel's masked
semantics (and exactly ``cgs.sweep_fplda_word``'s float-op order), used to
pin the Pallas kernel down bit-for-bit in tests and benchmarks.

The r-bucket draw runs over the capacity-``r_cap`` compacted topic vector
(:mod:`repro.kernels.fused_sweep.rbucket`): ``r_mode="dense"`` recomputes
the compaction from the dense ``n_td`` row per token, ``r_mode="sparse"``
maintains it as per-doc ``(topics, counts)`` side tables threaded through
the scan — bit-identical chains by construction (see the rbucket module
docstring for the exactness argument).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import ftree
from repro.kernels.fused_sweep import rbucket

F32 = jnp.float32


def fused_sweep_ref(tok_doc, tok_wrd, tok_valid, tok_bound, z, u,
                    n_td, n_wt, n_t, *, alpha, beta, beta_bar, F0=None,
                    r_mode="dense", r_cap=None, topics=None, counts=None):
    """Reference sweep; same signature/returns as ``fused_sweep_pallas``.

    ``F0`` is the incoming F+tree (zeros by default — the single-call
    convention); the cell-batch oracle threads it across cells to mirror
    the kernel's carried tree.

    ``r_cap`` is the compacted r-vector capacity (default ``T`` — note it
    is chain-affecting, see :mod:`rbucket`).  ``r_mode="sparse"`` threads
    per-doc ``(topics, counts)`` side tables (built from ``n_td`` when not
    given) and returns them appended: a 7-tuple instead of the dense
    5-tuple.
    """
    T = n_t.shape[-1]
    cap = T if r_cap is None else int(r_cap)
    sparse = r_mode == "sparse"
    if r_mode not in ("dense", "sparse"):
        raise ValueError(f"r_mode must be 'dense' or 'sparse', got {r_mode}")
    if sparse and topics is None:
        topics, counts = rbucket.build_side_table(n_td, cap)

    def q_of(nwt_row, nt):
        return (nwt_row.astype(F32) + beta) / (nt.astype(F32) + beta_bar)

    def step(carry, inp):
        if sparse:
            z, n_td, n_wt, n_t, F, tpc_tab, cnt_tab = carry
        else:
            z, n_td, n_wt, n_t, F = carry
        k, u01 = inp
        d, w = tok_doc[k], tok_wrd[k]
        valid, boundary = tok_valid[k] != 0, tok_bound[k] != 0
        t_old = z[k]
        one = valid.astype(jnp.int32)

        F = lax.cond(boundary, lambda: ftree.build(q_of(n_wt[w], n_t)),
                     lambda: F)

        n_td = n_td.at[d, t_old].add(-one)
        n_wt = n_wt.at[w, t_old].add(-one)
        n_t = n_t.at[t_old].add(-one)
        new_leaf = ((n_wt[w, t_old].astype(F32) + beta)
                    / (n_t[t_old].astype(F32) + beta_bar))
        F = ftree.set_leaf(F, t_old,
                           jnp.where(valid, new_leaf, F[T + t_old]))

        q = ftree.leaves(F)
        if sparse:
            tpc, cnt = rbucket.decrement(tpc_tab[d], cnt_tab[d],
                                         t_old, valid)
        else:
            tpc, cnt = rbucket.compact_row(n_td[d], cap)
        c = rbucket.r_cumsum(tpc, cnt, q)
        r_mass = c[-1]
        q_total = ftree.total(F)
        norm = alpha * q_total + r_mass
        u_val = u01 * norm
        in_r = u_val < r_mass
        t_r = rbucket.pick(tpc, cnt, c, u_val)
        t_q = ftree.sample(F, jnp.clip((u_val - r_mass)
                                       / jnp.maximum(alpha * q_total, 1e-30),
                                       0.0, 1.0 - 1e-7))
        t_new = jnp.where(valid, jnp.where(in_r, t_r, t_q), t_old)

        n_td = n_td.at[d, t_new].add(one)
        n_wt = n_wt.at[w, t_new].add(one)
        n_t = n_t.at[t_new].add(one)
        new_leaf2 = ((n_wt[w, t_new].astype(F32) + beta)
                     / (n_t[t_new].astype(F32) + beta_bar))
        F = ftree.set_leaf(F, t_new,
                           jnp.where(valid, new_leaf2, F[T + t_new]))
        z = z.at[k].set(t_new)
        if sparse:
            tpc, cnt = rbucket.increment(tpc, cnt, t_new, valid)
            tpc_tab = tpc_tab.at[d].set(tpc)
            cnt_tab = cnt_tab.at[d].set(cnt)
            return (z, n_td, n_wt, n_t, F, tpc_tab, cnt_tab), None
        return (z, n_td, n_wt, n_t, F), None

    n = tok_doc.shape[0]
    if F0 is None:
        F0 = jnp.zeros((2 * T,), F32)
    carry0 = (z, n_td, n_wt, n_t, F0)
    if sparse:
        carry0 += (topics, counts)
    carry, _ = lax.scan(step, carry0,
                        (jnp.arange(n, dtype=jnp.int32), u))
    return carry if sparse else carry[:5]


def fused_sweep_cells_ref(tok_doc, tok_wrd, tok_valid, tok_bound, z, u,
                          n_td, n_wt, n_t, *, alpha, beta, beta_bar,
                          cell_start=0, num_cells=None,
                          r_mode="dense", r_cap=None,
                          topics=None, counts=None):
    """Oracle for the cell-batch kernel: the k cells swept one after another
    with ``n_td``/``n_t``/``F`` carried through — same signature/returns as
    ``fused_sweep_cells_pallas`` (tok_* (k, L); n_wt (k, J, T)).

    ``cell_start``/``num_cells`` mirror ``ops.fused_sweep_cells``'s
    sub-queue restriction: only cells ``[cell_start, cell_start+num_cells)``
    are swept and returned.  ``r_mode="sparse"`` threads the doc-side
    tables across cells and appends them to the return."""
    k_total = tok_doc.shape[0]
    sparse = r_mode == "sparse"
    if num_cells is None:
        num_cells = k_total - cell_start
    T = n_t.shape[-1]
    cap = T if r_cap is None else int(r_cap)
    if sparse and topics is None:
        topics, counts = rbucket.build_side_table(n_td, cap)
    z_rows, nwt_rows = [], []
    F = jnp.zeros((2 * T,), F32)
    for c in range(cell_start, cell_start + num_cells):
        out = fused_sweep_ref(
            tok_doc[c], tok_wrd[c], tok_valid[c], tok_bound[c], z[c], u[c],
            n_td, n_wt[c], n_t, alpha=alpha, beta=beta, beta_bar=beta_bar,
            F0=F, r_mode=r_mode, r_cap=cap, topics=topics, counts=counts)
        if sparse:
            z_c, n_td, nwt_c, n_t, F, topics, counts = out
        else:
            z_c, n_td, nwt_c, n_t, F = out
        z_rows.append(z_c)
        nwt_rows.append(nwt_c)
    if not z_rows:
        out = (z[:0], n_td, n_wt[:0], n_t, F)
        return out + ((topics, counts) if sparse else ())
    out = (jnp.stack(z_rows), n_td, jnp.stack(nwt_rows), n_t, F)
    return out + ((topics, counts) if sparse else ())


def fused_sweep_ragged_ref(tok_doc, tok_wrd, tok_valid, tok_bound, z, u,
                           cell_of_tile, n_td, n_wt, n_t, *,
                           alpha, beta, beta_bar, n_blk,
                           tile_start=0, num_tiles=None,
                           cell_start=0, num_cells=None,
                           r_mode="dense", r_cap=None,
                           topics=None, counts=None):
    """Oracle for the ragged-stream kernel — same signature/returns as
    ``ops.fused_sweep_ragged`` (tok_* (S,); cell_of_tile (S//n_blk,);
    n_wt (k, J, T)).

    The paged per-cell blocks are emulated by flattening the queue to one
    ``(k·J, T)`` table and addressing rows at ``cell·J + tok_wrd`` — the
    same rows, touched by the same float ops in the same order, so the
    kernel is pinned bit-for-bit."""
    k_total, J, T = n_wt.shape
    sparse = r_mode == "sparse"
    cap = T if r_cap is None else int(r_cap)
    if sparse and topics is None:
        topics, counts = rbucket.build_side_table(n_td, cap)
    r_total = cell_of_tile.shape[0]
    nt_ = r_total - tile_start if num_tiles is None else int(num_tiles)
    nc = k_total - cell_start if num_cells is None else int(num_cells)
    lo, hi = tile_start * n_blk, (tile_start + nt_) * n_blk
    sub = lambda a: a[lo:hi]
    cot = cell_of_tile[tile_start:tile_start + nt_] - cell_start
    nwt_sub = n_wt[cell_start:cell_start + nc]
    if nt_ == 0 or nc == 0:
        out = (z[:0], n_td, nwt_sub[:0], n_t, jnp.zeros((2 * T,), F32))
        return out + ((topics, counts) if sparse else ())
    cell_tok = jnp.broadcast_to(cot[:, None], (nt_, n_blk)).reshape(-1)
    wrd_flat = cell_tok * J + sub(tok_wrd)
    out = fused_sweep_ref(
        sub(tok_doc), wrd_flat, sub(tok_valid), sub(tok_bound),
        sub(z), sub(u), n_td, nwt_sub.reshape(nc * J, T), n_t,
        alpha=alpha, beta=beta, beta_bar=beta_bar,
        r_mode=r_mode, r_cap=cap, topics=topics, counts=counts)
    if sparse:
        z_s, n_td, nwt_flat, n_t, F, topics, counts = out
        return (z_s, n_td, nwt_flat.reshape(nc, J, T), n_t, F,
                topics, counts)
    z_s, n_td, nwt_flat, n_t, F = out
    return z_s, n_td, nwt_flat.reshape(nc, J, T), n_t, F
