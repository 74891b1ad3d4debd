"""Doc-sparse r-bucket: compacted (topics, counts) side tables (paper §3).

The F+LDA conditional p = α·q + r has r_t = n_td·q_t supported on the
document's |T_d| nonzero topics with |T_d| ≪ T (the paper's complexity
argument for Alg. 3).  This module defines the **canonical r-draw** shared
by every fused-sweep implementation (the Pallas kernels and the scan
oracle): the r-term prefix sum runs over a fixed-capacity compacted vector —
the document's active topics in ascending order, zero-padded to a static
capacity ``cap`` — instead of a dense ``(T,)`` vector.

Two ways to obtain the compacted vector, selected by ``r_mode``:

* ``"dense"``  — recompute it from the dense ``n_td`` row at every token
  (:func:`compact_row`): Θ(T) per token, no extra state.
* ``"sparse"`` — maintain it incrementally as a per-doc ``(topics, counts)``
  side table (:func:`decrement` / :func:`increment`): Θ(cap) per token, so
  the r-draw cost stops scaling with T.

Exactness argument: both modes operate on the *same* compacted vector —
the side table's invariant is ``(topics, counts) == compact_row(n_td[d])``
at every step, preserved by the integer-only increment/decrement — so the
float ops of the draw (:func:`repro.core.prefix.prefix_sum` over
``counts·q[topics]``) are performed on bit-identical inputs and the two
chains are bit-equal by construction.  Note the compacted prefix sum is
**not** bit-equal to a dense ``(T,)`` one (the log-step scan associates
by position, so dropping zeros reorders the partial sums); that is why
*both* modes draw from the compacted vector.  A compiled kernel builds
that vector with :func:`pack`, which moves values and never adds them.
For the same reason the capacity is chain-affecting: runs compared
bit-for-bit must share ``cap`` (the default ``cap = T`` everywhere keeps
cross-mode comparisons trivially paired).

Zero padding is exact: pad slots are ``(topic 0, count 0)`` and contribute
``0·q[0] = 0.0`` to the prefix sum, and a scan position only adds
entries at or below it, so the padded suffix never perturbs a partial sum
of the active prefix.

Capacity bound: ``cap = min(T, max_d len(d))`` suffices — a document of
``n`` tokens holds at most ``n`` distinct topics, and at increment time the
document holds ``n − 1`` assigned tokens, so either the incoming topic is
already present or the table has a free slot (``NomadLayout.r_cap``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.prefix import (flat_roll, prefix_sum, prefix_sum_tiles,
                               topic_iota)

F32 = jnp.float32

__all__ = ["compact_row", "build_side_table", "decrement", "increment",
           "r_cumsum", "pick", "pack"]


def compact_row(row, cap: int):
    """Compact a dense ``(T,)`` count row into capacity-``cap`` parallel
    ``(topics, counts)`` int32 vectors: active topics ascending, padded
    with ``(0, 0)`` slots.  Active topics beyond ``cap`` are dropped
    (never happens under the layout's capacity bound)."""
    T = row.shape[-1]
    row = row.astype(jnp.int32)
    active = row > 0
    rank = jnp.cumsum(active.astype(jnp.int32)) - 1
    pos = jnp.where(active, rank, cap)                   # inactive → dropped
    topics = jnp.zeros((cap,), jnp.int32).at[pos].set(
        jnp.arange(T, dtype=jnp.int32), mode="drop")
    counts = jnp.zeros((cap,), jnp.int32).at[pos].set(row, mode="drop")
    return topics, counts


def build_side_table(n_td, cap: int):
    """Per-doc side tables for a whole ``(I, T)`` doc-topic table:
    returns ``(topics, counts)``, each ``(I, cap)`` int32."""
    return jax.vmap(functools.partial(compact_row, cap=cap))(n_td)


def decrement(topics, counts, t, valid):
    """Remove one occurrence of topic ``t`` from the table (no-op when
    ``valid`` is False).  ``t`` must be present with count ≥ 1 for a valid
    token (it is the token's current assignment); a count reaching zero
    shifts the tail left so active entries stay packed and ascending."""
    cap = topics.shape[0]
    j = jnp.arange(cap, dtype=jnp.int32)
    pos = jnp.sum(((topics < t) & (counts > 0)).astype(jnp.int32))
    newc = counts[pos] - 1
    remove = newc == 0
    t_next = jnp.concatenate([topics[1:], jnp.zeros((1,), jnp.int32)])
    c_next = jnp.concatenate([counts[1:], jnp.zeros((1,), jnp.int32)])
    topics2 = jnp.where(remove & (j >= pos), t_next, topics)
    counts2 = jnp.where(remove,
                        jnp.where(j >= pos, c_next, counts),
                        jnp.where(j == pos, newc, counts))
    return (jnp.where(valid, topics2, topics),
            jnp.where(valid, counts2, counts))


def increment(topics, counts, t, valid):
    """Add one occurrence of topic ``t`` (no-op when ``valid`` is False):
    bump the count if present, else shift the tail right and insert
    ``(t, 1)`` at its ascending position (a free slot exists under the
    capacity bound — see module docstring)."""
    cap = topics.shape[0]
    j = jnp.arange(cap, dtype=jnp.int32)
    pos = jnp.sum(((topics < t) & (counts > 0)).astype(jnp.int32))
    present = (counts[pos] > 0) & (topics[pos] == t)
    t_prev = jnp.concatenate([jnp.zeros((1,), jnp.int32), topics[:-1]])
    c_prev = jnp.concatenate([jnp.zeros((1,), jnp.int32), counts[:-1]])
    ins_t = jnp.where(j > pos, t_prev, jnp.where(j == pos, t, topics))
    ins_c = jnp.where(j > pos, c_prev, jnp.where(j == pos, 1, counts))
    topics2 = jnp.where(present, topics, ins_t)
    counts2 = jnp.where(present,
                        jnp.where(j == pos, counts + 1, counts), ins_c)
    return (jnp.where(valid, topics2, topics),
            jnp.where(valid, counts2, counts))


def r_cumsum(topics, counts, q):
    """Cumulative r-bucket masses over the compacted vector:
    ``prefix_sum(counts · q[topics])`` (pad slots contribute exactly
    0.0)."""
    return prefix_sum(counts.astype(F32) * q[topics])


def pack(vals, active, *, roll=None):
    """Stable compaction of ``(R, C)`` topic tiles (row-major order)
    without a scatter: the ``active`` entries of ``vals`` move to the
    front in order, zeros behind.  Returns ``(packed, rank)`` with
    ``rank`` each entry's count of active entries before it (its packed
    slot when active).

    A log-step network: every active entry must move down by ``s`` = the
    inactive entries before it, and round ``b`` moves the entries whose
    ``s`` has bit ``b`` set by ``2^b``.  ``s`` never decreases along the
    order and exceeds no entry's own index, so no two entries ever meet
    and none wraps.  Values are only selected, never added, so
    ``packed`` is bit-identical to ``compact_row``'s gather for any
    float.  ``roll`` is the axis roll (``prefix.tpu_roll`` inside a
    Pallas body; ``jnp.roll`` by default)."""
    kw = {} if roll is None else {"roll": roll}
    R, C = vals.shape[-2:]
    n = R * C
    flat = topic_iota(vals.shape)
    act = active.astype(jnp.int32)
    rank = prefix_sum_tiles(act, **kw) - act
    move = jnp.where(active, flat - rank, -1)      # -1: empty slot
    v = jnp.where(active, vals, jnp.zeros_like(vals))
    s = 1
    while s < n:
        leaving = (move >= 0) & ((move & s) != 0)
        move_in = flat_roll(move, n - s, **kw)     # from slot + s
        v_in = flat_roll(v, n - s, **kw)
        arriving = (move_in >= 0) & ((move_in & s) != 0)
        v = jnp.where(arriving, v_in,
                      jnp.where(leaving, jnp.zeros_like(v), v))
        move = jnp.where(arriving, move_in, jnp.where(leaving, -1, move))
        s *= 2
    return v, rank


def pick(topics, counts, c, u_val):
    """Zero-mass-aware LSearch on the compacted prefix sum: the drawn slot
    is ``#{c ≤ u_val}``, guarded to the last active slot so a
    boundary-rounded ``u_val`` can never land on a zero-count pad (pad
    entries hold the active total, re-associated, so they may sit an ulp
    off the last active entry)."""
    m = jnp.sum((counts > 0).astype(jnp.int32))
    j_r = jnp.minimum(jnp.sum((c <= u_val).astype(jnp.int32)),
                      jnp.maximum(m - 1, 0))
    return topics[j_r]
