"""Nomad-distributed F+LDA on a JAX device mesh (paper §4).

The paper's nomadic framework, mapped to SPMD TPU semantics (DESIGN.md §3):

* **Word tokens** τ_j: the word-topic count blocks ``n_wt[b]`` are the
  nomadic payloads.  ``W`` workers form a flat ring over the whole mesh and
  each owns a **queue of k = B/W blocks** (paper §4: circulate more blocks
  than workers).  The queue hops one ring position per round via
  ``lax.ppermute``: in round ``r`` (of ``W`` per sweep) worker ``w`` holds
  chunk ``c = (w + r) % W`` — global blocks ``c·k .. c·k+k−1`` — and sweeps
  all ``k`` of those cells (all occurrences of the queue's words in its
  document shard) before passing the queue on.  Chunks are disjoint, so the
  word counts stay **always exact and conflict-free** — the paper's key
  invariant — for any ``B`` that is a multiple of ``W``.  Raising ``B``
  shrinks each block's vocabulary slice (the fused kernel's VMEM page) at
  no round-balance cost: the hierarchical LPT in ``data/sharding.py``
  keeps ``NomadLayout.round_imbalance`` equal to the ``B = W`` packing
  (DESIGN.md §4).

  Two rotation schedules (``ring_mode``): ``"barrier"`` sweeps the whole
  queue then hops it in one ``ppermute``; ``"pipelined"`` forwards the
  first ``half_queue_split(k)`` blocks as soon as their cells finish, so
  that hop can overlap the second half's sweep — the paper's
  communication-hides-behind-sampling property on a lock-step mesh.  Cell
  order and s-token fold point are unchanged, so both schedules run the
  **bit-identical** per-token chain (asserted across the whole
  sync × inner × B matrix by ``launch/lda_matrix_check.py``).

* **The s token** τ_s: the only globally shared state is ``s = n_t`` (size
  T).  Three synchronization modes:

    - ``"stoken"``   (paper-faithful): one authoritative ``s`` vector rides
      the same ring; each worker keeps a working copy ``s_l`` and folds its
      accumulated delta in when the token passes (Alg. 4: s += s_l − s̄).
      Staleness ≤ W−1 ring rounds (k cells each), exactly the paper's bound.
    - ``"stale"``    (AD-LDA-like): no intra-sweep sync; deltas psum at
      sweep end.  Staleness = 1 sweep.
    - ``"allreduce"``(beyond-paper): psum the cumulative deltas every round.
      Staleness ≤ 1 round; costs one (T,) all-reduce per round — cheap on
      ICI, impossible on the paper's commodity cluster.

  Every mode finishes the sweep with an **exact** ``n_t`` (additivity of
  s — the paper's observation), so count invariants hold at sweep
  boundaries regardless of mode.

* **Documents** never move (paper: "keep the ownership of d_i").
  ``n_td`` is sharded by worker; ``z`` is sharded with its token cells.

The per-round compute is the word-by-word F+LDA cell sweep (Alg. 3) over the
padded cell, with the same F+tree q-term maintenance as the serial version.

Two token geometries feed that sweep (``NomadLayout.kind``, DESIGN.md §4):
the **dense** ``(W, B, L)`` cell grid, and the **ragged** ``(W, W, S)``
per-chunk tile streams whose padding stays bounded by the tile size for any
``B``.  Initial assignments and per-token uniforms are derived from
canonical token coordinates (not array positions), so the two layouts run
**bit-identical** chains — the layout is purely a storage/throughput choice.

A third axis, ``doc_tile`` (DESIGN.md §7), lifts the doc-topic VMEM
ceiling: a layout built with ``doc_tile`` orders each cell's tokens by doc
group so the fused kernels can page one ``(doc_tile, T)`` slab of ``n_td``
through VMEM (``NomadLDA(doc_tile=...)``) — again with paged, unpaged,
dense and ragged execution all bit-identical over the same layout.
"""
from __future__ import annotations

import contextlib
import functools
from dataclasses import dataclass
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.compat import shard_map
from repro.data.sharding import NomadLayout, half_queue_split

__all__ = ["NomadLDA", "nomad_sweep_fn"]

F32 = jnp.float32


# ---------------------------------------------------------------------------
# Ring topology helpers (flat ring over possibly-multiple mesh axes).
# ---------------------------------------------------------------------------
def _flat_index(axes: Sequence[str], sizes: Sequence[int]):
    idx = jnp.zeros((), jnp.int32)
    for ax, sz in zip(axes, sizes):
        idx = idx * sz + lax.axis_index(ax)
    return idx


def _ring_shift_down(x, axes: Sequence[str], sizes: Sequence[int]):
    """Move value from flat-ring position i+1 to position i (blocks travel
    toward lower worker index, so worker w picks up block w+r+1 next round).

    For a single axis this is one ppermute; with a leading 'pod' axis the
    wrap-around element additionally hops across pods (DESIGN.md §4).
    """
    inner = axes[-1]
    n_inner = sizes[-1]
    perm = [(i, (i - 1) % n_inner) for i in range(n_inner)]
    x_w = lax.ppermute(x, inner, perm)
    if len(axes) == 1:
        return x_w
    # multi-axis: the element that wrapped within the pod actually belongs
    # to the previous pod's boundary worker — fix it with a pod-axis hop.
    outer = axes[0]
    n_outer = sizes[0]
    perm_o = [(p, (p - 1) % n_outer) for p in range(n_outer)]
    x_pw = lax.ppermute(x_w, outer, perm_o)
    at_boundary = lax.axis_index(inner) == n_inner - 1
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(at_boundary, b, a), x_w, x_pw)


# ---------------------------------------------------------------------------
# Layout-independent per-token uniforms.
# ---------------------------------------------------------------------------
def _token_uniforms(key, uids):
    """Counter-mode uniforms: one draw per token id, independent of the
    array geometry the ids arrive in.

    ``uid = global_block·L + slot`` names a token by its canonical cell
    coordinates, so the dense grid and the ragged stream draw the *same*
    uniform for the same token — the property that makes the two layouts'
    Gibbs chains bit-identical (and padding slots' draws harmless: they
    are computed but discarded by the valid mask)."""
    keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(key, uids.ravel())
    return jax.vmap(jax.random.uniform)(keys).reshape(uids.shape)


# ---------------------------------------------------------------------------
# Per-cell word-by-word F+LDA sweep (Alg. 3 with masking + local indices).
# ---------------------------------------------------------------------------
def _cell_sweep(tok_doc, tok_wrd, tok_valid, tok_bound, z_cell,
                n_td, n_wt, n_t, u, alpha, beta, beta_bar,
                r_mode: str = "dense", r_cap: int = 0,
                topics=None, counts=None):
    """Exact CGS over one padded cell (Alg. 3 with masking + local indices).

    tok_* / z_cell / u: (L,); n_td: (I,T) int32 (local docs); n_wt: (J,T)
    int32 (current block, local words); n_t: (T,) int32 (worker's working
    copy — possibly stale).  Returns updated (z_cell, n_td, n_wt, n_t)
    — with the per-doc ``(topics, counts)`` r-bucket side tables appended
    when ``r_mode="sparse"`` (see :mod:`repro.kernels.fused_sweep.rbucket`).

    The masked per-token chain itself lives in
    :func:`repro.kernels.fused_sweep.ref.fused_sweep_ref` — the single
    jnp reference all implementations (this scan mode, the fused Pallas
    kernel, its tests) share, so the float-op order is defined once.
    """
    from repro.kernels.fused_sweep.ref import fused_sweep_ref
    out = fused_sweep_ref(
        tok_doc, tok_wrd, tok_valid, tok_bound, z_cell, u,
        n_td, n_wt, n_t, alpha=alpha, beta=beta, beta_bar=beta_bar,
        r_mode=r_mode, r_cap=r_cap or None, topics=topics, counts=counts)
    if r_mode == "sparse":
        return out[0], out[1], out[2], out[3], out[5], out[6]
    return out[0], out[1], out[2], out[3]


def _vectorized_pass(doc_idx, wrd_idx, mask, z, n_td, n_wt, n_t, u,
                     alpha, beta, beta_bar):
    """One batched delayed-count pass over a flat token segment: every
    ``mask``-selected token is sampled against the counts as of entry
    (minus its own contribution — the standard delayed/minibatch CGS),
    then the deltas are applied exactly (batched scatter-add, duplicates
    accumulate).  Unmasked tokens are exact no-ops.

    The single definition both vectorized inner modes share: the dense
    grid passes one cell with ``mask = tok_valid``, the ragged stream
    passes the whole segment with ``mask`` selecting one cell — keeping
    the float-op order identical is what makes the two layouts'
    vectorized chains bit-equal.
    """
    T = n_t.shape[-1]
    one = mask.astype(jnp.int32)
    z_oh = jax.nn.one_hot(z, T, dtype=jnp.int32) * one[:, None]

    ntd_rows = n_td[doc_idx] - z_oh                    # (L,T) self-excluded
    nwt_rows = n_wt[wrd_idx] - z_oh
    nt_rows = n_t[None, :] - z_oh

    p = ((ntd_rows.astype(F32) + alpha)
         * (nwt_rows.astype(F32) + beta)
         / (nt_rows.astype(F32) + beta_bar))
    c = jnp.cumsum(p, axis=-1)
    draw = jnp.sum(c <= (u * c[:, -1])[:, None], axis=-1).astype(jnp.int32)
    z_new = jnp.where(mask, jnp.clip(draw, 0, T - 1), z)

    n_td = n_td.at[doc_idx, z].add(-one).at[doc_idx, z_new].add(one)
    n_wt = n_wt.at[wrd_idx, z].add(-one).at[wrd_idx, z_new].add(one)
    n_t = n_t.at[z].add(-one).at[z_new].add(one)
    return z_new, n_td, n_wt, n_t


def _cell_sweep_vectorized(tok_doc, tok_wrd, tok_valid, tok_bound, z_cell,
                           n_td, n_wt, n_t, u, alpha, beta, beta_bar):
    """Beyond-paper TPU mode (DESIGN §3 last row): the whole cell is sampled
    in one batched pass against counts frozen at cell start (minus each
    token's own contribution — the standard delayed/minibatch CGS, AD-LDA
    style *within* a cell), then the count deltas are applied exactly
    (:func:`_vectorized_pass`).

    Trades the paper's per-token exact chain for full 8×128-lane VPU
    utilization — the dense conditional here is exactly what the
    ``lda_scores`` Pallas kernel computes per tile.  Staleness ≤ one cell;
    cross-cell/nomad semantics unchanged.
    """
    return _vectorized_pass(tok_doc, tok_wrd, tok_valid, z_cell,
                            n_td, n_wt, n_t, u, alpha, beta, beta_bar)


def _queue_sweep_fused(tok_doc, tok_wrd, tok_valid, tok_bound, z_q,
                       n_td, n_wt_q, n_t, u, alpha, beta, beta_bar,
                       cell_start: int = 0, num_cells: int | None = None,
                       dto=None, doc_rows: int = 0, doc_blk: int = 0,
                       r_mode: str = "dense", r_cap: int = 0,
                       topics=None, counts=None, *, interpret: bool):
    """Exact per-token chain like :func:`_cell_sweep`, but the worker's whole
    per-round block queue runs as ONE fused ``pallas_call``
    (:func:`repro.kernels.fused_sweep.fused_sweep_cells`): grid over the k
    cells, F+tree / ``n_t`` / ``n_td`` carried across grid steps, one
    word-topic block VMEM-resident at a time (DESIGN.md §7).  Bit-exact
    same chain as ``inner_mode="scan"`` over the same queue.

    tok_* / z_q / u: (k, L); n_td: (I,T); n_wt_q: (k,J,T); n_t: (T,).
    ``cell_start``/``num_cells`` restrict the call to a sub-queue (the
    pipelined ring's half-queues); returned ``z_q``/``n_wt_q`` then cover
    only that range.  ``dto``/``doc_rows``/``doc_blk`` (a doc-tiled
    layout being *paged*, DESIGN.md §7) swap in the doc-tiled kernel:
    only one ``(doc_rows, T)`` doc-topic slab is VMEM-resident, with the
    chain untouched.
    """
    from repro.kernels.fused_sweep import fused_sweep_cells
    kw = dict(doc_tile_of=dto, doc_rows=doc_rows,
              n_blk=doc_blk) if dto is not None else {}
    out = fused_sweep_cells(
        tok_doc, tok_wrd, tok_valid, tok_bound, z_q, u, n_td, n_wt_q, n_t,
        alpha=alpha, beta=beta, beta_bar=beta_bar,
        cell_start=cell_start, num_cells=num_cells, interpret=interpret,
        r_mode=r_mode, r_cap=r_cap or None, topics=topics, counts=counts,
        **kw)
    if r_mode == "sparse":
        return out[0], out[1], out[2], out[3], out[5], out[6]
    return out[0], out[1], out[2], out[3]


def _queue_sweep_cells(cell_fn, tok_doc, tok_wrd, tok_valid, tok_bound, z_q,
                       n_td, n_wt_q, n_t, u, alpha, beta, beta_bar,
                       cell_start: int = 0, num_cells: int | None = None,
                       dto=None, doc_rows: int = 0, doc_blk: int = 0,
                       r_mode: str = "dense",
                       topics=None, counts=None):
    """Sweep a worker's k-cell queue with a per-cell function (``scan`` /
    ``vectorized`` inner modes): an inner ``lax.scan`` over the stacked
    cells, the exact chain carried through ``n_td``/``n_t``; each cell's
    ``z`` row and word-topic block ride as scan xs/ys.  Same shapes and
    sub-queue convention as :func:`_queue_sweep_fused`; the doc-tiling
    arguments are accepted and ignored — XLA manages residency here, and
    a doc-grouped layout's order is already baked into the token arrays,
    so the chain matches the paged fused kernel bit-for-bit.  With
    ``r_mode="sparse"`` the per-doc r-bucket side tables ride the scan
    carry next to ``n_td`` and are appended to the return."""
    del dto, doc_rows, doc_blk
    sparse = r_mode == "sparse"
    if num_cells is None:
        num_cells = tok_doc.shape[0] - cell_start
    sub = lambda a: a[cell_start:cell_start + num_cells]

    def cell_body(carry, xs):
        tok_d, tok_w, tok_v, tok_b, z_c, nwt_c, u_c = xs
        if sparse:
            n_td, n_t, tpc, cnt = carry
            z_c, n_td, nwt_c, n_t, tpc, cnt = cell_fn(
                tok_d, tok_w, tok_v, tok_b, z_c, n_td, nwt_c, n_t, u_c,
                alpha, beta, beta_bar, topics=tpc, counts=cnt)
            return (n_td, n_t, tpc, cnt), (z_c, nwt_c)
        n_td, n_t = carry
        z_c, n_td, nwt_c, n_t = cell_fn(
            tok_d, tok_w, tok_v, tok_b, z_c, n_td, nwt_c, n_t, u_c,
            alpha, beta, beta_bar)
        return (n_td, n_t), (z_c, nwt_c)

    carry0 = (n_td, n_t, topics, counts) if sparse else (n_td, n_t)
    carry, (z_q, n_wt_q) = lax.scan(
        cell_body, carry0,
        (sub(tok_doc), sub(tok_wrd), sub(tok_valid), sub(tok_bound),
         sub(z_q), sub(n_wt_q), sub(u)))
    if sparse:
        n_td, n_t, topics, counts = carry
        return z_q, n_td, n_wt_q, n_t, topics, counts
    n_td, n_t = carry
    return z_q, n_td, n_wt_q, n_t


# ---------------------------------------------------------------------------
# Ragged-stream queue sweeps (NomadLayout kind="ragged", DESIGN.md §4/§7):
# tok_* / z / u are flat (S,) per-chunk streams, cot the (S//tile,)
# tile→cell map; same sub-range convention as the dense queue sweeps but
# expressed as (tile_start, num_tiles) + (cell_start, num_cells).
# ---------------------------------------------------------------------------
def _queue_sweep_ragged_fused(tok_doc, tok_wrd, tok_valid, tok_bound, z_s,
                              n_td, n_wt_q, n_t, u, cot,
                              alpha, beta, beta_bar, *, tile,
                              tile_start=0, num_tiles=None,
                              cell_start=0, num_cells=None,
                              dto=None, doc_rows: int = 0,
                              r_mode: str = "dense", r_cap: int = 0,
                              topics=None, counts=None,
                              interpret: bool):
    """The ragged nomad hot path: the worker's whole per-round stream as
    ONE flat-grid ``pallas_call`` with scalar-prefetch block paging
    (:func:`repro.kernels.fused_sweep.fused_sweep_ragged`).  Bit-exact
    same chain as the dense queue sweeps over the same tokens.
    ``dto``/``doc_rows`` page the doc-topic slab (DESIGN.md §7)."""
    from repro.kernels.fused_sweep import fused_sweep_ragged
    out = fused_sweep_ragged(
        tok_doc, tok_wrd, tok_valid, tok_bound, z_s, u, cot,
        n_td, n_wt_q, n_t, alpha=alpha, beta=beta, beta_bar=beta_bar,
        n_blk=tile, tile_start=tile_start, num_tiles=num_tiles,
        cell_start=cell_start, num_cells=num_cells,
        doc_tile_of=dto, doc_rows=doc_rows,
        r_mode=r_mode, r_cap=r_cap or None, topics=topics, counts=counts,
        interpret=interpret)
    if r_mode == "sparse":
        return out[0], out[1], out[2], out[3], out[5], out[6]
    return out[0], out[1], out[2], out[3]


def _queue_sweep_ragged_scan(tok_doc, tok_wrd, tok_valid, tok_bound, z_s,
                             n_td, n_wt_q, n_t, u, cot,
                             alpha, beta, beta_bar, *, tile,
                             tile_start=0, num_tiles=None,
                             cell_start=0, num_cells=None,
                             dto=None, doc_rows: int = 0,
                             r_mode: str = "dense", r_cap: int = 0,
                             topics=None, counts=None):
    """Exact per-token chain over the ragged stream: one ``lax.scan``
    (the shared oracle) with the queue's blocks flattened to a
    ``(k·J, T)`` table — the same float ops in the same order as the
    dense ``"scan"`` mode over the same tokens.  Doc-tiling arguments
    accepted and ignored (see :func:`_queue_sweep_cells`)."""
    del dto, doc_rows
    from repro.kernels.fused_sweep.ref import fused_sweep_ragged_ref
    out = fused_sweep_ragged_ref(
        tok_doc, tok_wrd, tok_valid, tok_bound, z_s, u, cot,
        n_td, n_wt_q, n_t, alpha=alpha, beta=beta, beta_bar=beta_bar,
        n_blk=tile, tile_start=tile_start, num_tiles=num_tiles,
        cell_start=cell_start, num_cells=num_cells,
        r_mode=r_mode, r_cap=r_cap or None, topics=topics, counts=counts)
    if r_mode == "sparse":
        return out[0], out[1], out[2], out[3], out[5], out[6]
    return out[0], out[1], out[2], out[3]


def _queue_sweep_ragged_vectorized(tok_doc, tok_wrd, tok_valid, tok_bound,
                                   z_s, n_td, n_wt_q, n_t, u, cot,
                                   alpha, beta, beta_bar, *, tile,
                                   tile_start=0, num_tiles=None,
                                   cell_start=0, num_cells=None,
                                   dto=None, doc_rows: int = 0):
    """Beyond-paper batched mode on the ragged stream: one masked pass per
    cell over the stream segment (:func:`_vectorized_pass`), counts frozen
    at cell start — the same per-cell freeze points (and bit-identical
    draws) as :func:`_cell_sweep_vectorized` on the dense grid.
    Doc-tiling arguments accepted and ignored (see
    :func:`_queue_sweep_cells`)."""
    del dto, doc_rows
    k_total, J, T = n_wt_q.shape
    r_total = cot.shape[0]
    nt_ = r_total - tile_start if num_tiles is None else int(num_tiles)
    nc = k_total - cell_start if num_cells is None else int(num_cells)
    lo, hi = tile_start * tile, (tile_start + nt_) * tile
    sub = lambda a: a[lo:hi]
    cell_tok = jnp.broadcast_to(
        (cot[tile_start:tile_start + nt_] - cell_start)[:, None],
        (nt_, tile)).reshape(nt_ * tile)
    doc_seg, valid_seg = sub(tok_doc), sub(tok_valid)
    wrd_flat = cell_tok * J + sub(tok_wrd)
    u_seg = sub(u)
    nwt_flat = n_wt_q[cell_start:cell_start + nc].reshape(nc * J, T)

    def cell_body(carry, j):
        z_s, n_td, nwt_flat, n_t = carry
        mask = valid_seg & (cell_tok == j)
        return _vectorized_pass(doc_seg, wrd_flat, mask, z_s,
                                n_td, nwt_flat, n_t, u_seg,
                                alpha, beta, beta_bar), None

    (z_seg, n_td, nwt_flat, n_t), _ = lax.scan(
        cell_body, (sub(z_s), n_td, nwt_flat, n_t),
        jnp.arange(nc, dtype=jnp.int32))
    return z_seg, n_td, nwt_flat.reshape(nc, J, T), n_t


# ---------------------------------------------------------------------------
# The distributed sweep.
# ---------------------------------------------------------------------------
def nomad_sweep_fn(mesh: Mesh, ring_axes: Sequence[str], *,
                   B: int, T: int, alpha: float, beta: float,
                   beta_bar: float, sync_mode: str = "stoken",
                   inner_mode: str = "scan", ring_mode: str = "barrier",
                   interpret: bool | None = None,
                   collect_lag: bool = False,
                   layout_kind: str = "dense", tile: int = 0,
                   n_tiles: int = 0, tile_split: int = 0,
                   rng_stride: int = 0,
                   doc_rows: int = 0, doc_blk: int = 0,
                   page_docs: bool = False,
                   r_mode: str = "dense", r_cap: int = 0):
    """Build the jittable distributed sweep for ``mesh``.

    Ring spans the product of ``ring_axes`` (e.g. ('worker',) or
    ('pod', 'worker')).  Returns ``sweep(tok_*, z, n_td, n_wt, n_t, seed)``
    operating on global arrays sharded as documented in NomadLayout.

    ``B`` may be any multiple of the ring size ``W``: each worker's shard of
    the ``(B, J_max, T)`` word-topic array is its ``k = B/W``-block queue,
    and the sweep runs ``W`` ring rounds of ``k`` cells each (``B`` cell
    sweeps per worker per sweep — every (worker, block) pair exactly once).

    inner_mode: "scan" = exact per-token chain (paper Alg. 3), inner scan
    over the queue; "fused" = the same chain with the whole queue as ONE
    fused Pallas kernel per round (see :func:`_queue_sweep_fused`);
    "vectorized" = beyond-paper batched cell pass (see
    :func:`_cell_sweep_vectorized`).  ``interpret=None`` auto-selects the
    compiled Pallas path on TPU and the interpreter elsewhere.

    ring_mode: "barrier" = sweep all k cells, then hop the whole queue —
    one ``ppermute`` on the critical path per round.  "pipelined" = sweep
    the first half-queue (``half_queue_split(k)`` cells), issue its hop
    immediately, sweep the second half while that collective is in flight,
    then hop the rest together with the s token (DESIGN.md §4).  The cell
    order and the s-token fold point are identical in both modes, so the
    per-token chain is **bit-identical** — only the moment the first
    half's ``ppermute`` is *issued* moves.  With ``k < 2`` the pipelined
    schedule degenerates to the barrier one.

    collect_lag: diagnostic mode — the sweep additionally returns a
    ``(W_rounds, W, 2, T)`` int32 array holding, per round and worker,
    ``n_t_local`` after the round's s synchronization and the cumulative
    ``delta_mine``.  Adds no collectives (the exact ``n_t`` is
    reconstructed offline by summing deltas); used by
    ``launch/stoken_lag_check.py`` to verify the staleness bound.

    layout_kind: the token geometry the sweep operates on (DESIGN.md §4).
    ``"dense"``: tok_* are the padded ``(W, B, L)`` cell grid.
    ``"ragged"``: tok_* are the ``(W, W, S)`` per-chunk tile streams and
    the returned sweep takes two extra trailing arguments,
    ``cell_of_tile`` ``(W, W, n_tiles)`` and ``tok_slot`` ``(W, W, S)``;
    ``tile``/``n_tiles``/``tile_split`` are the layout's static tile
    geometry and ``rng_stride`` its ``L``.  Both layouts draw uniforms
    per canonical token id (:func:`_token_uniforms`), so for the same
    corpus, seed and modes their per-token chains are **bit-identical**
    (asserted across the whole matrix by ``launch/lda_matrix_check.py``).

    r_mode / r_cap: the r-bucket draw mode (DESIGN.md §7a,
    :mod:`repro.kernels.fused_sweep.rbucket`).  ``"dense"`` recomputes the
    capacity-``r_cap`` compacted topic vector from the ``n_td`` row per
    token; ``"sparse"`` maintains it as per-doc ``(topics, counts)`` side
    tables — the sweep then takes two extra trailing ``(W, I_max, r_cap)``
    table arguments (sharded like ``n_td``; build them with
    ``rbucket.build_side_table``) and returns them updated after the base
    four outputs.  Both modes draw from the same compacted vector, so for
    equal ``r_cap`` the chains are bit-identical; ``r_cap`` itself is
    chain-affecting (``0`` → ``T``, which preserves the dense default).
    ``"sparse"`` requires an exact per-token inner mode
    (``inner_mode != "vectorized"``).

    doc_rows / doc_blk / page_docs: a ``doc_tile``-grouped layout
    (DESIGN.md §7) sets ``doc_rows`` to its slab height — the sweep then
    takes a trailing ``doc_tile_of`` argument (and, for dense layouts, a
    ``tok_slot`` array so RNG ids stay position-independent across the
    group-padded rows).  ``page_docs=True`` makes the fused inner modes
    page one ``(doc_rows, T)`` doc-topic slab through VMEM instead of
    holding the whole ``(I_max, T)`` shard; all other modes (and
    ``page_docs=False``) run whole-shard on the identical grouped order,
    so paged, unpaged, dense and ragged chains are all bit-identical
    over the same layout.  ``doc_blk`` is the dense grid step the layout
    was built for (``NomadLayout.doc_blk``; ragged pages at its own
    ``tile``).
    """
    sizes = tuple(int(mesh.shape[ax]) for ax in ring_axes)
    W = int(np.prod(sizes))
    if B % W != 0 or B < W:
        raise ValueError(
            f"B must be a positive multiple of the ring size; got B={B}, "
            f"W={W}")
    k = B // W
    if sync_mode not in ("stoken", "stale", "allreduce"):
        raise ValueError(sync_mode)
    if inner_mode not in ("scan", "fused", "vectorized"):
        raise ValueError(inner_mode)
    if ring_mode not in ("barrier", "pipelined"):
        raise ValueError(ring_mode)
    if layout_kind not in ("dense", "ragged"):
        raise ValueError(layout_kind)
    ragged = layout_kind == "ragged"
    if ragged and (tile < 1 or n_tiles < 1 or rng_stride < 1):
        raise ValueError(
            f"ragged sweep needs the layout's tile geometry; got "
            f"tile={tile}, n_tiles={n_tiles}, rng_stride={rng_stride}")
    grouped = doc_rows > 0
    if page_docs and not grouped:
        raise ValueError(
            "page_docs needs a doc_tile-grouped layout (doc_rows > 0)")
    if grouped and rng_stride < 1:
        raise ValueError(
            "doc-grouped sweeps need rng_stride (the layout's true L)")
    if grouped and not ragged and doc_blk < 1:
        raise ValueError(
            "doc-grouped dense sweeps need doc_blk (the layout's grid "
            "step)")
    if r_mode not in ("dense", "sparse"):
        raise ValueError(f"r_mode must be 'dense' or 'sparse', got {r_mode}")
    sparse = r_mode == "sparse"
    if sparse and inner_mode == "vectorized":
        raise ValueError(
            "r_mode='sparse' needs an exact per-token chain; the batched "
            "'vectorized' inner mode has no per-token side-table order")
    cap = int(r_cap) if r_cap else T
    if not 1 <= cap <= T:
        raise ValueError(f"r_cap must be in [1, T]; got {r_cap} (T={T})")
    rbk = dict(r_mode=r_mode, r_cap=cap)
    if interpret is None:
        from repro.kernels.fused_sweep import default_interpret
        interpret = default_interpret()
    if ragged:
        if inner_mode == "fused":
            queue_fn = functools.partial(_queue_sweep_ragged_fused,
                                         tile=tile, interpret=interpret,
                                         **rbk)
        elif inner_mode == "scan":
            queue_fn = functools.partial(_queue_sweep_ragged_scan,
                                         tile=tile, **rbk)
        else:
            queue_fn = functools.partial(_queue_sweep_ragged_vectorized,
                                         tile=tile)
    elif inner_mode == "fused":
        queue_fn = functools.partial(_queue_sweep_fused, interpret=interpret,
                                     **rbk)
    else:
        cell_fn = {"scan": functools.partial(_cell_sweep, **rbk),
                   "vectorized": _cell_sweep_vectorized}[inner_mode]
        queue_fn = functools.partial(_queue_sweep_cells, cell_fn,
                                     r_mode=r_mode)
    k0 = half_queue_split(k) if ring_mode == "pipelined" else 0
    # the static tile index of the ragged half split (0 degenerates to the
    # barrier schedule, exactly like k0 = 0 on the dense grid)
    r0 = tile_split if (ragged and k0 > 0) else 0

    spec_tok = P(tuple(ring_axes), None, None)
    spec_td = P(tuple(ring_axes), None, None)
    spec_wt = P(tuple(ring_axes), None, None)
    spec_rep = P()

    def worker_fn(tok_doc, tok_wrd, tok_valid, tok_bound,
                  z, n_td, n_wt_q, n_t, seed, *aux):
        # local shapes: tok_* (1,B,L) dense / (1,W,S) ragged; n_td (1,I,T);
        # n_wt_q (k,J,T) — the worker's block queue; n_t (T,) replicated;
        # seed () replicated.  Trailing aux arrays, in order: ragged adds
        # cell_of_tile (1,W,n_tiles); ragged-or-grouped adds tok_slot
        # (1,W,S)|(1,B,L); grouped adds doc_tile_of (1,W,n_tiles)|
        # (1,B,L//doc_blk); sparse r-mode adds the rb_topics/rb_counts
        # side tables (1,I,r_cap), sharded like n_td.
        a = list(aux)
        cell_of_tile = a.pop(0) if ragged else None
        tok_slot = a.pop(0) if (ragged or grouped) else None
        doc_tile_of = a.pop(0) if grouped else None
        rb_t, rb_c = (a.pop(0), a.pop(0)) if sparse else (None, None)
        w_flat = _flat_index(ring_axes, sizes)
        key = jax.random.fold_in(jax.random.key(seed), w_flat)
        # RNG stride: the true heaviest cell.  Ungrouped dense rows ARE
        # that long; group padding makes rows longer, so the stride must
        # come from the layout there.
        L = rng_stride if (ragged or grouped) else tok_doc.shape[-1]
        S = tok_doc.shape[-1]

        n_t_start = n_t
        s_tok = n_t                       # authoritative s payload (holder 0)
        delta_folded = jnp.zeros_like(n_t)

        def round_body(carry, r):
            if sparse:
                (z, n_td, n_wt_q, n_t_local, delta_mine, s_tok,
                 delta_folded, rb_t, rb_c) = carry
                rb_kw = dict(topics=rb_t[0], counts=rb_c[0])
            else:
                (z, n_td, n_wt_q, n_t_local, delta_mine, s_tok,
                 delta_folded) = carry
                rb_t = rb_c = None
                rb_kw = {}
            c = (w_flat + r) % W          # chunk id this queue corresponds to
            b0 = c * k                    # its first global block index
            key_r = jax.random.fold_in(key, r)
            n_t_before = n_t_local
            doc_kw = {}
            if ragged:
                chunk = lambda a: lax.dynamic_slice_in_dim(a[0], c, 1,
                                                           axis=0)[0]
                tq = (chunk(tok_doc), chunk(tok_wrd), chunk(tok_valid),
                      chunk(tok_bound))
                z_q_in = chunk(z)
                cot = chunk(cell_of_tile)                      # (n_tiles,)
                # every tile holds `tile` tokens: a broadcast, not a
                # jnp.repeat (whose total_repeat_length form the TPU
                # compiler takes minutes over at real stream lengths)
                cell_tok = jnp.broadcast_to(
                    cot[:, None], (cot.shape[0], tile)).reshape(S)
                uid = (b0 + cell_tok) * L + chunk(tok_slot)
                u = _token_uniforms(key_r, uid)
                sweep_args = tq + (z_q_in, n_td[0], n_wt_q, n_t_local, u,
                                   cot, alpha, beta, beta_bar)
                if page_docs:
                    doc_kw = dict(dto=chunk(doc_tile_of), doc_rows=doc_rows)
                if r0 > 0:
                    halves = dict(
                        first=dict(tile_start=0, num_tiles=r0,
                                   cell_start=0, num_cells=k0),
                        second=dict(tile_start=r0, num_tiles=n_tiles - r0,
                                    cell_start=k0, num_cells=k - k0))
            else:
                queue = lambda a: lax.dynamic_slice_in_dim(a[0], b0, k,
                                                           axis=0)
                tq = (queue(tok_doc), queue(tok_wrd), queue(tok_valid),
                      queue(tok_bound))
                z_q_in = queue(z)
                if grouped:
                    # group padding breaks the position == slot identity
                    # of the ungrouped dense row, so slots ride along
                    uid = ((b0 + jnp.arange(k, dtype=jnp.int32))[:, None]
                           * L + queue(tok_slot))
                else:
                    uid = ((b0 + jnp.arange(k, dtype=jnp.int32))[:, None]
                           * L + jnp.arange(L, dtype=jnp.int32)[None, :])
                u = _token_uniforms(key_r, uid)
                sweep_args = tq + (z_q_in, n_td[0], n_wt_q, n_t_local, u,
                                   alpha, beta, beta_bar)
                if page_docs:
                    doc_kw = dict(dto=queue(doc_tile_of),
                                  doc_rows=doc_rows, doc_blk=doc_blk)
                if k0 > 0:
                    halves = dict(
                        first=dict(cell_start=0, num_cells=k0),
                        second=dict(cell_start=k0, num_cells=k - k0))
            pipelined = (r0 if ragged else k0) > 0
            if pipelined:
                # Pipelined: sweep the first half-queue, hop its blocks
                # right away — nothing consumes the shifted value until the
                # next round, so the collective can run concurrently with
                # the second half's sweep (one extra ppermute per round,
                # but off the critical path).
                out0 = queue_fn(*sweep_args, **doc_kw, **rb_kw,
                                **halves["first"])
                z_h0, n_td0, nwt_h0, n_t_local = out0[:4]
                if sparse:
                    rb_kw = dict(topics=out0[4], counts=out0[5])
                nwt_h0 = _ring_shift_down(nwt_h0, ring_axes, sizes)
                args2 = (sweep_args[:5] + (n_td0, n_wt_q, n_t_local)
                         + sweep_args[8:])
                out1 = queue_fn(*args2, **doc_kw, **rb_kw,
                                **halves["second"])
                z_h1, n_td0, nwt_h1, n_t_local = out1[:4]
                if sparse:
                    rb_t, rb_c = out1[4][None], out1[5][None]
                z_q = jnp.concatenate([z_h0, z_h1], axis=0)
            else:
                out = queue_fn(*sweep_args, **doc_kw, **rb_kw)
                z_q, n_td0, nwt_swept, n_t_local = out[:4]
                if sparse:
                    rb_t, rb_c = out[4][None], out[5][None]
            n_td = n_td0[None]
            if ragged:
                z = lax.dynamic_update_slice_in_dim(
                    z[0], z_q[None], c, axis=0)[None]
            else:
                z = lax.dynamic_update_slice_in_dim(
                    z[0], z_q, b0, axis=0)[None]
            delta_mine = delta_mine + (n_t_local - n_t_before)

            # --- s synchronization ---------------------------------------
            # Identical fold point in both ring modes (after the whole
            # k-cell round) — this is what keeps the chains bit-identical.
            if sync_mode == "allreduce":
                n_t_local = n_t_start + lax.psum(delta_mine, tuple(ring_axes))
            elif sync_mode == "stoken":
                has_token = ((w_flat + r) % W) == 0
                fold = delta_mine - delta_folded
                s_new = s_tok + fold
                s_tok = jnp.where(has_token, s_new, s_tok)
                n_t_local = jnp.where(has_token, s_new, n_t_local)
                delta_folded = jnp.where(has_token, delta_mine, delta_folded)
            # "stale": nothing until sweep end.

            # --- rotate the remaining nomadic payloads --------------------
            if pipelined:
                nwt_h1, s_tok = _ring_shift_down((nwt_h1, s_tok),
                                                 ring_axes, sizes)
                n_wt_q = jnp.concatenate([nwt_h0, nwt_h1], axis=0)
            else:
                n_wt_q, s_tok = _ring_shift_down((nwt_swept, s_tok),
                                                 ring_axes, sizes)
            ys = (jnp.stack([n_t_local, delta_mine])[None]
                  if collect_lag else None)
            carry = (z, n_td, n_wt_q, n_t_local, delta_mine, s_tok,
                     delta_folded)
            if sparse:
                carry += (rb_t, rb_c)
            return carry, ys

        carry0 = (z, n_td, n_wt_q, n_t, jnp.zeros_like(n_t), s_tok,
                  delta_folded)
        if sparse:
            carry0 += (rb_t, rb_c)
        carry, lag = lax.scan(
            round_body, carry0, jnp.arange(W, dtype=jnp.int32))
        z, n_td, n_wt_q, _, delta_mine = carry[:5]

        # W shifts = one full loop: every queue is back home, in block order.
        # exact sweep-end resync (additivity of s)
        n_t_out = n_t_start + lax.psum(delta_mine, tuple(ring_axes))
        out = (z, n_td, n_wt_q, n_t_out)
        if sparse:
            out += (carry[7], carry[8])
        if collect_lag:
            out += (lag,)
        return out

    out_specs = (spec_tok, spec_td, spec_wt, spec_rep)
    if sparse:
        out_specs += (spec_td, spec_td)                # rb_topics, rb_counts
    if collect_lag:
        out_specs += (P(None, tuple(ring_axes), None, None),)
    in_specs = (spec_tok, spec_tok, spec_tok, spec_tok,
                spec_tok, spec_td, spec_wt, spec_rep, spec_rep)
    if ragged:
        # trailing cell_of_tile + tok_slot, sharded with the token streams
        in_specs += (spec_tok, spec_tok)
        if grouped:
            in_specs += (spec_tok,)                    # doc_tile_of
    elif grouped:
        in_specs += (spec_tok, spec_tok)               # tok_slot, dto
    if sparse:
        in_specs += (spec_td, spec_td)                 # rb_topics, rb_counts
    fn = shard_map(
        worker_fn, mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False)
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# Driver.
# ---------------------------------------------------------------------------
@dataclass
class NomadLDA:
    """End-to-end distributed LDA trainer (the paper's F+Nomad LDA).

    ``layout.B`` may be any multiple of the ring size: each worker then
    carries a ``k = B/W``-block queue around the ring (paper §4's
    blocks ≫ workers setup).  ``interpret=None`` (the default) compiles the
    ``inner_mode="fused"`` Pallas path on TPU and interprets it elsewhere.
    ``ring_mode="pipelined"`` overlaps each round's first half-queue hop
    with the second half's sweep — bit-identical chain to ``"barrier"``
    (see :func:`nomad_sweep_fn`).  The token geometry follows the layout:
    ``build_layout(layout="ragged")`` swaps the padded cell grid for the
    ragged tile streams (bit-identical chain again), which keeps
    pad_fraction — and throughput — independent of ``B``.

    ``doc_tile`` lifts the doc-topic VMEM ceiling (DESIGN.md §7): on a
    layout built with the same ``doc_tile``, the fused kernels page one
    ``(doc_tile, T)`` slab of each worker's ``n_td`` shard through VMEM
    instead of holding the whole ``(I_max, T)`` table.  ``doc_tile=None``
    (default) runs whole-shard — today's behavior — even on a grouped
    layout, and is bit-identical to the paged run over the same layout
    (the grouping lives in the token order, the paging only in memory
    residency).

    ``r_mode="sparse"`` maintains the per-doc r-bucket side tables
    (DESIGN.md §7a) as two extra ``(W, I_max, r_cap)`` sweep arrays,
    initialised from ``n_td`` by :meth:`init_arrays` and threaded through
    :meth:`sweep`.  ``r_cap=0`` (default) keeps the full ``T`` capacity —
    bit-identical to the dense default; set ``r_cap=layout.r_cap`` (the
    per-shard max-doc-length bound) to make the r-draw cost independent
    of ``T`` (chain-affecting: compared runs must share ``r_cap``).
    """
    mesh: Mesh
    ring_axes: tuple
    layout: NomadLayout
    alpha: float
    beta: float
    sync_mode: str = "stoken"
    inner_mode: str = "scan"
    ring_mode: str = "barrier"
    interpret: bool | None = None  # Pallas mode for inner_mode="fused"
    doc_tile: int | None = None    # page (doc_tile, T) n_td slabs if set
    r_mode: str = "dense"          # r-bucket draw: "dense" | "sparse"
    r_cap: int = 0                 # compaction capacity (0 → T; the layout's
                                   #   T_d_max bound is ``layout.r_cap``)
    checkpoint_every: int | None = None  # sweeps between chain checkpoints
    checkpoint_path: str | None = None   # ``.npz`` = single file; else a
                                         #   CheckpointRotation directory
    resume_from: str | None = None       # chain checkpoint ``run`` loads
                                         #   (same file-vs-directory rule)
    checkpoint_keep: int = 3             # rotation slots kept (dirs only)

    def __post_init__(self):
        lay = self.layout
        if self.checkpoint_every is not None:
            if self.checkpoint_every < 1:
                raise ValueError(
                    f"checkpoint_every must be >= 1, got "
                    f"{self.checkpoint_every}")
            if not self.checkpoint_path:
                raise ValueError(
                    "checkpoint_every needs checkpoint_path to write to")
        W = int(np.prod([self.mesh.shape[ax] for ax in self.ring_axes]))
        if lay.W != W:
            raise ValueError(
                f"layout built for {lay.W} workers but the ring has {W}")
        if lay.B % lay.W != 0:
            raise ValueError(
                f"layout B={lay.B} is not a multiple of W={lay.W}")
        if self.doc_tile is not None and self.doc_tile != lay.doc_tile:
            raise ValueError(
                f"doc_tile={self.doc_tile} but the layout was built with "
                f"doc_tile={lay.doc_tile or None}; the slab height is a "
                f"layout-build-time choice (it fixes the token order)")
        self.beta_bar = self.beta * lay.num_words
        self._sweep = nomad_sweep_fn(
            self.mesh, self.ring_axes, B=lay.B, T=lay.T,
            alpha=self.alpha, beta=self.beta, beta_bar=self.beta_bar,
            sync_mode=self.sync_mode, inner_mode=self.inner_mode,
            ring_mode=self.ring_mode, interpret=self.interpret,
            layout_kind=lay.kind, tile=lay.tile, n_tiles=lay.n_tiles,
            tile_split=lay.tile_split, rng_stride=lay.L,
            doc_rows=lay.doc_tile, doc_blk=lay.doc_blk,
            page_docs=self.doc_tile is not None,
            r_mode=self.r_mode, r_cap=self.r_cap)
        ring = tuple(self.ring_axes)
        self._sh_tok = NamedSharding(self.mesh, P(ring, None, None))
        self._sh_rep = NamedSharding(self.mesh, P())
        # What one sweep does, for its span: the work of every (round,
        # worker, half), the fused-kernel calls each chip runs, and each
        # device's ring position.
        self._work = lay.half_work()
        coords = np.indices(self.mesh.devices.shape)
        pos = np.ravel_multi_index(
            [coords[self.mesh.axis_names.index(ax)] for ax in self.ring_axes],
            [self.mesh.shape[ax] for ax in self.ring_axes])
        self._worker_of = {int(d.id): int(w) for d, w in
                           zip(self.mesh.devices.flat, pos.flat)}
        split = half_queue_split(lay.k) > 0 and (
            lay.kind != "ragged" or lay.tile_split > 0)
        per_round = 2 if self.ring_mode == "pipelined" and split else 1
        self._calls = per_round * W if self.inner_mode == "fused" else 0
        self._tokens, self._rebuilds = (
            int(n) for n in self._work[..., :2].sum((0, 1, 2)))

    # -- state construction --------------------------------------------------
    def init_arrays(self, seed: int = 0):
        with obs.span("nomad.init_arrays"):
            return self._init_arrays(seed)

    def _init_arrays(self, seed: int):
        lay = self.layout
        rng = np.random.default_rng(seed)
        # Initial assignments are drawn in canonical token order — the same
        # per-token values whichever geometry (dense/ragged) carries them,
        # so sweeps over the two layouts start from the identical chain.
        z_canon = rng.integers(0, lay.T,
                               lay.canon_idx.shape[0]).astype(np.int32)
        n_td = np.zeros((lay.W, lay.I_max, lay.T), np.int32)
        n_wt = np.zeros((lay.B, lay.J_max, lay.T), np.int32)
        w_idx, b_idx, d_idx, j_idx = lay.token_coords()
        np.add.at(n_td, (w_idx, d_idx, z_canon), 1)
        np.add.at(n_wt, (b_idx, j_idx, z_canon), 1)
        n_t = np.bincount(z_canon, minlength=lay.T)

        put = lambda a, sh: jax.device_put(a, sh)
        arrays = dict(
            tok_doc=put(lay.tok_doc, self._sh_tok),
            tok_wrd=put(lay.tok_wrd, self._sh_tok),
            tok_valid=put(lay.tok_valid, self._sh_tok),
            tok_bound=put(lay.tok_bound, self._sh_tok),
            z=put(lay.place_canonical(z_canon), self._sh_tok),
            n_td=put(n_td, self._sh_tok),
            n_wt=put(n_wt, self._sh_tok),
            n_t=put(n_t.astype(np.int32), self._sh_rep),
        )
        if lay.kind == "ragged":
            arrays.update(
                cell_of_tile=put(lay.cell_of_tile, self._sh_tok),
                tok_slot=put(lay.tok_slot, self._sh_tok))
        elif lay.doc_tile > 0:
            arrays.update(tok_slot=put(lay.tok_slot, self._sh_tok))
        if lay.doc_tile > 0:
            arrays.update(doc_tile_of=put(lay.doc_tile_of, self._sh_tok))
        if self.r_mode == "sparse":
            from repro.kernels.fused_sweep import rbucket
            cap = self.r_cap or lay.T
            tpc, cnt = rbucket.build_side_table(
                jnp.asarray(n_td.reshape(lay.W * lay.I_max, lay.T)), cap)
            arrays.update(
                rb_topics=put(np.asarray(
                    tpc.reshape(lay.W, lay.I_max, cap)), self._sh_tok),
                rb_counts=put(np.asarray(
                    cnt.reshape(lay.W, lay.I_max, cap)), self._sh_tok))
        return arrays

    def sweep(self, arrays: dict, seed: int) -> dict:
        """One sweep, dispatched and not waited for.  Its span
        ``nomad.sweep`` times argument assembly and dispatch and carries
        the seed, the fused-kernel ``calls`` each chip runs, the
        layout's per-(round, worker, half) ``work``
        (:meth:`NomadLayout.half_work`) and ``worker_of``, each device
        id's ring position."""
        lay = self.layout
        with obs.span("nomad.sweep", seed=int(seed), calls=self._calls,
                      work=self._work, worker_of=self._worker_of):
            args = (arrays["tok_doc"], arrays["tok_wrd"],
                    arrays["tok_valid"], arrays["tok_bound"], arrays["z"],
                    arrays["n_td"], arrays["n_wt"], arrays["n_t"],
                    jnp.int32(seed))
            if lay.kind == "ragged":
                args += (arrays["cell_of_tile"], arrays["tok_slot"])
            elif lay.doc_tile > 0:
                args += (arrays["tok_slot"],)
            if lay.doc_tile > 0:
                args += (arrays["doc_tile_of"],)
            if self.r_mode == "sparse":
                args += (arrays["rb_topics"], arrays["rb_counts"])
            res = self._sweep(*args)
        obs.count("nomad.sweeps")
        obs.count("nomad.tokens", self._tokens)
        obs.count("nomad.rebuilds", self._rebuilds)
        out = dict(arrays)
        out.update(z=res[0], n_td=res[1], n_wt=res[2], n_t=res[3])
        if self.r_mode == "sparse":
            out.update(rb_topics=res[4], rb_counts=res[5])
        return out

    # -- evaluation -----------------------------------------------------------
    def log_likelihood(self, arrays: dict) -> float:
        """Joint LL from the padded sharded tables (pad rows contribute 0)."""
        from jax.scipy.special import gammaln
        lay = self.layout
        T, J = lay.T, lay.num_words
        alpha, beta = self.alpha, self.beta
        n_td = arrays["n_td"].astype(F32)            # (W,I_max,T) padded
        n_wt = arrays["n_wt"].astype(F32)            # (B,J_max,T) padded
        n_t = arrays["n_t"].astype(F32)
        n_i = n_td.sum(axis=2)                       # (W,I_max)
        is_doc = jnp.asarray(self.layout.doc_of_worker >= 0)
        I = int(is_doc.sum())
        doc_part = (I * (gammaln(T * alpha) - T * gammaln(alpha))
                    - jnp.where(is_doc, gammaln(T * alpha + n_i), 0.0).sum()
                    + gammaln(alpha + n_td).sum()
                    - (~is_doc).sum() * T * gammaln(jnp.float32(alpha)))
        topic_part = (T * (gammaln(J * beta) - J * gammaln(beta))
                      - gammaln(J * beta + n_t).sum()
                      + gammaln(beta + n_wt).sum()
                      - (lay.B * lay.J_max - J) * T * gammaln(jnp.float32(beta)))
        return float(doc_part + topic_part)

    def global_counts(self, arrays: dict):
        """Gather compact global (n_td, n_wt, n_t) for validation."""
        lay = self.layout
        n_td_p = np.asarray(arrays["n_td"])
        n_wt_p = np.asarray(arrays["n_wt"])
        I = lay.doc_assign.shape[0]    # full doc-id space (retired docs
        J = lay.num_words              # keep zero rows, corpus_store)
        n_td = np.zeros((I, lay.T), np.int64)
        for w in range(lay.W):
            ids = lay.doc_of_worker[w]
            m = ids >= 0
            n_td[ids[m]] = n_td_p[w, m]
        n_wt = np.zeros((J, lay.T), np.int64)
        for b in range(lay.B):
            ids = lay.word_of_block[b]
            m = ids >= 0
            n_wt[ids[m]] = n_wt_p[b, m]
        return n_td, n_wt, np.asarray(arrays["n_t"], np.int64)

    # -- φ snapshot export (DESIGN.md §10) ------------------------------------
    def export_phi_snapshot(self, arrays: dict, *, sweep: int | None = None):
        """Freeze the current word-topic counts into a serving snapshot
        (``repro.serve.lda_engine.PhiSnapshot``): the posterior-mean φ̂
        plus α/β and provenance meta.  Derived state only — publishing
        never perturbs the chain, so a background ring can call this
        every ``publish_every`` sweeps while readers keep serving."""
        from repro.serve.lda_engine import snapshot_from_counts
        _, n_wt, n_t = self.global_counts(arrays)
        extra = {"source": "nomad", "T": self.layout.T,
                 "num_words": self.layout.num_words}
        if sweep is not None:
            extra["sweep"] = int(sweep)
        return snapshot_from_counts(n_wt, n_t, alpha=self.alpha,
                                    beta=self.beta, extra_meta=extra)

    # -- chain checkpoint/resume (DESIGN.md §9) -------------------------------
    def _chain_meta(self, *, next_seed: int) -> dict:
        """Every chain-affecting knob; a resume with any of these different
        would silently fork the chain, so :meth:`restore_chain_state`
        refuses mismatches."""
        lay = self.layout
        return {
            "next_seed": int(next_seed),    # the RNG counter: sweep seeds
            "ring_round": 0,                # checkpoints sit at sweep
            "half_pos": 0,                  # boundaries — queues are home
            "T": lay.T, "alpha": float(self.alpha), "beta": float(self.beta),
            "sync_mode": self.sync_mode, "r_mode": self.r_mode,
            "r_cap": int(self.r_cap), "rng_stride": int(lay.L),
            "n_tokens": int(lay.canon_idx.shape[0]),
            "W": lay.W, "B": lay.B, "layout_kind": lay.kind,
            "doc_tile": int(lay.doc_tile), "num_docs": lay.doc_assign.shape[0],
            "num_words": lay.num_words,
        }

    def export_chain_state(self, arrays: dict, *, next_seed: int):
        """Snapshot the chain at a sweep boundary → ``(state, meta)``.

        ``z`` is stored in canonical token order and the count tables
        compact (global doc/word ids), so the snapshot is independent of
        the padded token geometry.  The sparse r-bucket side tables are
        stored verbatim: they are maintained incrementally and a fresh
        rebuild from ``n_td`` may list a doc's topics in a different
        order — same distribution, different bits.  The F+tree is derived
        state (rebuilt inside each sweep at every block boundary from the
        current counts), so only a digest of its basis is kept, as a
        restore-time integrity check.
        """
        import hashlib
        lay = self.layout
        z_canon = lay.extract_canonical(np.asarray(arrays["z"]))
        n_td, n_wt, n_t = self.global_counts(arrays)
        state = {
            "z_canon": z_canon.astype(np.int32),
            "n_td": n_td.astype(np.int32),
            "n_wt": n_wt.astype(np.int32),
            "n_t": n_t.astype(np.int32),
        }
        if self.r_mode == "sparse":
            state["rb_topics"] = np.asarray(arrays["rb_topics"])
            state["rb_counts"] = np.asarray(arrays["rb_counts"])
        meta = self._chain_meta(next_seed=next_seed)
        meta["ftree_digest"] = hashlib.sha256(
            np.ascontiguousarray(state["n_wt"]).tobytes()).hexdigest()
        return state, meta

    def restore_chain_state(self, state: dict, meta: dict):
        """Rebuild the sharded sweep arrays from a chain snapshot →
        ``(arrays, next_seed)``.  Bit-exact inverse of
        :meth:`export_chain_state` for this trainer's layout."""
        import hashlib
        lay = self.layout
        want = self._chain_meta(next_seed=0)
        for k in ("T", "alpha", "beta", "sync_mode", "r_mode", "r_cap",
                  "rng_stride", "n_tokens", "W", "B", "doc_tile",
                  "num_docs", "num_words"):
            if meta.get(k) != want[k]:
                raise ValueError(
                    f"chain checkpoint mismatch on {k!r}: checkpoint has "
                    f"{meta.get(k)!r}, this trainer has {want[k]!r} — "
                    f"resuming would fork the chain")
        if meta.get("ring_round") or meta.get("half_pos"):
            raise ValueError(
                "chain checkpoint not at a sweep boundary "
                f"(ring_round={meta.get('ring_round')}, "
                f"half_pos={meta.get('half_pos')})")
        got = hashlib.sha256(np.ascontiguousarray(
            state["n_wt"].astype(np.int32)).tobytes()).hexdigest()
        if meta.get("ftree_digest") not in (None, got):
            raise ValueError("chain checkpoint n_wt digest mismatch — "
                             "corrupt or hand-edited snapshot")

        z_canon = state["z_canon"].astype(np.int32)
        n_td_c = state["n_td"]
        n_wt_c = state["n_wt"]
        n_td = np.zeros((lay.W, lay.I_max, lay.T), np.int32)
        for w in range(lay.W):
            ids = lay.doc_of_worker[w]
            m = ids >= 0
            n_td[w, m] = n_td_c[ids[m]]
        n_wt = np.zeros((lay.B, lay.J_max, lay.T), np.int32)
        for b in range(lay.B):
            ids = lay.word_of_block[b]
            m = ids >= 0
            n_wt[b, m] = n_wt_c[ids[m]]

        put = lambda a, sh: jax.device_put(a, sh)
        arrays = dict(
            tok_doc=put(lay.tok_doc, self._sh_tok),
            tok_wrd=put(lay.tok_wrd, self._sh_tok),
            tok_valid=put(lay.tok_valid, self._sh_tok),
            tok_bound=put(lay.tok_bound, self._sh_tok),
            z=put(lay.place_canonical(z_canon), self._sh_tok),
            n_td=put(n_td, self._sh_tok),
            n_wt=put(n_wt, self._sh_tok),
            n_t=put(state["n_t"].astype(np.int32), self._sh_rep),
        )
        if lay.kind == "ragged":
            arrays.update(
                cell_of_tile=put(lay.cell_of_tile, self._sh_tok),
                tok_slot=put(lay.tok_slot, self._sh_tok))
        elif lay.doc_tile > 0:
            arrays.update(tok_slot=put(lay.tok_slot, self._sh_tok))
        if lay.doc_tile > 0:
            arrays.update(doc_tile_of=put(lay.doc_tile_of, self._sh_tok))
        if self.r_mode == "sparse":
            cap = self.r_cap or lay.T
            for k in ("rb_topics", "rb_counts"):
                if state[k].shape != (lay.W, lay.I_max, cap):
                    raise ValueError(
                        f"checkpoint {k} shape {state[k].shape} != "
                        f"{(lay.W, lay.I_max, cap)}")
            arrays.update(
                rb_topics=put(state["rb_topics"].astype(np.int32),
                              self._sh_tok),
                rb_counts=put(state["rb_counts"].astype(np.int32),
                              self._sh_tok))
        return arrays, int(meta["next_seed"])

    def save_checkpoint(self, path: str, arrays: dict, *,
                        next_seed: int) -> str:
        """Checkpoint the chain to ``path`` → the written file.  A path
        ending ``.npz`` is the legacy single-file store; anything else is
        a :class:`repro.train.checkpoint.CheckpointRotation` directory
        (slot step = ``next_seed``, keeping ``checkpoint_keep`` slots)."""
        from repro.train import checkpoint
        state, meta = self.export_chain_state(arrays, next_seed=next_seed)
        if path.endswith(".npz"):
            return checkpoint.save_chain(path, state, meta)
        rot = checkpoint.CheckpointRotation(path, keep=self.checkpoint_keep)
        return rot.save(state, meta, step=next_seed)

    def load_checkpoint(self, path: str):
        """Inverse of :meth:`save_checkpoint`: a ``.npz`` path loads that
        file; a directory loads the newest *valid* rotation slot —
        damaged slots are skipped (DESIGN.md §11 self-healing fallback),
        and the resumed chain is bit-exact from the slot's sweep."""
        from repro.train import checkpoint
        if path.endswith(".npz"):
            state, meta = checkpoint.load_chain(path)
        else:
            rot = checkpoint.CheckpointRotation(
                path, keep=self.checkpoint_keep)
            state, meta, _ = rot.load_latest_valid()
        return self.restore_chain_state(state, meta)

    def run(self, n_sweeps: int, *, init_seed: int = 0, on_sweep=None,
            publish_every: int | None = None,
            on_publish=None, fault_plan=None) -> tuple[dict, int]:
        """Drive the chain to ``n_sweeps`` total sweeps, checkpointing
        every ``checkpoint_every`` sweeps (resuming from ``resume_from``
        if set) → ``(arrays, sweeps_done)``.  Sweep ``s`` always runs with
        ``seed=s`` whether reached directly or across a resume, so an
        interrupted run is bit-identical to a straight-through one.

        ``publish_every``/``on_publish`` is the serving hook (DESIGN.md
        §10): every ``publish_every`` sweeps the counts are frozen into a
        φ snapshot (:meth:`export_phi_snapshot`) and handed to
        ``on_publish`` — typically ``LdaEngine.publish`` — so readers get
        fresh topics while the ring keeps training.  Publishing reads the
        chain but never writes it: a run with and without the hook is
        bit-identical.

        ``fault_plan`` (a :class:`repro.fault.FaultPlan`) is installed
        for the duration of the loop (DESIGN.md §11).  Sites fired per
        sweep ``s``: ``"trainer.publish"`` (index ``s``, before a
        scheduled publish — ``drop`` skips it, ``delay`` stalls it),
        ``"chain.write"`` (inside the checkpoint write, so ``corrupt`` /
        ``truncate`` land on the slot just written) and
        ``"trainer.sweep"`` (index ``s``, *after* the checkpoint — the
        kill-after-checkpoint preemption the chaos harness replays)."""
        from repro import fault
        if publish_every is not None:
            if publish_every < 1:
                raise ValueError(
                    f"publish_every must be >= 1, got {publish_every}")
            if on_publish is None:
                raise ValueError("publish_every needs an on_publish "
                                 "callback to hand snapshots to")
        with fault.install(fault_plan) if fault_plan is not None \
                else contextlib.nullcontext():
            if self.resume_from:
                arrays, start = self.load_checkpoint(self.resume_from)
            else:
                arrays = self.init_arrays(seed=init_seed)
                start = 0
            for s in range(start, n_sweeps):
                arrays = self.sweep(arrays, seed=s)
                if on_sweep is not None:
                    on_sweep(s, arrays)
                if publish_every and (s + 1) % publish_every == 0:
                    with obs.span("nomad.publish", sweep=s):
                        jax.block_until_ready(arrays["n_t"])
                        if "drop" not in fault.fire("trainer.publish",
                                                    index=s):
                            on_publish(self.export_phi_snapshot(
                                arrays, sweep=s + 1))
                if (self.checkpoint_every
                        and (s + 1) % self.checkpoint_every == 0):
                    with obs.span("nomad.checkpoint", sweep=s):
                        jax.block_until_ready(arrays["n_t"])
                        self.save_checkpoint(self.checkpoint_path, arrays,
                                             next_seed=s + 1)
                fault.fire("trainer.sweep", index=s)
        return arrays, n_sweeps
