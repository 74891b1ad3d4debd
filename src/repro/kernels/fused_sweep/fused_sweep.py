"""Fused F+LDA token-sweep kernel (paper Alg. 3, whole inner loop on-chip).

The ``lax.scan`` sweeps in :mod:`repro.core.cgs` and :mod:`repro.core.nomad`
honour the exact Gibbs chain but pay for it in memory traffic: every token
re-reads and re-writes its count rows and the F+tree through HBM, and each
scan step is its own XLA while-loop iteration.  This kernel fuses the whole
word-by-word sweep (decrement → F.update → q/r two-level draw →
increment → F.update) into **one** ``pallas_call``.  Each piece of state
lives where the TPU can address it the way the chain needs:

* the token streams (doc, word, valid, boundary, ``z``, uniform) are read
  one scalar at a time, so their ``(n_blk,)`` tiles sit in **SMEM**, and
  ``z'`` is written back there;
* the F+tree walk and path update are scalar loops, so the tree sits in
  one ``(2T,)`` SMEM buffer: the ``T − 1`` internal nodes **in order** —
  the node over leaves ``[a, a + 2w)`` at position ``a + w``, the root at
  ``T/2`` — then the leaves, with a VMEM ``(R, C)`` mirror of the leaves
  for the vector side of the draw.  A word-boundary rebuild sums all
  ``log2 T`` levels in vregs — per level one flat roll and add, each node
  the single add ``left + right`` of its two children, and a roll and
  select to its in-order position — and DMAs nodes and leaves to SMEM
  at once, staged in a VMEM ``(2T,)`` buffer;
* the doc-topic table ``n_td``, the word-topic block ``n_wt`` and ``n_t``
  live in VMEM for the whole call; per token the kernel loads and stores
  exactly one ``(1, T)`` row of each table by dynamic sublane index
  (``pl.ds``) and updates lanes with iota-masked selects.  A scalar is
  pulled out of a row by a masked sum, exact because one term is nonzero;
* tokens are tiled over a sequential grid (``N_BLK`` per program).  The
  count outputs use constant index maps, so the state persists across
  grid steps — the standard Pallas accumulator pattern — and the tree
  persists in scratch, so the chain is exact across tile boundaries.

Three grids share the tile body:

* :func:`fused_sweep_pallas` — one token stream against one word-topic
  block (the serial ``cgs`` hot path).  Grid ``(n_tiles,)``.
* :func:`fused_sweep_cells_pallas` — a *batch of k cells* (one nomad
  worker's whole per-round block queue) in a single call.  Grid
  ``(k, n_tiles)`` with the cell index leftmost, so the k cells run in
  sequence on the sequential TPU grid; ``n_td``/``n_t``/F carry across
  cell boundaries, while the per-cell word-topic block ``n_wt[c]`` is
  paged in/out by the BlockSpec index map — only one ``(J, T)`` block is
  VMEM-resident at a time.  Cross-cell chain exactness needs no special
  handling: a cell's first valid token is always a word boundary
  (``NomadLayout.tok_bound``), which rebuilds the tree from the incoming
  block's q vector.  The same property makes the grid freely
  *splittable*: a call over a sub-queue of ``m ≤ k`` cells chains
  bit-identically with the calls for the remaining cells — the pipelined
  nomad ring sweeps half-queues this way.
* :func:`fused_sweep_ragged_pallas` — the same k-cell queue as a **ragged
  tile stream** (``NomadLayout`` ``kind="ragged"``): each cell is padded
  only to its next tile multiple and the grid flattens to ``(n_tiles,)``.
  The per-tile cell id rides in as a **scalar-prefetch** operand
  (``pltpu.PrefetchScalarGridSpec``): the ``n_wt`` BlockSpec index map
  reads ``cell_of_tile[t]`` to page the right ``(J, T)`` block, and the
  body compares ``cell_of_tile[t]`` against ``t−1``'s to detect cell
  starts (the map is non-decreasing, so each block is paged exactly
  once).  The chain is bit-equal to the cell-batch grid token for token.

Every entry point also has a **doc-tiled** twin (``*_docs_pallas``) that
lifts the whole-shard VMEM residency of the doc-topic table: ``n_td``
stays in ``ANY`` memory (HBM on TPU) and the kernel pages one
``(doc_rows, T)`` slab through a VMEM scratch buffer, driven by a
scalar-prefetched per-tile ``doc_tile_of`` map (``NomadLayout`` built
with ``doc_tile``, whose grouped token order guarantees each grid step
touches exactly one slab).  Slabs *recur* across cells, so BlockSpec
window paging cannot carry them (an input window re-fetch reads the
stale initial table; a revisited output window is undefined on TPU) —
instead the table's HBM input is aliased to its output and the kernel
DMAs slabs in/out of that one buffer explicitly
(``pltpu.make_async_copy``): every page-in reads the accumulated counts
because every write-back went through the same buffer.  The token chain
itself is untouched — tiled and untiled execution over the same layout
are bit-identical.

Masking follows the nomad cell-sweep convention: ``valid=False`` tokens are
no-ops (count deltas of 0, leaf rewritten to itself, ``z`` kept), which is
what makes arbitrary padding of the token stream safe.  ``boundary=True``
rebuilds the tree from the incoming word's q vector; the tree starts zeroed,
so the first valid token of the stream must be a boundary (guaranteed by
``Corpus.word_boundary`` and by ``NomadLayout.tok_bound``).

Chain exactness: every float op (q rebuild, path update, prefix sum, draw)
is the same op on the same operands, in the same order, as the scan oracle
``ref.fused_sweep_ref``: the tree walk and path adds are the scalar ops of
:mod:`repro.core.ftree`, each rebuilt node is the one add ``ftree.build``
makes, the prefix sum is :func:`repro.core.prefix.prefix_sum`
with a roll-based shift, and the r-vector is compacted by moving values
only (:func:`repro.kernels.fused_sweep.rbucket.pack`).  Given identical
uniforms the kernel reproduces the oracle's ``z``/counts/tree bit for bit,
interpreted on the CPU and compiled on the TPU alike.  Every entry point
takes ``interpret`` as a required keyword; ``ops.default_interpret`` is the
one place that picks it.

The r-bucket draw is **doc-sparse** (paper §3's |T_d| ≪ T argument,
DESIGN.md §7): the r-term prefix sum runs over the capacity-``r_cap``
compacted vector of the document's nonzero topics
(:mod:`repro.kernels.fused_sweep.rbucket`).  Every kernel takes a static
``r_cap`` and a ``sparse`` switch: dense mode compacts the VMEM ``n_td``
row per token (Θ(T log T) lane work); sparse mode maintains the compaction
as per-doc ``(topics, counts)`` side tables — two extra ``(I, r_cap)`` i32
operands riding in/out exactly like ``n_td`` (whole-VMEM with constant
index maps, *including* in the doc-tiled twins) — making the per-token
r-draw Θ(r_cap).  Both modes draw from the same compacted vector, so their
chains are bit-identical.  Sparse mode gathers ``q[topics]`` by value,
which Mosaic does not lower: it runs interpreted only, and a compiled
sparse call fails in the TPU compiler.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import ftree
from repro.core.prefix import (flat_roll, prefix_sum_tiles, topic_iota,
                               topic_tile, tpu_roll)
from repro.kernels.fused_sweep import rbucket

N_BLK = 256  # tokens per grid program

F32 = jnp.float32


def _copy(src, dst, sem):
    """One DMA, started and waited on."""
    cp = pltpu.make_async_copy(src, dst, sem)
    cp.start()
    cp.wait()


# ---------------------------------------------------------------------------
# The F+tree in order, in one (2T,) SMEM buffer: the internal node over leaves
# [a, a + 2w) at a + w (the root at T/2; 0 is never a node), leaf t at T + t.
# A VMEM (R, C) mirror of the leaves serves the vector side of the draw; a
# rebuild stages both halves in a VMEM (2T,) buffer and DMAs it to SMEM.
# ---------------------------------------------------------------------------
class _Tree:
    def __init__(self, T, smem, stage, mirror, sem):
        self.T, self.d = T, ftree.depth(T)
        self.R, self.C = topic_tile(T)
        self.smem, self.stage, self.mirror, self.sem = (smem, stage, mirror,
                                                        sem)

    def zero(self):
        self.stage[...] = jnp.zeros((2 * self.T,), F32)
        self.mirror[...] = jnp.zeros((self.R, self.C), F32)
        _copy(self.stage, self.smem, self.sem)

    def build(self, q):
        """``ftree.build`` level by level in vregs, then one DMA to SMEM.

        After step ``k`` (children of half-width ``w = 2^k``), ``s[a]`` for
        ``a ≡ 0 (mod 2w)`` is the single add ``left + right`` of the node
        over ``[a, a + 2w)``; other lanes hold sums nothing reads.  Rolled
        by ``w``, it lands on the node's in-order position ``a + w``."""
        T = self.T
        flat = topic_iota((self.R, self.C))
        s, nodes = q, jnp.zeros_like(q)
        for k in range(self.d):
            w = 1 << k
            s = s + flat_roll(s, -w, roll=tpu_roll)
            nodes = jnp.where((flat & (2 * w - 1)) == w,
                              flat_roll(s, w, roll=tpu_roll), nodes)
        self.stage[pl.ds(0, T)] = nodes.reshape(T)
        self.stage[pl.ds(T, T)] = q.reshape(T)
        self.mirror[...] = q
        _copy(self.stage, self.smem, self.sem)

    def leaf(self, t):
        return self.smem[self.T + t]

    def total(self):
        return self.smem[self.T // 2 if self.d else self.T]

    def set_leaf(self, t, value, flat):
        """``ftree.set_leaf``: add ``value − leaf`` along the leaf's path,
        then mirror the new leaf into the VMEM tile."""
        cur = self.leaf(t)
        delta = value - cur
        new = cur + delta
        self.smem[self.T + t] = new
        for k in range(self.d):                 # bottom-up, half-width 2^k
            w = 1 << k
            i = (t & ~(2 * w - 1)) | w
            self.smem[i] = self.smem[i] + delta
        self.mirror[...] = jnp.where(flat == t, new, self.mirror[...])

    def sample(self, u01):
        """``ftree.sample``: the guarded top-down walk, one scalar per
        level; ``m`` is the current node's in-order position."""
        u = u01 * self.total()
        m = jnp.int32(self.T // 2)
        for k in reversed(range(self.d)):       # the node's half-width 2^k
            h = (1 << k) >> 1
            lo, hi, base = (m - h, m + h, 0) if k else (m - 1, m, self.T)
            left, right = self.smem[base + lo], self.smem[base + hi]
            go_right = (u >= left) & (right > 0)
            m = jnp.where(go_right, hi, lo)
            u = jnp.where(go_right, u - left, u)
        return m

    def flush(self, out):
        _copy(self.smem, out, self.sem)


def _heap(tree, T):
    """The in-order ``(2T,)`` tree as ``ftree``'s heap array ``F``: heap
    level ``l``'s nodes sit at in-order positions ``w, 3w, 5w, …`` with
    ``w = T / 2^(l+1)``."""
    nodes, leaves = tree[:T], tree[T:]
    levels = [nodes[T >> (l + 1)::T >> l] for l in range(ftree.depth(T))]
    return jnp.concatenate([jnp.zeros((1,), F32), *levels, leaves])


def _pick(tile, t):
    """Entry ``t`` of an ``(R, C)`` topic tile as a scalar (masked sum:
    exact, one nonzero term)."""
    return jnp.sum(jnp.where(topic_iota(tile.shape) == t, tile,
                             jnp.zeros_like(tile)))


def _dense_r_draw(ntd_row, q, cap):
    """Dense r-mode on an ``(R, C)`` doc-row tile: the compacted prefix
    ``c`` (valid below ``cap``), the active count and each topic's rank —
    :func:`rbucket.compact_row` + :func:`rbucket.r_cumsum` spelt in lane
    moves."""
    active = ntd_row > 0
    packed, rank = rbucket.pack(ntd_row.astype(F32) * q, active,
                                roll=tpu_roll)
    c = prefix_sum_tiles(packed, roll=tpu_roll)
    m = jnp.minimum(jnp.sum(active.astype(jnp.int32)), cap)
    return c, m, active, rank


def _sweep_tile(T: int, n_blk: int, r_cap: int, alpha: float, beta: float,
                beta_bar: float, tok, z_out, nt_ref, tree: _Tree,
                ntd_load, ntd_store, nwt_load, nwt_store,
                rb_load=None, rb_store=None):
    """Exact Alg. 3 chain over one token tile.

    ``tok(k)`` reads token ``k``'s scalars ``(d, w, valid, boundary, u01,
    t_old)`` and ``z_out(k, t)`` writes its new topic.  Row access to the
    doc-topic / word-topic tables is abstracted behind ``*_load(idx) ->
    (R, C)`` / ``*_store(idx, row)`` so every grid shares the float-op
    order exactly.  Topic vectors are ``(R, C)`` tiles
    (:func:`repro.core.prefix.topic_tile`).  With ``rb_load``/``rb_store``
    unset (dense mode) the
    r-vector is compacted from the decremented doc row per token; set, it
    is loaded from / stored to the per-doc side table (``rb_load(d) ->
    (topics, counts)``, ``rb_store(d, topics, counts)``) and maintained
    incrementally.
    """

    def body(k, nt):
        d, w, valid, boundary, u01, t_old = tok(k)
        flat = topic_iota(topic_tile(T))
        one = valid.astype(jnp.int32)

        ntd_row = ntd_load(d)                         # doc-topic row tile
        nwt_row = nwt_load(w)                         # word-topic row tile

        # Word boundary: rebuild the tree for the incoming word's q vector
        # (only boundary tokens pay the Θ(T) build).
        @pl.when(boundary)
        def _rebuild():
            tree.build((nwt_row.astype(F32) + beta)
                       / (nt.astype(F32) + beta_bar))

        def leaf_value(nwt_row, nt, t):
            return ((_pick(nwt_row, t).astype(F32) + beta)
                    / (_pick(nt, t).astype(F32) + beta_bar))

        # --- decrement (Alg. 3 inner loop, masked) ------------------------
        dec = jnp.where(flat == t_old, one, 0)
        ntd_row, nwt_row, nt = ntd_row - dec, nwt_row - dec, nt - dec
        tree.set_leaf(t_old, jnp.where(valid, leaf_value(nwt_row, nt, t_old),
                                       tree.leaf(t_old)), flat)

        # --- two-level draw p = α·q + r (eq. (6), doc-sparse r-bucket) -----
        q = tree.mirror[...]
        if rb_load is None:
            c, m, active, rank = _dense_r_draw(ntd_row, q, r_cap)
            r_mass = _pick(c, r_cap - 1)
        else:
            topics_d, counts_d = rb_load(d)
            topics_d, counts_d = rbucket.decrement(topics_d, counts_d,
                                                   t_old, valid)
            c = rbucket.r_cumsum(topics_d, counts_d, q.reshape(T))
            r_mass = c[-1]
        q_total = tree.total()
        norm = alpha * q_total + r_mass
        u_val = u01 * norm
        in_r = u_val < r_mass
        if rb_load is None:
            below = jnp.sum(((c <= u_val) & (flat < r_cap))
                            .astype(jnp.int32))
            j_r = jnp.minimum(below, jnp.maximum(m - 1, 0))
            t_r = jnp.sum(jnp.where(active & (rank == j_r), flat, 0))
        else:
            t_r = rbucket.pick(topics_d, counts_d, c, u_val)
        t_q = tree.sample(jnp.clip((u_val - r_mass)
                                   / jnp.maximum(alpha * q_total, 1e-30),
                                   0.0, 1.0 - 1e-7))
        t_new = jnp.where(valid, jnp.where(in_r, t_r, t_q), t_old)

        # --- increment -----------------------------------------------------
        inc = jnp.where(flat == t_new, one, 0)
        ntd_row, nwt_row, nt = ntd_row + inc, nwt_row + inc, nt + inc
        tree.set_leaf(t_new, jnp.where(valid, leaf_value(nwt_row, nt, t_new),
                                       tree.leaf(t_new)), flat)

        if rb_store is not None:
            topics_d, counts_d = rbucket.increment(topics_d, counts_d,
                                                   t_new, valid)
            rb_store(d, topics_d, counts_d)
        ntd_store(d, ntd_row)
        nwt_store(w, nwt_row)
        z_out(k, t_new)
        return nt

    nt_ref[...] = jax.lax.fori_loop(0, n_blk, body, nt_ref[...])


def _rb_kw(sparse, tpc_ref, cnt_ref):
    """Row load/store on the whole-VMEM per-doc side tables (sparse mode)."""
    if not sparse:
        return {}

    def load(d):
        return (tpc_ref[pl.ds(d, 1), :][0], cnt_ref[pl.ds(d, 1), :][0])

    def store(d, topics, counts):
        tpc_ref[pl.ds(d, 1), :] = topics[None]
        cnt_ref[pl.ds(d, 1), :] = counts[None]

    return dict(rb_load=load, rb_store=store)


def _row_access(ref, lead=()):
    """(load, store) of one ``(R, C)`` row tile of a VMEM table ref
    (``lead`` indexes a leading block axis)."""
    load = lambda i: ref[lead + (i,)]
    store = lambda i, row: ref.__setitem__(lead + (i,), row)
    return load, store


def _tok_access(tok_refs, z_ref):
    """Scalar readers of the six SMEM token tiles + the ``z'`` writer."""
    doc, wrd, valid, bound, z_in, u = tok_refs

    def tok(k):
        at = (0, 0, k)
        return (doc[at], wrd[at], valid[at] != 0, bound[at] != 0, u[at],
                z_in[at])

    def z_out(k, t):
        z_ref[0, 0, k] = t

    return tok, z_out


def _split_refs(refs, n_prefetch, sparse):
    """Unpack a kernel's refs: scalar prefetch, the nine inputs, the
    optional side tables, the outputs and the scratch buffers."""
    pre, rest = refs[:n_prefetch], refs[n_prefetch:]
    ins, rest = rest[:9], rest[9:]
    rb_in, rest = (rest[:2], rest[2:]) if sparse else ((), rest)
    outs, rest = rest[:5], rest[5:]
    rb_out, rest = (rest[:2], rest[2:]) if sparse else ((None, None), rest)
    scratch = rest
    return pre, ins, rb_in, outs, rb_out, scratch


def _make_kernel(T, n_blk, r_cap, sparse, alpha, beta, beta_bar, *,
                 n_prefetch, grid_pos, nwt_lead, cell_start_of, doc_rows):
    """Build one of the six kernel bodies.

    ``grid_pos(pre)`` → (first, last, slab index g, previous g) in raster
    order; ``cell_start_of(pre)`` → whether this step starts a new
    word-topic block (``None``: a single whole block); ``nwt_lead`` the
    leading block index of ``n_wt`` rows; ``doc_rows > 0`` pages
    ``(doc_rows, T)`` slabs of an HBM ``n_td``.
    """
    docs = doc_rows > 0

    def kernel(*refs):
        pre, ins, rb_in, outs, rb_out, scratch = _split_refs(
            refs, n_prefetch, sparse)
        (tok_doc, tok_wrd, tok_valid, tok_bound, z_in, u,
         ntd_in_ref, nwt_in_ref, nt_in_ref) = ins
        z_ref, ntd_ref, nwt_ref, nt_ref, tree_out = outs
        tpc_ref, cnt_ref = rb_out
        smem, stage, mirror, sem = scratch[:4]
        tree = _Tree(T, smem, stage, mirror, sem)
        first, last, g, g_prev = grid_pos(pre)

        @pl.when(first)
        def _init():
            if not docs:
                ntd_ref[...] = ntd_in_ref[...]
            nt_ref[...] = nt_in_ref[...]
            tree.zero()
            if sparse:
                tpc_ref[...] = rb_in[0][...]
                cnt_ref[...] = rb_in[1][...]

        start = cell_start_of(pre)
        if start is None:
            @pl.when(first)
            def _load_block():
                nwt_ref[...] = nwt_in_ref[...]
        else:
            @pl.when(start)
            def _load_block():
                nwt_ref[...] = nwt_in_ref[...]

        if docs:
            slab = scratch[4]
            _doc_slab_page(doc_rows, g, g_prev, first, ntd_ref, slab, sem)
            ntd_load, ntd_store = _slab_accessors(slab, g, doc_rows)
        else:
            ntd_load, ntd_store = _row_access(ntd_ref)
        nwt_load, nwt_store = _row_access(nwt_ref, nwt_lead)
        tok, z_out = _tok_access(
            (tok_doc, tok_wrd, tok_valid, tok_bound, z_in, u), z_ref)

        _sweep_tile(T, n_blk, r_cap, alpha, beta, beta_bar, tok, z_out,
                    nt_ref, tree, ntd_load, ntd_store, nwt_load, nwt_store,
                    **_rb_kw(sparse, tpc_ref, cnt_ref))

        @pl.when(last)
        def _flush():
            if docs:
                _copy(slab, ntd_ref.at[pl.ds(g * doc_rows, doc_rows)], sem)
            tree.flush(tree_out)

    return kernel


# ---------------------------------------------------------------------------
# Doc-tiled paging: n_td stays in ANY/HBM, one (doc_rows, T) slab is paged
# through a VMEM scratch by explicit DMA (module docstring).
# ---------------------------------------------------------------------------
def _doc_slab_page(doc_rows, g, g_prev, first, ntd_ref, slab, sem):
    """Slab prologue of one grid step: at the first step pull the first
    slab; at a slab switch, write the previous slab back and pull the
    new one.  The table's input and output are one aliased HBM buffer,
    so every page-in reads the counts as of the last write-back."""
    @pl.when(first)
    def _init():
        _copy(ntd_ref.at[pl.ds(g * doc_rows, doc_rows)], slab, sem)

    @pl.when(jnp.logical_not(first) & (g != g_prev))
    def _switch():
        _copy(slab, ntd_ref.at[pl.ds(g_prev * doc_rows, doc_rows)], sem)
        _copy(ntd_ref.at[pl.ds(g * doc_rows, doc_rows)], slab, sem)


def _slab_accessors(slab, g, doc_rows):
    """Row load/store on the resident slab; ``tok_doc`` carries worker-local
    doc indices, the slab holds rows [g·doc_rows, (g+1)·doc_rows).  A
    valid token's row is always in the slab; a padding token's (doc 0)
    may not be, so the index is clamped into the slab — its masked update
    rewrites the row it read, which changes nothing — instead of
    addressing VMEM outside the buffer."""
    row = lambda d: jnp.clip(d - g * doc_rows, 0, doc_rows - 1)
    load = lambda d: slab[row(d)]
    store = lambda d, r: slab.__setitem__(row(d), r)
    return load, store


# ---------------------------------------------------------------------------
# pallas_call assembly shared by the six entry points.
# ---------------------------------------------------------------------------
def _whole(*shape):
    """A block that is the whole array, at every grid step."""
    return pl.BlockSpec(shape, lambda *g: (0,) * len(shape))


def _fused_call(prefetch, tokens, n_td, n_wt, n_t, topics, counts, *,
                grid, tok_index, nwt_index, grid_pos, cell_start_of,
                doc_rows, alpha, beta, beta_bar, r_cap, n_blk, vmem_limit,
                interpret, name):
    """One fused-sweep ``pallas_call``, named ``name``.

    The six token streams (any shape, ``n_blk`` tokens per tile) ride as
    ``(n_tiles, 1, n_blk)`` SMEM blocks — last two dims whole, so any
    ``n_blk`` meets the TPU block-shape rule and XLA's HBM layout — with
    ``tok_index(*grid_ids) -> tile``.  Every topic axis is laid out as
    ``(R, C)`` row tiles (:func:`repro.core.prefix.topic_tile`), so a
    table row is whole vregs behind one dynamic leading index.
    ``nwt_index`` pages one block of a ``(k, J, T)`` word-topic queue per
    step (``None``: one whole ``(J, T)`` block); ``doc_rows > 0`` leaves
    ``n_td`` in HBM, aliased input to output, paged by slab.  The tree
    sits in SMEM scratch, flushed to one HBM output and laid out as a
    heap after the call.  Returns (z',
    n_td', n_wt', n_t', F (2T,)) in the callers' shapes, plus the side
    tables in sparse mode.
    """
    T = n_t.shape[-1]
    tile = topic_tile(T)
    rows = lambda a: a.reshape(a.shape[:-1] + tile)
    n_td, n_wt, n_t = rows(n_td), rows(n_wt), rows(n_t)
    toks = [a.reshape(-1, 1, n_blk) for a in tokens]
    cap = int(r_cap) if r_cap else T
    sparse = topics is not None
    docs = doc_rows > 0
    kernel = _make_kernel(
        T, n_blk, cap, sparse, float(alpha), float(beta), float(beta_bar),
        n_prefetch=len(prefetch), grid_pos=grid_pos,
        nwt_lead=() if nwt_index is None else (0,),
        cell_start_of=cell_start_of, doc_rows=doc_rows)

    smem_tile = pl.BlockSpec((1, 1, n_blk),
                             lambda *g: tok_index(*g) + (0, 0),
                             memory_space=pltpu.SMEM)
    any_spec = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)
    ntd_spec = any_spec if docs else _whole(*n_td.shape)
    nwt_spec = (_whole(*n_wt.shape) if nwt_index is None else
                pl.BlockSpec((1,) + n_wt.shape[1:],
                             lambda *g: (nwt_index(*g), 0, 0, 0)))
    rb_specs = [_whole(*topics.shape)] * 2 if sparse else []
    scratch = [pltpu.SMEM((2 * T,), F32), pltpu.VMEM((2 * T,), F32),
               pltpu.VMEM(tile, F32), pltpu.SemaphoreType.DMA]
    if docs:
        scratch.append(pltpu.VMEM((doc_rows,) + tile, jnp.int32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=grid,
        in_specs=[*([smem_tile] * 6),                    # token stream
                  ntd_spec, nwt_spec, _whole(*tile),     # count tables
                  *rb_specs],                            # side tables
        out_specs=[smem_tile,                            # z'
                   ntd_spec, nwt_spec, _whole(*tile),    # count tables
                   any_spec,                             # F+tree (HBM)
                   *rb_specs],                           # side tables
        scratch_shapes=scratch,
    )
    shape = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[shape(toks[4]), shape(n_td), shape(n_wt), shape(n_t),
                   jax.ShapeDtypeStruct((2 * T,), F32),
                   *([shape(topics)] * 2 if sparse else [])],
        compiler_params=(pltpu.CompilerParams(vmem_limit_bytes=vmem_limit)
                         if vmem_limit else None),
        # doc-tiled: n_td's HBM input is its output, paged in place
        input_output_aliases={len(prefetch) + 6: 1} if docs else {},
        interpret=interpret,
        name=name,
    )(*prefetch, *toks, n_td, n_wt, n_t,
      *((topics, counts) if sparse else ()))
    z, n_td, n_wt, n_t, tree = out[:5]
    flat = lambda a: a.reshape(a.shape[:-2] + (T,))
    return (z.reshape(tokens[4].shape), flat(n_td), flat(n_wt), flat(n_t),
            _heap(tree, T)) + tuple(out[5:])


_STATIC = ("alpha", "beta", "beta_bar", "n_blk", "r_cap", "interpret",
           "vmem_limit", "name")


def _first_last_1d(pre):
    t = pl.program_id(0)
    return t == 0, t == pl.num_programs(0) - 1


def _ragged_cell_start(cot):
    t = pl.program_id(0)
    return (t == 0) | (cot[t] != cot[jnp.maximum(t - 1, 0)])


def _slab_pos_1d(dto):
    """(first, last, slab, previous slab) on a flat tile grid."""
    t = pl.program_id(0)
    return (t == 0, t == pl.num_programs(0) - 1, dto[t],
            dto[jnp.maximum(t - 1, 0)])


def _slab_pos_cells(dto):
    """(first, last, slab, previous slab) on a ``(k, tiles)`` grid; the
    previous step in raster order is the last tile of the previous cell
    when ``t == 0`` (unused garbage at the very first step)."""
    c, t = pl.program_id(0), pl.program_id(1)
    n_c, n_t_g = pl.num_programs(0), pl.num_programs(1)
    pc = jnp.where(t == 0, jnp.maximum(c - 1, 0), c)
    pt = jnp.where(t == 0, n_t_g - 1, t - 1)
    return ((c == 0) & (t == 0), (c == n_c - 1) & (t == n_t_g - 1),
            dto[c, t], dto[pc, pt])


def _first_last_cells(pre):
    c, t = pl.program_id(0), pl.program_id(1)
    return ((c == 0) & (t == 0),
            (c == pl.num_programs(0) - 1) & (t == pl.num_programs(1) - 1))


@functools.partial(jax.jit, static_argnames=_STATIC)
def fused_sweep_pallas(tok_doc: jax.Array, tok_wrd: jax.Array,
                       tok_valid: jax.Array, tok_bound: jax.Array,
                       z: jax.Array, u: jax.Array,
                       n_td: jax.Array, n_wt: jax.Array, n_t: jax.Array,
                       topics: jax.Array | None = None,
                       counts: jax.Array | None = None, *,
                       alpha: float, beta: float, beta_bar: float,
                       r_cap: int = 0, n_blk: int = N_BLK,
                       vmem_limit: int = 0, interpret: bool,
                       name: str = "fused_sweep"):
    """One fused F+LDA sweep over a padded token stream.

    Shapes: tok_* / z / u are (N,) with N % n_blk == 0; n_td (I, T) i32;
    n_wt (J, T) i32; n_t (T,) i32; T a power of two.  Returns
    (z', n_td', n_wt', n_t', F) with F the final F+tree (2T,) f32.

    ``r_cap`` (static; 0 → T) is the compacted r-vector capacity.  Passing
    ``topics``/``counts`` side tables ((I, r_cap) i32 each) selects sparse
    r-mode: they are maintained in VMEM and returned appended — a 7-tuple.
    ``vmem_limit`` (bytes, 0 = the compiler's default scoped limit) is
    passed to Mosaic.
    """
    return _fused_call(
        (), (tok_doc, tok_wrd, tok_valid, tok_bound, z, u), n_td, n_wt,
        n_t, topics, counts, grid=(tok_doc.shape[0] // n_blk,),
        tok_index=lambda b: (b,), nwt_index=None,
        grid_pos=lambda pre: _first_last_1d(pre) + (None, None),
        cell_start_of=lambda pre: None, doc_rows=0, alpha=alpha, beta=beta,
        beta_bar=beta_bar, r_cap=r_cap, n_blk=n_blk, vmem_limit=vmem_limit,
        interpret=interpret, name=name)


@functools.partial(jax.jit, static_argnames=_STATIC)
def fused_sweep_cells_pallas(tok_doc: jax.Array, tok_wrd: jax.Array,
                             tok_valid: jax.Array, tok_bound: jax.Array,
                             z: jax.Array, u: jax.Array,
                             n_td: jax.Array, n_wt: jax.Array,
                             n_t: jax.Array,
                             topics: jax.Array | None = None,
                             counts: jax.Array | None = None, *,
                             alpha: float, beta: float, beta_bar: float,
                             r_cap: int = 0, n_blk: int = N_BLK,
                             vmem_limit: int = 0, interpret: bool,
                             name: str = "fused_sweep_cells"):
    """One fused F+LDA sweep over a batch of k cells (a nomad block queue).

    Shapes: tok_* / z / u are (k, L) with L % n_blk == 0; n_td (I, T) i32
    shared across cells; n_wt (k, J, T) i32, one word-topic block per cell
    (``tok_wrd`` is block-local); n_t (T,) i32.  Cells are swept in order
    c = 0..k-1 with the exact chain carried through ``n_td``/``n_t``/F;
    returns (z', n_td', n_wt', n_t', F), plus the ``(topics, counts)``
    side tables appended when they are passed (sparse r-mode).
    """
    k, L = tok_doc.shape
    tiles = L // n_blk
    return _fused_call(
        (), (tok_doc, tok_wrd, tok_valid, tok_bound, z, u), n_td, n_wt,
        n_t, topics, counts, grid=(k, tiles),
        tok_index=lambda c, t: (c * tiles + t,),
        nwt_index=lambda c, t: c,
        grid_pos=lambda pre: _first_last_cells(pre) + (None, None),
        cell_start_of=lambda pre: pl.program_id(1) == 0, doc_rows=0,
        alpha=alpha, beta=beta, beta_bar=beta_bar, r_cap=r_cap,
        n_blk=n_blk, vmem_limit=vmem_limit, interpret=interpret, name=name)


@functools.partial(jax.jit, static_argnames=_STATIC)
def fused_sweep_ragged_pallas(cell_of_tile: jax.Array,
                              tok_doc: jax.Array, tok_wrd: jax.Array,
                              tok_valid: jax.Array, tok_bound: jax.Array,
                              z: jax.Array, u: jax.Array,
                              n_td: jax.Array, n_wt: jax.Array,
                              n_t: jax.Array,
                              topics: jax.Array | None = None,
                              counts: jax.Array | None = None, *,
                              alpha: float, beta: float, beta_bar: float,
                              r_cap: int = 0, n_blk: int,
                              vmem_limit: int = 0, interpret: bool,
                              name: str = "fused_sweep_ragged"):
    """One fused F+LDA sweep over a ragged cell stream (a nomad queue).

    Shapes: tok_* / z / u are (S,) with ``S = n_tiles·n_blk``;
    cell_of_tile (n_tiles,) i32, non-decreasing, values in [0, k);
    n_td (I, T) i32; n_wt (k, J, T) i32, one word-topic block per cell
    (``tok_wrd`` is block-local); n_t (T,) i32.  Tiles run in sequence
    with ``n_td``/``n_t``/F carried; tile ``t`` addresses word-topic
    block ``cell_of_tile[t]``, paged by scalar-prefetched index map.
    Returns (z', n_td', n_wt', n_t', F), plus the ``(topics, counts)``
    side tables appended when they are passed (sparse r-mode).
    """
    return _fused_call(
        (cell_of_tile,), (tok_doc, tok_wrd, tok_valid, tok_bound, z, u),
        n_td, n_wt, n_t, topics, counts,
        grid=(tok_doc.shape[0] // n_blk,), tok_index=lambda t, cot: (t,),
        nwt_index=lambda t, cot: cot[t],
        grid_pos=lambda pre: _first_last_1d(pre) + (None, None),
        cell_start_of=lambda pre: _ragged_cell_start(pre[0]), doc_rows=0,
        alpha=alpha, beta=beta, beta_bar=beta_bar, r_cap=r_cap,
        n_blk=n_blk, vmem_limit=vmem_limit, interpret=interpret, name=name)


@functools.partial(jax.jit, static_argnames=_STATIC + ("doc_rows",))
def fused_sweep_docs_pallas(doc_tile_of: jax.Array,
                            tok_doc: jax.Array, tok_wrd: jax.Array,
                            tok_valid: jax.Array, tok_bound: jax.Array,
                            z: jax.Array, u: jax.Array,
                            n_td: jax.Array, n_wt: jax.Array,
                            n_t: jax.Array,
                            topics: jax.Array | None = None,
                            counts: jax.Array | None = None, *,
                            alpha: float, beta: float, beta_bar: float,
                            doc_rows: int, r_cap: int = 0,
                            n_blk: int = N_BLK, vmem_limit: int = 0,
                            interpret: bool,
                            name: str = "fused_sweep_docs"):
    """Doc-tiled twin of :func:`fused_sweep_pallas`.

    ``doc_tile_of`` is the (n // n_blk,) per-tile slab map; ``n_td`` rows
    must be a whole number of ``doc_rows`` slabs (``ops`` pads) and every
    tile's tokens must address rows of its own slab only (guaranteed by
    ``build_layout(doc_tile=...)``'s grouped order).  The sparse-mode side
    tables stay whole-VMEM (they are a factor T/r_cap smaller than the
    table the slab paging evicts) and are not padded to slab multiples.
    """
    return _fused_call(
        (doc_tile_of,), (tok_doc, tok_wrd, tok_valid, tok_bound, z, u),
        n_td, n_wt, n_t, topics, counts,
        grid=(tok_doc.shape[0] // n_blk,), tok_index=lambda t, dto: (t,),
        nwt_index=None, grid_pos=lambda pre: _slab_pos_1d(pre[0]),
        cell_start_of=lambda pre: None, doc_rows=int(doc_rows),
        alpha=alpha, beta=beta, beta_bar=beta_bar, r_cap=r_cap,
        n_blk=n_blk, vmem_limit=vmem_limit, interpret=interpret, name=name)


@functools.partial(jax.jit, static_argnames=_STATIC + ("doc_rows",))
def fused_sweep_cells_docs_pallas(doc_tile_of: jax.Array,
                                  tok_doc: jax.Array, tok_wrd: jax.Array,
                                  tok_valid: jax.Array, tok_bound: jax.Array,
                                  z: jax.Array, u: jax.Array,
                                  n_td: jax.Array, n_wt: jax.Array,
                                  n_t: jax.Array,
                                  topics: jax.Array | None = None,
                                  counts: jax.Array | None = None, *,
                                  alpha: float, beta: float, beta_bar: float,
                                  doc_rows: int, r_cap: int = 0,
                                  n_blk: int = N_BLK, vmem_limit: int = 0,
                                  interpret: bool,
                                  name: str = "fused_sweep_cells_docs"):
    """Doc-tiled twin of :func:`fused_sweep_cells_pallas`; ``doc_tile_of``
    is the (k, L // n_blk) per-(cell, tile) slab map."""
    k, L = tok_doc.shape
    tiles = L // n_blk
    return _fused_call(
        (doc_tile_of,), (tok_doc, tok_wrd, tok_valid, tok_bound, z, u),
        n_td, n_wt, n_t, topics, counts, grid=(k, tiles),
        tok_index=lambda c, t, dto: (c * tiles + t,),
        nwt_index=lambda c, t, dto: c,
        grid_pos=lambda pre: _slab_pos_cells(pre[0]),
        cell_start_of=lambda pre: pl.program_id(1) == 0,
        doc_rows=int(doc_rows), alpha=alpha, beta=beta, beta_bar=beta_bar,
        r_cap=r_cap, n_blk=n_blk, vmem_limit=vmem_limit,
        interpret=interpret, name=name)


@functools.partial(jax.jit, static_argnames=_STATIC + ("doc_rows",))
def fused_sweep_ragged_docs_pallas(cell_of_tile: jax.Array,
                                   doc_tile_of: jax.Array,
                                   tok_doc: jax.Array, tok_wrd: jax.Array,
                                   tok_valid: jax.Array,
                                   tok_bound: jax.Array,
                                   z: jax.Array, u: jax.Array,
                                   n_td: jax.Array, n_wt: jax.Array,
                                   n_t: jax.Array,
                                   topics: jax.Array | None = None,
                                   counts: jax.Array | None = None, *,
                                   alpha: float, beta: float,
                                   beta_bar: float, doc_rows: int,
                                   r_cap: int = 0, n_blk: int,
                                   vmem_limit: int = 0, interpret: bool,
                                   name: str = "fused_sweep_ragged_docs"):
    """Doc-tiled twin of :func:`fused_sweep_ragged_pallas`: two
    scalar-prefetch maps drive the paging — ``cell_of_tile`` pages the
    word-topic block (BlockSpec window, visited once per cell) and
    ``doc_tile_of`` pages the doc-topic slab (explicit DMA, slabs
    recur)."""
    return _fused_call(
        (cell_of_tile, doc_tile_of),
        (tok_doc, tok_wrd, tok_valid, tok_bound, z, u),
        n_td, n_wt, n_t, topics, counts,
        grid=(tok_doc.shape[0] // n_blk,),
        tok_index=lambda t, cot, dto: (t,),
        nwt_index=lambda t, cot, dto: cot[t],
        grid_pos=lambda pre: _slab_pos_1d(pre[1]),
        cell_start_of=lambda pre: _ragged_cell_start(pre[0]),
        doc_rows=int(doc_rows), alpha=alpha, beta=beta, beta_bar=beta_bar,
        r_cap=r_cap, n_blk=n_blk, vmem_limit=vmem_limit,
        interpret=interpret, name=name)
