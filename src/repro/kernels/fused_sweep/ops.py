"""Public wrapper: padding, dtype plumbing and VMEM budgeting for the
fused F+LDA sweep kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.prefix import topic_tile
from repro.kernels.fused_sweep import rbucket
from repro.kernels.fused_sweep.fused_sweep import (
    N_BLK, fused_sweep_cells_docs_pallas, fused_sweep_cells_pallas,
    fused_sweep_docs_pallas, fused_sweep_pallas,
    fused_sweep_ragged_docs_pallas, fused_sweep_ragged_pallas)

# On-chip budgets of one TPU v5e TensorCore for a compiled call: 128 MiB
# of VMEM, of which a kernel may claim up to the budget below (the rest
# stays with the compiler), 16 MiB of it granted by default; 1 MiB of
# SMEM, shared by scalar-prefetch maps, token tiles and the F+tree.
VMEM_BUDGET_BYTES = 100 * 1024 * 1024
VMEM_SCOPED_DEFAULT_BYTES = 16 * 1024 * 1024
VMEM_HEADROOM_BYTES = 1024 * 1024
SMEM_BUDGET_BYTES = 960 * 1024


def _is_pow2(n: int) -> bool:
    return n >= 1 and (n & (n - 1)) == 0


def topic_row_bytes(T: int) -> int:
    """VMEM bytes of one 32-bit topic row: an ``(R, C)`` tile
    (:func:`repro.core.prefix.topic_tile`) padded to whole ``(8, 128)``
    vreg tiles — 4 KiB at T = 1024, where nothing is padded."""
    R, C = topic_tile(T)
    return 4 * (-(-R // 8) * 8) * (-(-C // 128) * 128)


def fused_vmem_bytes(I: int, J: int, T: int, n_blk: int = N_BLK,
                     doc_rows: int = 0, r_cap: int = 0) -> int:
    """Scoped VMEM bytes one compiled fused sweep call may need
    (DESIGN.md §7) — the worst case, in which XLA leaves every table in
    HBM; XLA may instead pin a whole small table in VMEM, outside the
    kernel's scoped allocation, and the kernel then needs less.

    Whole-shard mode (``doc_rows=0``) keeps the ``(I, T)`` doc-topic table
    in VMEM twice (the input block and the output accumulator; their
    index maps are constant, so neither is double-buffered); doc-tiled
    mode keeps a single ``(doc_rows, T)`` scratch slab and leaves the
    table in HBM.  The ``(J, T)`` word-topic block is paged per cell, so
    Pallas double-buffers it on the way in and on the way out: four
    copies.  ``r_cap > 0`` (sparse r-mode) adds the two ``(I, r_cap)`` i32
    side tables, each in+out whole-VMEM (doc-tiled twins included — the
    tables are never slabbed).  ``n_t``, the leaf mirror, the F+tree's
    two-row DMA stage and the compiler's own rows come to eighteen topic
    rows.  Token tiles live in SMEM (:func:`fused_smem_bytes`), so
    ``n_blk`` does not enter.
    """
    del n_blk
    row = topic_row_bytes(T)
    ntd = doc_rows * row if doc_rows > 0 else 2 * I * row
    rb = 4 * 4 * I * r_cap if r_cap > 0 else 0
    return ntd + rb + 4 * J * row + 18 * row


def fused_smem_bytes(n_tiles: int, n_blk: int, T: int,
                     n_maps: int) -> int:
    """SMEM bytes of one compiled fused sweep call: ``n_maps``
    scalar-prefetched per-tile maps (cell and/or slab), the seven token
    tiles double-buffered, and the F+tree — ``T − 1`` internal nodes and
    ``T`` leaves, 8·T bytes: 8 KiB at T = 1024, 32 KiB at T = 4096, and
    past the 960 KiB budget on its own only from T = 2^17.  The compiler
    adds a few KiB of its own (map padding): at T = 4096 a call this
    model puts 16 KiB under the core's 1 MiB compiles and one at 1 MiB
    does not (``tests/test_tpu_compile.py``)."""
    return 4 * (n_maps * n_tiles + 2 * 7 * n_blk + 2 * T)


def _vmem_limit(need: int, smem: int, what: str) -> int:
    """The ``vmem_limit_bytes`` a compiled call needs (0: the compiler's
    default scoped limit suffices); raises past either on-chip budget."""
    if need > VMEM_BUDGET_BYTES:
        raise ValueError(
            f"{what} ({need / 2**20:.1f} MiB) exceeds the VMEM budget "
            f"({VMEM_BUDGET_BYTES / 2**20:.0f} MiB); shard docs/vocab into "
            f"smaller nomad cells, tile the doc axis (build_layout "
            f"doc_tile) or use inner_mode='scan'")
    if smem > SMEM_BUDGET_BYTES:
        raise ValueError(
            f"{what} ({smem / 2**10:.0f} KiB of tile maps and token tiles) "
            f"exceeds the SMEM budget; use larger tiles or split the "
            f"stream into more calls")
    need += VMEM_HEADROOM_BYTES
    return need if need > VMEM_SCOPED_DEFAULT_BYTES else 0


def _resolve_rmode(r_mode: str, r_cap, T: int):
    """Validate ``r_mode``/``r_cap`` → (sparse, cap)."""
    if r_mode not in ("dense", "sparse"):
        raise ValueError(f"r_mode must be 'dense' or 'sparse', got {r_mode!r}")
    cap = T if r_cap is None else int(r_cap)
    if not 1 <= cap <= T:
        raise ValueError(f"r_cap must be in [1, T={T}], got {cap}")
    return r_mode == "sparse", cap


def _side_tables(sparse, topics, counts, n_td, cap):
    """Auto-build (or cast) the sparse-mode side tables; (None, None) in
    dense mode."""
    if not sparse:
        if topics is not None or counts is not None:
            raise ValueError("topics/counts side tables passed with "
                             "r_mode='dense'")
        return None, None
    if topics is None:
        return rbucket.build_side_table(n_td.astype(jnp.int32), cap)
    return topics.astype(jnp.int32), counts.astype(jnp.int32)


def _check_doc_args(doc_tile_of, doc_rows: int, shape) -> None:
    if (doc_tile_of is None) != (doc_rows <= 0):
        raise ValueError(
            "doc tiling needs both doc_tile_of and doc_rows > 0 "
            f"(got doc_rows={doc_rows}, "
            f"doc_tile_of={'set' if doc_tile_of is not None else None})")
    if doc_tile_of is not None and tuple(doc_tile_of.shape) != tuple(shape):
        raise ValueError(
            f"doc_tile_of shape {tuple(doc_tile_of.shape)} does not match "
            f"the {tuple(shape)} token-tile grid")


def _pad_doc_slabs(n_td, doc_rows: int):
    """Pad the doc-topic table to a whole number of ``doc_rows`` slabs so
    slab DMAs never run off the end; the pad rows are untouched (no token
    addresses them) and are stripped on return."""
    I = n_td.shape[0]
    pad = -I % doc_rows
    if pad:
        n_td = jnp.pad(n_td, ((0, pad), (0, 0)))
    return n_td, I


def _kernel_name(base: str, docs: bool, cell_start: int, num_cells: int,
                 k_total: int) -> str:
    """The ``pallas_call`` name a profile shows: ``base``, ``_docs`` for
    the doc-paged twin, and ``_h0``/``_h1`` where the call sweeps the
    first or the second half-queue of a pipelined ring round."""
    name = base + ("_docs" if docs else "")
    if (cell_start, num_cells) == (0, k_total):
        return name
    return name + ("_h0" if cell_start == 0 else "_h1")


def default_interpret() -> bool:
    """Pallas interpret-mode default: compiled on TPU, interpreted elsewhere.

    The kernels target the TPU memory hierarchy; on CPU/GPU backends the
    interpreter is the only correct way to run them.
    """
    return jax.default_backend() != "tpu"


def fused_sweep_tokens(tok_doc: jax.Array, tok_wrd: jax.Array,
                       tok_valid: jax.Array, tok_bound: jax.Array,
                       z: jax.Array, u: jax.Array,
                       n_td: jax.Array, n_wt: jax.Array, n_t: jax.Array, *,
                       alpha: float, beta: float, beta_bar: float,
                       doc_tile_of: jax.Array | None = None,
                       doc_rows: int = 0,
                       r_mode: str = "dense", r_cap: int | None = None,
                       topics: jax.Array | None = None,
                       counts: jax.Array | None = None,
                       n_blk: int = N_BLK, interpret: bool | None = None):
    """Fused word-by-word F+LDA sweep over an arbitrary-length token stream.

    Pads the stream to a multiple of ``n_blk`` with masked no-op tokens,
    runs the single-``pallas_call`` kernel, and unpads.  Returns
    ``(z', n_td', n_wt', n_t', F)`` where ``F`` is the final F+tree.

    ``doc_tile_of``/``doc_rows`` switch to the doc-tiled kernel: the
    stream must already be a whole number of ``n_blk`` tiles, each tile
    addressing doc rows of slab ``doc_tile_of[tile]`` only (the
    ``build_layout(doc_tile=...)`` grouped order); ``n_td`` stays in HBM
    and only one ``(doc_rows, T)`` slab is VMEM-resident.

    ``r_mode="sparse"`` maintains the per-doc ``(topics, counts)`` side
    tables ((I, r_cap) i32, built from ``n_td`` when not passed) instead
    of recomputing the compacted r-vector per token; the tables are
    returned appended — a 7-tuple.  ``r_cap`` defaults to ``T`` and is
    chain-affecting (see :mod:`repro.kernels.fused_sweep.rbucket`).
    """
    I, T = n_td.shape
    J = n_wt.shape[0]
    if not _is_pow2(T):
        raise ValueError(f"fused sweep needs a power-of-two T, got {T}")
    sparse, cap = _resolve_rmode(r_mode, r_cap, T)
    topics, counts = _side_tables(sparse, topics, counts, n_td, cap)
    interpret = default_interpret() if interpret is None else bool(interpret)
    n = tok_doc.shape[0]
    if n == 0:
        out = (z, n_td, n_wt, n_t, jnp.zeros((2 * T,), jnp.float32))
        return out + ((topics, counts) if sparse else ())
    docs = doc_tile_of is not None
    if docs and n % n_blk != 0:
        raise ValueError(
            f"doc-tiled stream length {n} is not a whole number of "
            f"{n_blk}-token tiles (the slab map is per tile)")
    _check_doc_args(doc_tile_of, doc_rows, (n // n_blk,) if docs else None)
    vmem_limit = 0 if interpret else _vmem_limit(
        fused_vmem_bytes(I, J, T, n_blk, doc_rows if docs else 0,
                         cap if sparse else 0),
        fused_smem_bytes(-(-n // n_blk), n_blk, T, int(docs)),
        "fused sweep state")

    n_pad = -n % n_blk
    pad_i = lambda a: jnp.pad(a.astype(jnp.int32), (0, n_pad))
    tok_doc, tok_wrd, z_p = pad_i(tok_doc), pad_i(tok_wrd), pad_i(z)
    tok_valid = jnp.pad(tok_valid.astype(jnp.int32), (0, n_pad))
    tok_bound = jnp.pad(tok_bound.astype(jnp.int32), (0, n_pad))
    u = jnp.pad(u.astype(jnp.float32), (0, n_pad))

    kw = dict(alpha=float(alpha), beta=float(beta),
              beta_bar=float(beta_bar), n_blk=n_blk,
              interpret=interpret, vmem_limit=vmem_limit)
    kw["r_cap"] = cap
    if sparse:
        kw.update(topics=topics, counts=counts)
    if docs:
        n_td_p, I = _pad_doc_slabs(n_td.astype(jnp.int32), doc_rows)
        out = fused_sweep_docs_pallas(
            doc_tile_of.astype(jnp.int32),
            tok_doc, tok_wrd, tok_valid, tok_bound, z_p, u,
            n_td_p, n_wt.astype(jnp.int32), n_t.astype(jnp.int32),
            doc_rows=int(doc_rows), **kw)
        z_out, n_td, n_wt, n_t, F = out[:5]
        return (z_out[:n], n_td[:I], n_wt, n_t, F) + tuple(out[5:])
    out = fused_sweep_pallas(
        tok_doc, tok_wrd, tok_valid, tok_bound, z_p, u,
        n_td.astype(jnp.int32), n_wt.astype(jnp.int32),
        n_t.astype(jnp.int32), **kw)
    z_out = out[0]
    return (z_out[:n],) + tuple(out[1:])


def fused_sweep_cells(tok_doc: jax.Array, tok_wrd: jax.Array,
                      tok_valid: jax.Array, tok_bound: jax.Array,
                      z: jax.Array, u: jax.Array,
                      n_td: jax.Array, n_wt: jax.Array, n_t: jax.Array, *,
                      alpha: float, beta: float, beta_bar: float,
                      cell_start: int = 0, num_cells: int | None = None,
                      doc_tile_of: jax.Array | None = None,
                      doc_rows: int = 0,
                      r_mode: str = "dense", r_cap: int | None = None,
                      topics: jax.Array | None = None,
                      counts: jax.Array | None = None,
                      n_blk: int = N_BLK, interpret: bool | None = None):
    """Fused F+LDA sweep over a batch of ``k`` padded cells in ONE kernel.

    This is the nomad hot path: ``tok_* / z / u`` are ``(k, L)`` — one row
    per cell of a worker's per-round block queue — and ``n_wt`` is
    ``(k, J, T)``, the queue's word-topic blocks.  The kernel's grid is
    ``(k, tiles)``: cells run in sequence, the word-topic block is paged per
    cell, and ``n_td``/``n_t``/the F+tree carry across cells, so the result
    is chain-identical to sweeping the cells one after another.

    ``cell_start``/``num_cells`` (static) restrict the call to the
    sub-queue ``[cell_start, cell_start + num_cells)``: the kernel grid
    shrinks to ``(num_cells, tiles)`` and the returned ``z'``/``n_wt'``
    cover only that range (leading dim ``num_cells``).  The pipelined ring
    (``core/nomad.py``, ``ring_mode="pipelined"``) uses this to sweep a
    half-queue per call; because every cell's first valid token is a word
    boundary (which rebuilds the F+tree from the incoming block), splitting
    a queue across calls is chain-identical to one whole-queue call.

    Pads ``L`` to a multiple of ``n_blk`` with masked no-op tokens and
    unpads.  ``doc_tile_of`` ((k, L // n_blk), with ``L`` already tiled)
    + ``doc_rows`` switch to the doc-tiled kernel (see
    :func:`fused_sweep_tokens`); the map is sliced along the cell range
    with the queue.  Returns ``(z', n_td', n_wt', n_t', F)``, plus the
    ``(topics, counts)`` side tables appended under ``r_mode="sparse"``
    (see :func:`fused_sweep_tokens`; the tables span the whole doc shard
    and are never sliced with the cell range).
    """
    I, T = n_td.shape
    k_total, J = n_wt.shape[0], n_wt.shape[1]
    if not _is_pow2(T):
        raise ValueError(f"fused sweep needs a power-of-two T, got {T}")
    sparse, cap = _resolve_rmode(r_mode, r_cap, T)
    topics, counts = _side_tables(sparse, topics, counts, n_td, cap)
    interpret = default_interpret() if interpret is None else bool(interpret)
    if tok_doc.shape[0] != k_total:
        raise ValueError(f"queue length mismatch: tokens have "
                         f"{tok_doc.shape[0]} cells, n_wt has {k_total} "
                         f"blocks")
    docs = doc_tile_of is not None
    if docs and tok_doc.shape[1] % n_blk != 0:
        raise ValueError(
            f"doc-tiled cell rows of {tok_doc.shape[1]} tokens are not a "
            f"whole number of {n_blk}-token tiles (the slab map is per "
            f"tile)")
    _check_doc_args(doc_tile_of, doc_rows,
                    (k_total, tok_doc.shape[1] // n_blk) if docs else None)
    cell_start = int(cell_start)
    k = k_total - cell_start if num_cells is None else int(num_cells)
    if cell_start < 0 or k < 0 or cell_start + k > k_total:
        raise ValueError(
            f"cell range [{cell_start}, {cell_start + k}) outside the "
            f"{k_total}-cell queue")
    if (cell_start, k) != (0, k_total):
        sub = lambda a: a[cell_start:cell_start + k]
        tok_doc, tok_wrd = sub(tok_doc), sub(tok_wrd)
        tok_valid, tok_bound = sub(tok_valid), sub(tok_bound)
        z, u, n_wt = sub(z), sub(u), sub(n_wt)
        if docs:
            doc_tile_of = sub(doc_tile_of)
    L = tok_doc.shape[1]
    if k == 0 or L == 0:
        out = (z, n_td, n_wt, n_t, jnp.zeros((2 * T,), jnp.float32))
        return out + ((topics, counts) if sparse else ())
    vmem_limit = 0 if interpret else _vmem_limit(
        fused_vmem_bytes(I, J, T, n_blk, doc_rows if docs else 0,
                         cap if sparse else 0),
        fused_smem_bytes(k * -(-L // n_blk), n_blk, T, int(docs)),
        "fused cell-batch state")

    n_pad = -L % n_blk
    pad_i = lambda a: jnp.pad(a.astype(jnp.int32), ((0, 0), (0, n_pad)))
    tok_doc, tok_wrd, z_p = pad_i(tok_doc), pad_i(tok_wrd), pad_i(z)
    tok_valid = jnp.pad(tok_valid.astype(jnp.int32), ((0, 0), (0, n_pad)))
    tok_bound = jnp.pad(tok_bound.astype(jnp.int32), ((0, 0), (0, n_pad)))
    u = jnp.pad(u.astype(jnp.float32), ((0, 0), (0, n_pad)))

    kw = dict(alpha=float(alpha), beta=float(beta),
              beta_bar=float(beta_bar), n_blk=n_blk,
              interpret=interpret, vmem_limit=vmem_limit,
              name=_kernel_name("fused_sweep_cells", docs, cell_start, k,
                                k_total))
    kw["r_cap"] = cap
    if sparse:
        kw.update(topics=topics, counts=counts)
    if docs:
        n_td_p, I = _pad_doc_slabs(n_td.astype(jnp.int32), doc_rows)
        out = fused_sweep_cells_docs_pallas(
            doc_tile_of.astype(jnp.int32),
            tok_doc, tok_wrd, tok_valid, tok_bound, z_p, u,
            n_td_p, n_wt.astype(jnp.int32), n_t.astype(jnp.int32),
            doc_rows=int(doc_rows), **kw)
        z_out, n_td, n_wt, n_t, F = out[:5]
        return (z_out[:, :L], n_td[:I], n_wt, n_t, F) + tuple(out[5:])
    out = fused_sweep_cells_pallas(
        tok_doc, tok_wrd, tok_valid, tok_bound, z_p, u,
        n_td.astype(jnp.int32), n_wt.astype(jnp.int32),
        n_t.astype(jnp.int32), **kw)
    return (out[0][:, :L],) + tuple(out[1:])


def fused_sweep_ragged(tok_doc: jax.Array, tok_wrd: jax.Array,
                       tok_valid: jax.Array, tok_bound: jax.Array,
                       z: jax.Array, u: jax.Array, cell_of_tile: jax.Array,
                       n_td: jax.Array, n_wt: jax.Array, n_t: jax.Array, *,
                       alpha: float, beta: float, beta_bar: float,
                       n_blk: int,
                       tile_start: int = 0, num_tiles: int | None = None,
                       cell_start: int = 0, num_cells: int | None = None,
                       doc_tile_of: jax.Array | None = None,
                       doc_rows: int = 0,
                       r_mode: str = "dense", r_cap: int | None = None,
                       topics: jax.Array | None = None,
                       counts: jax.Array | None = None,
                       interpret: bool | None = None):
    """Fused F+LDA sweep over a ragged cell stream (the nomad hot path).

    ``tok_* / z / u`` are flat ``(S,)`` streams — a worker's whole
    per-round queue with each cell padded only to the next ``n_blk``
    multiple (``NomadLayout`` ``kind="ragged"``); ``cell_of_tile`` is the
    non-decreasing ``(S // n_blk,)`` tile→cell map and ``n_wt`` is
    ``(k, J, T)``, the queue's word-topic blocks.  Grid is flat
    ``(num_tiles,)``; the map is scalar-prefetched so each tile pages the
    right block (see :func:`fused_sweep_ragged_pallas`).

    ``tile_start``/``num_tiles`` and ``cell_start``/``num_cells`` (static)
    restrict the call to a tile range and its matching cell range — the
    pipelined ring's half-queues at ``NomadLayout.tile_split``.  The tile
    range must cover every cell of ``[cell_start, cell_start+num_cells)``
    at least once (the layout builder gives every cell ≥ 1 tile) so each
    sliced ``n_wt`` block is paged through the kernel; returned
    ``z'``/``n_wt'`` cover only the requested ranges.  ``doc_tile_of``
    ((S // n_blk,), sliced with the tile range) + ``doc_rows`` switch to
    the doc-tiled kernel (see :func:`fused_sweep_tokens`).  Returns
    ``(z', n_td', n_wt', n_t', F)``, plus the ``(topics, counts)`` side
    tables appended under ``r_mode="sparse"`` (whole doc shard, never
    sliced with the tile/cell ranges).
    """
    I, T = n_td.shape
    k_total, J = n_wt.shape[0], n_wt.shape[1]
    if not _is_pow2(T):
        raise ValueError(f"fused sweep needs a power-of-two T, got {T}")
    sparse, cap = _resolve_rmode(r_mode, r_cap, T)
    topics, counts = _side_tables(sparse, topics, counts, n_td, cap)
    interpret = default_interpret() if interpret is None else bool(interpret)
    S = tok_doc.shape[0]
    if S % n_blk != 0 or cell_of_tile.shape[0] != S // n_blk:
        raise ValueError(
            f"ragged stream length {S} does not tile into "
            f"{cell_of_tile.shape[0]} tiles of {n_blk}")
    docs = doc_tile_of is not None
    _check_doc_args(doc_tile_of, doc_rows, (S // n_blk,) if docs else None)
    tile_start, cell_start = int(tile_start), int(cell_start)
    r_total = cell_of_tile.shape[0]
    nt_ = r_total - tile_start if num_tiles is None else int(num_tiles)
    nc = k_total - cell_start if num_cells is None else int(num_cells)
    if tile_start < 0 or nt_ < 0 or tile_start + nt_ > r_total:
        raise ValueError(
            f"tile range [{tile_start}, {tile_start + nt_}) outside the "
            f"{r_total}-tile stream")
    if cell_start < 0 or nc < 0 or cell_start + nc > k_total:
        raise ValueError(
            f"cell range [{cell_start}, {cell_start + nc}) outside the "
            f"{k_total}-cell queue")
    if (tile_start, nt_) != (0, r_total):
        lo, hi = tile_start * n_blk, (tile_start + nt_) * n_blk
        sub = lambda a: a[lo:hi]
        tok_doc, tok_wrd = sub(tok_doc), sub(tok_wrd)
        tok_valid, tok_bound = sub(tok_valid), sub(tok_bound)
        z, u = sub(z), sub(u)
    cot = cell_of_tile[tile_start:tile_start + nt_] - cell_start
    if docs:
        doc_tile_of = doc_tile_of[tile_start:tile_start + nt_]
    if (cell_start, nc) != (0, k_total):
        n_wt = n_wt[cell_start:cell_start + nc]
    if nt_ == 0 or nc == 0:
        out = (z, n_td, n_wt, n_t, jnp.zeros((2 * T,), jnp.float32))
        return out + ((topics, counts) if sparse else ())
    vmem_limit = 0 if interpret else _vmem_limit(
        fused_vmem_bytes(I, J, T, n_blk, doc_rows if docs else 0,
                         cap if sparse else 0),
        fused_smem_bytes(nt_, n_blk, T, 1 + int(docs)),
        "fused ragged-stream state")

    kw = dict(alpha=float(alpha), beta=float(beta),
              beta_bar=float(beta_bar), n_blk=n_blk,
              interpret=interpret, vmem_limit=vmem_limit,
              name=_kernel_name("fused_sweep_ragged", docs, cell_start, nc,
                                k_total))
    kw["r_cap"] = cap
    if sparse:
        kw.update(topics=topics, counts=counts)
    args = (tok_doc.astype(jnp.int32), tok_wrd.astype(jnp.int32),
            tok_valid.astype(jnp.int32), tok_bound.astype(jnp.int32),
            z.astype(jnp.int32), u.astype(jnp.float32))
    if docs:
        n_td_p, I = _pad_doc_slabs(n_td.astype(jnp.int32), doc_rows)
        out = fused_sweep_ragged_docs_pallas(
            cot.astype(jnp.int32), doc_tile_of.astype(jnp.int32), *args,
            n_td_p, n_wt.astype(jnp.int32), n_t.astype(jnp.int32),
            doc_rows=int(doc_rows), **kw)
        z_out, n_td, n_wt, n_t, F = out[:5]
        return (z_out, n_td[:I], n_wt, n_t, F) + tuple(out[5:])
    return tuple(fused_sweep_ragged_pallas(
        cot.astype(jnp.int32), *args,
        n_td.astype(jnp.int32), n_wt.astype(jnp.int32),
        n_t.astype(jnp.int32), **kw))
