"""Doc-axis tiling of the doc-topic shard (DESIGN.md §7).

Three layers under test:

* the **partition**: ``build_layout(doc_tile=...)`` groups each worker's
  local doc rows into slabs of ``doc_tile`` consecutive rows — every doc
  row lands in exactly one slab, slabs never exceed ``doc_tile`` rows
  (the last may be short when ``I_max`` is not a multiple), and the
  grouped token order guarantees every aligned token tile addresses one
  slab only (``doc_tile_of`` consistency);
* the **kernels**: the doc-tiled fused kernels (one ``(doc_tile, T)``
  slab VMEM-resident, explicit DMA paging) are bit-equal to the shared
  oracle and to whole-shard execution over the same token stream —
  including across slab switches and slab *revisits*;
* the **ceiling**: a doc-topic shard too large for the whole-shard VMEM
  budget is rejected by the untiled compiled-path guard but sweeps
  successfully (and exactly) with ``doc_tile`` set.

Property tests run under real ``hypothesis`` when installed, else the
deterministic shim in ``tests/conftest.py``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.data import synthetic
from repro.data.sharding import build_layout

i32 = lambda a: jnp.asarray(a, jnp.int32)


def _corpus(num_docs, vocab, seed):
    corpus, _, _ = synthetic.make_corpus(
        num_docs=num_docs, vocab_size=vocab, num_topics=8,
        mean_doc_len=12.0, seed=seed)
    return corpus


def _counts(lay, z_c, T):
    n_td = np.zeros((lay.I_max, T), np.int32)
    n_wt = np.zeros((lay.B, lay.J_max, T), np.int32)
    n_t = np.zeros((T,), np.int32)
    _, b_i, d_i, j_i = lay.token_coords()
    np.add.at(n_td, (d_i, z_c), 1)
    np.add.at(n_wt, (b_i, j_i, z_c), 1)
    np.add.at(n_t, z_c, 1)
    return i32(n_td), i32(n_wt), i32(n_t)


class TestDocTilePartition:
    @settings(max_examples=20, deadline=None)
    @given(W=st.integers(1, 4), mult=st.integers(1, 3),
           dt=st.integers(1, 9), num_docs=st.integers(8, 50),
           vocab=st.integers(24, 96), seed=st.integers(0, 6),
           kind=st.sampled_from(["dense", "ragged"]))
    def test_every_doc_row_in_exactly_one_slab(self, W, mult, dt, num_docs,
                                               vocab, seed, kind):
        corpus = _corpus(num_docs, vocab, seed)
        kw = dict(doc_blk=8) if kind == "dense" else {}
        lay = build_layout(corpus, n_workers=W, T=8, n_blocks=mult * W,
                           layout=kind, doc_tile=dt, **kw)
        # slab count covers I_max (non-multiple I_max ⇒ short last slab)
        assert lay.doc_tile == dt
        assert lay.n_doc_tiles == -(-lay.I_max // dt)
        groups = np.arange(lay.I_max) // dt
        # partition: every row in exactly one slab, none above doc_tile
        assert groups.min() == 0 and groups.max() == lay.n_doc_tiles - 1
        assert np.bincount(groups).max() <= dt
        # layout places every token exactly once
        assert int(lay.tok_valid.sum()) == corpus.num_tokens
        assert lay.word_map_mismatches() == 0

    @settings(max_examples=20, deadline=None)
    @given(W=st.integers(1, 4), mult=st.integers(1, 3),
           dt=st.integers(1, 9), num_docs=st.integers(8, 50),
           vocab=st.integers(24, 96), seed=st.integers(0, 6),
           kind=st.sampled_from(["dense", "ragged"]))
    def test_every_token_tile_touches_one_slab(self, W, mult, dt, num_docs,
                                               vocab, seed, kind):
        corpus = _corpus(num_docs, vocab, seed)
        kw = dict(doc_blk=8) if kind == "dense" else {}
        lay = build_layout(corpus, n_workers=W, T=8, n_blocks=mult * W,
                           layout=kind, doc_tile=dt, **kw)
        gran = lay.doc_blk if kind == "dense" else lay.tile
        assert gran == lay.doc_blk            # ragged records doc_blk=tile
        # the tile each token physically lands in must be mapped to the
        # token's own doc slab — the invariant the kernel paging rests on
        dto_flat = np.asarray(lay.doc_tile_of).reshape(-1)
        _, _, d, _ = lay.token_coords()
        np.testing.assert_array_equal(dto_flat[lay.canon_idx // gran],
                                      d // dt)
        assert dto_flat.min() >= 0
        assert dto_flat.max() < lay.n_doc_tiles
        # rows are whole tile multiples so the grid divides evenly
        assert lay.tok_doc.shape[-1] % gran == 0

    @settings(max_examples=15, deadline=None)
    @given(W=st.integers(1, 3), dt=st.integers(1, 6),
           num_docs=st.integers(8, 40), vocab=st.integers(24, 64),
           seed=st.integers(0, 6))
    def test_grouped_canonical_order_is_shared_and_complete(
            self, W, dt, num_docs, vocab, seed):
        """Dense and ragged grouped layouts carry the identical canonical
        token sequence (the cross-layout bit-equality precondition), and
        grouping only permutes the ungrouped sequence."""
        corpus = _corpus(num_docs, vocab, seed)
        dense = build_layout(corpus, n_workers=W, T=8, n_blocks=W,
                             doc_tile=dt, doc_blk=8)
        rag = build_layout(corpus, n_workers=W, T=8, n_blocks=W,
                           layout="ragged", doc_tile=dt)
        base = build_layout(corpus, n_workers=W, T=8, n_blocks=W)
        for a, b in ((dense, rag),):
            np.testing.assert_array_equal(a.extract_canonical(a.tok_gwrd),
                                          b.extract_canonical(b.tok_gwrd))
            np.testing.assert_array_equal(a.extract_canonical(a.tok_doc),
                                          b.extract_canonical(b.tok_doc))
        # same multiset of (global doc, global word) pairs as ungrouped
        def pairs(lay):
            gd, gw = lay.token_globals()
            return np.sort(gd.astype(np.int64) * corpus.num_words + gw)
        np.testing.assert_array_equal(pairs(dense), pairs(base))

    def test_single_doc_spans_many_tiles(self):
        """One document holding every token: a single slab spans the whole
        stream and tiling degenerates cleanly (doc_tile=1, I_max=1)."""
        corpus = _corpus(1, 24, 3)
        assert corpus.num_docs == 1
        for kind in ("dense", "ragged"):
            kw = dict(doc_blk=8) if kind == "dense" else dict(tile=8)
            lay = build_layout(corpus, n_workers=1, T=8, n_blocks=2,
                               layout=kind, doc_tile=1, **kw)
            assert lay.n_doc_tiles == 1
            assert int(lay.tok_valid.sum()) == corpus.num_tokens
            assert (np.asarray(lay.doc_tile_of) == 0).all()

    def test_doc_blk_without_doc_tile_rejected(self):
        corpus = _corpus(10, 32, 0)
        with pytest.raises(ValueError, match="doc_blk"):
            build_layout(corpus, n_workers=1, T=8, doc_blk=8)
        with pytest.raises(ValueError, match="doc_tile"):
            build_layout(corpus, n_workers=1, T=8, doc_tile=0)
        with pytest.raises(ValueError, match="tile"):
            build_layout(corpus, n_workers=1, T=8, layout="ragged",
                         doc_tile=2, doc_blk=8)


class TestDocTiledKernels:
    def _setup(self, T=16, B=4, dt=3, seed=11, tile=8):
        corpus = _corpus(18, 60, seed)
        lay = build_layout(corpus, n_workers=1, T=T, n_blocks=B,
                           layout="ragged", doc_tile=dt, tile=tile)
        rng = np.random.default_rng(seed)
        N = corpus.num_tokens
        z_c = rng.integers(0, T, N).astype(np.int32)
        u_c = rng.random(N).astype(np.float32)
        tok = tuple(i32(a[0, 0]) for a in (lay.tok_doc, lay.tok_wrd,
                                           lay.tok_valid, lay.tok_bound))
        z0 = i32(lay.place_canonical(z_c)[0, 0])
        u0 = jnp.asarray(lay.place_canonical(u_c)[0, 0])
        counts = _counts(lay, z_c, T)
        return lay, tok, z0, u0, counts

    def test_one_slab_switch_matches_ragged_ref(self):
        """The satellite's minimal case: a stream whose doc_tile_of map
        switches slab at least once (and revisits one) must be bit-equal
        to the whole-table oracle."""
        from repro.kernels.fused_sweep import fused_sweep_ragged
        from repro.kernels.fused_sweep.ref import fused_sweep_ragged_ref
        T = 16
        lay, tok, z0, u0, counts = self._setup(T=T, dt=3)
        cot = i32(lay.cell_of_tile[0, 0])
        dto = np.asarray(lay.doc_tile_of[0, 0])
        switches = int((dto[1:] != dto[:-1]).sum())
        assert switches >= 1                       # a slab switch happens
        assert len(np.unique(dto)) < switches + 1  # ... and a revisit too
        kw = dict(alpha=50.0 / T, beta=0.01, beta_bar=0.01 * 60)
        got = fused_sweep_ragged(*tok, z0, u0, cot, *counts,
                                 n_blk=lay.tile, doc_tile_of=i32(dto),
                                 doc_rows=lay.doc_tile, **kw)
        ref = fused_sweep_ragged_ref(*tok, z0, u0, cot, *counts,
                                     n_blk=lay.tile, **kw)
        for a, b in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_tile_split_halves_chain_with_paging(self):
        """The pipelined ring's half-stream calls, both paged: slabs are
        re-paged per call and the chain still matches one whole call."""
        from repro.data.sharding import half_queue_split
        from repro.kernels.fused_sweep import fused_sweep_ragged
        T = 16
        lay, tok, z0, u0, counts = self._setup(T=T, dt=3, seed=13)
        cot = i32(lay.cell_of_tile[0, 0])
        dto = i32(lay.doc_tile_of[0, 0])
        n_td, n_wt, n_t = counts
        kw = dict(alpha=50.0 / T, beta=0.01, beta_bar=0.01 * 60,
                  n_blk=lay.tile, doc_tile_of=dto, doc_rows=lay.doc_tile)
        whole = fused_sweep_ragged(*tok, z0, u0, cot, *counts, **kw)
        k0, r0 = half_queue_split(lay.k), lay.tile_split
        assert 0 < r0 < lay.n_tiles
        z_h0, n_td0, nwt0, n_t0, _ = fused_sweep_ragged(
            *tok, z0, u0, cot, *counts,
            tile_start=0, num_tiles=r0, cell_start=0, num_cells=k0, **kw)
        z_h1, n_td1, nwt1, n_t1, _ = fused_sweep_ragged(
            *tok, z0, u0, cot, n_td0, n_wt, n_t0,
            tile_start=r0, num_tiles=lay.n_tiles - r0,
            cell_start=k0, num_cells=lay.k - k0, **kw)
        got = (jnp.concatenate([z_h0, z_h1]), n_td1,
               jnp.concatenate([nwt0, nwt1]), n_t1)
        for a, b in zip(got, whole[:4]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_dense_cells_paged_matches_untiled(self):
        from repro.kernels.fused_sweep import fused_sweep_cells
        T = 16
        corpus = _corpus(18, 60, 17)
        lay = build_layout(corpus, n_workers=1, T=T, n_blocks=4,
                           doc_tile=4, doc_blk=16)
        rng = np.random.default_rng(17)
        z_c = rng.integers(0, T, corpus.num_tokens).astype(np.int32)
        u_c = rng.random(corpus.num_tokens).astype(np.float32)
        tok = tuple(i32(a[0]) for a in (lay.tok_doc, lay.tok_wrd,
                                        lay.tok_valid, lay.tok_bound))
        z0 = i32(lay.place_canonical(z_c)[0])
        u0 = jnp.asarray(lay.place_canonical(u_c)[0])
        counts = _counts(lay, z_c, T)
        kw = dict(alpha=50.0 / T, beta=0.01, beta_bar=0.01 * 60)
        base = fused_sweep_cells(*tok, z0, u0, *counts, **kw)
        paged = fused_sweep_cells(*tok, z0, u0, *counts,
                                  doc_tile_of=i32(lay.doc_tile_of[0]),
                                  doc_rows=lay.doc_tile,
                                  n_blk=lay.doc_blk, **kw)
        for a, b in zip(paged, base):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_doc_args_validated(self):
        from repro.kernels.fused_sweep import fused_sweep_ragged
        T = 16
        lay, tok, z0, u0, counts = self._setup(T=T)
        cot = i32(lay.cell_of_tile[0, 0])
        dto = i32(lay.doc_tile_of[0, 0])
        kw = dict(alpha=50.0 / T, beta=0.01, beta_bar=0.01 * 60,
                  n_blk=lay.tile)
        with pytest.raises(ValueError, match="doc tiling"):
            fused_sweep_ragged(*tok, z0, u0, cot, *counts,
                               doc_tile_of=dto, **kw)      # no doc_rows
        with pytest.raises(ValueError, match="doc tiling"):
            fused_sweep_ragged(*tok, z0, u0, cot, *counts,
                               doc_rows=3, **kw)           # no map
        with pytest.raises(ValueError, match="doc_tile_of shape"):
            fused_sweep_ragged(*tok, z0, u0, cot, *counts,
                               doc_tile_of=dto[:-1], doc_rows=3, **kw)


class TestVmemCeiling:
    """The acceptance case: a doc-topic shard past the whole-shard VMEM
    budget sweeps successfully — and exactly — once doc-tiled."""

    def _big_stream(self, I=2000, T=1024, J=8, n_blk=32, n_tiles=6,
                    doc_rows=256, seed=5):
        """A hand-built grouped token stream over a doc shard whose
        whole-table VMEM footprint exceeds the budget: each tile's tokens
        live in one (doc_rows, T) slab, slab ids revisit."""
        rng = np.random.default_rng(seed)
        dto = np.array([0, 1, 0, 2, 1, 0])[:n_tiles].astype(np.int32)
        tok_doc = np.concatenate([
            rng.integers(g * doc_rows, min((g + 1) * doc_rows, I), n_blk)
            for g in dto]).astype(np.int32)
        # word-major within each tile so boundary flags stay word-change
        wrd = rng.integers(0, J, n_tiles * n_blk).astype(np.int32)
        order = np.concatenate([np.arange(n_blk)[np.argsort(
            wrd[t * n_blk:(t + 1) * n_blk], kind="stable")] + t * n_blk
            for t in range(n_tiles)])
        tok_doc, wrd = tok_doc[order], wrd[order]
        bound = np.ones(n_tiles * n_blk, np.int32)
        bound[1:] = wrd[1:] != wrd[:-1]
        bound[0] = 1
        valid = np.ones(n_tiles * n_blk, np.int32)
        z = rng.integers(0, T, n_tiles * n_blk).astype(np.int32)
        u = rng.random(n_tiles * n_blk).astype(np.float32)
        n_td = np.zeros((I, T), np.int32)
        n_wt = np.zeros((J, T), np.int32)
        n_t = np.zeros((T,), np.int32)
        np.add.at(n_td, (tok_doc, z), 1)
        np.add.at(n_wt, (wrd, z), 1)
        np.add.at(n_t, z, 1)
        return (i32(tok_doc), i32(wrd), i32(valid), i32(bound), i32(z),
                jnp.asarray(u), i32(n_td), i32(n_wt), i32(n_t), i32(dto))

    def test_untiled_guard_rejects_then_tiled_sweeps(self):
        from repro.kernels.fused_sweep import (fused_sweep_tokens,
                                               fused_vmem_bytes)
        from repro.kernels.fused_sweep.ops import VMEM_BUDGET_BYTES
        from repro.kernels.fused_sweep.ref import fused_sweep_ref
        # a shard whose two whole-table copies outgrow the VMEM budget
        I, T, doc_rows, n_blk = 16384, 1024, 256, 32
        *args, dto = self._big_stream(I=I, T=T, doc_rows=doc_rows,
                                      n_blk=n_blk)
        kw = dict(alpha=50.0 / T, beta=0.01, beta_bar=0.01 * 8)
        # whole-shard estimate exceeds the budget → the compiled path
        # refuses (raised host-side, before any pallas_call)
        assert fused_vmem_bytes(I, 8, T, n_blk) > VMEM_BUDGET_BYTES
        with pytest.raises(ValueError, match="VMEM budget"):
            fused_sweep_tokens(*args, n_blk=n_blk, interpret=False, **kw)
        # the tiled estimate fits with an order of magnitude to spare
        assert fused_vmem_bytes(I, 8, T, n_blk, doc_rows) \
            < VMEM_BUDGET_BYTES // 8
        # ... and the tiled sweep runs the exact chain
        got = fused_sweep_tokens(*args, doc_tile_of=dto, doc_rows=doc_rows,
                                 n_blk=n_blk, **kw)
        ref = fused_sweep_ref(*args, **kw)
        for a, b in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestNomadDocTiling:
    def test_paged_equals_untiled_both_kinds(self):
        """W=1 in-process: paged fused execution ≡ whole-shard execution ≡
        scan, on dense and ragged grouped layouts."""
        from repro.core.nomad import NomadLDA
        T = 16
        corpus = _corpus(20, 50, 9)
        mesh = jax.make_mesh((1,), ("worker",))
        results = {}
        for kind in ("dense", "ragged"):
            lay = build_layout(
                corpus, n_workers=1, T=T, n_blocks=4, layout=kind,
                doc_tile=5, **(dict(doc_blk=16) if kind == "dense" else {}))
            for page, inner in ((None, "scan"), (None, "fused"),
                                (5, "fused")):
                lda = NomadLDA(mesh=mesh, ring_axes=("worker",),
                               layout=lay, alpha=50.0 / T, beta=0.01,
                               sync_mode="stoken", inner_mode=inner,
                               ring_mode="pipelined", doc_tile=page)
                arrays = lda.init_arrays(seed=0)
                for it in range(2):
                    arrays = lda.sweep(arrays, seed=it)
                results[kind, page, inner] = (
                    lay.extract_canonical(np.asarray(arrays["z"])),
                    *lda.global_counts(arrays))
        ref = results["dense", None, "scan"]
        for key, got in results.items():
            for a, b in zip(got, ref):
                np.testing.assert_array_equal(a, b, err_msg=str(key))

    def test_doc_tile_mismatch_rejected(self):
        from repro.core.nomad import NomadLDA
        corpus = _corpus(12, 32, 1)
        mesh = jax.make_mesh((1,), ("worker",))
        lay = build_layout(corpus, n_workers=1, T=8)
        with pytest.raises(ValueError, match="doc_tile"):
            NomadLDA(mesh=mesh, ring_axes=("worker",), layout=lay,
                     alpha=1.0, beta=0.01, doc_tile=4)


def test_padding_rows_stay_inside_the_slab():
    """Padding tokens carry doc 0, outside every slab but the first; the
    slab accessors must clamp them into the slab (on the chip an
    unclamped row addresses VMEM outside the buffer, which no interpreter
    reports), while valid rows map exactly."""
    from repro.kernels.fused_sweep.fused_sweep import _slab_accessors
    seen = []

    class Slab:
        def __getitem__(self, i):
            seen.append(int(i))

        def __setitem__(self, i, row):
            seen.append(int(i))

    load, store = _slab_accessors(Slab(), 3, 8)
    load(0)
    store(0, None)
    load(3 * 8 + 5)
    store(3 * 8 + 7, None)
    assert seen == [0, 0, 5, 7]
