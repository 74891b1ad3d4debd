"""Fused F+LDA sweep kernel: chain-exact parity + invariants.

The fused kernel must reproduce the ``lax.scan`` sweep bit-for-bit: same
``z``, same count tables, same final F+tree as its ``ref.py`` oracle —
across topic counts, non-power-of-two vocab/doc shapes, and token-tile
boundaries (small ``n_blk`` forces the chain to cross grid programs).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cgs
from repro.data import synthetic
from repro.kernels.fused_sweep import fused_sweep_tokens
from repro.kernels.fused_sweep.ref import fused_sweep_ref


def _setup(T, num_docs, vocab, mean_len, seed):
    corpus, _, _ = synthetic.make_corpus(
        num_docs=num_docs, vocab_size=vocab, num_topics=min(T, 32),
        mean_doc_len=mean_len, seed=seed)
    state = cgs.init_state(corpus, T, jax.random.key(seed))
    doc_ids = jnp.asarray(corpus.doc_ids)
    word_ids = jnp.asarray(corpus.word_ids)
    order = jnp.asarray(corpus.word_order())
    boundary = jnp.asarray(corpus.word_boundary())
    return corpus, state, doc_ids, word_ids, order, boundary


def _fused_inputs(state, doc_ids, word_ids, order, boundary):
    """Same uniforms the scan sweep derives from the chain key."""
    _, sweep_key = jax.random.split(state.key)
    u = jax.random.uniform(sweep_key, (order.shape[0],))
    valid = jnp.ones(order.shape[0], jnp.int32)
    return (doc_ids[order], word_ids[order], valid,
            boundary.astype(jnp.int32), state.z[order], u)


class TestChainExactParity:
    # Non-power-of-two I and J throughout; T must be a power of two.
    @pytest.mark.parametrize("T,num_docs,vocab,mean_len", [
        (4, 13, 37, 9.0),
        (64, 21, 150, 15.0),
        (1024, 11, 97, 10.0),
        (4096, 7, 61, 8.0),        # R = 32 rows: the tree's sublane rolls
    ])
    def test_fused_matches_scan_and_ref(self, T, num_docs, vocab, mean_len):
        corpus, state, doc_ids, word_ids, order, boundary = _setup(
            T, num_docs, vocab, mean_len, seed=T)
        alpha, beta = 50.0 / T, 0.01
        beta_bar = beta * corpus.num_words

        s_scan = cgs.sweep_fplda_word(state, doc_ids, word_ids, order,
                                      boundary, alpha, beta)
        s_fused = cgs.sweep_fplda_word(state, doc_ids, word_ids, order,
                                       boundary, alpha, beta,
                                       backend="fused")
        # identical chain: z and all three count tables bit-equal
        np.testing.assert_array_equal(np.asarray(s_scan.z),
                                      np.asarray(s_fused.z))
        np.testing.assert_array_equal(np.asarray(s_scan.n_td),
                                      np.asarray(s_fused.n_td))
        np.testing.assert_array_equal(np.asarray(s_scan.n_wt),
                                      np.asarray(s_fused.n_wt))
        np.testing.assert_array_equal(np.asarray(s_scan.n_t),
                                      np.asarray(s_fused.n_t))

        # kernel vs its oracle: z, counts AND the final F+tree, bit-equal
        tok = _fused_inputs(state, doc_ids, word_ids, order, boundary)
        kw = dict(alpha=alpha, beta=beta, beta_bar=beta_bar)
        z_k, ntd_k, nwt_k, nt_k, F_k = fused_sweep_tokens(
            *tok, state.n_td, state.n_wt, state.n_t, **kw)
        z_r, ntd_r, nwt_r, nt_r, F_r = fused_sweep_ref(
            *tok, state.n_td, state.n_wt, state.n_t, **kw)
        np.testing.assert_array_equal(np.asarray(z_k), np.asarray(z_r))
        np.testing.assert_array_equal(np.asarray(ntd_k), np.asarray(ntd_r))
        np.testing.assert_array_equal(np.asarray(nwt_k), np.asarray(nwt_r))
        np.testing.assert_array_equal(np.asarray(nt_k), np.asarray(nt_r))
        np.testing.assert_array_equal(np.asarray(F_k), np.asarray(F_r))

    @pytest.mark.parametrize("T", [16, 1024, 4096])
    def test_tree_is_build_after_each_boundary(self, T):
        """The F+tree a word boundary leaves is ``ftree.build`` of the
        word's q vector, bit for bit: the stream is cut just after each
        boundary, whose token is masked so that only the rebuild runs.
        Boundaries fall several to one 256-token tile and on a tile edge
        (token 256); β = 0 leaves every topic a word lacks at zero mass."""
        from repro.core import ftree
        n_blk, I, n_z = 256, 5, 6
        cuts = (3, 7, 100, 256, 300)
        runs = np.diff((0,) + cuts + (320,))
        rng = np.random.default_rng(T)
        wrd = np.repeat(np.arange(runs.size) % 4, runs).astype(np.int32)
        bound = np.zeros(wrd.size, np.int32)
        bound[[0, *cuts]] = 1
        doc = rng.integers(0, I, wrd.size).astype(np.int32)
        z = rng.integers(0, n_z, wrd.size).astype(np.int32)
        u = rng.random(wrd.size).astype(np.float32)
        n_td = np.zeros((I, T), np.int32)
        n_wt = np.zeros((4, T), np.int32)
        n_t = np.zeros((T,), np.int32)
        np.add.at(n_td, (doc, z), 1)
        np.add.at(n_wt, (wrd, z), 1)
        np.add.at(n_t, z, 1)
        kw = dict(alpha=50.0 / T, beta=0.0, beta_bar=1.0)
        tables = (jnp.asarray(n_td), jnp.asarray(n_wt), jnp.asarray(n_t))
        for c in cuts:
            valid = (np.arange(c + 1) < c).astype(np.int32)
            tok = [jnp.asarray(a[:c + 1]) for a in (doc, wrd)] + [
                jnp.asarray(valid), jnp.asarray(bound[:c + 1])] + [
                jnp.asarray(a[:c + 1]) for a in (z, u)]
            _, _, nwt_k, nt_k, F_k = fused_sweep_tokens(*tok, *tables,
                                                        n_blk=n_blk, **kw)
            q = ((nwt_k[wrd[c]].astype(jnp.float32) + kw["beta"])
                 / (nt_k.astype(jnp.float32) + kw["beta_bar"]))
            assert int(jnp.sum(q == 0)) >= T - n_z
            np.testing.assert_array_equal(np.asarray(F_k),
                                          np.asarray(ftree.build(q)))
            F_r = fused_sweep_ref(*tok, *tables, **kw)[4]
            np.testing.assert_array_equal(np.asarray(F_k), np.asarray(F_r))

    def test_chain_crosses_tile_boundaries(self):
        """n_blk smaller than N: state must persist across grid programs."""
        T = 16
        corpus, state, doc_ids, word_ids, order, boundary = _setup(
            T, 25, 60, 18.0, seed=7)
        alpha, beta = 50.0 / T, 0.01
        beta_bar = beta * corpus.num_words
        tok = _fused_inputs(state, doc_ids, word_ids, order, boundary)
        kw = dict(alpha=alpha, beta=beta, beta_bar=beta_bar)
        base = fused_sweep_tokens(*tok, state.n_td, state.n_wt, state.n_t,
                                  **kw)
        assert corpus.num_tokens > 32  # actually exercises >1 tile
        tiled = fused_sweep_tokens(*tok, state.n_td, state.n_wt, state.n_t,
                                   n_blk=32, **kw)
        for a, b in zip(base, tiled):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_invariants_after_fused_sweeps(self):
        T = 32
        corpus, state, doc_ids, word_ids, order, boundary = _setup(
            T, 30, 70, 14.0, seed=2)
        alpha, beta = 50.0 / T, 0.01
        for _ in range(2):
            state = cgs.sweep_fplda_word(state, doc_ids, word_ids, order,
                                         boundary, alpha, beta,
                                         backend="fused")
        v = cgs.check_invariants(state, corpus)
        assert all(x == 0 for x in v.values()), v
        assert int(state.n_t.sum()) == corpus.num_tokens


class TestMaskingAndEdges:
    def test_invalid_tokens_are_noops(self):
        """Interleaved valid=0 tokens must not perturb the chain."""
        T = 16
        corpus, state, doc_ids, word_ids, order, boundary = _setup(
            T, 12, 40, 10.0, seed=4)
        alpha, beta = 50.0 / T, 0.01
        beta_bar = beta * corpus.num_words
        tok_doc, tok_wrd, valid, bound, z0, u = _fused_inputs(
            state, doc_ids, word_ids, order, boundary)
        kw = dict(alpha=alpha, beta=beta, beta_bar=beta_bar)
        base = fused_sweep_tokens(tok_doc, tok_wrd, valid, bound, z0, u,
                                  state.n_td, state.n_wt, state.n_t, **kw)

        # duplicate every token, mark the copies invalid (boundary=0)
        n = tok_doc.shape[0]
        ileave = lambda a, pad: jnp.stack(
            [a, jnp.full_like(a, pad)], axis=1).reshape(2 * n)
        got = fused_sweep_tokens(
            ileave(tok_doc, 0), ileave(tok_wrd, 0), ileave(valid, 0),
            ileave(bound, 0), ileave(z0, 0), ileave(u, 0.5),
            state.n_td, state.n_wt, state.n_t, **kw)
        z2, ntd2, nwt2, nt2, F2 = got
        np.testing.assert_array_equal(np.asarray(z2[0::2]),
                                      np.asarray(base[0]))
        np.testing.assert_array_equal(np.asarray(ntd2), np.asarray(base[1]))
        np.testing.assert_array_equal(np.asarray(nwt2), np.asarray(base[2]))
        np.testing.assert_array_equal(np.asarray(nt2), np.asarray(base[3]))
        np.testing.assert_array_equal(np.asarray(F2), np.asarray(base[4]))

    def test_empty_stream(self):
        T = 8
        z, ntd, nwt, nt, F = fused_sweep_tokens(
            jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.int32),
            jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.int32),
            jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.float32),
            jnp.zeros((3, T), jnp.int32), jnp.zeros((5, T), jnp.int32),
            jnp.zeros((T,), jnp.int32),
            alpha=0.5, beta=0.01, beta_bar=0.05)
        assert z.shape == (0,)
        assert int(jnp.abs(ntd).sum()) == 0

    def test_non_pow2_T_rejected(self):
        T = 12
        with pytest.raises(ValueError, match="power-of-two"):
            fused_sweep_tokens(
                jnp.zeros((4,), jnp.int32), jnp.zeros((4,), jnp.int32),
                jnp.ones((4,), jnp.int32), jnp.ones((4,), jnp.int32),
                jnp.zeros((4,), jnp.int32), jnp.zeros((4,), jnp.float32),
                jnp.zeros((3, T), jnp.int32), jnp.zeros((5, T), jnp.int32),
                jnp.zeros((T,), jnp.int32),
                alpha=0.5, beta=0.01, beta_bar=0.05)


class TestCellBatchKernel:
    """One pallas_call over a whole k-cell block queue (nomad hot path)."""

    def _queue_setup(self, T=16, W=1, B=4, seed=11):
        from repro.data.sharding import build_layout
        corpus, _, _ = synthetic.make_corpus(
            num_docs=18, vocab_size=60, num_topics=8, mean_doc_len=12.0,
            seed=seed)
        lay = build_layout(corpus, n_workers=W, T=T, n_blocks=B)
        rng = np.random.default_rng(seed)
        z = np.where(lay.tok_valid,
                     rng.integers(0, T, lay.tok_valid.shape), 0)
        n_td = np.zeros((lay.I_max, T), np.int32)
        n_wt = np.zeros((B, lay.J_max, T), np.int32)
        n_t = np.zeros((T,), np.int32)
        w_i, b_i, l_i = np.nonzero(lay.tok_valid)
        zz = z[w_i, b_i, l_i]
        np.add.at(n_td, (lay.tok_doc[w_i, b_i, l_i], zz), 1)
        np.add.at(n_wt, (b_i, lay.tok_wrd[w_i, b_i, l_i], zz), 1)
        np.add.at(n_t, zz, 1)
        i32 = lambda a: jnp.asarray(a, jnp.int32)
        u = jnp.asarray(rng.random((B, lay.L)).astype(np.float32))
        return (i32(lay.tok_doc[0]), i32(lay.tok_wrd[0]),
                i32(lay.tok_valid[0]), i32(lay.tok_bound[0]),
                i32(z[0]), u, i32(n_td), i32(n_wt), i32(n_t))

    def test_cells_match_ref_and_sequential_calls(self):
        from repro.kernels.fused_sweep import (fused_sweep_cells,
                                               fused_sweep_tokens)
        from repro.kernels.fused_sweep.ref import fused_sweep_cells_ref
        T = 16
        args = self._queue_setup(T=T, B=4)
        kw = dict(alpha=50.0 / T, beta=0.01, beta_bar=0.01 * 60)

        got = fused_sweep_cells(*args, **kw)
        ref = fused_sweep_cells_ref(*args, **kw)
        for a, b in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

        # ... and one fused_sweep_tokens call per cell, chain carried by
        # hand, must be the identical chain the batched grid runs.
        tok_doc, tok_wrd, tok_valid, tok_bound, z, u, n_td, n_wt, n_t = args
        z_rows, nwt_rows = [], []
        for c in range(tok_doc.shape[0]):
            z_c, n_td, nwt_c, n_t, _ = fused_sweep_tokens(
                tok_doc[c], tok_wrd[c], tok_valid[c], tok_bound[c],
                z[c], u[c], n_td, n_wt[c], n_t, **kw)
            z_rows.append(z_c)
            nwt_rows.append(nwt_c)
        np.testing.assert_array_equal(np.asarray(got[0]),
                                      np.asarray(jnp.stack(z_rows)))
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(n_td))
        np.testing.assert_array_equal(np.asarray(got[2]),
                                      np.asarray(jnp.stack(nwt_rows)))
        np.testing.assert_array_equal(np.asarray(got[3]), np.asarray(n_t))

    def test_cells_cross_tile_boundaries(self):
        """Small n_blk: every cell spans several grid programs and the block
        page-in must still happen exactly once per cell."""
        from repro.kernels.fused_sweep import fused_sweep_cells
        T = 16
        args = self._queue_setup(T=T, B=2, seed=13)
        kw = dict(alpha=50.0 / T, beta=0.01, beta_bar=0.01 * 60)
        base = fused_sweep_cells(*args, **kw)
        tiled = fused_sweep_cells(*args, n_blk=8, **kw)
        for a, b in zip(base, tiled):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("split", [1, 2, 3])
    def test_sub_queue_calls_chain_like_whole_queue(self, split):
        """cell_start/num_cells (the pipelined ring's half-queues): sweeping
        [0, split) then [split, k) in two calls must reproduce the whole-
        queue call bit-for-bit — the boundary rebuild makes the split free."""
        from repro.kernels.fused_sweep import fused_sweep_cells
        T = 16
        args = self._queue_setup(T=T, B=4)
        kw = dict(alpha=50.0 / T, beta=0.01, beta_bar=0.01 * 60)
        whole = fused_sweep_cells(*args, **kw)

        tok_doc, tok_wrd, tok_valid, tok_bound, z, u, n_td, n_wt, n_t = args
        k = tok_doc.shape[0]
        z0, n_td0, nwt0, n_t0, _ = fused_sweep_cells(
            *args, cell_start=0, num_cells=split, **kw)
        assert z0.shape[0] == split and nwt0.shape[0] == split
        z1, n_td1, nwt1, n_t1, _ = fused_sweep_cells(
            tok_doc, tok_wrd, tok_valid, tok_bound, z, u,
            n_td0, n_wt, n_t0, cell_start=split, num_cells=k - split, **kw)
        got = (jnp.concatenate([z0, z1]), n_td1,
               jnp.concatenate([nwt0, nwt1]), n_t1)
        for a, b in zip(got, whole[:4]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_sub_queue_matches_ref_oracle(self):
        from repro.kernels.fused_sweep import fused_sweep_cells
        from repro.kernels.fused_sweep.ref import fused_sweep_cells_ref
        T = 16
        args = self._queue_setup(T=T, B=4, seed=17)
        kw = dict(alpha=50.0 / T, beta=0.01, beta_bar=0.01 * 60,
                  cell_start=1, num_cells=2)
        got = fused_sweep_cells(*args, **kw)
        ref = fused_sweep_cells_ref(*args, **kw)
        assert got[0].shape[0] == 2
        for a, b in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_bad_cell_range_rejected(self):
        from repro.kernels.fused_sweep import fused_sweep_cells
        T = 16
        args = self._queue_setup(T=T, B=4)
        kw = dict(alpha=50.0 / T, beta=0.01, beta_bar=0.01 * 60)
        for cell_start, num_cells in ((-1, 2), (3, 2), (0, 5)):
            with pytest.raises(ValueError, match="cell range"):
                fused_sweep_cells(*args, cell_start=cell_start,
                                  num_cells=num_cells, **kw)

    def test_queue_length_mismatch_rejected(self):
        from repro.kernels.fused_sweep import fused_sweep_cells
        T = 8
        zeros = lambda *s: jnp.zeros(s, jnp.int32)
        with pytest.raises(ValueError, match="queue length"):
            fused_sweep_cells(
                zeros(2, 4), zeros(2, 4), zeros(2, 4), zeros(2, 4),
                zeros(2, 4), jnp.zeros((2, 4), jnp.float32),
                zeros(3, T), zeros(3, 5, T), zeros(T),
                alpha=0.5, beta=0.01, beta_bar=0.05)

    def test_all_empty_cells_queue_is_noop(self):
        """A queue whose every cell is pure padding (valid=0 throughout,
        the layout's empty-cell convention): blocks still page through
        the kernel once each, and everything comes back bit-unchanged —
        the pad/ds no-op path doc tiling reuses."""
        from repro.kernels.fused_sweep import fused_sweep_cells
        from repro.kernels.fused_sweep.ref import fused_sweep_cells_ref
        T, k, L, J = 16, 3, 8, 5
        rng = np.random.default_rng(23)
        zeros = lambda *s: jnp.zeros(s, jnp.int32)
        n_td = jnp.asarray(rng.integers(0, 4, (7, T)), jnp.int32)
        n_wt = jnp.asarray(rng.integers(0, 4, (k, J, T)), jnp.int32)
        n_t = jnp.asarray(rng.integers(1, 40, (T,)), jnp.int32)
        args = (zeros(k, L), zeros(k, L), zeros(k, L), zeros(k, L),
                zeros(k, L), jnp.full((k, L), 0.5, jnp.float32),
                n_td, n_wt, n_t)
        kw = dict(alpha=0.5, beta=0.01, beta_bar=0.05)
        got = fused_sweep_cells(*args, **kw)
        ref = fused_sweep_cells_ref(*args, **kw)
        for a, b in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(n_td))
        np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(n_wt))
        np.testing.assert_array_equal(np.asarray(got[3]), np.asarray(n_t))

    def test_jmax_one_blocks(self):
        """J_max == 1: every block holds a single word, so every n_wt row
        access is the degenerate pl.ds(0, 1) and the whole cell is one
        word run (a single boundary rebuild) — the narrowest block page
        the kernel supports."""
        from repro.kernels.fused_sweep import fused_sweep_cells
        from repro.kernels.fused_sweep.ref import fused_sweep_cells_ref
        T, k, L, I, n_valid = 16, 3, 12, 5, 9
        rng = np.random.default_rng(29)
        tok_doc = rng.integers(0, I, (k, L)).astype(np.int32)
        tok_wrd = np.zeros((k, L), np.int32)           # one word per block
        tok_valid = np.zeros((k, L), np.int32)
        tok_valid[:, :n_valid] = 1
        tok_bound = np.zeros((k, L), np.int32)
        tok_bound[:, 0] = 1                            # single word run
        z = np.where(tok_valid, rng.integers(0, T, (k, L)), 0)
        u = rng.random((k, L)).astype(np.float32)
        n_td = np.zeros((I, T), np.int32)
        n_wt = np.zeros((k, 1, T), np.int32)
        n_t = np.zeros((T,), np.int32)
        c_i, l_i = np.nonzero(tok_valid)
        zz = z[c_i, l_i]
        np.add.at(n_td, (tok_doc[c_i, l_i], zz), 1)
        np.add.at(n_wt, (c_i, 0, zz), 1)
        np.add.at(n_t, zz, 1)
        i32 = lambda a: jnp.asarray(a, jnp.int32)
        args = (i32(tok_doc), i32(tok_wrd), i32(tok_valid), i32(tok_bound),
                i32(z), jnp.asarray(u), i32(n_td), i32(n_wt), i32(n_t))
        kw = dict(alpha=50.0 / T, beta=0.01, beta_bar=0.01 * k)
        got = fused_sweep_cells(*args, **kw)
        ref = fused_sweep_cells_ref(*args, **kw)
        for a, b in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # the sweep really did move counts (not vacuously empty)
        assert int(np.abs(np.asarray(got[2]) - np.asarray(n_wt)).sum()) > 0


class TestRaggedStreamKernel:
    """Flat-grid ragged stream (scalar-prefetch block paging): the same
    queue as TestCellBatchKernel, stored CSR-style — must run the chain
    bit-identically to the dense cell-batch grid and to its oracle."""

    def _stream_setup(self, T=16, B=4, seed=11, tile=None):
        from repro.data.sharding import build_layout
        corpus, _, _ = synthetic.make_corpus(
            num_docs=18, vocab_size=60, num_topics=8, mean_doc_len=12.0,
            seed=seed)
        dense = build_layout(corpus, n_workers=1, T=T, n_blocks=B)
        rag = build_layout(corpus, n_workers=1, T=T, n_blocks=B,
                           layout="ragged", tile=tile)
        rng = np.random.default_rng(seed)
        N = corpus.num_tokens
        z_c = rng.integers(0, T, N).astype(np.int32)
        u_c = rng.random(N).astype(np.float32)
        n_td = np.zeros((rag.I_max, T), np.int32)
        n_wt = np.zeros((B, rag.J_max, T), np.int32)
        n_t = np.zeros((T,), np.int32)
        _, b_i, d_i, j_i = rag.token_coords()
        np.add.at(n_td, (d_i, z_c), 1)
        np.add.at(n_wt, (b_i, j_i, z_c), 1)
        np.add.at(n_t, z_c, 1)
        i32 = lambda a: jnp.asarray(a, jnp.int32)

        def mk(lay):
            # W = 1: the dense queue is tok[0] (k, L); the ragged stream is
            # tok[0, 0] (S,) — chunk 0 holds all k cells.
            sel = (lambda a: a[0, 0]) if lay.kind == "ragged" \
                else (lambda a: a[0])
            return (i32(sel(lay.tok_doc)), i32(sel(lay.tok_wrd)),
                    i32(sel(lay.tok_valid)), i32(sel(lay.tok_bound)),
                    i32(sel(lay.place_canonical(z_c))),
                    jnp.asarray(sel(lay.place_canonical(u_c))))
        counts = (i32(n_td), i32(n_wt), i32(n_t))
        return dense, rag, mk(dense), mk(rag), counts

    def test_ragged_matches_ref_and_dense_cells(self):
        from repro.kernels.fused_sweep import (fused_sweep_cells,
                                               fused_sweep_ragged)
        from repro.kernels.fused_sweep.ref import fused_sweep_ragged_ref
        T = 16
        dense, rag, dense_tok, rag_tok, counts = self._stream_setup(T=T)
        kw = dict(alpha=50.0 / T, beta=0.01, beta_bar=0.01 * 60)
        cot = jnp.asarray(rag.cell_of_tile[0, 0])

        got = fused_sweep_ragged(*rag_tok, cot, *counts,
                                 n_blk=rag.tile, **kw)
        ref = fused_sweep_ragged_ref(*rag_tok, cot, *counts,
                                     n_blk=rag.tile, **kw)
        for a, b in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

        # vs the dense cell-batch kernel: per-token z and all tables equal
        dense_out = fused_sweep_cells(*dense_tok, *counts, **kw)
        np.testing.assert_array_equal(
            dense.extract_canonical(np.asarray(dense_out[0])[None, :]),
            rag.extract_canonical(np.asarray(got[0])[None, None, :]))
        for a, b in zip(dense_out[1:4], got[1:4]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_tile_split_chains_like_whole_stream(self):
        """The pipelined ring's halves: tiles [0, tile_split) over cells
        [0, k0) then the rest must reproduce the whole-stream call."""
        from repro.data.sharding import half_queue_split
        from repro.kernels.fused_sweep import fused_sweep_ragged
        T = 16
        _, rag, _, rag_tok, counts = self._stream_setup(T=T, seed=13)
        kw = dict(alpha=50.0 / T, beta=0.01, beta_bar=0.01 * 60)
        cot = jnp.asarray(rag.cell_of_tile[0, 0])
        n_td, n_wt, n_t = counts
        whole = fused_sweep_ragged(*rag_tok, cot, *counts,
                                   n_blk=rag.tile, **kw)
        k0, r0 = half_queue_split(rag.k), rag.tile_split
        assert 0 < r0 < rag.n_tiles
        z0, n_td0, nwt0, n_t0, _ = fused_sweep_ragged(
            *rag_tok, cot, *counts, n_blk=rag.tile,
            tile_start=0, num_tiles=r0, cell_start=0, num_cells=k0, **kw)
        assert nwt0.shape[0] == k0
        z1, n_td1, nwt1, n_t1, _ = fused_sweep_ragged(
            *rag_tok, cot, n_td0, n_wt, n_t0, n_blk=rag.tile,
            tile_start=r0, num_tiles=rag.n_tiles - r0,
            cell_start=k0, num_cells=rag.k - k0, **kw)
        got = (jnp.concatenate([z0, z1]), n_td1,
               jnp.concatenate([nwt0, nwt1]), n_t1)
        for a, b in zip(got, whole[:4]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_tiny_tile_crosses_cell_and_tile_boundaries(self):
        """tile=8 on word-sized cells: many grid steps per cell, page-in
        exactly at cell starts — still bit-equal to the oracle."""
        from repro.kernels.fused_sweep import fused_sweep_ragged
        from repro.kernels.fused_sweep.ref import fused_sweep_ragged_ref
        T = 16
        _, rag, _, rag_tok, counts = self._stream_setup(T=T, seed=17,
                                                        tile=8)
        assert rag.tile == 8 and rag.n_tiles > rag.k
        kw = dict(alpha=50.0 / T, beta=0.01, beta_bar=0.01 * 60)
        cot = jnp.asarray(rag.cell_of_tile[0, 0])
        got = fused_sweep_ragged(*rag_tok, cot, *counts, n_blk=8, **kw)
        ref = fused_sweep_ragged_ref(*rag_tok, cot, *counts, n_blk=8, **kw)
        for a, b in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_bad_ranges_rejected(self):
        from repro.kernels.fused_sweep import fused_sweep_ragged
        T = 16
        _, rag, _, rag_tok, counts = self._stream_setup(T=T)
        kw = dict(alpha=50.0 / T, beta=0.01, beta_bar=0.01 * 60,
                  n_blk=rag.tile)
        cot = jnp.asarray(rag.cell_of_tile[0, 0])
        with pytest.raises(ValueError, match="tile range"):
            fused_sweep_ragged(*rag_tok, cot, *counts,
                               tile_start=0, num_tiles=rag.n_tiles + 1, **kw)
        with pytest.raises(ValueError, match="cell range"):
            fused_sweep_ragged(*rag_tok, cot, *counts,
                               cell_start=rag.k, num_cells=1, **kw)
        with pytest.raises(ValueError, match="does not tile"):
            fused_sweep_ragged(*rag_tok, cot, *counts,
                               alpha=kw["alpha"], beta=kw["beta"],
                               beta_bar=kw["beta_bar"], n_blk=rag.tile + 1)


class TestSparseRBucket:
    """Doc-sparse r-bucket (DESIGN.md §7a): ``r_mode="sparse"`` walks the
    per-doc compacted side tables instead of recompacting the dense
    ``n_td`` row per token.  Both modes draw from the same capacity-``cap``
    compacted vector, so every kernel variant must stay bit-identical to
    its dense twin — and the returned side tables must equal a fresh
    compaction of the final ``n_td``."""

    @staticmethod
    def _tables_ok(topics, counts, n_td, cap):
        from repro.kernels.fused_sweep import rbucket
        ref_t, ref_c = rbucket.build_side_table(jnp.asarray(n_td), cap)
        return (bool(jnp.array_equal(topics, ref_t))
                and bool(jnp.array_equal(counts, ref_c)))

    @pytest.mark.parametrize("T", [16, 64])
    def test_sparse_tokens_match_dense_and_ref(self, T):
        corpus, state, doc_ids, word_ids, order, boundary = _setup(
            T, 15, 48, 11.0, seed=T + 1)
        kw = dict(alpha=50.0 / T, beta=0.01,
                  beta_bar=0.01 * corpus.num_words)
        tok = _fused_inputs(state, doc_ids, word_ids, order, boundary)
        dense = fused_sweep_tokens(*tok, state.n_td, state.n_wt,
                                   state.n_t, **kw)
        sparse = fused_sweep_tokens(*tok, state.n_td, state.n_wt,
                                    state.n_t, r_mode="sparse", **kw)
        assert len(sparse) == 7
        for a, b in zip(dense, sparse[:5]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        sref = fused_sweep_ref(*tok, state.n_td, state.n_wt, state.n_t,
                               r_mode="sparse", **kw)
        for a, b in zip(sparse, sref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert self._tables_ok(sparse[5], sparse[6], sparse[1], T)

    def test_sparse_cells_and_ragged_match_dense(self):
        from repro.data.sharding import build_layout
        from repro.kernels.fused_sweep import (fused_sweep_cells,
                                               fused_sweep_ragged)
        T = 16
        helper = TestCellBatchKernel()
        args = helper._queue_setup(T=T, B=4, seed=19)
        kw = dict(alpha=50.0 / T, beta=0.01, beta_bar=0.01 * 60)
        dense = fused_sweep_cells(*args, **kw)
        sparse = fused_sweep_cells(*args, r_mode="sparse", **kw)
        assert len(sparse) == 7
        for a, b in zip(dense, sparse[:5]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert self._tables_ok(sparse[5], sparse[6], sparse[1], T)

        rhelper = TestRaggedStreamKernel()
        _, rag, _, rag_tok, counts = rhelper._stream_setup(T=T, seed=19)
        cot = jnp.asarray(rag.cell_of_tile[0, 0])
        rdense = fused_sweep_ragged(*rag_tok, cot, *counts,
                                    n_blk=rag.tile, **kw)
        rsparse = fused_sweep_ragged(*rag_tok, cot, *counts,
                                     n_blk=rag.tile, r_mode="sparse", **kw)
        for a, b in zip(rdense, rsparse[:5]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert self._tables_ok(rsparse[5], rsparse[6], rsparse[1], T)

    def test_sub_T_cap_exact_when_valid(self):
        """A capacity below T is exact as long as no doc ever holds more
        than ``cap`` distinct topics mid-sweep; both modes share the cap,
        so the sparse run must still equal the dense run at the same cap."""
        T = 64
        corpus, state, doc_ids, word_ids, order, boundary = _setup(
            T, 15, 48, 6.0, seed=3)
        # distinct-topics-per-doc is bounded by doc length, +1 headroom
        # for the transient insert-before-remove inside a token update
        cap = min(T, int(np.bincount(np.asarray(corpus.doc_ids)).max()) + 1)
        assert cap < T
        kw = dict(alpha=50.0 / T, beta=0.01,
                  beta_bar=0.01 * corpus.num_words)
        tok = _fused_inputs(state, doc_ids, word_ids, order, boundary)
        dense = fused_sweep_tokens(*tok, state.n_td, state.n_wt,
                                   state.n_t, r_cap=cap, **kw)
        sparse = fused_sweep_tokens(*tok, state.n_td, state.n_wt,
                                    state.n_t, r_mode="sparse", r_cap=cap,
                                    **kw)
        for a, b in zip(dense, sparse[:5]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert self._tables_ok(sparse[5], sparse[6], sparse[1], cap)

    def test_bad_args_rejected(self):
        from repro.kernels.fused_sweep import fused_vmem_bytes
        T = 8
        zeros = lambda *s: jnp.zeros(s, jnp.int32)
        base = (zeros(4), zeros(4), jnp.ones((4,), jnp.int32),
                jnp.ones((4,), jnp.int32), zeros(4),
                jnp.zeros((4,), jnp.float32),
                zeros(3, T), zeros(5, T), zeros(T))
        kw = dict(alpha=0.5, beta=0.01, beta_bar=0.05)
        with pytest.raises(ValueError, match="r_mode"):
            fused_sweep_tokens(*base, r_mode="compact", **kw)
        with pytest.raises(ValueError, match="r_cap"):
            fused_sweep_tokens(*base, r_mode="sparse", r_cap=T + 1, **kw)
        with pytest.raises(ValueError, match="side tables"):
            fused_sweep_tokens(*base, topics=zeros(3, T),
                               counts=zeros(3, T), **kw)
        # VMEM model: sparse adds exactly the two (I, cap) i32 tables
        # (double-buffered), monotone in cap
        a = fused_vmem_bytes(100, 10, T, r_cap=4)
        b = fused_vmem_bytes(100, 10, T, r_cap=8)
        assert b > a > fused_vmem_bytes(100, 10, T)


class TestNomadFusedInnerMode:
    def test_single_device_ring_matches_scan(self):
        from repro.core.nomad import NomadLDA
        from repro.data.sharding import build_layout
        T = 16
        corpus, _, _ = synthetic.make_corpus(
            num_docs=20, vocab_size=50, num_topics=8, mean_doc_len=12.0,
            seed=9)
        layout = build_layout(corpus, n_workers=1, T=T)
        mesh = jax.make_mesh((1,), ("worker",))
        results = {}
        for mode in ("scan", "fused"):
            lda = NomadLDA(mesh=mesh, ring_axes=("worker",), layout=layout,
                           alpha=50.0 / T, beta=0.01, sync_mode="stoken",
                           inner_mode=mode)
            arrays = lda.init_arrays(seed=0)
            for it in range(2):
                arrays = lda.sweep(arrays, seed=it)
            results[mode] = (*lda.global_counts(arrays),
                             np.asarray(arrays["z"]))
        for a, b in zip(results["scan"], results["fused"]):
            np.testing.assert_array_equal(a, b)
