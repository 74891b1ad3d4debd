"""Nomad distributed LDA tests (paper §4).

Single-device ring (W=1, degenerate but exercises the full code path)
runs in-process; multi-device rings run in subprocesses with
XLA_FLAGS=--xla_force_host_platform_device_count so the main test process
keeps its single real device (per the dry-run isolation rule).
"""
import concurrent.futures
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core.nomad import NomadLDA
from repro.data import synthetic
from repro.data.sharding import build_layout, lpt_assign

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_module(module, *args, timeout=900):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", module, *map(str, args)],
        capture_output=True, text=True, env=env, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _run_check(n_dev, sync_mode, pods=1, inner_mode="scan", n_blocks=None,
               ring_mode="barrier", layout="dense"):
    return _run_module(
        "repro.launch.lda_dist_check", n_dev, sync_mode, pods, inner_mode,
        n_dev if n_blocks is None else n_blocks, ring_mode, layout)


class TestLayout:
    def test_lpt_balances_zipf(self):
        rng = np.random.default_rng(0)
        weights = (1e6 / np.arange(1, 2001) ** 1.1).astype(np.int64)
        assign = lpt_assign(weights, 8, balance=True)
        loads = np.bincount(assign, weights=weights, minlength=8)
        # LPT reaches the packing lower bound max(mean, heaviest item)
        lower = max(loads.mean(), weights.max())
        assert loads.max() <= lower * 1.01
        naive = lpt_assign(weights, 8, balance=False)
        loads_naive = np.bincount(naive, weights=weights, minlength=8)
        assert loads_naive.max() / loads_naive.mean() > 2.0  # skew is real

    def test_layout_covers_all_tokens(self):
        corpus, _, _ = synthetic.make_corpus(
            num_docs=50, vocab_size=128, num_topics=8, mean_doc_len=20.0,
            seed=1)
        lay = build_layout(corpus, n_workers=4, T=8)
        assert int(lay.tok_valid.sum()) == corpus.num_tokens
        # every token's global word id maps back through block/local index
        w, b, l = np.nonzero(lay.tok_valid)
        gw = lay.word_of_block[b, lay.tok_wrd[w, b, l]]
        np.testing.assert_array_equal(gw, lay.tok_gwrd[w, b, l])
        # word->block assignment is respected
        assert (lay.word_assign[gw] == b).all()

    def test_multiblock_layout_covers_all_tokens(self):
        """B = 3W: the queue geometry must still place every token exactly
        once, with the word→block map respected."""
        corpus, _, _ = synthetic.make_corpus(
            num_docs=50, vocab_size=128, num_topics=8, mean_doc_len=20.0,
            seed=1)
        lay = build_layout(corpus, n_workers=4, T=8, n_blocks=12)
        assert (lay.W, lay.B, lay.k) == (4, 12, 3)
        assert int(lay.tok_valid.sum()) == corpus.num_tokens
        w, b, l = np.nonzero(lay.tok_valid)
        gw = lay.word_of_block[b, lay.tok_wrd[w, b, l]]
        np.testing.assert_array_equal(gw, lay.tok_gwrd[w, b, l])
        assert (lay.word_assign[gw] == b).all()

    def test_more_blocks_smooth_round_imbalance(self):
        """The scaling knob must be free: a power-law vocabulary packed into
        B = 8W blocks round-balances exactly as well as B = W, because word
        chunks are LPT-packed at ring granularity first and only then split
        into the k per-queue blocks (hierarchical LPT)."""
        from repro.data.corpus import Corpus
        rng = np.random.default_rng(7)
        doc_ids = np.repeat(np.arange(200), 12)
        word_ids = np.minimum(rng.zipf(1.3, size=doc_ids.shape[0]), 500) - 1
        corpus = Corpus(doc_ids=doc_ids.astype(np.int32),
                        word_ids=word_ids.astype(np.int32),
                        num_docs=200, num_words=500)
        lay1 = build_layout(corpus, n_workers=4, T=8, n_blocks=4)
        lay8 = build_layout(corpus, n_workers=4, T=8, n_blocks=32)
        assert lay8.round_imbalance <= lay1.round_imbalance * 1.05, (
            lay1.round_imbalance, lay8.round_imbalance)

    def test_invalid_n_blocks_rejected(self):
        corpus, _, _ = synthetic.make_corpus(
            num_docs=20, vocab_size=64, num_topics=8, mean_doc_len=10.0,
            seed=3)
        for bad in (3, 6, 0):
            with pytest.raises(ValueError, match="multiple"):
                build_layout(corpus, n_workers=4, T=8, n_blocks=bad)

    def test_half_queue_split_points(self):
        from repro.data.sharding import half_queue_split
        assert half_queue_split(0) == 0
        assert half_queue_split(1) == 0          # degenerate: no overlap
        for k in range(2, 10):
            k0 = half_queue_split(k)
            assert 0 < k0 < k and k0 == k // 2

    def test_half_loads_balanced_on_zipf(self):
        """The pipelined split must produce load-matched half-queues even
        under power-law word skew: within each chunk the blocks are ordered
        (``_order_bins_for_halves``) so the halves differ by at most one
        block's load — the best any block-granular split can do."""
        from repro.data.corpus import Corpus
        rng = np.random.default_rng(11)
        doc_ids = np.repeat(np.arange(200), 12)
        word_ids = np.minimum(rng.zipf(1.3, size=doc_ids.shape[0]), 500) - 1
        corpus = Corpus(doc_ids=doc_ids.astype(np.int32),
                        word_ids=word_ids.astype(np.int32),
                        num_docs=200, num_words=500)
        lay = build_layout(corpus, n_workers=4, T=8, n_blocks=16)  # k = 4
        halves = lay.half_loads()                # (W_rounds, W, 2)
        W, k = lay.W, lay.k
        # the two halves together are exactly the round loads
        for r in range(W):
            for w in range(W):
                c = (w + r) % W
                assert halves[r, w].sum() == \
                    lay.cell_sizes[w, c * k:(c + 1) * k].sum()
        # at the granularity the split is enforced (global block loads),
        # the halves differ by at most the heaviest block of the chunk
        gaps = lay.half_balance_gaps()
        assert (gaps[:, 0] <= gaps[:, 1]).all(), gaps

    def test_boundaries_mark_distinct_words_per_cell(self):
        corpus, _, _ = synthetic.make_corpus(
            num_docs=30, vocab_size=64, num_topics=8, mean_doc_len=15.0,
            seed=2)
        lay = build_layout(corpus, n_workers=2, T=8)
        for w in range(lay.W):
            for b in range(lay.B):
                m = lay.tok_valid[w, b]
                words = lay.tok_gwrd[w, b][m]
                bounds = lay.tok_bound[w, b][m]
                assert bounds.sum() == len(np.unique(words))


class TestSingleDeviceRing:
    """W=1: the nomad machinery must reduce to serial F+LDA semantics,
    for any queue length k = B (the whole ring is one worker)."""

    @pytest.mark.parametrize("n_blocks,inner_mode,ring_mode,layout", [
        (1, "scan", "barrier", "dense"), (4, "scan", "barrier", "dense"),
        (4, "fused", "barrier", "dense"),
        (4, "vectorized", "barrier", "dense"),
        (1, "scan", "pipelined", "dense"), (4, "scan", "pipelined", "dense"),
        (4, "fused", "pipelined", "dense"),
        (1, "fused", "barrier", "ragged"), (4, "fused", "barrier", "ragged"),
        (4, "fused", "pipelined", "ragged"),
        (4, "scan", "pipelined", "ragged"),
        (4, "vectorized", "barrier", "ragged"),
    ])
    def test_invariants_and_ll(self, n_blocks, inner_mode, ring_mode,
                               layout):
        T = 8
        corpus, _, _ = synthetic.make_corpus(
            num_docs=60, vocab_size=128, num_topics=T, mean_doc_len=25.0,
            seed=4)
        mesh = jax.make_mesh((1,), ("worker",))
        lay = build_layout(corpus, n_workers=1, T=T, n_blocks=n_blocks,
                           layout=layout)
        lda = NomadLDA(mesh=mesh, ring_axes=("worker",), layout=lay,
                       alpha=50.0 / T, beta=0.01, inner_mode=inner_mode,
                       ring_mode=ring_mode)
        arrays = lda.init_arrays(seed=0)
        ll0 = lda.log_likelihood(arrays)
        for it in range(3):
            arrays = lda.sweep(arrays, seed=it)
        ll1 = lda.log_likelihood(arrays)
        assert ll1 > ll0

        n_td, n_wt, n_t = lda.global_counts(arrays)
        assert int(n_t.sum()) == corpus.num_tokens
        np.testing.assert_array_equal(n_td.sum(0), n_t)
        np.testing.assert_array_equal(n_wt.sum(0), n_t)

    def test_block_count_does_not_change_totals(self):
        """Same corpus under B=1 vs B=4 queues: different visit order (so a
        different chain), but identical exactness invariants and token mass
        per word — the block split must be invisible in the totals."""
        T = 8
        corpus, _, _ = synthetic.make_corpus(
            num_docs=40, vocab_size=96, num_topics=T, mean_doc_len=15.0,
            seed=6)
        mesh = jax.make_mesh((1,), ("worker",))
        per_word = {}
        for B in (1, 4):
            lay = build_layout(corpus, n_workers=1, T=T, n_blocks=B)
            lda = NomadLDA(mesh=mesh, ring_axes=("worker",), layout=lay,
                           alpha=50.0 / T, beta=0.01)
            arrays = lda.init_arrays(seed=0)
            arrays = lda.sweep(arrays, seed=0)
            _, n_wt, n_t = lda.global_counts(arrays)
            assert int(n_t.sum()) == corpus.num_tokens
            per_word[B] = n_wt.sum(1)
        np.testing.assert_array_equal(per_word[1], per_word[4])

    @pytest.mark.parametrize("inner_mode", ["scan", "fused", "vectorized"])
    def test_pipelined_is_bit_identical_to_barrier(self, inner_mode):
        """The tentpole invariant, in-process: the pipelined schedule only
        moves when the first half-queue's hop is issued — the per-token
        chain (z, all count tables) must be bit-equal to the barrier ring."""
        T = 8
        corpus, _, _ = synthetic.make_corpus(
            num_docs=40, vocab_size=96, num_topics=T, mean_doc_len=15.0,
            seed=12)
        mesh = jax.make_mesh((1,), ("worker",))
        lay = build_layout(corpus, n_workers=1, T=T, n_blocks=4)
        res = {}
        for ring_mode in ("barrier", "pipelined"):
            lda = NomadLDA(mesh=mesh, ring_axes=("worker",), layout=lay,
                           alpha=50.0 / T, beta=0.01, inner_mode=inner_mode,
                           ring_mode=ring_mode)
            arrays = lda.init_arrays(seed=0)
            for it in range(2):
                arrays = lda.sweep(arrays, seed=it)
            res[ring_mode] = arrays
        for name in ("z", "n_td", "n_wt", "n_t"):
            np.testing.assert_array_equal(
                np.asarray(res["barrier"][name]),
                np.asarray(res["pipelined"][name]))

    @pytest.mark.parametrize("inner_mode", ["scan", "fused", "vectorized"])
    def test_ragged_is_bit_identical_to_dense(self, inner_mode):
        """The ragged tentpole invariant, in-process: the tile-stream
        geometry changes only where tokens sit, never the chain — the
        canonical per-token z and every count table must be bit-equal to
        the dense run, in both ring modes."""
        T = 8
        corpus, _, _ = synthetic.make_corpus(
            num_docs=40, vocab_size=96, num_topics=T, mean_doc_len=15.0,
            seed=12)
        mesh = jax.make_mesh((1,), ("worker",))
        for ring_mode in ("barrier", "pipelined"):
            res = {}
            for kind in ("dense", "ragged"):
                lay = build_layout(corpus, n_workers=1, T=T, n_blocks=4,
                                   layout=kind)
                lda = NomadLDA(mesh=mesh, ring_axes=("worker",), layout=lay,
                               alpha=50.0 / T, beta=0.01,
                               inner_mode=inner_mode, ring_mode=ring_mode)
                arrays = lda.init_arrays(seed=0)
                for it in range(2):
                    arrays = lda.sweep(arrays, seed=it)
                res[kind] = (lay.extract_canonical(np.asarray(arrays["z"])),
                             *lda.global_counts(arrays))
            for a, b in zip(res["dense"], res["ragged"]):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_ragged_needs_tile_geometry(self):
        """nomad_sweep_fn must reject a ragged request without the
        layout's static tile geometry."""
        from repro.core.nomad import nomad_sweep_fn
        mesh = jax.make_mesh((1,), ("worker",))
        with pytest.raises(ValueError, match="tile geometry"):
            nomad_sweep_fn(mesh, ("worker",), B=4, T=8, alpha=1.0,
                           beta=0.01, beta_bar=0.64, layout_kind="ragged")

    def test_mismatched_layout_rejected(self):
        corpus, _, _ = synthetic.make_corpus(
            num_docs=20, vocab_size=64, num_topics=8, mean_doc_len=10.0,
            seed=8)
        mesh = jax.make_mesh((1,), ("worker",))
        lay = build_layout(corpus, n_workers=2, T=8)
        with pytest.raises(ValueError, match="ring has"):
            NomadLDA(mesh=mesh, ring_axes=("worker",), layout=lay,
                     alpha=1.0, beta=0.01)

    def test_invalid_ring_mode_rejected(self):
        corpus, _, _ = synthetic.make_corpus(
            num_docs=20, vocab_size=64, num_topics=8, mean_doc_len=10.0,
            seed=8)
        mesh = jax.make_mesh((1,), ("worker",))
        lay = build_layout(corpus, n_workers=1, T=8)
        with pytest.raises(ValueError, match="overlapped"):
            NomadLDA(mesh=mesh, ring_axes=("worker",), layout=lay,
                     alpha=1.0, beta=0.01, ring_mode="overlapped")


@pytest.mark.slow
class TestMultiDevice:
    @pytest.mark.parametrize("sync_mode", ["stoken", "stale", "allreduce"])
    def test_8dev_ring(self, sync_mode):
        rep = _run_check(8, sync_mode)
        assert rep["n_td_mismatch"] == 0, rep
        assert rep["n_wt_mismatch"] == 0, rep
        assert rep["n_t_mismatch"] == 0, rep
        assert rep["word_map_mismatch"] == 0
        assert rep["tokens_preserved"] and rep["z_in_range"]
        assert rep["ll_improved"], rep["ll"]

    def test_multipod_ring(self):
        """2 pods × 4 workers: the cross-pod boundary hop must be exact."""
        rep = _run_check(8, "stoken", pods=2)
        assert rep["n_td_mismatch"] == 0, rep
        assert rep["n_wt_mismatch"] == 0, rep
        assert rep["n_t_mismatch"] == 0, rep
        assert rep["ll_improved"], rep["ll"]

    def test_load_balance_beats_naive(self):
        rep = _run_check(4, "stale")
        assert rep["round_imbalance"] < 3.0, rep

    def test_vectorized_inner_mode(self):
        """Beyond-paper batched cell pass: exact tables, LL still improves."""
        rep = _run_check(4, "stoken", inner_mode="vectorized")
        assert rep["n_td_mismatch"] == 0, rep
        assert rep["n_wt_mismatch"] == 0, rep
        assert rep["n_t_mismatch"] == 0, rep
        assert rep["ll_improved"], rep["ll"]

    @pytest.mark.parametrize("inner_mode,ring_mode,layout", [
        ("scan", "barrier", "dense"), ("fused", "barrier", "dense"),
        ("scan", "pipelined", "dense"), ("fused", "pipelined", "dense"),
        ("fused", "barrier", "ragged"), ("fused", "pipelined", "ragged"),
    ])
    def test_block_queue_ring(self, inner_mode, ring_mode, layout):
        """B = 4W: each worker circulates a 4-block queue; counts must stay
        exact and the chain must still mix — in both ring schedules and
        both token layouts."""
        rep = _run_check(4, "stoken", inner_mode=inner_mode, n_blocks=16,
                         ring_mode=ring_mode, layout=layout)
        assert rep["blocks_per_worker"] == 4
        assert rep["layout"] == layout
        assert rep["n_td_mismatch"] == 0, rep
        assert rep["n_wt_mismatch"] == 0, rep
        assert rep["n_t_mismatch"] == 0, rep
        assert rep["ll_improved"], rep["ll"]
        if layout == "ragged":
            # the tile streams must actually be leaner than the dense grid
            dense = _run_check(4, "stoken", inner_mode=inner_mode,
                               n_blocks=16, ring_mode=ring_mode)
            assert rep["pad_fraction"] < dense["pad_fraction"], (
                rep["pad_fraction"], dense["pad_fraction"])

    @pytest.mark.parametrize("ring_mode", ["barrier", "pipelined"])
    def test_multipod_ragged_ring(self, ring_mode):
        """2 pods × 2 workers on the ragged streams: the wrap-around queue
        hop must cross the pod axis exactly with the tile geometry too."""
        rep = _run_check(4, "stoken", pods=2, n_blocks=8,
                         ring_mode=ring_mode, layout="ragged")
        assert rep["n_td_mismatch"] == 0, rep
        assert rep["n_wt_mismatch"] == 0, rep
        assert rep["n_t_mismatch"] == 0, rep
        assert rep["ll_improved"], rep["ll"]

    @pytest.mark.parametrize("ring_mode", ["barrier", "pipelined"])
    def test_multipod_block_queue(self, ring_mode):
        """2 pods × 2 workers with B = 2W: the wrap-around queue hop must
        cross the pod axis exactly (in pipelined mode, twice per round)."""
        rep = _run_check(4, "stoken", pods=2, n_blocks=8,
                         ring_mode=ring_mode)
        assert rep["n_td_mismatch"] == 0, rep
        assert rep["n_wt_mismatch"] == 0, rep
        assert rep["n_t_mismatch"] == 0, rep
        assert rep["ll_improved"], rep["ll"]

    def test_non_multiple_n_blocks_rejected_end_to_end(self):
        """B % W != 0 must die in the launch path too, not just in
        build_layout unit tests."""
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(REPO, "src")
        env.pop("XLA_FLAGS", None)
        out = subprocess.run(
            [sys.executable, "-m", "repro.launch.lda_dist_check",
             "4", "stoken", "1", "scan", "6"],
            capture_output=True, text=True, env=env, timeout=900)
        assert out.returncode != 0
        assert "multiple" in out.stderr

    def test_exactness_matrix(self):
        """The full sync × inner × B × ring × layout × doc_tile × r_mode
        matrix on the 8-device mesh: global counts bit-equal to a rebuild
        from z in every combination, the pipelined ring bit-equal to the
        barrier ring in every cell, the ragged layout bit-equal to the
        dense one in every cell, every doc-tiled (slab-paged) run
        bit-equal to the untiled run over the same grouped layout, and
        every sparse-r run bit-equal to its dense-r twin."""
        # 420 combos (the r_mode axis grew the matrix 252 -> 420) take
        # ~10 min in one process on a CPU host, so they run as four
        # shards side by side (each group's comparisons stay in its shard)
        with concurrent.futures.ThreadPoolExecutor(4) as ex:
            reps = list(ex.map(
                lambda i: _run_module("repro.launch.lda_matrix_check", 8,
                                      2, "full", f"{i}/4", timeout=2700),
                range(4)))
        rep = {"combos": [c for r in reps for c in r["combos"]],
               "all_exact": all(r["all_exact"] for r in reps)}
        assert len(rep["combos"]) == 420
        assert {c["ring_mode"] for c in rep["combos"]} == \
            {"barrier", "pipelined"}
        assert {c["layout"] for c in rep["combos"]} == {"dense", "ragged"}
        assert {c["r_mode"] for c in rep["combos"]} == {"dense", "sparse"}
        assert len({c["doc_tile"] for c in rep["combos"]}) == 3  # None + 2
        cross_ring = [c for c in rep["combos"]
                      if "vs_barrier_z_mismatch" in c]
        cross_layout = [c for c in rep["combos"]
                        if "vs_dense_z_mismatch" in c]
        cross_paging = [c for c in rep["combos"]
                        if "vs_untiled_z_mismatch" in c]
        cross_rmode = [c for c in rep["combos"]
                       if "vs_rdense_z_mismatch" in c]
        assert len(cross_ring) == 126 and len(cross_layout) == 126
        assert len(cross_paging) == 144
        # every exact inner mode (scan, fused) gets a sparse twin
        assert len(cross_rmode) == 168
        assert all(c["r_mode"] == "sparse" for c in cross_rmode)
        bad = [c for c in rep["combos"]
               if c["n_td_mismatch"] or c["n_wt_mismatch"]
               or c["n_t_mismatch"] or not c["tokens_preserved"]
               or any(c.get(f"{p}_{f}_mismatch", 0)
                      for p in ("vs_barrier", "vs_dense", "vs_untiled",
                                "vs_rdense")
                      for f in ("z", "n_wt", "n_t"))]
        assert rep["all_exact"], bad


class TestDocTileSmoke:
    """Fast (non-slow) doc-tiling + sparse-r regression signal: the
    matrix check's smoke subset — fused/pipelined/stoken at B = 2W on
    both layouts, doc_tile ∈ {None, 3}, paged vs untiled twins, plus a
    sparse-r twin per untiled layout — so a doc-tiling or r-bucket chain
    break fails tier-1's fast stage, not just the slow matrix."""

    def test_matrix_smoke_subset(self):
        rep = _run_module("repro.launch.lda_matrix_check", 4, 1, "smoke")
        assert rep["subset"] == "smoke"
        assert len(rep["combos"]) == 6
        assert {c["layout"] for c in rep["combos"]} == {"dense", "ragged"}
        tiled = [c for c in rep["combos"] if c["doc_tile"]]
        assert tiled and all("vs_untiled_z_mismatch" in c for c in tiled)
        sparse = [c for c in rep["combos"] if c["r_mode"] == "sparse"]
        assert len(sparse) == 2
        assert all("vs_rdense_z_mismatch" in c and not c["doc_tile"]
                   for c in sparse)
        # the smoke subset reports the slab-vs-whole-shard VMEM numbers
        # (ci.sh prints them for silicon tuning)
        assert all(s["ntd_slab_bytes"] < s["ntd_whole_bytes"]
                   for s in rep["slab_vmem"])
        assert rep["all_exact"], rep["combos"]


@pytest.mark.slow
class TestRingShift:
    """Direct unit coverage of ``_ring_shift_down`` (previously only hit
    through whole sweeps)."""

    def test_flat_ring(self):
        rep = _run_module("repro.launch.ring_shift_check", 8, 1)
        assert rep["one_shift_mismatch"] == 0, rep
        assert rep["one_shift_vec_mismatch"] == 0, rep
        assert rep["identity_mismatch"] == 0, rep
        assert rep["identity_vec_mismatch"] == 0, rep

    def test_two_axis_ring_crosses_pod_boundary(self):
        """('pod','worker') mesh: one shift moves flat position i+1 → i,
        the wrap-around element crosses the pod axis, and W shifts restore
        the identity."""
        rep = _run_module("repro.launch.ring_shift_check", 8, 2)
        assert rep["ring_axes"] == ["pod", "worker"]
        assert rep["one_shift_mismatch"] == 0, rep
        assert rep["one_shift_vec_mismatch"] == 0, rep
        assert rep["identity_mismatch"] == 0, rep
        assert rep["identity_vec_mismatch"] == 0, rep
        assert rep["cross_pod_ok"], rep


@pytest.mark.slow
class TestStokenStaleness:
    """The s-token working copy is stale but boundedly so (paper Alg. 4):
    instrumented sweeps must match the fold schedule exactly and never
    exceed the documented (W−1)·k-cell staleness bound — and the pipelined
    ring must produce the bit-identical lag trace."""

    @pytest.mark.parametrize("n_dev,inner_mode,n_blocks", [
        (8, "scan", 16), (4, "fused", 8),
    ])
    def test_lag_bounded_and_ring_mode_invariant(self, n_dev, inner_mode,
                                                 n_blocks):
        rep = _run_module("repro.launch.stoken_lag_check",
                          n_dev, inner_mode, n_blocks)
        assert rep["fold_schedule_exact"], rep
        assert rep["lag_within_bound"], rep
        assert rep["lag_nonzero"], rep          # the check isn't vacuous
        assert rep["documented_bound_ok"], rep
        assert rep["fold_window_rounds_max"] <= rep["n_devices"] - 1, rep
        assert rep["ring_modes_identical"], rep
        assert rep["layout_modes_identical"], rep
