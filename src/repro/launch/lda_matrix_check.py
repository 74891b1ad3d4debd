"""Distributed sweep exactness matrix (run as a subprocess).

Usage:  python -m repro.launch.lda_matrix_check [n_devices] [n_sweeps] \
            [subset] [shard]

One faked-multi-device process sweeps every combination of
``sync_mode`` ∈ {stoken, stale, allreduce} × ``inner_mode`` ∈ {scan, fused,
vectorized} × ``B`` × ``ring_mode`` ∈ {barrier, pipelined} × ``layout`` ∈
{dense, ragged} × ``doc_tile`` ∈ {None, I_max//3, 8} and, after each run,
rebuilds the count tables from the final assignments ``z``.  Five
invariants under test (DESIGN.md §4/§7):

* at every sweep boundary ``global_counts`` must be **bit-equal** to the
  rebuild, for any queue length — staleness modes only reorder when ``n_t``
  information travels, never what the counts are;
* the pipelined ring must be **bit-identical** to the barrier ring — same
  ``z``, same ``n_wt``, same ``n_t`` — in every cell, because pipelining
  only moves when the first half-queue's hop is issued, never the cell
  order or the s-token fold point;
* the ragged tile-stream layout must be **bit-identical** to the dense
  cell grid in every cell: both geometries carry the same tokens in the
  same order with the same per-token-uid uniforms, and padding slots are
  exact no-ops;
* for ``doc_tile`` layouts, the **paged** run (fused kernels keep one
  ``(doc_tile, T)`` doc-topic slab VMEM-resident) must be bit-identical
  to the **untiled** run (whole shard resident) over the same layout —
  doc tiling changes memory residency only, never the chain;
* the **sparse r-bucket** run (``r_mode="sparse"``: the r-draw walks
  per-doc compacted side tables instead of recompacting the dense
  ``n_td`` row per token, DESIGN.md §7a) must be bit-identical to the
  same-config dense run for every exact inner mode — both modes draw
  from the same compacted vector, so maintenance strategy is
  chain-invisible (``vectorized`` has no per-token chain and rejects
  sparse mode by construction).

``doc_tile`` values are layout-build-time choices (they fix the token
order), so the untiled reference runs on the *same grouped layout* with
``NomadLDA(doc_tile=None)``; the barrier-ring reference suffices for both
ring modes (pipelined paged ≡ barrier paged by the ring invariant).
``B`` runs {W, 2W, 4W} for ungrouped layouts and {W, 4W} for the doc-tile
axis to bound runtime.

``subset = "smoke"`` (argv[3]) runs a ~30 s slice — both layouts,
doc_tile ∈ {None, 3}, fused/pipelined/stoken at B = 2W with the untiled
twin and (ungrouped only) the sparse-r twin — and reports each layout's ``ntd_slab_bytes`` vs whole-shard bytes
(``repro.kernels.fused_sweep.fused_vmem_bytes``) so CI prints the slab
VMEM number; the full matrix stays behind the tier-1 ``slow`` marker.

``shard = "i/n"`` (argv[4]) runs only the (B × doc_tile, sync_mode,
inner_mode) groups whose index is ``i`` mod ``n``.  Every cross-run
comparison stays inside one group, so ``n`` shards run as ``n``
processes check exactly what one process checks.

Prints one JSON report: ``{"combos": [...], "all_exact": bool}``.
"""
import json
import os
import sys


def main() -> None:
    n_dev = int(sys.argv[1]) if len(sys.argv) > 1 else 8
    n_sweeps = int(sys.argv[2]) if len(sys.argv) > 2 else 2
    subset = sys.argv[3] if len(sys.argv) > 3 else "full"
    if subset not in ("full", "smoke"):
        raise SystemExit(f"unknown subset {subset!r} (full|smoke)")
    shard, n_shards = map(int, (sys.argv[4] if len(sys.argv) > 4
                                else "0/1").split("/"))

    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_dev} "
        + os.environ.get("XLA_FLAGS", ""))

    import jax
    import numpy as np

    from repro.core.nomad import NomadLDA
    from repro.data import synthetic
    from repro.data.sharding import build_layout, counts_from_layout
    from repro.kernels.fused_sweep import fused_vmem_bytes

    assert len(jax.devices()) == n_dev, jax.devices()

    T = 8
    alpha, beta = 50.0 / T, 0.01
    smoke = subset == "smoke"
    corpus, _, _ = synthetic.make_corpus(
        num_docs=32 if smoke else 64, vocab_size=96, num_topics=T,
        mean_doc_len=12.0, seed=5)
    mesh = jax.make_mesh((n_dev,), ("worker",))

    def run(layout, sync_mode, inner_mode, ring_mode, doc_page,
            r_mode="dense"):
        lda = NomadLDA(mesh=mesh, ring_axes=("worker",), layout=layout,
                       alpha=alpha, beta=beta, sync_mode=sync_mode,
                       inner_mode=inner_mode, ring_mode=ring_mode,
                       doc_tile=doc_page, r_mode=r_mode)
        arrays = lda.init_arrays(seed=0)
        for it in range(n_sweeps):
            arrays = lda.sweep(arrays, seed=it)
        n_td, n_wt, n_t = lda.global_counts(arrays)
        td_ref, wt_ref, t_ref = counts_from_layout(
            layout, np.asarray(arrays["z"]), T)
        # canonical per-token assignments: the layout-free view every
        # cross-run comparison (ring / layout / paging) uses
        z_c = layout.extract_canonical(np.asarray(arrays["z"]))
        entry = {
            "B": layout.B, "k": layout.k, "layout": layout.kind,
            "doc_tile": layout.doc_tile or None,
            "paged": doc_page is not None,
            "sync_mode": sync_mode,
            "inner_mode": inner_mode,
            "ring_mode": ring_mode,
            "r_mode": r_mode,
            "pad_fraction": layout.pad_fraction,
            "n_td_mismatch": int(np.abs(n_td - td_ref).sum()),
            "n_wt_mismatch": int(np.abs(n_wt - wt_ref).sum()),
            "n_t_mismatch": int(np.abs(n_t - t_ref).sum()),
            "tokens_preserved":
                int(n_t.sum()) == int(corpus.num_tokens),
        }
        return entry, (z_c, n_wt, np.asarray(n_t))

    def layouts_for(b_mult, dt):
        # small dense grid step so doc-group padding stays bounded on the
        # toy corpus (the N_BLK default is tuned for real streams)
        kw = dict(doc_tile=dt) if dt else {}
        dense = build_layout(corpus, n_workers=n_dev, T=T,
                             n_blocks=b_mult * n_dev,
                             **(dict(kw, doc_blk=16) if dt else {}))
        ragged = build_layout(corpus, n_workers=n_dev, T=T,
                              n_blocks=b_mult * n_dev, layout="ragged",
                              **kw)
        return {"dense": dense, "ragged": ragged}

    combos = []
    if smoke:
        cases = [(2, dt) for dt in (None, 3)]
        sync_modes, inner_modes = ("stoken",), ("fused",)
        ring_modes = ("pipelined",)
    else:
        cases = [(m, None) for m in (1, 2, 4)]
        i_max = layouts_for(1, None)["dense"].I_max
        for dt in (max(i_max // 3, 1), 8):
            cases += [(m, dt) for m in (1, 4)]
        sync_modes = ("stoken", "stale", "allreduce")
        inner_modes = ("scan", "fused", "vectorized")
        ring_modes = ("barrier", "pipelined")

    slab_report = []
    group = -1
    for b_mult, dt in cases:
        layouts = layouts_for(b_mult, dt)
        if dt:
            for kind, lay in layouts.items():
                slab_report.append({
                    "B": lay.B, "layout": kind, "doc_tile": dt,
                    "ntd_slab_bytes": lay.ntd_slab_bytes,
                    "ntd_whole_bytes": lay.ntd_whole_bytes,
                    "fused_vmem_bytes": fused_vmem_bytes(
                        lay.I_max, lay.J_max, lay.T,
                        lay.doc_blk if kind == "dense" else lay.tile,
                        doc_rows=dt),
                })
        for sync_mode in sync_modes:
            for inner_mode in inner_modes:
                group += 1
                if group % n_shards != shard:
                    continue
                per_run = {}
                for kind in ("dense", "ragged"):
                    layout = layouts[kind]
                    if dt:
                        # untiled twin: same grouped layout, whole-shard
                        # residency — the reference every paged run (and,
                        # transitively via vs_barrier, every ring mode)
                        # must reproduce bit-for-bit
                        _, per_run[kind, "untiled"] = run(
                            layout, sync_mode, inner_mode, "barrier", None)
                    for ring_mode in ring_modes:
                        entry, res = run(layout, sync_mode, inner_mode,
                                         ring_mode, dt if dt else None)
                        per_run[kind, ring_mode] = res
                        combos.append(entry)
                        # barrier vs pipelined (same layout): the
                        # per-token chain itself must be unchanged.
                        if ring_mode == "pipelined" and \
                                ("barrier" in ring_modes):
                            _diff(entry, "vs_barrier",
                                  per_run[kind, "barrier"],
                                  per_run[kind, "pipelined"])
                        # ragged vs dense (same ring): same canonical
                        # chain through the other token geometry.
                        if kind == "ragged":
                            _diff(entry, "vs_dense",
                                  per_run["dense", ring_mode],
                                  per_run["ragged", ring_mode])
                        # paged vs untiled (same layout): doc tiling
                        # must be memory-residency-only.
                        if dt:
                            _diff(entry, "vs_untiled",
                                  per_run[kind, "untiled"],
                                  per_run[kind, ring_mode])
                        # sparse vs dense r-bucket (same everything):
                        # side-table maintenance must be chain-invisible.
                        # (Smoke keeps one ungrouped sparse twin per
                        # layout to bound runtime.)
                        if inner_mode != "vectorized" and \
                                not (smoke and dt):
                            sentry, sres = run(
                                layout, sync_mode, inner_mode, ring_mode,
                                dt if dt else None, r_mode="sparse")
                            combos.append(sentry)
                            _diff(sentry, "vs_rdense",
                                  per_run[kind, ring_mode], sres)

    all_exact = all(
        c["n_td_mismatch"] == 0 and c["n_wt_mismatch"] == 0
        and c["n_t_mismatch"] == 0 and c["tokens_preserved"]
        and all(c.get(f"{p}_{f}_mismatch", 0) == 0
                for p in ("vs_barrier", "vs_dense", "vs_untiled",
                          "vs_rdense")
                for f in ("z", "n_wt", "n_t"))
        for c in combos)
    print(json.dumps({"n_devices": n_dev, "n_sweeps": n_sweeps,
                      "subset": subset, "shard": f"{shard}/{n_shards}",
                      "combos": combos,
                      "slab_vmem": slab_report, "all_exact": all_exact}))


def _diff(entry: dict, prefix: str, a, b) -> None:
    """Record mismatch counts between two runs' (canonical z, global n_wt,
    n_t) triples under ``{prefix}_{field}_mismatch`` keys."""
    import numpy as np
    za, wta, ta = a
    zb, wtb, tb = b
    entry[f"{prefix}_z_mismatch"] = int((za != zb).sum())
    entry[f"{prefix}_n_wt_mismatch"] = int(np.abs(wta - wtb).sum())
    entry[f"{prefix}_n_t_mismatch"] = int(
        np.abs(ta.astype(np.int64) - tb.astype(np.int64)).sum())


if __name__ == "__main__":
    main()
