"""F+Nomad LDA across 8 (faked) devices — the paper's distributed algorithm.

Run:  PYTHONPATH=src python examples/nomad_distributed.py [n_blocks]
                                                          [ring_mode]
                                                          [layout]
                                                          [doc_tile]
          [--sweeps N] [--checkpoint-every N [--checkpoint-path PATH]]
          [--resume-from PATH]
Documents sharded across an 8-worker ring; word-topic blocks travel the
ring as nomadic tokens — by default 4 blocks per worker (B = 4W, the
paper's blocks >> workers setup; pass n_blocks to override), with each
worker sweeping its whole block queue every ring round; the s-token
carries the global topic counts (paper Alg. 4).  ring_mode "pipelined"
(default; pass "barrier" to compare) forwards each round's first
half-queue while the second half sweeps — same chain bit-for-bit, hop
off the critical path.  layout "ragged" (default; pass "dense" to
compare) stores each worker's queue as a CSR-style tile stream, so
padding — and with it tokens/sec — no longer degrades as n_blocks
grows.  doc_tile (0 = off) pages (doc_tile, T) doc-topic slabs through
the fused kernels instead of holding each worker's whole (I_max, T)
shard in VMEM — the knob that lets per-worker documents scale past the
~12 MiB budget (DESIGN.md §7).  --checkpoint-every writes a resumable
chain checkpoint (DESIGN.md §9) every N sweeps; --resume-from continues
a killed run bit-for-bit (the resumed chain is identical to an
uninterrupted one — pass the same layout args or the load refuses).
Prints LL per sweep + exactness check.
"""
import argparse
import os

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))

from repro.launch.compile_cache import use_compile_cache  # noqa: E402

use_compile_cache()

import time  # noqa: E402

import jax   # noqa: E402
import numpy as np  # noqa: E402

from repro.core.nomad import NomadLDA          # noqa: E402
from repro.data import synthetic               # noqa: E402
from repro.data.sharding import build_layout   # noqa: E402


def main():
    ap = argparse.ArgumentParser(
        description="F+Nomad LDA on a faked 8-device ring")
    ap.add_argument("n_blocks", nargs="?", type=int, default=0,
                    help="ring blocks B (default 4W)")
    ap.add_argument("ring_mode", nargs="?", default="pipelined",
                    choices=("pipelined", "barrier"))
    ap.add_argument("layout", nargs="?", default="ragged",
                    choices=("ragged", "dense"))
    ap.add_argument("doc_tile", nargs="?", type=int, default=0,
                    help="doc-topic slab height (0 = whole shard)")
    ap.add_argument("--sweeps", type=int, default=10)
    ap.add_argument("--checkpoint-every", type=int, default=0, metavar="N",
                    help="write a chain checkpoint every N sweeps (0 = off)")
    ap.add_argument("--checkpoint-path", default="/tmp/nomad_chain.npz",
                    metavar="PATH")
    ap.add_argument("--resume-from", default=None, metavar="PATH",
                    help="resume bit-for-bit from a chain checkpoint")
    args = ap.parse_args()

    T = 32
    alpha, beta = 50.0 / T, 0.01
    corpus, _, _ = synthetic.make_corpus(
        num_docs=600, vocab_size=1024, num_topics=T, mean_doc_len=50.0,
        seed=1)
    n_dev = len(jax.devices())
    print(f"devices: {n_dev}; corpus: {corpus.num_tokens} tokens")

    n_blocks = args.n_blocks or 4 * n_dev
    mesh = jax.make_mesh((n_dev,), ("worker",))
    doc_kw = {}
    if args.doc_tile:
        doc_kw = dict(doc_tile=args.doc_tile)
        if args.layout == "dense":
            doc_kw["doc_blk"] = 16      # toy-corpus grid step (cf. N_BLK)
    layout = build_layout(corpus, n_workers=n_dev, T=T, n_blocks=n_blocks,
                          layout=args.layout, **doc_kw)
    print(f"layout: {layout.W}x{layout.B} cells ({layout.k} blocks/queue, "
          f"{layout.kind}), pad {layout.pad_fraction:.1%},"
          f" worst-round imbalance {layout.round_imbalance:.2f}x,"
          f" ring_mode {args.ring_mode}"
          + (f", doc_tile {args.doc_tile} "
             f"({layout.ntd_slab_bytes} B slab vs "
             f"{layout.ntd_whole_bytes} B whole-shard)"
             if args.doc_tile else ""))

    lda = NomadLDA(mesh=mesh, ring_axes=("worker",), layout=layout,
                   alpha=alpha, beta=beta, sync_mode="stoken",
                   ring_mode=args.ring_mode,
                   doc_tile=args.doc_tile if args.doc_tile else None,
                   checkpoint_every=args.checkpoint_every or None,
                   checkpoint_path=(args.checkpoint_path
                                    if args.checkpoint_every else None),
                   resume_from=args.resume_from)
    if args.resume_from:
        print(f"resuming chain from {args.resume_from}")
    else:
        print(f"initial ll: "
              f"{lda.log_likelihood(lda.init_arrays(seed=0)):.0f}")

    t0 = [time.time()]

    def on_sweep(it, arrays):
        jax.block_until_ready(arrays["n_t"])
        ll = lda.log_likelihood(arrays)
        print(f"sweep {it + 1:2d}  ll {ll:.0f}  "
              f"({corpus.num_tokens / (time.time() - t0[0]):,.0f} tok/s)")
        t0[0] = time.time()

    arrays, _ = lda.run(args.sweeps, on_sweep=on_sweep)
    if args.checkpoint_every:
        print(f"chain checkpoint at {args.checkpoint_path} "
              f"(resume with --resume-from)")

    # exactness: rebuild counts from assignments
    n_td, n_wt, n_t = lda.global_counts(arrays)
    assert int(n_t.sum()) == corpus.num_tokens
    np.testing.assert_array_equal(n_td.sum(0), n_t)
    np.testing.assert_array_equal(n_wt.sum(0), n_t)
    print("count tables exact across the ring ✓")


if __name__ == "__main__":
    main()
