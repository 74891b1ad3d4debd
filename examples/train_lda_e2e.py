"""End-to-end driver (the paper's kind = training): F+Nomad LDA at scale.

Run:  PYTHONPATH=src python examples/train_lda_e2e.py [--sweeps 100]
          [--checkpoint-every 10] [--resume-from /tmp/repro_lda_ckpt.npz]
A few hundred sweeps of distributed F+Nomad LDA on a PubMed-scaled-down
synthetic corpus (T=64), with a resumable chain checkpoint (DESIGN.md §9)
every --checkpoint-every sweeps — kill the run and pass --resume-from to
continue bit-for-bit where it left off — the paper's Fig. 5/6 protocol
end to end.
"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                           + os.environ.get("XLA_FLAGS", ""))

from repro.launch.compile_cache import use_compile_cache  # noqa: E402

use_compile_cache()

import argparse  # noqa: E402
import time      # noqa: E402

import jax       # noqa: E402

from repro.core.nomad import NomadLDA          # noqa: E402
from repro.data import synthetic               # noqa: E402
from repro.data.sharding import build_layout   # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sweeps", type=int, default=100)
    ap.add_argument("--topics", type=int, default=64)
    ap.add_argument("--docs", type=int, default=2000)
    ap.add_argument("--ckpt", default="/tmp/repro_lda_ckpt.npz")
    ap.add_argument("--checkpoint-every", type=int, default=10, metavar="N",
                    help="write a chain checkpoint every N sweeps (0 = off)")
    ap.add_argument("--resume-from", default=None, metavar="PATH",
                    help="resume bit-for-bit from a chain checkpoint")
    args = ap.parse_args()

    T = args.topics
    alpha, beta = 50.0 / T, 0.01
    corpus, _, _ = synthetic.make_corpus(
        num_docs=args.docs, vocab_size=2048, num_topics=T,
        mean_doc_len=80.0, seed=0)
    n_dev = len(jax.devices())
    mesh = jax.make_mesh((n_dev,), ("worker",))
    layout = build_layout(corpus, n_workers=n_dev, T=T)
    lda = NomadLDA(mesh=mesh, ring_axes=("worker",), layout=layout,
                   alpha=alpha, beta=beta, sync_mode="stoken",
                   checkpoint_every=args.checkpoint_every or None,
                   checkpoint_path=(args.ckpt if args.checkpoint_every
                                    else None),
                   resume_from=args.resume_from)

    print(f"{corpus.num_tokens:,} tokens on {n_dev} workers; "
          f"T={T}; {args.sweeps} sweeps"
          + (f"; resuming from {args.resume_from}"
             if args.resume_from else ""))
    t_start = time.time()
    done = [0]

    def on_sweep(it, arrays):
        done[0] += 1
        if (it + 1) % 10 == 0:
            jax.block_until_ready(arrays["n_t"])
            ll = lda.log_likelihood(arrays)
            rate = corpus.num_tokens * done[0] / (time.time() - t_start)
            print(f"sweep {it + 1:4d}  ll {ll:,.0f}  ({rate:,.0f} tok/s)")

    lda.run(args.sweeps, on_sweep=on_sweep)
    print(f"done in {time.time() - t_start:.1f}s"
          + (f"; chain checkpoint at {args.ckpt} "
             f"(resume with --resume-from)" if args.checkpoint_every else ""))


if __name__ == "__main__":
    main()
