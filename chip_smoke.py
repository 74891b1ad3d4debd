#!/usr/bin/env python3
"""Chip smoke test: F+Nomad LDA trains and serves on a TPU at NYTimes widths.

    python3 chip_smoke.py              # one chip: phases (a), (b), (c)
    python3 chip_smoke.py --chips 4    # 4-worker ring: phases (a), (b)

The deployment is shaped on the UCI Bag-of-Words NYTimes corpus (299,752
documents, vocabulary W = 102,660, ~100M tokens): the vocabulary, T = 1024
topics, a mean document length of ~330 tokens and the generator's Zipf
word skew are kept; the corpus is cut to 30,000 documents (~10M tokens)
per chip.  It is generated from ``--seed`` by ``data/synthetic.make_corpus``.

Phases, all in this one process, with every Pallas kernel compiled
(``interpret=False``):

(a) comparison — on a 2,000-document cut, one sweep with
    ``inner_mode="fused"`` (whole-shard kernel, then doc-paged kernel) and
    one with ``inner_mode="scan"``: ``z`` and every count table must be
    bit-identical.
(b) training — ``NomadLDA(layout="ragged", doc_tile, inner_mode="fused",
    ring_mode="pipelined").run(3, publish_every=3, ...)`` at full size: the
    count tables must equal a recount from ``z`` and the log-likelihood
    must rise.
(c) serving (one chip) — ``LdaEngine(inner_mode="fused")`` answers 64
    held-out documents from the corpus's length tail; every answer must be
    bit-identical to ``inner_mode="scan"`` on the same snapshot.

Times printed on the way are smoke-test readings, not a benchmark.  The
last line of stdout is the verdict
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": chips}}``,
with ``count`` the chips the ring ran on;
with no TPU visible, or when any check fails, it exits non-zero without it.
"""
from __future__ import annotations

import argparse
import faulthandler
import json
import sys
import threading
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

VOCAB = 102_660          # NYTimes vocabulary, kept
T = 1024                 # topics, kept
DOCS_PER_CHIP = 30_000   # cut from 299,752 (one chip's share of the run)
CUT_DOCS = 2_000         # the comparison cut, split over the ring
MEAN_LEN = 277.0         # lognormal median; the mean comes out ≈ 330
QUERY_POOL = 640         # held-out documents the queries are drawn from
QUERIES = 64
ALPHA, BETA = 50.0 / T, 0.01
SWEEPS = 3
# Kernel geometry, from the compile rehearsals (tests/test_tpu_compile.py):
# B = 48 word blocks keep each (J_max, T) word-topic block near 2.4k rows
# (four VMEM copies, ~37 MiB); (2048, T) doc slabs (8 MiB) page n_td;
# 256-token tiles keep the scalar-prefetch tile maps well inside SMEM.
BLOCKS = 48
TILE = 256
DOC_TILE = 2048
CUT_DOC_TILE = 512       # several slabs per worker at the 2,000-doc cut


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        print(f"chip_smoke FAILED: {msg}", file=sys.stderr, flush=True)
        raise SystemExit(1)


class Heartbeat:
    """Prints the running step once a minute, so a run cut by a time
    limit shows where it was; dumps every thread's stack if the run
    nears the 1,200 s a smoke may take.  :meth:`stop` silences it for
    good, so nothing can print after the verdict line."""

    def __init__(self, every: float = 60.0):
        self.step, self.t0 = "start-up", time.perf_counter()
        self._lock, self._done = threading.Lock(), threading.Event()
        faulthandler.dump_traceback_later(1050)
        threading.Thread(target=self._beat, args=(every,), daemon=True
                         ).start()

    def _beat(self, every):
        while not self._done.wait(every):
            with self._lock:
                if not self._done.is_set():
                    log(f"    [{time.perf_counter() - self.t0:.0f} s] "
                        f"{self.step}")

    def stop(self):
        with self._lock:
            self._done.set()
        faulthandler.cancel_dump_traceback_later()


BEAT = None


def step(name: str) -> None:
    if BEAT is not None:
        BEAT.step = name


class CompileClock:
    """Seconds the XLA/Mosaic backend spends compiling, read off JAX's
    monitoring events, so a phase's wall time splits into compile + run
    (tracing and lowering stay in the run share).  Programs read from the
    persistent cache are counted apart, with the compile seconds their
    first compile took: a warm cache is not a fast compile."""

    def __init__(self):
        import jax
        self.total, self.hits, self.saved = 0.0, 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.total += secs
        elif name == "/jax/compilation_cache/compile_time_saved_sec":
            self.saved += secs

    def _on_event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def timed(self, fn):
        """``(fn(), compile s, run s, note on cached programs)``."""
        c0, h0, s0, t0 = (self.total, self.hits, self.saved,
                          time.perf_counter())
        out = fn()
        wall = time.perf_counter() - t0
        comp = self.total - c0
        hits = self.hits - h0
        note = (f" + {hits} cached programs ({self.saved - s0:.1f} s of "
                f"compile saved)" if hits else "")
        return out, comp, wall - comp, note


def sub_corpus(corpus, n_docs: int):
    """The first ``n_docs`` documents (make_corpus emits them in order)."""
    from repro.data.corpus import Corpus
    n_tok = int(np.searchsorted(corpus.doc_ids, n_docs))
    return Corpus(doc_ids=corpus.doc_ids[:n_tok],
                  word_ids=corpus.word_ids[:n_tok],
                  num_docs=n_docs, num_words=corpus.num_words)


def held_out_tail(corpus, first: int, n_pool: int, n_pick: int):
    """The ``n_pick`` longest documents among ``n_pool`` held-out ones
    (ids ``first ..``): queries from the corpus's length tail."""
    lo, hi = np.searchsorted(corpus.doc_ids, [first, first + n_pool])
    ids, words = corpus.doc_ids[lo:hi], corpus.word_ids[lo:hi]
    lengths = np.bincount(ids - first, minlength=n_pool)
    pick = np.sort(np.argsort(-lengths, kind="stable")[:n_pick])
    return [words[ids == first + d] for d in pick]


def first_mismatch(layout, a, b):
    """(canonical token, doc id, word id, topic a, topic b) of the first
    token whose assignment differs, or None."""
    za, zb = layout.extract_canonical(a), layout.extract_canonical(b)
    diff = np.nonzero(za != zb)[0]
    if diff.size == 0:
        return None
    i = int(diff[0])
    w, b_idx, d, j = layout.token_coords()
    doc = int(layout.doc_of_worker[w[i], d[i]])
    word = int(layout.word_of_block[b_idx[i], j[i]])
    return i, doc, word, int(za[i]), int(zb[i])


def phase_compare(clock, mesh, corpus, chips, seed):
    """(a) fused (whole-shard and doc-paged) ≡ scan, one sweep."""
    from repro.core.nomad import NomadLDA
    from repro.data.sharding import build_layout
    step("(a) layout")
    cut = sub_corpus(corpus, CUT_DOCS)
    lay = build_layout(cut, n_workers=chips, T=T, n_blocks=BLOCKS,
                       layout="ragged", tile=TILE, doc_tile=CUT_DOC_TILE)
    log(f"(a) comparison: {cut.num_docs} docs, {cut.num_tokens} tokens, "
        f"B={lay.B} J_max={lay.J_max} I_max={lay.I_max} tile={lay.tile} "
        f"doc_tile={CUT_DOC_TILE}")
    common = dict(mesh=mesh, ring_axes=("worker",), layout=lay,
                  alpha=ALPHA, beta=BETA, ring_mode="pipelined")
    runs = {
        "scan": NomadLDA(**common, inner_mode="scan"),
        "fused whole-shard": NomadLDA(**common, inner_mode="fused",
                                      interpret=False),
        "fused doc-paged": NomadLDA(**common, inner_mode="fused",
                                    interpret=False, doc_tile=CUT_DOC_TILE),
    }
    arrays0 = runs["scan"].init_arrays(seed=seed)
    out = {}
    for name, trainer in runs.items():
        def sweep():
            res = trainer.sweep(arrays0, seed=0)
            return {k: np.asarray(res[k]) for k in ("z", "n_td", "n_wt",
                                                    "n_t")}
        step(f"(a) {name} sweep")
        out[name], comp, run, cached = clock.timed(sweep)
        log(f"    {name}: compile {comp:.1f} s{cached}, sweep {run:.2f} s "
            f"({cut.num_tokens / run:,.0f} tokens/s, not a benchmark)")
    ref = out["scan"]
    for name in ("fused whole-shard", "fused doc-paged"):
        bad = first_mismatch(lay, out[name]["z"], ref["z"])
        check(bad is None,
              f"{name} vs scan: first differing token (canonical "
              f"#, doc, word, fused z, scan z) = {bad}")
        for k in ("n_td", "n_wt", "n_t"):
            check(np.array_equal(out[name][k], ref[k]),
                  f"{name} vs scan: {k} differs")
    log("(a) ok: z, n_td, n_wt, n_t bit-identical (fused whole-shard, "
        "fused doc-paged, scan)")
    return {"tokens": cut.num_tokens, "docs": cut.num_docs}


def recount_ok(layout, arrays) -> bool:
    """Count tables equal a recount of the assignments, cell for cell."""
    z = layout.extract_canonical(np.asarray(arrays["z"])).astype(np.int64)
    w, b, d, j = layout.token_coords()
    n_td = np.asarray(arrays["n_td"])
    n_wt = np.asarray(arrays["n_wt"])
    td = np.bincount((w * layout.I_max + d) * T + z, minlength=n_td.size)
    wt = np.bincount((b * layout.J_max + j) * T + z, minlength=n_wt.size)
    return (np.array_equal(td.reshape(n_td.shape), n_td)
            and np.array_equal(wt.reshape(n_wt.shape), n_wt)
            and np.array_equal(np.bincount(z, minlength=T),
                               np.asarray(arrays["n_t"])))


def phase_train(clock, mesh, corpus, chips, seed, publish):
    """(b) NomadLDA.run at full size, fused doc-paged kernel."""
    import jax
    from repro.core.nomad import NomadLDA
    from repro.data.sharding import build_layout
    from repro.kernels.fused_sweep import fused_vmem_bytes
    step("(b) layout")
    train = sub_corpus(corpus, DOCS_PER_CHIP * chips)
    t0 = time.perf_counter()
    lay = build_layout(train, n_workers=chips, T=T, n_blocks=BLOCKS,
                       layout="ragged", tile=TILE, doc_tile=DOC_TILE)
    vmem = fused_vmem_bytes(lay.I_max, lay.J_max, T, lay.tile, DOC_TILE)
    log(f"(b) training: {train.num_docs} docs, {train.num_tokens} tokens, "
        f"W={chips} B={lay.B} J_max={lay.J_max} I_max={lay.I_max} "
        f"tile={lay.tile} doc_tile={DOC_TILE} pad={lay.pad_fraction:.3f} "
        f"kernel VMEM {vmem / 2**20:.1f} MiB "
        f"(layout {time.perf_counter() - t0:.1f} s)")
    trainer = NomadLDA(mesh=mesh, ring_axes=("worker",), layout=lay,
                       alpha=ALPHA, beta=BETA, inner_mode="fused",
                       ring_mode="pipelined", interpret=False,
                       doc_tile=DOC_TILE)
    step("(b) initial log-likelihood")
    lls = [trainer.log_likelihood(trainer.init_arrays(seed=seed))]
    spans = []          # (wall s, compile s, cached programs) per sweep
    mark = [time.perf_counter(), clock.total, clock.hits]

    def on_sweep(s, arrays):
        jax.block_until_ready(arrays["n_t"])
        step(f"(b) after sweep {s}: log-likelihood, publish")
        spans.append((time.perf_counter() - mark[0], clock.total - mark[1],
                      clock.hits - mark[2]))
        lls.append(trainer.log_likelihood(arrays))
        mark[:] = [time.perf_counter(), clock.total, clock.hits]

    step("(b) NomadLDA.run")
    arrays, done = trainer.run(SWEEPS, init_seed=seed, on_sweep=on_sweep,
                               publish_every=SWEEPS, on_publish=publish)
    check(done == SWEEPS, f"run returned {done} sweeps")
    for s, (wall, comp, hits) in enumerate(spans):
        run = wall - comp
        log(f"    sweep {s}: compile {comp:.1f} s + {hits} cached programs, "
            f"run {run:.2f} s"
            f"{' (with set-up)' if s == 0 else ''} "
            f"({train.num_tokens / run / chips:,.0f} tokens/s/chip, not a "
            f"benchmark), LL {lls[s + 1]:.6e}")
    step("(b) count checks")
    n_td, n_wt, n_t = trainer.global_counts(arrays)
    check(int(n_t.sum()) == train.num_tokens,
          f"Σ n_t = {int(n_t.sum())} != {train.num_tokens} tokens")
    check(np.array_equal(n_td.sum(0), n_t) and np.array_equal(n_wt.sum(0),
                                                               n_t),
          "n_td.sum(0), n_wt.sum(0) and n_t disagree")
    check(recount_ok(lay, arrays),
          "count tables differ from a recount of z")
    check(all(b > a for a, b in zip(lls, lls[1:])),
          f"log-likelihood did not rise every sweep: {lls}")
    log(f"(b) ok: counts exact (Σ = {train.num_tokens} tokens, recount "
        f"from z matches), LL {lls[0]:.6e} -> {lls[-1]:.6e}")
    return {"tokens": train.num_tokens, "docs": train.num_docs,
            "J_max": lay.J_max, "ll": lls}


def phase_serve(clock, corpus, seed, engines):
    """(c) fused fold-in serving ≡ scan, per document."""
    import jax
    from repro.serve.lda_engine import TopicQuery
    docs = held_out_tail(corpus, DOCS_PER_CHIP, QUERY_POOL, QUERIES)
    lens = np.array([d.size for d in docs])
    log(f"(c) serving: {len(docs)} held-out queries, lengths "
        f"{lens.min()}..{lens.max()} (mean {lens.mean():.0f}), "
        f"generation {engines['fused'].generation}")
    q = TopicQuery(docs=tuple(docs), key=jax.random.key(seed))
    res = {}
    for name, eng in engines.items():
        step(f"(c) {name} query")
        res[name], comp, run, cached = clock.timed(lambda: eng.query(q))
        log(f"    {name}: compile {comp:.1f} s{cached}, answer {run:.2f} s, "
            f"batch {res[name].batch_shape} (not a benchmark)")
    f, s = res["fused"], res["scan"]
    check(f.generation == s.generation and f.digest == s.digest,
          "engines answered from different snapshots")
    for i in range(len(docs)):
        check(np.array_equal(f.n_td[i], s.n_td[i]),
              f"query doc {i}: fused fold-in counts differ from scan")
    check(np.array_equal(f.theta, s.theta), "θ rows differ")
    check(all(int(f.n_td[i].sum()) == docs[i].size
              for i in range(len(docs))), "fold-in counts lost tokens")
    log(f"(c) ok: {len(docs)} answers bit-identical fused vs scan")
    return {"queries": len(docs), "max_len": int(lens.max())}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="ring size: 1 (default) or a 4-chip ring")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    check(dev.platform == "tpu",
          f"no TPU visible (jax.devices()[0].platform = {dev.platform!r})")
    check(len(devices) >= args.chips,
          f"--chips {args.chips} but only {len(devices)} devices")
    chips = args.chips
    global BEAT
    BEAT = Heartbeat()
    log(f"device: {dev.device_kind} ({dev.platform}), {len(devices)} "
        f"visible, ring of {chips}; compile cache {cache_dir}")

    from repro.data.synthetic import make_corpus
    from repro.serve.lda_engine import LdaEngine
    n_docs = DOCS_PER_CHIP * chips + QUERY_POOL
    t0 = time.perf_counter()
    step("corpus generation")
    corpus, _, _ = make_corpus(num_docs=n_docs, vocab_size=VOCAB,
                               num_topics=T, mean_doc_len=MEAN_LEN,
                               seed=args.seed)
    log(f"corpus: NYTimes-shaped, W={VOCAB} T={T}, {n_docs} docs "
        f"({corpus.num_tokens} tokens, mean length "
        f"{corpus.num_tokens / n_docs:.0f}) in "
        f"{time.perf_counter() - t0:.1f} s; cut: 299,752 -> "
        f"{DOCS_PER_CHIP * chips} training docs ({DOCS_PER_CHIP}/chip) + "
        f"{QUERY_POOL} held out")

    mesh = jax.make_mesh((chips,), ("worker",), devices=devices[:chips])
    clock = CompileClock()
    summary = {"chips": chips, "seed": args.seed}
    summary["compare"] = phase_compare(clock, mesh, corpus, chips, args.seed)

    engines = {"fused": LdaEngine(inner_mode="fused", interpret=False),
               "scan": LdaEngine(inner_mode="scan")}

    def publish(snapshot):
        for eng in engines.values():
            eng.publish(snapshot)

    summary["train"] = phase_train(clock, mesh, corpus, chips, args.seed,
                                   publish)
    check(engines["fused"].generation == 1,
          "training did not publish its φ snapshot")
    if chips == 1:
        summary["serve"] = phase_serve(clock, corpus, args.seed, engines)
    summary["compile_s"], summary["cached_programs"] = clock.total, clock.hits

    out = ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    (out / f"chip_smoke_{chips}.json").write_text(json.dumps(summary,
                                                             indent=1))
    BEAT.stop()
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": chips}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
