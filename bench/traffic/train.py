"""Training traffic: whole Gibbs sweeps of ``NomadLDA`` back to back.

Set-up builds the configuration's corpus on the device
(``bench/corpus.py``), the program's layout (``build_layout``) and one
``NomadLDA`` trainer with its arrays, initialised from the run's seed, and
compiles, or loads from the persistent cache, the sweep program at the
window's argument shapes without running it (:func:`warm_up`).  The window
then runs whole sweeps, each ended by ``block_until_ready``, until
``--seconds`` have passed: the same ``NomadLDA.sweep`` call
``NomadLDA.run`` makes, on the same object.

Sweep ``i`` of a run uses the chain seed ``base + i``, with ``base`` drawn
from the run's seed.  Once the window has closed and the peak memory has
been read, every window sweep is checked against the plain reference
(``bench/reference.py``) on ``CHECK_DRAWS`` draws sampled from the run's
seed (:func:`sample_draws`), and the final count tables against a recount.
"""
from __future__ import annotations

import numpy as np

import corpus as corpus_gen
import reference as ref

UNITS = {"train_tokens_per_s": "tokens/s", "setup_s": "s"}
CHECK_DRAWS = 8192       # draws checked against the reference per sweep


def layout_arrays(lay) -> dict:
    """The layout as the plain arrays the reference reads."""
    keys = ("tok_doc", "tok_wrd", "tok_valid", "tok_slot", "cell_of_tile",
            "doc_of_worker", "word_of_block")
    out = {k: np.asarray(getattr(lay, k)) for k in keys}
    out.update(W=lay.W, B=lay.B, L=lay.L, tile=lay.tile)
    return out


def chain_seeds(seed: int) -> tuple[int, int]:
    """(initial-assignment seed, first sweep seed) from the run's seed —
    any whole number; sweep seeds must fit a signed 32-bit integer."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return int(words[0]), int(words[1] & 0x3FFFFFFF)


def build(ctx):
    """Corpus, layout and trainer for the cell; host spans in ``ctx``."""
    from repro.data.corpus import Corpus
    from repro.data.sharding import build_layout
    cfg, chips = ctx.config, ctx.chips
    if int(cfg["workers"]) != chips:
        raise ValueError(f"{cfg['name']} is a ring of {cfg['workers']} "
                         f"workers, the cell asks for {chips} chips")
    with ctx.span("corpus"):
        doc_ids, word_ids = corpus_gen.generate(cfg)
    corpus = Corpus(doc_ids=doc_ids, word_ids=word_ids,
                    num_docs=int(cfg["num_docs"]),
                    num_words=int(cfg["vocab_size"]))
    with ctx.span("layout_build"):
        lay = build_layout(corpus, n_workers=chips,
                           T=int(cfg["num_topics"]),
                           n_blocks=int(cfg["blocks"]), layout=cfg["layout"],
                           tile=int(cfg["tile"]),
                           doc_tile=int(cfg["doc_tile"]))
    return corpus, lay, make_trainer(ctx, lay)


def make_trainer(ctx, lay):
    """The configuration's ``NomadLDA`` over ``lay``, on the cell's chips."""
    import jax
    from repro.core.nomad import NomadLDA
    cfg = ctx.config
    mesh = jax.make_mesh((ctx.chips,), ("worker",),
                         devices=ctx.devices[:ctx.chips])
    return NomadLDA(mesh=mesh, ring_axes=("worker",), layout=lay,
                    alpha=float(cfg["alpha"]), beta=float(cfg["beta"]),
                    sync_mode=cfg["sync_mode"], inner_mode=cfg["inner_mode"],
                    ring_mode=cfg["ring_mode"], interpret=ctx.interpret,
                    doc_tile=int(cfg["doc_tile"]))


def sweep_once(trainer, arrays, seed):
    import jax
    out = trainer.sweep(arrays, seed=seed)
    jax.block_until_ready(out)
    return out


class _Compiled(Exception):
    """Ends a warm-up call once its program is compiled."""


def warm_up(trainer, arrays, seed):
    """Compile, or load from the persistent cache, the program that
    ``trainer.sweep(arrays, seed)`` runs, at these argument shapes, without
    running it.  The program may change under the benchmark: where the
    trainer's sweep no longer goes through one jitted ``_sweep``, this runs
    one whole sweep instead and drops its result."""
    jitted = trainer.__dict__.get("_sweep")
    if not hasattr(jitted, "lower"):
        sweep_once(trainer, arrays, seed)
        return

    def compile_only(*args):
        jitted.lower(*args).compile()
        raise _Compiled
    trainer._sweep = compile_only
    try:
        sweep_once(trainer, arrays, seed)
    except _Compiled:
        pass
    finally:
        trainer._sweep = jitted


def sample_draws(sample_seed):
    """The tokens the check reads: call ``i`` gives the schedule indices
    of window sweep ``i``'s draws, out of ``n`` real tokens."""
    rng = np.random.default_rng([int(sample_seed), 7])
    return lambda n: rng.choice(n, size=min(CHECK_DRAWS, n), replace=False)


def sampled_draws(cfg, sch, zs, seeds, *, sample_seed):
    """Per window sweep ``zs[i] -> zs[i + 1]`` (chain seed ``seeds[i]``):
    ``(state, u, drawn)`` of the tokens :func:`sample_draws` picks — the
    counts each was drawn from, its uniform, and the topic the program
    drew."""
    T = int(cfg["num_topics"])
    draws = sample_draws(sample_seed)
    out = []
    for i, seed in enumerate(seeds):
        zb = np.asarray(zs[i]).reshape(-1)[sch.pos].astype(np.int64)
        za = np.asarray(zs[i + 1]).reshape(-1)[sch.pos].astype(np.int64)
        idx = draws(sch.pos.size)
        out.append((ref.visit_state(sch, zb, za, idx, T),
                    ref.uniforms(sch, idx, seed), za[idx]))
    return out


def gap_of(cfg, state, u, drawn) -> float:
    beta = float(cfg["beta"])
    return float(ref.draw_gap(state, u, drawn, alpha=float(cfg["alpha"]),
                              beta=beta,
                              beta_bar=beta * int(cfg["vocab_size"])).max())


def check(cfg, corpus, lay, zs, seeds, final, *, sample_seed):
    """Readings of the numbers compared, and the per-sweep draw gaps."""
    sch = ref.schedule(layout_arrays(lay))
    readings = {
        "layout_mismatch": ref.layout_mismatch(
            sch, corpus.doc_ids, corpus.word_ids, int(cfg["vocab_size"])),
        "count_mismatch": ref.count_mismatch(sch, zs[-1], *final),
    }
    gaps = [gap_of(cfg, *d) for d in sampled_draws(
        cfg, sch, zs, seeds, sample_seed=sample_seed)]
    readings["draw_gap"] = max(gaps)
    return readings, gaps


def run(ctx) -> dict:
    wl, cfg = ctx.workload, ctx.config
    corpus, lay, trainer = build(ctx)
    memory = {"corpus_and_layout": ctx.memory()}
    init_seed, seed = chain_seeds(ctx.seed)
    with ctx.span("init_arrays"):
        arrays = trainer.init_arrays(seed=init_seed)
    memory["state"] = ctx.memory()
    with ctx.span("warmup"):
        warm_up(trainer, arrays, seed)
    setup_s = ctx.setup_done()

    zs, seeds = [arrays["z"]], []
    with ctx.window() as win:
        while True:
            with ctx.span("sweep", trace=True):
                arrays = sweep_once(trainer, arrays, seed)
            zs.append(arrays["z"])
            seeds.append(seed)
            seed += 1
            if win.elapsed() >= ctx.seconds:
                break
    window_s = win.seconds
    memory["window"] = ctx.memory()
    peak = max(p for _, p in memory["window"])

    tokens = int(corpus.num_tokens)
    final = tuple(np.asarray(arrays[k]) for k in ("n_td", "n_wt", "n_t"))
    zs = [np.asarray(z) for z in zs]
    del arrays, trainer
    with ctx.span("check"):
        readings, gaps = check(cfg, corpus, lay, zs, seeds, final,
                               sample_seed=ctx.seed)
    limits = wl["limits"]
    bad_sweeps = sum(g > limits["draw_gap"] for g in gaps)
    if (readings["count_mismatch"] > limits["count_mismatch"]
            or readings["layout_mismatch"] > limits["layout_mismatch"]):
        bad_sweeps = max(bad_sweeps, 1)
    return {
        "e2e": {"train_tokens_per_s": tokens * len(seeds) / window_s
                / ctx.chips,
                "setup_s": setup_s},
        "readings": readings,
        "attempted": len(seeds),
        "failed": int(bad_sweeps),
        "memory_peak_bytes": peak,
        "facts": {"T": int(cfg["num_topics"]), "tokens": tokens,
                  "sweeps": len(seeds), "window_s": window_s,
                  "tokens_per_chip": tokens * len(seeds) / ctx.chips,
                  "stream_len": int(lay.stream_len), "J_max": int(lay.J_max),
                  "I_max": int(lay.I_max), "pad": float(lay.pad_fraction),
                  "memory": memory},
    }
