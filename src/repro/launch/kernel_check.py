"""Main-path kernels against their references, on the chip.

Usage:  python -m repro.launch.kernel_check

Sweeps one ragged token stream through the fused kernel twice — with the
doc-topic table whole in VMEM, and paged through VMEM in ``(doc_rows, T)``
slabs — and folds a batch of documents in with the fold-in kernel.  Each
result must equal its reference bit for bit: ``fused_sweep_ragged_ref``
(the scan oracle) for the sweeps, ``fold_in_batch`` for fold-in.

The stream pages slabs out of order and back again, and the tiles that
page a slab other than the first end in padding tokens (doc 0, word 0,
``valid = 0``), one of them wholly.  Doc 0 lies outside those slabs: it is
the case in which a slab row that is not clamped addresses memory outside
the slab.  Kernels run compiled on a TPU and interpreted elsewhere
(``ops.default_interpret``), at NYTimes widths (T = 1024) and the smoke's
tile and slab sizes.  Prints one JSON line; exits non-zero on a mismatch.
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

T = 1024
N_BLK = 256
DOC_ROWS = 512
SLABS = 4
WORDS = 512            # rows of each word-topic block
KW = dict(alpha=50.0 / T, beta=0.01, beta_bar=0.01 * 102_660)
# Tile → cell and tile → slab maps, and the padding tokens ending each tile.
CELL_OF_TILE = (0, 0, 0, 0, 1, 1, 1, 1, 2, 2, 2, 2)
SLAB_OF_TILE = (0, 1, 3, 2, 1, 0, 2, 3, 3, 2, 1, 0)
PAD_OF_TILE = (0, 37, 5, N_BLK, 1, 0, 64, 3, 11, 2, 7, 0)


def ragged_stream(T, n_blk, doc_rows, n_docs, n_words, cell_of_tile,
                  slab_of_tile, pad_of_tile, seed=0):
    """A ragged token stream and the count tables of its valid tokens.

    Tile ``t`` belongs to cell ``cell_of_tile[t]``; its valid tokens
    address docs of slab ``slab_of_tile[t]`` and its last
    ``pad_of_tile[t]`` tokens are padding (doc 0, word 0, ``valid = 0``).
    Tokens are sorted by word within each tile and every word change is a
    boundary.  Returns ``(cell_of_tile, slab_of_tile, tokens, tables)`` as
    device arrays: ``tokens = (doc, word, valid, boundary, z, u)``,
    ``tables = (n_td (n_docs, T), n_wt (cells, n_words, T), n_t (T,))``.
    """
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    cot = np.asarray(cell_of_tile, np.int32)
    dto = np.asarray(slab_of_tile, np.int32)
    S = cot.size * n_blk
    doc = np.repeat(dto, n_blk) * doc_rows + rng.integers(0, doc_rows, S)
    wrd = rng.integers(0, n_words, S)
    valid = np.ones(S, np.int32)
    for t, pad in enumerate(pad_of_tile):
        valid[(t + 1) * n_blk - pad:(t + 1) * n_blk] = 0
    doc[valid == 0] = 0
    wrd[valid == 0] = 0
    order = np.concatenate([np.argsort(wrd[t * n_blk:(t + 1) * n_blk],
                                       kind="stable") + t * n_blk
                            for t in range(cot.size)])
    doc, wrd, valid = doc[order], wrd[order], valid[order]
    bound = np.ones(S, np.int32)
    bound[1:] = wrd[1:] != wrd[:-1]
    z = rng.integers(0, T, S)
    u = rng.random(S).astype(np.float32)
    cell = np.repeat(cot, n_blk)
    n_td = np.zeros((n_docs, T), np.int32)
    n_wt = np.zeros((int(cot.max()) + 1, n_words, T), np.int32)
    n_t = np.zeros(T, np.int32)
    m = valid > 0
    np.add.at(n_td, (doc[m], z[m]), 1)
    np.add.at(n_wt, (cell[m], wrd[m], z[m]), 1)
    np.add.at(n_t, z[m], 1)
    i32 = lambda a: jnp.asarray(a, jnp.int32)
    toks = (i32(doc), i32(wrd), i32(valid), i32(bound), i32(z),
            jnp.asarray(u))
    return i32(cot), i32(dto), toks, (i32(n_td), i32(n_wt), i32(n_t))


def _mismatch(got, want) -> dict:
    names = ("z", "n_td", "n_wt", "n_t", "F")
    return {k: int(np.sum(np.asarray(a) != np.asarray(b)))
            for k, a, b in zip(names, got, want)}


def _timed(fn):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def check_sweeps() -> dict:
    """Whole-shard and doc-paged ragged sweeps vs the scan oracle."""
    from repro.kernels.fused_sweep import fused_sweep_ragged
    from repro.kernels.fused_sweep.ref import fused_sweep_ragged_ref
    cot, dto, toks, tables = ragged_stream(
        T, N_BLK, DOC_ROWS, SLABS * DOC_ROWS, WORDS, CELL_OF_TILE,
        SLAB_OF_TILE, PAD_OF_TILE)
    want, ref_s = _timed(lambda: fused_sweep_ragged_ref(
        *toks, cot, *tables, n_blk=N_BLK, **KW))
    out = {"tokens": int(toks[0].size),
           "padding": int(toks[0].size - np.asarray(toks[2]).sum()),
           "ref_s": ref_s}
    runs = {"whole": {}, "paged": dict(doc_tile_of=dto, doc_rows=DOC_ROWS)}
    for name, extra in runs.items():
        got, secs = _timed(lambda: fused_sweep_ragged(
            *toks, cot, *tables, n_blk=N_BLK, **extra, **KW))
        out[name] = {"s_with_compile": secs, "mismatch": _mismatch(got, want)}
    return out


def check_fold_in(docs=8, length=512, sweeps=3, vocab=2_000) -> dict:
    """The fold-in kernel vs ``fold_in_batch`` on one padded batch."""
    import jax
    import jax.numpy as jnp

    from repro.core.heldout import doc_fold_key, fold_in_batch
    from repro.kernels.fold_in import fold_in_fused
    rng = np.random.default_rng(1)
    phi = jnp.asarray(rng.dirichlet(np.ones(T), size=vocab)
                      .astype(np.float32))
    words = jnp.asarray(rng.integers(0, vocab, (docs, length)), jnp.int32)
    valid = jnp.asarray(rng.random((docs, length)) < 0.8)
    keys = jax.vmap(doc_fold_key, in_axes=(None, 0))(
        jax.random.key(0), jnp.arange(docs))
    alpha = KW["alpha"]
    want, ref_s = _timed(lambda: fold_in_batch(words, valid, phi, alpha,
                                               keys, sweeps))
    got, secs = _timed(lambda: fold_in_fused(words, valid, phi, alpha, keys,
                                             sweeps))
    return {"docs": docs, "length": length, "sweeps": sweeps,
            "ref_s": ref_s, "s_with_compile": secs,
            "mismatch": int(np.sum(np.asarray(got) != np.asarray(want)))}


def main() -> int:
    import jax

    from repro.kernels.fused_sweep.ops import default_interpret
    report = {"platform": jax.devices()[0].platform,
              "interpret": default_interpret(),
              "sweep": check_sweeps(), "fold_in": check_fold_in()}
    exact = (report["fold_in"]["mismatch"] == 0
             and all(not any(report["sweep"][k]["mismatch"].values())
                     for k in ("whole", "paged")))
    report["exact"] = exact
    print(json.dumps(report), flush=True)
    return 0 if exact else 1


if __name__ == "__main__":
    sys.exit(main())
