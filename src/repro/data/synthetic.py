"""Synthetic corpora drawn from the LDA generative process (paper §2).

Used for all experiments (no network access): topics φ_k ~ Dirichlet(β) over
a Zipf-weighted vocabulary, per-document θ_i ~ Dirichlet(α), document lengths
log-normal — mimicking the UCI bag-of-words statistics (Enron/NyTimes scale
is reachable by turning the knobs).
"""
from __future__ import annotations

import numpy as np

from repro.data.corpus import Corpus

__all__ = ["make_corpus", "SyntheticCorpusSpec"]


def make_corpus(
    *,
    num_docs: int,
    vocab_size: int,
    num_topics: int,
    mean_doc_len: float = 80.0,
    alpha: float = 0.1,
    beta: float = 0.01,
    zipf_a: float = 1.1,
    seed: int = 0,
) -> tuple[Corpus, np.ndarray, np.ndarray]:
    """Sample (corpus, true_theta, true_phi) from the LDA generative process.

    Vocabulary gets a Zipf tilt on top of Dirichlet(β) topics so word
    frequencies are realistically skewed (important: the nomad word-block
    load balancing is only interesting under skew).
    """
    rng = np.random.default_rng(seed)
    # Topic-word distributions with Zipf prior tilt.
    zipf = 1.0 / np.arange(1, vocab_size + 1) ** zipf_a
    rng.shuffle(zipf)
    phi = rng.dirichlet(np.full(vocab_size, beta) + beta * vocab_size *
                        zipf / zipf.sum(), size=num_topics)
    theta = rng.dirichlet(np.full(num_topics, alpha), size=num_docs)

    lengths = np.maximum(
        1, rng.lognormal(np.log(mean_doc_len), 0.6, size=num_docs).astype(int))
    N = int(lengths.sum())
    doc_ids = np.repeat(np.arange(num_docs, dtype=np.int32), lengths)
    # Topic per token, then word per token — vectorized inverse-CDF draws.
    z = _sample_rows(rng, theta, doc_ids)
    word_ids = _sample_rows(rng, phi, z).astype(np.int32)
    return (Corpus(doc_ids=doc_ids, word_ids=word_ids,
                   num_docs=num_docs, num_words=vocab_size),
            theta, phi)


def _sample_rows(rng: np.random.Generator, table: np.ndarray,
                 rows: np.ndarray) -> np.ndarray:
    """Draw one categorical sample from ``table[rows[k]]`` for each k.

    Token ``k`` takes ``#{j : cdf[rows[k], j] + k ≤ u_k + k}`` (float64
    sums) — the inverse CDF of its row with both sides offset by ``k``,
    the form a single ``searchsorted`` over all rows laid end to end
    needs.  Tokens are grouped by row so memory stays ``O(table + N)``
    at any corpus size (the end-to-end layout would be ``N × cols``).
    """
    cdf = np.cumsum(table, axis=1)
    cdf /= cdf[:, -1:]
    n, cols = rows.shape[0], table.shape[1]
    u = rng.random(n)
    k = np.arange(n, dtype=np.float64)
    target = u + k
    out = np.empty(n, np.int64)
    order = np.argsort(rows, kind="stable")
    bounds = np.searchsorted(rows[order], np.arange(table.shape[0] + 1))
    for r in range(table.shape[0]):
        idx = order[bounds[r]:bounds[r + 1]]
        if idx.size == 0:
            continue
        c, kk, tt = cdf[r], k[idx], target[idx]
        j = np.searchsorted(c, u[idx], side="right")
        # The offset sums round: settle j on the exact offset count.
        while True:
            up = (j < cols) & (c[np.minimum(j, cols - 1)] + kk <= tt)
            if not up.any():
                break
            j += up
        while True:
            down = (j > 0) & (c[np.maximum(j - 1, 0)] + kk > tt)
            if not down.any():
                break
            j -= down
        out[idx] = np.minimum(j, cols - 1)
    return out.astype(np.int32)


class SyntheticCorpusSpec:
    """Named corpus presets scaled down from the paper's Table 3."""

    PRESETS = {
        # name: (num_docs, vocab, topics, mean_len)  — scaled-down analogues
        "enron-xs": (400, 512, 16, 60.0),
        "enron-sm": (2_000, 2_048, 64, 80.0),
        "nytimes-sm": (6_000, 4_096, 64, 120.0),
        "pubmed-sm": (20_000, 8_192, 128, 90.0),
    }

    @classmethod
    def make(cls, name: str, seed: int = 0):
        d, v, t, ml = cls.PRESETS[name]
        return make_corpus(num_docs=d, vocab_size=v, num_topics=t,
                           mean_doc_len=ml, seed=seed)
