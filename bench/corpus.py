"""The benchmark's corpus generator: the LDA generative process on the device.

The same process as ``data/synthetic.make_corpus`` (paper §2): topics
φ_k ~ Dirichlet over a Zipf-tilted vocabulary, per-document θ_d ~
Dirichlet(α), log-normal document lengths, then a topic and a word per
token.  Kept here, apart from the program, so the yardstick cannot move
with it, and made cheap: every table lives on the device and the whole
draw is one jitted call, with no dense θ or φ on the host.

The configuration fixes the corpus: its ``corpus_seed`` draws the lengths
and the tokens, so every run of a cell trains on the same data, as
repeated training runs of one deployment do.  The run's ``--seed`` drives
the Gibbs chain instead (``traffic/train.py``).
"""
from __future__ import annotations

import functools

import numpy as np

__all__ = ["doc_lengths", "expected_word_share", "generate"]


def doc_lengths(cfg: dict) -> np.ndarray:
    """Document lengths: log-normal with the configuration's median and σ,
    at least one token each.  A function of the configuration alone."""
    rng = np.random.default_rng([int(cfg["corpus_seed"]), 1])
    raw = rng.lognormal(np.log(cfg["doc_len_median"]), cfg["doc_len_sigma"],
                        size=int(cfg["num_docs"]))
    return np.maximum(1, raw.astype(np.int64))


def expected_word_share(cfg: dict) -> np.ndarray:
    """Expected corpus share of each vocabulary rank (before the shuffle
    that assigns ranks to word ids): the mean of the topics' Dirichlet
    concentration, ``(β + β·V·zipf_k / Σ zipf) / (2·β·V)``."""
    V = int(cfg["vocab_size"])
    zipf = 1.0 / np.arange(1, V + 1) ** cfg["zipf_a"]
    conc = 1.0 + V * zipf / zipf.sum()
    return conc / conc.sum()


def _search(cdf, rows, u, width: int):
    """Per-token inverse CDF: ``#{j : cdf[rows, j] <= u}`` by binary search
    over each token's own row of the flat ``cdf`` (last entry 1)."""
    import jax.numpy as jnp
    from jax import lax
    lo = jnp.zeros_like(rows)
    hi = jnp.full_like(rows, width - 1)
    base = rows * width

    def step(_, lh):
        lo, hi = lh
        mid = (lo + hi) // 2
        right = cdf[base + mid] <= u
        return jnp.where(right, mid + 1, lo), jnp.where(right, hi, mid)

    lo, _ = lax.fori_loop(0, int(np.ceil(np.log2(width))) + 1, step,
                          (lo, hi))
    return jnp.minimum(lo, width - 1)


@functools.lru_cache(maxsize=None)
def _sampler(D: int, N: int, V: int, T: int, alpha: float, beta: float,
             zipf_a: float, topic_chunk: int):
    import jax
    import jax.numpy as jnp

    def cdf_rows(p):
        c = jnp.cumsum(p, axis=-1)
        return c / c[..., -1:]

    def draw(key, doc_ids):
        k_perm, k_phi, k_theta, k_z, k_w = jax.random.split(key, 5)
        zipf = 1.0 / jnp.arange(1, V + 1, dtype=jnp.float32) ** zipf_a
        zipf = jax.random.permutation(k_perm, zipf)
        conc = beta + beta * V * zipf / zipf.sum()
        # φ in topic chunks, so the peak is one chunk's draw, not T × V
        keys = jax.random.split(k_phi, T // topic_chunk)
        phi_cdf = jax.lax.map(
            lambda k: cdf_rows(jax.random.dirichlet(
                k, conc, (topic_chunk,))), keys).reshape(-1)
        theta_cdf = cdf_rows(jax.random.dirichlet(
            k_theta, jnp.full((T,), alpha, jnp.float32), (D,))).reshape(-1)
        z = _search(theta_cdf, doc_ids, jax.random.uniform(k_z, (N,)), T)
        return _search(phi_cdf, z, jax.random.uniform(k_w, (N,)), V)

    return jax.jit(draw)


def generate(cfg: dict):
    """``(doc_ids, word_ids)`` as host int32 arrays, documents in order.
    One jitted call on the default device, keyed by ``corpus_seed``."""
    import jax
    import jax.numpy as jnp
    lengths = doc_lengths(cfg)
    D, N = lengths.size, int(lengths.sum())
    T, V = int(cfg["num_topics"]), int(cfg["vocab_size"])
    doc_ids = np.repeat(np.arange(D, dtype=np.int32), lengths)
    draw = _sampler(D, N, V, T, float(cfg["alpha"]), float(cfg["beta"]),
                    float(cfg["zipf_a"]), min(T, 64))
    key = jax.random.key(int(cfg["corpus_seed"]))
    words = draw(key, jnp.asarray(doc_ids))
    return doc_ids, np.asarray(words).astype(np.int32)

