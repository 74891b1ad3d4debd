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

Pieces.  No Dirichlet draw has more than :data:`PIECE` elements.  θ is
drawn in pieces of documents (its rows are i.i.d.), φ in pieces of
``TOPIC_CHUNK`` topics by a slice of the vocabulary.  A vocabulary cut in
``S > 1`` slices uses the Dirichlet's aggregation property: for φ_k ~
Dir(c), the slice masses ``(M_k1 … M_kS) ~ Dir(Σ_{v∈s} c_v)`` are
independent of each slice's normalised vector ``~ Dir(c_s)``, so a token
of topic k draws its slice from ``M_k·`` (a second uniform) and then its
word from that slice alone.  Where every draw is one piece the process
and its keys are exactly as they were before pieces existed; where φ's
whole ``(T, V)`` table is small it is also held and searched at once
(:func:`_sampler`), and otherwise each piece is drawn, searched by the
tokens that fall in it and dropped (:func:`_streamed`), so no ``T × V``
array and no index of ``T·V`` exists.
"""
from __future__ import annotations

import functools

import numpy as np

__all__ = ["PIECE", "TOPIC_CHUNK", "doc_lengths", "expected_word_share",
           "generate"]

PIECE = 88 * 2**20
"""The most elements one Dirichlet draw of the generator has.  It is
PubMed's θ (90,112 documents × 1,024 topics), the largest draw of any
corpus generated before pieces existed, so each of those is still drawn
in one piece per draw.  JAX's gamma sampler keeps ≈ 90 B of temporaries
an element of a θ draw and ≈ 110 B of a φ draw, so a piece takes at most
≈ 10.2 GB.  Compiled for a described TPU v5e, the program at UMBC width
(φ pieces of 64 × 1,440,738 elements, at this bound) needs 11.5 GB in
all: inside the 13 GB of the chip's 15.75 GB that leave the runtime its
share."""

TOPIC_CHUNK = 64
"""Topics a φ draw covers."""


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


def _cdf_rows(p):
    import jax.numpy as jnp
    c = jnp.cumsum(p, axis=-1)
    return c / c[..., -1:]


def _concentration(k_perm, V: int, beta: float, zipf_a: float):
    """φ's Dirichlet concentration over word ids: the Zipf tilt, shuffled."""
    import jax
    import jax.numpy as jnp
    zipf = 1.0 / jnp.arange(1, V + 1, dtype=jnp.float32) ** zipf_a
    zipf = jax.random.permutation(k_perm, zipf)
    return beta + beta * V * zipf / zipf.sum()


@functools.lru_cache(maxsize=None)
def _sampler(D: int, N: int, V: int, T: int, alpha: float, beta: float,
             zipf_a: float, topic_chunk: int):
    """Every draw in one piece: φ's whole CDF held, each token searched
    once."""
    import jax
    import jax.numpy as jnp

    def draw(key, doc_ids):
        k_perm, k_phi, k_theta, k_z, k_w = jax.random.split(key, 5)
        conc = _concentration(k_perm, V, beta, zipf_a)
        # φ in topic chunks, so the peak is one chunk's draw, not T × V
        keys = jax.random.split(k_phi, T // topic_chunk)
        phi_cdf = jax.lax.map(
            lambda k: _cdf_rows(jax.random.dirichlet(
                k, conc, (topic_chunk,))), keys).reshape(-1)
        theta_cdf = _cdf_rows(jax.random.dirichlet(
            k_theta, jnp.full((T,), alpha, jnp.float32), (D,))).reshape(-1)
        z = _search(theta_cdf, doc_ids, jax.random.uniform(k_z, (N,)), T)
        return _search(phi_cdf, z, jax.random.uniform(k_w, (N,)), V)

    return jax.jit(draw)


def _piecewise(piece, n: int, x, u, table):
    """Per token, the inverse CDF of its own piece: ``table(i)`` draws
    piece ``i``'s ``(rows, width)`` CDF and gives the value of ``x`` at
    its first row.  Tokens are ordered by ``piece`` once, and each table
    is searched by its own tokens only, a window of ``ceil(N / n)`` at a
    time, then dropped.  Sorts and gathers only: with a ``bincount`` and
    a scatter of ``N`` updates in their place, an Amazon-width corpus took
    104.5 s on a TPU v5e, not 42.7 s."""
    import jax.numpy as jnp
    from jax import lax
    N = piece.shape[0]
    C = -(-N // n)
    iota = jnp.arange(N, dtype=jnp.int32)
    by_piece, order = lax.sort((piece, iota), num_keys=1)
    # piece i at [starts[i], starts[i + 1])
    starts = jnp.searchsorted(by_piece, jnp.arange(n + 1, dtype=piece.dtype))
    xs, us = x[order], u[order]

    def one(i, out):
        cdf, first = table(i)
        rows, width = cdf.shape
        cdf = cdf.reshape(-1)
        end = starts[i + 1]

        def window(st):
            pos, out = st
            at = jnp.minimum(pos, N - C)
            row = lax.dynamic_slice(xs, (at,), (C,)) - first
            hit = _search(cdf, jnp.clip(row, 0, rows - 1),
                          lax.dynamic_slice(us, (at,), (C,)), width)
            mine = (at + jnp.arange(C) >= pos) & (at + jnp.arange(C) < end)
            old = lax.dynamic_slice(out, (at,), (C,))
            return pos + C, lax.dynamic_update_slice(
                out, jnp.where(mine, hit, old), (at,))

        return lax.while_loop(lambda st: st[0] < end, window,
                              (starts[i], out))[1]

    found = lax.fori_loop(0, n, one, jnp.zeros((N,), jnp.int32))
    return found[lax.sort((order, iota), num_keys=1)[1]]


@functools.lru_cache(maxsize=None)
def _streamed(D: int, N: int, V: int, T: int, alpha: float, beta: float,
              zipf_a: float, topic_chunk: int, P: int, S: int):
    """θ in ``P`` pieces of documents and φ in pieces of ``topic_chunk``
    topics by ``S`` vocabulary slices, each drawn, searched by the tokens
    that fall in it and dropped (:func:`_piecewise`).  With ``P = S = 1``
    the draws, keys and answers are :func:`_sampler`'s."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    Dp, W = -(-D // P), -(-V // S)
    fold = jax.random.fold_in

    def draw(key, doc_ids):
        k_perm, k_phi, k_theta, k_z, k_w = jax.random.split(key, 5)
        conc = _concentration(k_perm, V, beta, zipf_a)
        alphas = jnp.full((T,), alpha, jnp.float32)

        def theta(p):
            k = k_theta if P == 1 else fold(k_theta, p)
            return _cdf_rows(jax.random.dirichlet(k, alphas, (Dp,))), p * Dp

        z = _piecewise(doc_ids // Dp, P, doc_ids,
                       jax.random.uniform(k_z, (N,)), theta)
        keys = jax.random.split(k_phi, T // topic_chunk)
        part = jnp.zeros((N,), jnp.int32)
        if S > 1:
            # each topic's slice masses, then each token's slice
            padded = jnp.pad(conc, (0, S * W - V))
            masses = jax.vmap(lambda k: jax.random.dirichlet(
                fold(k, S), padded.reshape(S, W).sum(1),
                (topic_chunk,)))(keys).reshape(T, S)
            part = _search(_cdf_rows(masses).reshape(-1), z,
                           jax.random.uniform(fold(k_w, 1), (N,)), S)
            conc = jnp.pad(conc, (0, S * W - V), constant_values=1.0)

        def phi(i):
            c, s = i // S, i % S
            if S == 1:
                p = jax.random.dirichlet(keys[c], conc, (topic_chunk,))
            else:   # the slice's own Dirichlet; its padded tail gets no mass
                real = s * W + jnp.arange(W) < V
                lg = jax.random.loggamma(
                    fold(keys[c], s), lax.dynamic_slice(conc, (s * W,), (W,)),
                    (topic_chunk, W))
                p = jax.nn.softmax(jnp.where(real, lg, -jnp.inf), axis=-1)
            return _cdf_rows(p), c * topic_chunk

        hit = _piecewise(z // topic_chunk * S + part, T // topic_chunk * S,
                         z, jax.random.uniform(k_w, (N,)), phi)
        return part * W + jnp.minimum(hit, V - 1 - part * W)

    return jax.jit(draw)


def _pieces(D: int, V: int, T: int, piece: int) -> tuple[int, int, int]:
    """``(topic_chunk, P, S)``: θ's document pieces and φ's vocabulary
    slices, as few as keep every draw within ``piece`` elements."""
    chunk = min(T, TOPIC_CHUNK)
    return (chunk, -(-D // max(1, piece // T)),
            -(-V // max(1, piece // chunk)))


def _program(D: int, N: int, V: int, T: int, alpha: float, beta: float,
             zipf_a: float, piece: int = PIECE):
    """The jitted ``draw(key, doc_ids) -> word_ids`` for these sizes.  φ's
    whole CDF is held while every draw is one piece and the table is at
    most two pieces (4 B an element beside a piece's temporaries)."""
    chunk, P, S = _pieces(D, V, T, piece)
    if P == S == 1 and T * V <= 2 * piece:
        return _sampler(D, N, V, T, alpha, beta, zipf_a, chunk)
    return _streamed(D, N, V, T, alpha, beta, zipf_a, chunk, P, S)


def generate(cfg: dict):
    """``(doc_ids, word_ids)`` as host int32 arrays, documents in order.
    One jitted call on the default device, keyed by ``corpus_seed``."""
    return _generate(cfg, PIECE)


def _generate(cfg: dict, piece: int):
    import jax
    import jax.numpy as jnp
    lengths = doc_lengths(cfg)
    D, N = lengths.size, int(lengths.sum())
    T, V = int(cfg["num_topics"]), int(cfg["vocab_size"])
    doc_ids = np.repeat(np.arange(D, dtype=np.int32), lengths)
    draw = _program(D, N, V, T, float(cfg["alpha"]), float(cfg["beta"]),
                    float(cfg["zipf_a"]), piece)
    key = jax.random.key(int(cfg["corpus_seed"]))
    words = draw(key, jnp.asarray(doc_ids))
    return doc_ids, np.asarray(words).astype(np.int32)
