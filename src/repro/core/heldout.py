"""Held-out evaluation and fold-in inference: the φ-frozen Gibbs primitives.

The paper evaluates training log-likelihood (§5, following Yahoo!LDA); the
standard complementary check in the LDA literature is document completion:
hold out a set of documents, estimate each held-out document's θ from the
first half of its tokens (Gibbs with the trained φ frozen), then score the
second half:

    perplexity = exp( − Σ log p(w | θ̂, φ̂) / N_second_half )

φ̂ is the posterior mean from the trained counts:
    φ̂_tw = (n_wt + β) / (n_t + Jβ)
θ̂ from the fold-in counts:  θ̂_dt = (n_td + α) / (n_d + Tα).

The same φ-frozen fold-in is the *serving* algorithm (DESIGN.md §10): an
incoming document's θ is exactly a fold-in against a published φ snapshot.
Two implementations share one chain:

* :func:`fold_in` — the serial reference: a flat ``(word_ids, doc_ids)``
  token list, one ``lax.scan`` over all tokens.
* :func:`fold_in_batch` — the serving hot path: a padded ``(D, L)`` doc
  batch swept by one vmapped multi-sweep kernel
  (``repro.serve.lda_engine`` batches requests into it).

**RNG contract (what makes them bit-identical per document):** every draw
is counter-mode per (document stream, position-in-document[, sweep]) —
``doc_fold_key(key, d)`` names document ``d``'s stream, and within it
position ``p``'s init assignment and sweep-``k`` uniform are derived by
``fold_in`` chains, never by array-shaped draws.  A document's chain
therefore depends only on its own stream key and its own tokens — not on
the batch it rides in, the padding around it, or the other documents in a
flat serial call — so a batched padded row reproduces the serial path
bit-for-bit (``tests/test_serving.py`` pins this, hypothesis-style).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.prefix import prefix_sum
from repro.core.samplers import lsearch_guarded
from repro.data.corpus import Corpus

__all__ = ["document_completion_perplexity", "fold_in", "fold_in_batch",
           "doc_fold_key", "theta_from_counts"]

# Role indices of the two per-document RNG sub-streams.
_ROLE_INIT = 0    # initial z assignments
_ROLE_SWEEP = 1   # per-sweep LSearch uniforms


def _phi_hat(n_wt, n_t, beta):
    J = n_wt.shape[0]
    return ((n_wt.astype(jnp.float32) + beta)
            / (n_t.astype(jnp.float32)[None, :] + J * beta))  # (J,T)


def doc_fold_key(key, d):
    """Document ``d``'s fold-in RNG stream under ``key``.

    :func:`fold_in` derives it internally as ``fold_in(key, doc_id)``; a
    :func:`fold_in_batch` row keyed with ``doc_fold_key(key, d)`` runs the
    bit-identical chain to serial document ``d`` under ``key`` — the
    contract the serving engine uses to stay provably exact.
    """
    return jax.random.fold_in(key, d)


def theta_from_counts(n_td, alpha):
    """Posterior-mean θ rows from fold-in counts: (n+α)/(Σn+Tα).

    Shared by the perplexity path and the serving engine so their float
    ops agree bit-for-bit on equal counts.  All-zero rows (empty
    documents) come out uniform 1/T.
    """
    T = n_td.shape[-1]
    n_d = n_td.sum(-1, keepdims=True)
    return ((n_td.astype(jnp.float32) + alpha)
            / (n_d.astype(jnp.float32) + T * alpha))


def _positions_in_doc(doc_ids: np.ndarray) -> np.ndarray:
    """Occurrence rank of each token within its document (host-side).

    Stable in input order for any interleaving: token i's position is the
    number of earlier tokens with the same doc id.
    """
    n = doc_ids.shape[0]
    order = np.argsort(doc_ids, kind="stable")
    sorted_ids = doc_ids[order]
    idx = np.arange(n, dtype=np.int32)
    is_start = np.ones(n, bool)
    is_start[1:] = sorted_ids[1:] != sorted_ids[:-1]
    start = np.maximum.accumulate(np.where(is_start, idx, 0))
    pos = np.empty(n, np.int32)
    pos[order] = idx - start
    return pos


def _validate_fold_in(word_ids, doc_ids, num_docs, num_words):
    """Explicit ValueErrors (mirroring ``data/corpus.py``): fold-in inputs
    arrive from serving requests and held-out splits, not just code."""
    d, w = np.asarray(doc_ids), np.asarray(word_ids)
    if d.ndim != 1 or d.shape != w.shape:
        raise ValueError(
            f"word_ids/doc_ids must be 1-D parallel arrays; got shapes "
            f"{w.shape} and {d.shape}")
    if num_docs < 1:
        raise ValueError(
            f"fold_in needs num_docs >= 1, got {num_docs} (an empty "
            f"fold-in corpus has no θ to estimate)")
    if d.size == 0:
        raise ValueError(
            "fold_in got an empty token list; a document with no tokens "
            "is served by fold_in_batch as an all-False mask row (its θ "
            "is the uniform α prior), not by the serial path")
    if int(d.min()) < 0 or int(d.max()) >= num_docs:
        raise ValueError(
            f"doc_ids out of range [0, {num_docs}): "
            f"[{d.min()}, {d.max()}]")
    if int(w.min()) < 0 or int(w.max()) >= num_words:
        raise ValueError(
            f"word_ids out of range [0, {num_words}) (φ has {num_words} "
            f"rows): [{w.min()}, {w.max()}]")


def _fold_in_core(word_ids, doc_ids, pos, phi, alpha, key, *,
                  num_docs: int, sweeps: int):
    """Jittable serial fold-in body (validation and position ranking live
    in :func:`fold_in`; harnesses jit this directly for repeated
    fixed-shape reference runs)."""
    T = phi.shape[1]
    dk = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(key, doc_ids)
    ik = jax.vmap(jax.random.fold_in, in_axes=(0, None))(dk, _ROLE_INIT)
    ik = jax.vmap(jax.random.fold_in)(ik, pos)
    z = jax.vmap(
        lambda kk: jax.random.randint(kk, (), 0, T, dtype=jnp.int32))(ik)
    n_td = jnp.zeros((num_docs, T), jnp.int32).at[doc_ids, z].add(1)
    sk = jax.vmap(jax.random.fold_in, in_axes=(0, None))(dk, _ROLE_SWEEP)
    N = word_ids.shape[0]

    def sweep(carry, k):
        z, n_td = carry
        uk = jax.vmap(jax.random.fold_in, in_axes=(0, None))(sk, k)
        uk = jax.vmap(jax.random.fold_in)(uk, pos)
        u = jax.vmap(jax.random.uniform)(uk)

        def step(c, inp):
            z, n_td = c
            i, u01 = inp
            d, w, t_old = doc_ids[i], word_ids[i], z[i]
            n_td = n_td.at[d, t_old].add(-1)
            p = (n_td[d].astype(jnp.float32) + alpha) * phi[w]
            cdf = prefix_sum(p)
            # Guarded LSearch: u01·cdf[-1] shares the prefix sum, so
            # overrun needs u01·M to round up to M — impossible for
            # u01 ≤ 1−2⁻²⁴ f32 — but the guard also covers all-zero φ
            # rows, where a clip would silently select topic T−1 with
            # zero mass.
            t_new = lsearch_guarded(cdf, u01 * cdf[-1])
            n_td = n_td.at[d, t_new].add(1)
            z = z.at[i].set(t_new)
            return (z, n_td), None

        (z, n_td), _ = lax.scan(step, (z, n_td),
                                (jnp.arange(N, dtype=jnp.int32), u))
        return (z, n_td), None

    (z, n_td), _ = lax.scan(sweep, (z, n_td),
                            jnp.arange(sweeps, dtype=jnp.int32))
    return n_td


def fold_in(word_ids, doc_ids, num_docs, phi, alpha, key, sweeps: int = 20):
    """Gibbs fold-in with φ frozen: sample z for held-out tokens, return
    per-doc topic counts.  word_ids/doc_ids: (N,) flat token list (any
    interleaving; within-document order is the chain order).

    Raises ``ValueError`` on an empty token list, ``num_docs < 1``, or
    out-of-range ids — serving requests and held-out splits must fail
    loudly, not fold garbage (mirrors ``data/corpus.py`` validation).

    RNG: each document runs its own counter-mode stream (see the module
    docstring), so per-document results are independent of the other
    documents in the call and bit-reproducible by :func:`fold_in_batch`.
    """
    _validate_fold_in(word_ids, doc_ids, num_docs, phi.shape[0])
    pos = jnp.asarray(_positions_in_doc(np.asarray(doc_ids)))
    return _fold_in_core(jnp.asarray(word_ids), jnp.asarray(doc_ids), pos,
                         phi, alpha, key, num_docs=int(num_docs),
                         sweeps=int(sweeps))


def fold_in_batch(word_ids, valid, phi, alpha, doc_keys, sweeps: int = 20):
    """Padded-batch fold-in — the serving hot path.

    word_ids: (D, L) int32 padded word ids; valid: (D, L) bool mask;
    doc_keys: (D,) per-document stream keys (``doc_fold_key``).  Returns
    (D, T) int32 fold-in counts.

    Row ``d`` is **bit-identical** to the serial path on that document
    alone: ``fold_in(words, zeros, 1, phi, alpha, key)`` with
    ``doc_keys[d] == doc_fold_key(key, 0)``.  Padded positions are inert
    by construction — they draw from their own counter-mode slots (the
    draws are discarded), add 0 to every count, and re-assign ``z`` to
    itself — so growing L or changing the garbage in padded word slots
    cannot perturb a row.  An all-False row (empty document) returns a
    zero count row (θ becomes the uniform α prior).  Fully jittable:
    validation here is shape-only.
    """
    if word_ids.ndim != 2 or word_ids.shape != valid.shape:
        raise ValueError(
            f"word_ids/valid must be matching (D, L) arrays; got "
            f"{word_ids.shape} and {valid.shape}")
    if doc_keys.shape[0] != word_ids.shape[0]:
        raise ValueError(
            f"doc_keys carries {doc_keys.shape[0]} keys for "
            f"{word_ids.shape[0]} rows")
    T = phi.shape[1]
    L = word_ids.shape[1]
    pos = jnp.arange(L, dtype=jnp.int32)

    def one_doc(words, mask, dk):
        ik = jax.random.fold_in(dk, _ROLE_INIT)
        tk = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(ik, pos)
        z = jax.vmap(
            lambda kk: jax.random.randint(kk, (), 0, T,
                                          dtype=jnp.int32))(tk)
        v = mask.astype(jnp.int32)
        n_td = jnp.zeros((T,), jnp.int32).at[z].add(v)
        sk = jax.random.fold_in(dk, _ROLE_SWEEP)

        def sweep(carry, k):
            z, n_td = carry
            ks = jax.random.fold_in(sk, k)
            uk = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(ks, pos)
            u = jax.vmap(jax.random.uniform)(uk)

            def step(c, inp):
                z, n_td = c
                i, u01, vi = inp
                w, t_old = words[i], z[i]
                n_td = n_td.at[t_old].add(-vi)
                p = (n_td.astype(jnp.float32) + alpha) * phi[w]
                cdf = prefix_sum(p)
                t_new = lsearch_guarded(cdf, u01 * cdf[-1])
                t_new = jnp.where(vi > 0, t_new, t_old)
                n_td = n_td.at[t_new].add(vi)
                z = z.at[i].set(t_new)
                return (z, n_td), None

            (z, n_td), _ = lax.scan(step, (z, n_td), (pos, u, v))
            return (z, n_td), None

        (z, n_td), _ = lax.scan(sweep, (z, n_td),
                                jnp.arange(sweeps, dtype=jnp.int32))
        return n_td

    return jax.vmap(one_doc)(word_ids, valid, doc_keys)


def document_completion_perplexity(
        heldout: Corpus, n_wt, n_t, *, alpha: float, beta: float,
        key=None, fold_sweeps: int = 20) -> float:
    """Split each held-out doc's tokens in half (alternating positions),
    fold in on the first half, score the second half.

    A corpus of single-token documents puts every token in the
    estimation half: the score half is empty, the log-likelihood sum is
    0 over 0 tokens, and the perplexity is exactly 1.0 — *not* a raise
    through :func:`fold_in`'s empty-token ValueError, which only an
    entirely token-free corpus can trigger (``tests/test_serving.py``
    pins this edge)."""
    key = jax.random.key(0) if key is None else key
    phi = _phi_hat(jnp.asarray(n_wt), jnp.asarray(n_t), beta)   # (J,T)

    order = heldout.doc_order()
    # alternate within each document: even position → estimation half
    pos_in_doc = _positions_in_doc(heldout.doc_ids[order])
    first = (pos_in_doc % 2 == 0)
    est_idx, score_idx = order[first], order[~first]

    n_td = fold_in(jnp.asarray(heldout.word_ids[est_idx]),
                   jnp.asarray(heldout.doc_ids[est_idx]),
                   heldout.num_docs, phi, alpha, key, fold_sweeps)
    theta = theta_from_counts(n_td, alpha)                      # (I,T)

    w = jnp.asarray(heldout.word_ids[score_idx])
    d = jnp.asarray(heldout.doc_ids[score_idx])
    p_tok = jnp.einsum("nt,nt->n", theta[d], phi[w])
    ll = jnp.log(jnp.maximum(p_tok, 1e-30)).sum()
    return float(jnp.exp(-ll / max(len(score_idx), 1)))
