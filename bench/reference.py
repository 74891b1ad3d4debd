"""The plain reference that decides ``correct`` for a training cell.

Collapsed Gibbs sampling for LDA (paper eq. (2), decomposition (5)) in
float64 on the host, written from the paper and independent of the
program: it imports nothing of ``repro`` and takes none of its tables.
From the program it takes only what is checked — the assignments ``z``
before and after each sweep and the final count tables — and the
schedule the sweep follows, which it first verifies against the corpus
it generated itself.

What one sweep must be.  Every token is visited once, in the order the
schedule gives (ring round, then stream position), and draws its new
topic from the collapsed conditional at the moment of its visit:

    q_t = (n_wt + β)/(n_t + β̄),  r_t = n_td·q_t,  p_t = α·q_t + r_t

with the counts of every token visited before it at their new topic,
every token after it at their old one, and itself left out.  ``n_wt`` and
``n_td`` are exact (a word's block and a document live on one worker at a
time); ``n_t`` is the worker's working copy under the paper's s-token
protocol (Alg. 4), which :func:`_stoken_bases` replays.  The uniform
``u`` of a token is the counter-mode draw
``uniform(fold_in(fold_in(fold_in(key(seed), worker), round), uid))``
with ``uid = block·L + slot``; the topic is the one whose interval holds
``x = u·Σp`` when the r-bucket's intervals (active topics ascending) are
laid before the α·q-bucket's (all topics ascending).

The numbers compared:

* ``draw_gap`` — over a sample of tokens drawn from the run's seed, the
  widest distance from ``x`` to the interval of the topic the program
  drew, as a share of ``Σp``.  Rounding in float32 puts a few draws a
  hair outside; a wrong count, a wrong ``u``, a skipped token or a
  lower precision puts them far outside.
* ``count_mismatch`` — entries of the final ``n_td``, ``n_wt``, ``n_t``
  that differ from a recount of the final ``z`` (exact: limit 0).
* ``layout_mismatch`` — tokens by which the schedule's (document, word)
  multiset differs from the generated corpus (exact: limit 0), so a
  schedule that leaves tokens out cannot pass.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import numpy as np

__all__ = ["Schedule", "schedule", "layout_mismatch", "count_mismatch",
           "recount", "visit_state", "draw_gap", "pick"]


@dataclass
class Schedule:
    """Every real token of a ragged ``(W, W, S)`` stream layout, in flat
    position order: where it sits and when it is visited."""
    pos: np.ndarray      # flat index into (W, W, S)
    w: np.ndarray        # worker
    r: np.ndarray        # ring round of the visit
    s: np.ndarray        # position in the worker's round stream
    block: np.ndarray    # global word block
    doc: np.ndarray      # global document id
    word: np.ndarray     # global word id
    d_loc: np.ndarray    # row in the worker's n_td shard
    j_loc: np.ndarray    # row in the block's n_wt page
    uid: np.ndarray      # counter of the token's uniform
    W: int
    S: int
    order_doc: np.ndarray = None   # tokens by (doc, visit)
    order_word: np.ndarray = None  # tokens by (word, visit)
    order_run: np.ndarray = None   # tokens by (worker, round, position)

    @property
    def visit(self):
        return self.r.astype(np.int64) * self.S + self.s


def schedule(lay: dict) -> Schedule:
    """Decode the layout arrays (``tok_doc``, ``tok_wrd``, ``tok_valid``,
    ``tok_slot``, ``cell_of_tile``, ``doc_of_worker``, ``word_of_block``
    and the scalars ``W``, ``B``, ``L``, ``tile``)."""
    W, B, L, tile = (int(lay[k]) for k in ("W", "B", "L", "tile"))
    S = lay["tok_doc"].shape[-1]
    k = B // W
    pos = np.flatnonzero(np.asarray(lay["tok_valid"]).reshape(-1))
    w, c, s = pos // (W * S), (pos // S) % W, pos % S
    cell = np.asarray(lay["cell_of_tile"])[w, c, s // tile]
    block = c * k + cell
    d_loc = np.asarray(lay["tok_doc"]).reshape(-1)[pos]
    j_loc = np.asarray(lay["tok_wrd"]).reshape(-1)[pos]
    slot = np.asarray(lay["tok_slot"]).reshape(-1)[pos]
    sch = Schedule(
        pos=pos, w=w, r=(c - w) % W, s=s, block=block,
        doc=np.asarray(lay["doc_of_worker"])[w, d_loc],
        word=np.asarray(lay["word_of_block"])[block, j_loc],
        d_loc=d_loc, j_loc=j_loc,
        uid=(block.astype(np.int64) * L + slot), W=W, S=S)
    v = sch.visit
    sch.order_doc = np.lexsort((v, sch.doc))
    sch.order_word = np.lexsort((v, sch.word))
    sch.order_run = np.lexsort((sch.s, sch.r, sch.w))
    return sch


def layout_mismatch(sch: Schedule, doc_ids, word_ids, V: int) -> int:
    """Size of the symmetric difference between the schedule's and the
    corpus's (document, word) multisets."""
    a = np.sort(sch.doc.astype(np.int64) * V + sch.word)
    b = np.sort(np.asarray(doc_ids, np.int64) * V + word_ids)
    if a.size != b.size or a.size == 0:
        return abs(a.size - b.size) + int(a.size == 0)
    return int(np.count_nonzero(a != b))


def _flat(sch: Schedule, z, shapes):
    """Each token's flat index into tables of ``shapes`` (``n_td``,
    ``n_wt``, ``n_t``) under its topic in ``z``."""
    T = shapes[2][-1]
    zt = np.asarray(z).reshape(-1)[sch.pos].astype(np.int64)
    rows = (sch.w.astype(np.int64) * shapes[0][1] + sch.d_loc,
            sch.block.astype(np.int64) * shapes[1][1] + sch.j_loc,
            np.zeros_like(zt))
    return [r * T + zt for r in rows]


def _recounts(sch: Schedule, z, shapes):
    """Flat recounts of ``z`` into tables of ``shapes``; longer than a
    table where a row falls outside."""
    return [np.bincount(f, minlength=int(np.prod(shape)))
            for f, shape in zip(_flat(sch, z, shapes), shapes)]


def recount(sch: Schedule, z, shapes) -> list:
    """``n_td``, ``n_wt``, ``n_t`` of ``z``, in the program's shapes."""
    return [c.reshape(shape) for c, shape in
            zip(_recounts(sch, z, shapes), shapes)]


def _slab_mismatch(flat, table) -> int:
    """Entries of ``table`` that differ from the count of the flat indices
    ``flat``, one slab of its leading axis at a time, and 1 more where an
    index falls past its end: :func:`_recounts`'s answer without a whole
    recount of the table."""
    table = np.asarray(table)
    slabs = table.reshape(table.shape[0] if table.ndim > 1 else 1, -1)
    m = slabs.shape[1]
    inside = flat < table.size
    flat = np.sort(flat[inside])
    if flat.size and flat[0] < 0:
        raise ValueError("a recount index is negative")
    ends = np.searchsorted(flat, np.arange(1, slabs.shape[0] + 1) * m)
    bad, lo = int(not inside.all()), 0
    for i, hi in enumerate(ends):
        c = np.bincount(flat[lo:hi] - i * m, minlength=m)
        bad += int(np.count_nonzero(c != slabs[i]))
        lo = hi
    return bad


def count_mismatch(sch: Schedule, z, n_td, n_wt, n_t) -> int:
    """Entries of the three tables that differ from a recount of ``z``,
    each recounted one slab (a worker's ``n_td``, a block's ``n_wt``
    page) at a time."""
    tables = (n_td, n_wt, n_t)
    return sum(_slab_mismatch(f, t) for f, t in
               zip(_flat(sch, z, [np.shape(t) for t in tables]), tables))


@functools.lru_cache(maxsize=None)
def _uniform_fn():
    import jax

    def one(seed, w, r, uid):
        key = jax.random.fold_in(jax.random.key(seed), w)
        key = jax.random.fold_in(jax.random.fold_in(key, r), uid)
        return jax.random.uniform(key)
    return jax.jit(jax.vmap(one, in_axes=(None, 0, 0, 0)))


def uniforms(sch: Schedule, idx, seed: int) -> np.ndarray:
    """The counter-mode uniforms of tokens ``idx`` for sweep ``seed``,
    computed on the host's CPU device where there is one."""
    import jax
    try:
        dev = jax.devices("cpu")[0]
    except RuntimeError:
        dev = None
    args = [np.int32(seed), np.asarray(sch.w[idx], np.int32),
            np.asarray(sch.r[idx], np.int32),
            np.asarray(sch.uid[idx], np.uint32)]
    with jax.default_device(dev):
        return np.asarray(_uniform_fn()(*args), np.float64)


def _stoken_bases(sch: Schedule, zb, za, T: int) -> np.ndarray:
    """``(W, W, T)``: each worker's working ``n_t`` at the start of each
    round, replaying the s-token protocol (paper Alg. 4): a worker adds
    its own changes as it makes them; the worker holding the token after
    round ``r`` (``(w + r) % W == 0``) folds its changes since its last
    fold into the token and adopts it; then the token moves one place
    down the ring."""
    W = sch.W
    run = (sch.w.astype(np.int64) * W + sch.r) * T
    rd = (np.bincount(run + za, minlength=W * W * T)
          - np.bincount(run + zb, minlength=W * W * T)).reshape(W, W, T)
    n_t0 = np.bincount(zb, minlength=T)
    local = np.repeat(n_t0[None], W, 0)
    token = local.copy()
    mine, folded = np.zeros_like(local), np.zeros_like(local)
    base = np.zeros((W, W, T), np.int64)
    for r in range(W):
        base[:, r] = local
        local = local + rd[:, r]
        mine = mine + rd[:, r]
        for w in range(W):
            if (w + r) % W == 0:
                token[w] = token[w] + mine[w] - folded[w]
                local[w] = token[w]
                folded[w] = mine[w]
        token = np.roll(token, -1, axis=0)
    return base


def visit_state(sch: Schedule, zb, za, idx, T: int):
    """``(n_td, n_wt, n_t)``, each ``(len(idx), T)`` int64: the counts
    token ``idx[i]`` is drawn from, itself left out.  ``zb``/``za`` are
    per-token (schedule order) topics before and after the sweep."""
    m = len(idx)
    out = [np.zeros((m, T), np.int64) for _ in range(3)]
    for table, order, key in ((out[0], sch.order_doc, sch.doc),
                              (out[1], sch.order_word, sch.word)):
        inv = np.empty_like(order)
        inv[order] = np.arange(order.size)
        sk = key[order]
        for i, k in enumerate(idx):
            p = inv[k]
            a = np.searchsorted(sk, key[k], "left")
            b = np.searchsorted(sk, key[k], "right")
            table[i] = (np.bincount(za[order[a:p]], minlength=T)
                        + np.bincount(zb[order[p + 1:b]], minlength=T))
    # n_t: the round's base plus the worker's changes before the token
    base = _stoken_bases(sch, zb, za, T)
    order = sch.order_run
    inv = np.empty_like(order)
    inv[order] = np.arange(order.size)
    run = sch.w.astype(np.int64) * sch.W + sch.r
    run_sorted = run[order]
    cur_run, prev, moved = None, 0, np.zeros(T, np.int64)
    for i in np.argsort(inv[idx], kind="stable"):   # walk in visit order
        k = idx[i]
        p = inv[k]
        if run[k] != cur_run:
            cur_run = run[k]
            prev = np.searchsorted(run_sorted, cur_run, "left")
            moved[:] = 0
        seg = order[prev:p]
        moved += (np.bincount(za[seg], minlength=T)
                  - np.bincount(zb[seg], minlength=T))
        prev = p
        out[2][i] = base[sch.w[k], sch.r[k]] + moved
        out[2][i, zb[k]] -= 1
    return out


def _masses(n_td, n_wt, n_t, alpha, beta, beta_bar, xp=np, dtype=None):
    f = (lambda a: a.astype(dtype)) if dtype is not None else (
        lambda a: a.astype(np.float64))
    q = (f(n_wt) + f(xp.asarray(beta))) / (f(n_t) + f(xp.asarray(beta_bar)))
    return q, f(n_td) * q


def draw_gap(state, u, drawn, *, alpha, beta, beta_bar) -> np.ndarray:
    """Per token: how far ``x = u·Σp`` lies outside the interval of the
    topic ``drawn``, as a share of ``Σp`` (0 when inside)."""
    n_td, n_wt, n_t = state
    q, r = _masses(n_td, n_wt, n_t, alpha, beta, beta_bar)
    R, Q = r.sum(1), q.sum(1)
    Z = alpha * Q + R
    x = u * Z
    rows = np.arange(len(drawn))
    cum_r = np.cumsum(r, 1) - r
    cum_q = np.cumsum(q, 1) - q
    lo_r, w_r = cum_r[rows, drawn], r[rows, drawn]
    lo_q = R + alpha * cum_q[rows, drawn]
    w_q = alpha * q[rows, drawn]

    def dist(lo, width):
        return np.maximum(np.maximum(lo - x, x - (lo + width)), 0.0)

    d_r = np.where(w_r > 0, dist(lo_r, w_r), np.inf)
    return np.minimum(d_r, dist(lo_q, w_q)) / Z


def pick(state, u, *, alpha, beta, beta_bar, dtype):
    """The topic the same draw picks when computed in ``dtype`` (a jnp
    dtype): the control, one precision below the configuration's.  Runs
    on JAX's default device."""
    import jax.numpy as jnp
    n_td, n_wt, n_t = (jnp.asarray(a, jnp.int32) for a in state)
    q, r = _masses(n_td, n_wt, n_t, alpha, beta, beta_bar, xp=jnp,
                   dtype=dtype)
    cr = jnp.cumsum(r, 1, dtype=dtype)
    cq = jnp.cumsum(jnp.asarray(alpha, dtype) * q, 1, dtype=dtype)
    R = cr[:, -1:]
    x = jnp.asarray(u, dtype)[:, None] * (R + cq[:, -1:])
    in_r = x < R
    t_r = jnp.sum(cr <= x, 1)
    t_q = jnp.sum(cq <= x - R, 1)
    T = q.shape[1]
    return np.asarray(jnp.minimum(jnp.where(in_r[:, 0], t_r, t_q), T - 1))
