"""F+tree: the paper's O(log T) multinomial sampling structure (paper §3.1).

The F+tree is a complete binary tree over the ``T`` unnormalized multinomial
parameters ``p`` (leaves), where every internal node stores the sum of its two
children and the root stores the normalizer ``Σ_t p_t``.  It is represented
heap-style in a flat array ``F`` of length ``2T``:

    F[0]        unused (kept 0)
    F[1]        root = Σ p
    F[i]        internal node, children at 2i and 2i+1
    F[T + t]    leaf t, stores p_t          (t = 0..T-1)

Operations (all pure, jit/vmap/scan friendly):

    build(p)          Θ(T)        construct from parameters
    total(F)          Θ(1)        normalizer  (= F[1])
    sample(F, u01)    Θ(log T)    inverse-CDF draw, top-down traversal (Alg. 1)
    update(F, t, δ)   Θ(log T)    p_t += δ, bottom-up path add      (Alg. 2)
    leaves(F)         Θ(1)        view of p
    set_leaf(F,t,v)   Θ(log T)    p_t = v  (update with δ = v - p_t)

``T`` must be a power of two (paper's simplifying assumption); :func:`pad_pow2`
zero-pads arbitrary ``p``.  Zero-probability leaves are never returned by
``sample`` provided no negative leaves exist: the traversal refuses to enter
a zero-mass right subtree, so even ``u01`` so close to 1 that ``u01 * F[1]``
rounds up to ``F[1]`` in f32 (easy at large totals) lands on the last
positive leaf instead of falling off the right edge onto padding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "build",
    "depth",
    "leaves",
    "pad_pow2",
    "sample",
    "sample_batch",
    "set_leaf",
    "total",
    "update",
    "update_batch",
]


def _is_pow2(n: int) -> bool:
    return n >= 1 and (n & (n - 1)) == 0


def depth(T: int) -> int:
    """Tree depth = number of traversal steps = log2(T)."""
    if not _is_pow2(T):
        raise ValueError(f"F+tree size must be a power of two, got {T}")
    return T.bit_length() - 1


def pad_pow2(p: jax.Array) -> jax.Array:
    """Zero-pad the last axis of ``p`` up to the next power of two."""
    T = p.shape[-1]
    Tp = 1 << max(0, (T - 1).bit_length())
    if Tp == T:
        return p
    pad = [(0, 0)] * (p.ndim - 1) + [(0, Tp - T)]
    return jnp.pad(p, pad)


def build(p: jax.Array) -> jax.Array:
    """Construct an F+tree from unnormalized parameters ``p`` (paper eq. (3)).

    Works on the last axis; leading axes are batch.  Θ(T) work, built level by
    level with pairwise sums (vectorized — the paper's reverse-index loop).
    Each node is the single add ``left + right`` of its children, spelt as
    an elementwise add so no compiler can merge levels into a wider
    reduction — the Pallas kernels rebuild the same tree node by node.
    """
    T = p.shape[-1]
    if not _is_pow2(T):
        raise ValueError(f"F+tree size must be a power of two, got {T} "
                         "(use pad_pow2)")
    levels = [p]
    cur = p
    while cur.shape[-1] > 1:
        cur = cur[..., 0::2] + cur[..., 1::2]
        levels.append(cur)
    zero = jnp.zeros_like(p[..., :1])
    return jnp.concatenate([zero] + levels[::-1], axis=-1)


def total(F: jax.Array) -> jax.Array:
    """Normalizer Σ_t p_t — stored at the root."""
    return F[..., 1]


def leaves(F: jax.Array) -> jax.Array:
    """The parameter vector ``p`` (leaf values)."""
    T = F.shape[-1] // 2
    return F[..., T:]


def sample(F: jax.Array, u01: jax.Array) -> jax.Array:
    """Draw ``z = min{t : Σ_{s≤t} p_s > u}`` with ``u = u01 * F[1]`` (Alg. 1).

    ``F`` is a single tree (1-D); use :func:`sample_batch`/vmap for batches.
    Θ(log T): one gather + select per level.

    Edge guard: descending right additionally requires the right subtree to
    hold positive mass.  Without it, ``u = u01 * F[1]`` can round up to
    ``F[1]`` exactly (f32, large totals) and the walk marches off the right
    edge onto a zero-probability padded leaf.
    """
    T = F.shape[-1] // 2
    d = depth(T)
    u = u01 * F[1]

    def step(_, carry):
        i, u = carry
        left = F[2 * i]
        go_right = (u >= left) & (F[2 * i + 1] > 0)
        i = 2 * i + go_right.astype(i.dtype)
        u = jnp.where(go_right, u - left, u)
        return i, u

    i0 = jnp.asarray(1, dtype=jnp.int32)
    i, _ = lax.fori_loop(0, d, step, (i0, u))
    return i - T


@functools.partial(jax.jit, static_argnames=())
def sample_batch(F: jax.Array, u01: jax.Array) -> jax.Array:
    """Vectorized draws from one tree: ``u01`` is any-shape uniforms in [0,1).

    Same zero-mass-right-subtree guard as :func:`sample`.
    """
    T = F.shape[-1] // 2
    d = depth(T)
    u = u01 * F[1]
    i = jnp.ones_like(u, dtype=jnp.int32)

    def step(_, carry):
        i, u = carry
        left = F[2 * i]
        go_right = (u >= left) & (F[2 * i + 1] > 0)
        i = 2 * i + go_right.astype(i.dtype)
        u = jnp.where(go_right, u - left, u)
        return i, u

    i, _ = lax.fori_loop(0, d, step, (i, u))
    return i - T


def _path_indices(T: int, t: jax.Array) -> jax.Array:
    """Heap indices of leaf t and all its ancestors (incl. root), shape (d+1,)."""
    d = depth(T)
    node = t + T
    shifts = jnp.arange(d + 1, dtype=jnp.int32)
    return (node[..., None] >> shifts).astype(jnp.int32)


def update(F: jax.Array, t: jax.Array, delta: jax.Array) -> jax.Array:
    """``p_t += delta``: add ``delta`` to leaf t and every ancestor (Alg. 2)."""
    T = F.shape[-1] // 2
    idx = _path_indices(T, jnp.asarray(t))
    return F.at[idx].add(jnp.broadcast_to(delta, idx.shape).astype(F.dtype))


def update_batch(F: jax.Array, ts: jax.Array, deltas: jax.Array) -> jax.Array:
    """Batched updates ``p_{ts[k]} += deltas[k]``; duplicate paths accumulate."""
    T = F.shape[-1] // 2
    idx = _path_indices(T, ts)                      # (..., d+1)
    vals = jnp.broadcast_to(deltas[..., None], idx.shape).astype(F.dtype)
    return F.at[idx.reshape(-1)].add(vals.reshape(-1))


def set_leaf(F: jax.Array, t: jax.Array, value: jax.Array) -> jax.Array:
    """``p_t = value`` — the Alg. 3 form ``F.update(t, v - F[leaf(t)])``."""
    T = F.shape[-1] // 2
    cur = F[..., T + t]
    return update(F, t, value - cur)
