"""The one prefix sum every sampler chain shares (kernels and references).

``jnp.cumsum`` has no Mosaic lowering, and XLA's cumsum associates its
partial sums in a backend-chosen order, so a Pallas kernel cannot
reproduce it bit for bit.  :func:`prefix_sum` fixes the association
instead.  A topic vector of length ``n`` is laid out the way the TPU
holds it — :func:`topic_tile`: ``R`` rows of ``C = min(n, 128)`` lanes,
row-major — and scanned in two Hillis–Steele log-step stages:

* within each row, ``x ← x + shift(x, s)`` for ``s = 1, 2, 4, … < C``
  (``shift(x, s)[j] = x[j − s]`` for ``j ≥ s`` and ``0`` below);
* across rows, the same scan over the row totals, whose exclusive form
  is then added to every lane of the row.

The result is a pure function of those adds, so any two implementations
that perform them — XLA on a reference path, a Pallas kernel with
``pltpu.roll`` plus iota masks — give identical bits.  Position ``j``
only ever adds entries ``≤ j``, and its adds do not depend on ``n``, so
the first ``m`` outputs of a length-``n`` scan equal a length-``m`` scan
of the first ``m`` inputs: a kernel may scan a zero-padded ``(R, C)``
tile and read a prefix of it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["LANES", "flat_roll", "prefix_sum", "prefix_sum_tiles",
           "topic_iota", "topic_tile", "tpu_roll"]

LANES = 128


def topic_tile(n: int) -> tuple[int, int]:
    """``(R, C)``: a length-``n`` topic vector as ``R`` rows of ``C``
    lanes (``n`` must be a whole number of rows past 128)."""
    C = min(n, LANES)
    if n % C:
        raise ValueError(f"a length-{n} vector is not a whole number of "
                         f"{C}-lane rows")
    return n // C, C


def topic_iota(shape):
    """Each entry's topic: its row-major index in an ``(R, C)`` tile."""
    return _iota(shape, -2) * shape[-1] + _iota(shape, -1)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis % len(shape))


def _jnp_roll(x, s, axis):
    return jnp.roll(x, s, axis)


def tpu_roll(x, s, axis):
    """``jnp.roll`` spelt for a Pallas TPU body."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.roll(x, s, axis % x.ndim)


def prefix_sum_tiles(x, roll=_jnp_roll):
    """Inclusive prefix sum of ``(..., R, C)`` tiles in row-major order
    (the association described in the module docstring)."""
    R, C = x.shape[-2:]
    lane, row = _iota(x.shape, -1), _iota(x.shape, -2)
    zero = jnp.zeros_like(x)
    s = 1
    while s < C:
        x = x + jnp.where(lane >= s, roll(x, s, -1), zero)
        s *= 2
    if R > 1:
        last = jnp.where(lane == C - 1, x, zero)      # one nonzero: exact
        tot = jnp.broadcast_to(jnp.sum(last, axis=-1, keepdims=True),
                               x.shape)
        s = 1
        while s < R:
            tot = tot + jnp.where(row >= s, roll(tot, s, -2), zero)
            s *= 2
        x = x + jnp.where(row >= 1, roll(tot, 1, -2), zero)
    return x


def prefix_sum(x):
    """Inclusive prefix sum along the last axis of a flat ``(..., n)``
    array, with the tiled association of :func:`prefix_sum_tiles`."""
    n = x.shape[-1]
    C = min(n, LANES)
    R = -(-n // C)
    lead = x.shape[:-1]
    pad = [(0, 0)] * len(lead) + [(0, R * C - n)]
    tiles = jnp.pad(x, pad).reshape(lead + (R, C))
    return prefix_sum_tiles(tiles).reshape(lead + (R * C,))[..., :n]


def flat_roll(x, s: int, roll=_jnp_roll):
    """``jnp.roll`` of the row-major flattening of ``(..., R, C)`` tiles
    by ``s`` places, built from a lane roll and sublane rolls."""
    R, C = x.shape[-2:]
    q, r = divmod(s % (R * C), C)

    def rows(y, k):
        return roll(y, k % R, -2) if R > 1 and k % R else y

    y = roll(x, r, -1) if r else x
    if not r:
        return rows(y, q)
    return jnp.where(_iota(x.shape, -1) >= r, rows(y, q), rows(y, q + 1))
