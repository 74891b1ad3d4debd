"""The shared prefix sum and the lane moves the kernels build on.

``core/prefix.py:prefix_sum`` fixes the association every kernel and
reference uses, and ``rbucket.pack`` compacts by moving values only; the
kernel-parity tests rely on both, and these pin the properties directly.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.prefix import flat_roll, prefix_sum, topic_tile
from repro.kernels.fused_sweep import rbucket

SIZES = [1, 8, 64, 128, 1024, 2048]


@pytest.mark.parametrize("n", SIZES)
def test_prefix_sum_is_a_prefix_sum_and_prefix_stable(n):
    """Close to cumsum, and the first m outputs of a length-n scan are
    bit-equal to a length-m scan (what lets a kernel scan a zero-padded
    tile and read a prefix)."""
    rng = np.random.default_rng(n)
    x = rng.random(n).astype(np.float32)
    got = np.asarray(prefix_sum(jnp.asarray(x)))
    np.testing.assert_allclose(got, np.cumsum(x), rtol=1e-5)
    for m in {1, n // 3 + 1, n}:
        part = np.asarray(prefix_sum(jnp.asarray(x[:m])))
        np.testing.assert_array_equal(got[:m].view(np.int32),
                                      part.view(np.int32))


@pytest.mark.parametrize("n", SIZES)
def test_flat_roll_matches_jnp_roll(n):
    x = np.arange(n, dtype=np.int32)
    tiles = jnp.asarray(x).reshape(topic_tile(n))
    for s in {0, 1, n // 2, n - 1}:
        np.testing.assert_array_equal(
            np.asarray(flat_roll(tiles, s)).reshape(-1), np.roll(x, s))


@pytest.mark.parametrize("n", SIZES)
def test_pack_equals_compact_row_gather(n):
    """The scatter-free compaction is bit-identical to ``compact_row``'s
    gather of ``counts · q[topics]``, and ``rank`` is each active
    topic's slot."""
    rng = np.random.default_rng(n + 1)
    for density in (0.0, 0.1, 0.7, 1.0):
        row = rng.integers(1, 4, n) * (rng.random(n) < density)
        q = rng.random(n).astype(np.float32)
        topics, counts = rbucket.compact_row(jnp.asarray(row), n)
        want = np.asarray(counts.astype(jnp.float32) * jnp.asarray(q)[topics])
        tile = topic_tile(n)
        packed, rank = rbucket.pack(
            jnp.asarray(row * q, jnp.float32).reshape(tile),
            jnp.asarray(row > 0).reshape(tile))
        np.testing.assert_array_equal(
            np.asarray(packed).reshape(-1).view(np.int32),
            want.view(np.int32))
        active = np.nonzero(row > 0)[0]
        np.testing.assert_array_equal(
            np.asarray(rank).reshape(-1)[active], np.arange(active.size))
