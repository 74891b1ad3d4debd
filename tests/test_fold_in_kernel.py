"""Pallas fold-in kernel tests (DESIGN.md §10a).

The tentpole equality, factored in two:

* the draw precompute + pure-jnp oracle (`fold_in_kernel_ref`) is
  bit-identical to `core/heldout.py:fold_in_batch` — the counter-mode
  chains agree when hoisted out of the sweep loop;
* the Pallas kernel (`fold_in_pallas`, via the `fold_in_fused` wrapper)
  is bit-identical to that oracle — the kernel replays the chain
  faithfully across doc counts, length buckets, sweep counts, empty
  docs and garbage padding.

Wrapper policy (interpret default, VMEM budget, validation) rides the
same class.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.heldout import doc_fold_key, fold_in, fold_in_batch
from repro.kernels.fold_in import (fold_in_draws, fold_in_fused,
                                   fold_in_kernel_ref, fold_in_smem_bytes,
                                   fold_in_vmem_bytes)
from repro.kernels.fused_sweep.ops import (SMEM_BUDGET_BYTES,
                                           VMEM_SCOPED_DEFAULT_BYTES)

J, T = 31, 8
ALPHA = 0.375


@pytest.fixture(scope="module")
def phi():
    rng = np.random.default_rng(11)
    return jnp.asarray(rng.random((J, T), np.float32))


def _batch(seed, lengths, L):
    rng = np.random.default_rng(seed)
    D = len(lengths)
    w = rng.integers(0, J, (D, L)).astype(np.int32)
    v = np.arange(L)[None, :] < np.asarray(lengths)[:, None]
    return jnp.asarray(w), jnp.asarray(v)


def _keys(key, D):
    return jax.vmap(doc_fold_key, in_axes=(None, 0))(
        key, jnp.arange(D, dtype=jnp.int32))


class TestFoldInKernelParity:
    @pytest.mark.parametrize("lengths,L,sweeps", [
        ([0, 1, 5, 12], 16, 3),
        ([4], 4, 1),
        ([7, 7, 7, 7, 7, 7, 7, 7], 8, 2),
        ([0, 0], 8, 4),                      # all-empty batch
        ([30, 2], 32, 5),
    ])
    def test_fused_bitexact_vs_scan(self, phi, lengths, L, sweeps):
        w, v = _batch(0, lengths, L)
        dk = _keys(jax.random.key(7), len(lengths))
        ref = np.asarray(fold_in_batch(w, v, phi, ALPHA, dk, sweeps))
        z0, u = fold_in_draws(dk, L, T, sweeps)
        oracle = np.asarray(fold_in_kernel_ref(
            w, v, z0, u, jnp.float32(ALPHA), phi))
        fused = np.asarray(fold_in_fused(w, v, phi, ALPHA, dk, sweeps))
        np.testing.assert_array_equal(oracle, ref)
        np.testing.assert_array_equal(fused, ref)

    def test_fused_matches_serial_fold_in(self, phi):
        words = np.asarray([3, 3, 9, 14, 2], np.int32)
        key = jax.random.key(123)
        serial = np.asarray(fold_in(words, np.zeros(5, np.int32), 1, phi,
                                    ALPHA, key, sweeps=4))
        w = jnp.asarray(np.pad(words, (0, 3))[None])
        v = jnp.asarray((np.arange(8) < 5)[None])
        fused = np.asarray(fold_in_fused(
            w, v, phi, ALPHA, doc_fold_key(key, 0)[None], 4))
        np.testing.assert_array_equal(fused[0], serial[0])

    def test_padding_garbage_inert(self, phi):
        """Garbage word ids in padded slots and a wider L cannot perturb
        any row — same contract as fold_in_batch."""
        lengths = [3, 6]
        w, v = _batch(1, lengths, 8)
        dk = _keys(jax.random.key(3), 2)
        base = np.asarray(fold_in_fused(w, v, phi, ALPHA, dk, 3))
        w_g = np.asarray(w).copy()
        w_g[~np.asarray(v)] = J - 1
        garbage = np.asarray(fold_in_fused(
            jnp.asarray(w_g), v, phi, ALPHA, dk, 3))
        np.testing.assert_array_equal(base, garbage)
        w32, v32 = _batch(1, lengths, 32)
        w32 = np.asarray(w32).copy()
        w32[:, :8] = np.asarray(w)           # same real tokens
        wider = np.asarray(fold_in_fused(
            jnp.asarray(w32), v32, phi, ALPHA, dk, 3))
        np.testing.assert_array_equal(base, wider)

    def test_draws_match_reference_chains(self, phi):
        """z0/u are the exact arrays fold_in_batch derives internally:
        a doc keyed identically in two different batch positions draws
        identically (row RNG is batch-independent)."""
        dk = _keys(jax.random.key(5), 4)
        z0, u = fold_in_draws(dk, 8, T, 2)
        assert z0.shape == (4, 8) and z0.dtype == jnp.int32
        assert u.shape == (4, 2, 8) and u.dtype == jnp.float32
        z0b, ub = fold_in_draws(dk[2:3], 8, T, 2)
        np.testing.assert_array_equal(np.asarray(z0[2]), np.asarray(z0b[0]))
        np.testing.assert_array_equal(np.asarray(u[2]), np.asarray(ub[0]))
        assert (np.asarray(z0) >= 0).all() and (np.asarray(z0) < T).all()


class TestFoldInWrapper:
    def test_shape_validation(self, phi):
        w, v = _batch(0, [2, 2], 4)
        dk = _keys(jax.random.key(0), 2)
        with pytest.raises(ValueError, match="matching"):
            fold_in_fused(w, v[:1], phi, ALPHA, dk, 2)
        with pytest.raises(ValueError, match="keys"):
            fold_in_fused(w, v, phi, ALPHA, dk[:1], 2)
        with pytest.raises(ValueError, match="sweeps"):
            fold_in_fused(w, v, phi, ALPHA, dk, 0)

    def test_vmem_budget_guard_compiled_only(self, phi):
        w, v = _batch(0, [2, 2], 4)
        dk = _keys(jax.random.key(0), 2)
        # the token rows live in SMEM: their estimate grows with L and the
        # guard trips only on the compiled path; interpret mode must not
        # consult it
        assert fold_in_vmem_bytes(4, T, 2) < VMEM_SCOPED_DEFAULT_BYTES
        assert fold_in_smem_bytes(4) < SMEM_BUDGET_BYTES
        big_L = SMEM_BUDGET_BYTES  # the token rows alone blow the budget
        assert fold_in_smem_bytes(big_L) > SMEM_BUDGET_BYTES
        wide = jnp.zeros((1, big_L), jnp.int32)
        with pytest.raises(ValueError, match="SMEM budget"):
            fold_in_fused(wide, wide.astype(bool), phi, ALPHA, dk[:1],
                          2, interpret=False)

    def test_jittable_inside_theta_kernel(self, phi):
        """The wrapper traces under jit with alpha as a tracer (the
        engine's _theta_kernel passes buf.alpha as a traced arg)."""
        w, v = _batch(2, [3, 1], 4)
        dk = _keys(jax.random.key(1), 2)

        @jax.jit
        def run(w, v, phi, alpha, dk):
            return fold_in_fused(w, v, phi, alpha, dk, 2)

        got = np.asarray(run(w, v, phi, jnp.float32(ALPHA), dk))
        ref = np.asarray(fold_in_batch(w, v, phi, ALPHA, dk, 2))
        np.testing.assert_array_equal(got, ref)
