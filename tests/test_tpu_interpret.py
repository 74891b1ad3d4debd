"""The main-path kernels under Pallas's TPU interpreter.

``interpret=True`` runs a kernel as plain XLA, which hides TPU memory
semantics.  ``pltpu.InterpretParams`` simulates them instead: DMAs and
semaphores, scratch that starts as NaN, and cross-access race checks.
Each kernel must still match its reference bit for bit, with padding
tokens (doc 0, word 0) in tiles that page doc slabs other than the first.
(The interpreter does not report out-of-range VMEM rows; the slab clamp
that keeps padding rows in range is pinned in ``test_doc_tiling.py``.)
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.core.heldout import doc_fold_key
from repro.kernels.fold_in import fold_in_draws, fold_in_kernel_ref
from repro.kernels.fold_in.fold_in import fold_in_pallas
from repro.kernels.fused_sweep.fused_sweep import (
    fused_sweep_ragged_docs_pallas, fused_sweep_ragged_pallas)
from repro.kernels.fused_sweep.ref import fused_sweep_ragged_ref
from repro.launch import kernel_check
from repro.launch.kernel_check import ragged_stream

T, N_BLK, I, J, DOC_ROWS = 16, 8, 32, 6, 8
KW = dict(alpha=0.5, beta=0.01, beta_bar=0.06)


def _interpreter():
    return pltpu.InterpretParams(detect_races=True,
                                 uninitialized_memory="nan")


def _stream(seed=0):
    """Six tiles over two cells; tiles page slabs 0, 1, 3, 2, 1, 0 and
    the first two end in padding tokens."""
    return ragged_stream(T, N_BLK, DOC_ROWS, I, J, (0, 0, 0, 1, 1, 1),
                         (0, 1, 3, 2, 1, 0), (3, 2, 0, 0, 0, 0), seed)


@pytest.mark.parametrize("paged", [False, True], ids=["whole", "paged"])
def test_ragged_sweep_matches_ref(paged):
    cot, dto, toks, tables = _stream()
    ref = fused_sweep_ragged_ref(*toks, cot, *tables, n_blk=N_BLK, **KW)
    if paged:
        got = fused_sweep_ragged_docs_pallas(
            cot, dto, *toks, *tables, doc_rows=DOC_ROWS, n_blk=N_BLK,
            interpret=_interpreter(), **KW)
    else:
        got = fused_sweep_ragged_pallas(cot, *toks, *tables, n_blk=N_BLK,
                                        interpret=_interpreter(), **KW)
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fold_in_matches_ref():
    rng = np.random.default_rng(1)
    D, L, n_words, sweeps, alpha = 4, 16, 11, 3, 0.375
    phi = jnp.asarray(rng.dirichlet(np.ones(T), size=n_words)
                      .astype(np.float32))
    words = jnp.asarray(rng.integers(0, n_words, (D, L)), jnp.int32)
    valid = jnp.asarray(rng.random((D, L)) < 0.8, jnp.int32)
    keys = jax.vmap(doc_fold_key, in_axes=(None, 0))(jax.random.key(0),
                                                      jnp.arange(D))
    z0, u = fold_in_draws(keys, L, T, sweeps)
    want = fold_in_kernel_ref(words, valid, z0, u, alpha, phi)
    got = fold_in_pallas(words, valid, z0, u,
                         jnp.full((1, 1), alpha, jnp.float32), phi,
                         sweeps=sweeps, interpret=_interpreter())
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_kernel_check_passes_interpreted(capsys):
    """The chip's kernel check (T = 1024, padding tokens in slabs g > 0)
    runs here interpreted and finds every kernel exact."""
    assert kernel_check.main() == 0
    assert '"exact": true' in capsys.readouterr().out
