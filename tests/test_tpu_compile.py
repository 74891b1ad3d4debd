"""Ahead-of-time compiles of the main-path kernels for a described TPU v5e.

No chip is needed: the TPU compiler is installed and compiles for a
topology that is described, not attached.  Each case lowers a kernel
through its public wrapper with ``interpret=False`` at T=1024 and the
block sizes ``chip_smoke.py`` runs (NYTimes widths), so Mosaic refusals,
VMEM/SMEM overruns and HBM overflow show up here at no chip cost.  The
VMEM models in ``ops`` are checked against the compiler both ways: the
limit the wrapper passes compiles, and a limit a tenth below the paged
blocks' share does not.  The SMEM model is checked the same way at
T=4096, where the F+tree is widest.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fold_in import fold_in_fused, fold_in_vmem_bytes
from repro.kernels.fused_sweep import (fused_sweep_ragged,
                                       fused_vmem_bytes)
from repro.kernels.fused_sweep.fused_sweep import (
    fused_sweep_ragged_docs_pallas, fused_sweep_ragged_pallas)
from repro.kernels.fused_sweep.ops import (VMEM_SCOPED_DEFAULT_BYTES,
                                           fused_smem_bytes)

T = 1024
VOCAB = 102_660
TILE = 256
HBM_BYTES = 16 * 2**30          # one v5e chip
KW = dict(alpha=50.0 / T, beta=0.01, beta_bar=0.01 * VOCAB)

# (docs I, block rows J, blocks k, doc slab rows) at the smoke's shapes:
# the 2,000-doc whole-shard cut and a 30,720-row doc-paged shard.
WHOLE = (2000, 3328, 48, 0)
PAGED = (30720, 2560, 48, 2048)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache)


def _spec(sh, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)


def _ragged_args(sh, I, J, k, doc_rows, n_tiles=4 * 48):
    S = n_tiles * TILE
    tok = [_spec(sh, (S,)) for _ in range(5)] + [_spec(sh, (S,),
                                                        jnp.float32)]
    maps = [_spec(sh, (n_tiles,))] * (2 if doc_rows else 1)
    return maps, tok, [_spec(sh, (I, T)), _spec(sh, (k, J, T)),
                       _spec(sh, (T,))]


def _fits_hbm(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)
    assert used < HBM_BYTES, f"{used / 2**30:.2f} GiB does not fit a v5e"


@pytest.mark.parametrize("shape", [WHOLE, PAGED], ids=["whole", "paged"])
def test_ragged_sweep_compiles(one_chip, shape):
    """``fused_sweep_ragged`` (whole shard) and its doc-paged twin, through
    the public wrapper with the VMEM limit it chooses."""
    I, J, k, doc_rows = shape
    maps, tok, tables = _ragged_args(one_chip, I, J, k, doc_rows)
    docs = dict(doc_rows=doc_rows) if doc_rows else {}

    def sweep(cot, *rest):
        dto, rest = (rest[0], rest[1:]) if doc_rows else (None, rest)
        return fused_sweep_ragged(*rest[:6], cot, *rest[6:], n_blk=TILE,
                                  doc_tile_of=dto, interpret=False, **docs,
                                  **KW)

    compiled = jax.jit(sweep).lower(*maps, *tok, *tables).compile()
    _fits_hbm(compiled)
    assert "tpu_custom_call" in compiled.as_text()
    assert fused_vmem_bytes(I, J, T, TILE, doc_rows) > \
        VMEM_SCOPED_DEFAULT_BYTES          # the limit really is raised


@pytest.mark.parametrize("shape", [WHOLE, PAGED], ids=["whole", "paged"])
def test_vmem_model_is_tight(one_chip, shape):
    """The paged word-topic windows and the doc slab are counted right: a
    limit 10% below their share of ``fused_vmem_bytes`` is refused.  (The
    model's whole-shard ``n_td`` copies are its worst case — XLA may pin
    that table in VMEM outside the kernel's scoped allocation.)"""
    I, J, k, doc_rows = shape
    maps, tok, tables = _ragged_args(one_chip, I, J, k, doc_rows)
    paged = (fused_vmem_bytes(I, J, T, TILE, doc_rows)
             - (0 if doc_rows else fused_vmem_bytes(I, 0, T)
                - fused_vmem_bytes(0, 0, T)))
    kern, kw = ((fused_sweep_ragged_docs_pallas, dict(doc_rows=doc_rows))
                if doc_rows else (fused_sweep_ragged_pallas, {}))
    f = jax.jit(lambda *a: kern(*a, n_blk=TILE, interpret=False,
                                vmem_limit=int(0.9 * paged), **kw, **KW))
    with pytest.raises(Exception, match="vmem"):
        f.lower(*maps, *tok, *tables).compile()


@pytest.mark.parametrize("slack,fits", [(16 * 2**10, True), (0, False)],
                         ids=["fits", "over"])
def test_smem_model_is_tight_at_t4096(one_chip, slack, fits):
    """The doc-paged ragged sweep at T=4096 (a 32 KiB F+tree) with as many
    tiles as its SMEM model leaves room for: a model ``slack`` under the
    core's 1 MiB compiles, one at 1 MiB is refused.  So the compiler needs
    no more than the model plus 16 KiB — half the tree — and the tree the
    kernel allocates is the one ``ops.fused_smem_bytes`` counts."""
    T4, I, J, k, doc_rows = 4096, 4096, 128, 8, 1024
    smem = 2**20 - slack
    base = fused_smem_bytes(0, TILE, T4, 2)
    assert base == 4 * (14 * TILE + 2 * T4)
    n_tiles = (smem - base) // 8
    assert fused_smem_bytes(n_tiles, TILE, T4, 2) == smem
    S = n_tiles * TILE
    spec = lambda shape, dtype=jnp.int32: _spec(one_chip, shape, dtype)
    args = ([spec((n_tiles,))] * 2 + [spec((S,))] * 5
            + [spec((S,), jnp.float32), spec((I, T4)), spec((k, J, T4)),
               spec((T4,))])
    f = jax.jit(lambda *a: fused_sweep_ragged_docs_pallas(
        *a, n_blk=TILE, interpret=False, doc_rows=doc_rows,
        vmem_limit=fused_vmem_bytes(I, J, T4, TILE, doc_rows) + 2**20,
        alpha=50.0 / T4, beta=0.01, beta_bar=0.01 * VOCAB))
    if fits:
        assert "tpu_custom_call" in f.lower(*args).compile().as_text()
    else:
        with pytest.raises(Exception, match="smem"):
            f.lower(*args).compile()


def test_fold_in_compiles(one_chip):
    """The serving kernel at the smoke's widest bucket: 64 docs × 2048
    tokens, 20 sweeps, against a NYTimes-wide φ."""
    D, L, sweeps = 64, 2048, 20
    assert fold_in_vmem_bytes(L, T, sweeps) < VMEM_SCOPED_DEFAULT_BYTES
    keys = jax.ShapeDtypeStruct((D,), jax.random.key(0).dtype,
                                sharding=one_chip)
    f = jax.jit(lambda w, v, phi, dk: fold_in_fused(
        w, v, phi, 50.0 / T, dk, sweeps, interpret=False))
    compiled = f.lower(_spec(one_chip, (D, L)),
                       _spec(one_chip, (D, L), jnp.bool_),
                       _spec(one_chip, (VOCAB, T), jnp.float32),
                       keys).compile()
    _fits_hbm(compiled)
    assert "tpu_custom_call" in compiled.as_text()
