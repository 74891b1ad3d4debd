"""Every ``pallas_call`` carries a stable name, and the name is the HLO
instruction a device profile shows for it.

Compiled ahead of time for a described TPU v5e (no chip needed), at small
shapes.  The benchmark's kernel readers match ``^fused_sweep_``; the two
half-queues of a pipelined ring round show apart as ``_h0`` and ``_h1``.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fold_in import fold_in_fused
from repro.kernels.fused_sweep import fused_sweep_cells, fused_sweep_ragged

T = 128
TILE = 128
KW = dict(alpha=0.1, beta=0.01, beta_bar=10.0)


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


def _custom_calls(compiled) -> set:
    """Names of the compiled program's ``tpu_custom_call`` instructions,
    without their numbers."""
    return {re.sub(r"\.\d+$", "", m.group(1))
            for m in re.finditer(r"%([\w.\-]+) = .*custom-call\(.*"
                                 r"custom_call_target=\"tpu_custom_call\"",
                                 compiled.as_text())}


@pytest.mark.parametrize("paged", [False, True], ids=["whole", "paged"])
def test_ragged_halves_are_named_apart(one_chip, paged):
    """A pipelined round's two half-queues, as ``core/nomad.py`` calls
    them, and a whole-queue call."""
    n_tiles, k, I, J, rows = 4, 4, 64, 32, 32
    S = n_tiles * TILE
    sh = one_chip
    tok = [_spec(sh, (S,)) for _ in range(5)] + [_spec(sh, (S,),
                                                        jnp.float32)]
    maps = [_spec(sh, (n_tiles,))] * (2 if paged else 1)
    tables = [_spec(sh, (I, T)), _spec(sh, (k, J, T)), _spec(sh, (T,))]
    docs = dict(doc_rows=rows) if paged else {}

    def sweep(cot, *rest):
        dto, rest = (rest[0], rest[1:]) if paged else (None, rest)
        call = lambda ranges: fused_sweep_ragged(
            *rest[:6], cot, *rest[6:], n_blk=TILE, doc_tile_of=dto,
            interpret=False, **ranges, **docs, **KW)
        h0 = call(dict(tile_start=0, num_tiles=2, cell_start=0,
                       num_cells=2))
        h1 = call(dict(tile_start=2, num_tiles=2, cell_start=2,
                       num_cells=2))
        return h0[0], h1[0], call({})[0]

    compiled = jax.jit(sweep).lower(*maps, *tok, *tables).compile()
    base = "fused_sweep_ragged_docs" if paged else "fused_sweep_ragged"
    assert _custom_calls(compiled) == {base + "_h0", base + "_h1", base}


def test_dense_cells_and_fold_in_names(one_chip):
    k, L, I, J = 4, 256, 64, 32
    sh = one_chip
    tok = [_spec(sh, (k, L)) for _ in range(5)] + [_spec(sh, (k, L),
                                                          jnp.float32)]
    tables = [_spec(sh, (I, T)), _spec(sh, (k, J, T)), _spec(sh, (T,))]

    def sweep(*a):
        return fused_sweep_cells(*a[:6], *a[6:], cell_start=2, num_cells=2,
                                 interpret=False, **KW)[0]

    compiled = jax.jit(sweep).lower(*tok, *tables).compile()
    assert _custom_calls(compiled) == {"fused_sweep_cells_h1"}

    D, Lq, V = 8, 128, 512
    keys = jax.ShapeDtypeStruct((D,), jax.random.key(0).dtype, sharding=sh)
    f = jax.jit(lambda w, v, phi, dk: fold_in_fused(
        w, v, phi, 0.1, dk, 2, interpret=False))
    compiled = f.lower(_spec(sh, (D, Lq)), _spec(sh, (D, Lq), jnp.bool_),
                       _spec(sh, (V, T), jnp.float32), keys).compile()
    assert _custom_calls(compiled) == {"fold_in"}
