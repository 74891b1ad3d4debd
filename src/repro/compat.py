"""Version spellings of the jax API surface this repo depends on.

Every internal user imports :func:`shard_map` from here, so a future
rename of the entry point or of its replication-check keyword is a
one-line change.
"""
from __future__ import annotations

from jax import shard_map as _shard_map

__all__ = ["shard_map"]


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    return _shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                      check_vma=check_vma)
