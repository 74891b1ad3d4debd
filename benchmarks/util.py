"""Timing helpers for the benchmark harness."""
from __future__ import annotations

import os
import time

import jax


def time_fn(fn, *args, warmup: int = 2, iters: int = 5) -> float:
    """Median wall-seconds per call of a jitted fn (blocks on results)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def row(name: str, us_per_call: float, derived: str = "") -> str:
    return f"{name},{us_per_call:.3f},{derived}"


def cpu_child_env(repo: str) -> dict:
    """Environment for a benchmark child process: the repo's sources on
    the path, no inherited device-count flags, and JAX held to the CPU.
    The children are correctness witnesses on faked CPU devices; on a
    machine with a TPU the parent may hold the chip, and a child that
    reached for it would fail or hang."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    return env
