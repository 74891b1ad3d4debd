"""Operations and bytes of the work a training sweep must do, for roofline
and utilization shares.

The basis is the plain collapsed-Gibbs step of one token over all ``T``
topics, as the serial reference in ``core/cgs.py`` (``sweep_reference``)
computes it:

* decrement the token's three counts (3 ops);
* the conditional ``p_t = (n_td + α)(n_wt + β) / (n_t + β̄)`` for every
  topic: three adds, a multiply and a divide (5·T ops);
* its prefix sum (T ops), the scale of ``u`` by the total (1 op) and the
  linear search ``#{c_t ≤ u}`` (T compares);
* increment the three counts at the new topic (3 ops);

so ``ops = 7·T + 7``.  Bytes: the three int32 count rows read (``n_td[d]``,
``n_wt[w]``, ``n_t``: 12·T), the six count updates read and written back
(48), and the token's document, word and topic read and topic written
(16): ``bytes = 12·T + 64``.

Both depend on ``T`` alone, never on the layout, the kernel or the
schedule, so every implementation of the sweep is measured against the
same work.  A token that a faster sampler skips some of this for (the
F+tree's Θ(log T) draw) still counts it: the shares say how far the
implementation is from doing the plain step's work at the chip's peak.
"""
from __future__ import annotations

__all__ = ["ops_per_token", "bytes_per_token", "least_seconds"]


def ops_per_token(T: int) -> int:
    return 7 * int(T) + 7


def bytes_per_token(T: int) -> int:
    return 12 * int(T) + 64


def least_seconds(T: int, tokens: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take for ``tokens`` token steps: the
    larger of operations over peak FLOP/s and bytes over peak bytes/s, and
    which of the two bounds it."""
    t_ops = tokens * ops_per_token(T) / peak["flops"]
    t_bytes = tokens * bytes_per_token(T) / peak["bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")
