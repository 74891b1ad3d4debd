"""Device time of one F+tree rebuild in the fused sweep kernel.

Layer: fused sweep kernel (``kernels/fused_sweep``).  Moves
``train_tokens_per_s``.  Source: the device trace, with the work of each
kernel call from the program's own ``nomad.sweep`` spans (``repro.obs``):
each carries the layout's ``NomadLayout.half_work()``, the real tokens,
tree rebuilds and stream slots of every (ring round, worker, half-queue),
and the ring position of each chip.

There is no clock inside a kernel, but the calls differ in their mix of
work.  A least-squares fit over the window's calls, all chips, of each
call's device duration against its (tokens, rebuilds, slots) gives the
rebuild's coefficient.  A chip's call ``i`` in a sweep is ring round
``i // 2``, half ``i % 2`` when the ring is pipelined (two calls a round),
else round ``i`` with both halves.  Reads nothing where the calls hold
fewer than :data:`MIN_MIXES` distinct work mixes or the fit explains less
than :data:`MIN_R2` of the durations' variance.  The window's sweeps and
calls are found as ``ring_hop_ms`` finds them.
"""
from __future__ import annotations

import numpy as np

UNIT = "us/rebuild"

MIN_MIXES = 4
MIN_R2 = 0.9


def calls_work(trace, spans, sweeps, hop):
    """``(work, us)``: each window call's (tokens, rebuilds, slots) and
    its device microseconds, or None."""
    got = hop.sweep_calls(trace, spans, sweeps)
    if got is None:
        return None
    calls, cut, _ = got
    attrs = hop.window_sweeps(spans, sweeps)[-1].attrs
    work, worker_of = attrs.get("work"), attrs.get("worker_of", {})
    if work is None or any(d not in worker_of for d in cut):
        return None
    per_round = calls // work.shape[0]
    if per_round not in (1, 2) or per_round * work.shape[0] != calls:
        return None
    rows, us = [], []
    for d, per_sweep in cut.items():
        w = worker_of[d]
        for ks in per_sweep:
            for i, (start, end) in enumerate(ks):
                r = i // per_round
                rows.append(work[r, w, i % 2] if per_round == 2
                            else work[r, w].sum(axis=0))
                us.append((end - start) / 1e3)
    return np.asarray(rows, np.float64), np.asarray(us, np.float64)


def fit(work, us):
    """``(coefficients per token, rebuild and slot in us, R²)`` of the
    least-squares fit, or None under :data:`MIN_MIXES` or
    :data:`MIN_R2`."""
    if len({tuple(r) for r in work}) < MIN_MIXES:
        return None
    coef = np.linalg.lstsq(work, us, rcond=None)[0]
    resid = us - work @ coef
    total = ((us - us.mean()) ** 2).sum()
    r2 = 1.0 - (resid ** 2).sum() / total if total > 0 else 0.0
    return (coef, r2) if r2 >= MIN_R2 else None


def rebuild_fit(trace, spans, sweeps, hop):
    """The fit over the window: ``{"us_per_token", "us_per_rebuild",
    "us_per_slot", "r2", "rebuild_share"}`` (the rebuilds' share of the
    calls' device time), or None."""
    got = calls_work(trace, spans, sweeps, hop)
    done = got and fit(*got)
    if not done:
        return None
    (tok, reb, slot), r2 = done
    work, us = got
    return {"us_per_token": tok, "us_per_rebuild": reb, "us_per_slot": slot,
            "r2": r2, "rebuild_share": reb * work[:, 1].sum() / us.sum()}


def read(m):
    hop = m.readers["ring_hop_ms"]
    got = rebuild_fit(m.trace, hop.recorded(), m.facts.get("sweeps"), hop)
    return None if got is None else float(got["us_per_rebuild"])
