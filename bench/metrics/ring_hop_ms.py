"""Device time the ring's hops themselves take, per sweep.

Layer: ring hop and s-token fold (``core/nomad.py:nomad_sweep_fn``,
``ppermute``, ``psum``).  Moves ``train_tokens_per_s``.  Source: the
device trace, cut by the program's own ``nomad.sweep`` spans
(``repro.obs``).

A sweep on each chip is ``calls`` fused-kernel calls (the ``nomad.sweep``
span says how many).  Ring step ``j`` of a chip is the gap between its
calls ``j`` and ``j + 1``; its last step is the tail from its last call
to the end of its last device operation of the sweep (the final hop and
the fold).  Every hop is a rendezvous of all chips, so the least gap over
the chips at a step is what the hop costs; the rest is waiting for a
slower peer (``ring_wait_ms``).  This reads the least gaps, summed over
the steps, per sweep of the window.

The window's sweeps are the newest ``sweeps`` ``nomad.sweep`` spans that
ended without error: the recorder's clock is not the trace's, so spans
are chosen by recency.  The trace's ``bench.sweep`` host events bound
each sweep's device operations.  A run on one chip, or a program without
the recorder, reads nothing.
"""
from __future__ import annotations

import re

import numpy as np

UNIT = "ms/sweep"

SWEEP = "nomad.sweep"
# Fused-kernel events, as ``sweep_kernel_us_per_token.KERNEL`` finds them.
KERNEL = r"fused_sweep_"


def recorded() -> list:
    """The program's recorded spans; none where it has no recorder."""
    try:
        from repro import obs
    except ImportError:
        return []
    return obs.spans()


def window_sweeps(spans, sweeps) -> list:
    """The newest ``sweeps`` ``nomad.sweep`` spans that ended without
    error, oldest first, or ``[]`` when there are fewer."""
    ok = [s for s in spans if s.name == SWEEP and s.error is None]
    return ok[-sweeps:] if sweeps and len(ok) >= sweeps else []


def sweep_calls(trace, spans, sweeps):
    """``(calls, {chip: [[(start, end) of each call] per sweep]},
    {chip: [end of the sweep's last operation per sweep]})`` of the
    window, or None where the trace does not cut into ``sweeps`` sweeps
    of ``calls`` kernel calls on every chip."""
    window = window_sweeps(spans, sweeps)
    calls = window[-1].attrs.get("calls") if window else 0
    if not calls:
        return None
    lo, hi = trace.window()
    bounds = [o for o in trace.host
              if o.name == "bench.sweep" and lo <= o.start < hi][-sweeps:]
    if len(bounds) != sweeps:
        return None
    rx = re.compile(KERNEL)
    cut, last = {}, {}
    for d, ops in trace.devices.items():
        ks = [(o.start, o.end) for o in ops
              if rx.match(o.name) and lo <= o.start < hi]
        if len(ks) != calls * sweeps:
            return None
        cut[d] = [ks[i * calls:(i + 1) * calls] for i in range(sweeps)]
        last[d] = [max((o.end for o in ops
                        if b.start <= o.start <= b.end), default=0)
                   for b in bounds]
    return calls, cut, last


def step_gaps(trace, spans, sweeps):
    """``(sweeps, chips, calls)`` array of ring-step gaps in ns, or
    None."""
    got = sweep_calls(trace, spans, sweeps)
    if got is None or len(got[1]) < 2:
        return None
    _, cut, last = got
    out = []
    for s in range(sweeps):
        rows = []
        for d in sorted(cut):
            ks = cut[d][s]
            rows.append([b[0] - a[1] for a, b in zip(ks, ks[1:])]
                        + [max(last[d][s] - ks[-1][1], 0)])
        out.append(rows)
    return np.asarray(out, np.float64)


def split_ms(trace, spans, sweeps):
    """``(hop, wait)`` in ms per sweep, or None."""
    gaps = step_gaps(trace, spans, sweeps)
    if gaps is None:
        return None
    least = gaps.min(axis=1, keepdims=True)
    hop = least.sum(axis=(1, 2)).mean()
    wait = (gaps - least).mean(axis=1).sum(axis=1).mean()
    return hop / 1e6, wait / 1e6


def read(m):
    if m.chips < 2:
        return None
    got = split_ms(m.trace, recorded(), m.facts.get("sweeps"))
    return None if got is None else got[0]
