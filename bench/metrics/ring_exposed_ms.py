"""Collective time of the ring left exposed, per sweep.

Layer: ring hop and s-token fold (``core/nomad.py:nomad_sweep_fn``,
``ppermute``).  Moves ``train_tokens_per_s``.  Source: the device
trace — the time in which a collective operation runs and nothing else
does on the same chip, averaged over the chips, per sweep of the window.
A run on one chip has no collectives and reads nothing.
"""
UNIT = "ms/sweep"


def read(m):
    if m.chips < 2 or not m.facts.get("sweeps"):
        return None
    exposed = m.tracelib.exposed_ns(m.trace)
    if not exposed:
        return None
    return sum(exposed.values()) / len(exposed) / 1e6 / m.facts["sweeps"]
