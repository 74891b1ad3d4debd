"""Device time chips wait at the ring's hops for a slower peer, per sweep.

Layer: ring hop and s-token fold (``core/nomad.py:nomad_sweep_fn``).
Moves ``train_tokens_per_s``.  Source: the device trace, cut by the
program's own ``nomad.sweep`` spans, as ``ring_hop_ms`` cuts it: at each
ring step, the mean over the chips of the gap beyond the least gap any
chip saw, summed over the steps, per sweep of the window.  Each hop is a
rendezvous, so this is the time a faster chip's kernel call waits for
the slowest one's.  A run on one chip, or a program without the
recorder, reads nothing.
"""
UNIT = "ms/sweep"


def read(m):
    if m.chips < 2:
        return None
    hop = m.readers["ring_hop_ms"]
    got = hop.split_ms(m.trace, hop.recorded(), m.facts.get("sweeps"))
    return None if got is None else got[1]
