"""The whole training step's share of the chip's peak FLOP/s.

Layer: sweep step (``core/nomad.py:NomadLDA.sweep``).  Moves
``train_tokens_per_s``.  Source: the host clock of the traced run —
Gibbs tokens per second per chip times the plain step's ``7·T + 7``
operations a token (``bench/work.py``), over the peak.  It bounds every
kernel's share from above: a kernel taken off the path leaves its own
roofline silent, not this.
"""
UNIT = "%"


def read(m):
    rate = m.e2e.get("train_tokens_per_s")
    if m.peak is None or not rate or "T" not in m.facts:
        return None
    return 100.0 * rate * m.work.ops_per_token(m.facts["T"]) / m.peak["flops"]
