"""Host seconds the trainer takes to build its state.

Layer: state initialisation (``core/nomad.py:NomadLDA.init_arrays``).
Moves ``setup_s``.  Source: the host clock, as the program's own
``nomad.init_arrays`` span (``repro.obs``) records it: the newest such
span.  A program without the recorder reads nothing.
"""
UNIT = "s"


def read(m):
    inits = [s for s in m.readers["ring_hop_ms"].recorded()
             if s.name == "nomad.init_arrays" and s.error is None]
    return inits[-1].seconds if inits else None
