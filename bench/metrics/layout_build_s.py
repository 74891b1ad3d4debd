"""Host seconds the program's layout build takes.

Layer: layout build (``data/sharding.py:build_layout``).  Moves
``setup_s``.  Source: the host clock around the call, in the traced run.
"""
UNIT = "s"


def read(m):
    return m.spans.get("layout_build")
