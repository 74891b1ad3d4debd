"""Share of the window in which the device ran no operation.

Layer: device.  Moves ``train_tokens_per_s``.  Source: the device
trace — one minus the union of the operations' intervals over the
window, averaged over the chips.
"""
UNIT = "%"


def read(m):
    busy = m.tracelib.busy_ns(m.trace)
    if not busy or "tokens" not in m.facts:
        return None
    lo, hi = m.trace.window()
    return 100.0 * (1.0 - sum(busy.values()) / len(busy) / (hi - lo))
