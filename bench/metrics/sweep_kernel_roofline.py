"""The fused sweep kernel's share of its roofline.

Layer: fused sweep kernel (``kernels/fused_sweep``).  Moves
``train_tokens_per_s``.  Source: the device trace.  The least time the
chip could take for the window's token steps — the larger of operations
over peak FLOP/s and bytes over peak bytes/s, both from ``bench/work.py``
(``7·T + 7`` ops and ``12·T + 64`` bytes a token, from ``T`` alone) — over
the kernel's device time per chip, as ``sweep_kernel_us_per_token``
finds it.
"""
UNIT = "%"


def read(m):
    if m.peak is None or "T" not in m.facts:
        return None
    sec = m.readers["sweep_kernel_us_per_token"].kernel_seconds(m)
    if not sec:
        return None
    least, _ = m.work.least_seconds(m.facts["T"], m.facts["tokens_per_chip"],
                                    m.peak)
    return 100.0 * least / sec
