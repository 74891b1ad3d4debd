"""Device time of the fused sweep kernel per token swept.

Layer: fused sweep kernel (``kernels/fused_sweep``).  Moves
``train_tokens_per_s``.  Source: the device trace — the summed duration
of the kernel's events in the window, averaged over the chips, divided by
the tokens each chip swept in the window.

The kernels carry no ``name=`` yet.  A v5e trace names each fused-sweep
``pallas_call`` after the Python function that builds it: the ragged,
doc-paged kernel of these cells shows as the custom call
``fused_sweep_ragged_docs_pallas.<n>`` (two per ring round, one per
pipelined half-queue); the other fused-sweep variants share the prefix.
"""
UNIT = "us/token"

# Event names of the fused sweep kernel in a v5e trace.
KERNEL = r"fused_sweep_"


def kernel_seconds(m):
    """Per-chip device seconds of the kernel in the window, or None."""
    per_dev = m.tracelib.named_ns(m.trace, KERNEL)
    if not per_dev or not any(per_dev.values()):
        return None
    return sum(per_dev.values()) / len(per_dev) / 1e9


def read(m):
    sec = kernel_seconds(m)
    if sec is None or not m.facts.get("tokens_per_chip"):
        return None
    return sec / m.facts["tokens_per_chip"] * 1e6
