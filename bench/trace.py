"""From a profiler trace to device metrics: kernel time by event name,
device busy and idle time, collective time left exposed, and the
breakdown of device operations and idle gaps.  Also the table of peaks.

A trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  Device
planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per operation run, in nanoseconds on the same clock as the host
planes, where the benchmark marks its window (``bench.window``) and each
sweep (``bench.sweep``) with ``jax.profiler.TraceAnnotation``.

    python3 bench/trace.py <trace.xplane.pb>   # planes, lines, top events
"""
from __future__ import annotations

import glob
import json
import os
import re
import sys
from collections import Counter, defaultdict
from dataclasses import dataclass, field

__all__ = ["PEAKS", "peak", "Op", "Trace", "find", "load", "union_ns",
           "busy_ns", "named_ns", "exposed_ns", "breakdown"]

# Published peaks per chip, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "bytes_per_s": 819e9,
                    "source": "Google Cloud TPU v5e documentation"},
}

# Collective operations as they appear on a device's op line.
COLLECTIVE = re.compile(
    r"collective-permute|all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"send|recv|ppermute|psum", re.I)
# Control flow whose event spans the operations it runs (the ring's
# ``lax.scan`` over rounds is a ``while`` on a multi-chip mesh): counted
# in busy time, never as an operation of its own.
CONTAINER = {"while", "conditional", "call"}


def peak(kind: str) -> dict:
    """The peaks of ``kind``; a device not in the table is an error."""
    if kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {kind!r}: add it to "
                       f"bench/trace.py PEAKS with its source")
    return PEAKS[kind]


@dataclass
class Op:
    name: str      # HLO instruction, e.g. fused_sweep_ragged_docs_pallas.5
    start: float   # ns
    end: float     # ns

    @property
    def kind(self) -> str:
        """The instruction without its number."""
        return re.sub(r"\.\d+$", "", self.name)


def op_name(event_name: str) -> str:
    """A device event is named by its HLO text, ``%name = type op(...)``:
    the instruction name."""
    m = re.match(r"%?([\w.\-]+)", event_name)
    return m.group(1) if m else event_name


@dataclass
class Trace:
    devices: dict = field(default_factory=dict)   # device id -> [Op]
    in_flight: dict = field(default_factory=dict)  # device id -> [Op], async
    host: list = field(default_factory=list)      # [Op] annotations
    lines: dict = field(default_factory=dict)     # plane -> {line: count}

    def window(self) -> tuple[float, float]:
        """The benchmark's window on the trace clock: its host span."""
        spans = [o for o in self.host if o.name == "bench.window"]
        if not spans:
            raise ValueError("the trace has no bench.window annotation")
        return spans[0].start, spans[0].end


def find(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> Trace:
    """Read a trace: every device plane's ``XLA Ops`` events (operations
    run, one at a time), its ``Async XLA Ops`` events (asynchronous
    copies and collectives from start to done), and the host events whose
    names start with ``bench.``."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    tr = Trace()
    for plane in data.planes:
        tr.lines[plane.name] = {ln.name: sum(1 for _ in ln.events)
                                for ln in plane.lines}
        m = re.match(r"/device:[A-Z]+:(\d+)", plane.name)
        if m:
            dev = int(m.group(1))
            for ln in plane.lines:
                if ln.name == "XLA Ops":
                    ops = tr.devices.setdefault(dev, [])
                elif ln.name == "Async XLA Ops":
                    ops = tr.in_flight.setdefault(dev, [])
                else:
                    continue
                for ev in ln.events:
                    ops.append(Op(op_name(ev.name), ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
        elif plane.name.startswith("/host"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith("bench."):
                        tr.host.append(Op(ev.name, ev.start_ns,
                                          ev.start_ns + ev.duration_ns))
    tr.devices = {d: sorted(v, key=lambda o: o.start)
                  for d, v in tr.devices.items() if v}
    tr.in_flight = {d: sorted(v, key=lambda o: o.start)
                    for d, v in tr.in_flight.items() if d in tr.devices}
    tr.host.sort(key=lambda o: o.start)
    return tr


def union_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``(start, end)`` intervals clipped to
    ``[lo, hi]``."""
    return sum(e - s for s, e in _merged(intervals, lo, hi))


def _merged(intervals, lo, hi):
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(tr: Trace) -> dict:
    """Per device: nanoseconds of the window in which an operation ran."""
    lo, hi = tr.window()
    return {d: union_ns([(o.start, o.end) for o in ops], lo, hi)
            for d, ops in tr.devices.items()}


def named_ns(tr: Trace, pattern: str) -> dict:
    """Per device: summed duration of the window's operations whose
    instruction name matches the regular expression ``pattern``."""
    lo, hi = tr.window()
    rx = re.compile(pattern)
    return {d: sum(min(o.end, hi) - max(o.start, lo) for o in ops
                   if rx.match(o.name) and o.end > lo and o.start < hi)
            for d, ops in tr.devices.items()}


def exposed_ns(tr: Trace) -> dict:
    """Per device: nanoseconds in which a collective was in flight or
    waited on and no other operation ran (collective time not hidden
    behind compute)."""
    lo, hi = tr.window()
    out = {}
    for d, ops in tr.devices.items():
        coll = _merged([(o.start, o.end)
                        for o in ops + tr.in_flight.get(d, [])
                        if COLLECTIVE.search(o.name)], lo, hi)
        comp = _merged([(o.start, o.end) for o in ops
                        if not COLLECTIVE.search(o.name)
                        and o.kind not in CONTAINER], lo, hi)
        hidden, j = 0.0, 0
        for s, e in coll:
            while j < len(comp) and comp[j][1] <= s:
                j += 1
            k = j
            while k < len(comp) and comp[k][0] < e:
                hidden += min(e, comp[k][1]) - max(s, comp[k][0])
                k += 1
        out[d] = sum(e - s for s, e in coll) - hidden
    return out


def breakdown(tr: Trace, top: int = 10) -> dict:
    """``device_ops``: the operations that took most device time (summed
    over devices, per device on average); ``idle_gaps``: the longest idle
    gaps on any device, each named by the host span it fell in."""
    lo, hi = tr.window()
    n = max(len(tr.devices), 1)
    by_name = defaultdict(float)
    gaps = []
    for d, ops in tr.devices.items():
        for o in ops:
            if o.end > lo and o.start < hi and o.kind not in CONTAINER:
                by_name[o.kind] += (
                    min(o.end, hi) - max(o.start, lo))
        prev = lo
        for s, e in _merged([(o.start, o.end) for o in ops], lo, hi) + [
                [hi, hi]]:
            if s > prev:
                gaps.append((s - prev, prev, s))
            prev = max(prev, e)
    gaps.sort(reverse=True)

    def host_at(t):
        inner = [o for o in tr.host if o.start <= t <= o.end
                 and o.name != "bench.window"]
        return min(inner, key=lambda o: o.end - o.start).name if inner \
            else "bench.window"

    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v / n / 1e9] for k, v in ops],
            "idle_gaps": [[host_at((s + e) / 2), g / 1e9]
                          for g, s, e in gaps[:top]]}


if __name__ == "__main__":
    tr = load(sys.argv[1])
    print(json.dumps(tr.lines, indent=1))
    names = Counter()
    for ops in tr.devices.values():
        names.update(o.kind for o in ops)
    print(json.dumps({"window": tr.window(), "busy_ns": busy_ns(tr),
                      "top_names": names.most_common(30),
                      "breakdown": breakdown(tr)}, indent=1))
