"""Reduce a profiler trace (``.xplane.pb``) to device busy time and op times.

Device planes are those named ``/device:TPU:<n>``; their operations are
the events of the ``XLA Ops`` line, named by the HLO instruction
(``%flash_decode_pallas.11 = f32[...] custom-call(...)`` reads as
``flash_decode_pallas``; a Pallas kernel carries its wrapper's name).
Control flow that holds other operations (``while``, ``conditional``,
``call``) is left out, so busy time is that of the operations that run.
The traced window runs from the
start of the first to the end of the last ``engine.step`` annotation on
the host plane (the benchmark's own spans, on the profiler's clock).
Busy time is the union of the operation intervals inside the window,
averaged over the device planes.  Each gap in the union is put down to
the host span that covers its middle (``engine.step``, ``gen.submit``,
``gen.wait``, or ``none``).
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

__all__ = ["Op", "Trace", "op_name", "reduce_file", "reduce_profile",
           "reduce_planes",
           "HOST_SPANS"]

HOST_SPANS = ("engine.step", "gen.submit", "gen.wait")
_DEVICE = re.compile(r"^/device:TPU:\d+$")
_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)*(?:\s*=|$)")
_CONTAINERS = {"while", "conditional", "call"}


@dataclass
class Op:
    name: str          # the HLO instruction's name without its suffix
    start_ns: float
    dur_ns: float


def op_name(event_name: str) -> str:
    m = _NAME.match(event_name)
    return m.group(1) if m else event_name


@dataclass
class Trace:
    window_s: float
    busy_s: float
    n_devices: int
    ops: List[Op] = field(default_factory=list)          # inside the window
    idle_by_host: Dict[str, float] = field(default_factory=dict)

    def op_seconds(self, pattern: str) -> Tuple[float, int]:
        """Total device seconds and count of ops whose name matches."""
        rx = re.compile(pattern)
        hits = [o for o in self.ops if rx.search(o.name)]
        return sum(o.dur_ns for o in hits) / 1e9 / self.n_devices, len(hits)

    def top_ops(self, n: int = 10):
        tot = defaultdict(float)
        for o in self.ops:
            tot[o.name] += o.dur_ns / 1e9 / self.n_devices
        return sorted(([k, v] for k, v in tot.items()),
                      key=lambda kv: -kv[1])[:n]

    def top_idle(self, n: int = 10):
        return sorted(([k, v] for k, v in self.idle_by_host.items()),
                      key=lambda kv: -kv[1])[:n]


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def reduce_planes(device_ops: List[List[Op]], host_spans) -> Trace:
    """``device_ops``: one list of ops per device; ``host_spans``:
    (name, start_ns, end_ns) of the benchmark's host annotations."""
    steps = [(a, b) for n, a, b in host_spans if n == "engine.step"]
    if not steps or not device_ops:
        raise ValueError("trace holds no engine.step span or no device")
    lo, hi = min(a for a, _ in steps), max(b for _, b in steps)
    busy = 0.0
    idle = defaultdict(float)
    inside = []
    # the benchmark's spans do not nest: the latest one to start before
    # a moment is the only one that can cover it
    spans = sorted(host_spans, key=lambda s: s[1])
    starts = [s[1] for s in spans]
    for ops in device_ops:
        ops = [o for o in ops if o.start_ns < hi and
               o.start_ns + o.dur_ns > lo]
        inside.extend(ops)
        union = _union((max(o.start_ns, lo), min(o.start_ns + o.dur_ns, hi))
                       for o in ops)
        busy += sum(b - a for a, b in union)
        edges = [lo] + [x for iv in union for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) / 2
            i = bisect.bisect_right(starts, mid) - 1
            label = spans[i][0] if i >= 0 and spans[i][2] >= mid else "none"
            idle[label] += (b - a) / 1e9 / len(device_ops)
    return Trace(window_s=(hi - lo) / 1e9,
                 busy_s=busy / 1e9 / len(device_ops),
                 n_devices=len(device_ops), ops=inside,
                 idle_by_host=dict(idle))


def reduce_file(path) -> Trace:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(str(path)))


def reduce_profile(pd) -> Trace:
    """Reduce a ``jax.profiler.ProfileData``."""
    device_ops, host = [], []
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        name = op_name(ev.name)
                        if name not in _CONTAINERS:
                            ops.append(Op(name, ev.start_ns, ev.duration_ns))
            device_ops.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    return reduce_planes(device_ops, host)
