"""What the serve engine names in a profiler trace: its programs, its host
spans, and the device time of the ops under a ``jax.named_scope``; and
the waits it stamps on each request.

``bench/xplane.py`` reduces a trace from outside the program: device ops
by instruction, idle gaps by the benchmark's own spans.  This reduces
what ``repro.serve.scheduler`` names itself, over the same window (the
first to the last ``engine.step``):

* ``XLA Modules`` events of each device plane, one per run of a jitted
  program, named ``jit_<function>(<fingerprint>)``: the engine's
  ``jit_decode_tick`` and ``jit_prefill_chunk``;
* host spans named ``serve.*`` (the phases of ``step``), which nest in
  the benchmark's ``engine.step``: each idle moment of the device goes
  to the innermost host span that covers it;
* ``XLA Ops`` events by their full instruction name (``fusion.12``),
  joined to that instruction's ``op_name`` metadata in the program's
  compiled text, since the trace carries no metadata: an op lies under a
  scope when a component of its ``op_name`` path is the scope's name.

The engine's ``request_times`` give each request's waits on the engine's
clock, which must be the drive's (``waits``).
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

from bench.xplane import _CONTAINERS, _DEVICE, _union, HOST_SPANS, op_name

__all__ = ["Run", "EngineTrace", "SERVE", "innermost", "op_names",
           "reduce_planes", "reduce_profile", "reduce_file",
           "compiled_programs", "waits"]

SERVE = "serve."
_MODULE = re.compile(r"^(.*?)(?:\(\d+\))?$")
_INSTRUCTION = re.compile(r"^%?([\w.\-]+)")
_HLO_LINE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?\bmetadata=\{[^}]*?"
    r"op_name=\"([^\"]*)\"")


@dataclass
class Run:
    """One run of a program on a device, and the ops it ran."""

    program: str                 # module name without its fingerprint
    start_ns: float
    dur_ns: float
    ops: List[Tuple[str, float]] = field(default_factory=list)
    # (full instruction name, device ns inside the window)


@dataclass
class EngineTrace:
    window_s: float
    n_devices: int
    runs: List[Run] = field(default_factory=list)        # in the window
    idle_by_span: Dict[str, float] = field(default_factory=dict)

    def run_seconds(self, program: str) -> List[float]:
        """Device seconds of each run of ``program`` in the window."""
        return [r.dur_ns / 1e9 for r in self.runs if r.program == program]

    def scope_seconds(self, program: str, hlo_text: str,
                      scope: str) -> Tuple[float, int]:
        """Device seconds of the ops of ``program``'s runs whose
        ``op_name`` lies under ``scope`` in ``hlo_text`` (that program's
        compiled text), averaged over devices; and the runs counted."""
        names = op_names(hlo_text)
        runs = [r for r in self.runs if r.program == program]
        ns = sum(d for r in runs for ins, d in r.ops
                 if scope in names.get(ins, "").split("/"))
        return ns / 1e9 / self.n_devices, len(runs) // self.n_devices

    def idle_split(self) -> Dict[str, float]:
        """Idle seconds under each ``serve.*`` span, largest first."""
        return dict(sorted(((k, v) for k, v in self.idle_by_span.items()
                            if k.startswith(SERVE)), key=lambda kv: -kv[1]))


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` metadata of a compiled program."""
    out = {}
    for line in hlo_text.splitlines():
        m = _HLO_LINE.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def innermost(spans) -> List[Tuple[float, float, str]]:
    """Nested (name, start, end) spans as disjoint (start, end, name)
    segments, each moment given to the innermost span covering it."""
    out, stack, t = [], [], None

    def seg(a, b, name):
        if b > a:
            out.append((a, b, name))

    for name, a, b in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][2] <= a:
            top, _, end = stack.pop()
            seg(t, end, top)
            t = max(t, end)
        if stack:
            seg(t, a, stack[-1][0])
        t = a
        stack.append((name, a, b))
    while stack:
        top, _, end = stack.pop()
        seg(t, end, top)
        t = max(t, end)
    return out


def _split(gaps, segments, idle, scale):
    """Add each gap's overlap with each labelled segment to ``idle``;
    what no segment covers goes to ``none``.  Both lists sorted."""
    i = 0
    for a, b in gaps:
        covered = 0.0
        while i < len(segments) and segments[i][1] <= a:
            i += 1
        j = i
        while j < len(segments) and segments[j][0] < b:
            lo, hi = max(a, segments[j][0]), min(b, segments[j][1])
            if hi > lo:
                idle[segments[j][2]] += (hi - lo) * scale
                covered += hi - lo
            j += 1
        if b - a > covered:
            idle["none"] += (b - a - covered) * scale


def reduce_planes(device_modules: List[List[Run]],
                  device_ops: List[List[Tuple[str, float, float]]],
                  host_spans) -> EngineTrace:
    """``device_modules``: each device's program runs; ``device_ops``:
    each device's (instruction, start_ns, dur_ns); ``host_spans``:
    (name, start_ns, end_ns) of the benchmark's and the engine's spans."""
    steps = [(a, b) for n, a, b in host_spans if n == "engine.step"]
    if not steps or not device_ops:
        raise ValueError("trace holds no engine.step span or no device")
    lo, hi = min(a for a, _ in steps), max(b for _, b in steps)
    segments = innermost(host_spans)
    n = len(device_ops)
    runs, idle = [], defaultdict(float)
    for modules, ops in zip(device_modules, device_ops):
        mine = sorted((r for r in modules
                       if r.start_ns < hi and r.start_ns + r.dur_ns > lo),
                      key=lambda r: r.start_ns)
        starts = [r.start_ns for r in mine]
        inside = [(ins, max(a, lo), min(a + d, hi)) for ins, a, d in ops
                  if a < hi and a + d > lo]
        for ins, a, b in inside:
            k = bisect.bisect_right(starts, a) - 1
            if k >= 0 and a < mine[k].start_ns + mine[k].dur_ns:
                mine[k].ops.append((ins, b - a))
        runs.extend(mine)
        edges = [lo] + [x for iv in _union((a, b) for _, a, b in inside)
                        for x in iv] + [hi]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        _split(gaps, segments, idle, 1e-9 / n)
    return EngineTrace(window_s=(hi - lo) / 1e9, n_devices=n, runs=runs,
                       idle_by_span=dict(idle))


def reduce_profile(pd) -> EngineTrace:
    """Reduce a ``jax.profiler.ProfileData``."""
    modules, ops, host = [], [], []
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            mods, dev_ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods.extend(Run(_MODULE.match(ev.name).group(1),
                                    ev.start_ns, ev.duration_ns)
                                for ev in line.events)
                elif line.name == "XLA Ops":
                    # control flow holds the ops that run, as in xplane
                    dev_ops.extend((_INSTRUCTION.match(ev.name).group(1),
                                    ev.start_ns, ev.duration_ns)
                                   for ev in line.events
                                   if op_name(ev.name) not in _CONTAINERS)
            modules.append(mods)
            ops.append(dev_ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS or ev.name.startswith(SERVE):
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    return reduce_planes(modules, ops, host)


def reduce_file(path) -> EngineTrace:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(str(path)))


def compiled_programs(engine) -> Dict[str, str]:
    """The compiled text of the engine's two programs at the shapes it
    runs them, by module name.  Lowering traces the programs again, so
    call it after the window's check on ``engine.trace_counts``."""
    import jax
    import numpy as np

    def shapes(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                            tree)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, np.int32)

    n, width = engine.page_table.shape
    state = shapes((engine.params, engine.cache))
    out: Dict[str, str] = {}
    for name, fn, rows, cols in (
            ("jit_decode_tick", engine._decode, n, 1),
            ("jit_prefill_chunk", engine._prefill, 1, engine.prefill_chunk)):
        out[name] = fn.lower(*state, ints(rows, cols), ints(rows, width),
                             ints(rows), ints(rows)).compile().as_text()
    return out


def waits(window, times, since: str, until: str) -> List[float]:
    """``until`` minus ``since`` (fields of the engine's ``RequestTimes``)
    for each request due in the window that had reached ``since`` by the
    close; one that has not reached ``until`` by then counts what it has
    waited so far."""
    by_rid = {t.rid: t for t in times}
    out = []
    for s in window.served:
        t = by_rid.get(s.rid)
        if t is None or not window.t_open <= s.due <= window.t_close:
            continue
        a, b = getattr(t, since), getattr(t, until)
        if a is None or a > window.t_close:
            continue
        out.append((b if b is not None and b <= window.t_close
                    else window.t_close) - a)
    return out
