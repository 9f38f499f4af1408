"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

A device plane (``/device:TPU:0`` ...) holds an ``XLA Modules`` line, one
event per run of a compiled program (``jit_<name>(<fingerprint>)``), and
an ``XLA Ops`` line with the program's operations, nested (a ``while``
event spans the ops of its body).  Host planes hold the benchmark's own
``jax.profiler.TraceAnnotation`` spans, all named ``bench.<what>``, on the
same clock.

- busy time: the union of the ``XLA Ops`` intervals, averaged over the
  devices; idle share = 1 - busy / window;
- a program's device time: the summed durations of its ``XLA Modules``
  events;
- the top operations by self time (an event's duration less that of the
  events nested in it);
- the longest idle gaps, each named by the innermost ``bench.*`` host span
  that covers its middle.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:"
HOST_PREFIX = "/host:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANNOTATION_PREFIX = "bench."

Interval = Tuple[float, float]      # [start_ns, end_ns)
Event = Tuple[float, float, str]    # (start_ns, end_ns, name)


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Event]]      # device plane -> XLA Ops events
    modules: Dict[str, List[Event]]  # device plane -> XLA Modules events
    host: List[Event]                # bench.* annotations on host planes

    @property
    def devices(self) -> List[str]:
        return sorted(self.ops)


def find(log_dir: str) -> str:
    """The one ``.xplane.pb`` a profiler session wrote under ``log_dir``."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, got {paths}")
    return paths[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                events = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in line.events]
                (ops if line.name == OPS_LINE else modules)[plane.name] = events
        elif plane.name.startswith(HOST_PREFIX):
            for line in plane.lines:
                host.extend(
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events if e.name.startswith(ANNOTATION_PREFIX)
                )
    for name in modules:
        ops.setdefault(name, [])
    return Trace(ops=ops, modules=modules, host=host)


def merge(intervals) -> List[Interval]:
    """Union of half-open intervals as sorted disjoint intervals."""
    out: List[list] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def span(trace: Trace, name: str) -> Optional[Interval]:
    """The interval of the host annotation ``name`` (first and last ends
    when it occurs more than once); None if absent."""
    hits = [(s, e) for s, e, n in trace.host if n == name]
    if not hits:
        return None
    return min(s for s, _ in hits), max(e for _, e in hits)


def busy_s(trace: Trace, lo: float, hi: float) -> Optional[float]:
    """Seconds inside [lo, hi) in which an operation ran, averaged over the
    device planes; None when the trace has no device operations."""
    planes = [d for d in trace.devices if trace.ops[d]]
    if not planes:
        return None
    total = 0
    for d in planes:
        merged = merge((s, e) for s, e, _ in trace.ops[d])
        total += sum(e - s for s, e in clip(merged, lo, hi))
    return total / len(planes) / 1e9


def module_s(trace: Trace, prefix: str) -> Optional[float]:
    """Device seconds of the programs whose name starts with ``prefix``,
    averaged over the device planes that ran any; None if none did."""
    per_plane = [
        sum(e - s for s, e, n in events if n.startswith(prefix))
        for events in trace.modules.values()
    ]
    per_plane = [t for t in per_plane if t > 0]
    if not per_plane:
        return None
    return sum(per_plane) / len(per_plane) / 1e9


def short_op_name(name: str) -> str:
    """``%fusion.8 = bf16[512,512]{...} fusion(...)`` -> ``fusion.8 bf16[512,512]``."""
    lhs, _, rhs = name.partition(" = ")
    lhs = lhs.lstrip("%")
    shape = rhs.split("{", 1)[0].split(" ", 1)[0] if rhs and not rhs.startswith("(") else ""
    return f"{lhs} {shape}".strip()[:96]


def self_times(events: List[Event], lo: float, hi: float) -> Dict[str, float]:
    """Nanoseconds of self time per operation (duration less nested
    children) for events that start inside [lo, hi)."""
    totals: Dict[str, float] = {}
    stack: List[list] = []  # [start, end, name, child_ns] of open events

    def pop():
        start, end, name, child_ns = stack.pop()
        totals[name] = totals.get(name, 0) + (end - start) - child_ns

    chosen = sorted((ev for ev in events if lo <= ev[0] < hi),
                    key=lambda ev: (ev[0], -ev[1]))
    for start, end, name in chosen:
        while stack and stack[-1][1] <= start:
            pop()
        if stack:
            stack[-1][3] += end - start
        stack.append([start, end, short_op_name(name), 0])
    while stack:
        pop()
    return totals


def top_ops(trace: Trace, lo: float, hi: float, n: int = 10) -> List[list]:
    """The ``n`` operations with the most self time in [lo, hi), in seconds
    averaged over the device planes."""
    planes = [d for d in trace.devices if trace.ops[d]]
    totals: Dict[str, float] = {}
    for d in planes:
        for name, ns in self_times(trace.ops[d], lo, hi).items():
            totals[name] = totals.get(name, 0) + ns
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / len(planes) / 1e9] for name, ns in ranked]


def idle_gaps(trace: Trace, lo: float, hi: float, n: int = 10) -> List[list]:
    """The ``n`` longest device-idle gaps in [lo, hi) on the first device
    plane, each named by the innermost ``bench.*`` host span covering the
    gap's middle (``"untraced host"`` where none does)."""
    planes = [d for d in trace.devices if trace.ops[d]]
    if not planes:
        return []
    busy = clip(merge((s, e) for s, e, _ in trace.ops[planes[0]]), lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for start, end in gaps[:n]:
        mid = (start + end) / 2
        covering = [(e - s, name) for s, e, name in trace.host if s <= mid < e]
        label = min(covering)[1] if covering else "untraced host"
        out.append([label, (end - start) / 1e9])
    return out
