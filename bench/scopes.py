"""Reduction of a traced run of the program's chunk to the program's own
layers: device self time by named scope, and idle gaps by program span.

The program names the work of a round with ``jax.named_scope`` (``SCOPES``:
the PaME step's selection, exchange, local step, update and metrics, the
PME average's masks and average inside the exchange, and the engine's
per-round carry).  A scope reaches the compiled program only as
``metadata={op_name="jit(chunk)/while/body/.../pame.exchange/pme.mask/..."}``
on HLO instructions; a device trace names an operation by its instruction
(``%fusion.27 = ...``) and carries no ``op_name``.  So:

- ``scope_map(hlo_text)`` maps each instruction to its scope path (outer to
  inner).  A fusion's own ``op_name`` is its root's, which may carry no
  scope, so a fusion takes the path that most instructions of its fused
  computations carry, ties going to the root's;
- ``scope_ns(ops_ns, scope_map)`` sums device self time per path; an
  operation under no scope, or whose name the map lacks, falls under
  ``None``;
- ``host_spans(path)`` reads the program's host spans (``engine.*``,
  ``train.*``) from an ``.xplane.pb``, and ``named_gaps`` names each
  device-idle gap by the innermost of them around its middle.
"""
from __future__ import annotations

import collections
import re
import sys
from typing import Dict, List, Optional, Tuple

from bench import xplane

SCOPES = ("pame.select", "pame.exchange", "pame.local_step", "pame.update",
          "pame.metrics", "pme.mask", "pme.average", "engine.carry")
SPAN_PREFIXES = ("engine.", "train.")
ENGINE_SPANS = ("engine.batches", "engine.stack", "engine.dispatch", "engine.readback")

Path = Optional[Tuple[str, ...]]  # scope names, outer to inner; None: no scope

_SCOPE = re.compile(r"(?<![\w.])(" + "|".join(re.escape(s) for s in SCOPES) + r")(?![\w.])")
_HEADER = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=\{?(%[\w.\-]+(?:,\s*%[\w.\-]+)*)\}?")


def path_of(op_name: str) -> Path:
    """The scopes named in an ``op_name``, as path parts, also inside
    ``jvp(...)`` and ``transpose(...)``; None where it names none."""
    parts: List[str] = []
    for name in _SCOPE.findall(op_name):
        if not parts or parts[-1] != name:
            parts.append(name)
    return tuple(parts) or None


def opcode(rhs: str) -> str:
    """The opcode of an instruction's right-hand side (after its shape)."""
    i = 0
    if rhs.startswith("("):  # tuple shape: skip to its closing parenthesis
        depth = 0
        for i, ch in enumerate(rhs):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
    rest = rhs[i:].split(" ", 1)[1] if " " in rhs[i:] else ""
    return rest.split("(", 1)[0]


def parse(hlo_text: str) -> Dict[str, list]:
    """computation -> [(instruction, is_root, opcode, own path, called
    computations)] of an HLO module's text."""
    computations: Dict[str, list] = {}
    current = None
    for line in hlo_text.splitlines():
        if current is None:
            header = _HEADER.match(line)
            if header:
                current = computations.setdefault(header.group(1), [])
            continue
        if line.startswith("}"):
            current = None
            continue
        inst = _INSTRUCTION.match(line)
        if not inst:
            continue
        rhs = inst.group(3)
        op_name = _OP_NAME.search(rhs)
        calls = _CALLS.search(rhs)
        current.append((
            inst.group(2), bool(inst.group(1)), opcode(rhs),
            path_of(op_name.group(1)) if op_name else None,
            re.findall(r"%([\w.\-]+)", calls.group(1)) if calls else [],
        ))
    return computations


def scope_map(hlo_text: str) -> Dict[str, Path]:
    """instruction -> scope path over every computation of the module; a
    fusion by the paths of its fused computations' instructions."""
    computations = parse(hlo_text)

    def inner(name: str, seen: set) -> list:
        """(path, is_root) of the instructions a fused computation holds,
        nested fusions included."""
        out = []
        for inst, root, _, path, calls in computations.get(name, ()):
            out.append((path, root))
            for callee in calls:
                if callee not in seen:
                    seen.add(callee)
                    out.extend((p, False) for p, _ in inner(callee, seen))
        return out

    mapping: Dict[str, Path] = {}
    for instructions in computations.values():
        for inst, _, op, path, calls in instructions:
            if op != "fusion" or not calls:
                mapping[inst] = path
                continue
            held = [entry for callee in calls for entry in inner(callee, {callee})]
            counts = collections.Counter(p for p, _ in held if p is not None)
            if not counts:
                mapping[inst] = path
                continue
            top = max(counts.values())
            tied = [p for p, n in counts.items() if n == top]
            roots = [p for p, root in held if root and p in tied]
            mapping[inst] = roots[0] if roots else (path if path in tied else tied[0])
    return mapping


def instruction(short_name: str) -> str:
    """``fusion.27 pred[4,100352,2048]`` (``xplane.short_op_name``) -> ``fusion.27``."""
    return short_name.split(" ", 1)[0]


def program_ops_ns(trace: xplane.Trace, prefix: str) -> Optional[Dict[str, float]]:
    """Nanoseconds of self time per operation inside the runs of the
    programs named ``prefix``, averaged over the device planes that ran
    them; None when no device plane did."""
    planes = []
    for device, modules in trace.modules.items():
        runs = [(s, e) for s, e, n in modules if n.startswith(prefix)]
        if not runs:
            continue
        totals: Dict[str, float] = collections.Counter()
        for lo, hi in runs:
            totals.update(xplane.self_times(trace.ops[device], lo, hi))
        planes.append(totals)
    if not planes:
        return None
    merged = collections.Counter()
    for totals in planes:
        merged.update(totals)
    return {name: ns / len(planes) for name, ns in merged.items()}


def scope_ns(ops_ns: Dict[str, float], mapping: Dict[str, Path]) -> Dict[Path, float]:
    """Self time per scope path; operations the map lacks count as None."""
    out: Dict[Path, float] = collections.Counter()
    for name, ns in ops_ns.items():
        out[mapping.get(instruction(name))] += ns
    return dict(out)


def under(times: Dict[Path, float], *names: str) -> float:
    """Self time whose scope path holds any of ``names``."""
    return sum(ns for path, ns in times.items()
               if path is not None and any(n in path for n in names))


Span = Tuple[float, float, str, Optional[int]]  # (start_ns, end_ns, name, step_num)


def host_spans(path: str, prefixes=SPAN_PREFIXES) -> List[Span]:
    """The program's host spans in an ``.xplane.pb``, with the ``step_num``
    of a step span."""
    from jax.profiler import ProfileData

    spans: List[Span] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(xplane.HOST_PREFIX):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefixes):
                    step = dict(e.stats).get("step_num")
                    spans.append((e.start_ns, e.start_ns + e.duration_ns, e.name,
                                  None if step is None else int(step)))
    return sorted(spans)


def named_gaps(trace: xplane.Trace, spans: List[Span], lo: float, hi: float) -> List[list]:
    """Every device-idle gap in [lo, hi), longest first, as [name of the
    innermost program span around its middle (``"untraced host"`` where
    none is), seconds]."""
    named = xplane.Trace(ops=trace.ops, modules=trace.modules,
                         host=[(s, e, n) for s, e, n, _ in spans])
    return xplane.idle_gaps(named, lo, hi, n=sys.maxsize)


def chunk_gaps(trace: xplane.Trace, spans: List[Span]) -> List[list]:
    """``named_gaps`` inside each ``engine.chunk`` span, longest first.
    Between two runner calls the host runs the caller's code, which no
    program span can name, so a gap across that junction counts only in
    the parts that lie inside the chunks."""
    gaps = [gap for lo, hi, name, _ in spans if name == "engine.chunk"
            for gap in named_gaps(trace, spans, lo, hi)]
    return sorted(gaps, key=lambda gap: -gap[1])


def round_probe(ctx) -> Optional[dict]:
    """``probes/round_scopes.py``'s measurement for a per-layer reader; None
    without a device plane in the window's trace (CPU), where the probe
    would find nothing to read."""
    if xplane.busy_s(ctx.trace, *ctx.window) is None:
        return None
    return ctx.probe("round_scopes")


def round_ms(ctx, *names: str) -> Optional[float]:
    """Device ms per round of the chunk under any of the scopes ``names``."""
    probe = round_probe(ctx)
    if probe is None or probe["scope_ns"] is None:
        return None
    return under(probe["scope_ns"], *names) / 1e6
