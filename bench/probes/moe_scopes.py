"""The MoE and MLA layers of the program's own chunk, traced: device self
time per round under the model's scopes ``moe.route``, ``moe.experts``,
``moe.shared``, ``moe.combine`` and ``mla.attend`` (inside
``pame.local_step``), the device self time per round of the grouped-matmul
kernels alone (``kernel_ms``: the Pallas ``gmm``/``tgmm`` calls), and the
round metric ``expert_rows`` of the traced rounds (the (token, choice)
pairs the held experts computed, summed over nodes).

A fresh state from the run's seeds is fed by the program's ``make_batch``
from round ``chunk`` on; one chunk runs untraced, then ``CHUNKS`` chunks in
a profiler session of its own.  Instructions are named by scope through
the runner's optimized HLO as ``bench/scopes.py`` names them (a fusion by
the scope most of its fused instructions carry, ties to the root's).

Where the chunk's HLO carries none of these scopes (a model without MoE or
MLA layers, or a program that predates them) nothing is traced, and every
scope and the kernels read 0 ms: the readers report 0 there, since
per-layer metrics carry no ``workloads`` key and are reported in every
cell."""
import collections
import re
import shutil
import tempfile

from bench import harness, scopes, xplane

CHUNKS = 2
PROGRAM = "jit_chunk("
KERNEL = re.compile(r"^t?gmm(\.\d+)?$")  # the grouped matmuls' instructions
NAMES = ("moe.route", "moe.experts", "moe.shared", "moe.combine", "mla.attend")
_NAME = re.compile(r"(?<![\w.])(" + "|".join(re.escape(n) for n in NAMES) + r")(?![\w.])")


def _innermost(op_name: str):
    found = _NAME.findall(op_name)
    return found[-1] if found else None


def scope_map(hlo_text: str) -> dict:
    """instruction -> the innermost of ``NAMES`` it carries (None where it
    carries none); a fusion by its fused instructions' names."""
    computations = collections.defaultdict(list)
    current = None
    for line in hlo_text.splitlines():
        if current is None:
            header = scopes._HEADER.match(line)
            if header:
                current = computations[header.group(1)]
            continue
        if line.startswith("}"):
            current = None
            continue
        inst = scopes._INSTRUCTION.match(line)
        if inst:
            rhs = inst.group(3)
            op_name, calls = scopes._OP_NAME.search(rhs), scopes._CALLS.search(rhs)
            current.append((inst.group(2), bool(inst.group(1)), scopes.opcode(rhs),
                            _innermost(op_name.group(1)) if op_name else None,
                            re.findall(r"%([\w.\-]+)", calls.group(1)) if calls else []))

    def held(name, seen):
        out = []
        for _, root, _, scope, calls in computations.get(name, ()):
            out.append((scope, root))
            for callee in calls:
                if callee not in seen:
                    seen.add(callee)
                    out.extend((s, False) for s, _ in held(callee, seen))
        return out

    mapping = {}
    for instructions in computations.values():
        for inst, _, op, scope, calls in instructions:
            inner = ([entry for c in calls for entry in held(c, {c})]
                     if op == "fusion" else [])
            counts = collections.Counter(s for s, _ in inner if s is not None)
            if not counts:
                mapping[inst] = scope
                continue
            top = max(counts.values())
            tied = [s for s, n in counts.items() if n == top]
            roots = [s for s, root in inner if root and s in tied]
            mapping[inst] = roots[0] if roots else (scope if scope in tied else tied[0])
    return mapping


def probe(ctx):
    """This probe's measurement for a per-layer reader; None without a
    device plane in the window's trace (CPU)."""
    if xplane.busy_s(ctx.trace, *ctx.window) is None:
        return None
    return ctx.probe("moe_scopes")


def measure(ctx):
    prog = ctx.program
    empty = {"ms": {name: 0.0 for name in NAMES}, "kernel_ms": 0.0, "expert_rows": None}
    if not hasattr(prog.runner, "optimized_hlo"):
        return {"ms": None, "kernel_ms": None, "expert_rows": None}
    mapping = {}
    for text in prog.runner.optimized_hlo().values():
        mapping.update(scope_map(text))
    if not any(mapping.values()):
        return empty
    state, aux = prog.start(ctx.seeds)
    feed = harness.Feed(prog.make_batch, ctx.seeds.data_offset, keep=0)
    k = prog.chunk
    state, aux, _ = prog.chunk_from(state, aux, feed, k)
    rows = []
    log_dir = tempfile.mkdtemp(prefix="bench-moe-")
    try:
        with harness.profiled(log_dir):
            for _ in range(CHUNKS):
                k += prog.chunk
                state, aux, metrics = prog.chunk_from(state, aux, feed, k)
                rows.extend(metrics.get("expert_rows", ()))
        trace = xplane.load(xplane.find(log_dir))
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    del state, aux
    rounds = CHUNKS * prog.chunk
    ops_ns = scopes.program_ops_ns(trace, PROGRAM)
    if ops_ns is None:
        return {"ms": None, "kernel_ms": None, "expert_rows": None}
    ms, kernel_ms = dict(empty["ms"]), 0.0
    for name, ns in ops_ns.items():
        inst = scopes.instruction(name)
        scope = mapping.get(inst)
        if scope is not None:
            ms[scope] += ns / rounds / 1e6
        if KERNEL.match(inst):
            kernel_ms += ns / rounds / 1e6
    return {"ms": ms, "kernel_ms": kernel_ms,
            "expert_rows": sum(float(r) for r in rows) / rounds if rows else None}
